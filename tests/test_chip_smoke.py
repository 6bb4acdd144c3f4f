"""chip_smoke.py rehearsed without the chip (ISSUE 22).

The script itself only runs on a TPU: `main()` fails when JAX reports
another platform, and nothing in it forces one. What CAN be checked
here is (a) exactly that refusal, as the driver sees it — nonzero exit,
no `"ok": true` line — and (b) its phases, called as functions at a
tiny preset on the CPU: the gateway path end to end on one device, and
the sharded path with its depth-cut comparison on four virtual ones.
The kernels run in interpret mode here, so this proves paths, arguments
and control flow, never a speed.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# tiny-llama at the script's own engine settings; attn="flash" stands in
# for what "auto" resolves to on the chip (on the CPU it resolves dense,
# and the script's no-hidden-fallback phase would rightly refuse that).
# float32, so that which program computed a token (a plain segment or a
# ragged one, depending on when the join lands) cannot flip a greedy
# near-tie: the token streams below are then the same in every run.
TINY = dict(chip_smoke.ONE_CHIP_ENGINE, model="tiny-llama",
            max_seq_len=2048, attn="flash", dtype="float32", seed=0)


@pytest.fixture()
def phases(capsys):
    def read() -> dict:
        lines = [json.loads(line) for line in
                 capsys.readouterr().out.splitlines() if line.strip()]
        return {rec["phase"]: rec for rec in lines}
    return read


@pytest.fixture(autouse=True)
def _fresh_process_state():
    from theroundtaible_tpu.engine import reset_engines
    from theroundtaible_tpu.engine.pallas import attention as pattn
    from theroundtaible_tpu.utils import telemetry

    reset_engines()
    pattn.reset_ragged_counters()
    telemetry.REGISTRY.reset()
    yield
    reset_engines()


def test_refuses_to_run_without_a_tpu():
    """As the driver's sandbox run sees it: JAX held to the CPU → a
    nonzero exit, the reason on stderr, and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable,
                           os.path.join(REPO, "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=240)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_refuses_to_run_alone(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo it must fail too (the contract's second negative run)."""
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"],
                          capture_output=True, text=True, env=env,
                          cwd=tmp_path, timeout=240)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


@pytest.mark.gateway
def test_one_chip_phases_at_a_tiny_preset(phases):
    walls = chip_smoke.serve_through_gateway(TINY)
    assert set(walls) == {"build", "serve", "check"}
    out = phases()
    assert out["build"]["model"] == "tiny-llama"
    # Counts are "at most": a 512-word random model says eos now and
    # then, and the stream is eos-trimmed.
    served = {r["what"]: r["completion_tokens"]
              for r in out["serve"]["requests"]}
    assert set(served) == {
        "chat", "chat-sse", "round1:Lancelot", "round1:Galahad",
        "round1:Percival", "round2:Lancelot", "round2:Galahad",
        "round2:Percival"}
    assert all(1 <= n <= 256 for n in served.values())
    assert out["serve"]["gateway"]["admitted"] == 4
    nf = out["no_hidden_fallback"]
    assert nf["ragged_path"] == "pallas_ragged"
    assert nf["scheduler"]["ragged_joins"] >= 1
    assert nf["ragged_kernel_dispatches"] > 0
    assert nf["prefix_cache"]["reused_tokens"] > 0
    scores = out["right_answers"]["requests"]
    assert len(scores) == 8
    assert all(s["gap_sigmas"] <= chip_smoke.LOGIT_TOL_SIGMAS
               for s in scores)
    assert out["compiles_and_memory"]["compiles"] > 0


def test_four_chip_phases_on_virtual_devices(phases):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    cfg = dict(TINY, mesh={"model": 2}, num_pages=256)
    # tiny-llama has 2 kv heads: a 2-way model axis shards them as the
    # 4-way axis shards Llama-3-8B's 8.
    walls = chip_smoke.serve_sharded(cfg, comparison_layers=1)
    assert set(walls) == {"build_comparison", "comparison", "build",
                          "serve"}
    out = phases()
    assert out["placement"]["layers"] == 2      # the last one printed
    assert out["no_hidden_fallback"]["scheduler"]["ragged_joins"] >= 1
    assert len(out["right_answers"]["requests"]) == 5
    assert len(out["serve"]["requests"]) == 5
