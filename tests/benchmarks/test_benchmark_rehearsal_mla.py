"""The whole command rehearsed on the CPU on latent pages: a cell on
tiny-axk1 (multi-head latent attention read in absorbed form, half of
the gated routed experts held, a dense first layer), whose configuration
reaches the engine through its `architecture` block alone. Added to a
copy of the manifest by new files and appended entries only, as
test_benchmark_rehearsal_hybrid.py does it; the latent readers' entries
come from layer_metrics/mla_entries.json, because BENCHMARK.json cannot
take them yet (PERF.md, Open questions)."""
import copy
import json
import os
import subprocess
import sys

import pytest

import bench_paths
from harness import manifest as mf

RUN = os.path.join(bench_paths.BENCH, "run.py")
CELL = "tiny-axk1-cpu.tiny-latent-table"
REAL = "a.x-k1-ep16.roundtable"
NEW = ("kernel.mla_roofline", "kernel.mla_busy_share",
       "step.decode_roofline.mla")


def _entries():
    with open(os.path.join(bench_paths.BENCH, "layer_metrics",
                           "mla_entries.json"), encoding="utf-8") as f:
        return json.load(f)["per_layer"]


def _env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)     # one CPU device, as one chip
    return env


@pytest.fixture(scope="module")
def grown_manifest(tmp_path_factory):
    base = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    grown = copy.deepcopy(base)
    grown["paths"].append("tests/benchmarks/rehearsal_mla")
    grown["configs"].append({
        "name": "tiny-axk1-cpu",
        "source": "tests only: the registry's tiny-axk1 sizes",
        "file": "tests/benchmarks/rehearsal_mla/configs/"
                "tiny-axk1-cpu.json",
        "reduced": [], "why": "rehearsal of latent pages on the CPU"})
    grown["workloads"].append({
        "name": CELL, "config": "tiny-axk1-cpu",
        "traffic": "tiny-latent-table", "chips": 1,
        "why": "closed loop, 2 discussions x 3 knights x 2 rounds at a "
               "size the CPU serves in seconds"})
    grown["per_layer"].extend(_entries())
    for m in grown["end_to_end"] + grown["per_layer"]:
        if REAL in m.get("workloads", ()):  # what the real cell reports
            m["workloads"].append(CELL)
    assert mf.problems(grown, bench_paths.REPO) == []
    for key in ("configs", "workloads"):
        assert grown[key][:len(base[key])] == base[key]
    path = tmp_path_factory.mktemp("manifest") / "BENCHMARK.json"
    path.write_text(json.dumps(grown))
    return str(path)


def _run(manifest, trace):
    proc = subprocess.run(
        [sys.executable, RUN, "--manifest", manifest, "--workload", CELL,
         "--seed", "3000000011", "--seconds", "6", "--trace", str(trace)],
        capture_output=True, text=True, env=_env(), timeout=400,
        cwd=bench_paths.REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()]
    return lines[-1], {x["phase"]: x for x in lines[:-1]}


def test_latent_cell_runs_end_to_end_untraced(grown_manifest):
    result, phases = _run(grown_manifest, 0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 6
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"]["tokens_per_s"]["value"] > 0
    assert phases["degraded_paths"]["problems"] == []
    # float32 engine (absorbed form over pages) against the float32
    # reference (expanded form, no cache): the served token is the
    # reference's own maximum but for a rounding-level tie.
    assert phases["right_answers"]["worst_gap_sigmas"] <= 0.01
    assert phases["build"]["model"] == "tiny-axk1-cpu"
    assert phases["build"]["layers"] == 6       # two a published layer
    # One latent pool a layer: 96 pages x 128 positions x 128 cells x 4 B
    # x 3 attention layers, and no value pool.
    assert phases["build"]["pool_bytes"] == 96 * 128 * 128 * 4 * 3
    assert phases["program"]["ragged"]["path"] == "pallas_ragged"


def test_latent_cell_traced_reports_what_the_cpu_can_read(grown_manifest):
    """No device trace on the CPU: the three latent readers find nothing
    and the line leaves them out; the counters' and spans' readers
    report."""
    result, _phases = _run(grown_manifest, 1)
    got = result["metrics"]
    assert {"kv.prefix_reuse_share", "compile.in_window",
            "sched.rows_per_segment"} <= set(got)
    assert not set(NEW) & set(got)
    assert got["kv.prefix_reuse_share"]["value"] > 30.0
    assert result["correct"] is True


def test_the_two_copies_of_the_architecture_agree():
    """The harness's registry entry reads the file's top-level keys,
    the engine its `architecture` block: one model, stated twice."""
    for file in ("benchmarks/configs/a.x-k1-ep16.json",
                 "tests/benchmarks/rehearsal_mla/configs/"
                 "tiny-axk1-cpu.json"):
        with open(os.path.join(bench_paths.REPO, file),
                  encoding="utf-8") as f:
            config = json.load(f)
        arch = config["engine"]["architecture"]
        assert arch and all(config[k] == v for k, v in arch.items()), file


def test_the_new_metrics_have_readers_and_entries_the_manifest_takes():
    """Appended to a copy, the three entries break no rule of the
    manifest; the real one lists the cell on no metric whose reader
    cannot read it."""
    manifest = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    assert mf.problems(manifest, bench_paths.REPO) == []
    by = {m["name"]: m for m in manifest["per_layer"]}
    # `pool_operand` cannot match a latent pool.
    assert REAL not in by["kernel.attn_roofline"]["workloads"]
    assert REAL not in by["kernel.attn_busy_share"]["workloads"]
    for name in ("client.tpot_p95_ms", "sched.rows_per_segment",
                 "step.decode_ms_per_token", "device.idle_share"):
        assert by[name]["workloads"][-1] == REAL
    assert not set(NEW) & set(by)
    grown = copy.deepcopy(manifest)
    grown["per_layer"].extend(_entries())
    assert mf.problems(grown, bench_paths.REPO) == []
    assert tuple(m["name"] for m in _entries()) == NEW
    for m in _entries():
        assert m["workloads"] == [REAL] and m["moves"] == "tokens_per_s"
        assert os.path.isfile(mf.reader_file(manifest, bench_paths.REPO,
                                             m["name"]))


def test_the_configuration_keeps_every_published_number():
    """The catalog's `config` for A.X-K1, key for key, but for the four
    keys `reduced` names; the published values stand beside them."""
    with open(os.path.join(bench_paths.BENCH, "configs",
                           "a.x-k1-ep16.json"), encoding="utf-8") as f:
        config = json.load(f)
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 7168,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "axk1",
        "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8,
        "n_routed_experts": 192, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 61,
        "num_key_value_heads": 64, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "none", "v_head_dim": 128, "vocab_size": 163840}
    differ = sorted(k for k, v in published.items() if config[k] != v)
    assert differ == sorted(config["reduced"]) == sorted(
        ["num_hidden_layers", "n_routed_experts", "vocab_size", "ep_size"])
    assert config["published"] == {k: published[k] for k in differ}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["ep_size"]) == (6, 12, 20480, 16)
    assert "router" in config["assumed"] and "16 chips" in config[
        "deployment"]
    # Router width 192 and top-8 stay.
    assert config["n_routed_experts"] * config["ep_size"] == 192
    manifest = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    entry = [c for c in manifest["configs"] if c["name"] == config["name"]]
    assert entry and sorted(entry[0]["reduced"]) == differ
