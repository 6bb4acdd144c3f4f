"""The whole command rehearsed on the CPU on window and full attention
layers: a cell on tiny-laguna's widths at three layers (full, sliding,
sliding: heads 6 and 8 over 2 kv heads, a 64-token window over 32-wide
pages, YaRN over half a head, the per-head gate, all 8 experts held, a
dense first layer), whose configuration reaches the
engine through its `architecture` block alone. Added to a copy of the
manifest by new files and appended entries only, as
test_benchmark_rehearsal_mla.py does it; the three readers' entries come
from layer_metrics/laguna_entries.json, because BENCHMARK.json cannot
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
CELL = "tiny-laguna-cpu.tiny-window-table"
REAL = "laguna-xs.2-d5.roundtable"
NEW = ("kernel.attn_roofline.window", "kv.window_skip_share",
       "step.decode_roofline.window")
FILES = ("benchmarks/configs/laguna-xs.2-d5.json",
         "tests/benchmarks/rehearsal_laguna/configs/tiny-laguna-cpu.json")


def _entries():
    with open(os.path.join(bench_paths.BENCH, "layer_metrics",
                           "laguna_entries.json"), encoding="utf-8") as f:
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
    grown["paths"].append("tests/benchmarks/rehearsal_laguna")
    grown["configs"].append({
        "name": "tiny-laguna-cpu",
        "source": "tests only: the registry's tiny-laguna sizes",
        "file": "tests/benchmarks/rehearsal_laguna/configs/"
                "tiny-laguna-cpu.json",
        "reduced": [], "why": "rehearsal of window layers on the CPU"})
    grown["workloads"].append({
        "name": CELL, "config": "tiny-laguna-cpu",
        "traffic": "tiny-window-table", "chips": 1,
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
         "--seed", "3000000033", "--seconds", "6", "--trace", str(trace)],
        capture_output=True, text=True, env=_env(), timeout=600,
        cwd=bench_paths.REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()]
    return lines[-1], {x["phase"]: x for x in lines[:-1]}


def test_window_cell_runs_end_to_end_untraced(grown_manifest):
    result, phases = _run(grown_manifest, 0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 6
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"]["tokens_per_s"]["value"] > 0
    assert phases["degraded_paths"]["problems"] == []
    # float32 engine (pages, the window's pages skipped) against the
    # float32 reference (a dense mask, no cache): the served token is
    # the reference's own maximum but for a rounding-level tie.
    assert phases["right_answers"]["worst_gap_sigmas"] <= 0.01
    assert phases["build"]["model"] == "tiny-laguna-cpu"
    assert phases["build"]["layers"] == 6       # two a published layer
    # One pool shape for all three attention layers: 256 pages x 32
    # positions x 2 kv heads x 16 x 4 B, keys and values.
    assert phases["build"]["pool_bytes"] == 256 * 32 * 2 * 16 * 4 * 2 * 3
    assert phases["program"]["ragged"]["path"] == "pallas_ragged"


def test_window_cell_traced_reports_what_the_cpu_can_read(grown_manifest):
    """No device trace on the CPU: the two roofline readers find nothing
    and the line leaves them out; the spans' reader reports what the
    windows skipped."""
    result, _phases = _run(grown_manifest, 1)
    got = result["metrics"]
    # (the window's own counters may hold no finished segment when six
    # workers share the CPU: only what the whole run's totals give)
    assert {"compile.in_window", "kv.window_skip_share"} <= set(got)
    assert not {"kernel.attn_roofline.window",
                "step.decode_roofline.window"} & set(got)
    # contexts of 200 to 500 tokens (7 to 16 pages) against a window of
    # 64 (3 pages), two layers of three: about half of all visits
    assert 35.0 < got["kv.window_skip_share"]["value"] < 60.0
    assert result["correct"] is True


def test_the_two_copies_of_the_architecture_agree():
    """The harness's registry entry reads the file's top-level keys,
    the engine its `architecture` block: one model, stated twice. The
    two top-level keys the block lacks are for files the benchmark
    already has (`assumed` says which)."""
    for file in FILES:
        with open(os.path.join(bench_paths.REPO, file),
                  encoding="utf-8") as f:
            config = json.load(f)
        arch = config["engine"]["architecture"]
        assert arch and all(config[k] == v for k, v in arch.items()), file
        assert config["n_routed_experts"] == arch["num_experts"]
        assert config["rope_theta"] == arch["rope_parameters"][
            "sliding_attention"]["rope_theta"]
        assert "rope_theta" not in arch and "n_routed_experts" not in arch


def test_the_new_metrics_have_readers_and_entries_the_manifest_takes():
    manifest = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    assert mf.problems(manifest, bench_paths.REPO) == []
    by = {m["name"]: m for m in manifest["per_layer"]}
    # where this PR lists the cell (PERF.md, Open questions, has the
    # lists that wait for a `benchmark` PR)
    for name in ("kernel.attn_busy_share", "sched.loop_wait_share",
                 "device.idle_unnamed_share"):
        assert REAL in by[name]["workloads"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert REAL in e2e["tokens_per_s"]["workloads"]
    grown = copy.deepcopy(manifest)
    grown["per_layer"] = [m for m in grown["per_layer"]
                          if m["name"] not in NEW]
    grown["per_layer"].extend(_entries())
    assert mf.problems(grown, bench_paths.REPO) == []
    assert tuple(m["name"] for m in _entries()) == NEW
    for m in _entries():
        assert m["workloads"] == [REAL] and m["moves"] == "tokens_per_s"
        assert os.path.isfile(mf.reader_file(manifest, bench_paths.REPO,
                                             m["name"]))


def test_the_configuration_keeps_every_published_number():
    """The catalog's `config` for Laguna-XS.2, key for key, but for the
    depth and the three per-layer lists shortened to it; the published
    values stand beside them."""
    with open(os.path.join(bench_paths.BENCH, "configs",
                           "laguna-xs.2-d5.json"), encoding="utf-8") as f:
        config = json.load(f)
    period = ["full_attention"] + ["sliding_attention"] * 3
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
        "intermediate_size": 8192, "num_hidden_layers": 40,
        "num_attention_heads": 48, "num_key_value_heads": 8,
        "head_dim": 128, "max_position_embeddings": 262144,
        "attention_bias": False, "rms_norm_eps": 1e-06,
        "num_experts": 256, "num_experts_per_tok": 8,
        "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "gating": True,
        "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1,
                "beta_fast": 64, "attention_factor": 1.4158883083359672,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000,
                                  "partial_rotary_factor": 1},
            "original_max_position_embeddings": 4096},
        "layer_types": period * 10,
        "moe_apply_router_weight_on_input": False,
        "partial_rotary_factor": 0.5,
        "mlp_layer_types": ["dense"] + ["sparse"] * 39,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}
    differ = sorted(k for k, v in published.items() if config[k] != v)
    reduced = ["layer_types", "mlp_layer_types",
               "num_attention_heads_per_layer", "num_hidden_layers"]
    assert differ == sorted(config["reduced"]) == reduced
    assert config["published"] == {k: published[k] for k in differ}
    # the three lists are only shortened to the depth
    assert config["num_hidden_layers"] == 5
    for key in reduced[:3]:
        assert config[key] == published[key][:5]
    assert config["layer_types"][1:] == period[1:] + period[:1]
    assert {"gating", "router", "qk_norm", "rope_scaling"} \
        <= set(config["assumed"])
    assert "33.44 B" in config["assumed"]["gating"]
    assert "eight pipeline stages" in config["deployment"]
    manifest = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    entry = [c for c in manifest["configs"] if c["name"] == config["name"]]
    assert entry and sorted(entry[0]["reduced"]) == differ
    assert entry[0]["file"] == "benchmarks/configs/laguna-xs.2-d5.json"
