"""The whole command rehearsed on the CPU on a model with gated
short-convolution layers and 64-wide heads: a cell on tiny-lfm2 (both
dense layers and one period: five conv tails of two rows a sequence
beside one attention layer's pages, two heads a lane row; eight experts,
all held), whose configuration reaches the engine through its
`architecture` block alone. Added to a copy of the manifest by new files
and appended entries only, as test_benchmark_rehearsal_jamba.py does it;
the two new readers' entries come from layer_metrics/lfm2_entries.json
and the state and expert readers' from hybrid_entries.json and
retention_entries.json, because BENCHMARK.json cannot take them yet
(PERF.md, Open questions). The store holds a state for every page of the
pool, as the real cell's does: nothing is ever evicted. ONE run, traced
(on the CPU the tracer yields no device metric): the test budget of
ISSUE 52."""
import copy
import json
import os
import subprocess
import sys

import pytest

import bench_paths
from harness import manifest as mf

RUN = os.path.join(bench_paths.BENCH, "run.py")
CELL = "tiny-lfm2-cpu.tiny-conv-table"
REAL = "lfm2-24b-a2b-stage0.roundtable"
NEW = ("step.decode_roofline.shortconv", "kernel.attn_roofline.d64")
WAITING = ("state.rescan_share", "state.snapshot_peak_share",
           "state.copy_ms_per_join", "moe.experts_hit_share")


def _entries(file="lfm2_entries.json"):
    with open(os.path.join(bench_paths.BENCH, "layer_metrics", file),
              encoding="utf-8") as f:
        return json.load(f)["per_layer"]


def _env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)     # one CPU device, as one chip
    return env


def grow(base):
    """BENCHMARK.json with the waiting entries this cell reads appended
    (the two new readers', the state and expert readers' with the cell
    on their lists): what `run.py --manifest` takes on the chip."""
    grown = copy.deepcopy(base)
    grown["per_layer"].extend(_entries())
    grown["per_layer"].extend(
        dict(m, workloads=m["workloads"] + [REAL])
        for file in ("hybrid_entries.json", "retention_entries.json")
        for m in _entries(file) if m["name"] in WAITING)
    return grown


@pytest.fixture(scope="module")
def grown_manifest(tmp_path_factory):
    base = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    grown = grow(base)
    grown["paths"].append("tests/benchmarks/rehearsal_lfm2")
    grown["configs"].append({
        "name": "tiny-lfm2-cpu",
        "source": "tests only: the registry's tiny-lfm2 sizes",
        "file": "tests/benchmarks/rehearsal_lfm2/configs/"
                "tiny-lfm2-cpu.json",
        "reduced": [], "why": "rehearsal of the conv tails on the CPU"})
    grown["workloads"].append({
        "name": CELL, "config": "tiny-lfm2-cpu",
        "traffic": "tiny-conv-table", "chips": 1,
        "why": "closed loop, 2 discussions x 3 knights x 2 rounds at a "
               "size the CPU serves in seconds"})
    for m in grown["end_to_end"] + grown["per_layer"]:
        if REAL in m.get("workloads", ()):  # what the real cell reports
            m["workloads"].append(CELL)
    assert mf.problems(grown, bench_paths.REPO) == []
    for key in ("configs", "workloads"):
        assert grown[key][:len(base[key])] == base[key]
    path = tmp_path_factory.mktemp("manifest") / "BENCHMARK.json"
    path.write_text(json.dumps(grown))
    return str(path)


def test_lfm2_cell_runs_end_to_end_traced(grown_manifest):
    proc = subprocess.run(
        [sys.executable, RUN, "--manifest", grown_manifest, "--workload",
         CELL, "--seed", "3000000052", "--seconds", "6", "--trace", "1"],
        capture_output=True, text=True, env=_env(), timeout=400,
        cwd=bench_paths.REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()]
    result, phases = lines[-1], {x["phase"]: x for x in lines[:-1]}
    # (a row whose FIRST sampled token is the end of sequence counts as
    # failed: the traffic's own lottery, as the other rehearsals hold)
    assert result["correct"] is True and result["failed"] <= 2
    assert phases["window"]["errors"] in ([], ["200:done"])
    assert result["attempted"] >= 6
    assert result["device"]["platform"] == "cpu"
    # (a traced run's window is the slice: it reports no end-to-end rate)
    assert phases["degraded_paths"]["problems"] == []
    # float32 engine against the float32 reference: the served token is
    # the reference's own maximum but for a rounding-level tie.
    assert phases["right_answers"]["worst_gap_sigmas"] <= 0.01
    assert phases["build"]["model"] == "tiny-lfm2-cpu"
    assert phases["build"]["layers"] == 12
    # One attention layer of 4 kv heads of 64: 2 x 4 x 64 x 4 B a
    # position, in rows of 128 lanes.
    assert phases["build"]["pool_bytes"] == 256 * 128 * 2 * 4 * 64 * 4
    assert phases["program"]["prefix_cache"]["hits"] > 0
    got = result["metrics"]
    assert {"state.rescan_share", "moe.experts_hit_share",
            "kv.prefix_reuse_share", "kv.pool_peak_share",
            "compile.in_window"} <= set(got)
    assert 0.0 <= got["state.rescan_share"]["value"] < 100.0
    assert 0.0 < got["moe.experts_hit_share"]["value"] <= 100.0
    manifest = mf.load(grown_manifest)
    device_metrics = {m["name"] for m in manifest["per_layer"]
                      if m["source"] == "device_trace"}
    assert not set(got) & device_metrics


@pytest.mark.parametrize("file", [
    "benchmarks/configs/lfm2-24b-a2b-stage0.json",
    "tests/benchmarks/rehearsal_lfm2/configs/tiny-lfm2-cpu.json"])
def test_the_two_copies_of_the_architecture_agree(file):
    """The harness's registry entry reads the file's top-level keys,
    the engine its `architecture` block: one model, stated twice."""
    with open(os.path.join(bench_paths.REPO, file),
              encoding="utf-8") as f:
        config = json.load(f)
    arch = dict(config["engine"]["architecture"])
    assert arch and all(config[k] == v for k, v in arch.items()), file
    assert not {"rope_theta", "rms_norm_eps", "tie_word_embeddings",
                "n_routed_experts"} & set(arch)
    assert config["rms_norm_eps"] == arch["norm_eps"]
    assert config["rope_theta"] == arch["rope_parameters"]["rope_theta"]
    assert config["n_routed_experts"] == arch["num_experts"]


def test_the_new_metrics_have_readers_and_entries_the_manifest_takes():
    manifest = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    assert mf.problems(manifest, bench_paths.REPO) == []
    by = {m["name"]: m for m in manifest["per_layer"]}
    assert not set(NEW) & set(by)
    grown = grow(manifest)
    assert mf.problems(grown, bench_paths.REPO) == []
    assert tuple(m["name"] for m in _entries()) == NEW
    for m in _entries():
        assert m["workloads"] == [REAL] and m["moves"] == "tokens_per_s"
        assert m["unit"] == "%" and m["source"] == "device_trace"
        assert os.path.isfile(mf.reader_file(manifest, bench_paths.REPO,
                                             m["name"]))
    names = {m["name"] for m in mf.cell(grown, REAL)["per_layer"]}
    assert set(NEW) | set(WAITING) <= names


def test_the_cell_is_one_chip_and_reports_what_lists_no_cells():
    manifest = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    cell = mf.cell(manifest, REAL)
    assert cell["workload"]["chips"] == 1
    assert cell["workload"]["traffic"] == "roundtable"
    assert cell["config"]["reduced"] == ["num_hidden_layers", "layer_types"]
    names = {m["name"] for m in cell["per_layer"]}
    assert {"kv.pool_peak_share", "kv.prefix_reuse_share",
            "sched.loop_wait_share", "device.idle_unnamed_share"} <= names
    # The accepted readers know an attention kernel by the pool [pages,
    # page, kv heads, D] among its operands; this pool is [640,128,4,128]
    # (two heads a lane row), so the cell is not on their lists.
    assert not {"kernel.attn_busy_share", "kernel.attn_roofline"} & names
    assert {m["name"] for m in cell["end_to_end"]} == {
        "ttft_p50_ms", "ttft_p90_ms", "tokens_per_s", "setup_s"}
    # eight configurations, eight cells, all on one chip
    assert len(manifest["configs"]) == len(manifest["workloads"]) == 8
