"""The whole command rehearsed on the CPU on a model whose Mamba-1 runs
are scanned: a cell on tiny-jamba (one period: seven Mamba-1 blocks,
attention over one kv head at layer 7, six more; a state and a conv tail
a layer a sequence beside two-pool pages), whose configuration reaches
the engine through its `architecture` block alone. Added to a copy of
the manifest by new files and appended entries only, as
test_benchmark_rehearsal_brumby.py does it; the three Mamba-1 readers'
entries come from layer_metrics/jamba_entries.json and the state
readers' from hybrid_entries.json and retention_entries.json, because
BENCHMARK.json cannot take them yet (PERF.md, Open questions). The store
holds 3 snapshots of 73 216 bytes: the run fills it, so eviction is
rehearsed too."""
import copy
import json
import os
import subprocess
import sys

import pytest

import bench_paths
from harness import manifest as mf
from harness import mamba1_cost

RUN = os.path.join(bench_paths.BENCH, "run.py")
CELL = "tiny-jamba-cpu.tiny-scan-table"
REAL = "jamba2-3b.roundtable"
NEW = ("kernel.mamba1_scan_roofline", "kernel.mamba1_busy_share",
       "step.decode_roofline.mamba1")
STATE = ("state.rescan_share", "state.snapshot_peak_share",
         "state.copy_ms_per_join")


def _entries(file="jamba_entries.json"):
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
    (the three Mamba-1 readers', the three state readers' with the cell
    on their lists): what `run.py --manifest` takes on the chip."""
    grown = copy.deepcopy(base)
    grown["per_layer"].extend(_entries())
    grown["per_layer"].extend(
        dict(m, workloads=m["workloads"] + [REAL])
        for file in ("hybrid_entries.json", "retention_entries.json")
        for m in _entries(file) if m["name"] in STATE)
    return grown


@pytest.fixture(scope="module")
def grown_manifest(tmp_path_factory):
    base = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    grown = grow(base)
    grown["paths"].append("tests/benchmarks/rehearsal_jamba")
    grown["configs"].append({
        "name": "tiny-jamba-cpu",
        "source": "tests only: the registry's tiny-jamba sizes",
        "file": "tests/benchmarks/rehearsal_jamba/configs/"
                "tiny-jamba-cpu.json",
        "reduced": [], "why": "rehearsal of the scanned runs on the CPU"})
    grown["workloads"].append({
        "name": CELL, "config": "tiny-jamba-cpu",
        "traffic": "tiny-scan-table", "chips": 1,
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


def _run(manifest, trace):
    proc = subprocess.run(
        [sys.executable, RUN, "--manifest", manifest, "--workload", CELL,
         "--seed", "3000000013", "--seconds", "6", "--trace", str(trace)],
        capture_output=True, text=True, env=_env(), timeout=400,
        cwd=bench_paths.REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()]
    return lines[-1], {x["phase"]: x for x in lines[:-1]}


def test_jamba_cell_runs_end_to_end_untraced(grown_manifest):
    result, phases = _run(grown_manifest, 0)
    # (a row whose FIRST sampled token is the end of sequence counts as
    # failed: the traffic's own lottery, as the other rehearsals hold)
    assert result["correct"] is True and result["failed"] <= 2
    assert phases["window"]["errors"] in ([], ["200:done"])
    assert result["attempted"] >= 6
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"]["tokens_per_s"]["value"] > 0
    assert phases["degraded_paths"]["problems"] == []
    # float32 engine against the float32 reference: the served token is
    # the reference's own maximum but for a rounding-level tie.
    assert phases["right_answers"]["worst_gap_sigmas"] <= 0.01
    assert phases["build"]["model"] == "tiny-jamba-cpu"
    assert phases["build"]["layers"] == 28
    # One attention layer of one kv head: 2 x 1 x 16 x 4 B a position.
    assert phases["build"]["pool_bytes"] == 256 * 128 * 2 * 16 * 4
    assert phases["program"]["prefix_cache"]["hits"] > 0


def test_jamba_cell_traced_reports_the_state_and_the_pages(grown_manifest):
    result, _phases = _run(grown_manifest, 1)
    got = result["metrics"]
    # (the snapshot store's peak and the copies' milliseconds are read
    # from a traced slice's spans: the CPU has none)
    assert {"state.rescan_share", "kv.prefix_reuse_share",
            "kv.pool_peak_share", "compile.in_window"} <= set(got)
    assert 0.0 <= got["state.rescan_share"]["value"] < 100.0
    assert got["kv.prefix_reuse_share"]["value"] > 0.0
    manifest = mf.load(grown_manifest)
    device_metrics = {m["name"] for m in manifest["per_layer"]
                      if m["source"] == "device_trace"}
    assert not set(got) & device_metrics
    assert result["correct"] is True


@pytest.mark.parametrize("file", [
    "benchmarks/configs/jamba2-3b.json",
    "tests/benchmarks/rehearsal_jamba/configs/tiny-jamba-cpu.json"])
def test_the_two_copies_of_the_architecture_agree(file):
    """The harness's registry entry reads the file's top-level keys,
    the engine its `architecture` block: one model, stated twice."""
    with open(os.path.join(bench_paths.REPO, file),
              encoding="utf-8") as f:
        config = json.load(f)
    arch = config["engine"]["architecture"]
    assert arch and all(config[k] == v for k, v in arch.items()), file
    assert "rope_theta" not in arch and "head_dim" not in arch


def test_the_new_metrics_have_readers_and_entries_the_manifest_takes():
    manifest = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    assert mf.problems(manifest, bench_paths.REPO) == []
    by = {m["name"]: m for m in manifest["per_layer"]}
    assert not set(NEW) & set(by)
    grown = grow(manifest)
    assert mf.problems(grown, bench_paths.REPO) == []
    assert tuple(m["name"] for m in _entries()) == NEW
    for m in _entries():
        assert m["workloads"] == [REAL]
        assert os.path.isfile(mf.reader_file(manifest, bench_paths.REPO,
                                             m["name"]))
    names = {m["name"] for m in mf.cell(grown, REAL)["per_layer"]}
    assert set(NEW) | set(STATE) <= names


def test_the_cell_is_one_chip_whole_and_reports_what_lists_no_cells():
    manifest = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    cell = mf.cell(manifest, REAL)
    assert cell["workload"]["chips"] == 1
    assert cell["workload"]["traffic"] == "roundtable"
    assert cell["config"]["reduced"] == []
    names = {m["name"] for m in cell["per_layer"]}
    assert {"kv.pool_peak_share", "kv.prefix_reuse_share",
            "sched.loop_wait_share", "device.idle_unnamed_share"} <= names
    # The accepted reader knows an attention kernel by the pool
    # [pages, page, kv heads, D] among its operands; with ONE kv head
    # XLA folds the unit axis away and the trace prints [640,128,128]
    # (my chip runs, PR 47), so the cell is not on that list.
    assert "kernel.attn_busy_share" not in names
    assert {m["name"] for m in cell["end_to_end"]} == {
        "ttft_p50_ms", "ttft_p90_ms", "tokens_per_s", "setup_s"}


def test_the_floors_come_from_the_files_keys():
    with open(os.path.join(bench_paths.BENCH, "configs",
                           "jamba2-3b.json"), encoding="utf-8") as f:
        config = json.load(f)
    assert mamba1_cost.is_mamba1(config)
    s = mamba1_cost.sizes(config)
    assert (s["mamba"], s["attention"], s["d"], s["n"]) == (26, 2, 5120, 16)
    # ISSUE 47's arithmetic: a Mamba layer, an attention layer, the model.
    assert mamba1_cost.mamba_params(config) \
        + mamba1_cost.mlp_params(config) == 104_161_472
    assert mamba1_cost.attention_params(config) \
        + mamba1_cost.mlp_params(config) == 76_682_240
    assert mamba1_cost.param_count(config) == 3_029_337_472
    assert mamba1_cost.state_bytes_per_layer(config) == 327_680
    assert mamba1_cost.state_bytes_per_sequence(config) == 10_117_120
    assert mamba1_cost.kv_bytes_per_position(config) == 1024
    # 6.39 GB a step at 15 rows of 2 k context.
    step = mamba1_cost.decode_floor(config, steps=1, row_steps=15,
                                    context_positions=15 * 2000)
    assert 6.35e9 < step["bytes"] < 6.45e9
    scan = mamba1_cost.scan_floor(config, 1024 * 26)
    assert scan["bytes"] == 1024 * 26 * (61_568 + 2 * 327_680 / 128)
    assert scan["flops"] / scan["bytes"] < 10.0
    ops = {"mamba1_scan [pallas f32[1024,40,128] f32[17,13,16,40,128]]": 2.0,
           "mamba1_step [pallas f32[17,40,128]]": 0.5, "%fusion.7": 1.0,
           "body [pallas bf16[640,128,1,128]]": 4.0}
    assert mamba1_cost.kernel_seconds(ops) == 2.5
    assert mamba1_cost.kernel_seconds(ops, mamba1_cost.KERNEL) == 2.0
