"""The whole command rehearsed on the CPU on a model with NO attention
layer: a cell on tiny-brumby (power-retention state a layer a sequence,
an empty pool tree, pages as ids), whose configuration reaches the
engine through its `architecture` block alone. Added to a copy of the
manifest by new files and appended entries only, as
test_benchmark_rehearsal_hybrid.py does it; the retention readers'
entries come from layer_metrics/retention_entries.json and the state
readers' from hybrid_entries.json, because BENCHMARK.json cannot take
them yet (PERF.md, Open questions). The store holds 3 snapshots of
58 752 bytes: the run fills it, so eviction is rehearsed too."""
import copy
import json
import os
import subprocess
import sys

import pytest

import bench_paths
from harness import manifest as mf
from harness import retention_cost

RUN = os.path.join(bench_paths.BENCH, "run.py")
CELL = "tiny-brumby-cpu.tiny-state-table"
REAL = "brumby-14b-d6.roundtable"
NEW = ("kernel.retention_roofline", "kernel.retention_chunk_roofline",
       "kernel.retention_busy_share",
       "step.decode_roofline.retention", "state.copy_ms_per_join")
STATE = ("state.rescan_share", "state.snapshot_peak_share")


def _entries(file="retention_entries.json"):
    with open(os.path.join(bench_paths.BENCH, "layer_metrics", file),
              encoding="utf-8") as f:
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
    grown["paths"].append("tests/benchmarks/rehearsal_brumby")
    grown["configs"].append({
        "name": "tiny-brumby-cpu",
        "source": "tests only: the registry's tiny-brumby sizes",
        "file": "tests/benchmarks/rehearsal_brumby/configs/"
                "tiny-brumby-cpu.json",
        "reduced": [], "why": "rehearsal of the retention path on the CPU"})
    grown["workloads"].append({
        "name": CELL, "config": "tiny-brumby-cpu",
        "traffic": "tiny-state-table", "chips": 1,
        "why": "closed loop, 2 discussions x 3 knights x 2 rounds at a "
               "size the CPU serves in seconds"})
    grown["per_layer"].extend(_entries())
    grown["per_layer"].extend(
        dict(m, workloads=m["workloads"] + [REAL])
        for m in _entries("hybrid_entries.json") if m["name"] in STATE)
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


def test_retention_cell_runs_end_to_end_untraced(grown_manifest):
    result, phases = _run(grown_manifest, 0)
    # (a row whose FIRST sampled token is the end of sequence counts as
    # failed: at 0.7 over 512 rows of random weights that is one row in
    # some hundreds, and this model serves hundreds in six seconds —
    # PERF.md, the traffic's own lottery)
    assert result["correct"] is True and result["failed"] <= 2
    assert phases["window"]["errors"] in ([], ["200:done"])
    assert result["attempted"] >= 6
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"]["tokens_per_s"]["value"] > 0
    assert phases["degraded_paths"]["problems"] == []
    # float32 engine against the float32 reference: the served token is
    # the reference's own maximum but for a rounding-level tie.
    assert phases["right_answers"]["worst_gap_sigmas"] <= 0.01
    assert phases["build"]["model"] == "tiny-brumby-cpu"
    assert phases["build"]["layers"] == 6
    # No attention layer: the pool holds no byte, and its pages are
    # still counted, mapped and reused.
    assert phases["build"]["pool_bytes"] == 0
    assert phases["build"]["num_pages"] == 256
    assert phases["window"]["pool"]["peak_in_use"] > 0
    assert phases["program"]["prefix_cache"]["hits"] > 0


def test_retention_cell_traced_reports_the_state_and_the_pages(
        grown_manifest):
    result, _phases = _run(grown_manifest, 1)
    got = result["metrics"]
    assert {"state.rescan_share", "kv.prefix_reuse_share",
            "kv.pool_peak_share", "compile.in_window"} <= set(got)
    assert 0.0 <= got["state.rescan_share"]["value"] < 100.0
    assert got["kv.prefix_reuse_share"]["value"] > 0.0
    assert got["kv.pool_peak_share"]["value"] > 0.0
    manifest = mf.load(grown_manifest)
    device_metrics = {m["name"] for m in manifest["per_layer"]
                      if m["source"] == "device_trace"}
    assert not set(got) & device_metrics
    assert result["correct"] is True


@pytest.mark.parametrize("file", [
    "benchmarks/configs/brumby-14b-d6.json",
    "tests/benchmarks/rehearsal_brumby/configs/tiny-brumby-cpu.json"])
def test_the_two_copies_of_the_architecture_agree(file):
    """The harness's registry entry reads the file's top-level keys,
    the engine its `architecture` block: one model, stated twice."""
    with open(os.path.join(bench_paths.REPO, file),
              encoding="utf-8") as f:
        config = json.load(f)
    arch = config["engine"]["architecture"]
    assert arch and all(config[k] == v for k, v in arch.items()), file


def test_the_new_metrics_have_readers_and_entries_the_manifest_takes():
    manifest = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    assert mf.problems(manifest, bench_paths.REPO) == []
    by = {m["name"]: m for m in manifest["per_layer"]}
    assert REAL not in by["kernel.attn_busy_share"]["workloads"]
    assert not set(NEW) & set(by)
    grown = copy.deepcopy(manifest)
    grown["per_layer"].extend(_entries())
    assert mf.problems(grown, bench_paths.REPO) == []
    assert tuple(m["name"] for m in _entries()) == NEW
    for m in _entries():
        assert m["workloads"] == [REAL]
        assert os.path.isfile(mf.reader_file(manifest, bench_paths.REPO,
                                             m["name"]))


def test_the_cell_reports_every_metric_that_lists_no_cells():
    """A per-layer metric without a `workloads` list is every cell's:
    the new cell is measured by it whether or not it has attention."""
    manifest = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    cell = mf.cell(manifest, REAL)
    assert cell["workload"]["chips"] == 1
    names = {m["name"] for m in cell["per_layer"]}
    assert {"kv.pool_peak_share", "kv.prefix_reuse_share",
            "sched.loop_wait_share", "device.idle_unnamed_share"} <= names
    assert "kernel.attn_busy_share" not in names
    assert {m["name"] for m in cell["end_to_end"]} == {
        "ttft_p50_ms", "ttft_p90_ms", "tokens_per_s", "setup_s"}


def test_the_floor_is_the_least_layout_whatever_the_program_lays_out():
    with open(os.path.join(bench_paths.BENCH, "configs",
                           "brumby-14b-d6.json"), encoding="utf-8") as f:
        config = json.load(f)
    assert retention_cost.is_retention(config)
    assert retention_cost.state_rows_min(config) == 8256
    assert retention_cost.state_bytes_per_layer(config) \
        == 8 * 8256 * 129 * 4
    # 204.5 MB a sequence, 7.08 GB of weights (ISSUE 42's arithmetic).
    assert retention_cost.state_bytes_per_sequence(config) == 204_484_608
    layer = (5120 * 128 * (2 * 40 + 2 * 8) + 5120 * 8 + 2 * 128 + 5120
             + 3 * 5120 * 17_408 + 5120)
    assert layer == 330_352_896                # 0.661 GB in bfloat16
    assert retention_cost.fixed_step_bytes(config) \
        == 2 * (6 * layer + 151_936 * 5120 + 5120)    # 5.52 GB a step
    ops = {"retention_step [pallas f32[16,8,8,128] f32[17,8,65,128,128]]":
           2.0, "%fusion.7": 1.0,
           "body [pallas bf16[640,128,8,128]]": 4.0}
    assert retention_cost.retention_seconds(ops, config) == 2.0
    assert retention_cost.retention_seconds(
        ops, config, retention_cost.KERNEL) == 2.0
    floor = retention_cost.step_kernel_floor(config, 15)
    assert floor["bytes"] > 15 * 2 * 204_484_608
    assert floor["flops"] / floor["bytes"] < 4.0
