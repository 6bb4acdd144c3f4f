"""The whole command rehearsed on the CPU: a cell on tiny-mistral, added
to a copy of the manifest by new files and appended entries only, runs
through gateway and child load generator; its line is labelled `cpu` and
carries no device metric. And the real cells refuse to run here."""
import copy
import json
import os
import subprocess
import sys

import pytest

import bench_paths
from harness import manifest as mf

RUN = os.path.join(bench_paths.BENCH, "run.py")
CELL = "tiny-mistral-cpu.tiny-table"


def _env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)     # one CPU device, as one chip
    return env


@pytest.fixture(scope="module")
def grown_manifest(tmp_path_factory):
    """BENCHMARK.json plus the rehearsal cell: a path, a configuration
    and a cell appended, the closed-loop metrics' `workloads` lists
    extended — nothing that was there is edited, no file of the
    benchmark is touched."""
    base = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    grown = copy.deepcopy(base)
    grown["paths"].append("tests/benchmarks/rehearsal")
    grown["configs"].append({
        "name": "tiny-mistral-cpu",
        "source": "tests only: the registry's tiny-mistral sizes",
        "file": "tests/benchmarks/rehearsal/configs/"
                "tiny-mistral-cpu.json",
        "reduced": [], "why": "rehearsal of the harness on the CPU"})
    grown["workloads"].append({
        "name": CELL, "config": "tiny-mistral-cpu",
        "traffic": "tiny-table", "chips": 1,
        "why": "closed loop, 2 discussions x 3 knights x 2 rounds at a "
               "size the CPU serves in seconds"})
    for m in grown["end_to_end"] + grown["per_layer"]:
        if "workloads" in m:        # the closed-loop cells' metrics
            m["workloads"].append(CELL)
    assert mf.problems(grown, bench_paths.REPO) == []
    for key in ("configs", "workloads"):
        assert grown[key][:len(base[key])] == base[key]
    path = tmp_path_factory.mktemp("manifest") / "BENCHMARK.json"
    path.write_text(json.dumps(grown))
    return str(path)


def _run(manifest, trace, cell=CELL):
    proc = subprocess.run(
        [sys.executable, RUN, "--manifest", manifest, "--workload", cell,
         "--seed", "3000000001", "--seconds", "6", "--trace", str(trace)],
        capture_output=True, text=True, env=_env(), timeout=280,
        cwd=bench_paths.REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()]
    return lines[-1], {x["phase"]: x for x in lines[:-1]}


def test_rehearsal_cell_runs_end_to_end_untraced(grown_manifest):
    result, phases = _run(grown_manifest, 0)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 6
    assert result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"]
    assert result["metrics"]["setup_s"]["value"] > 0
    assert result["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    # Twelve rows are no sample for a 90th percentile: it is left out,
    # not made up.
    assert "ttft_p90_ms" not in result["metrics"]
    assert phases["degraded_paths"]["problems"] == []
    assert phases["right_answers"]["worst_gap_sigmas"] <= 0.25
    assert len(phases["right_answers"]["requests"]) == 5
    assert phases["window"]["scheduler"]["ragged_joins"] >= 1
    assert set(phases["setup"]) >= {"build_s", "warmup_s", "ramp_s"}


def test_rehearsal_cell_traced_reports_counts_and_no_device_metric(
        grown_manifest):
    result, _phases = _run(grown_manifest, 1)
    got = set(result["metrics"])
    assert {"kv.prefix_reuse_share", "kv.pool_peak_share",
            "compile.in_window", "gateway.admit_ms",
            "sched.rows_per_segment"} <= got
    manifest = mf.load(grown_manifest)
    device_metrics = {m["name"] for m in manifest["per_layer"]
                      if m["source"] == "device_trace"}
    assert not got & device_metrics
    assert "breakdown" not in result
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"]["kv.prefix_reuse_share"]["value"] > 30


def _real_cells():
    return [w["name"] for w in mf.load(os.path.join(
        bench_paths.REPO, "BENCHMARK.json"))["workloads"]]


@pytest.mark.parametrize("cell", _real_cells())
def test_a_real_cell_refuses_to_run_without_a_tpu(cell):
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed", "1",
         "--seconds", "5", "--trace", "0"],
        capture_output=True, text=True, env=_env(), timeout=120,
        cwd=bench_paths.REPO)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "JAX found 'cpu'" in proc.stderr


def test_the_command_refuses_to_run_alone(tmp_path):
    """In a directory that holds BENCHMARK.json and the files under
    `paths` and nothing else of the repo: nonzero, no result."""
    import shutil
    shutil.copy(os.path.join(bench_paths.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(bench_paths.BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "mistral-7b-int8.roundtable", "--seed", "1", "--seconds", "5",
         "--trace", "0"], capture_output=True, text=True, env=_env(),
        timeout=120, cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_an_unknown_cell_is_refused():
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", "nowhere", "--seed", "1",
         "--seconds", "5", "--trace", "0"],
        capture_output=True, text=True, env=_env(), timeout=120,
        cwd=bench_paths.REPO)
    assert proc.returncode != 0 and "no cell" in proc.stderr
