"""The whole command rehearsed on the CPU on the hybrid path: a cell on
tiny-nemotron-h (Mamba-2 state beside paged KV, half of the routed
experts held), whose configuration reaches the engine through its
`architecture` block alone. Added to a copy of the manifest by new files
and appended entries only, as test_benchmark_rehearsal.py does it; the
hybrid readers' entries come from layer_metrics/hybrid_entries.json,
because BENCHMARK.json cannot take them yet (PERF.md, Open questions)."""
import copy
import json
import os
import subprocess
import sys

import pytest

import bench_paths
from harness import manifest as mf

RUN = os.path.join(bench_paths.BENCH, "run.py")
CELL = "tiny-nemotron-h-cpu.tiny-hybrid-table"
REAL = "nemotron-3-nano-ep2.roundtable"
NEW = ("state.rescan_share", "state.snapshot_peak_share",
       "moe.experts_hit_share", "step.decode_roofline",
       "kernel.attn_roofline.hybrid")


def _entries():
    with open(os.path.join(bench_paths.BENCH, "layer_metrics",
                           "hybrid_entries.json"), encoding="utf-8") as f:
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
    grown["paths"].append("tests/benchmarks/rehearsal_hybrid")
    grown["configs"].append({
        "name": "tiny-nemotron-h-cpu",
        "source": "tests only: the registry's tiny-nemotron-h sizes",
        "file": "tests/benchmarks/rehearsal_hybrid/configs/"
                "tiny-nemotron-h-cpu.json",
        "reduced": [], "why": "rehearsal of the hybrid path on the CPU"})
    grown["workloads"].append({
        "name": CELL, "config": "tiny-nemotron-h-cpu",
        "traffic": "tiny-hybrid-table", "chips": 1,
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
         "--seed", "3000000007", "--seconds", "6", "--trace", str(trace)],
        capture_output=True, text=True, env=_env(), timeout=400,
        cwd=bench_paths.REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()]
    return lines[-1], {x["phase"]: x for x in lines[:-1]}


def test_hybrid_cell_runs_end_to_end_untraced(grown_manifest):
    result, phases = _run(grown_manifest, 0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 6
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"]["tokens_per_s"]["value"] > 0
    assert phases["degraded_paths"]["problems"] == []
    # float32 engine against the float32 reference: the served token is
    # the reference's own maximum but for a rounding-level tie.
    assert phases["right_answers"]["worst_gap_sigmas"] <= 0.01
    assert phases["build"]["model"] == "tiny-nemotron-h-cpu"
    assert phases["build"]["layers"] == 5


def test_hybrid_cell_traced_reports_the_state_and_expert_counts(
        grown_manifest):
    result, _phases = _run(grown_manifest, 1)
    got = result["metrics"]
    assert {"state.rescan_share", "moe.experts_hit_share",
            "kv.prefix_reuse_share", "compile.in_window"} <= set(got)
    assert 0.0 <= got["state.rescan_share"]["value"] < 100.0
    assert 0.0 < got["moe.experts_hit_share"]["value"] <= 100.0
    manifest = mf.load(grown_manifest)
    device_metrics = {m["name"] for m in manifest["per_layer"]
                      if m["source"] == "device_trace"}
    assert not set(got) & device_metrics
    assert result["correct"] is True


def test_the_two_copies_of_the_architecture_agree():
    """The harness's registry entry reads the file's top-level keys,
    the engine its `architecture` block: one model, stated twice."""
    for file in ("benchmarks/configs/nemotron-3-nano-ep2.json",
                 "tests/benchmarks/rehearsal_hybrid/configs/"
                 "tiny-nemotron-h-cpu.json"):
        with open(os.path.join(bench_paths.REPO, file),
                  encoding="utf-8") as f:
            config = json.load(f)
        arch = config["engine"]["architecture"]
        assert arch and all(config[k] == v for k, v in arch.items()), file


def test_the_new_metrics_have_readers_and_entries_the_manifest_takes():
    """Appended to a copy, the five entries break no rule of the
    manifest; the real one lists the cell on no metric whose reader
    cannot read it."""
    manifest = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    assert mf.problems(manifest, bench_paths.REPO) == []
    by = {m["name"]: m for m in manifest["per_layer"]}
    assert REAL not in by["kernel.attn_roofline"]["workloads"]
    assert not set(NEW) & set(by)
    grown = copy.deepcopy(manifest)
    grown["per_layer"].extend(_entries())
    assert mf.problems(grown, bench_paths.REPO) == []
    assert tuple(m["name"] for m in _entries()) == NEW
    for m in _entries():
        assert m["workloads"] == [REAL]
        assert os.path.isfile(mf.reader_file(manifest, bench_paths.REPO,
                                             m["name"]))
