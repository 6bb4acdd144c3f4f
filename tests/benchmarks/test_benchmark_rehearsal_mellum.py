"""The whole command rehearsed on the CPU on a `mellum` decoder: a cell on
tiny-mellum's widths at four layers (sliding, sliding, sliding, full:
8 heads over 2 kv heads, a 64-token window over 32-wide pages, YaRN on
the full layer, a softmax top-2 router over 8 held experts and no
shared one), whose configuration reaches the engine through its
`architecture` block alone, under a three-round cut of `roundtable-long`.
Added to a copy of the manifest by new files and appended entries only,
as test_benchmark_rehearsal_laguna.py does it; the readers' entries come
from layer_metrics/laguna_entries.json and mellum_entries.json, because
BENCHMARK.json cannot take them yet (PERF.md, Open questions). The pool
(512 pages of 32 for contexts under 700) cannot shed."""
import copy
import json
import os
import subprocess
import sys

import pytest

import bench_paths
from harness import manifest as mf

RUN = os.path.join(bench_paths.BENCH, "run.py")
CELL = "tiny-mellum-cpu.tiny-long-table"
REAL = "mellum2-12b-a2.5b-d8.roundtable-long"
LAGUNA = "laguna-xs.2-d5.roundtable"
NEW = "kv.window_dead_share"
FILES = ("benchmarks/configs/mellum2-12b-a2.5b-d8.json",
         "tests/benchmarks/rehearsal_mellum/configs/tiny-mellum-cpu.json")
# what a top-level key of the file stands in for, where the published
# config has no such key (the file's `assumed` says why)
STAND_INS = ("rope_theta", "n_routed_experts",
             "num_attention_heads_per_layer",
             "shared_expert_intermediate_size")


def _entries(name):
    with open(os.path.join(bench_paths.BENCH, "layer_metrics",
                           name + "_entries.json"), encoding="utf-8") as f:
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
    grown["paths"].append("tests/benchmarks/rehearsal_mellum")
    grown["configs"].append({
        "name": "tiny-mellum-cpu",
        "source": "tests only: the registry's tiny-mellum sizes",
        "file": "tests/benchmarks/rehearsal_mellum/configs/"
                "tiny-mellum-cpu.json",
        "reduced": [], "why": "rehearsal of a softmax router on the CPU"})
    grown["workloads"].append({
        "name": CELL, "config": "tiny-mellum-cpu",
        "traffic": "tiny-long-table", "chips": 1,
        "why": "closed loop, 2 discussions x 3 knights x 3 rounds at a "
               "size the CPU serves in seconds"})
    # the window readers this cell can use unedited, and the new one
    grown["per_layer"].extend(_entries("laguna") + _entries("mellum"))
    for m in grown["end_to_end"] + grown["per_layer"]:
        if REAL in m.get("workloads", ()) \
                or m["name"] == "kv.window_skip_share":
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
         "--seed", "3000000040", "--seconds", "6", "--trace", str(trace)],
        capture_output=True, text=True, env=_env(), timeout=600,
        cwd=bench_paths.REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()]
    return lines[-1], {x["phase"]: x for x in lines[:-1]}


def test_mellum_cell_runs_end_to_end_untraced(grown_manifest):
    result, phases = _run(grown_manifest, 0)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 6
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"]["tokens_per_s"]["value"] > 0
    assert phases["degraded_paths"]["problems"] == []
    # float32 engine (pages, the window's pages skipped, the grouped
    # product) against the float32 reference (a dense mask, no cache,
    # an expert at a time): the served token is the reference's own
    # maximum but for a rounding-level tie.
    assert phases["right_answers"]["worst_gap_sigmas"] <= 0.01
    assert phases["build"]["model"] == "tiny-mellum-cpu"
    assert phases["build"]["layers"] == 8       # two a published layer
    # One pool shape for all four attention layers: 512 pages x 32
    # positions x 2 kv heads x 16 x 4 B, keys and values.
    assert phases["build"]["pool_bytes"] == 512 * 32 * 2 * 16 * 4 * 2 * 4
    assert phases["program"]["ragged"]["path"] == "pallas_ragged"
    # the pool cannot shed: nothing refused, spilled or preempted
    sched = phases["window"]["scheduler"]
    assert (sched["refused"], sched["spills"], sched["preemptions"],
            sched["failed"]) == (0, 0, 0, 0)
    assert phases["window"]["gateway"]["shed"] == 0


def test_mellum_cell_traced_reports_what_the_cpu_can_read(grown_manifest):
    """No device trace on the CPU: the two roofline readers find nothing
    and the line leaves them out; the spans' readers report what the
    windows skipped and what the rows held behind them."""
    result, _phases = _run(grown_manifest, 1)
    got = result["metrics"]
    assert {"compile.in_window", "kv.window_skip_share", NEW} <= set(got)
    assert not {"kernel.attn_roofline.window",
                "step.decode_roofline.window"} & set(got)
    # contexts of 200 to 700 tokens (7 to 22 pages) against a window of
    # 64 (3 pages), three layers of four
    assert 35.0 < got["kv.window_skip_share"]["value"] < 70.0
    assert 35.0 < got[NEW]["value"] < 70.0
    assert result["correct"] is True


def test_the_two_copies_of_the_architecture_agree():
    """The harness's registry entry reads the file's top-level keys,
    the engine its `architecture` block: one model, stated twice. The
    four top-level keys the block lacks are for files the benchmark
    already has (`assumed` says which)."""
    for file in FILES:
        with open(os.path.join(bench_paths.REPO, file),
                  encoding="utf-8") as f:
            config = json.load(f)
        arch = config["engine"]["architecture"]
        assert arch and all(config[k] == v for k, v in arch.items()), file
        assert not set(STAND_INS) & set(arch)
        assert config["n_routed_experts"] == arch["num_experts"]
        for kind in ("full_attention", "sliding_attention"):
            assert config["rope_theta"] == arch["rope_parameters"][kind][
                "rope_theta"]
        assert config["num_attention_heads_per_layer"] == \
            [arch["num_attention_heads"]] * arch["num_hidden_layers"]
        assert config["shared_expert_intermediate_size"] == 0


def test_the_new_metric_has_a_reader_and_an_entry_the_manifest_takes():
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
    entries = _entries("mellum")
    assert [m["name"] for m in entries] == [NEW]
    assert entries[0]["workloads"] == [LAGUNA, REAL]
    assert entries[0]["moves"] == "tokens_per_s"
    assert os.path.isfile(mf.reader_file(manifest, bench_paths.REPO, NEW))
    grown = copy.deepcopy(manifest)
    grown["per_layer"].extend(entries)
    assert mf.problems(grown, bench_paths.REPO) == []


def test_the_cell_is_in_the_manifest_on_one_chip():
    manifest = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    cell = mf.cell(manifest, REAL)
    assert cell["workload"] == {
        "name": REAL, "config": "mellum2-12b-a2.5b-d8",
        "traffic": "roundtable-long", "chips": 1,
        "why": cell["workload"]["why"]}
    assert {m["name"] for m in cell["end_to_end"]} == {
        "ttft_p50_ms", "ttft_p90_ms", "tokens_per_s", "setup_s"}
    assert cell["config"]["source"] == (
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
        "blob/main/config.json")


def test_the_mix_is_the_roundtables_own_run_to_twelve_rounds():
    """`roundtable-long` differs from `roundtable` in the rounds, the
    stagger (a fifth of one discussion's measured length) and the
    `why`, and in nothing else."""
    mixes = {}
    for name in ("roundtable", "roundtable-long"):
        with open(os.path.join(bench_paths.BENCH, "traffic",
                               name + ".json"), encoding="utf-8") as f:
            mixes[name] = json.load(f)
    long, short = mixes["roundtable-long"], mixes["roundtable"]
    assert {k for k in long if long[k] != short.get(k)} == {
        "why", "rounds", "stagger_s"}
    assert (long["kind"], long["rounds"], long["concurrency"],
            long["knights"]) == ("discussions", 12, 5, 3)
    assert long["max_new_tokens"] == {"dist": "fixed", "value": 128}
    assert 3.0 <= long["stagger_s"] <= 4.0


def test_the_configuration_keeps_every_published_number():
    """The catalog's `config` for Mellum2-12B-A2.5B-Instruct, key for
    key, but for the depth and the two per-layer lists shortened to it;
    the published values stand beside them."""
    with open(os.path.join(bench_paths.BENCH, "configs",
                           "mellum2-12b-a2.5b-d8.json"),
              encoding="utf-8") as f:
        config = json.load(f)
    period = ["sliding_attention"] * 3 + ["full_attention"]
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "layer_types": period * 7, "mlp_layer_types": ["sparse"] * 28,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True}
    differ = sorted(k for k, v in published.items() if config[k] != v)
    reduced = ["layer_types", "mlp_layer_types", "num_hidden_layers"]
    assert differ == sorted(config["reduced"]) == reduced
    assert config["published"] == {k: published[k] for k in differ}
    # two whole periods, the lists only shortened to the depth
    assert config["num_hidden_layers"] == 8
    assert config["layer_types"] == period * 2
    assert config["mlp_layer_types"] == ["sparse"] * 8
    assert {"qk_norm", "router", "shared_expert", "mtp_head",
            "rope_scaling", "prefix_cache_pages"} <= set(config["assumed"])
    assert all(f"{k} (top level)" in config["assumed"] for k in STAND_INS)
    assert "LEFT OUT" in config["assumed"]["mtp_head"]
    assert "four pipeline stages" in config["deployment"]
    engine = config["engine"]
    assert (engine["dtype"], engine["quant"], engine["page_size"],
            engine["num_pages"], engine["num_slots"]) == (
        "bfloat16", "none", 128, 1024, 16)
    manifest = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    entry = next(c for c in manifest["configs"]
                 if c["name"] == config["name"])
    assert sorted(entry["reduced"]) == differ
    assert entry["file"] == "benchmarks/configs/mellum2-12b-a2.5b-d8.json"
    assert entry["source"] == config["source"]
