"""The whole command rehearsed on the CPU on a model whose upper half
keeps no cache: a cell on tiny-phi4flash (depth 8: three Mamba-1 layers,
two window-16 layers, one full layer, one gated memory unit, one cross
layer over the full layer's pages; a kv pair of 64-wide heads a lane
row), whose configuration reaches the engine through its `architecture`
block alone. Added to a copy of the manifest by new files and appended
entries only, as test_benchmark_rehearsal_jamba.py does it; the three
new readers' entries come from layer_metrics/phi4flash_entries.json and
the state and window readers' from hybrid_entries.json,
retention_entries.json and mellum_entries.json, because BENCHMARK.json
cannot take them yet (PERF.md, Open questions). ONE run, untraced (the
test budget of ISSUE 56); the readers are driven by hand over a made-up
slice."""
import copy
import json
import os
import subprocess
import sys

import pytest

import bench_paths
from harness import manifest as mf
from harness import sambay_cost

RUN = os.path.join(bench_paths.BENCH, "run.py")
CELL = "tiny-phi4flash-cpu.tiny-seam-table"
REAL = "phi-4-mini-flash.roundtable"
NEW = ("step.seam_token_share", "kernel.attn_roofline.diff",
       "step.decode_roofline.sambay")
WAITING = ("state.rescan_share", "state.snapshot_peak_share",
           "state.copy_ms_per_join", "kv.window_dead_share")


def _entries(file="phi4flash_entries.json"):
    with open(os.path.join(bench_paths.BENCH, "layer_metrics", file),
              encoding="utf-8") as f:
        return json.load(f)["per_layer"]


def _config(file="phi-4-mini-flash.json"):
    with open(os.path.join(bench_paths.BENCH, "configs", file),
              encoding="utf-8") as f:
        return json.load(f)


def _env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)     # one CPU device, as one chip
    return env


def grow(base):
    """BENCHMARK.json with the waiting entries this cell reads appended
    (the three new readers', the state and window readers' with the cell
    on their lists): what `run.py --manifest` takes on the chip."""
    grown = copy.deepcopy(base)
    grown["per_layer"].extend(_entries())
    grown["per_layer"].extend(
        dict(m, workloads=m["workloads"] + [REAL])
        for file in ("hybrid_entries.json", "retention_entries.json",
                     "mellum_entries.json")
        for m in _entries(file) if m["name"] in WAITING)
    return grown


@pytest.fixture(scope="module")
def grown_manifest(tmp_path_factory):
    base = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    grown = grow(base)
    grown["paths"].append("tests/benchmarks/rehearsal_phi4flash")
    grown["configs"].append({
        "name": "tiny-phi4flash-cpu",
        "source": "tests only: the registry's tiny-phi4flash sizes",
        "file": "tests/benchmarks/rehearsal_phi4flash/configs/"
                "tiny-phi4flash-cpu.json",
        "reduced": [], "why": "rehearsal of the seam on the CPU"})
    grown["workloads"].append({
        "name": CELL, "config": "tiny-phi4flash-cpu",
        "traffic": "tiny-seam-table", "chips": 1,
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


def test_phi4flash_cell_runs_end_to_end_untraced(grown_manifest):
    proc = subprocess.run(
        [sys.executable, RUN, "--manifest", grown_manifest, "--workload",
         CELL, "--seed", "3000000056", "--seconds", "6", "--trace", "0"],
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
    assert result["metrics"]["tokens_per_s"]["value"] > 0
    assert phases["degraded_paths"]["problems"] == []
    # float32 engine against the float32 reference: the served token is
    # the reference's own maximum but for a rounding-level tie.
    assert phases["right_answers"]["worst_gap_sigmas"] <= 0.01
    assert phases["build"]["model"] == "tiny-phi4flash-cpu"
    assert phases["build"]["layers"] == 16
    # THREE pooled layers (two window, one full) of 4 kv heads of 64, a
    # pair a lane row: 2 x 2 x 128 x 4 B a position a layer; the cross
    # layer owns no pool.
    assert phases["build"]["pool_bytes"] == 3 * 128 * 128 * 2 * 2 * 128 * 4
    assert phases["program"]["prefix_cache"]["hits"] > 0


@pytest.mark.parametrize("file", [
    "benchmarks/configs/phi-4-mini-flash.json",
    "tests/benchmarks/rehearsal_phi4flash/configs/tiny-phi4flash-cpu.json"])
def test_the_two_copies_of_the_architecture_agree(file):
    """The harness's registry entry reads the file's top-level keys,
    the engine its `architecture` block: one model, stated twice."""
    with open(os.path.join(bench_paths.REPO, file),
              encoding="utf-8") as f:
        config = json.load(f)
    arch = config["engine"]["architecture"]
    assert arch and all(config[k] == v for k, v in arch.items()), file
    assert "rope_theta" not in arch and "rms_norm_eps" not in arch
    assert config["rms_norm_eps"] == config["layer_norm_eps"]


def test_the_file_holds_the_catalogs_keys_and_cuts_one():
    config = _config()
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 32}
    assert config["num_hidden_layers"] == 12
    assert config["engine"]["architecture"] == dict(published,
                                                    num_hidden_layers=12)
    for key in ("mamba_d_state", "mamba_d_conv", "mamba_expand",
                "mamba_dt_rank", "differential_attention", "head_dim",
                "rope_theta, rms_norm_eps", "weights", "layer_order"):
        assert key in config["assumed"], key


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
    assert set(NEW) | set(WAITING) <= names


def test_the_cell_is_one_chip_and_appended_last():
    manifest = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    assert manifest["workloads"][-1]["name"] == REAL
    assert manifest["configs"][-1]["name"] == "phi-4-mini-flash"
    assert len(manifest["workloads"]) == 9
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    cell = mf.cell(manifest, REAL)
    assert cell["workload"]["traffic"] == "roundtable"
    assert cell["config"]["reduced"] == ["num_hidden_layers"]
    names = {m["name"] for m in cell["per_layer"]}
    assert {"kv.pool_peak_share", "kv.prefix_reuse_share",
            "sched.loop_wait_share", "device.idle_unnamed_share"} <= names
    # (the accepted reader looks for [640,128,20,64] among a kernel's
    # operands; the walk's operand is the pool as XLA stores it,
    # [640,1280,128]: sambay_cost.pool_operand)
    assert "kernel.attn_busy_share" not in names
    assert {m["name"] for m in cell["end_to_end"]} == {
        "ttft_p50_ms", "ttft_p90_ms", "tokens_per_s", "setup_s"}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in ("tokens_per_s", "sched.loop_wait_share",
                         "device.idle_unnamed_share"):
            assert m["workloads"][-1] == REAL


def test_the_floors_come_from_the_files_keys():
    config = _config()
    assert sambay_cost.is_sambay(config)
    s = sambay_cost.sizes(config)
    assert (s["mamba"], s["window_layers"], s["full"], s["gmu"],
            s["cross"]) == (4, 3, 1, 2, 2)
    assert (s["d"], s["n"], s["r"], s["head_dim"]) == (5120, 16, 160, 64)
    whole = dict(config, num_hidden_layers=32)
    w = sambay_cost.sizes(whole)
    assert (w["mamba"], w["window_layers"], w["full"], w["gmu"],
            w["cross"]) == (9, 8, 1, 7, 7)
    # ISSUE 56's arithmetic, a layer of each kind with its MLP, in M.
    mlp = sambay_cost.mlp_params(config)
    assert [round((n + mlp) / 1e6, 1) for n in (
        sambay_cost.mamba_params(config),
        sambay_cost.attention_params(config),
        sambay_cost.gmu_params(config),
        sambay_cost.cross_params(config))] == [119.9, 98.3, 104.9, 91.8]
    assert 3.850e9 < sambay_cost.param_count(whole) < 3.855e9
    assert sambay_cost.param_count(config) == 1_778_306_310
    assert sambay_cost.kv_bytes_per_position_a_layer(config) == 5120
    assert sambay_cost.state_bytes_per_sequence(config) == 4 * 389_120
    assert sambay_cost.pool_operand(config) == "[640,1280,128]"
    assert sambay_cost.pool_operand(dict(
        config, num_key_value_heads=16)) == "[640,128,8,128]"
    # A token at 2000: three window layers read 512 each, the full layer
    # 2000, two cross layers 2000 each from the pool they share.
    read = sambay_cost.positions_read(config, [2000, 300])
    assert read == {"own": 3 * 512 + 2000 + 4 * 300,
                    "shared": 2 * 2300}
    walk = sambay_cost.decode_walk_floor(config, [2000])
    assert walk["bytes"] == (3 * 512 + 3 * 2000) * 5120
    assert walk["flops"] == (3 * 512 + 3 * 2000) * 40 * 384
    # 4.18 GB a step at 15 rows of 2 k context.
    step = sambay_cost.decode_floor(config, steps=1, row_steps=15,
                                    context_lengths=[2000])
    assert 4.15e9 < step["bytes"] < 4.22e9
    ops = {"paged_decode_attention [pallas s32[16,64] s32[16] "
           "bf16[16,40,128] bf16[640,1280,128] bf16[640,1280,128]]": 2.0,
           "ragged_paged_attention [pallas bf16[640,1280,128]]": 1.0,
           "paged_decode_attention [pallas bf16[640,128,8,128]]": 4.0}
    assert sambay_cost.decode_walk_seconds(ops, config) == 2.0


def _reader(name):
    import importlib.util
    path = os.path.join(bench_paths.BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _ctx(config, monkeypatch, spans):
    from harness import loopspans
    monkeypatch.setattr(loopspans, "slice_spans", lambda ctx, *a: spans)
    rows = [{"sent": 0.0, "prompt_tokens": 2000,
             "flushes": [(1.0, 1), (2.0, 8)]}]
    pool = "bf16[640,1280,128]"
    return {
        "config": config, "slice": {"start": 0.0, "end": 10.0},
        "rows": rows,
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "names": {"programs": {"decode": ["jit_decode_loop"]}},
        "trace": {
            "op_seconds": {
                f"paged_decode_attention [pallas {pool}]": 0.002},
            "module_seconds": {"jit_decode_loop_hybrid(1)": 0.16}}}


def test_the_three_readers_read_this_cell_and_nothing_elsewhere(monkeypatch):
    spans = [
        {"rung": "segment", "t0": 1.0, "dur_s": 0.1, "attrs": {
            "kind": "ragged", "lower_tokens": 310, "upper_rows": 11}},
        {"rung": "segment", "t0": 2.0, "dur_s": 0.1, "attrs": {
            "kind": "plain", "steps": 8, "decode_tokens": 8,
            "lower_tokens": 0, "upper_rows": 0}},
        # (half of this one lies before the slice: 8 of its 16 steps)
        {"rung": "segment", "t0": -0.5, "dur_s": 1.0, "attrs": {
            "kind": "plain", "steps": 16, "decode_tokens": 16,
            "lower_tokens": 0, "upper_rows": 0}}]
    ctx = _ctx(_config(), monkeypatch, spans)
    assert _reader("step.seam_token_share").read(ctx) \
        == pytest.approx(100.0 * 11 / 310)
    walk = _reader("kernel.attn_roofline.diff").read(ctx)
    step = _reader("step.decode_roofline.sambay").read(ctx)
    # 8 tokens decoded at 2001..2008: (3 x 512 + 3 x C) x 5120 B each
    # over 819 GB/s, against 2 ms of walk; 16 steps of 3.56 GB (8 and
    # the half of 16 that lies inside the slice) and the reads against
    # 160 ms of the decode program.
    want = sum((3 * 512 + 3 * c) * 5120 for c in range(2001, 2009)) \
        / 819e9 / 0.002 * 100.0
    assert walk == pytest.approx(want)
    assert 40.0 < step < 60.0
    # Another cell's trace and spans: nothing to read, and no raise.
    other = _ctx(_config("jamba2-3b.json"), monkeypatch, [
        {"rung": "segment", "t0": 2.0, "dur_s": 0.1, "attrs": {
            "kind": "plain", "steps": 8, "decode_tokens": 8}}])
    for name in NEW:
        assert _reader(name).read(other) is None
    # A floor over the time is an error, not a value.
    ctx["trace"]["op_seconds"] = {k: 1e-6 for k in
                                  ctx["trace"]["op_seconds"]}
    with pytest.raises(RuntimeError, match="counts too much"):
        _reader("kernel.attn_roofline.diff").read(ctx)
