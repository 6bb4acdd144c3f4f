"""The trace reduction reproduces, on a small recorded trace, the busy
share and the time per name worked out by hand, clipped to the span the
benchmark marked; the per-layer readers read it; and what finds nothing
to read returns nothing."""
import importlib.util
import json
import os

import pytest

import bench_paths
from harness import kernel_cost, manifest as mf, tracered

HERE = os.path.dirname(os.path.abspath(__file__))
WINDOW_S = 5e-3
BODY = ("%body [pallas s32[16,64] s32[16] bf16[16,8,4,128] "
        "bf16[2048,128,8,128] bf16[2048,128,8,128]]")
RAGGED = ("%ragged_step [pallas s32[17,64] bf16[8,4,256,128] "
          "bf16[2048,128,8,128] bf16[2048,128,8,128]]")
MLP = "%mlp [pallas bf16[16,4096] s8[4096,14336]]"


def recorded():
    with open(os.path.join(HERE, "trace_small.json"),
              encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced():
    return tracered.reduce(recorded())


def reader(name):
    path = os.path.join(bench_paths.BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("r_" + name.replace(
        ".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_busy_time_is_the_union_of_operation_intervals(reduced):
    # [0, 1.0] (the while and what it encloses) + [1.5, 1.8]
    # + [1.81, 2.12] + [3.0, 4.0] + [4.95, 5.0] ms = 2.66 ms of 5: the
    # enclosing `while` is not added to its body, the event that
    # straddles the span's end counts for its part inside, and the
    # 3 ms that ran after the span (while stop_trace returned) for
    # nothing.
    assert reduced["busy_s"] == pytest.approx(2.66e-3)
    assert reduced["idle_share"] == pytest.approx(1 - 2.66 / 5)
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(WINDOW_S)


def test_without_the_span_the_trace_is_reduced_over_its_own_events():
    trace = recorded()
    trace["host"] = [e for e in trace["host"] if e[0] != "bench:slice"]
    whole = tracered.reduce(trace)
    assert whole["window_s"] == pytest.approx(9e-3)
    assert whole["busy_s"] == pytest.approx((2.66 + 0.05 + 3.0) * 1e-3)


def test_seconds_per_name_are_self_times(reduced):
    assert reduced["op_seconds"] == pytest.approx({
        "while.1": 1e-4,              # 1.0 ms less the 0.9 ms inside it
        "fusion.1": 5.9e-4,           # 0.4 + 0.19; the third is outside
        BODY: 5e-4, "fusion.2": 3.5e-4,     # 0.3 + 0.05 of 0.1
        MLP: 1.2e-4, RAGGED: 1e-3})
    assert [n for n, _s in reduced["device_ops"]] == [
        RAGGED, "fusion.1", BODY, "fusion.2", MLP, "while.1"]
    assert reduced["module_seconds"] == pytest.approx({
        "jit_decode_loop_paged_direct": 1e-3, "jit_ragged_step": 2.55e-3})


def test_an_attention_kernel_is_a_mosaic_call_that_reads_the_pool(
        reduced):
    ctx = _ctx(reduced)
    assert kernel_cost.pool_operand(ctx["config"]) == "[2048,128,8,128]"
    # The Pallas MLP kernel reads no pool: it is not attention.
    assert kernel_cost.attention_seconds(
        reduced["op_seconds"], ctx["config"]) == pytest.approx(1.5e-3)
    other = dict(ctx["config"], engine=dict(ctx["config"]["engine"],
                                            num_pages=512))
    assert kernel_cost.attention_seconds(reduced["op_seconds"],
                                         other) == 0.0


def test_idle_gaps_are_named_by_the_innermost_host_span(reduced):
    # 1.0-1.5 ms lies inside rt:segment (inside rt:turn); 2.12-3.0 and
    # 4.0-4.95 ms only inside rt:turn — and inside an rt:request of the
    # gateway's thread, which started later but is the outer rung; the
    # 10 us between 1.80 and 1.81 ms is the device's own turn-around and
    # is no gap. The benchmark's own span names no gap.
    assert dict(map(tuple, reduced["idle_gaps"])) == pytest.approx({
        "rt:turn": 1.83e-3, "rt:segment": 5e-4})


def test_operation_names_are_shortened_and_kernels_marked():
    hlo = ('%body.81 = bf16[16,8,4,128]{3,2,1,0} custom-call(s32[16,64] '
           '%get-tuple-element.5278), custom_call_target="tpu_custom_call"'
           ', operand_layout_constraints={}')
    assert tracered.short_name(hlo) == "%body [pallas s32[16,64]]"
    hlo = ('%ragged_step.15 = bf16[8,4,256,128]{3,2,1,0:T(8,128)(2,1)S(1)}'
           ' custom-call(s32[17,64]{1,0:T(8,128)S(1)} %copy-done.116, '
           'bf16[8,4,256,128]{3,2,1,0:T(8,128)(2,1)S(1)} %fusion.330, '
           'bf16[2048,128,8,128]{3,2,1,0:T(8,128)(2,1)} %fusion.21, '
           'bf16[2048,128,8,128]{3,2,1,0:T(8,128)(2,1)} %fusion.22), '
           'custom_call_target="tpu_custom_call", '
           'operand_layout_constraints={s32[17,64]{1,0}}, '
           'frontend_attributes={kernel_metadata={}}')
    assert tracered.short_name(hlo) == RAGGED
    assert tracered.short_name(
        "%fusion.770 = (f32[16]) fusion(bf16[16,4096] %x), kind=kLoop"
    ) == "%fusion.770"
    assert tracered.short_name(
        '%custom-call.97 = (f32[16,128]) custom-call(f32[16,32000] %y), '
        'custom_call_target="TopK"') == "%custom-call.97"
    assert tracered.short_name("rt:segment") == "rt:segment"


def test_merged_and_self_seconds_on_edge_cases():
    assert tracered.merged([(5, 6), (0, 2), (1, 3), (3, 4)]) == [
        (0, 4), (5, 6)]
    assert tracered.merged([]) == []
    assert tracered.self_seconds([]) == {}
    nested = [["a", 0, 100], ["b", 10, 50], ["c", 20, 10], ["a", 200, 5]]
    assert tracered.self_seconds(nested) == pytest.approx(
        {"a": (50 + 5) / 1e9, "b": 40 / 1e9, "c": 10 / 1e9})


def test_a_trace_with_no_device_plane_reduces_to_nothing(tmp_path):
    assert tracered.reduce({"devices": {}, "host": []}) == {}
    assert tracered.find_xplane(str(tmp_path)) is None


def test_the_loader_reads_a_real_profile_and_finds_no_tpu_here(tmp_path):
    """On the CPU the profiler writes host planes only: the loader
    reads the file, keeps the program's mirrored spans, and reports no
    device plane — never a CPU line under a device's name."""
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation(tracered.SLICE_SPAN):
        with jax.profiler.TraceAnnotation("rt:segment"):
            jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = tracered.find_xplane(str(tmp_path))
    assert path is not None
    trace = tracered.load_xplane(path)
    assert trace["devices"] == {}
    assert any(name == "rt:segment" for name, _s, _d in trace["host"])
    lo, hi = tracered.slice_span(trace)
    assert hi > lo
    assert tracered.reduce(trace) == {}


def _ctx(reduced):
    with open(os.path.join(bench_paths.BENCH, "layer_metrics",
                           "names.json"), encoding="utf-8") as f:
        names = json.load(f)
    with open(os.path.join(bench_paths.BENCH, "configs",
                           "mistral-7b-int8.json"),
              encoding="utf-8") as f:
        config = json.load(f)
    # The recorded trace's pool, whatever the cell's is today.
    config = dict(config, engine=dict(config["engine"], num_pages=2048))
    with open(os.path.join(bench_paths.BENCH, "peaks.json"),
              encoding="utf-8") as f:
        peaks = json.load(f)["device_kinds"]["TPU v5 lite"]

    def counters(decode, prefill, segments, ragged, compiles, reused,
                 prefilled):
        return {"scheduler": {"segment_decode_tokens": decode,
                              "segment_prefill_tokens": prefill,
                              "segments": segments,
                              "ragged_segments": ragged},
                "compiles": compiles, "reused_tokens": reused,
                "prefill_tokens": prefilled,
                "pool": {"pages": 351, "in_use": 100}}

    a = counters(1000, 500, 10, 5, 40, 3000, 1000)
    b = counters(1070, 900, 11, 11, 40, 6000, 2000)
    rows = [{"sent": 0.0, "prompt_tokens": 1000, "due": 0.0,
             "first": 1.0, "last": 3.0, "tokens": 5, "ok": True,
             "measured": True, "asked_tokens": 5,
             "flushes": [[1.0, 1], [3.0, 4]]}] * 200
    traces = [{"kind": "request", "stages": {
        "admission": 0.001 * i, "placement": 0.001,
        "queue_wait": 0.01 * i}} for i in range(1, 41)]
    return {"names": names, "config": config, "peaks": peaks,
            "trace": reduced, "counters": {"start": a, "end": b},
            "slice": {"start": 0.5, "end": 2.6, "counters_start": a,
                      "counters_end": b},
            "rows": rows, "request_traces": traces,
            "window": {"start": 0.0, "end": 10.0},
            "traffic": {"drain_s": 60.0}, "pool_peak_in_use": 234}


EXPECTED = {
    "client.tpot_p95_ms": 500.0,                # 2 s over 5 - 1 tokens
    "client.ttft_p95_ms": 1000.0,               # due at 0, first at 1 s
    "compile.in_window": 0.0,
    "kv.prefix_reuse_share": 75.0,              # 3000 of 3000 + 1000
    "kv.pool_peak_share": 100 * 234 / 351,
    "sched.rows_per_segment": 70 / (64 + 6),    # one segment, six ragged
    "gateway.admit_ms": 21.5,                   # median of i + 1, i=1..40
    "sched.queue_wait_p95_ms": 390.0,           # 39th of 40 waits of 10 i
    "step.decode_ms_per_token": 1e3 * 3.55e-3 / 70,
    "step.prefill_ms_per_ktok": 1e6 * 2.55e-3 / 400,
    "kernel.attn_busy_share": 100 * 1.5e-3 / 2.66e-3,
    "device.idle_share": 46.8,
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader(reduced, metric):
    assert reader(metric)(_ctx(reduced)) == pytest.approx(
        EXPECTED[metric])


def test_the_roofline_reader_counts_a_floor_of_the_work(reduced):
    ctx = _ctx(reduced)
    # The flush of 4 at 3.0 s spreads over 1.0-3.0 s: tokens at 1.5,
    # 2.0, 2.5 and 3.0, of which three fall in [0.5, 2.6), at contexts
    # 1001-1003 (the row's first token came from its prefill and is no
    # decode). Prefilled through ragged joins: 400.
    assert kernel_cost.decoded_in(ctx["rows"][:1], 0.5, 2.6) == [
        1001, 1002, 1003]
    ctx["rows"] = ctx["rows"][:1]
    layers = ctx["config"]["num_hidden_layers"]
    per_token = 2 * 8 * 128 * 2 * layers        # K and V, 8 heads, bf16
    assert kernel_cost.kv_bytes_per_token(ctx["config"]) == per_token
    floor_s = (3006 + 400) * per_token / 819e9  # memory-bound
    got = reader("kernel.attn_roofline")(ctx)
    assert got == pytest.approx(100 * floor_s / 1.5e-3)
    work = kernel_cost.decode_floor(ctx["config"], [1001, 1002])
    assert kernel_cost.least_seconds(work, ctx["peaks"])["bound"] == \
        "memory"
    assert kernel_cost.least_seconds(
        {"bytes": 1.0, "flops": 1e6}, ctx["peaks"])["bound"] == "compute"


@pytest.mark.parametrize("metric", [
    "step.decode_ms_per_token", "step.prefill_ms_per_ktok",
    "kernel.attn_busy_share", "kernel.attn_roofline",
    "device.idle_share"])
def test_a_device_reader_with_no_trace_returns_nothing(reduced, metric):
    ctx = dict(_ctx(reduced), trace={}, slice=None)
    assert reader(metric)(ctx) is None


def test_every_per_layer_metric_of_the_manifest_has_a_reader():
    manifest = mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))
    for m in manifest["per_layer"]:
        assert callable(reader(m["name"]))
