"""The `laguna` plain reference and what is measured against it: the
reference (a dense mask with the window, a head at a time, no cache)
against the program's own whole forward at tiny sizes on the CPU,
causality, position and the window live in it, the comparison that
decides `correct` on it, the three readers of the window cell on traces
and spans made by hand, and harness/window_cost.py checked by hand on
one decode step and one kernel call. (The program through pages,
against this reference: tests/test_laguna_model.py.)"""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths
from configs import laguna_reference as ref
from harness import correct, kernel_cost, window_cost
from theroundtaible_tpu.utils import telemetry

CELL = os.path.join(bench_paths.BENCH, "configs", "laguna-xs.2-d5.json")
TINY_FILE = os.path.join(bench_paths.REPO, "tests", "benchmarks",
                         "rehearsal_laguna", "configs",
                         "tiny-laguna-cpu.json")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's configuration (full, sliding, sliding; window 64)
    and seeded weights."""
    from theroundtaible_tpu.engine.models.common import init_params
    from theroundtaible_tpu.engine.models.registry import (
        resolve_model_config)
    with open(TINY_FILE, encoding="utf-8") as f:
        config = json.load(f)
    cfg = resolve_model_config(dict(config["engine"], model="t"))
    return cfg, init_params(cfg, jax.random.PRNGKey(3), jnp.float32), \
        config


@pytest.fixture(scope="module")
def cell():
    with open(CELL, encoding="utf-8") as f:
        return json.load(f)


def _program_logits(params, cfg, tokens, row):
    from theroundtaible_tpu.engine.models.common import forward
    t = len(tokens)
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(
            params, cfg, jnp.asarray(tokens)[None], jnp.arange(t)[None],
            None, None, jnp.asarray([t]), last_pos=jnp.asarray([row]))
    return np.asarray(logits[0, 0], np.float32)


@pytest.mark.parametrize("length", [96, 200])
def test_reference_gives_the_programs_logits(tiny, length):
    cfg, params, config = tiny
    tokens = np.random.RandomState(1).randint(3, 500, size=(length,))
    got = np.asarray(ref.logits_at(params, config, tokens,
                                   [length - 1, 40]))
    # Float32 both ways, sums in another order (a head at a time
    # against all heads at once, an expert at a time against a masked
    # loop): agreement to 1e-4 of a logit whose spread is about 1.
    assert np.abs(got[0] - _program_logits(params, cfg, tokens,
                                           length - 1)).max() < 1e-4
    assert np.abs(got[1] - _program_logits(params, cfg, tokens[:41],
                                           40)).max() < 1e-4


def test_what_follows_a_row_never_reaches_it(tiny):
    _cfg, params, config = tiny
    a = np.arange(3, 67)
    b = np.concatenate([a[:32], np.full((32,), 9)])
    la = ref.logits_at(params, config, a, [31])
    lb = ref.logits_at(params, config, b, [31])
    assert np.allclose(np.asarray(la), np.asarray(lb), atol=1e-5)


def test_position_and_the_window_matter_to_the_reference(tiny):
    """Both rotary tables are live (the same tokens one place later give
    other logits), and so is the window: a token 194 places back reaches
    the last row through the full layer only, and the reference with
    every layer sliding does not see it."""
    _cfg, params, config = tiny
    a = np.arange(3, 63)
    shifted = np.concatenate([[7], a])
    la = np.asarray(ref.logits_at(params, config, a, [59]))
    lb = np.asarray(ref.logits_at(params, config, shifted, [60]))
    assert np.abs(la - lb).max() > 1e-3
    # (three stacked windows of 64 reach 189 places back: 194 is past)
    long = np.arange(3, 203)
    early = long.copy()
    early[5] = 400
    assert np.abs(
        np.asarray(ref.logits_at(params, config, early, [199]))
        - np.asarray(ref.logits_at(params, config, long, [199]))
    ).max() > 1e-4
    blind = dict(config, layer_types=["sliding_attention"] * 3)
    assert np.allclose(
        np.asarray(ref.logits_at(params, blind, early, [199])),
        np.asarray(ref.logits_at(params, blind, long, [199])), atol=1e-6)


def test_the_sizes_are_read_from_the_published_keys(cell):
    sizes = ref.sizes_of(cell)
    assert (sizes["held"], sizes["top_k"], sizes["scale"]) == (256, 8, 2.5)
    assert (sizes["blocks"], sizes["kv_heads"], sizes["head_dim"],
            sizes["window"]) == (5, 8, 128, 512)
    assert sizes["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    freqs, mult = ref.rotary_table(
        sizes["rotary"]["full_attention"], 128)
    assert freqs.shape == (32,) and mult == 1.4158883083359672
    # the fastest dimension keeps theta's own frequency, the slowest is
    # divided by the factor
    assert freqs[0] == 1.0
    assert freqs[-1] == pytest.approx(500000.0 ** (-62 / 64) / 64,
                                      rel=1e-5)
    plain, one = ref.rotary_table(
        sizes["rotary"]["sliding_attention"], 128)
    assert plain.shape == (64,) and one == 1.0


def test_correct_is_decided_on_this_reference(tiny):
    _cfg, params, config = tiny
    prompt = [int(t) for t in
              np.random.RandomState(2).randint(3, 250, size=(40,))]
    logits = np.asarray(ref.logits_at(params, config, np.asarray(prompt),
                                      [39]))
    best, worst = int(logits[0].argmax()), int(logits[0].argmin())
    good = correct.score(ref, params, config, [
        {"what": "first-token-0", "prompt": prompt, "ids": [best]}])
    bad = correct.score(ref, params, config, [
        {"what": "first-token-0", "prompt": prompt, "ids": [worst]}])
    assert good["correct"] and good["worst_gap_sigmas"] == 0.0
    assert not bad["correct"] and bad["worst_gap_sigmas"] > 2.0


# --- the cost, by hand -------------------------------------------------------


def test_the_window_span_by_hand():
    assert window_cost.window_span(300, 512, 128) == 300
    assert window_cost.window_span(300, None, 128) == 300
    assert window_cost.window_span(512, 512, 128) == 512
    # the window of a token at 3000 starts at 2488, 56 into page 19
    assert window_cost.window_span(3000, 512, 128) == 512 + 56
    assert window_cost.window_span(3000, None, 128) == 3000
    assert window_cost.window_span(640, 512, 128) == 512     # page start


def test_the_cost_of_the_kernels_by_hand(cell):
    """One token at context 3000: the two full layers read 3000
    positions of 4096 B, the three sliding layers 568; 4 x heads x 128
    operations a position in the causal window."""
    assert window_cost.is_laguna(cell)
    assert window_cost.attention_layers(cell) == [
        (48, None), (64, 512), (64, 512), (64, 512), (48, None)]
    assert window_cost.class_counts(cell) == (2, 3)
    assert window_cost.kv_bytes_per_position_a_layer(cell) == 4096
    work = window_cost.decode_kernel_floor(cell, [3000])
    assert work["bytes"] == 4096 * (2 * 3000 + 3 * 568)
    assert work["flops"] == 4 * 128 * (2 * 48 * 3000 + 3 * 64 * 512)
    least = kernel_cost.least_seconds(work, PEAKS)
    assert least["bound"] == "memory"
    # what the accepted cost would charge: every layer the whole context
    accepted = kernel_cost.decode_floor(cell, [3000])["bytes"]
    assert accepted == 4096 * 5 * 3000
    assert accepted / work["bytes"] == pytest.approx(1.95, abs=0.01)
    assert window_cost.prefill_write_bytes(cell, 10) == 10 * 4096 * 5
    assert kernel_cost.pool_operand(cell) == "[640,128,8,128]"
    assert window_cost.unwindowed_visits(40, cell) == 100


def test_the_cost_of_one_decode_step_by_hand(cell):
    """15 rows at context 3000, 97 of the 256 experts hit in each of the
    4 sparse layers — counted here on paper, from the published sizes."""
    e, d, bf16 = 2048, 128, 2
    attn = {h: 2 * e * h * d + 2 * e * 8 * d + e * h + e for h in (48, 64)}
    dense = 3 * e * 8192 + e
    expert = 3 * e * 512
    sparse_fixed = expert + e * 256 + e
    fixed = (2 * attn[48] + 3 * attn[64] + dense + 4 * sparse_fixed
             + 100352 * e + e) * bf16
    assert window_cost.attention_params(cell, 48) == attn[48] == 29_460_480
    assert window_cost.attention_params(cell, 64) == attn[64] == 37_881_856
    assert window_cost.dense_mlp_params(cell) == dense
    assert window_cost.expert_params(cell) == expert == 3_145_728
    assert window_cost.sparse_layer_fixed_params(cell) == sparse_fixed
    assert window_cost.fixed_step_bytes(cell) == fixed
    assert 0.88e9 < fixed < 0.90e9
    work = window_cost.decode_floor(
        cell, steps=1, experts_hit=4 * 97, row_steps=15,
        context_lengths=[3000] * 15)
    kv = 15 * 4096 * (2 * 3000 + 3 * 568)
    by_hand = fixed + 4 * 97 * expert * bf16 + kv
    assert work["bytes"] == by_hand
    # 3.8 GB a step, 4.6 ms at 819 GB/s: the routed experts are two
    # thirds of it, attention's keys and values a seventh of a window-
    # less model's 0.92 GB
    assert 3.7e9 < by_hand < 3.9e9
    assert 0.6 < 4 * 97 * expert * bf16 / by_hand < 0.7
    assert kv == pytest.approx(0.47e9, rel=0.02)
    assert work["flops"] / 197e12 < work["bytes"] / 819e9    # memory-bound
    # another slice's rows scale by their mean: 30 row-steps, the same
    # contexts
    twice = window_cost.decode_floor(
        cell, steps=1, experts_hit=4 * 97, row_steps=30,
        context_lengths=[3000] * 15)
    assert twice["bytes"] == by_hand + kv


# --- the readers -----------------------------------------------------------

SLICE = {"start": 10.0, "end": 16.0}
POOL = "[640,128,8,128]"
ROWS = [{"sent": 9.0, "prompt_tokens": 2000,
         "flushes": [[10.0, 1], [16.0, 120]]}]


def reader(name):
    path = os.path.join(bench_paths.BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "r_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def segment(t0, kind, steps, rows, hit, full, window):
    n_e = 4
    return {"rung": "segment", "t0": t0, "dur_s": 0.5, "trace_id": "s",
            "attrs": {"kind": kind, "steps": steps,
                      "decode_tokens": rows * steps, "experts_hit": hit,
                      "local_assignments": rows * steps * n_e * 8,
                      "expert_layer_steps": steps * n_e,
                      "page_visits_full": full,
                      "page_visits_window": window}}


SPANS = [
    # 15 rows x 64 steps at ~24 pages: 2 full layers x 24, 3 window x 5
    segment(10.2, "plain", 64, 15, 64 * 4 * 97, 64 * 15 * 2 * 24,
            64 * 15 * 3 * 5),
    segment(11.5, "ragged", 1, 17, 4 * 256, 2 * 60, 3 * 20),
    segment(13.0, "plain", 64, 10, 64 * 4 * 80, 64 * 10 * 2 * 24,
            64 * 10 * 3 * 5),
]
OPS = {
    f"%paged_decode_attention [pallas s32[16,64] s32[16] bf16[16,48,128] "
    f"bf16{POOL} bf16{POOL}]": 0.10,
    f"%paged_decode_attention [pallas s32[16,64] s32[16] bf16[16,64,128] "
    f"bf16{POOL} bf16{POOL}]": 0.06,
    f"%ragged_paged_attention [pallas bf16[8,6,1024,128] bf16{POOL} "
    f"bf16{POOL}]": 0.04,
    "%fusion.7": 3.0,
    "%mla_paged_decode [pallas bf16[640,128,640]]": 9.0,
}


@pytest.fixture
def buffered(monkeypatch):
    monkeypatch.setattr(
        telemetry, "spans_between",
        lambda a, b: [r for r in SPANS if a <= r["t0"] < b])
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)


def ctx(cell, **over):
    return dict({"slice": dict(SLICE, counters_start={"scheduler": {
        "segment_prefill_tokens": 1000}}, counters_end={"scheduler": {
            "segment_prefill_tokens": 1400}}),
                 "config": cell, "rows": ROWS, "peaks": PEAKS,
                 "names": {"programs": {"decode": ["jit_decode_loop"]}},
                 "trace": {"op_seconds": OPS, "busy_s": 5.0, "devices": 1,
                           "module_seconds": {
                               "jit_decode_loop_hybrid(7)": 1.6,
                               "jit_ragged_step_hybrid(9)": 0.8}}},
                **over)


def test_window_roofline_on_a_hand_made_trace(cell):
    """One row decoding tokens 2 to 120 of its answer in the slice at a
    prompt of 2000, and 400 prompt tokens joined: its contexts by layer
    class, over BOTH lowerings of the decode walk and the join kernel,
    not the latent kernel of another pool."""
    contexts = kernel_cost.decoded_in(ROWS, SLICE["start"], SLICE["end"])
    assert len(contexts) == 119
    reads = sum(2 * c + 3 * window_cost.window_span(c, 512, 128)
                for c in contexts)
    want = 100.0 * (reads + 400 * 5) * 4096 / 819e9 / 0.20
    c = ctx(cell)
    assert reader("kernel.attn_roofline.window")(c) == pytest.approx(want)
    assert 1.0 < want < 100.0
    # the accepted reader on the same trace counts 5 x the context
    accepted = reader("kernel.attn_roofline")(c)
    assert accepted == pytest.approx(
        100.0 * (sum(contexts) * 5 + 400 * 5) * 4096 / 819e9 / 0.20)
    assert accepted > 1.6 * want
    c["trace"]["op_seconds"] = {k: v / 1000 for k, v in OPS.items()}
    with pytest.raises(RuntimeError, match="attn_roofline.window"):
        reader("kernel.attn_roofline.window")(c)


def test_window_skip_share_on_hand_made_spans(cell, buffered):
    full = 64 * 15 * 48 + 120 + 64 * 10 * 48
    window = 64 * 15 * 15 + 60 + 64 * 10 * 15
    want = 100.0 * (1 - (full + window) / (full * 5 / 2))
    got = reader("kv.window_skip_share")(ctx(cell))
    assert got == pytest.approx(want)
    # 24 pages against 5 on three layers of five: 47.5 % of a decode
    # step's visits
    assert got == pytest.approx(47.5, abs=0.2)


def test_decode_roofline_on_a_hand_made_slice(cell, buffered):
    c = ctx(cell)
    steps, row_steps = 128, 64 * 15 + 64 * 10
    hit = int((64 * 4 * 97 + 4 * 256 + 64 * 4 * 80) / (129 * 4)
              * steps * 4)
    contexts = kernel_cost.decoded_in(ROWS, SLICE["start"], SLICE["end"])
    work = window_cost.decode_floor(
        cell, steps=steps, experts_hit=hit, row_steps=row_steps,
        context_lengths=contexts)
    want = 100.0 * work["bytes"] / 819e9 / 1.6
    assert reader("step.decode_roofline.window")(c) == pytest.approx(want)
    assert 15.0 < want < 100.0
    c["trace"]["module_seconds"]["jit_decode_loop_hybrid(7)"] = 0.2
    with pytest.raises(RuntimeError, match="decode_roofline.window"):
        reader("step.decode_roofline.window")(c)


@pytest.mark.parametrize("name", [
    "kernel.attn_roofline.window", "kv.window_skip_share",
    "step.decode_roofline.window"])
def test_nothing_to_read_gives_nothing(monkeypatch, cell, name):
    """A program without these spans (the parent), a run without a
    slice, a buffer that overflowed, another model's cell: the reader
    returns None and does not raise."""
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)
    monkeypatch.setattr(telemetry, "spans_between", lambda a, b: [
        {"rung": "segment", "t0": 11.0, "dur_s": 0.1, "trace_id": "s",
         "attrs": {"kind": "plain", "steps": 0, "decode_tokens": 0}}])
    bare = ctx(cell, trace={"op_seconds": {"%fusion.7": 3.0},
                            "busy_s": 5.0, "devices": 1,
                            "module_seconds": {"jit_decode_loop(1)": 1.0}})
    assert reader(name)(bare) is None
    if name != "kv.window_skip_share":      # (it needs the spans alone)
        assert reader(name)(ctx(cell, trace={})) is None
    # (without a slice the skip share reads the run's totals: none yet)
    monkeypatch.setattr(telemetry.REGISTRY, "counter_total",
                        lambda name: 0.0)
    assert reader(name)(ctx(cell, slice=None)) is None
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 3)
    assert reader(name)(bare) is None
    monkeypatch.delattr(telemetry, "spans_between")
    assert reader(name)(bare) is None
    mistral = ctx({"engine": {}, "hidden_size": 4096})
    assert reader(name)(mistral) is None
    axk1 = ctx({"engine": {}, "kv_lora_rank": 512})
    assert reader(name)(axk1) is None
