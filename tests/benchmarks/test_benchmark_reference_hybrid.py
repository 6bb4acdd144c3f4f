"""The `nemotron_h` plain reference and what is measured against it:
the reference against the program's own forward at tiny sizes on the
CPU (as test_benchmark_reference.py holds the GQA reference), causality
of every layer kind, the comparison that decides `correct` on it, the
four readers of the hybrid cell on span lists made by hand, and
harness/hybrid_cost.py checked by hand on one decode step."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths
from configs import nemotron_h_reference as ref
from harness import correct, hybrid_cost, kernel_cost
from theroundtaible_tpu.utils import telemetry

CELL = os.path.join(bench_paths.BENCH, "configs",
                    "nemotron-3-nano-ep2.json")
TINY = {"hybrid_override_pattern": "ME*ME", "norm_eps": 1e-5,
        "mamba_num_heads": 4, "mamba_head_dim": 16, "ssm_state_size": 16,
        "n_groups": 2, "conv_kernel": 4, "num_experts_per_tok": 2,
        "routed_scaling_factor": 2.5, "n_routed_experts": 8}


@pytest.fixture(scope="module")
def tiny():
    from theroundtaible_tpu.engine.models.common import init_params
    from theroundtaible_tpu.engine.models.registry import get_model_config
    cfg = get_model_config("tiny-nemotron-h")
    return cfg, init_params(cfg, jax.random.PRNGKey(3), jnp.float32)


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
    cfg, params = tiny
    tokens = np.random.RandomState(1).randint(3, 500, size=(length,))
    got = np.asarray(ref.logits_at(params, TINY, tokens, [length - 1, 40]))
    # Float32 both ways, sums in another order (a recurrence against a
    # chunked scan, one expert at a time against a masked loop):
    # agreement to 1e-4 of a logit whose spread is about 1.
    assert np.abs(got[0] - _program_logits(params, cfg, tokens,
                                           length - 1)).max() < 1e-4
    assert np.abs(got[1] - _program_logits(params, cfg, tokens[:41],
                                           40)).max() < 1e-4


def test_what_follows_a_row_never_reaches_it(tiny):
    _cfg, params = tiny
    a = np.arange(3, 67)
    b = np.concatenate([a[:32], np.full((32,), 9)])
    la = ref.logits_at(params, TINY, a, [31])
    lb = ref.logits_at(params, TINY, b, [31])
    assert np.allclose(np.asarray(la), np.asarray(lb), atol=1e-5)


def test_the_share_is_read_from_the_published_keys():
    with open(CELL, encoding="utf-8") as f:
        config = json.load(f)
    sizes = ref.sizes_of(config)
    assert (sizes["held"], sizes["published"], sizes["offset"]) \
        == (64, 128, 0)
    assert sizes["pattern"] == "MEMEM*EMEMEM*" and sizes["top_k"] == 6


def test_correct_is_decided_on_the_hybrid_reference(tiny):
    """The harness's comparison, with this reference: the tokens the
    reference itself prefers are right, another token is not."""
    _cfg, params = tiny
    prompt = [int(t) for t in
              np.random.RandomState(2).randint(3, 250, size=(40,))]
    logits = np.asarray(ref.logits_at(params, TINY, np.asarray(prompt),
                                      [39]))
    best = int(logits[0].argmax())
    worst = int(logits[0].argmin())
    good = correct.score(ref, params, TINY, [
        {"what": "first-token-0", "prompt": prompt, "ids": [best]}])
    bad = correct.score(ref, params, TINY, [
        {"what": "first-token-0", "prompt": prompt, "ids": [worst]}])
    assert good["correct"] and good["worst_gap_sigmas"] == 0.0
    assert not bad["correct"] and bad["worst_gap_sigmas"] > 2.0


# --- the readers -----------------------------------------------------------

SLICE = {"start": 10.0, "end": 16.0}


def reader(name):
    path = os.path.join(bench_paths.BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "r_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def admit(t0, prompt, matched, reused, **more):
    return {"rung": "admit", "t0": t0, "dur_s": 0.1, "trace_id": "r",
            "attrs": dict({"prompt_tokens": prompt,
                           "kv_matched_tokens": matched,
                           "state_reused_tokens": reused}, **more)}


def segment(t0, kind, steps, rows, hit, snap_bytes):
    n_e = 5
    return {"rung": "segment", "t0": t0, "dur_s": 0.5, "trace_id": "s",
            "attrs": {"kind": kind, "steps": steps,
                      "decode_tokens": rows * steps, "experts_hit": hit,
                      "local_assignments": 3 * rows * steps * n_e,
                      "expert_layer_steps": steps * n_e,
                      "snapshots_taken": 1, "snapshot_bytes": snap_bytes}}


SPANS = [
    admit(9.0, 9000, 9000, 0),                   # before the slice
    admit(10.5, 3000, 2800, 2800),               # continued: no re-scan
    admit(12.0, 3000, 2800, 2560),               # snapshot 240 back
    admit(14.0, 4000, 3500, 0),                  # pages, no state
    {"rung": "admit", "t0": 15.0, "dur_s": 0.1, "trace_id": "r",
     "attrs": {"sync_s": 0.0}},                  # a model without state
    segment(10.2, "plain", 64, 15, 64 * 5 * 40, 2_000_000_000),
    segment(11.5, "ragged", 1, 17, 5 * 64, 2_600_000_000),
    segment(13.0, "plain", 64, 10, 64 * 5 * 30, 2_340_000_000),
]


@pytest.fixture
def buffered(monkeypatch):
    monkeypatch.setattr(
        telemetry, "spans_between",
        lambda a, b: [r for r in SPANS if a <= r["t0"] < b])
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)


def ctx(**over):
    with open(CELL, encoding="utf-8") as f:
        config = json.load(f)
    return dict({"slice": dict(SLICE), "trace": {}, "config": config},
                **over)


def test_rescan_share_on_a_hand_made_span_list(buffered):
    # (0 + 240 + 3500) of 10000 prompt tokens had pages and no state.
    assert reader("state.rescan_share")(ctx()) == pytest.approx(37.4)


def test_snapshot_peak_share_on_a_hand_made_span_list(buffered):
    assert reader("state.snapshot_peak_share")(ctx()) \
        == pytest.approx(100.0)


def test_experts_hit_share_on_a_hand_made_span_list(buffered):
    # (64*5*40 + 5*64 + 64*5*30) hits of 64 held x (320 + 5 + 320)
    # expert-layer steps.
    hits = 64 * 5 * 40 + 5 * 64 + 64 * 5 * 30
    assert reader("moe.experts_hit_share")(ctx()) \
        == pytest.approx(100.0 * hits / (64 * 645))


@pytest.mark.parametrize("name", [
    "state.rescan_share", "state.snapshot_peak_share",
    "moe.experts_hit_share", "step.decode_roofline",
    "kernel.attn_roofline.hybrid"])
def test_nothing_to_read_gives_nothing(monkeypatch, name):
    """A program without these spans (the parent, a model without
    recurrent state), a run without a slice, a buffer that overflowed:
    the reader returns None and does not raise."""
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)
    monkeypatch.setattr(
        telemetry, "spans_between",
        lambda a, b: [r for r in SPANS if "sync_s" in r["attrs"]])
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    full = ctx(trace={"module_seconds": {"jit_decode_loop(1)": 1.0},
                      "op_seconds": {}},
               rows=[], peaks=peaks,
               names={"programs": {"decode": ["jit_decode_loop"]}})
    assert reader(name)(full) is None
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 3)
    assert reader(name)(full) is None
    monkeypatch.delattr(telemetry, "spans_between")
    assert reader(name)(full) is None
    mistral = dict(full, config={"engine": {}, "hidden_size": 4096})
    assert reader(name)(mistral) is None


def test_decode_roofline_on_a_hand_made_slice(buffered):
    rows = [{"sent": 9.0, "prompt_tokens": 2000,
             "flushes": [[10.0, 1], [16.0, 120]]}]
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    c = ctx(trace={"module_seconds": {"jit_decode_loop_hybrid(7)": 1.6,
                                      "jit_ragged_step_hybrid(9)": 0.4}},
            rows=rows, peaks=peaks,
            names={"programs": {"decode": ["jit_decode_loop"]}})
    steps, row_steps = 128, 64 * 15 + 64 * 10
    # Experts hit a layer-step over ALL the slice's spans (the join's
    # 64 a layer among them), times the plain segments' layer-steps.
    hit = int((64 * 5 * 40 + 5 * 64 + 64 * 5 * 30) / 645 * steps * 5)
    contexts = kernel_cost.decoded_in(rows, SLICE["start"], SLICE["end"])
    mean_context = sum(contexts) / len(contexts)    # about 2060
    work = hybrid_cost.decode_floor(
        c["config"], steps=steps, experts_hit=hit, row_steps=row_steps,
        context_positions=int(mean_context * row_steps))
    want = 100.0 * work["bytes"] / 819e9 / 1.6
    assert reader("step.decode_roofline")(c) == pytest.approx(want)
    assert 30.0 < want < 100.0
    # Half the device time for the same work: over 100 is an error.
    c["trace"]["module_seconds"]["jit_decode_loop_hybrid(7)"] = 0.5
    with pytest.raises(RuntimeError, match="decode_roofline"):
        reader("step.decode_roofline")(c)


def test_attention_roofline_of_a_hybrid_cell_by_hand():
    """One row decoding tokens 2 to 120 of its answer in the slice at a
    prompt of 2000, 300 prompt tokens joined there: keys and values of
    the 2 attention layers alone, 2 KiB a position."""
    rows = [{"sent": 9.0, "prompt_tokens": 2000,
             "flushes": [[10.0, 1], [16.0, 120]]}]
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    sl = dict(SLICE, counters_start={"scheduler": {
        "segment_prefill_tokens": 1000}}, counters_end={"scheduler": {
            "segment_prefill_tokens": 1300}})
    pool = "[640,128,2,128]"
    c = ctx(slice=sl, rows=rows, peaks=peaks, trace={"op_seconds": {
        f"%paged_decode_attention [pallas bf16{pool}]": 0.004,
        f"%ragged_paged_attention [pallas bf16{pool}]": 0.001,
        "%fusion.7": 3.0}})
    positions = sum(kernel_cost.decoded_in(rows, sl["start"], sl["end"]))
    want = 100.0 * (positions + 300) * 2048 / 819e9 / 0.005
    got = reader("kernel.attn_roofline.hybrid")(c)
    assert got == pytest.approx(want) and 1.0 < got < 100.0
    # The accepted cost would count all 13 layers: 6.5 times the share.
    assert kernel_cost.kv_bytes_per_token(c["config"]) == 6.5 * 2048
    c["trace"]["op_seconds"] = {k: v / 100 for k, v in
                                c["trace"]["op_seconds"].items()}
    with pytest.raises(RuntimeError, match="attn_roofline"):
        reader("kernel.attn_roofline.hybrid")(c)


def test_the_cost_of_one_decode_step_by_hand():
    """16 rows at context 3000, 34 experts hit in each of the 5 expert
    layers — counted here on paper, from the published sizes."""
    with open(CELL, encoding="utf-8") as f:
        config = json.load(f)
    e, bf16 = 2688, 2
    mamba = (e * 10304 + 4096 * e + 5 * 6144 + 3 * 64 + 4096 + e)
    attn = e * 128 * (32 + 2) * 2 + e
    expert_fixed = 2 * e * 3712 + (e + 1) * 128 + e
    fixed = (6 * mamba + 2 * attn + 5 * expert_fixed + 65536 * e + e) * bf16
    assert hybrid_cost.mamba2_params(config) == mamba == 38_744_896
    assert hybrid_cost.attention_params(config) == attn
    assert hybrid_cost.fixed_step_bytes(config) == fixed
    state = 6 * (64 * 64 * 128 + 3 * 6144) * 4
    assert hybrid_cost.state_bytes_per_sequence(config) == state \
        == 13_025_280
    assert hybrid_cost.kv_bytes_per_position(config) == 2 * 2 * 128 * 2 * 2
    work = hybrid_cost.decode_floor(
        config, steps=1, experts_hit=5 * 34, row_steps=16,
        context_positions=16 * 3000)
    by_hand = (fixed + 5 * 34 * 2 * e * 1856 * bf16 + 16 * 2 * state
               + 16 * 3000 * 2048)
    assert work["bytes"] == by_hand
    # 5.0 GB a step, 6.2 ms at 819 GB/s: the experts hit are two thirds.
    assert 4.9e9 < by_hand < 5.2e9
    assert work["flops"] / 197e12 < work["bytes"] / 819e9    # memory-bound
