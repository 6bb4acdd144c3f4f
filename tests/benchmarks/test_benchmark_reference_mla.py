"""The `axk1` plain reference and what is measured against it: the
reference (expanded attention, no cache) against the program's own
forward at tiny sizes on the CPU (as test_benchmark_reference.py holds
the GQA reference), causality, the comparison that decides `correct` on
it, the three readers of the latent cell on traces made by hand, and
harness/mla_cost.py checked by hand on one decode step and one kernel
call."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths
from configs import mla_moe_reference as ref
from harness import correct, kernel_cost, mla_cost
from theroundtaible_tpu.utils import telemetry

CELL = os.path.join(bench_paths.BENCH, "configs", "a.x-k1-ep16.json")
TINY = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "rms_norm_eps": 1e-6, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "kv_lora_rank": 32, "rope_theta": 10000.0,
    "rope_scaling": {"type": "yarn", "factor": 32.0,
                     "original_max_position_embeddings": 64.0,
                     "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 1.0,
                     "mscale_all_dim": 1.0},
    "num_experts_per_tok": 2, "routed_scaling_factor": 2.5,
    "n_routed_experts": 8}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def tiny():
    from theroundtaible_tpu.engine.models.common import init_params
    from theroundtaible_tpu.engine.models.registry import get_model_config
    cfg = get_model_config("tiny-axk1")
    return cfg, init_params(cfg, jax.random.PRNGKey(3), jnp.float32)


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
    cfg, params = tiny
    tokens = np.random.RandomState(1).randint(3, 500, size=(length,))
    got = np.asarray(ref.logits_at(params, TINY, tokens, [length - 1, 40]))
    # Float32 both ways, sums in another order (a head at a time
    # against all heads at once, one expert at a time against a masked
    # loop): agreement to 1e-4 of a logit whose spread is about 1.
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


def test_position_matters_to_the_reference(tiny):
    """The rotary part is live: the same tokens one place later give
    other logits (a reference without R would pass every other test
    here against a program without it)."""
    _cfg, params = tiny
    a = np.arange(3, 43)
    shifted = np.concatenate([[7], a])
    la = np.asarray(ref.logits_at(params, TINY, a, [39]))
    lb = np.asarray(ref.logits_at(params, TINY, shifted, [40]))
    assert np.abs(la - lb).max() > 1e-3


def test_the_share_is_read_from_the_published_keys(cell):
    sizes = ref.sizes_of(cell)
    assert (sizes["held"], sizes["published"], sizes["offset"]) \
        == (12, 192, 0)
    assert (sizes["blocks"], sizes["dense_blocks"], sizes["top_k"]) \
        == (6, 1, 8)
    assert (sizes["nope"], sizes["rope"], sizes["rank"]) == (128, 64, 512)
    assert ref.softmax_scale(sizes) == pytest.approx(0.130861, rel=1e-5)


def test_correct_is_decided_on_the_latent_reference(tiny):
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


# --- the cost, by hand -------------------------------------------------------


def test_the_cost_of_one_decode_step_by_hand(cell):
    """15 rows at context 3000, 6 of the 12 held experts hit in each of
    the 5 expert layers — counted here on paper, from the published
    sizes."""
    e, bf16 = 7168, 2
    attn = (e * 1536 + 1536 + 1536 * 64 * 192 + e * 576 + 512
            + 512 * 64 * 256 + 64 * 128 * e + e)
    dense = 3 * e * 18432 + e
    expert = 3 * e * 2048
    expert_fixed = expert + e * 192 + e
    fixed = (6 * attn + dense + 5 * expert_fixed + 20480 * e + e) * bf16
    assert mla_cost.attention_params(cell) == attn == 101_131_264
    assert mla_cost.dense_mlp_params(cell) == dense == 396_368_896
    assert mla_cost.expert_params(cell) == expert == 44_040_192
    assert mla_cost.expert_layer_fixed_params(cell) == expert_fixed
    assert mla_cost.fixed_step_bytes(cell) == fixed == 2_754_164_736
    assert mla_cost.latent_bytes_per_position(cell) == 1152 * 6
    work = mla_cost.decode_floor(cell, steps=1, experts_hit=5 * 6,
                                 row_steps=15, context_positions=15 * 3000)
    by_hand = fixed + 30 * expert * bf16 + 15 * 3000 * 1152 * 6
    assert work["bytes"] == by_hand
    # 5.7 GB a step, 7.0 ms at 819 GB/s: the two new layer kinds
    # (attention weights and latents, experts) are over three quarters.
    assert 5.6e9 < by_hand < 5.8e9
    assert (6 * attn * bf16 + 15 * 3000 * 1152 * 6
            + 5 * expert_fixed * bf16 + 30 * expert * bf16) \
        > 0.75 * by_hand
    assert work["flops"] / 197e12 < work["bytes"] / 819e9    # memory-bound


def test_the_cost_of_the_decode_kernel_by_hand(cell):
    """One token at context 3000: 3000 x 1152 B and 2 x 64 x 1088 x
    3000 operations a layer, 121 operations a byte — under the v5e's
    ridge of 240, so the bytes bound it."""
    work = mla_cost.decode_kernel_floor(cell, [3000])
    assert work["bytes"] == 3000 * 1152 * 6
    assert work["flops"] == 3000 * 2 * 64 * (576 + 512) * 6
    assert work["flops"] / work["bytes"] == pytest.approx(120.9, abs=0.1)
    least = kernel_cost.least_seconds(work, PEAKS)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(3000 * 1152 * 6 / 819e9)
    assert mla_cost.pool_operand(cell) == "[640,128,640]"
    assert kernel_cost.pool_operand(cell) == "[640,128,64,112]"  # no kernel


# --- the readers -----------------------------------------------------------

SLICE = {"start": 10.0, "end": 16.0}
POOL = "[640,128,640]"
ROWS = [{"sent": 9.0, "prompt_tokens": 2000,
         "flushes": [[10.0, 1], [16.0, 120]]}]


def reader(name):
    path = os.path.join(bench_paths.BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "r_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def segment(t0, kind, steps, rows, hit):
    n_e = 5
    return {"rung": "segment", "t0": t0, "dur_s": 0.5, "trace_id": "s",
            "attrs": {"kind": kind, "steps": steps,
                      "decode_tokens": rows * steps, "experts_hit": hit,
                      "local_assignments": rows * steps * n_e // 2,
                      "expert_layer_steps": steps * n_e,
                      "latent_positions": rows * steps * 3000}}


SPANS = [
    segment(10.2, "plain", 64, 15, 64 * 5 * 6),
    segment(11.5, "ragged", 1, 17, 5 * 12),
    segment(13.0, "plain", 64, 10, 64 * 5 * 5),
]
OPS = {
    f"%mla_paged_decode [pallas s32[16,64] s32[16] bf16[16,64,640] "
    f"bf16{POOL}]": 0.9,
    f"%mla_ragged [pallas s32[17,64] bf16[1,64,1088,640] bf16{POOL}]": 0.5,
    f"%mla_paged_prefill [pallas bf16{POOL}]": 0.1,
    "%fusion.7": 3.0,
    "%paged_decode_attention [pallas bf16[640,128,8,128]]": 9.0,
}


@pytest.fixture
def buffered(monkeypatch):
    monkeypatch.setattr(
        telemetry, "spans_between",
        lambda a, b: [r for r in SPANS if a <= r["t0"] < b])
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)


def ctx(cell, **over):
    return dict({"slice": dict(SLICE), "config": cell, "rows": ROWS,
                 "peaks": PEAKS,
                 "names": {"programs": {"decode": ["jit_decode_loop"]}},
                 "trace": {"op_seconds": OPS, "busy_s": 5.0, "devices": 1,
                           "module_seconds": {
                               "jit_decode_loop_hybrid(7)": 3.2,
                               "jit_ragged_step_hybrid(9)": 0.8}}},
                **over)


def test_mla_busy_share_on_a_hand_made_trace(cell):
    # The three latent kernels, not the GQA kernel of another pool.
    assert reader("kernel.mla_busy_share")(ctx(cell)) \
        == pytest.approx(100.0 * 1.5 / 5.0)


def test_mla_roofline_on_a_hand_made_trace(cell):
    """One row decoding tokens 2 to 120 of its answer in the slice at a
    prompt of 2000: its contexts' latent entries at 1152 B a layer over
    the decode kernel's seconds alone."""
    contexts = kernel_cost.decoded_in(ROWS, SLICE["start"], SLICE["end"])
    assert len(contexts) == 119
    want = 100.0 * sum(contexts) * 1152 * 6 / 819e9 / 0.9
    c = ctx(cell)
    assert reader("kernel.mla_roofline")(c) == pytest.approx(want)
    c["trace"]["op_seconds"] = {k: v / 1000 for k, v in OPS.items()}
    with pytest.raises(RuntimeError, match="mla_roofline"):
        reader("kernel.mla_roofline")(c)


def test_decode_roofline_on_a_hand_made_slice(cell, buffered):
    c = ctx(cell)
    steps, row_steps = 128, 64 * 15 + 64 * 10
    hit = int((64 * 5 * 6 + 5 * 12 + 64 * 5 * 5) / 645 * steps * 5)
    contexts = kernel_cost.decoded_in(ROWS, SLICE["start"], SLICE["end"])
    mean_context = sum(contexts) / len(contexts)
    work = mla_cost.decode_floor(
        cell, steps=steps, experts_hit=hit, row_steps=row_steps,
        context_positions=int(mean_context * row_steps))
    want = 100.0 * work["bytes"] / 819e9 / 3.2
    assert reader("step.decode_roofline.mla")(c) == pytest.approx(want)
    assert 15.0 < want < 100.0
    c["trace"]["module_seconds"]["jit_decode_loop_hybrid(7)"] = 0.2
    with pytest.raises(RuntimeError, match="decode_roofline.mla"):
        reader("step.decode_roofline.mla")(c)


@pytest.mark.parametrize("name", [
    "kernel.mla_roofline", "kernel.mla_busy_share",
    "step.decode_roofline.mla"])
def test_nothing_to_read_gives_nothing(monkeypatch, cell, name):
    """A program without these kernels and spans (the parent), a run
    without a slice, a buffer that overflowed, another model's cell:
    the reader returns None and does not raise."""
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)
    monkeypatch.setattr(telemetry, "spans_between", lambda a, b: [])
    bare = ctx(cell, trace={"op_seconds": {"%fusion.7": 3.0},
                            "busy_s": 5.0, "devices": 1,
                            "module_seconds": {"jit_decode_loop(1)": 1.0}})
    assert reader(name)(bare) is None
    assert reader(name)(ctx(cell, trace={})) is None
    if name != "kernel.mla_busy_share":     # (it needs the trace alone)
        assert reader(name)(ctx(cell, slice=None)) is None
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 3)
    assert reader(name)(bare) is None
    monkeypatch.delattr(telemetry, "spans_between")
    assert reader(name)(bare) is None
    mistral = ctx({"engine": {}, "hidden_size": 4096})
    assert reader(name)(mistral) is None
    nemotron = ctx({"engine": {}, "hybrid_override_pattern": "ME*"})
    assert reader(name)(nemotron) is None
