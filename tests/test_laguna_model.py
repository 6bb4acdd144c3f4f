"""`laguna` (Laguna-XS.2): attention layers with a geometry of their own
each — full or sliding, 6 or 8 query heads over 2 kv heads here (48 or
64 over 8 published), a rotary table a layer type, a per-head output
gate — beside a dense MLP or routed + shared gated experts, against the
plain reference (benchmarks/configs/laguna_reference.py: a dense mask, a
head at a time, an expert at a time, float32 highest, no cache).

Tolerances, on LOGITS whose spread over the vocabulary is about 1:
float32 program against float32 reference 1e-4 — order of sums alone
(blockwise online softmax against a dense one, the experts' grouped
product against an expert at a time); measured 1e-6. A bfloat16 program reads
0.013 to 0.18 off over these positions and FAILS 1e-4, so computing in
one pass of bfloat16 where float32 is stated is told apart
(`test_a_bfloat16_program_is_told_apart...`).

The three paged kernels run in interpret mode at the PUBLISHED group 6
(48 / 8 heads of 128) with the published 512 window over 128-wide pages
against a dense softmax over the same pages: 2e-5, float32 throughout.
"""
import dataclasses
import math
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from configs import laguna_reference as ref  # noqa: E402

from theroundtaible_tpu.engine.models import common, hybrid  # noqa: E402
from theroundtaible_tpu.engine.models.common import (  # noqa: E402
    AttnLayer, _forward_hybrid_whole, init_params)
from theroundtaible_tpu.engine.models.registry import (  # noqa: E402
    get_model_config, resolve_model_config)
from theroundtaible_tpu.engine.paged_forward import (  # noqa: E402
    forward_paged_hybrid, forward_ragged_hybrid)
from theroundtaible_tpu.engine.pallas import attention as pattn  # noqa: E402
from theroundtaible_tpu.engine.serving_loop import (  # noqa: E402
    RaggedSeq, build_ragged_batch)

PAGE = 8
TOL = 1e-4

# tiny-laguna as a published config.json would state it
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 5,
    "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 512, "attention_bias": False,
    "rms_norm_eps": 1e-6, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 16,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 32, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.2079441541679836,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 32},
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    "moe_apply_router_weight_on_input": False,
    "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
}


def tokens_of(seed, n):
    return [int(t) for t in
            np.random.RandomState(seed).randint(3, 250, size=(n,))]


@pytest.fixture(scope="module")
def tiny():
    cfg = get_model_config("tiny-laguna")
    return cfg, init_params(cfg, jax.random.PRNGKey(3), jnp.float32)


def empty_pools(cfg, pages, dtype=jnp.float32):
    shape = (pages, PAGE, cfg.num_kv_heads, cfg.head_dim)
    return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
            for _ in cfg.attention_layers]


STATE = {"ssm": [], "conv": []}


def prefill(params, cfg, tokens, pools, table, start=0):
    """One prologue chunk, padded to the kernels' 8 rows as the engine's
    buckets pad it (the pads' cells lie past `kv_valid`: never read)."""
    n = len(tokens)
    t = -(-n // 8) * 8
    with jax.default_matmul_precision("highest"):
        logits, pools, *_ = forward_paged_hybrid(
            params, cfg, jnp.asarray(list(tokens) + [0] * (t - n))[None],
            (start + jnp.arange(t))[None], pools, table,
            jnp.asarray([start + n]), STATE, lengths=jnp.asarray([n]))
    return np.asarray(logits[0, :n], np.float32), pools


def decode(params, cfg, token, pos, pools, table):
    with jax.default_matmul_precision("highest"):
        logits, pools, *_ = forward_paged_hybrid(
            params, cfg, jnp.asarray([[token]]), jnp.asarray([[pos]]),
            pools, table, jnp.asarray([pos + 1]), STATE,
            active=jnp.asarray([True]))
    return np.asarray(logits[0, 0], np.float32), pools


def ragged(params, cfg, seqs, pools, t=64, s_max=5):
    """One ragged dispatch; -> (last-token logits a sequence, pools)."""
    b = build_ragged_batch(seqs, t_budget=t, s_max=s_max,
                           pages_per_seq=len(seqs[0].table),
                           scratch_page=0, pad_id=0, page_size=PAGE)
    a = {k: jnp.asarray(v) for k, v in b.items()
         if isinstance(v, np.ndarray)}
    with jax.default_matmul_precision("highest"):
        logits, pools, *_ = forward_ragged_hybrid(
            params, cfg, a["tokens"], a["positions"], pools, a["tables"],
            a["seq_of_block"], a["block_qstart"], a["query_offsets"],
            a["kv_valid"], a["token_pages"], a["token_offs"],
            a["token_seq"], a["last_rows"], STATE,
            jnp.zeros((s_max,), jnp.int32), jnp.zeros((s_max,), jnp.int32))
    return np.asarray(logits, np.float32), pools


def reference(params, tokens, rows, config=PUBLISHED):
    return np.asarray(ref.logits_at(params, config, np.asarray(tokens),
                                    rows))


# --- the rotary tables -------------------------------------------------------


def test_the_yarn_table_against_hand_computed_values():
    """Frequencies over HALF a head (8 of 16 dimensions), blended
    between theta's own and theta's / 8; cos and sin carry the given
    attention factor; program and reference agree with the formula."""
    full = PUBLISHED["rope_parameters"]["full_attention"]
    freqs, mult = ref.rotary_table(full, 16)
    assert freqs.shape == (4,) and mult == 1.2079441541679836
    assert math.isclose(mult, 0.1 * math.log(8) + 1)
    theta, rot = 500000.0, 8

    def turning(rotations):
        return rot * math.log(32 / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low, high = max(math.floor(turning(64)), 0), min(
        math.ceil(turning(1)), rot - 1)
    assert (low, high) == (0, 1)
    want = []
    for j in range(4):
        plain = theta ** (-2 * j / rot)
        ramp = min(max((j - low) / (high - low), 0), 1)
        want.append(plain * (1 - ramp) + plain / 8 * ramp)
    assert np.allclose(freqs, want, rtol=1e-6)
    mine = common.yarn_inv_freq(8, theta, 8.0, 32.0, 64.0, 1.0)
    assert np.allclose(mine, want, rtol=1e-6)
    plain, one = ref.rotary_table(
        PUBLISHED["rope_parameters"]["sliding_attention"], 16)
    assert one == 1.0 and np.allclose(
        plain, [10000.0 ** (-2 * j / 16) for j in range(8)], rtol=1e-6)


def test_partial_rotary_passes_the_second_half_through_unscaled(tiny):
    cfg, _ = tiny
    full, sliding = cfg.attention_layer(0), cfg.attention_layer(1)
    x = jnp.asarray(np.random.RandomState(0).randn(1, 5, 2, 16), jnp.float32)
    pos = jnp.arange(5)[None] + 7
    got = common.rope_heads(x, pos, full)
    assert np.array_equal(np.asarray(got[..., 8:]), np.asarray(x[..., 8:]))
    assert not np.allclose(np.asarray(got[..., :8]), np.asarray(x[..., :8]))
    # the turned half has grown by the attention factor
    assert np.allclose(
        np.linalg.norm(np.asarray(got[..., :8]), axis=-1),
        1.2079441541679836 * np.linalg.norm(np.asarray(x[..., :8]),
                                            axis=-1), rtol=1e-5)
    # a sliding layer turns the whole head, as the plain rope does
    assert np.allclose(np.asarray(common.rope_heads(x, pos, sliding)),
                       np.asarray(common.rope(x, pos, 10000.0)))


# --- whole forward, layer by layer -------------------------------------------


@pytest.mark.parametrize("length", [12, 40, 100])
def test_whole_forward_matches_the_reference(tiny, length):
    """Under, across and far past the 16-token window."""
    cfg, params = tiny
    tokens = [1] + tokens_of(length, length - 1)
    with jax.default_matmul_precision("highest"):
        got, _ = _forward_hybrid_whole(
            params, cfg, jnp.asarray(tokens)[None],
            jnp.arange(length)[None], jnp.asarray([length]), None)
    want = reference(params, tokens, list(range(length)))
    assert np.abs(np.asarray(got[0]) - want).max() < TOL


def test_the_window_and_the_gate_and_the_heads_each_matter(tiny):
    """The reference with one piece of the geometry taken away is far
    from the program: none of them is inert at this size."""
    cfg, params = tiny
    tokens = [1] + tokens_of(5, 59)
    want = reference(params, tokens, list(range(40, 60)))
    for change in ({"sliding_window": 512},
                   {"layer_types": ["full_attention"] * 5},
                   {"rope_parameters": dict(
                       PUBLISHED["rope_parameters"], full_attention=PUBLISHED[
                           "rope_parameters"]["sliding_attention"])}):
        other = reference(params, tokens, list(range(40, 60)),
                          dict(PUBLISHED, **change))
        assert np.abs(other - want).max() > 100 * TOL, change
    # the gate: with W_g zeroed every head is halved, and that shows
    flat = dict(params, layers=[
        dict(layer, g_proj=jnp.zeros_like(layer["g_proj"]))
        if "g_proj" in layer else layer for layer in params["layers"]])
    other = reference(flat, tokens, list(range(40, 60)))
    assert np.abs(other - want).max() > 100 * TOL


# --- through pages: prologue, decode, join, fork, reuse ------------------------


def test_prologue_then_decode_through_the_cache(tiny):
    """48 tokens as one chunk into pages, then 6 single steps through
    the decode walk: three of five layers read a 16-token window that is
    two pages of the six held."""
    cfg, params = tiny
    tokens = [1] + tokens_of(40, 53)
    table = jnp.arange(1, 9)[None]
    logits, pools = prefill(params, cfg, tokens[:48], empty_pools(cfg, 9),
                            table)
    got = [logits[47]]
    for pos in range(48, 54):
        step, pools = decode(params, cfg, tokens[pos], pos, pools, table)
        got.append(step)
    want = reference(params, tokens, list(range(47, 54)))
    assert np.abs(np.asarray(got) - want).max() < TOL


def test_a_ragged_join_of_runs_and_decode_rows(tiny):
    """A leader's 21-token run at position 35 (its window starts inside
    pages it did not write), a decode row and a short run in one flat
    buffer, each against the reference's whole forward."""
    cfg, params = tiny
    pools = empty_pools(cfg, 32)
    seqs, whole, at = [], [], 1
    for seed, have, new in ((1, 35, 21), (2, 29, 1), (3, 8, 9)):
        tokens = [1] + tokens_of(seed, have + new - 1)
        table = np.zeros(10, np.int32)
        table[:8] = np.arange(at, at + 8)
        at += 8
        _, pools = prefill(params, cfg, tokens[:have], pools,
                           jnp.asarray(table[None]))
        seqs.append(RaggedSeq(tokens[have:], have, table))
        whole.append(tokens)
    got, _ = ragged(params, cfg, seqs, pools)
    for i, tokens in enumerate(whole):
        want = reference(params, tokens, [len(tokens) - 1])
        assert np.abs(got[i] - want[0]).max() < TOL, i


def test_a_follower_forks_from_a_leaders_pages_under_the_window(tiny):
    """The follower's table ALIASES the leader's first five pages (40
    positions) and its own run starts at 40: the 16-token window of its
    first rows spans shared pages and its own, the full layers read all
    five shared pages."""
    cfg, params = tiny
    opening = [1] + tokens_of(6, 39)
    leader = np.zeros(10, np.int32)
    leader[:8] = np.arange(1, 9)
    _, pools = prefill(params, cfg, opening, empty_pools(cfg, 24),
                       jnp.asarray(leader[None]))
    follower = np.zeros(10, np.int32)
    follower[:5] = leader[:5]
    follower[5:8] = np.arange(9, 12)
    own = tokens_of(7, 13)
    got, pools = ragged(params, cfg, [RaggedSeq(own, 40, follower)], pools)
    want = reference(params, opening + own, [52])
    assert np.abs(got[0] - want[0]).max() < TOL
    # ... and then decodes on through the walk
    nxt = int(want[0].argmax())
    step, _ = decode(params, cfg, nxt, 53, pools,
                     jnp.asarray(follower[None]))
    want = reference(params, opening + own + [nxt], [53])
    assert np.abs(step - want[0]).max() < TOL


def test_a_reused_slot_prefills_only_its_new_tokens(tiny):
    """A slot that kept its pages takes the next turn as a chunk at its
    own offset (the paged prefill kernel at a non-zero start, window
    layers starting two pages in)."""
    cfg, params = tiny
    first = [1] + tokens_of(8, 37)
    more = tokens_of(9, 16)
    table = jnp.arange(1, 9)[None]
    _, pools = prefill(params, cfg, first, empty_pools(cfg, 9), table)
    logits, _ = prefill(params, cfg, more, pools, table, start=38)
    want = reference(params, first + more, list(range(38, 54)))
    assert np.abs(logits - want).max() < TOL


def test_a_bfloat16_program_is_told_apart_from_a_float32_one(tiny):
    """The same positions in one pass of bfloat16: over the tolerance by
    two orders of magnitude, so the comparisons above would fail it."""
    cfg, _ = tiny
    params = init_params(cfg, jax.random.PRNGKey(3), jnp.bfloat16)
    tokens = [1] + tokens_of(40, 53)
    table = jnp.arange(1, 9)[None]
    logits, pools = prefill(params, cfg, tokens[:48],
                            empty_pools(cfg, 9, jnp.bfloat16), table)
    got = [logits[47]]
    for pos in range(48, 54):
        step, pools = decode(params, cfg, tokens[pos], pos, pools, table)
        got.append(step)
    want = reference(params, tokens, list(range(47, 54)))
    worst = np.abs(np.asarray(got) - want).max()
    assert 100 * TOL < worst < 0.5


# --- the other readings are plain errors --------------------------------------


@pytest.mark.parametrize("gating", ["elementwise", False])
def test_another_gate_is_a_plain_error(tiny, gating):
    """One logit a head is the one reading this engine and the reference
    are written for (the parameter count and the sibling config say so);
    a config that asks for another fails by name in both."""
    cfg, params = tiny
    assert cfg.attn_gate is True
    assert params["layers"][2]["g_proj"].shape == (64, 8)
    arch = dict(PUBLISHED, gating=gating)
    with pytest.raises(ValueError, match=f"gating={gating!r}"):
        resolve_model_config({"model": "t", "architecture": arch})
    with pytest.raises(ValueError, match="gating"):
        ref.sizes_of(arch)


def test_a_softmax_router_is_a_plain_error():
    arch = dict(PUBLISHED, scoring_func="softmax")
    with pytest.raises(ValueError, match="scoring_func='softmax'"):
        resolve_model_config({"model": "t", "architecture": arch})
    with pytest.raises(ValueError, match="scoring_func"):
        ref.sizes_of(arch)
    assert resolve_model_config({"model": "t", "architecture": dict(
        PUBLISHED, scoring_func="sigmoid")}).router_rule == "sigmoid_topk"


# --- the resolver ------------------------------------------------------------


def test_an_architecture_block_builds_the_model(tiny):
    cfg, _ = tiny
    built = resolve_model_config({"model": "tiny-laguna",
                                  "architecture": dict(PUBLISHED)})
    assert built == cfg
    assert built.attention_classes == ((6, None, 2), (8, 16, 3))
    assert built.attention_layer(1) == dataclasses.replace(
        built, attn_layers=None, num_heads=8, sliding_window=16,
        rope_theta=10000.0, rotary_dim=16)
    assert resolve_model_config({"model": "tiny-laguna", "architecture":
                                 dict(PUBLISHED, gating="per-head")}) == cfg


@pytest.mark.parametrize("change,message", [
    ({"q_norm": True}, r"unknown keys \['q_norm'\]"),
    ({"attention_bias": True}, "attention_bias=True"),
    ({"moe_apply_router_weight_on_input": True},
     "moe_apply_router_weight_on_input=True"),
    ({"gating": "per-channel"}, "gating='per-channel'"),
    ({"layer_types": ["full_attention"] * 4}, "num_hidden_layers says 5"),
    ({"layer_types": ["linear_attention"] * 5}, "linear_attention"),
    ({"rope_parameters": {"full_attention": {"rope_theta": 1.0,
                                             "rope_type": "llama3"},
                          "sliding_attention": {"rope_theta": 1.0}}},
     "rope_type 'llama3'"),
    ({"rope_parameters": {"full_attention": {"rope_theta": 1.0, "mscale": 1},
                          "sliding_attention": {"rope_theta": 1.0}}},
     r"rope_parameters.full_attention keys \['mscale'\]"),
])
def test_what_the_layers_are_not_written_for_fails_by_name(change, message):
    with pytest.raises(ValueError, match=message):
        resolve_model_config({"model": "t", "architecture":
                              dict(PUBLISHED, **change)})
    arch = dict(PUBLISHED)
    del arch["sliding_window"]
    with pytest.raises(ValueError, match="lacks the key 'sliding_window'"):
        resolve_model_config({"model": "t", "architecture": arch})


def test_the_published_widths_and_the_parameter_count():
    """40 layers: 10 full (48 heads, YaRN over half a head), 30 sliding
    (64 heads, window 512); 33.44 B parameters, the published 33.4 B —
    which is what decides the gate's width (a logit a head: +0.005 B; a
    logit an element would make it 34.1 B)."""
    from theroundtaible_tpu.engine.fleet import estimate_param_count
    cfg = get_model_config("laguna-xs.2")
    assert (cfg.embed_dim, cfg.num_kv_heads, cfg.head_dim, cfg.mlp_dim,
            cfg.vocab_size) == (2048, 8, 128, 8192, 100_352)
    assert (cfg.routed_experts, cfg.experts_held, cfg.moe_top_k,
            cfg.expert_dim, cfg.shared_expert_dim, cfg.routed_scaling) \
        == (256, 256, 8, 512, 512, 2.5)
    assert cfg.attention_classes == ((48, None, 10), (64, 512, 30))
    assert cfg.layer_kinds[:4] == (hybrid.ATTENTION, hybrid.MLP,
                                   hybrid.ATTENTION, hybrid.EXPERTS)
    assert cfg.attn_layers[0] == AttnLayer(
        48, None, 500_000.0, 64, (64.0, 4096.0, 64.0, 1.0),
        1.4158883083359672)
    assert cfg.attn_layers[1] == AttnLayer(64, 512, 10_000.0, 128)
    n = estimate_param_count(cfg)
    assert 33.43e9 < n < 33.45e9
    # the gate is a logit a head: 0.005 B of it
    bare = estimate_param_count(dataclasses.replace(cfg, attn_gate=False))
    assert n - bare == 2048 * (10 * 48 + 30 * 64)
    tiny_cfg = get_model_config("tiny-laguna")
    assert estimate_param_count(tiny_cfg) == common.param_count(
        init_params(tiny_cfg, jax.random.PRNGKey(0), jnp.float32))


# --- the kernels at the published group and window ----------------------------

H, K, D, PS, WINDOW = 48, 8, 128, 128, 512


def dense_softmax(q, keys, values, q_pos, window):
    """q [n, H, D] at positions q_pos against keys / values [L, K, D]:
    causal, the last `window` positions; head i reads kv head i // 6."""
    group = q.shape[1] // keys.shape[1]
    k = jnp.repeat(keys, group, axis=1)
    v = jnp.repeat(values, group, axis=1)
    s = jnp.einsum("nhd,lhd->nhl", q, k,
                   precision=jax.lax.Precision.HIGHEST)
    at = jnp.arange(keys.shape[0])[None, :]
    seen = (at <= q_pos[:, None]) & (at > q_pos[:, None] - window)
    p = jax.nn.softmax(jnp.where(seen[:, None, :], s, -jnp.inf), -1)
    return jnp.einsum("nhl,lhd->nhd", p, v,
                      precision=jax.lax.Precision.HIGHEST)


@pytest.fixture(scope="module")
def wide_pool():
    """Two sequences' pages, 14 each, scattered through a pool of 30."""
    rng = np.random.RandomState(21)
    k = jnp.asarray(rng.randn(30, PS, K, D), jnp.float32)
    v = jnp.asarray(rng.randn(30, PS, K, D), jnp.float32)
    tables = np.zeros((2, 16), np.int32)
    tables[0, :14] = rng.permutation(np.arange(1, 15))
    tables[1, :14] = np.arange(15, 29)
    return k, v, tables


def flat(pool, table, n):
    return pool[jnp.asarray(table)].reshape(-1, K, D)[:n]


def queries(seed, *shape):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * 0.3,
                       jnp.float32)


def test_the_decode_walk_at_group_six_under_the_window(wide_pool):
    """Rows at 1500 and 700 positions: the walk starts at page 7 and 1
    (the first the 512 window touches), not at 0."""
    k, v, tables = wide_pool
    valid = jnp.asarray([1500, 700])
    q = queries(1, 2, 1, H, D)
    got = pattn.paged_decode_attention(
        q, k, v, jnp.asarray(tables), valid, sliding_window=WINDOW,
        interpret=True)
    for b in range(2):
        n = int(valid[b])
        want = dense_softmax(q[b], flat(k, tables[b], n),
                             flat(v, tables[b], n),
                             jnp.asarray([n - 1]), WINDOW)
        assert np.abs(np.asarray(got[b]) - np.asarray(want)).max() < 2e-5
    # the same rows without a window read something else
    free = pattn.paged_decode_attention(
        q, k, v, jnp.asarray(tables), valid, interpret=True)
    assert np.abs(np.asarray(free) - np.asarray(got)).max() > 1e-3


def test_the_paged_prefill_kernel_at_group_six_under_the_window(wide_pool):
    k, v, tables = wide_pool
    q = queries(2, 1, 128, H, D)
    got = pattn.paged_prefill_attention(
        q, k, v, jnp.asarray(tables[:1]), jnp.asarray([1000]),
        jnp.asarray([1128]), sliding_window=WINDOW, interpret=True)
    want = dense_softmax(q[0], flat(k, tables[0], 1128),
                         flat(v, tables[0], 1128),
                         1000 + jnp.arange(128), WINDOW)
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 2e-5


def test_the_ragged_walk_at_group_six_under_the_window(wide_pool):
    """A 40-token join at 1200 and a decode row at 650 in one buffer."""
    k, v, tables = wide_pool
    runs = [(40, 1200), (1, 650)]
    seqs = [RaggedSeq([1] * n, pos, tables[i])
            for i, (n, pos) in enumerate(runs)]
    b = build_ragged_batch(seqs, t_budget=64, s_max=3, pages_per_seq=16,
                           scratch_page=0, pad_id=0, page_size=PS)
    q = queries(3, 64, H, D)
    got = pattn.ragged_paged_attention(
        q, k, v, *(jnp.asarray(b[n]) for n in (
            "tables", "seq_of_block", "block_qstart", "query_offsets",
            "kv_valid")), sliding_window=WINDOW, interpret=True)
    row = 0
    for i, (n, pos) in enumerate(runs):
        want = dense_softmax(q[row:row + n], flat(k, tables[i], pos + n),
                             flat(v, tables[i], pos + n),
                             pos + jnp.arange(n), WINDOW)
        assert np.abs(np.asarray(got[row:row + n])
                      - np.asarray(want)).max() < 2e-5, (n, pos)
        row += -(-n // 8) * 8
    # what the walk was asked to read: pages 5..9 of the join (its first
    # row's window starts at 689), 1..5 of the decode row, and the
    # scratch page of the first inert pad tile
    visits = pattn.ragged_page_visits(
        b, page_size=PS, block_q=64, sliding_window=WINDOW)
    assert visits[0] == 5 + 5 + 1
    assert pattn.ragged_page_visits(b, page_size=PS, block_q=64)[0] \
        == 10 + 6 + 1
