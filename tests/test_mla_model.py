"""Multi-head latent attention and the `axk1` block (models/mla.py,
models/hybrid.py) against the plain reference
(benchmarks/configs/mla_moe_reference.py), at tiny sizes on the CPU with
seeded random weights: YaRN's frequencies and the softmax scale against
hand-computed values, the absorbed form against the expanded one, the
paged kernels' latent mode (one pool, key width != value width) in
interpret mode, the resolver, and the sixteen shares of an
expert-parallel group.

Tolerances. A float32 program and the float32 reference differ in the
order of their sums, and the absorbed form multiplies W_UK into the
query where the expanded one multiplies it into the keys: every logit
within 1e-4 where the logits' spread is about 1 (measured here: about
2e-6). A bfloat16 program was measured 0.02 to 0.05 from the reference
on these sizes (seeds 0-4, CPU), so it is held to 0.15 and must FAIL
1e-4: computing in a lower precision than stated is told apart."""
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
from configs import mla_moe_reference as ref  # noqa: E402

from theroundtaible_tpu.engine.fleet import estimate_param_count  # noqa: E402
from theroundtaible_tpu.engine.models import hybrid, mla  # noqa: E402
from theroundtaible_tpu.engine.models.common import (  # noqa: E402
    forward, init_params, make_attention_mask, mlp, param_count)
from theroundtaible_tpu.engine.models.registry import (  # noqa: E402
    get_model_config, resolve_model_config)
from theroundtaible_tpu.engine.pallas import attention as pattn  # noqa: E402

F32_BOUND = 1e-4
BF16_BOUND = 0.15


def published(cfg, **extra):
    """The keys of a config.json that describe `cfg`."""
    factor, original, fast, slow, mscale, mscale_all = cfg.rope_yarn
    return dict({
        "model_type": "axk1", "num_hidden_layers": cfg.num_layers // 2,
        "first_k_dense_replace": cfg.layer_kinds.count(hybrid.MLP),
        "hidden_size": cfg.embed_dim, "vocab_size": cfg.vocab_size,
        "intermediate_size": cfg.mlp_dim,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_dim,
        "qk_rope_head_dim": cfg.qk_rope_dim, "v_head_dim": cfg.v_head_dim,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "rope_scaling": {
            "type": "yarn", "factor": factor,
            "original_max_position_embeddings": original,
            "beta_fast": fast, "beta_slow": slow, "mscale": mscale,
            "mscale_all_dim": mscale_all},
        "n_routed_experts": cfg.experts_held,
        "num_experts_per_tok": cfg.moe_top_k,
        "moe_intermediate_size": cfg.expert_dim,
        "routed_scaling_factor": cfg.routed_scaling,
        "topk_method": "none", "scoring_func": "sigmoid",
        "n_group": 8, "topk_group": 4}, **extra)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_model_config("tiny-axk1")
    return cfg, init_params(cfg, jax.random.PRNGKey(3), jnp.float32)


def program_logits(params, cfg, tokens):
    t = len(tokens)
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, cfg, jnp.asarray(tokens)[None],
                            jnp.arange(t)[None], None, None,
                            jnp.asarray([t]))
    return np.asarray(logits[0], np.float32)


# --- YaRN -------------------------------------------------------------------


def test_yarn_frequencies_against_hand_computed_values():
    """A.X-K1's own numbers: 64 rotary dimensions, theta 1e4, factor 32
    over 4096, beta 32 / 1. By hand: a dimension pair j turns
    4096 theta^(-2j/64) / 2 pi times over the original context; 32
    turns at j = 10.47, one at j = 22.51, so pairs 0..10 keep their
    frequency, pairs 23..31 have it divided by 32, and pair 16 stands
    6/13 of the way: 10^-2 (7/13 + 6/13 / 32)."""
    cfg = get_model_config("a.x-k1")
    got, mult = mla.rope_frequencies(cfg)
    want = {0: 1.0, 10: 10 ** -1.25, 11: 10 ** -1.375 * (12 / 13 + 1 / 13 / 32),
            16: 1e-2 * (7 / 13 + 6 / 13 / 32),
            23: 10 ** -2.875 / 32, 31: 10 ** -3.875 / 32}
    assert got.shape == (32,) and mult == 1.0
    for j, value in want.items():
        assert got[j] == pytest.approx(value, rel=1e-6), j
    sizes = ref.sizes_of(published(cfg))
    assert np.allclose(ref.yarn_frequencies(sizes), got, rtol=1e-6)


def test_the_softmax_scale_against_a_hand_computed_value():
    """192^-0.5 (0.1 ln 32 + 1)^2 = 0.0721688 x 1.8132606."""
    cfg = get_model_config("a.x-k1")
    m = 0.1 * math.log(32) + 1
    assert m == pytest.approx(1.3465736, rel=1e-7)
    assert mla.softmax_scale(cfg) == pytest.approx(0.130861, rel=1e-5)
    assert ref.softmax_scale(ref.sizes_of(published(cfg))) \
        == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    # Without YaRN: the plain scale and theta's own frequencies.
    plain = dataclasses.replace(cfg, rope_yarn=None)
    assert mla.softmax_scale(plain) == pytest.approx(192 ** -0.5)
    assert mla.rope_frequencies(plain)[0][16] == pytest.approx(1e-2)


# --- the layers -------------------------------------------------------------


@pytest.mark.parametrize("length", [40, 128, 200])
def test_whole_forward_matches_the_reference(tiny, length):
    cfg, params = tiny
    tokens = np.random.RandomState(length).randint(3, 500, size=(length,))
    got = program_logits(params, cfg, tokens)
    want = np.asarray(ref.logits_at(params, published(cfg), tokens,
                                    [length - 1, 17]))
    assert abs(got.std() - 1.0) < 0.3
    assert np.abs(want[0] - got[length - 1]).max() < F32_BOUND
    assert np.abs(want[1] - got[17]).max() < F32_BOUND


@pytest.mark.parametrize("kind", [hybrid.ATTENTION, hybrid.MLP,
                                  hybrid.EXPERTS])
def test_each_layer_kind_matches_the_reference(tiny, kind):
    cfg, params = tiny
    li = cfg.layer_kinds.index(kind)
    layer = params["layers"][li]
    x = jax.random.normal(jax.random.PRNGKey(li), (1, 150, cfg.embed_dim))
    sizes = ref.sizes_of(published(cfg))
    with jax.default_matmul_precision("highest"):
        h = hybrid.layer_norm_in(x, layer, cfg)
        if kind == hybrid.ATTENTION:
            pos = jnp.arange(150)[None]
            out, (c_kv, k_rope) = mla.expanded_attention(
                h, layer, cfg, pos,
                make_attention_mask(pos, 150, jnp.asarray([150]), None))
            # What a cache would hold: 32 + 8 values a position.
            assert c_kv.shape == (1, 150, 32) and k_rope.shape == (1, 150, 8)
            want = ref.mla_layer(
                layer, x[0], jnp.asarray(ref.yarn_frequencies(sizes)),
                nope=sizes["nope"], rank=sizes["rank"], eps=sizes["eps"],
                scale=ref.softmax_scale(sizes), multiplier=1.0)
        elif kind == hybrid.MLP:
            out = mlp(h, layer, cfg)
            want = ref.dense_layer(layer, x[0], eps=sizes["eps"])
        else:
            out, counts = hybrid.experts_mlp(h, layer, cfg)
            want = ref.experts_layer(layer, x[0], sizes)
            assert int(counts[1]) == 150 * cfg.moe_top_k
            assert 0 < int(counts[0]) <= cfg.experts_held
    assert np.abs(np.asarray(x[0] + out[0]) - np.asarray(want)).max() \
        < F32_BOUND


def dense_absorbed(q, entries, v_dim, q_pos):
    """softmax(q . entry) entry[:v_dim] over positions <= q_pos, dense:
    q [T,H,W] (scaled), entries [S,W] -> [T,H,v_dim]."""
    logits = jnp.einsum("thw,sw->ths", q, entries)
    seen = jnp.arange(entries.shape[0])[None, :] <= q_pos[:, None]
    probs = jax.nn.softmax(jnp.where(seen[:, None, :], logits, -jnp.inf), -1)
    return jnp.einsum("ths,sv->thv", probs, entries[:, :v_dim])


def test_the_absorbed_form_is_the_expanded_form(tiny):
    """q~_i = q_nope_i W_UK,i^T against the latent entry, then
    o_i = o-_i W_UV,i: the same layer output as per-head keys and
    values built from c_kv."""
    cfg, params = tiny
    layer = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 90, cfg.embed_dim))
    pos = jnp.arange(90)[None]
    with jax.default_matmul_precision("highest"):
        h = hybrid.layer_norm_in(x, layer, cfg)
        want, (c_kv, k_rope) = mla.expanded_attention(
            h, layer, cfg, pos,
            make_attention_mask(pos, 90, jnp.asarray([90]), None))
        q, entry = mla.latents(h, layer, cfg, pos)
        # One entry a position: c_kv, the roped key part, zeros to a
        # whole lane row; the query's padding is zeros too.
        assert entry.shape == (1, 90, cfg.page_width) == (1, 90, 128)
        assert np.array_equal(entry[0, :, :32], c_kv[0])
        assert np.array_equal(entry[0, :, 32:40], k_rope[0])
        assert not np.asarray(entry[0, :, 40:]).any()
        assert not np.asarray(q[..., 40:]).any()
        o_lat = dense_absorbed(q[0], entry[0], cfg.kv_lora_rank, pos[0])
        o = mla.values_of(o_lat[None], layer, cfg)
        got = jnp.einsum("bthd,hde->bte", o, layer["o_proj"])
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


# --- the kernels' latent mode (interpret) ------------------------------------

PS, W, DV, HEADS = 16, 128, 32, 4


@pytest.fixture(scope="module")
def latent_pool():
    """A pool [P, ps, W] of 12 pages whose entries are random in the
    first 40 columns, two sequences' tables over it, and queries."""
    rng = np.random.RandomState(5)
    pool = np.zeros((12, PS, W), np.float32)
    pool[1:, :, :40] = rng.randn(11, PS, 40)
    tables = np.zeros((3, 6), np.int32)
    tables[0, :4] = [3, 7, 1, 9]
    tables[1, :3] = [2, 5, 11]
    return jnp.asarray(pool), jnp.asarray(tables)


def entries_of(pool, table, n):
    return pool[table].reshape(-1, pool.shape[-1])[:n]


def queries(seed, *shape):
    q = np.zeros(shape + (W,), np.float32)
    q[..., :40] = np.random.RandomState(seed).randn(*shape, 40) * 0.3
    return jnp.asarray(q)


def test_the_decode_walk_reads_a_latent_pool(latent_pool):
    pool, tables = latent_pool
    valid = jnp.asarray([57, 33, 0])
    q = queries(1, 3, 1, HEADS)
    got = pattn.paged_decode_attention(q, pool, None, tables, valid,
                                       v_dim=DV, interpret=True)
    assert got.shape == (3, 1, HEADS, DV)
    for b, n in enumerate([57, 33]):
        want = dense_absorbed(q[b], entries_of(pool, tables[b], n), DV,
                              jnp.asarray([n - 1]))
        assert np.abs(np.asarray(got[b]) - np.asarray(want)).max() < 1e-5
    assert not np.asarray(got[2]).any()         # a row with nothing valid


@pytest.mark.parametrize("offset,t", [(0, 32), (16, 32), (24, 16)])
def test_the_paged_prefill_kernel_reads_a_latent_pool(latent_pool, offset,
                                                      t):
    pool, tables = latent_pool
    q = queries(2, 1, t, HEADS)
    got = pattn.paged_prefill_attention(
        q, pool, None, tables[:1], jnp.asarray([offset]),
        jnp.asarray([offset + t]), v_dim=DV, interpret=True)
    want = dense_absorbed(q[0], entries_of(pool, tables[0], offset + t),
                          DV, offset + jnp.arange(t))
    assert got.shape == (1, t, HEADS, DV)
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-5


def test_the_ragged_kernel_reads_a_latent_pool(latent_pool):
    """A join of 24 fresh tokens over 30 cached positions beside a
    decode token at position 40, in one flat buffer."""
    from theroundtaible_tpu.engine.serving_loop import RAGGED_BLOCK_Q as Q
    pool, tables = latent_pool
    q = queries(3, 24 + Q, HEADS)
    seq_of_block = jnp.asarray([0, 0, 0, 1])
    block_qstart = jnp.asarray([0, Q, 2 * Q, 0])
    offsets, valid = jnp.asarray([30, 40, 0]), jnp.asarray([54, 41, 0])
    got = pattn.ragged_paged_attention(
        q, pool, None, tables, seq_of_block, block_qstart, offsets, valid,
        v_dim=DV, interpret=True)
    assert got.shape == (24 + Q, HEADS, DV)
    join = dense_absorbed(q[:24], entries_of(pool, tables[0], 54), DV,
                          30 + jnp.arange(24))
    step = dense_absorbed(q[24:25], entries_of(pool, tables[1], 41), DV,
                          jnp.asarray([40]))
    assert np.abs(np.asarray(got[:24]) - np.asarray(join)).max() < 1e-5
    assert np.abs(np.asarray(got[24:25]) - np.asarray(step)).max() < 1e-5


@pytest.mark.parametrize("t,runs", [
    # a join longer than the walk's query block beside a decode row
    # and a verify tile; the join ends one past a page boundary
    (256, [(129, 16), (1, 90), (4, 60)]),
    # a follower and a short run share a tile of products; contexts
    # end on a page boundary and one before it
    (128, [(44, 20), (9, 6), (7, 9)]),
])
def test_the_ragged_walk_reads_a_latent_pool(t, runs):
    """Runs of every kind in one flat buffer over a latent pool, every
    real row against the dense absorbed form (ISSUE 32: the kernel's
    query block is not the packing's 8)."""
    from theroundtaible_tpu.engine.serving_loop import (
        RaggedSeq, build_ragged_batch)
    rng = np.random.RandomState(11)
    seqs, page = [], 1
    for n, pos in runs:
        table = np.zeros(12, np.int32)
        need = -(-(pos + n) // PS)
        table[:need] = np.arange(page, page + need)
        page += need
        seqs.append(RaggedSeq([1] * n, pos, table))
    b = build_ragged_batch(seqs, t_budget=t, s_max=4,
                           pages_per_seq=12, scratch_page=0, pad_id=0,
                           page_size=PS)
    pool = np.zeros((page, PS, W), np.float32)
    pool[1:, :, :40] = rng.randn(page - 1, PS, 40)
    pool = jnp.asarray(pool)
    q = queries(4, t, HEADS)
    got = pattn.ragged_paged_attention(
        q, pool, None, *(jnp.asarray(b[k]) for k in (
            "tables", "seq_of_block", "block_qstart", "query_offsets",
            "kv_valid")), v_dim=DV, interpret=True)
    assert got.shape == (t, HEADS, DV)
    row = 0
    for i, (n, pos) in enumerate(runs):
        want = dense_absorbed(
            q[row:row + n], entries_of(pool, b["tables"][i], pos + n), DV,
            pos + jnp.arange(n))
        assert np.abs(np.asarray(got[row:row + n])
                      - np.asarray(want)).max() < 1e-5, (n, pos)
        row += -(-n // 8) * 8


def test_the_latent_kernels_carry_their_names(latent_pool):
    """The trace finds them by name, and a latent call has one pool
    among its operands."""
    pool, tables = latent_pool
    jaxpr = jax.make_jaxpr(lambda q: pattn.paged_decode_attention(
        q, pool, None, tables, jnp.asarray([57, 33, 0]), v_dim=DV,
        interpret=True))(queries(1, 3, 1, HEADS))
    call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "mla_paged_decode"
    # table, valid, q and ONE pool
    assert len(call.invars) == 4


# --- the resolver -----------------------------------------------------------


def test_an_architecture_block_builds_the_model(tiny):
    cfg, _ = tiny
    built = resolve_model_config({
        "model": "from-keys", "max_seq_len": 512,
        "architecture": published(cfg)})
    assert dataclasses.replace(built, name=cfg.name) == cfg
    assert built.layer_kinds == ("attention", "mlp", "attention",
                                 "experts", "attention", "experts")
    assert built.latent and built.page_heads == 1 and not built.recurrent
    assert built.page_width == 128 and built.page_cells == 128
    assert built.attention_layers == (0, 2, 4)


def test_the_chips_share_of_an_expert_parallel_group(tiny):
    cfg, _ = tiny
    share = resolve_model_config({
        "model": "share", "max_seq_len": 512,
        "architecture": published(cfg, n_routed_experts=2, ep_size=4,
                                  ep_rank=3)})
    assert (share.routed_experts, share.experts_held,
            share.expert_offset) == (8, 2, 6)
    with pytest.raises(ValueError, match="ep_rank 4"):
        resolve_model_config({"model": "x", "architecture": published(
            cfg, ep_size=4, ep_rank=4)})


@pytest.mark.parametrize("change,message", [
    ({"topk_method": "noaux_tc"}, "topk_method='noaux_tc'"),
    ({"scoring_func": "softmax"}, "scoring_func='softmax'"),
    ({"n_shared_experts": 2}, "n_shared_experts=2"),
    ({"rope_scaling": {"type": "linear"}}, "must be of type 'yarn'"),
    ({"some_new_key": 1}, "unknown keys"),
])
def test_what_the_layers_are_not_written_for_fails_at_once(tiny, change,
                                                           message):
    cfg, _ = tiny
    with pytest.raises(ValueError, match=message):
        resolve_model_config({"model": "x",
                              "architecture": published(cfg, **change)})


def test_the_published_widths_and_the_parameter_count(tiny):
    """A.X-K1 whole: 519 G parameters, as its card says; the closed
    form agrees with the tree at the tiny size."""
    cfg, params = tiny
    assert estimate_param_count(cfg) == param_count(params)
    big = get_model_config("a.x-k1")
    assert 518e9 < estimate_param_count(big) < 520e9
    assert big.page_width == 640 and big.kv_lora_rank + big.qk_rope_dim == 576
    assert len(big.attention_layers) == 61 and len(big.expert_layers) == 60


# --- precision --------------------------------------------------------------


def test_a_bfloat16_program_is_told_apart_from_a_float32_one(tiny):
    cfg, params = tiny
    tokens = np.random.RandomState(9).randint(3, 500, size=(96,))
    want = np.asarray(ref.logits_at(params, published(cfg), tokens,
                                    [95, 50]))
    gaps = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        cast = jax.tree_util.tree_map(
            lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a,
            params)
        cast["layers"] = [dict(l, **({"router": p["router"]}
                                     if "router" in p else {}))
                          for l, p in zip(cast["layers"], params["layers"])]
        got = program_logits(cast, cfg, tokens)
        gaps[dtype] = max(np.abs(want[0] - got[95]).max(),
                          np.abs(want[1] - got[50]).max())
    assert gaps[jnp.float32] < F32_BOUND
    assert F32_BOUND < gaps[jnp.bfloat16] < BF16_BOUND


# --- the sixteen shares -----------------------------------------------------


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """32 experts, 2 held a chip: each of the 16 chips' shares of one
    expert layer computed by the PROGRAM, the shared expert counted
    once, add up to the uncut reference's layer."""
    cfg = dataclasses.replace(
        get_model_config("tiny-axk1"), routed_experts=32, experts_held=32,
        moe_top_k=8)
    layer = hybrid.init_layer(cfg, hybrid.EXPERTS, jax.random.PRNGKey(2),
                              jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 64, cfg.embed_dim))
    whole = ref.experts_layer(layer, x[0], ref.sizes_of(published(cfg)))
    total, assigned = None, 0
    with jax.default_matmul_precision("highest"):
        h = hybrid.layer_norm_in(x, layer, cfg)
        for rank in range(16):
            share = dataclasses.replace(cfg, experts_held=2,
                                        expert_offset=2 * rank)
            part = dict(layer, experts={
                k: v[2 * rank:2 * rank + 2]
                for k, v in layer["experts"].items()})
            if rank:    # the shared expert is counted once
                part["shared"] = {k: jnp.zeros_like(v)
                                  for k, v in layer["shared"].items()}
            out, counts = hybrid.experts_mlp(h, part, share)
            total = out if total is None else total + out
            assigned += int(counts[1])
            sizes = ref.sizes_of(published(share, ep_size=16,
                                           ep_rank=rank))
            assert sizes["published"] == 32 and sizes["offset"] == 2 * rank
            if rank == 5:
                # The reference given the same share agrees with the
                # program's (what the other chips add is left out in
                # both).
                want = ref.experts_layer(part, x[0], sizes)
                assert np.abs(np.asarray(x[0] + out[0])
                              - np.asarray(want)).max() < F32_BOUND
    # Every token's eight experts are held by some chip, once.
    assert assigned == 64 * 8
    assert np.abs(np.asarray(x[0] + total[0]) - np.asarray(whole)).max() \
        < F32_BOUND
