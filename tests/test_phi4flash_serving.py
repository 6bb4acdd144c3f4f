"""A decoder whose upper half keeps no cache of its own, through the
serving path on the CPU (tiny-phi4flash, depth 8 by the model's own
rule: three Mamba-1 layers without inner norms, two window-16 and one
full differential-attention layer, one gated memory unit over the last
Mamba layer's scan output, one differential cross layer over the full
layer's pages; a kv pair of 64-wide heads a 128-lane row): prologue and
decode through pages and slots, the seam on against the seam off, the
lane-row pairing against two explicit softmaxes through every paged
kernel, a join restored from a snapshot, ragged joins through the
scheduler with their seam counts, the decline table.

Every path ends in a comparison with the plain reference
(benchmarks/configs/phi4flash_reference.py: the recurrence a token at a
time, two softmaxes a head pair over explicit masks, no cache, no seam)
on the engine's own weights. GAP: a float32 engine serves the
reference's own maximum at every position but for rounding-level ties.
LOGIT_TOL, for logits compared as logits: the paged, chunked, packed
form sums the same float32 products in another order, which moves a
logit by 1.2e-6 here (the reading); the REFERENCE computed with its
matrices rounded to bfloat16 — the next precision down — moves by
1.4e-2 (the control's reading), of a spread of 0.16. 2e-5 lies between,
an order above the first and nearly three under the second."""
import dataclasses
import os
import sys
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from configs import phi4flash_reference as ref  # noqa: E402

from theroundtaible_tpu.engine.engine import InferenceEngine  # noqa: E402
from theroundtaible_tpu.engine.models import (diffattn, hybrid,  # noqa: E402
                                              mamba1)
from theroundtaible_tpu.engine.models.registry import (  # noqa: E402
    get_model_config, phi4flash_kinds, resolve_model_config)
from theroundtaible_tpu.engine.paged_forward import (  # noqa: E402
    forward_paged_hybrid, forward_ragged_hybrid)
from theroundtaible_tpu.engine.pallas import attention as pattn  # noqa: E402
from theroundtaible_tpu.engine.scheduler import SessionScheduler  # noqa: E402
from theroundtaible_tpu.engine.serving_loop import (  # noqa: E402
    RaggedSeq, build_ragged_batch)
from theroundtaible_tpu.utils import telemetry  # noqa: E402

GAP = 1e-3
LOGIT_TOL = 2e-5
PAGE = 16
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 8,
    "num_hidden_layers": 8, "num_key_value_heads": 4, "resid_pdrop": 0,
    "sliding_window": 16, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 512,
    # (the family's defaults are the published widths': stated here)
    "mamba_d_state": 8, "mamba_dt_rank": 4, "head_dim": 64}


def make_engine(**kw):
    config = {"model": "tiny-phi4flash", "dtype": "float32",
              "kv_layout": "paged", "page_size": PAGE, "num_slots": 8,
              "max_seq_len": 512, "seed": 3,
              "sampling": {"temperature": 0.0},
              "mesh": {"data": 1, "model": 1}}
    config.update(kw)
    return InferenceEngine.from_config(config)


@pytest.fixture(scope="module")
def engine():
    return make_engine()


def tokens_of(seed, n):
    return [int(t) for t in
            np.random.RandomState(seed).randint(3, 250, size=(n,))]


def reference_logits(eng, seq, rows, read=ref.as_float32):
    # (padded to one of a few widths: every layer is causal, and the
    # reference's layers are compiled a width)
    padded = np.zeros((-(-len(seq) // 128) * 128,), np.int32)
    padded[:len(seq)] = seq
    return np.asarray(ref.logits_at(eng.params, PUBLISHED, padded, rows,
                                    read=read))


def worst_gap(eng, prompt, served):
    seq = prompt + served
    logits = reference_logits(
        eng, seq, list(range(len(prompt) - 1, len(seq) - 1)))
    return max(float(row.max() - row[tok])
               for row, tok in zip(logits, served))


def serve(eng, name, prompt, n=8):
    _texts, stats = eng.generate_batch_with_stats(
        [(name, prompt)], max_new_tokens=n)
    committed = eng.kv._slots[name].tokens
    assert committed[:len(prompt)] == prompt
    return committed[len(prompt):], stats


def test_the_published_keys_build_the_preset_and_an_unknown_key_fails():
    cfg = resolve_model_config({"model": "tiny-phi4flash",
                                "architecture": dict(PUBLISHED),
                                "max_seq_len": 512})
    assert cfg == get_model_config("tiny-phi4flash")
    m, a, g, c, f = (hybrid.MAMBA1, hybrid.ATTENTION, hybrid.GMU,
                     hybrid.CROSS, hybrid.MLP)
    assert cfg.layer_kinds == (m, f, a, f, m, f, a, f, m, f, a, f, g, f,
                               c, f)
    assert cfg.attention_classes == ((8, 16, 2), (8, None, 1))
    assert (cfg.memory_layer, cfg.last_token_from, cfg.cross_layers) \
        == (8, 12, (14,))
    assert cfg.recurrent and cfg.tie_embeddings and not cfg.rope
    assert not cfg.mamba1_norms and get_model_config(
        "tiny-jamba").mamba1_norms
    with pytest.raises(ValueError, match="unknown keys .*mamba_dt_scale"):
        resolve_model_config({"model": "x", "architecture": dict(
            PUBLISHED, mamba_dt_scale=2)})
    with pytest.raises(ValueError, match="mb_per_layer=4"):
        resolve_model_config({"model": "x", "architecture": dict(
            PUBLISHED, mb_per_layer=4)})
    with pytest.raises(ValueError, match="attention_bias=False"):
        resolve_model_config({"model": "x", "architecture": dict(
            PUBLISHED, attention_bias=False)})
    with pytest.raises(ValueError, match="a multiple of 4"):
        resolve_model_config({"model": "x", "architecture": dict(
            PUBLISHED, num_hidden_layers=10)})
    with pytest.raises(ValueError, match="lacks the key 'sliding_window'"):
        resolve_model_config({"model": "x", "architecture": {
            k: v for k, v in PUBLISHED.items() if k != "sliding_window"}})


@pytest.mark.parametrize("depth,kinds", [
    (8, (3, 2, 1, 1, 1)), (12, (4, 3, 1, 2, 2)), (32, (9, 8, 1, 7, 7))])
def test_the_depth_rule_and_the_published_sizes(depth, kinds):
    whole = get_model_config("phi-4-mini-flash-reasoning")
    assert (whole.num_layers, whole.embed_dim, whole.mlp_dim,
            whole.mamba1_dim, whole.num_heads, whole.num_kv_heads,
            whole.head_dim, whole.vocab_size) == (
        64, 2560, 10240, 5120, 40, 20, 64, 200064)
    assert (whole.page_heads, whole.page_width, whole.lane_pack) \
        == (10, 128, 2)
    got = phi4flash_kinds(depth)
    mixers = got[::2]
    assert got[1::2] == (hybrid.MLP,) * depth
    windows = depth // 4
    assert (mixers.count(hybrid.MAMBA1), windows, 1,
            mixers.count(hybrid.GMU), mixers.count(hybrid.CROSS)) == kinds
    assert mixers.count(hybrid.ATTENTION) == windows + 1
    # ... as the reference derives it, a layer at a time.
    names = {ref.MAMBA: hybrid.MAMBA1, ref.WINDOW: hybrid.ATTENTION,
             ref.FULL: hybrid.ATTENTION, ref.MEMORY: hybrid.GMU,
             ref.CROSS: hybrid.CROSS}
    assert tuple(names[ref.mixer_of(i, depth)]
                 for i in range(depth)) == mixers
    assert mixers[depth // 2] == hybrid.MAMBA1
    assert ref.mixer_of(depth // 2 + 1, depth) == ref.FULL


def test_prologue_then_decode_through_pages_and_slots(engine):
    prompt = [1] + tokens_of(1, 69)
    served, stats = serve(engine, "a", prompt)
    assert len(served) == 7 and stats.prefill_tokens == 70
    assert len(set(served)) > 3           # not the last token read, again
    assert worst_gap(engine, prompt, served) < GAP
    info = engine.describe()
    assert info["paged_decode"] == "pool-direct"
    # The cross layer owns no pool: window layers + 1, a kv PAIR a row.
    assert len(engine.kv.pools) == 2 + 1
    assert engine.kv.pools[0][0].shape[1:] == (PAGE, 2, 128)
    state = engine.hybrid.state
    assert [a.shape for a in state["ssm1"]] == [(9, 1, 8, 1, 128)] * 3
    assert [a.shape[1:3] for a in state["conv1"]] == [(1, 3)] * 3
    layers = engine.params["layers"]
    assert sorted(layers[0]) == ["mamba1", "mlp"]
    assert "dt_norm" not in layers[0]["mamba1"]
    # (entries: a Mamba layer and its MLP one, every other layer one)
    assert "k_proj" in layers[1] and "k_proj" not in layers[-2]
    assert layers[-2]["o_proj"].shape == (4, 128, 64)
    # l0 of the published layers 1, 3, 5 (attention) and 7 (cross).
    assert [float(layers[i]["lambda_init"]) for i in (1, 4, 7, 11)] \
        == pytest.approx([ref.lambda_init(i) for i in (1, 3, 5, 7)])


def test_describe_names_the_seam_and_the_declines(engine):
    info = engine.describe()
    seam = info["seam"]
    assert (seam["from_layer"], seam["layers_above"], seam["cross_layers"],
            seam["memory_layer"]) == (12, 4, 1, 8)
    assert seam["lower_tokens"] > 0
    assert 0 < seam["upper_rows"] == seam["memory_rows"] \
        < seam["lower_tokens"]
    assert seam["shared_pool_positions"] > 0
    assert set(seam) == set(telemetry.SURFACE_BINDINGS["engine_seam"])
    assert info["mamba1"]["scan_runs"] == [1, 1, 1]
    assert info["declines"]["spec_decode"] == "recurrent-state"
    assert "evacuation" in info["declines"]
    assert engine.joins_ragged_alone
    # (what is asked for and declined says why, as for every model with
    # recurrent state: tests/test_jamba_serving.py builds that engine)


# --- the serving path's own forward -----------------------------------------


def _pools(cfg, pages=8):
    return [tuple(jnp.zeros((pages, PAGE, cfg.page_heads, cfg.page_width),
                            jnp.float32) for _ in range(2))
            for _ in cfg.attention_layers]


def prologue_logits(eng, cfg, tokens, state=None, pools=None):
    """(logits [V] of the last position, pools, state) of ONE prologue
    chunk from a zero state through `forward_paged_hybrid`."""
    n = len(tokens)
    state = hybrid.zero_state(cfg, 2) if state is None else state
    pools = _pools(cfg) if pools is None else pools
    table = jnp.arange(1, 9, dtype=jnp.int32)[None] % 8
    logits, pools, state, _c, _n = forward_paged_hybrid(
        eng.params, cfg, jnp.asarray(tokens)[None], jnp.arange(n)[None],
        pools, table, jnp.asarray([n]), state, lengths=jnp.asarray([n]),
        last_pos=jnp.asarray([n - 1]), page_size=PAGE,
        rows=jnp.asarray([0]))
    return np.asarray(logits[0, 0]), pools, state


def step_logits(eng, tokens, n_prompt):
    """Logits [len(tokens) - n_prompt + 1, V]: the prompt as one
    prologue chunk, then every further token as one decode step."""
    cfg = eng.cfg
    first, pools, state = prologue_logits(eng, cfg, tokens[:n_prompt])
    table = jnp.arange(1, 9, dtype=jnp.int32)[None] % 8
    out = [first]
    step = jax.jit(lambda tok, at, pools, state: forward_paged_hybrid(
        eng.params, cfg, tok, at, pools, table, at[0] + 1, state,
        active=jnp.asarray([True]), page_size=PAGE,
        rows=jnp.asarray([0]))[:3])
    for at in range(n_prompt, len(tokens)):
        logits, pools, state = step(jnp.asarray([[tokens[at]]]),
                                    jnp.asarray([[at]]), pools, state)
        out.append(np.asarray(logits[0, 0]))
    return np.stack(out)


def test_prologue_then_decode_logits_and_a_bfloat16_reference_fails(engine):
    """LOGITS of the served forward against the reference's: the last
    position of a 48-token prologue (past the 16 window, across three
    pages) and 20 decode steps, within LOGIT_TOL. The control: the
    REFERENCE with every matrix rounded to bfloat16 leaves it."""
    tokens = [1] + tokens_of(13, 67)
    rows = list(range(47, len(tokens)))
    want = reference_logits(engine, tokens, rows)
    got = step_logits(engine, tokens, 48)
    assert np.abs(got - want).max() < LOGIT_TOL

    def rounded(leaf):
        leaf = jnp.asarray(leaf, jnp.float32)
        return (leaf.astype(jnp.bfloat16).astype(jnp.float32)
                if leaf.ndim >= 2 else leaf)

    off = np.abs(reference_logits(engine, tokens, rows, read=rounded)
                 - want).max()
    assert off > 5 * LOGIT_TOL, off


def _ragged_logits(eng, cfg, runs, t_budget=64):
    """Logits [len(runs), V] of one ragged step from zero states: `runs`
    are token lists, each a sequence from position 0."""
    s_max = 4
    table = np.zeros((s_max - 1, 8), np.int32)
    for i in range(len(runs)):
        table[i, :2] = [1 + 2 * i, 2 + 2 * i]
    b = build_ragged_batch(
        [RaggedSeq(list(r), 0, table[i]) for i, r in enumerate(runs)],
        t_budget=t_budget, s_max=s_max, pages_per_seq=8, scratch_page=0,
        pad_id=0, page_size=PAGE)
    state = hybrid.zero_state(cfg, s_max + 1)
    snaps = {p: state[p] for p in hybrid.SLOT_PARTS if p in state}
    seq_slot = np.full((s_max,), s_max, np.int32)
    seq_slot[:len(runs)] = np.arange(len(runs))
    zeros = jnp.zeros((s_max,), jnp.int32)
    logits, *_ = forward_ragged_hybrid(
        eng.params, cfg, *(jnp.asarray(b[k]) for k in (
            "tokens", "positions")), _pools(cfg), *(jnp.asarray(b[k])
                                                    for k in (
            "tables", "seq_of_block", "block_qstart", "query_offsets",
            "kv_valid", "token_pages", "token_offs", "token_seq",
            "last_rows")), state, jnp.asarray(seq_slot), zeros,
        attn_path="kernel", page_size=PAGE, snaps=snaps, snap_idx=zeros)
    return np.asarray(logits[:len(runs)])


@pytest.mark.parametrize("program", ["prologue", "ragged"])
def test_the_seam_on_against_the_seam_off(engine, program):
    """The layers above the seam on a row's last token alone, against
    every layer on every token (`last_token_from` None: the cross layer
    then takes the prefill kernel / the ragged walk over the whole
    chunk): the served positions' logits are equal to rounding, and the
    reference's."""
    cfg = engine.cfg
    off = dataclasses.replace(cfg, last_token_from=None)
    if program == "prologue":
        tokens = [1] + tokens_of(21, 47)
        on = prologue_logits(engine, cfg, tokens)[0]
        whole = prologue_logits(engine, off, tokens)[0]
        want = reference_logits(engine, tokens, [len(tokens) - 1])[0]
    else:
        runs = [[1] + tokens_of(22, 26), [1] + tokens_of(23, 8)]
        on = _ragged_logits(engine, cfg, runs)
        whole = _ragged_logits(engine, off, runs)
        want = np.stack([reference_logits(engine, r, [len(r) - 1])[0]
                         for r in runs])
    assert np.abs(on - whole).max() < LOGIT_TOL
    assert np.abs(on - want).max() < LOGIT_TOL


# --- the lane-row pairing against two explicit softmaxes ----------------------


def _two_softmaxes(q, k, v, pos, window=None):
    """float32 [n, H, 2D]: for query head h of pair j = h // 2, the
    softmax of q_h over kv head 2 (j // per) + h % 2, times V_p; q
    [n, H, D] at positions `pos`, k / v [L, K, D]."""
    n, heads, d = q.shape
    per = (heads // 2) // (k.shape[1] // 2)
    out = np.zeros((n, heads, 2 * d), np.float32)
    at = np.arange(len(k))[None]
    mask = at <= pos[:, None]
    if window is not None:
        mask &= at > pos[:, None] - window
    for h in range(heads):
        p = (h // 2) // per
        s = np.where(mask, q[:, h] @ k[:, 2 * p + h % 2].T, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        out[:, h] = w @ np.concatenate([v[:, 2 * p], v[:, 2 * p + 1]], -1)
    return out


@pytest.mark.parametrize("kernel", ["dense", "decode", "prologue",
                                    "ragged"])
def test_the_lane_row_pairing_against_the_two_softmax_form(kernel):
    """`diffattn.pack_queries` over a pool that holds a kv pair a
    128-lane row, through the dense form and the three paged kernels
    (interpret mode) at the published group (8 heads over 2 rows: 4):
    A1 V_p at even heads, A2 V_p at odd, against two explicit softmaxes
    a head pair; bfloat16 pages read as the rounded values they hold."""
    h, kh, d, ps = 8, 4, 64, 16
    rng = np.random.default_rng(56)
    pool = [jnp.asarray(rng.standard_normal((12, ps, kh // 2, 2 * d)),
                        jnp.bfloat16) for _ in range(2)]
    kf, vf = (np.asarray(p.astype(jnp.float32)) for p in pool)
    tables = np.zeros((3, 6), np.int32)
    tables[0, :3], tables[1, :4], tables[2, :2] = [1, 2, 3], [4, 5, 6, 7], \
        [8, 9]

    def seq(i, n):
        return (kf[tables[i]].reshape(-1, kh, d)[:n],
                vf[tables[i]].reshape(-1, kh, d)[:n])

    def queries(*shape):
        q = jnp.asarray(rng.standard_normal(shape + (h, d)) * d ** -0.5,
                        jnp.bfloat16)
        return diffattn.pack_queries(q), np.asarray(q.astype(jnp.float32))

    tol = dict(atol=2e-2, rtol=2e-2)
    if kernel == "dense":
        q, qf = queries(1, 24)
        k, v = seq(0, 24)
        mask = np.arange(24)[None, :, None] >= np.arange(24)[None, None, :]
        out = diffattn.dense_attention(
            q, jnp.asarray(k)[None], jnp.asarray(v)[None], jnp.asarray(mask))
        np.testing.assert_allclose(
            np.asarray(out[0], np.float32),
            _two_softmaxes(qf[0], k, v, np.arange(24)), **tol)
    elif kernel == "decode":
        valid = np.array([40, 57, 17], np.int32)
        q, qf = queries(3, 1)
        for window in (None, 16):
            out = pattn.paged_decode_attention(
                q, pool[0], pool[1], jnp.asarray(tables),
                jnp.asarray(valid), sliding_window=window)
            for i in range(3):
                np.testing.assert_allclose(
                    np.asarray(out[i], np.float32), _two_softmaxes(
                        qf[i], *seq(i, valid[i]),
                        np.array([valid[i] - 1]), window), **tol)
    elif kernel == "prologue":
        first, t = np.array([16, 32], np.int32), 16
        q, qf = queries(2, t)
        out = pattn.paged_prefill_attention(
            q, pool[0], pool[1], jnp.asarray(tables[:2]),
            jnp.asarray(first), jnp.asarray(first + t), sliding_window=16)
        assert out is not None
        for i in range(2):
            np.testing.assert_allclose(
                np.asarray(out[i], np.float32), _two_softmaxes(
                    qf[i], *seq(i, first[i] + t), first[i] + np.arange(t),
                    16), **tol)
    else:
        runs = [(21, 19), (1, 56), (9, 8)]
        batch = build_ragged_batch(
            [RaggedSeq([5] * n, pos, tables[i])
             for i, (n, pos) in enumerate(runs)],
            t_budget=64, s_max=4, pages_per_seq=6, scratch_page=0,
            pad_id=0, page_size=ps)
        q, qf = queries(64)
        out = np.asarray(pattn.ragged_paged_attention(
            q, pool[0], pool[1], *(jnp.asarray(batch[k]) for k in (
                "tables", "seq_of_block", "block_qstart", "query_offsets",
                "kv_valid"))), np.float32)
        row = 0
        for i, (n, pos) in enumerate(runs):
            np.testing.assert_allclose(
                out[row:row + n], _two_softmaxes(
                    qf[row:row + n], *seq(i, pos + n), pos + np.arange(n)),
                **tol)
            row += -(-n // 8) * 8


def test_the_pair_norm_and_lambda_against_the_equations():
    cfg = get_model_config("tiny-phi4flash")
    rng = np.random.default_rng(5)
    layer = {"lambda_q1": jnp.asarray(rng.normal(0, .1, 64), jnp.float32),
             "lambda_k1": jnp.asarray(rng.normal(0, .1, 64), jnp.float32),
             "lambda_q2": jnp.asarray(rng.normal(0, .1, 64), jnp.float32),
             "lambda_k2": jnp.asarray(rng.normal(0, .1, 64), jnp.float32),
             "lambda_init": jnp.asarray(diffattn.lambda_init(5)),
             "sub_norm": jnp.asarray(rng.normal(1, .1, 128), jnp.float32)}
    out = rng.standard_normal((1, 3, 8, 128)).astype(np.float32)
    got = np.asarray(diffattn.combine(jnp.asarray(out), layer, cfg))
    l0 = 0.8 - 0.6 * np.exp(-0.3 * 5)
    lam = (np.exp(np.dot(layer["lambda_q1"], layer["lambda_k1"]))
           - np.exp(np.dot(layer["lambda_q2"], layer["lambda_k2"])) + l0)
    diff = out[:, :, 0::2] - lam * out[:, :, 1::2]
    want = diff / np.sqrt((diff ** 2).mean(-1, keepdims=True) + 1e-5) \
        * np.asarray(layer["sub_norm"]) * (1 - l0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# --- the mixer without Jamba's inner norms ------------------------------------


@pytest.mark.parametrize("model", ["tiny-phi4flash", "tiny-jamba"])
def test_mamba1_with_and_without_the_inner_norms(model):
    """`mamba1._selective` behind `cfg.mamba1_norms`: off, the mixer is
    the reference recurrence of this family (and `emit` hands out m, the
    scan output before the gate); on — tiny-jamba, unchanged — the
    layer keeps its three norms and its own reference holds it."""
    cfg = get_model_config(model)
    e, d = cfg.embed_dim, cfg.mamba1_dim
    key = jax.random.PRNGKey(7)
    layer = hybrid.init_layer(cfg, hybrid.MAMBA1, key, jnp.float32)
    assert ("dt_norm" in layer) == cfg.mamba1_norms == (model
                                                        == "tiny-jamba")
    # (make the norms matter where they exist)
    layer = {k: (v * 1.5 if k.endswith("_norm") else v)
             for k, v in layer.items()}
    h = jax.random.normal(jax.random.fold_in(key, 1), (1, 24, e))
    state = mamba1.zero_state(dataclasses.replace(
        cfg, layer_kinds=(hybrid.MAMBA1,)), 2)
    out, _s, _c, _snaps, m = mamba1.mamba1_prefill(
        h, layer, cfg, state["ssm1"][0], state["conv1"][0], 0,
        jnp.asarray([0]), jnp.asarray([24]), emit=True)
    plain = mamba1.mamba1_prefill(
        h, layer, cfg, state["ssm1"][0], state["conv1"][0], 0,
        jnp.asarray([0]), jnp.asarray([24]))
    assert len(plain) == 4
    np.testing.assert_array_equal(np.asarray(plain[0]), np.asarray(out))
    sizes = (d, cfg.ssm_state, cfg.conv_kernel, cfg.dt_rank)
    if model == "tiny-phi4flash":
        # The reference layer norms its input; hand it `h` as the normed
        # input of an identity norm by giving x = h, weight 1, bias 0 —
        # LayerNorm(h) != h, so compare the mixer alone instead.
        ident = dict(layer, norm=jnp.ones((e,)), norm_b=jnp.zeros((e,)))
        x = h[0]
        normed = ref._layer_norm(x, 1.0, 0.0, 1e-5)
        got_x, got_m = ref.mamba_layer(ident, x, eps=1e-5,
                                       read=ref.as_float32, sizes=sizes)
        out_n, *_r, m_n = mamba1.mamba1_prefill(
            normed[None], layer, cfg, state["ssm1"][0], state["conv1"][0],
            0, jnp.asarray([0]), jnp.asarray([24]), emit=True)
        np.testing.assert_allclose(np.asarray(out_n[0]),
                                   np.asarray(got_x - x), atol=2e-6)
        np.testing.assert_allclose(np.asarray(m_n[0]), np.asarray(got_m),
                                   atol=2e-6)
    else:
        from configs import jamba_reference as jref
        x = h[0]
        normed = jref._normed(x, jnp.ones((e,)), 1e-6)
        ident = dict(layer, norm=jnp.ones((e,)))
        with_norms = jref.mamba_layer(ident, x, eps=1e-6, norms=True,
                                      read=jref.as_float32, sizes=sizes)
        without = jref.mamba_layer(ident, x, eps=1e-6, norms=False,
                                   read=jref.as_float32, sizes=sizes)
        out_n = mamba1.mamba1_prefill(
            normed[None], layer, cfg, state["ssm1"][0], state["conv1"][0],
            0, jnp.asarray([0]), jnp.asarray([24]))[0]
        np.testing.assert_allclose(np.asarray(out_n[0]),
                                   np.asarray(with_norms - x), atol=2e-6)
        assert np.abs(np.asarray(without - with_norms)).max() > 1e-3
    assert m.shape == (1, 24, d) and m.dtype == jnp.float32


# --- snapshots ----------------------------------------------------------------


def test_a_join_restored_from_a_snapshot_equals_a_join_from_zero(engine):
    """A donor of 70 tokens leaves a snapshot at the 64-token page
    boundary. A taker of its prefix starts from that state and the
    donor's pages and prefills 40 tokens of its own: the logits of its
    last position are the reference's, which scans from zero."""
    seen = []
    program = engine._prefill_step_hybrid

    def spy(*args, **kw):
        out = program(*args, **kw)
        seen.append(np.asarray(out[0])[0])
        return out

    engine._prefill_step_hybrid = spy
    try:
        donor = [1] + tokens_of(14, 69)
        serve(engine, "donor", donor, n=2)
        assert engine.hybrid.holds(donor, 64)
        taker = donor[:64] + tokens_of(16, 40)
        want = reference_logits(engine, taker, [103])[0]
        before = engine.hybrid.describe()
        served, stats = serve(engine, "taker", taker, n=4)
        after = engine.hybrid.describe()
        assert stats.prefill_tokens == 40      # the prefix: reused, by id
        assert after["reused_tokens"] - before["reused_tokens"] == 64
        assert after["restore_bytes"] - before["restore_bytes"] \
            == after["bytes_per_state"] == 3 * (8 + 3) * 128 * 4
        assert np.abs(seen[-1] - want).max() < LOGIT_TOL
        assert worst_gap(engine, taker, served) < GAP
    finally:
        engine._prefill_step_hybrid = program


# --- through the scheduler ----------------------------------------------------

KNIGHTS = ["lancelot", "galahad", "percival"]


def cue(knight, round_no):
    return [3 + ord(c) for c in f"\n[r{round_no}] {knight}: "]


def discussion(sched, eng, sid, opening, rounds=2, new=10):
    transcript, served = list(opening), []
    for r in range(1, rounds + 1):
        turns = [(k, transcript + cue(k, r)) for k in KNIGHTS]
        sched.submit(sid, turns, max_new_tokens=new)
        for k, p in turns:
            name = next(n for n in eng.kv._slots
                        if n.endswith(k) and sid in n)
            answer = eng.kv._slots[name].tokens[len(p):]
            served.append((p, answer))
            transcript = transcript + cue(k, r) + answer
    for p, a in served:
        assert worst_gap(eng, p, a) < GAP
    return served


def test_three_knights_two_rounds_with_joins_mid_decode(engine):
    eng = engine
    sched = SessionScheduler(eng)
    telemetry.arm()
    t_a = time.monotonic()
    seam0 = dict(eng.describe()["seam"])
    errors = []

    def run(sid, seed, n_open):
        try:
            discussion(sched, eng, sid, [1] + tokens_of(seed, n_open))
        except BaseException as e:  # noqa: BLE001 — asserted below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(f"s{i}", 30 + i,
                                                  40 + 30 * i))
               for i in range(2)]
    for t in threads:
        t.start()
        time.sleep(0.3)
    for t in threads:
        t.join()
    spans = telemetry.spans_between(t_a, time.monotonic())
    telemetry.disarm()
    sched.close()
    assert not errors, errors
    d = sched.describe()
    assert d["failed"] == 0 and d["completed"] == 4
    assert d["ragged_joins"] == d["admitted"] == 4
    segs = [s["attrs"] for s in spans if s["rung"] == "segment"][1:]
    names = {"lower_tokens", "upper_rows", "memory_rows",
             "shared_pool_positions"}
    assert segs and all(names | {"scan_tokens", "pages_held"} <= set(a)
                        for a in segs)
    seam = eng.describe()["seam"]
    moved = {k: seam[k] - seam0[k] for k in names}
    # Joins ran the layers above the seam on each sequence's last token:
    # far fewer rows than tokens; the cross layer read the pool it does
    # not own at every join AND every decode step.
    assert 0 < moved["upper_rows"] == moved["memory_rows"]
    assert moved["upper_rows"] * 4 < moved["lower_tokens"]
    assert moved["shared_pool_positions"] > moved["lower_tokens"]
    for k in names:
        assert 0 < sum(a[k] for a in segs) <= moved[k]
    plain = [a for a in segs if a["kind"] == "plain"]
    assert plain and all(a["lower_tokens"] == 0 == a["upper_rows"]
                         and a["shared_pool_positions"] > 0
                         for a in plain if a["steps"])
    # The scan ran below the seam only: tokens x 3 Mamba layers.
    assert sum(a["scan_tokens"] for a in segs) \
        == 3 * sum(a["lower_tokens"] for a in segs)
    counters = telemetry.REGISTRY.snapshot()["counters"]
    for k in names:
        assert any(c.startswith(f"roundtable_seam_{k}_total")
                   and v >= moved[k] for c, v in counters.items())
    assert eng.describe()["prefix_cache"]["hits"] > 0
