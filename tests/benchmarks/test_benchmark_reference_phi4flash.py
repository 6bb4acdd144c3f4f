"""`phi4flash` (Phi-4-mini-flash-reasoning): the plain reference
(benchmarks/configs/phi4flash_reference.py — float32 highest, the
recurrence a token at a time, two explicit softmaxes a differential
head, no cache, no seam) against a SECOND derivation that shares nothing
with it: the differential head, the gated memory unit, the LayerNorm and
the Mamba mixer without inner norms written out in numpy float64 from
the equations, and the depth rule stated as index sets at L = 8, 12, 32
(9 / 8 / 1 / 7 / 7 kinds and 3.85 G parameters at the published sizes);
the reference's controls' seams (a matrix rounded on the way); the
comparison that decides `correct`, and that the token rule reads the
differential form, the memory and the cross layers' pool through the
seeded weights.

Tolerances, on LOGITS whose spread over the vocabulary is about 0.16:
the reference against the float64 equations 1e-5 on a layer's output of
order 1; with bfloat16 matrices the reference's logits move by over
5e-3, with float8 by over 2e-2: both leave the 2e-5 the serving tests
hold the float32 program to (tests/test_phi4flash_serving.py)."""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths
from configs import phi4flash_reference as ref
from harness import correct, sambay_cost
from theroundtaible_tpu.engine import fleet
from theroundtaible_tpu.engine.models.common import init_params, param_count
from theroundtaible_tpu.engine.models.registry import get_model_config

CELL = os.path.join(bench_paths.BENCH, "configs", "phi-4-mini-flash.json")
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 8,
    "num_hidden_layers": 8, "num_key_value_heads": 4, "resid_pdrop": 0,
    "sliding_window": 16, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 512, "mamba_d_state": 8,
    "mamba_dt_rank": 4, "head_dim": 64}


@pytest.fixture(scope="module")
def tiny():
    cfg = get_model_config("tiny-phi4flash")
    return cfg, init_params(cfg, jax.random.PRNGKey(5), jnp.float32)


@pytest.fixture(scope="module")
def cell():
    with open(CELL, encoding="utf-8") as f:
        return json.load(f)


def tokens_of(seed, n):
    return [int(t) for t in
            np.random.RandomState(seed).randint(3, 250, size=(n,))]


def reference(params, tokens, rows, **kw):
    return np.asarray(ref.logits_at(params, PUBLISHED, np.asarray(tokens),
                                    rows, **kw))


# --- the reference against the equations in float64 --------------------------


def _f64(a):
    return np.asarray(a, np.float64)


def _ln(a, layer, eps=1e-5):
    mu = a.mean(-1, keepdims=True)
    var = ((a - mu) ** 2).mean(-1, keepdims=True)
    return (a - mu) / np.sqrt(var + eps) * _f64(layer["norm"]) \
        + _f64(layer["norm_b"])


def _silu(a):
    return a / (1.0 + np.exp(-a))


def _mamba64(layer, x):
    """x [T, E] float64 -> (x + Mamba(LN x), m), loops over tokens; NO
    inner norms."""
    t, (d, n, r) = x.shape[0], (128, 8, 4)
    h = _ln(x, layer)
    uz = h @ _f64(layer["in_proj"])
    u, z = uz[:, :d], uz[:, d:]
    w, bias = _f64(layer["conv_w"]), _f64(layer["conv_b"])
    a = -np.exp(_f64(layer["A_log"])).T                       # [d, N]
    state, out, mem = np.zeros((d, n)), np.zeros_like(x), np.zeros((t, d))
    for i in range(t):
        acc = bias.copy()
        for j in range(4):
            if i - 3 + j >= 0:
                acc += w[j] * u[i - 3 + j]
        c = _silu(acc)
        xp = c @ _f64(layer["x_proj"])
        dl, b, cm = xp[:r], xp[r:r + n], xp[r + n:]
        dt = np.log1p(np.exp(dl @ _f64(layer["dt_proj"])
                             + _f64(layer["dt_bias"])))
        state = np.exp(dt[:, None] * a) * state \
            + (dt * c)[:, None] * b[None, :]
        mem[i] = state @ cm + _f64(layer["D"]) * c
        out[i] = x[i] + (mem[i] * _silu(z[i])) @ _f64(layer["out_proj"])
    return out, mem


def _differential64(layer, x, depth, window=None, kv=None):
    """One differential layer a QUERY at a time: for token t and
    differential head j the two softmaxes over the positions it may see,
    the 128-wide value pair, the subtraction, the norm, (1 - l0)."""
    t = x.shape[0]
    h = _ln(x, layer)
    q = np.einsum("te,ehd->thd", h, _f64(layer["q_proj"])) \
        + _f64(layer["q_bias"])
    if kv is None:
        k = np.einsum("te,ekd->tkd", h, _f64(layer["k_proj"])) \
            + _f64(layer["k_bias"])
        v = np.einsum("te,ekd->tkd", h, _f64(layer["v_proj"])) \
            + _f64(layer["v_bias"])
    else:
        k, v = kv
    heads, d = q.shape[1], q.shape[2]
    pairs = k.shape[1] // 2
    l0 = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = (math.exp(float(_f64(layer["lambda_q1"]) @ _f64(layer["lambda_k1"])))
           - math.exp(float(_f64(layer["lambda_q2"])
                            @ _f64(layer["lambda_k2"]))) + l0)
    out = x + _f64(layer["o_bias"])
    for i in range(t):
        lo = 0 if window is None else max(0, i - window + 1)
        for j in range(heads // 2):
            p = j * pairs // (heads // 2)
            v_p = np.concatenate([v[lo:i + 1, 2 * p],
                                  v[lo:i + 1, 2 * p + 1]], -1)

            def soft(qh, kh):
                s = k[lo:i + 1, kh] @ q[i, qh] / math.sqrt(d)
                e = np.exp(s - s.max())
                return e / e.sum()

            diff = soft(2 * j, 2 * p) @ v_p \
                - lam * (soft(2 * j + 1, 2 * p + 1) @ v_p)
            o = diff / np.sqrt((diff ** 2).mean() + 1e-5) \
                * _f64(layer["sub_norm"]) * (1.0 - l0)
            out[i] += o @ _f64(layer["o_proj"])[j]
    return out, (k, v)


def _perturbed(layer, seed, names):
    key = jax.random.PRNGKey(seed)
    layer = dict(layer)
    for i, name in enumerate(names):
        layer[name] = layer[name] + 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), layer[name].shape)
    return layer


def test_the_layers_are_the_equations(tiny):
    """A Mamba layer (conv bias, D and the LayerNorm's bias away from
    their seeds) with its memory, a window layer, the full layer, the
    cross layer over the full layer's keys and values, and the memory
    unit, each against float64 loops written from the equations."""
    _cfg, params = tiny
    layers = list(ref.published_layers(params, ref.sizes_of(PUBLISHED)))
    assert [k for k, _m, _f in layers] == [
        ref.MAMBA, ref.WINDOW, ref.MAMBA, ref.WINDOW, ref.MAMBA, ref.FULL,
        ref.MEMORY, ref.CROSS]
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (40, 64)))
    xj, kw = jnp.asarray(x), dict(eps=1e-5, read=ref.as_float32)
    mamba = _perturbed(layers[4][1], 4, ("D", "conv_b", "norm", "norm_b"))
    with jax.default_matmul_precision("highest"):
        got, mem = ref.mamba_layer(mamba, xj, sizes=(128, 8, 4, 4), **kw)
    want, mem64 = _mamba64(mamba, _f64(x))
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    assert np.abs(np.asarray(mem) - mem64).max() < 1e-5

    diff = ("sub_norm", "norm_b", "lambda_q1", "lambda_k1", "lambda_q2",
            "lambda_k2", "q_bias", "o_bias")
    window = _perturbed(layers[3][1], 5, diff)
    full = _perturbed(layers[5][1], 6, diff)
    cross = _perturbed(layers[7][1], 7, diff)
    with jax.default_matmul_precision("highest"):
        k_w, v_w = ref._kv_of(window, xj, **kw)
        got_w = ref.differential_layer(window, xj, k_w, v_w, window=16,
                                       depth=3, **kw)
        k_f, v_f = ref._kv_of(full, xj, **kw)
        got_f = ref.differential_layer(full, xj, k_f, v_f, window=None,
                                       depth=5, **kw)
        got_c = ref.differential_layer(cross, xj, k_f, v_f, window=None,
                                       depth=7, **kw)
        unit = ref.memory_layer(layers[6][1], xj, mem, **kw)
    want_w, _kv = _differential64(window, _f64(x), 3, window=16)
    want_f, kv = _differential64(full, _f64(x), 5)
    want_c, _kv = _differential64(cross, _f64(x), 7, kv=kv)
    assert np.abs(np.asarray(got_w) - want_w).max() < 1e-5
    assert np.abs(np.asarray(got_f) - want_f).max() < 1e-5
    assert np.abs(np.asarray(got_c) - want_c).max() < 1e-5
    # (the window matters at 40 tokens, and the cross layer is not the
    # full layer again)
    assert np.abs(want_w - _differential64(window, _f64(x), 3)[0]).max() \
        > 1e-3
    assert np.abs(want_c - want_f).max() > 1e-3
    g = layers[6][1]
    want_u = x + (mem64 * _silu(_ln(_f64(x), g) @ _f64(g["in_proj"]))) \
        @ _f64(g["out_proj"])
    assert np.abs(np.asarray(unit) - want_u).max() < 1e-5


@pytest.mark.parametrize("depth,kinds", [
    (8, (3, 2, 1, 1, 1)), (12, (4, 3, 1, 2, 2)), (32, (9, 8, 1, 7, 7))])
def test_the_depth_rule_as_index_sets(depth, kinds):
    """The rule stated a second way: sets of layer indices."""
    half = depth // 2
    mamba = set(range(0, half + 1, 2))
    window = set(range(1, half, 2))
    full = {half + 1}
    memory = set(range(half + 2, depth, 2))
    cross = set(range(half + 3, depth, 2))
    assert tuple(map(len, (mamba, window, full, memory, cross))) == kinds
    assert mamba | window | full | memory | cross == set(range(depth))
    for i in range(depth):
        want = (ref.MAMBA if i in mamba else ref.WINDOW if i in window
                else ref.FULL if i in full else ref.MEMORY if i in memory
                else ref.CROSS)
        assert ref.mixer_of(i, depth) == want, i
    s = sambay_cost.sizes({"hidden_size": 2560, "num_hidden_layers": depth,
                           "num_attention_heads": 40,
                           "num_key_value_heads": 20, "sliding_window": 512,
                           "intermediate_size": 10240})
    assert (s["mamba"], s["window_layers"], s["full"], s["gmu"],
            s["cross"]) == kinds


def test_the_whole_models_arithmetic(cell):
    """3.85 G parameters at the published sizes and depth; the cost
    file, the fleet planner's closed form and the tree the engine would
    build agree to the parameter, at the cell's depth and whole."""
    assert ref.sizes_of(cell) == {
        "depth": 12, "eps": 1e-5, "heads": 40, "kv_heads": 20,
        "window": 512, "d_inner": 5120, "d_state": 16, "d_conv": 4,
        "dt_rank": 160}
    whole = get_model_config("phi-4-mini-flash-reasoning")
    tree = jax.eval_shape(lambda k: init_params(whole, k, jnp.bfloat16),
                          jax.random.PRNGKey(0))
    n = param_count(tree)
    assert 3.850e9 < n < 3.855e9
    assert n == sambay_cost.param_count(dict(cell, num_hidden_layers=32)) \
        == fleet.estimate_param_count(whole)
    from theroundtaible_tpu.engine.models.registry import (
        resolve_model_config)
    cut = resolve_model_config({"model": "phi-4-mini-flash",
                                "architecture": dict(
                                    cell["engine"]["architecture"]),
                                "max_seq_len": 8192})
    assert (cut.num_layers, cut.last_token_from, cut.memory_layer) \
        == (24, 16, 12)
    assert fleet.estimate_param_count(cut) \
        == sambay_cost.param_count(cell) == 1_778_306_310


def test_what_follows_a_row_never_reaches_the_reference(tiny):
    _cfg, params = tiny
    tokens = tokens_of(1, 64)
    short = reference(params, tokens[:40], [10, 39])
    padded = reference(params, tokens[:40] + [0] * 24, [10, 39])
    assert np.abs(short - padded).max() < 1e-6


def test_a_rounded_matrix_moves_the_reference(tiny):
    _cfg, params = tiny
    tokens = tokens_of(2, 48)
    base = reference(params, tokens, [47])

    def through(dtype):
        return lambda leaf: jnp.asarray(leaf, jnp.float32).astype(
            dtype).astype(jnp.float32)

    assert np.abs(reference(params, tokens, [47],
                            read=through(jnp.float8_e4m3fn))
                  - base).max() > 2e-2
    assert np.abs(reference(params, tokens, [47],
                            read=through(jnp.bfloat16)) - base).max() > 5e-3
    with pytest.raises(ValueError, match="mb_per_layer 2"):
        ref.logits_at(params, dict(PUBLISHED, mb_per_layer=4), [1], [0])
    with pytest.raises(ValueError, match="% 4"):
        ref.logits_at(params, dict(PUBLISHED, num_hidden_layers=10), [1],
                      [0])


# --- the comparison that decides `correct` -----------------------------------


def test_correct_is_decided_on_this_reference(tiny):
    _cfg, params = tiny
    prompt = [1] + tokens_of(3, 40)
    best = int(reference(params, prompt, [len(prompt) - 1])[0].argmax())
    good = correct.score(ref, params, PUBLISHED, [
        {"what": "good", "prompt": prompt, "ids": [best]}])
    bad = correct.score(ref, params, PUBLISHED, [
        {"what": "bad", "prompt": prompt, "ids": [(best + 7) % 512]}])
    assert good["correct"] and good["worst_gap_sigmas"] == 0.0
    assert not bad["correct"]


def test_each_new_mechanism_moves_the_logits_and_the_upper_half_the_tokens(
        tiny):
    """What the seeded recipe is for. A model computed WITHOUT one of
    this family's mechanisms — the memory (the unit's out-projection
    zeroed), the cross layer, the LayerNorms' biases — moves the
    reference's logits by hundreds of times the 2e-5 the float32 program
    is held to (tests/test_phi4flash_serving.py): the logit comparisons
    see each. The harness's TOKEN rule (0.25 sigma of the reference's
    maximum) is coarser: it refuses 16 greedy tokens of a model whose
    whole upper half — the four layers above the seam — adds nothing,
    and passes the model itself at 0.0."""
    _cfg, params = tiny
    prompt, ids = [1] + tokens_of(9, 95), []
    for _ in range(16):                       # (one length: one trace)
        at = len(prompt) + len(ids) - 1
        seq = (prompt + ids + [0] * 16)[:112]
        ids.append(int(reference(params, seq, [at])[0].argmax()))
    assert len(set(ids)) > 4 and ids[0] != prompt[-1]
    served = [{"what": "greedy-0", "prompt": prompt, "ids": ids}]
    base = reference(params, prompt, [95])

    def without(*changes):
        changed = jax.tree_util.tree_map(lambda a: a, params)
        for change in changes:
            change(changed["layers"])
        return changed

    def no_memory(layers):
        layers[9] = dict(layers[9], out_proj=layers[9]["out_proj"] * 0)

    def no_cross(layers):
        layers[11] = dict(layers[11], o_proj=layers[11]["o_proj"] * 0,
                          o_bias=layers[11]["o_bias"] * 0)

    def no_mlps_above(layers):
        for i in (10, 12):
            layers[i] = dict(layers[i],
                             down_proj=layers[i]["down_proj"] * 0)

    def no_bias(layers):
        for i, layer in enumerate(layers):
            if "norm_b" in layer:
                layers[i] = dict(layer, norm_b=layer["norm_b"] * 0)

    for change in (no_memory, no_cross, no_bias):
        moved = np.abs(reference(without(change), prompt, [95])
                       - base).max()
        assert moved > 1e-2, (change.__name__, moved)

    def control(tree):
        class Control:
            @staticmethod
            def logits_at(_p, c, seq, rows):
                return ref.logits_at(tree, c, seq, rows)
        return correct.score(Control, params, PUBLISHED, served)

    assert control(params)["worst_gap_sigmas"] == 0.0
    off = control(without(no_memory, no_cross, no_mlps_above))
    assert not off["correct"], off["worst_gap_sigmas"]
