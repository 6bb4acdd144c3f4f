"""The plain reference of the `lfm2_moe` decoder
(benchmarks/configs/lfm2_reference.py) against its equations written
again in float64 loops; what moves it (a rounded matrix, the router's
bias, the head norms) and what must not (what follows a row); the
configuration file's keys, cut and arithmetic; `correct` decided on this
reference through the seeded weights; and the two new readers on spans
and a trace made by hand. TOL as the serving tests hold it: 2e-5 between
float32 sums in another order and the next precision down."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths
from configs import lfm2_reference as ref
from harness import correct, shortconv_cost
from theroundtaible_tpu.engine.models.common import init_params
from theroundtaible_tpu.engine.models.registry import get_model_config

TOL = 2e-5
CELL = os.path.join(bench_paths.BENCH, "configs",
                    "lfm2-24b-a2b-stage0.json")
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 64,
    "intermediate_size": 128,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 32, "norm_eps": 1e-5, "norm_topk_prob": True,
    "num_attention_heads": 8, "num_dense_layers": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 6,
    "num_key_value_heads": 4,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 512,
    "head_dim": 64}


@pytest.fixture(scope="module")
def tiny():
    cfg = get_model_config("tiny-lfm2")
    return cfg, init_params(cfg, jax.random.PRNGKey(5), jnp.float32)


@pytest.fixture(scope="module")
def cell():
    with open(CELL, encoding="utf-8") as f:
        return json.load(f)


def tokens_of(seed, n):
    return [int(t) for t in
            np.random.RandomState(seed).randint(3, 250, size=(n,))]


def reference(params, tokens, rows, config=PUBLISHED, **kw):
    return np.asarray(ref.logits_at(params, config, np.asarray(tokens),
                                    rows, **kw))


# --- the reference against the equations in float64 --------------------------


def _f64(a):
    return np.asarray(a, np.float64)


def _norm(a, w, eps=1e-5):
    return a / np.sqrt((a * a).mean(-1, keepdims=True) + eps) * _f64(w)


def _conv64(layer, x):
    h = _norm(x, layer["norm"])
    bcu = h @ _f64(layer["in_proj"])
    e = x.shape[1]
    g, out = bcu[:, :e] * bcu[:, 2 * e:], np.zeros_like(x)
    w = _f64(layer["conv_w"])
    for t in range(len(x)):
        conv = sum(w[j] * g[t - 2 + j] for j in range(3) if t - 2 + j >= 0)
        out[t] = (bcu[t, e:2 * e] * conv) @ _f64(layer["out_proj"])
    return x + out


def _attention64(layer, x, theta=1e6):
    h = _norm(x, layer["norm"])
    w_q, w_k, w_v, w_o = (_f64(layer[k]) for k in (
        "q_proj", "k_proj", "v_proj", "o_proj"))
    heads, d = w_q.shape[1:]
    group = heads // w_k.shape[1]
    freq = theta ** (-2.0 * np.arange(d // 2) / d)

    def turn(a, t):
        lo, hi = a[:d // 2], a[d // 2:]
        c, s = np.cos(t * freq), np.sin(t * freq)
        return np.concatenate([lo * c - hi * s, hi * c + lo * s])

    out = np.zeros_like(x)
    for i in range(heads):
        q = _norm(h @ w_q[:, i], layer["q_norm"])
        k = _norm(h @ w_k[:, i // group], layer["k_norm"])
        v = h @ w_v[:, i // group]
        for t in range(len(x)):
            s = np.array([turn(q[t], t) @ turn(k[j], j) for j in
                          range(t + 1)]) * d ** -0.5
            p = np.exp(s - s.max())
            out[t] += ((p / p.sum()) @ v[:t + 1]) @ w_o[i]
    return x + out


def _experts64(layer, x, top_k=2):
    h = _norm(x, layer["norm"])
    s = 1.0 / (1.0 + np.exp(-(h @ _f64(layer["router"]))))
    out = np.zeros_like(x)
    stack = {k: _f64(v) for k, v in layer["experts"].items()}
    for t in range(len(x)):
        chosen = np.argsort(-(s[t] + _f64(layer["router_bias"])),
                            kind="stable")[:top_k]
        for e in chosen:
            a = h[t] @ stack["gate"][e]
            a = a / (1.0 + np.exp(-a)) * (h[t] @ stack["up"][e])
            out[t] += s[t, e] / s[t, chosen].sum() * (a @ stack["down"][e])
    return x + out


def test_every_layer_is_its_equations(tiny):
    """The conv layer, the attention layer with its head norms away from
    ones, and the experts with a bias large enough to change the choice
    (and, used for the choice alone, not the weights), each against
    float64 loops written from the equations."""
    _cfg, params = tiny
    layers = params["layers"]
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (24, 64)))
    key = jax.random.PRNGKey(4)
    att = dict(layers[4])
    for i, name in enumerate(("q_norm", "k_norm", "norm")):
        att[name] = att[name] + 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), att[name].shape)
    moe = dict(layers[5], router_bias=jax.random.normal(
        jax.random.fold_in(key, 9), (8,)) * 0.5)
    freq = jnp.asarray(1e6 ** (-2.0 * np.arange(32) / 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        conv = ref.conv_layer(layers[0], jnp.asarray(x), taps=3, eps=1e-5,
                              read=ref.as_float32)
        attn = ref.attention_layer(att, jnp.asarray(x), freq, eps=1e-5,
                                   read=ref.as_float32)
        ffn = ref.experts_layer(moe, jnp.asarray(x), top_k=2, scale=1.0,
                                eps=1e-5, read=ref.as_float32)
        plain = ref.experts_layer(layers[5], jnp.asarray(x), top_k=2,
                                  scale=1.0, eps=1e-5, read=ref.as_float32)
    assert np.abs(np.asarray(conv) - _conv64(layers[0], _f64(x))).max() < 1e-5
    assert np.abs(np.asarray(attn) - _attention64(att, _f64(x))).max() < 1e-5
    assert np.abs(np.asarray(ffn) - _experts64(moe, _f64(x))).max() < 1e-5
    assert np.abs(np.asarray(ffn) - np.asarray(plain)).max() > 1e-3


def test_what_follows_a_row_never_reaches_the_reference(tiny):
    _cfg, params = tiny
    tokens = tokens_of(1, 64)
    short = reference(params, tokens[:40], [10, 39])
    padded = reference(params, tokens[:40] + [0] * 24, [10, 39])
    other = reference(params, tokens, [10, 39])
    # (another length is another blocking of the same float32 sums)
    assert np.abs(short - padded).max() < 1e-6
    assert np.abs(short - other).max() < 1e-6


def test_a_rounded_matrix_moves_the_reference(tiny):
    _cfg, params = tiny
    tokens = tokens_of(2, 48)
    base = reference(params, tokens, [47])

    def through(dtype):
        return lambda leaf: jnp.asarray(leaf, jnp.float32).astype(
            dtype).astype(jnp.float32)

    assert np.abs(reference(params, tokens, [47],
                            read=through(jnp.float8_e4m3fn))
                  - base).max() > 1e-2
    assert np.abs(reference(params, tokens, [47],
                            read=through(jnp.bfloat16)) - base).max() > TOL


def test_the_reference_refuses_what_it_is_not_written_for(tiny):
    _cfg, params = tiny
    for change, complaint in [
            ({"layer_types": ["conv"] * 5 + ["sliding_attention"]},
             "conv and full_attention"),
            ({"num_hidden_layers": 5}, "has 6 entries"),
            ({"conv_bias": True}, "conv_bias false"),
            ({"use_expert_bias": False}, "use_expert_bias true")]:
        with pytest.raises(ValueError, match=complaint):
            ref.logits_at(params, dict(PUBLISHED, **change), [1, 2], [1])


# --- the configuration file --------------------------------------------------


def test_the_file_keeps_every_published_key_and_says_what_it_assumed(cell):
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776,
        "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv",
                                           "conv"] * 9 + ["full_attention",
                                                          "conv"],
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1536, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    assert len(published["layer_types"]) == 40
    assert cell["source"].endswith("LFM2-24B-A2B/blob/main/config.json")
    assert cell["reduced"] == ["num_hidden_layers", "layer_types"]
    for key, value in published.items():
        if key not in cell["reduced"]:
            assert cell[key] == value, key
            assert cell["engine"]["architecture"][key] == value, key
    assert set(cell["engine"]["architecture"]) == set(published)
    assert cell["published"] == {k: published[k] for k in cell["reduced"]}
    assert cell["num_hidden_layers"] == 10
    assert cell["layer_types"] == published["layer_types"][:10]
    assert cell["layer_types"].count("full_attention") == 2
    # every published width, unchanged
    assert (cell["hidden_size"], cell["num_attention_heads"],
            cell["num_key_value_heads"], cell["intermediate_size"],
            cell["num_experts"], cell["moe_intermediate_size"],
            cell["num_experts_per_tok"], cell["conv_L_cache"],
            cell["vocab_size"]) == (2048, 32, 8, 11776, 64, 1536, 4, 3,
                                    65536)
    for key in ("head_dim", "tie_word_embeddings", "in_proj_order",
                "state_dtype", "weights", "page_cell", "max_seq_len",
                "state_snapshot_bytes", "spec_decode"):
        assert key in cell["assumed"], key
    assert "first of four pipeline stages" in cell["deployment"]
    engine = cell["engine"]
    assert (engine["num_slots"], engine["num_pages"],
            engine["prefix_cache_pages"], engine["page_size"],
            engine["dtype"], engine["quant"], engine["spec_decode"]) == (
        16, 640, 448, 128, "bfloat16", "none", False)
    # a state for every page boundary the pool can hold
    assert engine["state_snapshot_bytes"] \
        // shortconv_cost.state_bytes_per_sequence(cell) > 640


def test_the_cuts_arithmetic(cell):
    """ISSUE 52's numbers: 5,267,090,176 parameters (10.53 GB), a conv
    mixer, an attention layer, a dense SwiGLU, an expert; 2 KB a
    position a layer; 64 KB of state a sequence; some 6.9 GB a step."""
    e = 2048
    assert shortconv_cost.is_shortconv(cell)
    s = shortconv_cost.sizes(cell)
    assert (s["conv"], s["attention"], s["dense"], s["sparse"]) == (8, 2, 2,
                                                                    8)
    assert shortconv_cost.conv_params(cell) - e == 16_783_360
    assert shortconv_cost.attention_params(cell) - e == 10_485_888
    assert shortconv_cost.dense_mlp_params(cell) - e == 72_351_744
    assert shortconv_cost.expert_params(cell) == 9_437_184
    assert shortconv_cost.param_count(cell) == 5_267_090_176
    assert shortconv_cost.kv_bytes_per_position_a_layer(cell) == 2048
    assert shortconv_cost.state_bytes_per_sequence(cell) == 65_536
    assert shortconv_cost.pool_operand(cell) == "[640,128,4,128]"
    step = shortconv_cost.decode_floor(
        cell, steps=1, experts_hit=39 * 8, row_steps=15,
        context_positions=15 * 2500)
    assert 6.8e9 < step["bytes"] < 7.0e9
    join = shortconv_cost.join_flops(cell, tokens=1024, runs=1,
                                     attended_positions=1024 * 1500)
    assert 1.1e12 < join < 1.4e12
    walk = shortconv_cost.decode_walk_floor(cell, [2500] * 15)
    assert walk["bytes"] == 15 * 2500 * 4096
    assert walk["flops"] / walk["bytes"] < 10.0      # the bytes bound it


# --- `correct`, through the seeded weights -----------------------------------


def test_correct_is_decided_on_this_reference(tiny):
    _cfg, params = tiny
    prompt = tokens_of(2, 40)
    logits = reference(params, prompt, [39])
    best, worst = int(logits[0].argmax()), int(logits[0].argmin())
    good = correct.score(ref, params, PUBLISHED, [
        {"what": "first-token-0", "prompt": prompt, "ids": [best]}])
    bad = correct.score(ref, params, PUBLISHED, [
        {"what": "first-token-0", "prompt": prompt, "ids": [worst]}])
    assert good["correct"] and good["worst_gap_sigmas"] == 0.0
    assert not bad["correct"] and bad["worst_gap_sigmas"] > 2.0


def test_the_token_rule_sees_the_conv_through_the_seeded_weights(tiny):
    """What the recipe is for (the tied embedding at the initialiser's
    range, the mixers' out-projections at SHORTCONV_SHARE so that they
    and not the token's own row carry the logits): 12 greedy tokens of
    the reference itself are not the last token read again, and scored
    by `correct.score` against a reference whose conv layers have lost
    their two older taps (a layer that keeps no state) they fail the
    harness's 0.25 sigma; against itself they stand 0.0 off."""
    _cfg, params = tiny
    prompt, ids = tokens_of(9, 96), []
    for _ in range(12):                       # (one length: one trace)
        at = len(prompt) + len(ids) - 1
        seq = (prompt + ids + [0] * 16)[:112]
        ids.append(int(reference(params, seq, [at])[0].argmax()))
    assert len(set(ids)) > 4 and ids[0] != prompt[-1]
    served = [{"what": "greedy-0", "prompt": prompt, "ids": ids}]
    assert correct.score(ref, params, PUBLISHED,
                         served)["worst_gap_sigmas"] == 0.0
    stateless = dict(params, layers=[
        dict(layer, conv_w=layer["conv_w"].at[:2].set(0.0))
        if "conv_w" in layer else layer for layer in params["layers"]])
    off = correct.score(ref, stateless, PUBLISHED, served)
    assert not off["correct"] and off["worst_gap_sigmas"] > 0.5


# --- the readers, on spans and a trace made by hand -------------------------


def _reader(name):
    path = os.path.join(bench_paths.BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _span(rung, t0, **attrs):
    return {"rung": rung, "t0": t0, "dur_s": 0.01, "span_id": "s",
            "parent_id": None, "trace_id": "t", "attrs": attrs}


PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
WALK_OP = "%paged_decode_attention [pallas s32[16,64] s32[16] " \
    "bf16[16,32,128] bf16[640,128,4,128] bf16[640,128,4,128]]"
JOIN_OP = "%ragged_paged_attention [pallas s32[17,64] bf16[1024,32,128] " \
    "bf16[640,128,4,128] bf16[640,128,4,128]]"
NAMES = ("step.decode_roofline.shortconv", "kernel.attn_roofline.d64")


def _ctx(cell, monkeypatch, spans, op_seconds, decode_s, rows):
    from theroundtaible_tpu.utils import telemetry
    monkeypatch.setattr(telemetry, "spans_between",
                        lambda a, b: [s for s in spans if a <= s["t0"] < b])
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)
    return {"config": cell, "peaks": PEAKS, "rows": rows,
            "slice": {"start": 10.0, "end": 16.0},
            "trace": {"op_seconds": op_seconds, "busy_s": 4.0,
                      "devices": 1, "module_seconds": {
                          "jit_decode_loop_hybrid(123)": decode_s,
                          "jit_ragged_step_hybrid(9)": 1.0}},
            "names": {"programs": {"decode": ["jit_decode_loop"]}}}


# One row: 2,000 prompt tokens, 65 tokens flushed inside the slice — the
# first from its prefill, 64 decoded at contexts 2,001 .. 2,064.
ROWS_SEEN = [{"sent": 10.0, "prompt_tokens": 2000,
              "flushes": [[15.0, 65]]}]


def test_the_two_readers_by_hand(cell, monkeypatch):
    """64 steps of 15 rows that hit 39 of 64 experts a layer a step, and
    a walk over 64 decoded tokens' contexts."""
    spans = [_span("segment", 11.0, kind="plain", steps=64,
                   decode_tokens=960, experts_hit=64 * 8 * 39,
                   expert_layer_steps=64 * 8),
             _span("segment", 9.0, kind="plain", steps=64,
                   decode_tokens=960, experts_hit=1,
                   expert_layer_steps=8)]          # before the slice
    ctx = _ctx(cell, monkeypatch, spans,
               {WALK_OP: 0.004, JOIN_OP: 0.5, "%fusion.3": 2.0}, 0.9,
               ROWS_SEEN)
    contexts = list(range(2001, 2065))
    walk_s = sum(contexts) * 4096 / 819e9
    assert _reader("kernel.attn_roofline.d64")(ctx) == pytest.approx(
        100 * walk_s / 0.004)
    mean = sum(contexts) / 64
    step_s = (64 * shortconv_cost.fixed_step_bytes(cell)
              + 64 * 8 * 39 * 9_437_184 * 2 + 960 * 2 * 65_536
              + int(mean * 960) * 4096) / 819e9
    assert _reader("step.decode_roofline.shortconv")(ctx) == pytest.approx(
        100 * step_s / 0.9)
    assert 55.0 < 100 * step_s / 0.9 < 65.0


def test_a_share_over_100_is_an_error_and_another_model_reads_nothing(
        cell, monkeypatch):
    spans = [_span("segment", 11.0, kind="plain", steps=64,
                   decode_tokens=960, experts_hit=64 * 8 * 39,
                   expert_layer_steps=64 * 8)]
    ctx = _ctx(cell, monkeypatch, spans, {WALK_OP: 0.0001}, 0.3, ROWS_SEEN)
    for name in NAMES:
        with pytest.raises(RuntimeError, match="counts too much"):
            _reader(name)(ctx)
    other = dict(ctx, config=dict(cell, model_type="mistral"))
    for name in NAMES:
        assert _reader(name)(other) is None
    # a program without the kernel's name or the spans' attributes (the
    # parent commit, a pool the walk never read, a run without a slice):
    # nothing to read, nothing raised
    bare = _ctx(cell, monkeypatch, [_span("segment", 12.0, kind="ragged")],
                {"%fusion.3": 2.0,
                 WALK_OP.replace("[640,128,4,128]", "[640,128,8,64]"): 1.0},
                0.0, ROWS_SEEN)
    for name in NAMES:
        assert _reader(name)(bare) is None
    assert all(_reader(n)(dict(ctx, slice=None, trace={})) is None
               for n in NAMES)
