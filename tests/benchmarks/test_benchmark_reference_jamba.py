"""`jamba` (AI21-Jamba2-3B): the plain reference
(benchmarks/configs/jamba_reference.py — the recurrence a token at a
time under `lax.scan`, float32 highest, no chunks, no scan over layers)
against the same equations written out in numpy float64 that share
nothing with it; its two controls' seams (the three small norms left
out, a matrix rounded on the way); the program's scanned runs through
`forward_paged_hybrid` and `forward_ragged_hybrid` — a prologue chunk, a
join that restarts from the slot's row, decode steps — against the
reference's whole forward; the configuration file's keys and
arithmetic; the comparison that decides `correct`; the cost file's
readers on spans made by hand.

Tolerances, on LOGITS whose spread over the vocabulary is about 0.16:
float32 program against float32 reference 2e-5 (the order of sums alone;
measured 2.4e-6 through the engine's step programs), the reference
against the float64 equations 1e-5 on a layer's output of order 1. Without the three norms logits move by over
1e-2, with float8 matrices by over 1e-2: both FAIL 2e-5."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths
from configs import jamba_reference as ref
from harness import correct, mamba1_cost
from theroundtaible_tpu.engine.models import hybrid
from theroundtaible_tpu.engine.models.common import init_params
from theroundtaible_tpu.engine.models.registry import get_model_config
from theroundtaible_tpu.engine.paged_forward import (
    forward_paged_hybrid, forward_ragged_hybrid)
from theroundtaible_tpu.engine.serving_loop import (RaggedSeq,
                                                    build_ragged_batch)

PAGE = 16
TOL = 2e-5
CELL = os.path.join(bench_paths.BENCH, "configs", "jamba2-3b.json")
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 8,
    "mamba_dt_rank": 4, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 4, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 14, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-6, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 512}


@pytest.fixture(scope="module")
def tiny():
    cfg = get_model_config("tiny-jamba")
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


def _norm(a, w, eps=1e-6):
    return a / np.sqrt((a * a).mean(-1, keepdims=True) + eps) * _f64(w)


def _silu(a):
    return a / (1.0 + np.exp(-a))


def _mamba64(layer, x):
    """x [T, E] float64 -> x + Mamba(norm x), python loops over tokens."""
    t, (d, n, r) = x.shape[0], (128, 8, 4)
    h = _norm(x, layer["norm"])
    uz = h @ _f64(layer["in_proj"])
    u, z = uz[:, :d], uz[:, d:]
    w, bias = _f64(layer["conv_w"]), _f64(layer["conv_b"])
    a = -np.exp(_f64(layer["A_log"])).T                       # [d, N]
    state, out = np.zeros((d, n)), np.zeros_like(x)
    for i in range(t):
        acc = bias.copy()
        for j in range(4):
            if i - 3 + j >= 0:
                acc += w[j] * u[i - 3 + j]
        c = _silu(acc)
        xp = c @ _f64(layer["x_proj"])
        dl = _norm(xp[:r], layer["dt_norm"])
        b = _norm(xp[r:r + n], layer["b_norm"])
        cm = _norm(xp[r + n:], layer["c_norm"])
        dt = np.log1p(np.exp(dl @ _f64(layer["dt_proj"])
                             + _f64(layer["dt_bias"])))
        state = np.exp(dt[:, None] * a) * state \
            + (dt * c)[:, None] * b[None, :]
        y = (state @ cm + _f64(layer["D"]) * c) * _silu(z[i])
        out[i] = x[i] + y @ _f64(layer["out_proj"])
    return out


def _attention64(layer, x):
    t = x.shape[0]
    h = _norm(x, layer["norm"])
    k = h @ _f64(layer["k_proj"])[:, 0]
    v = h @ _f64(layer["v_proj"])[:, 0]
    out = x.copy()
    for n in range(4):
        q = h @ _f64(layer["q_proj"])[:, n]
        s = np.where(np.tril(np.ones((t, t), bool)), q @ k.T / 4.0, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        out += (w / w.sum(-1, keepdims=True)) @ v @ _f64(layer["o_proj"])[n]
    return out


def test_the_scan_form_is_the_equations(tiny):
    """A Mamba layer with its small norms, conv bias and D away from
    their initial ones, and the attention layer, each against float64
    loops written from the equations."""
    _cfg, params = tiny
    layers = list(ref.published_layers(params, ref.sizes_of(PUBLISHED)))
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (40, 64)))
    _is, mamba, _mlp = layers[9]
    key = jax.random.PRNGKey(4)
    mamba = dict(mamba)
    for i, name in enumerate(("dt_norm", "b_norm", "c_norm", "D", "conv_b",
                              "norm")):
        mamba[name] = mamba[name] + 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), mamba[name].shape)
    with jax.default_matmul_precision("highest"):
        got = ref.mamba_layer(mamba, jnp.asarray(x), eps=1e-6, norms=True,
                              read=ref.as_float32, sizes=(128, 8, 4, 4))
        att = ref.attention_layer(layers[7][1], jnp.asarray(x), eps=1e-6,
                                  read=ref.as_float32)
    assert layers[7][0] and not layers[9][0]
    assert np.abs(np.asarray(got) - _mamba64(mamba, _f64(x))).max() < 1e-5
    assert np.abs(np.asarray(att)
                  - _attention64(layers[7][1], _f64(x))).max() < 1e-5


def test_what_follows_a_row_never_reaches_the_reference(tiny):
    _cfg, params = tiny
    tokens = tokens_of(1, 64)
    short = reference(params, tokens[:40], [10, 39])
    padded = reference(params, tokens[:40] + [0] * 24, [10, 39])
    other = reference(params, tokens, [10, 39])
    # (another length is another blocking of the same float32 sums)
    assert np.abs(short - padded).max() < 1e-6
    assert np.abs(short - other).max() < 1e-6


def test_the_norms_left_out_and_a_rounded_matrix_move_the_reference(tiny):
    _cfg, params = tiny
    tokens = tokens_of(2, 48)
    base = reference(params, tokens, [47])

    def through(dtype):
        return lambda leaf: jnp.asarray(leaf, jnp.float32).astype(
            dtype).astype(jnp.float32)

    assert np.abs(reference(params, tokens, [47], norms=False)
                  - base).max() > 1e-2
    assert np.abs(reference(params, tokens, [47],
                            read=through(jnp.float8_e4m3fn))
                  - base).max() > 1e-2
    assert np.abs(reference(params, tokens, [47],
                            read=through(jnp.bfloat16)) - base).max() > TOL


def test_the_reference_refuses_what_it_is_not_written_for(tiny):
    _cfg, params = tiny
    with pytest.raises(ValueError, match="one dense feed-forward"):
        ref.logits_at(params, dict(PUBLISHED, num_experts=2), [1, 2], [1])
    with pytest.raises(ValueError, match="no window"):
        ref.logits_at(params, dict(PUBLISHED, sliding_window=64), [1], [0])


# --- the program's scanned runs against the reference -----------------------


def _zero(cfg, pages=12):
    pools = [tuple(jnp.zeros((pages, PAGE, 1, cfg.head_dim), jnp.float32)
                   for _ in range(2))]
    return pools, hybrid.zero_state(cfg, 3), hybrid.zero_state(cfg, 3)


def test_prologue_join_and_decode_through_the_scanned_runs(tiny, first=32,
                                                           second=30):
    """A prologue chunk of `first` tokens from a zero state (a snapshot
    at its last page boundary), a ragged join of `second` more that
    restarts from the slot's row, then decode steps: every logit row the
    programs return against the reference's whole forward."""
    cfg, params = tiny
    tokens = tokens_of(first, first + second + 6)
    pools, state, snaps = _zero(cfg)
    table = jnp.arange(1, 9, dtype=jnp.int32)[None]
    rows = jnp.asarray([1])
    with jax.default_matmul_precision("highest"):
        logits, pools, state, cap, _n = forward_paged_hybrid(
            params, cfg, jnp.asarray(tokens[:first])[None],
            jnp.arange(first)[None], pools, table, jnp.asarray([first]),
            state, lengths=jnp.asarray([first]),
            cap_len=jnp.asarray([first // PAGE * PAGE]),
            last_pos=jnp.asarray([first - 1]), page_size=PAGE, rows=rows,
            snaps=snaps, snap_idx=jnp.asarray([0]))
        got = [np.asarray(logits[0, 0])]
        b = build_ragged_batch(
            [RaggedSeq(tokens[first:first + second], first,
                       np.asarray(table[0]))],
            t_budget=64, s_max=3, pages_per_seq=8, scratch_page=0,
            pad_id=0, page_size=PAGE)
        arr = {k: jnp.asarray(v) for k, v in b.items()
               if isinstance(v, np.ndarray)}
        logits, pools, state, _cap, _n = forward_ragged_hybrid(
            params, cfg, arr["tokens"], arr["positions"], pools,
            arr["tables"], arr["seq_of_block"], arr["block_qstart"],
            arr["query_offsets"], arr["kv_valid"], arr["token_pages"],
            arr["token_offs"], arr["token_seq"], arr["last_rows"], state,
            jnp.asarray([1, 2, 2]), jnp.zeros((3,), jnp.int32),
            page_size=PAGE, snaps=cap, snap_idx=jnp.asarray([2, 2, 2]))
        got.append(np.asarray(logits[0]))
        for at in range(first + second, len(tokens)):
            logits, pools, state, _c, _n = forward_paged_hybrid(
                params, cfg, jnp.asarray([[tokens[at]]]),
                jnp.asarray([[at]]), pools, table, jnp.asarray([at + 1]),
                state, active=jnp.asarray([True]), page_size=PAGE,
                rows=rows)
            got.append(np.asarray(logits[0, 0]))
    rows_ = [first - 1, first + second - 1] + list(
        range(first + second, len(tokens)))
    want = reference(params, tokens, rows_)
    assert np.abs(np.stack(got) - want).max() < TOL
    # The capture: the state a zero-state scan of the first whole pages
    # leaves, every layer of both runs.
    n = first // PAGE * PAGE
    _p, fresh, _s = _zero(cfg)
    with jax.default_matmul_precision("highest"):
        _l, _p, fresh, _c, _n = forward_paged_hybrid(
            params, cfg, jnp.asarray(tokens[:n])[None], jnp.arange(n)[None],
            _zero(cfg)[0], table, jnp.asarray([n]), fresh,
            lengths=jnp.asarray([n]), last_pos=jnp.asarray([n - 1]),
            page_size=PAGE, rows=rows)
    for part in ("ssm1", "conv1"):
        for held, stood in zip(cap[part], fresh[part]):
            assert np.abs(np.asarray(held[0])
                          - np.asarray(stood[1])).max() < 1e-6


# --- the configuration file -------------------------------------------------


def test_the_file_keeps_every_published_key_and_says_what_it_assumed(cell):
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
        "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536}
    assert cell["reduced"] == [] and cell["published"] == {}
    for key, value in published.items():
        assert cell[key] == value, key
        assert cell["engine"]["architecture"][key] == value, key
    assert set(cell["engine"]["architecture"]) == set(published)
    for key in ("layer_order", "num_experts", "head_dim", "rope",
                "rope_theta", "state_dtype", "state_layout", "weights",
                "max_seq_len", "state_snapshot_bytes", "spec_decode"):
        assert key in cell["assumed"], key
    assert "whole" in cell["deployment"]
    engine = cell["engine"]
    assert (engine["num_slots"], engine["num_pages"],
            engine["prefix_cache_pages"], engine["page_size"],
            engine["dtype"], engine["quant"]) == (
        16, 640, 448, 128, "bfloat16", "none")
    assert engine["state_snapshot_bytes"] == 2_000_000_000
    assert engine["state_snapshot_bytes"] \
        // mamba1_cost.state_bytes_per_sequence(cell) == 197
    assert cell["source"].endswith("AI21-Jamba2-3B/blob/main/config.json")


def test_the_whole_models_arithmetic(cell):
    """ISSUE 47's numbers: nothing is cut, 6.06 GB in bfloat16, some
    8.3 GB held (49 % of 16.9)."""
    assert mamba1_cost.param_count(cell) == 3_029_337_472
    held = (2 * mamba1_cost.param_count(cell)
            + 17 * mamba1_cost.state_bytes_per_sequence(cell)
            + 198 * mamba1_cost.state_bytes_per_sequence(cell)
            + 640 * 128 * mamba1_cost.kv_bytes_per_position(cell))
    assert 8.2e9 < held < 8.4e9


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


def test_the_token_rule_sees_the_norms_through_the_seeded_weights(tiny):
    """What the recipe is for (the tied embedding at the initialiser's
    range, every out-projection at MAMBA1_SHARE, so the mixers and not
    the token's own row carry the logits): 16 greedy tokens of the
    reference itself, scored by `correct.score` against the reference
    with the three small norms left out, fail the harness's 0.25 sigma;
    against itself they stand 0.0 off."""
    _cfg, params = tiny
    prompt, ids = tokens_of(9, 96), []
    for _ in range(16):                       # (one length: one trace)
        at = len(prompt) + len(ids) - 1
        seq = (prompt + ids + [0] * 16)[:112]
        ids.append(int(reference(params, seq, [at])[0].argmax()))
    assert len(set(ids)) > 4 and ids[0] != prompt[-1]
    served = [{"what": "greedy-0", "prompt": prompt, "ids": ids}]

    def control(**kw):
        class Control:
            @staticmethod
            def logits_at(p, c, seq, rows):
                return ref.logits_at(p, c, seq, rows, **kw)
        return correct.score(Control, params, PUBLISHED, served)

    assert control()["worst_gap_sigmas"] == 0.0
    off = control(norms=False)
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
SCAN_OP = "mamba1_scan [pallas s32[1] s32[128] f32[1024,40,128] " \
    "f32[17,13,16,40,128]]"
STEP_OP = "mamba1_step [pallas s32[1] s32[17] f32[17,40,128] " \
    "f32[17,13,16,40,128]]"


def _ctx(cell, monkeypatch, spans, op_seconds, decode_s):
    from theroundtaible_tpu.utils import telemetry
    monkeypatch.setattr(telemetry, "spans_between",
                        lambda a, b: [s for s in spans if a <= s["t0"] < b])
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)
    return {"config": cell, "peaks": PEAKS, "rows": [],
            "slice": {"start": 10.0, "end": 16.0},
            "trace": {"op_seconds": op_seconds, "busy_s": 4.0,
                      "devices": 1, "module_seconds": {
                          "jit_decode_loop_hybrid(123)": decode_s,
                          "jit_ragged_step_hybrid(9)": 1.0}},
            "names": {"programs": {"decode": ["jit_decode_loop"]}}}


def test_the_mamba1_readers_by_hand(cell, monkeypatch):
    """A join of 1 024 tokens through 26 layers and 64 steps of 15 rows."""
    spans = [_span("segment", 11.0, kind="plain", steps=64,
                   decode_tokens=960, scan_tokens=0),
             _span("segment", 12.0, kind="ragged", steps=1,
                   decode_tokens=3, scan_tokens=1024 * 26),
             _span("segment", 9.0, kind="ragged", steps=1,
                   decode_tokens=3, scan_tokens=999)]   # before the slice
    ctx = _ctx(cell, monkeypatch, spans,
               {SCAN_OP: 0.012, STEP_OP: 0.028, "%fusion.3": 2.0}, 0.7)
    scan_s = 1024 * 26 * (61_568 + 2 * 327_680 / 128) / 819e9
    assert _reader("kernel.mamba1_scan_roofline")(ctx) == pytest.approx(
        100 * scan_s / 0.012)
    assert _reader("kernel.mamba1_busy_share")(ctx) == pytest.approx(
        100 * 0.040 / 4.0)
    step_s = (64 * 2 * 3_029_337_472 + 960 * 2 * 10_117_120) / 819e9
    assert _reader("step.decode_roofline.mamba1")(ctx) == pytest.approx(
        100 * step_s / 0.7)


def test_a_share_over_100_is_an_error_and_another_model_reads_nothing(
        cell, monkeypatch):
    spans = [_span("segment", 11.0, kind="plain", steps=64,
                   decode_tokens=960, scan_tokens=1024 * 26)]
    ctx = _ctx(cell, monkeypatch, spans, {SCAN_OP: 0.001}, 0.3)
    with pytest.raises(RuntimeError, match="counts too much"):
        _reader("kernel.mamba1_scan_roofline")(ctx)
    with pytest.raises(RuntimeError, match="counts too much"):
        _reader("step.decode_roofline.mamba1")(ctx)
    names = ("kernel.mamba1_scan_roofline", "kernel.mamba1_busy_share",
             "step.decode_roofline.mamba1")
    other = dict(ctx, config=dict(cell, model_type="mistral"))
    for name in names:
        assert _reader(name)(other) is None
    # a program without the span attribute or the kernels (the parent
    # commit, a run without a slice): nothing to read, nothing raised
    bare = _ctx(cell, monkeypatch, [_span("segment", 12.0, kind="ragged")],
                {"%fusion.3": 2.0}, 0.0)
    for name in names:
        assert _reader(name)(bare) is None
    assert all(_reader(n)(dict(ctx, slice=None, trace={})) is None
               for n in names)
