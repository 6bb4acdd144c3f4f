"""`brumby` (Brumby-14B-Base): the plain reference
(benchmarks/configs/brumby_reference.py — the QUADRATIC form of power
retention, a head at a time, float32 highest, no state, no feature map)
against a recurrence written out in numpy float64 that shares nothing
with it; its two controls' seams (the gate off, a matrix rounded on the
way); the program's chunked and step forms through `forward_paged_hybrid`
and `forward_ragged_hybrid` against its whole forward; the
configuration file's keys and arithmetic; the comparison that decides
`correct`; the cost file and the new readers on spans made by hand.

Tolerances, on LOGITS whose spread over the vocabulary is about 1:
float32 program against float32 reference 5e-5 (the order of sums
alone; measured 1.4e-6), the reference against the float64 recurrence
2e-4 on the mixer's output of order 1. The gate off moves logits by
over 1e-2, float8 matrices by over 1e-1: both FAIL 5e-5."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths
from configs import brumby_reference as ref
from harness import correct, retention_cost
from theroundtaible_tpu.engine import fleet
from theroundtaible_tpu.engine.models import hybrid
from theroundtaible_tpu.engine.models.common import init_params
from theroundtaible_tpu.engine.models.registry import get_model_config
from theroundtaible_tpu.engine.paged_forward import (
    forward_paged_hybrid, forward_ragged_hybrid)
from theroundtaible_tpu.engine.serving_loop import (RaggedSeq,
                                                    build_ragged_batch)

PAGE = 8
TOL = 5e-5
CELL = os.path.join(bench_paths.BENCH, "configs", "brumby-14b-d6.json")
PUBLISHED = {
    "attention_bias": False, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 32768, "max_window_layers": 3,
    "model_type": "brumby", "num_attention_heads": 6,
    "num_hidden_layers": 3, "num_key_value_heads": 2,
    "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 512}


@pytest.fixture(scope="module")
def tiny():
    cfg = get_model_config("tiny-brumby")
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


# --- the reference against a recurrence in float64 ---------------------------


def _recurrent_layer(layer, x, theta=1e6, eps=1e-6):
    """x [T, E] float64 -> x + W_o Ret(norm x), the state form with the
    plain degree-2 feature map u (x) u (all D^2 products: no packing),
    a token at a time."""
    f64 = lambda a: np.asarray(a, np.float64)          # noqa: E731
    norm = lambda a, w: a / np.sqrt(                   # noqa: E731
        (a * a).mean(-1, keepdims=True) + eps) * f64(w)
    t = x.shape[0]
    h = norm(x, layer["norm"])
    w_q, w_k, w_v = (f64(layer[n]) for n in ("q_proj", "k_proj", "v_proj"))
    heads, d = w_q.shape[1], w_q.shape[2]
    kv = w_k.shape[1]
    freq = theta ** (-2.0 * np.arange(d // 2) / d)

    def turn(a, pos):
        c, s = np.cos(pos * freq), np.sin(pos * freq)
        lo, hi = a[..., :d // 2], a[..., d // 2:]
        return np.concatenate([lo * c - hi * s, hi * c + lo * s], -1)

    z = h @ f64(layer["g_proj"])
    log_g = -np.log1p(np.exp(-z))                      # log sigmoid
    state = np.zeros((kv, d * d, d + 1))
    out = np.zeros_like(x)
    for i in range(t):
        q = turn(norm(np.einsum("e,ehd->hd", h[i], w_q),
                      layer["q_norm"]), i)
        k = turn(norm(np.einsum("e,ekd->kd", h[i], w_k),
                      layer["k_norm"]), i)
        v = np.einsum("e,ekd->kd", h[i], w_v)
        y = np.zeros((heads, d))
        for m in range(kv):
            fk = np.outer(k[m], k[m]).reshape(-1)
            state[m] = np.exp(log_g[i, m]) * state[m] + np.outer(
                fk, np.append(v[m], 1.0))
        for n in range(heads):
            fq = np.outer(q[n], q[n]).reshape(-1) / d   # (q.k / sqrt d)^2
            got = fq @ state[n // (heads // kv)]
            y[n] = got[:d] / got[d]
        out[i] = x[i] + np.einsum("hd,hde->e", y, f64(layer["o_proj"]))
    return out


def test_the_quadratic_form_is_the_recurrence(tiny):
    _cfg, params = tiny
    tokens = tokens_of(1, 48)
    x0 = np.asarray(params["embedding"], np.float64)[tokens]
    want = _recurrent_layer(params["layers"][0], x0)
    freq = jnp.asarray(ref.rotary_frequencies(1e6, 16), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = ref.retention_layer(params["layers"][0],
                                  jnp.asarray(x0, jnp.float32), freq,
                                  eps=1e-6, read=ref.as_float32)
    assert np.abs(np.asarray(got) - want).max() < 2e-4


def test_what_follows_a_row_never_reaches_the_reference(tiny):
    _cfg, params = tiny
    a = tokens_of(2, 40) + tokens_of(3, 24)
    b = a[:40] + tokens_of(4, 24)
    rows = list(range(40))
    assert np.array_equal(reference(params, a, rows),
                          reference(params, b, rows))


def test_the_gate_off_and_a_rounded_matrix_move_the_reference(tiny):
    """The two controls of the chip's replay, at their seams."""
    _cfg, params = tiny
    tokens, rows = tokens_of(5, 64), list(range(32, 64))
    base = reference(params, tokens, rows)
    off = reference(params, tokens, rows, gate=False)
    assert np.abs(off - base).max() > 1e-2 > TOL

    def float8(leaf):
        return jnp.asarray(leaf, jnp.float32).astype(
            jnp.float8_e4m3fn).astype(jnp.float32)

    coarse = reference(params, tokens, rows, read=float8)
    assert np.abs(coarse - base).max() > 1e-1 > TOL


def test_the_reference_refuses_what_it_is_not_written_for(tiny):
    _cfg, params = tiny
    for change in ({"attention_bias": True},
                   {"rope_scaling": {"type": "yarn"}}):
        with pytest.raises(ValueError):
            ref.logits_at(params, dict(PUBLISHED, **change),
                          np.asarray(tokens_of(6, 16)), [15])


# --- the program through pages against the reference's whole forward ---------


def _serve_in_pieces(cfg, params, tokens, first, second):
    """A prologue chunk of `first` tokens (prefill program), a ragged
    join of `second` more beside one decode token of another sequence,
    then decode steps to the end: -> logits at every position from
    first - 1 on where the program yields them (the chunk's last, the
    join's last, each step's)."""
    state = hybrid.zero_state(cfg, 4)                  # rows 0..2, scratch
    snaps = hybrid.zero_state(cfg, 3)
    table = np.arange(1, 33, dtype=np.int32)
    rows = jnp.asarray([0])
    tok = jnp.asarray(tokens)
    out = {}
    logits, _p, state, cap, _n = forward_paged_hybrid(
        params, cfg, tok[None, :first], jnp.arange(first)[None], [],
        jnp.asarray(table)[None], jnp.asarray([first]), state,
        lengths=jnp.asarray([first]), cap_len=jnp.asarray([first // PAGE
                                                           * PAGE]),
        last_pos=jnp.asarray([first - 1]), page_size=PAGE, rows=rows,
        snaps=snaps, snap_idx=jnp.asarray([1]))
    snaps = {p: cap[p] for p in snaps}
    out[first - 1] = np.asarray(logits[0, 0])
    # the other sequence: 5 tokens in slot row 1, then its decode token
    other = tokens_of(99, 6)
    _l, _p, state, _c, _n = forward_paged_hybrid(
        params, cfg, jnp.asarray(other[:5])[None], jnp.arange(5)[None], [],
        jnp.asarray(table)[None], jnp.asarray([5]), state,
        lengths=jnp.asarray([5]), page_size=PAGE, rows=jnp.asarray([1]))
    b = build_ragged_batch(
        [RaggedSeq(tokens[first:first + second], first, table),
         RaggedSeq(other[5:6], 5, table)],
        t_budget=64, s_max=4, pages_per_seq=32, scratch_page=0, pad_id=0,
        page_size=PAGE)
    arr = {k: jnp.asarray(v) for k, v in b.items()
           if isinstance(v, np.ndarray)}
    seq_slot = jnp.asarray([0, 1, 3, 3])
    end = first + second
    logits, _p, state, cap, _n = forward_ragged_hybrid(
        params, cfg, arr["tokens"], arr["positions"], [], arr["tables"],
        arr["seq_of_block"], arr["block_qstart"], arr["query_offsets"],
        arr["kv_valid"], arr["token_pages"], arr["token_offs"],
        arr["token_seq"], arr["last_rows"], state, seq_slot,
        jnp.asarray([end // PAGE * PAGE - first, 0, 0, 0]),
        page_size=PAGE, snaps=snaps, snap_idx=jnp.asarray([2, 0, 0, 0]))
    snaps = {p: cap[p] for p in snaps}
    out[end - 1] = np.asarray(logits[0])
    for at in range(end, len(tokens)):
        logits, _p, state, _c, _n = forward_paged_hybrid(
            params, cfg, tok[None, at:at + 1], jnp.asarray([[at]]), [],
            jnp.asarray(table)[None], jnp.asarray([at + 1]), state,
            active=jnp.asarray([True]), page_size=PAGE, rows=rows)
        out[at] = np.asarray(logits[0, 0])
    return out, state, snaps


@pytest.mark.parametrize("first,second", [(19, 30), (24, 9), (5, 43)])
def test_prologue_join_and_decode_through_the_state(tiny, first, second):
    """Chunk and page boundaries inside the prologue and the join, a
    join that starts inside a page, a decode row riding the join."""
    cfg, params = tiny
    tokens = tokens_of(7, 60)
    got, _state, _snaps = _serve_in_pieces(cfg, params, tokens, first,
                                           second)
    want = reference(params, tokens, sorted(got))
    for row, at in zip(want, sorted(got)):
        assert np.abs(got[at] - row).max() < TOL, at


def test_the_captures_are_the_state_at_their_page_boundaries(tiny):
    """The prologue's capture (after 16 of 19 tokens) and the join's
    (after 48 of 49): each continues another slot to the reference's
    logits."""
    cfg, params = tiny
    tokens = tokens_of(8, 60)
    _got, state, snaps = _serve_in_pieces(cfg, params, tokens, 19, 30)
    table = jnp.arange(1, 33, dtype=jnp.int32)[None]
    for snap, at in ((1, 16), (2, 48)):
        st = {p: [a.at[2].set(s[snap]) for a, s in zip(state[p], snaps[p])]
              for p in state}
        n = len(tokens) - at
        logits, _p, _s, _c, _n = forward_paged_hybrid(
            params, cfg, jnp.asarray(tokens[at:])[None],
            at + jnp.arange(n)[None], [], table, jnp.asarray([len(tokens)]),
            st, lengths=jnp.asarray([n]), page_size=PAGE,
            rows=jnp.asarray([2]))
        want = reference(params, tokens, list(range(at, len(tokens))))
        assert np.abs(np.asarray(logits[0]) - want).max() < TOL, at


# --- the configuration file ---------------------------------------------------


def test_the_file_keeps_every_published_key_and_says_what_it_assumed(cell):
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 17408,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    assert cell["reduced"] == ["num_hidden_layers"]
    assert cell["published"] == {"num_hidden_layers": 40}
    for key, value in published.items():
        want = 6 if key == "num_hidden_layers" else value
        assert cell[key] == want, key
        assert cell["engine"]["architecture"][key] == want, key
    assert set(cell["engine"]["architecture"]) == set(published)
    for key in ("power", "gate", "normaliser", "qk_norm", "state_dtype",
                "state_layout", "weights", "max_seq_len",
                "state_snapshot_bytes"):
        assert key in cell["assumed"], key
    assert "pipeline" in cell["deployment"]
    engine = cell["engine"]
    assert (engine["num_slots"], engine["num_pages"],
            engine["prefix_cache_pages"], engine["page_size"]) == (
        16, 640, 448, 128)
    assert engine["state_snapshot_bytes"] == 3_000_000_000


def test_the_cuts_arithmetic(cell):
    """ISSUE 42's numbers, from the registry's own count."""
    whole = get_model_config("brumby-14b")
    e, f = 5120, 17408
    layer = (2 * e * 40 * 128 + 2 * e * 8 * 128 + e * 8 + 2 * 128 + e
             + 3 * e * f + e)
    assert fleet.estimate_param_count(whole) == \
        40 * layer + 2 * 151_936 * e + e
    assert 14.7e9 < fleet.estimate_param_count(whole) < 14.8e9
    cut = 6 * layer + 2 * 151_936 * e + e
    assert 3.53e9 < cut < 3.55e9                        # 7.08 GB in bf16
    assert retention_cost.fixed_step_bytes(cell) \
        == 2 * (cut - 151_936 * e)                      # less the embedding
    per = hybrid.state_bytes_per_sequence(
        get_model_config("brumby-14b", num_layers=12,
                         layer_kinds=(hybrid.RETENTION, hybrid.MLP) * 6))
    assert per == 6 * 8 * 8320 * 129 * 4                # 206.1 MB laid out
    assert retention_cost.state_bytes_per_sequence(cell) \
        == 6 * 8 * 8256 * 129 * 4                       # 204.5 MB least
    assert cell["engine"]["state_snapshot_bytes"] // per == 14


def test_the_parameter_count_agrees_with_the_tree_leaf_for_leaf(tiny):
    cfg, params = tiny
    leaves = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert leaves == fleet.estimate_param_count(cfg)
    layer = params["layers"][0]
    assert set(layer) == {"norm", "q_proj", "k_proj", "v_proj", "g_proj",
                          "q_norm", "k_norm", "o_proj"}
    assert layer["g_proj"].shape == (64, 2)


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


def test_the_token_rule_sees_the_gate_through_the_seeded_weights(tiny):
    """What the recipe of hybrid.init_layer is for (every
    out-projection at RETENTION_SHARE, so the mixers carry the logits):
    24 greedy tokens of the reference itself, scored by `correct.score`
    against the reference with the gate left out, fail the harness's
    0.25 sigma (1.28 here; at RESIDUAL_SHARE the chip's replay read
    0.021 and passed); scored against the reference with its matrices
    through float8 they stand 0.18 off, through bfloat16 0.0."""
    _cfg, params = tiny
    prompt, ids = tokens_of(9, 192), []
    for _ in range(24):
        at = len(prompt) + len(ids) - 1
        ids.append(int(reference(params, prompt + ids, [at])[0].argmax()))
    served = [{"what": "greedy-0", "prompt": prompt, "ids": ids}]

    def control(**kw):
        class Control:
            @staticmethod
            def logits_at(p, c, seq, rows):
                return ref.logits_at(p, c, seq, rows, **kw)
        return correct.score(Control, params, PUBLISHED, served)

    def through(dtype):
        return lambda leaf: jnp.asarray(leaf, jnp.float32).astype(
            dtype).astype(jnp.float32)

    assert control()["worst_gap_sigmas"] == 0.0
    off = control(gate=False)
    assert not off["correct"] and off["worst_gap_sigmas"] > 1.0
    assert control(read=through(jnp.bfloat16))["worst_gap_sigmas"] < 0.01
    assert control(read=through(jnp.float8_e4m3fn))[
        "worst_gap_sigmas"] > 0.1


# --- the readers, on spans and a trace made by hand ---------------------------


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
KERNEL_OP = "retention_step [pallas s32[16] f32[16,8,8,128] " \
    "f32[17,8,65,128,128] f32[17,8,65,128]]"
CHUNK_OP = "retention_chunk [pallas s32[1] f32[8,640,128] " \
    "f32[17,8,65,128,128] f32[17,8,65,128]]"


def _ctx(cell, monkeypatch, spans, op_seconds, decode_s):
    from theroundtaible_tpu.utils import telemetry
    monkeypatch.setattr(telemetry, "spans_between",
                        lambda a, b: [s for s in spans if a <= s["t0"] < b])
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)
    return {"config": cell, "peaks": PEAKS,
            "slice": {"start": 10.0, "end": 16.0,
                      "counters_start": {"scheduler": {
                          "segment_prefill_tokens": 1000}},
                      "counters_end": {"scheduler": {
                          "segment_prefill_tokens": 13800}}},
            "trace": {"op_seconds": op_seconds, "busy_s": 4.0,
                      "devices": 1, "module_seconds": {
                          "jit_decode_loop_hybrid(123)": decode_s,
                          "jit_ragged_step_hybrid(9)": 1.0}},
            "names": {"programs": {"decode": ["jit_decode_loop"]}}}


def test_the_retention_readers_by_hand(cell, monkeypatch):
    """64 steps of 15 rows: the kernel's floor is 15 x 64 x 6 layers x
    (2 x 34.08 MB + the row's small operands) at 819 GB/s."""
    spans = [_span("segment", 11.0, kind="plain", steps=64,
                   decode_tokens=960, state_capture_bytes=0),
             _span("segment", 12.0, kind="ragged", steps=1,
                   decode_tokens=3, state_capture_bytes=2 * 206_097_920),
             _span("admit", 12.0, state_copy_bytes=206_097_920),
             _span("admit", 12.5, state_copy_bytes=0),
             _span("segment", 9.0, kind="plain", steps=64,
                   decode_tokens=960)]                  # before the slice
    ctx = _ctx(cell, monkeypatch, spans,
               {KERNEL_OP: 0.8, CHUNK_OP: 0.4, "%fusion.3": 2.0}, 2.0)
    floor_s = 960 * 6 * (2 * 8 * 8256 * 129 * 4
                         + (2 * 40 * 128 + 2 * 8 * 128 + 8) * 4) / 819e9
    assert _reader("kernel.retention_roofline")(ctx) == pytest.approx(
        100 * floor_s / 0.8)
    assert _reader("kernel.retention_busy_share")(ctx) == pytest.approx(
        100 * (0.8 + 0.4) / 4.0)
    # 12 800 tokens joined: 100 pages of state a layer in and out
    # (0.05 s at 819 GB/s) against 12 operations a state value a token
    # (0.04 s at 197 TFLOP/s): the bytes decide.
    assert _reader("kernel.retention_chunk_roofline")(ctx) == pytest.approx(
        100 * (100 * 6 * 2 * 8 * 8256 * 129 * 4 / 819e9) / 0.4)
    step_s = (64 * retention_cost.fixed_step_bytes(cell)) / 819e9 + floor_s
    assert _reader("step.decode_roofline.retention")(ctx) == pytest.approx(
        100 * step_s / 2.0)
    assert _reader("state.copy_ms_per_join")(ctx) == pytest.approx(
        1e3 * 2 * 3 * 206_097_920 / 819e9 / 2)


def test_a_share_over_100_is_an_error_and_another_model_reads_nothing(
        cell, monkeypatch):
    spans = [_span("segment", 11.0, kind="plain", steps=64,
                   decode_tokens=960)]
    ctx = _ctx(cell, monkeypatch, spans, {KERNEL_OP: 0.2}, 0.3)
    with pytest.raises(RuntimeError, match="narrower"):
        _reader("kernel.retention_roofline")(ctx)
    with pytest.raises(RuntimeError, match="counts too much"):
        _reader("step.decode_roofline.retention")(ctx)
    other = dict(ctx, config=dict(cell, model_type="mistral"))
    for name in ("kernel.retention_roofline", "kernel.retention_busy_share",
                 "kernel.retention_chunk_roofline",
                 "step.decode_roofline.retention"):
        assert _reader(name)(other) is None
    # spans without the attribute (a program before this PR): nothing
    bare = _ctx(cell, monkeypatch, [_span("admit", 12.0, rows=3)], {}, 1.0)
    assert _reader("state.copy_ms_per_join")(bare) is None
    assert _reader("kernel.retention_roofline")(bare) is None


def test_a_step_served_without_the_kernel_is_an_error(cell, monkeypatch):
    """A geometry the step kernel declines is served through jax.numpy
    and `degraded_paths` stays empty: the roofline's reader is where a
    traced run learns of it."""
    spans = [_span("segment", 11.0, kind="plain", steps=64,
                   decode_tokens=960)]
    ctx = _ctx(cell, monkeypatch, spans, {"%fusion.3": 2.0}, 2.0)
    with pytest.raises(RuntimeError, match="no `retention_step` kernel"):
        _reader("kernel.retention_roofline")(ctx)
