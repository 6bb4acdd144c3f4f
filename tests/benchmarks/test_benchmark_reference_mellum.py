"""`mellum` (Mellum2-12B-A2.5B): three window layers to one full layer at
ONE head count over one GQA page pool, two rotary tables by layer type,
a softmax top-k router and no shared expert — the program through pages
(a prologue chunk, a ragged join, then decode steps through the cache,
contexts past the window and over page boundaries) against the plain
reference's whole forward (benchmarks/configs/mellum_reference.py: a
dense mask, a head at a time, an expert at a time, float32 highest, no
cache); the router's rule, the expert layer without a shared expert,
the YaRN table, the resolver's errors, the comparison that decides
`correct`, and the new counter and its reader on spans made by hand.

Tolerances, on LOGITS whose spread over the vocabulary is about 1:
float32 program against float32 reference 1e-4 — the order of sums
alone (blockwise online softmax against a dense one, the grouped
product against an expert at a time); measured 1e-6. Dropping the
window or swapping the router's rule moves logits by over 1e-2 and
FAILS 1e-4 (`test_the_comparison_fails_when...`).
"""
import dataclasses
import functools
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths
from configs import mellum_reference as ref
from harness import correct
from theroundtaible_tpu.engine import fleet
from theroundtaible_tpu.engine.models import common, hybrid
from theroundtaible_tpu.engine.models.common import init_params
from theroundtaible_tpu.engine.models.registry import (
    get_model_config, resolve_model_config)
from theroundtaible_tpu.engine.paged_forward import (
    forward_paged_hybrid, forward_ragged_hybrid)
from theroundtaible_tpu.engine.serving_loop import (RaggedSeq,
                                                    build_ragged_batch)
from theroundtaible_tpu.utils import telemetry

PAGE = 8
TOL = 1e-4
CELL = os.path.join(bench_paths.BENCH, "configs",
                    "mellum2-12b-a2.5b-d8.json")
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]

# tiny-mellum as a published config.json would state it: two periods,
# window 16 (two 8-wide pages), 8 heads over 2 kv heads, 8 experts top-2
PUBLISHED = {
    "model_type": "mellum", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 512, "max_window_layers": 0,
    "attention_bias": False, "hidden_act": "silu", "rms_norm_eps": 1e-6,
    "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "tie_word_embeddings": False, "sliding_window": 16,
    "use_sliding_window": True,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 8,
            "original_max_position_embeddings": 32, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2079441541679836},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "layer_types": PERIOD * 2, "mlp_layer_types": ["sparse"] * 8,
}
STATE = {"ssm": [], "conv": []}


def tokens_of(seed, n):
    return [int(t) for t in
            np.random.RandomState(seed).randint(3, 250, size=(n,))]


@pytest.fixture(scope="module")
def tiny():
    cfg = get_model_config("tiny-mellum")
    return cfg, init_params(cfg, jax.random.PRNGKey(3), jnp.float32)


@pytest.fixture(scope="module")
def cell():
    with open(CELL, encoding="utf-8") as f:
        return json.load(f)


def empty_pools(cfg, pages):
    shape = (pages, PAGE, cfg.num_kv_heads, cfg.head_dim)
    return [(jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))
            for _ in cfg.attention_layers]


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


@functools.lru_cache(maxsize=None)
def _decode_step(cfg):
    """One decode step, traced once a config (interpret-mode kernels
    retrace in seconds a call otherwise)."""
    def step(params, token, pos, pools, table):
        with jax.default_matmul_precision("highest"):
            logits, pools, *_ = forward_paged_hybrid(
                params, cfg, token[None, None], pos[None, None], pools,
                table, pos[None] + 1, STATE, active=jnp.asarray([True]))
        return logits[0, 0], pools
    return jax.jit(step)


def decode(params, cfg, token, pos, pools, table):
    logits, pools = _decode_step(cfg)(
        params, jnp.int32(token), jnp.int32(pos), pools, table)
    return np.asarray(logits, np.float32), pools


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


@functools.lru_cache(maxsize=None)
def _served(cfg):
    params = init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    return served_through_pages(params, cfg)


def served_through_pages(params, cfg):
    """A 41-token prologue chunk, a ragged join of 27 more (its window
    starts inside pages the chunk wrote), then 12 single steps through
    the decode walk to position 80: six of eight layers read a 16-token
    window that ends up two or three pages of the ten held. -> (tokens,
    the rows scored, their logits)."""
    tokens = [1] + tokens_of(40, 79)
    table = np.zeros(12, np.int32)
    table[:10] = np.arange(1, 11)
    logits, pools = prefill(params, cfg, tokens[:41], empty_pools(cfg, 12),
                            jnp.asarray(table[None]))
    got, rows = [logits[40]], [40]
    joined, pools = ragged(params, cfg,
                           [RaggedSeq(tokens[41:68], 41, table)], pools)
    got.append(joined[0])
    rows.append(67)
    for pos in range(68, 80):
        step, pools = decode(params, cfg, tokens[pos], pos, pools,
                             jnp.asarray(table[None]))
        got.append(step)
        rows.append(pos)
    return tokens, rows, np.asarray(got)


# --- the program against the reference ---------------------------------------


@pytest.mark.parametrize("length", [24, 80, 200])
def test_whole_forward_matches_the_reference(tiny, length):
    cfg, params = tiny
    tokens = [1] + tokens_of(length, length - 1)
    with jax.default_matmul_precision("highest"):
        logits, _ = common.forward(
            params, cfg, jnp.asarray(tokens)[None],
            jnp.arange(length)[None], None, None, jnp.asarray([length]),
            last_pos=jnp.asarray([length - 1]))
    want = reference(params, tokens, [length - 1])
    assert np.abs(np.asarray(logits[0, 0]) - want[0]).max() < TOL


def test_prologue_join_and_decode_through_the_cache(tiny):
    cfg, params = tiny
    tokens, rows, got = _served(cfg)     # (the fixture's own weights)
    want = reference(params, tokens, rows)
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("change", ["window", "router"])
def test_the_comparison_fails_when_the_window_or_the_router_changes(
        tiny, change):
    """The same served logits against a reference WITHOUT the window
    (every layer full in its mask, the rotary tables as they were), and
    a program whose router scores with a sigmoid against the softmax
    reference: both miss 1e-4 by two orders, on the rows past the
    window and on every row."""
    cfg, params = tiny
    if change == "window":
        tokens, rows, got = _served(cfg)
        want = reference(params, tokens, rows,
                         dict(PUBLISHED, sliding_window=10 ** 6))
        # rows 40.. all lie past three stacked windows of 16
        assert np.abs(got - want).max() > 1e-2
    else:
        other = dataclasses.replace(cfg, router_rule="sigmoid_topk")
        tokens = [1] + tokens_of(80, 79)
        with jax.default_matmul_precision("highest"):
            got, _ = common.forward(
                params, other, jnp.asarray(tokens)[None],
                jnp.arange(80)[None], None, None, jnp.asarray([80]),
                last_pos=jnp.asarray([79]))
        want = reference(params, tokens, [79])
        assert np.abs(np.asarray(got[0, 0]) - want[0]).max() > 1e-2


def test_what_follows_a_row_never_reaches_the_reference(tiny):
    _cfg, params = tiny
    a = np.arange(3, 67)
    b = np.concatenate([a[:32], np.full((32,), 9)])
    assert np.allclose(reference(params, a, [31]),
                       reference(params, b, [31]), atol=1e-5)


def test_a_matrix_rounded_on_the_way_moves_the_reference(tiny):
    """`read` is the one seam of the reference: a control that rounds
    every matrix through float8 reads other logits (the chip's control,
    PERF.md PR 40, at the published widths)."""
    _cfg, params = tiny
    tokens = [1] + tokens_of(7, 63)

    def through_float8(leaf):
        return jnp.asarray(leaf, jnp.float32).astype(
            jnp.float8_e4m3fn).astype(jnp.float32)

    plain = reference(params, tokens, [63])
    rounded = np.asarray(ref.logits_at(params, PUBLISHED,
                                       np.asarray(tokens), [63],
                                       read=through_float8))
    assert np.abs(plain - rounded).max() > 1e-2


# --- the router and the expert layer -----------------------------------------


def test_the_softmax_router_against_a_hand_written_top_k_near_a_tie():
    """softmax over ALL experts in float32, the k largest (the lower id
    on an exact tie, as lax.top_k), renormalised to sum to one; no
    scale. Row 0 has a near-tie at the cut (logits 1.0 and 1.0 - 1e-6
    for the second place), row 1 an exact tie."""
    cfg = dataclasses.replace(get_model_config("tiny-mellum"),
                              embed_dim=4, routed_experts=6,
                              experts_held=6, moe_top_k=2)
    logits = np.array([[0.3, 1.0 - 1e-6, 2.0, 1.0, -1.0, 0.0],
                       [0.5, 0.5, -2.0, 0.5, 3.0, 0.1],
                       [5.0, -5.0, 0.0, 0.0, 0.0, 4.0]], np.float32)
    h = np.eye(4, dtype=np.float32)[:3]
    router = np.zeros((4, 6), np.float32)
    router[:3] = logits
    ids, w = hybrid.route(jnp.asarray(h), {"router": jnp.asarray(router)},
                          cfg)
    for row, (got_ids, got_w) in enumerate(zip(np.asarray(ids),
                                               np.asarray(w))):
        p = np.exp(logits[row].astype(np.float64))
        p /= p.sum()
        order = sorted(range(6), key=lambda e: (-p[e], e))[:2]
        assert list(got_ids) == order, row
        assert np.allclose(got_w, p[order] / p[order].sum(), rtol=1e-6)
        assert math.isclose(float(got_w.sum()), 1.0, rel_tol=1e-6)
    assert list(np.asarray(ids)[0]) == [2, 3]      # 1.0 over 1.0 - 1e-6
    assert list(np.asarray(ids)[1]) == [4, 0]      # the lowest id of a tie
    # the reference's dense weights are the same numbers
    dense = np.asarray(ref.router_weights(jnp.asarray(h),
                                          jnp.asarray(router), 2))
    for row in range(3):
        assert np.allclose(dense[row][np.asarray(ids)[row]],
                           np.asarray(w)[row], rtol=1e-6)
        assert np.count_nonzero(dense[row]) == 2


def test_an_unknown_router_rule_is_a_plain_error():
    cfg = dataclasses.replace(get_model_config("tiny-mellum"),
                              router_rule="softmax")
    with pytest.raises(ValueError, match="router_rule 'softmax'"):
        hybrid.route(jnp.zeros((1, 64)), {"router": jnp.zeros((64, 8))},
                     cfg)


def test_an_expert_layer_without_a_shared_expert_is_the_sum_of_its_experts(
        tiny):
    """No `shared` leaf, no shared product: the layer's output is
    sum_e w_e Expert_e(h) over the chosen two and nothing else."""
    cfg, params = tiny
    layer = params["layers"][1]
    assert set(layer) == {"norm", "router", "experts"}
    h = jnp.asarray(np.random.RandomState(5).randn(6, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, counts = hybrid.experts_mlp(h, layer, cfg)
        ids, w = hybrid.route(h, layer, cfg)
    stack = {k: np.asarray(v, np.float64)
             for k, v in layer["experts"].items()}
    x = np.asarray(h, np.float64)
    want = np.zeros_like(x)
    for t in range(6):
        for e, w_e in zip(np.asarray(ids)[t], np.asarray(w)[t]):
            a = x[t] @ stack["gate"][e]
            a = a / (1 + np.exp(-a)) * (x[t] @ stack["up"][e])
            want[t] += w_e * (a @ stack["down"][e])
    assert np.abs(np.asarray(out) - want).max() < 1e-5
    assert int(counts[1]) == 12                     # 6 tokens x top-2


def test_the_parameter_count_has_no_shared_expert(tiny):
    cfg, params = tiny
    leaves = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert leaves == fleet.estimate_param_count(cfg)
    whole = get_model_config("mellum2-12b-a2.5b")
    e, f = 2304, 896
    layer = (2 * e * 32 * 128 + 2 * e * 4 * 128 + e     # attention, norm
             + 64 * 3 * e * f + e * 64 + e)             # experts, router
    assert fleet.estimate_param_count(whole) == \
        28 * layer + 2 * 98_304 * e + e
    # 12.15 B: the model's "12B"; the cut's eight layers 3.795 B
    assert 12.1e9 < fleet.estimate_param_count(whole) < 12.2e9
    assert 3.79e9 < 8 * layer + 2 * 98_304 * e + e < 3.80e9


# --- the rotary tables -------------------------------------------------------


def test_the_yarn_table_against_transformers_at_the_published_numbers(cell):
    """Frequencies over all 128 dimensions, blended between theta's own
    and theta's / 16 (beta 32 / 1 over 8192); cos and sin carry the
    given attention factor: program, reference and `transformers`."""
    pytest.importorskip("torch")
    from transformers import PretrainedConfig
    from transformers.modeling_rope_utils import _compute_yarn_parameters

    full = cell["rope_parameters"]["full_attention"]
    hf = PretrainedConfig(
        rope_theta=full["rope_theta"], head_dim=128, hidden_size=2304,
        num_attention_heads=32, max_position_embeddings=131072,
        rope_scaling={k: full[k] for k in (
            "rope_type", "factor", "original_max_position_embeddings",
            "beta_fast", "beta_slow", "attention_factor")})
    want, factor = _compute_yarn_parameters(hf, "cpu")
    want = want.numpy().astype(np.float64)
    freqs, mult = ref.rotary_frequencies(full, 128)
    assert freqs.shape == (64,)
    assert mult == factor == 1.2772588722239782
    assert math.isclose(mult, 0.1 * math.log(16) + 1)
    assert np.allclose(freqs, want, rtol=1e-5)
    view = get_model_config("mellum2-12b-a2.5b").attention_layer(3)
    assert view.sliding_window is None and view.rope_yarn == (
        16.0, 8192.0, 32.0, 1.0)
    mine = np.asarray(common.yarn_inv_freq(128, 500000.0, *view.rope_yarn))
    assert np.allclose(mine, want, rtol=1e-5)
    assert view.rope_attention_factor == mult
    # the fastest pair keeps theta's own frequency, the slowest is / 16
    assert freqs[0] == 1.0 and math.isclose(
        freqs[-1], 500000.0 ** (-126 / 128) / 16, rel_tol=1e-9)
    plain, one = ref.rotary_frequencies(
        cell["rope_parameters"]["sliding_attention"], 128)
    assert one == 1.0 and np.allclose(
        plain, [500000.0 ** (-2 * j / 128) for j in range(64)])
    sliding = get_model_config("mellum2-12b-a2.5b").attention_layer(0)
    assert (sliding.sliding_window, sliding.rope_yarn,
            sliding.rope_theta) == (1024, None, 500000.0)


def test_position_matters_on_both_tables(tiny):
    _cfg, params = tiny
    a = np.arange(3, 63)
    shifted = np.concatenate([[7], a])
    assert np.abs(reference(params, a, [59])
                  - reference(params, shifted, [60])).max() > 1e-3


# --- the resolver ------------------------------------------------------------


def test_an_architecture_block_builds_the_model(tiny):
    cfg, _ = tiny
    built = resolve_model_config({"model": "tiny-mellum",
                                  "architecture": PUBLISHED,
                                  "max_seq_len": 512})
    assert built == cfg
    assert built.attention_classes == ((8, 16, 6), (8, None, 2))
    assert built.router_rule == "softmax_topk"
    assert built.shared_expert_dim == 0 and built.routed_scaling == 1.0
    assert not built.attn_gate and len(built.expert_layers) == 8


@pytest.mark.parametrize("change,message", [
    ({"shared_expert_intermediate_size": 32}, "unknown keys"),
    ({"layer_types": PERIOD}, "entries, num_hidden_layers says 8"),
    ({"mlp_layer_types": ["sparse"] * 7},
     "entries, num_hidden_layers says 8"),
    ({"mlp_layer_types": ["dense"] + ["sparse"] * 7}, "every layer sparse"),
    ({"scoring_func": "sigmoid"}, r"unknown keys \['scoring_func'\]"),
    ({"norm_topk_prob": False}, "norm_topk_prob=False"),
    ({"attention_bias": True}, "attention_bias=True"),
    ({"layer_types": ["chunked_attention"] * 8}, "layer types"),
])
def test_what_the_layers_are_not_written_for_fails_by_name(change, message):
    arch = dict(PUBLISHED, **change)
    with pytest.raises(ValueError, match=message):
        resolve_model_config({"model": "t", "architecture": arch})


def test_a_missing_key_fails_by_name():
    arch = {k: v for k, v in PUBLISHED.items() if k != "sliding_window"}
    with pytest.raises(ValueError, match="lacks the key 'sliding_window'"):
        resolve_model_config({"model": "t", "architecture": arch})


def test_the_reference_refuses_what_it_is_not_written_for():
    with pytest.raises(ValueError, match="num_hidden_layers"):
        ref.sizes_of(dict(PUBLISHED, layer_types=PERIOD))
    with pytest.raises(ValueError, match="every one sparse"):
        ref.sizes_of(dict(PUBLISHED,
                          mlp_layer_types=["dense"] + ["sparse"] * 7))
    with pytest.raises(ValueError, match="norm_topk_prob"):
        ref.sizes_of(dict(PUBLISHED, norm_topk_prob=False))


def test_the_sizes_are_read_from_the_published_keys(cell):
    sizes = ref.sizes_of(cell)
    assert (sizes["depth"], sizes["heads"], sizes["kv_heads"],
            sizes["head_dim"], sizes["window"]) == (8, 32, 4, 128, 1024)
    assert (sizes["experts"], sizes["top_k"]) == (64, 8)
    assert sizes["types"] == PERIOD * 2


# --- `correct`, the counter and its reader -----------------------------------


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


def _reader(name):
    path = os.path.join(bench_paths.BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _segment(t0, **attrs):
    return {"rung": "segment", "t0": t0, "dur_s": 0.01, "span_id": "s",
            "parent_id": None, "trace_id": "t", "attrs": attrs}


def test_window_dead_share_reads_the_segments_of_the_slice(monkeypatch,
                                                           cell):
    """Two segments inside the slice, one before it: 30 + 50 of 100 +
    100 page-layers lay behind a window. Spans without the attributes
    (the parent's program) give nothing to read."""
    read = _reader("kv.window_dead_share")
    spans = [_segment(9.0, pages_held=100, pages_behind_window=90),
             _segment(10.5, pages_held=100, pages_behind_window=30),
             _segment(11.5, pages_held=100, pages_behind_window=50),
             {"rung": "admit", "t0": 11.0, "dur_s": 0.01, "attrs": {}}]
    monkeypatch.setattr(
        telemetry, "spans_between",
        lambda a, b: [r for r in spans if a <= r["t0"] <= b])
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)
    ctx = {"config": cell, "slice": {"start": 10.0, "end": 12.0}}
    assert read(ctx) == pytest.approx(40.0)
    spans[1]["attrs"], spans[2]["attrs"] = {"steps": 3}, {"steps": 1}
    assert read(ctx) is None
    assert read({"config": {"hidden_size": 4096},
                 "slice": ctx["slice"]}) is None


def test_what_the_rows_hold_behind_their_windows_by_hand():
    """window_page_holdings from the rows' frontiers: pages a row holds
    x attention layers, and on the window layers the pages whose every
    position is more than the window back (where the decode walk
    starts: (valid - window) // page)."""
    from theroundtaible_tpu.engine.engine import InferenceEngine

    class Stub:
        cfg = get_model_config("mellum2-12b-a2.5b")
        kv = type("KV", (), {"page_size": 128})()
        noted = {}

        def _note_window_reads(self, reads):
            self.noted = reads
            return reads

    stub = Stub()
    got = InferenceEngine.window_page_holdings(stub, (3400, 900, 1024, 0))
    # 27 + 8 + 8 pages x 28 layers; behind: (3400 - 1024) // 128 = 18
    # pages x 21 window layers, and nothing for contexts inside a window
    assert got == {"pages_held": 43 * 28, "pages_behind_window": 18 * 21}
    assert stub.noted is got
    # at one context of 3400 the share is 0.75 x 18 / 27 = 0.5
    one = InferenceEngine.window_page_holdings(stub, (3400,))
    assert one["pages_behind_window"] / one["pages_held"] == 0.5


def test_the_new_series_are_bound():
    bound = telemetry.SURFACE_BINDINGS["engine_attention"]
    assert bound["pages_held"] == "roundtable_window_pages_held_total"
    assert bound["pages_behind_window"] == \
        "roundtable_window_pages_behind_total"
    assert "router_rule" in telemetry.SURFACE_BINDINGS["engine_moe"]
