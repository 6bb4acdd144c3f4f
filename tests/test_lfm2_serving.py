"""A model of gated short-convolution layers, attention layers of 64-wide
heads and routed experts, through the serving path on the CPU (tiny-lfm2:
both dense layers and one period — five conv layers, one attention
layer of 8 query heads over 4 kv heads of 64, so the pool holds two
heads a lane row as the published widths do; models/shortconv.py,
pallas/attention.py: lane_pack): prefill and decode through pages and
tails, own-slot continuation, capture at a page boundary and restore
into another slot, ragged joins through the scheduler with their
`conv_tokens`, the decline table, the packed pool through the three
paged kernels at the published geometry, a plain decoder of 64-wide
heads through both of its serving paths, and two controls that must FAIL
— the tails kept in bfloat16, and a tail restored from the wrong
snapshot. (The leader's hand-over: tests/test_state_handover.py, whose
fourth row this module is.)

Every path ends in a comparison with the plain reference
(benchmarks/configs/lfm2_reference.py: the convolution as three shifted
rows, a dense loop over the experts) on the engine's own weights. GAP: a
float32 engine serves the reference's own maximum at every position but
for rounding-level ties (1e-3 of a logit whose spread is about 0.16, as
the other models' serving tests hold it). LOGIT_TOL, for logits compared
as logits: the served forward sums the same float32 products in another
order, which moves a logit by 1.2e-6 here (the reading); with the tails
kept in bfloat16 — the next precision down — it moves by 1.4e-3 (the
control's reading). 2e-5 lies between, an order above the first and two
under the second. No share test (the guide asks one of an expert layer
that holds a share): every expert is held, and `experts_held ==
routed_experts` is asserted."""
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
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from configs import lfm2_reference as ref  # noqa: E402

from theroundtaible_tpu.engine.engine import InferenceEngine  # noqa: E402
from theroundtaible_tpu.engine.hybrid_state import page_keys  # noqa: E402
from theroundtaible_tpu.engine.models import hybrid, shortconv  # noqa: E402
from theroundtaible_tpu.engine.models.registry import (  # noqa: E402
    get_model_config, resolve_model_config)
from theroundtaible_tpu.engine.pallas import attention as pattn  # noqa: E402
from theroundtaible_tpu.engine.scheduler import SessionScheduler  # noqa: E402
from theroundtaible_tpu.utils import telemetry  # noqa: E402

GAP = 1e-3
LOGIT_TOL = 2e-5
PAGE = 16
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
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 512}
# (not the model's key: what a configuration file may state beside them)
STATED = {"head_dim": 64}


def make_engine(**kw):
    config = {"model": "tiny-lfm2", "dtype": "float32",
              "kv_layout": "paged", "page_size": PAGE, "num_slots": 8,
              "max_seq_len": 512, "seed": 3,
              # a state for every page of the pool (129 x 5 tails of 512
              # B), as the benchmark's cell has: no eviction, ever
              "state_snapshot_bytes": 129 * 5 * 512,
              "sampling": {"temperature": 0.0},
              "mesh": {"data": 1, "model": 1}}
    config.update(kw)
    return InferenceEngine.from_config(config)


@pytest.fixture(scope="module")
def engine():
    return make_engine()


def tokens_of(seed, n):
    # (streams of its own: under the other models' seeds one of
    # tests/test_state_handover.py's rows draws the end of sequence)
    return [int(t) for t in
            np.random.RandomState(1000 + seed).randint(3, 250, size=(n,))]


def reference_logits(eng, seq, rows):
    return np.asarray(ref.logits_at(eng.params, dict(PUBLISHED, **STATED),
                                    np.asarray(seq), rows))


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
    cfg = resolve_model_config({"model": "tiny-lfm2", "architecture": dict(
        PUBLISHED, **STATED), "max_seq_len": 512})
    assert cfg == get_model_config("tiny-lfm2")
    assert cfg.layer_kinds[:6] == (
        hybrid.SHORTCONV, hybrid.MLP, hybrid.SHORTCONV, hybrid.MLP,
        hybrid.ATTENTION, hybrid.EXPERTS)
    assert cfg.layer_kinds.count(hybrid.SHORTCONV) == 5
    assert cfg.attention_layers == (4,)
    assert cfg.recurrent and cfg.tie_embeddings and cfg.qk_norm
    assert cfg.experts_held == cfg.routed_experts == 8   # no share here
    assert cfg.router_rule == "sigmoid_bias_topk" and not cfg.shared_expert_dim
    # two heads of 64 a lane row: the pool's cell
    assert (cfg.lane_pack, cfg.page_heads, cfg.page_width) == (2, 2, 128)
    assert cfg.page_cells == 2 * 4 * 64
    for extra, complaint in [
            ({"conv_dilation": 2}, "unknown keys .*conv_dilation"),
            ({"conv_bias": True}, "conv_bias=True"),
            ({"use_expert_bias": False}, "use_expert_bias=False"),
            ({"layer_types": ["conv"] * 5 + ["sliding_attention"]},
             "layer_types .*sliding_attention"),
            ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
             "plain rotary"),
            ({"num_hidden_layers": 9}, "has 6 entries")]:
        with pytest.raises(ValueError, match=complaint):
            resolve_model_config({"model": "x", "architecture": dict(
                PUBLISHED, **extra)})
    with pytest.raises(ValueError, match="lacks the key 'conv_L_cache'"):
        resolve_model_config({"model": "x", "architecture": {
            k: v for k, v in PUBLISHED.items() if k != "conv_L_cache"}})
    full = get_model_config("lfm2-24b-a2b")
    assert (full.num_layers, full.embed_dim, full.mlp_dim, full.expert_dim,
            full.kv_repeat, full.head_dim) == (80, 2048, 11776, 1536, 4, 64)
    assert len(full.shortconv_layers) == 30
    assert full.attention_layers == tuple(range(4, 80, 8))
    assert (full.page_heads, full.page_width) == (4, 128)
    assert shortconv.bytes_per_state(full) == 8192       # bfloat16
    from theroundtaible_tpu.engine.fleet import estimate_param_count
    assert estimate_param_count(full) == 23_843_661_440


def test_prefill_then_decode_through_pages_and_tails(engine):
    prompt = [1] + tokens_of(1, 69)
    served, stats = serve(engine, "a", prompt)
    assert len(served) == 7 and stats.prefill_tokens == 70
    assert len(set(served)) > 3           # not the last token read, again
    assert worst_gap(engine, prompt, served) < GAP
    info = engine.describe()
    assert info["paged_decode"] == "pool-direct"
    assert info["ragged"]["path"] == "pallas_ragged"
    assert info["ragged"]["fallback_reason"] is None
    assert info["hybrid_state"]["misses"] >= 1
    # One attention layer over 4 kv heads of 64: pools of 2 rows of 128
    # lanes a position; five tails of two rows of the model's width.
    assert len(engine.kv.pools) == 1
    assert engine.kv.pools[0][0].shape[1:] == (PAGE, 2, 128)
    state = engine.hybrid.state
    assert set(state) == {"ssm", "conv", "sconv"}
    assert state["ssm"] == [] and [a.shape for a in state["sconv"]] \
        == [(9, 2, 64)] * 5
    assert sorted(engine.params["layers"][0]) == [
        "conv_w", "in_proj", "norm", "out_proj"]
    assert engine.params["layers"][4]["q_norm"].shape == (64,)


def test_describe_names_the_state_and_the_declines(engine):
    info = engine.describe()
    ran = info["shortconv"].pop("conv_tokens")
    assert ran > 0 and ran % 5 == 0
    assert info["shortconv"] == {
        "layers": 5, "channels": 64, "taps": 3,
        "bytes_per_state": 2 * 64 * 4, "state_dtype": "float32"}
    assert info["hybrid_state"]["bytes_per_state"] == 5 * 2 * 64 * 4
    assert info["declines"]["spec_decode"] == "recurrent-state"
    assert info["declines"]["grouped_product"].startswith("not on a TPU")
    # a row part, copied by the store: nothing of it keeps a slot from
    # being evacuated, and the leader pass hands it on
    assert "evacuation" not in info["declines"]
    assert "leader_state_handover" not in info["declines"]
    assert set(info["shortconv"]) | {"conv_tokens"} == set(
        telemetry.SURFACE_BINDINGS["engine_shortconv"])
    assert engine.joins_ragged_alone


def test_own_slot_continuation(engine):
    first = [1] + tokens_of(2, 50)
    served, _ = serve(engine, "cont", first)
    before = engine.hybrid.describe()
    longer = first + served + tokens_of(3, 30)
    again, stats = serve(engine, "cont", longer)
    after = engine.hybrid.describe()
    assert stats.prefill_tokens == 30
    assert after["continued_tokens"] - before["continued_tokens"] \
        == len(first + served)
    assert after["restore_bytes"] == before["restore_bytes"]
    assert worst_gap(engine, longer, again) < GAP


def test_a_snapshot_restores_into_another_slot_and_the_wrong_one_fails(
        engine):
    """Two donors of 64 tokens leave snapshots at the page boundary. A
    taker of the first donor's prefix starts from that tail and prefills
    ONE page more: the logits of its first new position's neighbourhood
    are the reference's (a conv layer's tail reaches two positions, five
    of them ten: the chunk's last position, 4 on, still reads it).
    Then the store's index is made to hand it the second donor's tails:
    the same logits leave LOGIT_TOL."""
    seen = []
    program = engine._prefill_step_hybrid

    def spy(*args, **kw):
        out = program(*args, **kw)
        seen.append(np.asarray(out[0])[0])
        return out

    engine._prefill_step_hybrid = spy
    try:
        one, two = [1] + tokens_of(14, 69), [1] + tokens_of(15, 69)
        serve(engine, "one", one, n=2)
        serve(engine, "two", two, n=2)
        assert engine.hybrid.holds(one, 64) and engine.hybrid.holds(two, 64)
        taker = one[:64] + tokens_of(16, 4)
        want = reference_logits(engine, taker, [67])[0]
        store = engine.hybrid
        before = store.describe()
        served, stats = serve(engine, "right", taker, n=4)
        after = store.describe()
        assert stats.prefill_tokens == 4       # the prefix: reused, by id
        assert after["reused_tokens"] - before["reused_tokens"] == 64
        per = after["bytes_per_state"]
        assert after["restore_bytes"] - before["restore_bytes"] == per
        assert np.abs(seen[-1] - want).max() < LOGIT_TOL
        assert worst_gap(engine, taker, served) < GAP
        key_one = page_keys(one, PAGE, 64)[-1]
        key_two = page_keys(two, PAGE, 64)[-1]
        snap = store._snap
        snap[key_one], snap[key_two] = snap[key_two], snap[key_one]
        serve(engine, "wrong", taker, n=2)
        snap[key_one], snap[key_two] = snap[key_two], snap[key_one]
        assert store.describe()["reused_tokens"] \
            - before["reused_tokens"] == 128
        assert np.abs(seen[-1] - want).max() > 5 * LOGIT_TOL
    finally:
        engine._prefill_step_hybrid = program


def step_logits(eng, tokens, n_prompt, dtype=jnp.float32):
    """Logits [len(tokens) - n_prompt + 1, V] of the serving path's own
    forward (paged_forward.forward_paged_hybrid, as the step programs
    call it): the prompt as one prefill from zero tails of `dtype`,
    then every further token as one decode step on pages and tails."""
    from theroundtaible_tpu.engine.paged_forward import forward_paged_hybrid
    cfg = eng.cfg
    state = hybrid.zero_state(cfg, 1, dtype)
    pools = [tuple(jnp.zeros((8, PAGE, 2, 128), jnp.float32)
                   for _ in range(2))]
    rows = jnp.asarray([0])
    table = jnp.arange(1, 9, dtype=jnp.int32)[None] % 8
    prompt = jnp.asarray(tokens[:n_prompt])[None]
    logits, pools, state, _c, _n = forward_paged_hybrid(
        eng.params, cfg, prompt, jnp.arange(n_prompt)[None], pools, table,
        jnp.asarray([n_prompt]), state, lengths=jnp.asarray([n_prompt]),
        last_pos=jnp.asarray([n_prompt - 1]), page_size=PAGE, rows=rows)
    out = [np.asarray(logits[0, 0])]
    step = jax.jit(lambda tok, at, pools, state: forward_paged_hybrid(
        eng.params, cfg, tok, at, pools, table, at[0] + 1, state,
        active=jnp.asarray([True]), page_size=PAGE, rows=rows)[:3])
    for at in range(n_prompt, len(tokens)):
        logits, pools, state = step(jnp.asarray([[tokens[at]]]),
                                    jnp.asarray([[at]]), pools, state)
        out.append(np.asarray(logits[0, 0]))
    return np.stack(out)


def test_prefill_then_decode_logits_and_the_bfloat16_tail_control(engine):
    """LOGITS of the served forward against the reference's, the last
    position of a 40-token prefill (across two page boundaries) and 20
    decode steps, within LOGIT_TOL. The control: the same with the tails
    — and so g, which is rounded once to their dtype — in bfloat16, the
    next precision down, leaves that tolerance."""
    tokens = [1] + tokens_of(13, 59)
    want = reference_logits(engine, tokens, list(range(39, len(tokens))))
    got = step_logits(engine, tokens, 40)
    assert np.abs(got - want).max() < LOGIT_TOL
    off = np.abs(step_logits(engine, tokens, 40, jnp.bfloat16) - want).max()
    assert off > 5 * LOGIT_TOL, off
    # ... and the whole-sequence forward (no pages, no tails kept) agrees.
    from theroundtaible_tpu.engine.models.common import forward
    whole, _ = forward(engine.params, engine.cfg, jnp.asarray([tokens]),
                       jnp.arange(len(tokens))[None], None, None,
                       jnp.asarray([len(tokens)]))
    assert np.abs(np.asarray(whole[0, 39:]) - want).max() < LOGIT_TOL


# --- the packed pool through the three paged kernels ------------------------


def _dense(q, keys, vals, pos, group):
    """float32 attention of q [n, H, D] at positions `pos` over one
    sequence's keys / values [L, K, D], query head h on kv head
    h // group."""
    k = np.repeat(keys, group, axis=1)
    v = np.repeat(vals, group, axis=1)
    s = np.einsum("nhd,lhd->nhl", q, k)
    s = np.where((np.arange(len(keys))[None] <= pos[:, None])[:, None],
                 s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("nhl,lhd->nhd", w / w.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("kernel", ["decode", "prologue", "ragged"])
def test_heads_of_64_two_a_lane_row_through_the_paged_kernels(kernel):
    """The published attention geometry (32 query heads over 8 kv heads
    of 64) along the lane layout the chip takes — interpret mode accepts
    any shape, so the pool is handed over PACKED, [P, ps, 4, 128], as the
    engine stores it: the wrappers then take the path the chip takes —
    against a dense softmax over the heads' own 64 values, bfloat16 pages
    read as the rounded values they hold."""
    from theroundtaible_tpu.engine.serving_loop import (RaggedSeq,
                                                        build_ragged_batch)
    h, kh, d, ps = 32, 8, 64, 16
    assert pattn.lane_pack(kh, d) == 2 and pattn._token_major(kh // 2, 2)
    rng = np.random.default_rng(52)
    plain = [jnp.asarray(rng.standard_normal((12, ps, kh, d)), jnp.bfloat16)
             for _ in range(2)]
    pool = [p.reshape(12, ps, kh // 2, 2 * d) for p in plain]
    kf, vf = (np.asarray(p.astype(jnp.float32)) for p in plain)
    tables = np.zeros((3, 6), np.int32)
    tables[0, :3], tables[1, :4], tables[2, :2] = [1, 2, 3], [4, 5, 6, 7], \
        [8, 9]

    def seq(i, n):
        return (kf[tables[i]].reshape(-1, kh, d)[:n],
                vf[tables[i]].reshape(-1, kh, d)[:n])

    def queries(*shape):
        q = jnp.asarray(rng.standard_normal(shape + (h, d)) * d ** -0.5,
                        jnp.bfloat16)
        return q, np.asarray(q.astype(jnp.float32))

    def close(got, want):
        assert got.shape == want.shape            # 64 wide, not 128
        np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)

    if kernel == "decode":
        valid = np.array([40, 57, 17], np.int32)
        q, qf = queries(3, 1)
        out = pattn.paged_decode_attention(
            q, pool[0], pool[1], jnp.asarray(tables), jnp.asarray(valid))
        for i in range(3):
            close(np.asarray(out[i], np.float32),
                  _dense(qf[i], *seq(i, valid[i]),
                         np.array([valid[i] - 1]), h // kh))
    elif kernel == "prologue":
        first, t = np.array([16, 32], np.int32), 16
        q, qf = queries(2, t)
        out = pattn.paged_prefill_attention(
            q, pool[0], pool[1], jnp.asarray(tables[:2]),
            jnp.asarray(first), jnp.asarray(first + t))
        for i in range(2):
            close(np.asarray(out[i], np.float32),
                  _dense(qf[i], *seq(i, first[i] + t),
                         first[i] + np.arange(t), h // kh))
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
            close(out[row:row + n],
                  _dense(qf[row:row + n], *seq(i, pos + n),
                         pos + np.arange(n), h // kh))
            row += -(-n // 8) * 8


@pytest.mark.parametrize("attn", ["auto", "dense"])
def test_a_plain_decoder_of_64_wide_heads_serves_from_the_packed_pool(attn):
    """Every model's pool packs 64-wide pairs, a plain decoder's too
    (tiny-llama at head_dim 64 over 2 kv heads): pool-direct through the
    kernels and the gather view both give the cache-free decode's tokens,
    a prefill, a ragged join and a continuation among them."""
    from reference_decode import assert_greedy
    from theroundtaible_tpu.engine.models.registry import register
    import dataclasses
    register(dataclasses.replace(get_model_config("tiny-llama"),
                                 name="tiny-llama-d64", head_dim=64))
    eng = InferenceEngine.from_config({
        "model": "tiny-llama-d64", "dtype": "float32",
        "kv_layout": "paged", "page_size": PAGE, "num_slots": 4,
        "max_seq_len": 256, "seed": 5, "attn": attn,
        "sampling": {"temperature": 0.0}, "mesh": {"data": 1, "model": 1}})
    assert eng.kv.pools[0][0].shape[1:] == (PAGE, 1, 128)
    assert (eng.describe()["paged_decode"] == "pool-direct") \
        == (attn == "auto")
    turns = [("a", [1] + tokens_of(61, 40)), ("b", [1] + tokens_of(62, 21))]
    ids = assert_greedy(eng, turns, 6)
    assert_greedy(eng, [("a", ids[0] + eng.kv._slots["a"].tokens[
        len(ids[0]):] + tokens_of(63, 9))], 5)


# --- through the scheduler -------------------------------------------------

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
    ran = eng.describe()["shortconv"]["conv_tokens"]
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
    assert d["ragged_joins"] > 0
    info = eng.hybrid.describe()
    assert info["continued_tokens"] > 0 and info["reused_tokens"] > 0
    # (the store holds a state a page: what `evictions` counts here are
    # snapshots that went with their radix node, never the LRU's)
    assert info["snapshots"] < info["snapshot_capacity"]
    admits = [s["attrs"] for s in spans if s["rung"] == "admit"]
    assert admits and all(
        {"state_from", "state_copy_bytes", "kv_matched_tokens",
         "state_reused_tokens"} <= set(a) for a in admits)
    segs = [s["attrs"] for s in spans if s["rung"] == "segment"][1:]
    assert segs and all({"conv_tokens", "state_capture_bytes",
                         "snapshot_bytes", "experts_hit"} <= set(a)
                        for a in segs)
    # What the join programs ran through the conv layers: tokens x 5
    # layers, pads left out, re-scanned ones among them; one writer
    # (HybridStateStore.note_scan), one series.
    total = eng.describe()["shortconv"]["conv_tokens"] - ran
    assert total > 0 and total % 5 == 0
    assert 0 < sum(a["conv_tokens"] for a in segs) <= total
    counters = telemetry.REGISTRY.snapshot()["counters"]
    assert any(k.startswith("roundtable_shortconv_tokens_total")
               and v >= total for k, v in counters.items())
    assert eng.describe()["prefix_cache"]["hits"] > 0
    assert set(eng.describe()["ragged"]["dispatches"]) == {"pallas_ragged"}
