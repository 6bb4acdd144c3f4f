"""A model with NO attention layer through the serving path, on the CPU
(tiny-brumby: power-retention layers, models/retention.py): prefill and
decode through an EMPTY pool tree and the slot states, own-slot
continuation, capture at a page boundary and restore into another slot,
ragged joins through the scheduler, the decline table, the seeded
gate's memory, and two controls that must FAIL — the state rounded to
bfloat16 every step, and a state restored from the wrong snapshot.

Every path ends in a comparison with the plain reference
(benchmarks/configs/brumby_reference.py: the quadratic form, no state)
on the engine's own weights. GAP: a float32 engine serves the
reference's own maximum at every position but for rounding-level ties
(held to 1e-3 of a logit whose spread is about 1, as the other models'
serving tests hold it). LOGIT_TOL, for logits compared as logits: the
recurrent form sums the same non-negative weights in another order,
which moves a logit by 4.5e-6 here (the float32 reading); with the
state rounded to bfloat16 after every step it moves by 6.3e-2 (the
control's reading: every out-projection at hybrid.RETENTION_SHARE, so
the mixers carry the logits; at RESIDUAL_SHARE it read 9.7e-4). 5e-5
lies between, an order of magnitude above the first."""
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
from configs import brumby_reference as ref  # noqa: E402

from theroundtaible_tpu.engine.engine import InferenceEngine  # noqa: E402
from theroundtaible_tpu.engine.hybrid_state import page_keys  # noqa: E402
from theroundtaible_tpu.engine.models import hybrid, retention  # noqa: E402
from theroundtaible_tpu.engine.models.registry import (  # noqa: E402
    get_model_config, resolve_model_config)
from theroundtaible_tpu.engine.scheduler import SessionScheduler  # noqa: E402
from theroundtaible_tpu.utils import telemetry  # noqa: E402

GAP = 1e-3
LOGIT_TOL = 5e-5
PAGE = 16
PUBLISHED = {
    "attention_bias": False, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 32768, "max_window_layers": 3,
    "model_type": "brumby", "num_attention_heads": 6,
    "num_hidden_layers": 3, "num_key_value_heads": 2,
    "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 512}


def make_engine(**kw):
    config = {"model": "tiny-brumby", "dtype": "float32",
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


def worst_gap(eng, prompt, served):
    seq = prompt + served
    rows = list(range(len(prompt) - 1, len(seq) - 1))
    logits = np.asarray(ref.logits_at(eng.params, PUBLISHED,
                                      np.asarray(seq), rows))
    return max(float(row.max() - row[tok])
               for row, tok in zip(logits, served))


def serve(eng, name, prompt, n=8):
    _texts, stats = eng.generate_batch_with_stats(
        [(name, prompt)], max_new_tokens=n)
    committed = eng.kv._slots[name].tokens
    assert committed[:len(prompt)] == prompt
    return committed[len(prompt):], stats


def test_the_published_keys_build_the_preset_and_an_unknown_key_fails():
    cfg = resolve_model_config({"model": "tiny-brumby",
                                "architecture": dict(PUBLISHED),
                                "max_seq_len": 512})
    assert cfg == get_model_config("tiny-brumby")
    assert cfg.layer_kinds == (hybrid.RETENTION, hybrid.MLP) * 3
    assert cfg.recurrent and cfg.qk_norm and not cfg.attention_layers
    with pytest.raises(ValueError, match="unknown keys .*power"):
        resolve_model_config({"model": "x", "architecture": dict(
            PUBLISHED, power=4)})
    with pytest.raises(ValueError, match="use_sliding_window=True"):
        resolve_model_config({"model": "x", "architecture": dict(
            PUBLISHED, use_sliding_window=True)})
    with pytest.raises(ValueError, match="lacks the key 'head_dim'"):
        resolve_model_config({"model": "x", "architecture": {
            k: v for k, v in PUBLISHED.items() if k != "head_dim"}})
    full = get_model_config("brumby-14b")
    assert (full.num_layers, full.embed_dim, full.mlp_dim) == (
        80, 5120, 17408)
    assert retention.bytes_per_state(full) == 8 * 8320 * 129 * 4


def test_the_whole_forward_is_the_reference(engine):
    """Logits, every position, prefill through the chunked form."""
    from theroundtaible_tpu.engine.models.common import forward
    tokens = np.asarray([1] + tokens_of(11, 99))
    got, _ = forward(engine.params, engine.cfg, jnp.asarray(tokens)[None],
                     jnp.arange(100)[None], None, None, jnp.asarray([100]))
    want = ref.logits_at(engine.params, PUBLISHED, tokens, list(range(100)))
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-4


def test_prefill_then_decode_through_an_empty_pool_tree(engine):
    prompt = [1] + tokens_of(1, 69)
    served, stats = serve(engine, "a", prompt)
    assert len(served) == 7 and stats.prefill_tokens == 70
    assert worst_gap(engine, prompt, served) < GAP
    info = engine.describe()
    assert info["paged_decode"] == "pool-direct"
    assert info["hybrid_state"]["misses"] >= 1
    # No attention layer: no pool holds a byte; pages are ids, counted
    # and mapped as for every model.
    assert engine.kv.pools == [] and engine.kv.hbm_bytes() == 0
    assert engine.kv.usable_pages() > 0
    assert engine.kv.pages_in_use() >= 5
    assert set(engine.hybrid.state) == {"ssm", "conv", "ret", "retn"}
    assert engine.hybrid.state["ssm"] == []
    assert len(engine.hybrid.state["ret"]) == 3


def test_describe_names_the_layout_the_kernel_and_the_declines(engine):
    info = engine.describe()
    assert info["retention"] == {
        "power": 2, "layers": 3, "state_rows": 144, "state_rows_min": 136,
        "bytes_per_state": 2 * 144 * 17 * 4, "kernel": "jnp"}
    assert info["hybrid_state"]["bytes_per_state"] == 3 * 2 * 144 * 17 * 4
    assert info["declines"]["retention_step"].startswith("not on a TPU")
    assert info["declines"]["spec_decode"] == "recurrent-state"
    assert "evacuation" in info["declines"]
    # (the leader pass hands its state on: tests/test_state_handover.py)
    assert "leader_state_handover" not in info["declines"]
    assert set(info["hybrid_state"]) == set(
        telemetry.SURFACE_BINDINGS["engine_hybrid_state"])


def test_the_join_buffer_is_sized_by_the_engine_config():
    """`ragged_tokens`: what one join dispatch's flat buffer holds (the
    benchmark's cell sizes it to a session's three knights)."""
    eng = make_engine(ragged_tokens=1536)
    assert eng.ragged_tokens == 1536
    assert eng.ragged_shapes == (64, 256, 1024, 1536)
    assert eng.describe()["ragged"]["tokens_budget"] == 1536
    assert make_engine().ragged_tokens == 1024


@pytest.mark.parametrize("feature,kw,reason", [
    ("quant", {"quant": "int8"}, "recurrent-state:quant-leaves"),
    ("kv_quant", {"kv_quant": "int8"}, "recurrent-state"),
    ("seq_parallel", {"seq_parallel": 2}, "recurrent-state"),
    ("kv_offload", {"kv_offload": True}, "recurrent-state"),
    ("lora", {"lora": {"adapters": {}}}, "recurrent-state"),
])
def test_what_is_asked_for_and_declined_says_why(feature, kw, reason):
    eng = make_engine(**kw)
    assert eng.describe()["declines"][feature] == reason


@pytest.mark.parametrize("kw,match", [
    ({"kv_layout": "contiguous"}, "kv_layout 'paged' only"),
    ({"mesh": {"data": 1, "model": 2}}, "2 devices is not supported"),
])
def test_what_the_model_cannot_be_served_without_fails_at_build(kw, match):
    if "mesh" in kw and len(jax.devices()) < 2:
        pytest.skip("one device here")
    with pytest.raises(ValueError, match=match):
        make_engine(**kw)


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


def test_capture_at_a_page_boundary_restores_into_another_slot(engine):
    base = [1] + tokens_of(4, 70)             # crosses pages 16..64
    serve(engine, "donor", base)
    assert engine.hybrid.holds(base, 64)
    other = base[:64] + tokens_of(5, 25)
    before = engine.hybrid.describe()
    served, stats = serve(engine, "taker", other)
    after = engine.hybrid.describe()
    assert stats.prefill_tokens == 25         # the prefix: reused, by id
    assert after["reused_tokens"] - before["reused_tokens"] == 64
    per = after["bytes_per_state"]
    assert after["restore_bytes"] - before["restore_bytes"] == per
    assert after["capture_bytes"] - before["capture_bytes"] == per
    assert worst_gap(engine, other, served) < GAP
    # and the same logits as a slot that scanned it all itself
    fresh = make_engine()
    again, _ = serve(fresh, "alone", other)
    assert again == served


def test_a_span_rescanned_from_zero_leaves_its_boundary_behind():
    """Pages cached and no state anywhere (a new session behind the
    preamble every session opens with): the first row that crosses the
    cached span's end leaves a snapshot THERE, its sibling behind it at
    the last boundary as ever, and the next such prompt starts from it."""
    eng = make_engine()
    store = eng.hybrid
    preamble = [1] + tokens_of(40, 2 * PAGE - 1)           # two whole pages
    first = preamble + tokens_of(41, 40)
    serve(eng, "first", first, n=2)                        # no page cached yet
    assert not store.holds(first, 2 * PAGE)                # only its last one
    assert store.holds(first, 4 * PAGE)
    topic = preamble + tokens_of(42, 45)
    turns = [("lancelot", topic + cue("lancelot", 1)),
             ("galahad", topic + cue("galahad", 1))]
    before = store.describe()
    eng.generate_batch_with_stats(turns, max_new_tokens=4)
    after = store.describe()
    assert after["rescanned_tokens"] - before["rescanned_tokens"] \
        == 2 * 2 * PAGE                                    # both from zero
    assert store.holds(topic, 2 * PAGE)                    # the leader's
    last = len(turns[1][1]) // PAGE * PAGE
    assert last > len(topic) - PAGE and store.holds(turns[1][1], last)
    assert after["snapshots_taken"] - before["snapshots_taken"] == 2
    for name, prompt in turns:
        served = eng.kv._slots[name].tokens[len(prompt):]
        assert worst_gap(eng, prompt, served) < GAP
    third = preamble + tokens_of(43, 30)
    before = after
    served, stats = serve(eng, "third", third)
    after = store.describe()
    assert after["rescanned_tokens"] == before["rescanned_tokens"]
    assert after["reused_tokens"] - before["reused_tokens"] == 2 * PAGE
    assert stats.prefill_tokens == len(third) - 2 * PAGE
    assert worst_gap(eng, third, served) < GAP
    fresh = make_engine()
    again, _ = serve(fresh, "alone", third)
    assert again == served


def test_a_snapshot_is_evicted_with_its_node(engine):
    base = [1] + tokens_of(8, 40)
    serve(engine, "evict", base)
    key = page_keys(base, PAGE, 32)[-1]
    store = engine.hybrid
    assert key in store._snap
    assert engine.prefix_cache.match(base)[1].snap == key
    engine.kv.release("evict")
    evicted = store.evictions
    while key in store._snap:
        assert engine.prefix_cache.reclaim(want=1) == 1
    assert store.evictions > evicted
    again, _stats = serve(engine, "evict2", base)
    assert worst_gap(engine, base, again) < GAP


def test_the_byte_budget_bounds_the_store():
    per = 3 * 2 * 144 * 17 * 4
    eng = make_engine(state_snapshot_bytes=3 * per + 100, num_slots=4)
    assert eng.hybrid.capacity == 3
    for i in range(4):
        serve(eng, f"k{i}", [1] + tokens_of(20 + i, 40), n=2)
    info = eng.hybrid.describe()
    assert info["snapshots"] == 3 and info["evictions"] >= 1
    assert info["bytes"] <= info["budget"]


def _median_gate(eng, tokens):
    """The median of g = sigmoid(W_g h) over `tokens`, every layer and
    kv head, from the weights alone (the reference's residual stream)."""
    gates = []
    for l in range(3):
        x = ref.hidden_after(eng.params, PUBLISHED, np.asarray(tokens),
                             n_blocks=l)
        layer = eng.params["layers"][2 * l]
        h = ref._normed(x, ref.as_float32(layer["norm"]), 1e-6)
        gates.append(np.asarray(jax.nn.sigmoid(
            h @ ref.as_float32(layer["g_proj"]))))
    return float(np.median(np.concatenate(gates)))


def test_the_seeded_gate_remembers_for_tens_to_hundreds_of_tokens(engine):
    """The recipe of common.init_params / hybrid.init_layer: without it
    g sits near 0.5 and no comparison below could see the state."""
    g = _median_gate(engine, [1] + tokens_of(12, 199))
    assert 0.98 <= g <= 0.999, g
    emb = np.asarray(engine.params["embedding"])
    assert (emb[:, hybrid.GATE_CHANNEL] == 1.0).all()
    # ... and no out-projection writes to that channel, while each
    # stands at RETENTION_SHARE of unit scale (so the mixers, not the
    # token's own embedding, carry the logits).
    for layer in engine.params["layers"]:
        out = np.asarray(layer.get("o_proj", layer.get("down_proj")),
                         np.float32)
        assert (out[..., hybrid.GATE_CHANNEL] == 0.0).all()
        fan_in = out.size // out.shape[-1]
        assert out[..., 1:].std() * fan_in ** 0.5 == pytest.approx(
            hybrid.RETENTION_SHARE, rel=0.05)


def step_logits(eng, tokens, n_prompt):
    """Logits [len(tokens) - n_prompt + 1, V] of the serving path's own
    forward (paged_forward.forward_paged_hybrid, as the step programs
    call it): the prompt as one prefill from a zero state, then every
    further token as one decode step on that state."""
    from theroundtaible_tpu.engine.paged_forward import forward_paged_hybrid
    cfg = eng.cfg
    state = hybrid.zero_state(cfg, 2)          # the row and the scratch
    rows, table = jnp.asarray([0]), jnp.zeros((1, 32), jnp.int32)
    prompt = jnp.asarray(tokens[:n_prompt])[None]
    logits, _p, state, _c, _n = forward_paged_hybrid(
        eng.params, cfg, prompt, jnp.arange(n_prompt)[None], [], table,
        jnp.asarray([n_prompt]), state, lengths=jnp.asarray([n_prompt]),
        last_pos=jnp.asarray([n_prompt - 1]), page_size=PAGE, rows=rows)
    out = [np.asarray(logits[0, 0])]
    for at in range(n_prompt, len(tokens)):
        logits, _p, state, _c, _n = forward_paged_hybrid(
            eng.params, cfg, jnp.asarray([[tokens[at]]]),
            jnp.asarray([[at]]), [], table, jnp.asarray([at + 1]), state,
            active=jnp.asarray([True]), page_size=PAGE, rows=rows)
        out.append(np.asarray(logits[0, 0]))
    return np.stack(out)


def test_prefill_then_decode_logits_and_the_bfloat16_state_control(
        engine, monkeypatch):
    """LOGITS of the served forward against the reference's, the last
    position of a prefill and 48 decode steps, within LOGIT_TOL. The
    control: the same steps with the state rounded to bfloat16 after
    each one — the next precision down — leaves that tolerance by an
    order of magnitude."""
    tokens = [1] + tokens_of(13, 88)
    want = np.asarray(ref.logits_at(engine.params, PUBLISHED,
                                    np.asarray(tokens),
                                    list(range(40, len(tokens)))))
    got = step_logits(engine, tokens, 41)
    assert np.abs(got - want).max() < LOGIT_TOL
    exact = retention.step_rows

    def rounded(q, k, v, log_g, ret, retn):
        y, ret, retn = exact(q, k, v, log_g, ret, retn)
        return (y, ret.astype(jnp.bfloat16).astype(jnp.float32),
                retn.astype(jnp.bfloat16).astype(jnp.float32))

    monkeypatch.setattr(retention, "step_rows", rounded)
    # (a layer's body is a function `jax.jit` has seen, and keeps the
    # trace it made above: the control's layers are traced where they
    # stand, so that they call what was just put in)
    from theroundtaible_tpu.engine import paged_forward
    monkeypatch.setattr(paged_forward, "_paged_hybrid_layer",
                        paged_forward._paged_hybrid_layer.__wrapped__)
    off = np.abs(step_logits(engine, tokens, 41) - want).max()
    assert off > 5 * LOGIT_TOL, off


def test_a_state_restored_from_the_wrong_snapshot_fails_64_tokens_on():
    """Two donors of 64 tokens; the store's index is made to hand the
    taker of the first donor's prefix the second donor's state. The
    taker prefills 64 tokens of its own from there: the logits of its
    LAST position, 64 tokens after the restore, are the reference's
    from the right state and leave LOGIT_TOL from the wrong one — the
    seeded gate's memory carries a wrong restore that far."""
    eng = make_engine()
    seen = []
    program = eng._prefill_step_hybrid

    def spy(*args, **kw):
        out = program(*args, **kw)
        seen.append(np.asarray(out[0])[0])
        return out

    eng._prefill_step_hybrid = spy
    one, two = [1] + tokens_of(14, 69), [1] + tokens_of(15, 69)
    serve(eng, "one", one)
    serve(eng, "two", two)
    taker = one[:64] + tokens_of(16, 64)
    want = np.asarray(ref.logits_at(eng.params, PUBLISHED,
                                    np.asarray(taker), [127]))[0]
    store = eng.hybrid
    before = store.describe()["reused_tokens"]
    serve(eng, "right", taker, n=2)
    assert store.describe()["reused_tokens"] - before == 64
    assert np.abs(seen[-1] - want).max() < LOGIT_TOL
    key_one = page_keys(one, PAGE, 64)[-1]
    key_two = page_keys(two, PAGE, 64)[-1]
    store._snap[key_one], store._snap[key_two] = (
        store._snap[key_two], store._snap[key_one])
    serve(eng, "wrong", taker, n=2)
    assert store.describe()["reused_tokens"] - before == 128
    assert np.abs(seen[-1] - want).max() > 5 * LOGIT_TOL


# --- through the scheduler -------------------------------------------------

KNIGHTS = ["lancelot", "galahad", "percival"]


def cue(knight, round_no):
    return [3 + ord(c) for c in f"\n[r{round_no}] {knight}: "]


def discussion(sched, eng, sid, opening, rounds=3, new=12):
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


def test_three_knights_three_rounds_with_joins_mid_decode():
    eng = make_engine()
    sched = SessionScheduler(eng)
    telemetry.arm()
    t_a = time.monotonic()
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
    assert d["failed"] == 0 and d["completed"] == 6
    # Every join takes the ragged program, the first into an empty batch
    # too and however few tokens it brings: no prologue program exists
    # for this model once it is scheduled.
    assert eng.joins_ragged_alone and eng.ragged_defer_min == 0
    assert d["ragged_joins"] == d["admitted"] == 6
    info = eng.hybrid.describe()
    assert info["continued_tokens"] > 0 and info["reused_tokens"] > 0
    admits = [s["attrs"] for s in spans if s["rung"] == "admit"]
    assert admits and all(
        {"state_from", "state_copy_bytes", "kv_matched_tokens",
         "state_reused_tokens"} <= set(a) for a in admits)
    # (a laggard's restore is on the `share` span that unblocked it)
    shares = [s["attrs"] for s in spans if s["rung"] == "share"]
    assert sum(a["state_copy_bytes"] for a in admits + shares) \
        == info["restore_bytes"]
    assert sum(a["handed"] for a in shares) == info["share_handed"] == 2
    assert any(a["state_snapshot"] and a["state_copy_bytes"]
               for a in admits)
    segs = [s["attrs"] for s in spans if s["rung"] == "segment"][1:]
    assert segs and all({"state_capture_bytes", "snapshot_bytes",
                         "snapshots_taken"} <= set(a) for a in segs)
    assert all(a["state_capture_bytes"]
               == a["snapshots_taken"] * info["bytes_per_state"]
               for a in segs)
    assert sum(a["state_capture_bytes"] for a in segs) > 0
    counters = telemetry.REGISTRY.snapshot()["counters"]
    for cause in ("restore", "capture"):
        assert any(k.startswith("roundtable_state_copy_bytes_total")
                   and f"cause={cause}" in k and v > 0
                   for k, v in counters.items()), cause
    # the pages: ids that hold no bytes, reused across knights
    assert eng.describe()["prefix_cache"]["hits"] > 0
