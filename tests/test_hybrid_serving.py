"""A model with recurrent state through the serving path, on the CPU:
prefill and decode through the paged cache and the slot states, the
joint reuse plan (own-slot continuation, snapshot attach at a page
boundary, divergence after a shared span, eviction of a snapshot with
its radix node), ragged joins mid-decode, three knights over three
rounds through the scheduler, no compile in steady state, and the
decline table.

Every path ends in a comparison with the plain reference
(benchmarks/configs/nemotron_h_reference.py) on the engine's own
weights: a float32 engine serves the reference's own maximum at every
position (gap 0 but for rounding-level ties, held to 1e-3 of a logit
whose spread is about 1)."""
import os
import sys
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from configs import nemotron_h_reference as ref  # noqa: E402

from theroundtaible_tpu.engine import compile_watch  # noqa: E402
from theroundtaible_tpu.engine.engine import InferenceEngine  # noqa: E402
from theroundtaible_tpu.engine.hybrid_state import page_keys  # noqa: E402
from theroundtaible_tpu.engine.scheduler import SessionScheduler  # noqa: E402
from theroundtaible_tpu.utils import telemetry  # noqa: E402

GAP = 1e-3
PAGE = 16
PUBLISHED = {
    "hybrid_override_pattern": "ME*ME", "norm_eps": 1e-5,
    "mamba_num_heads": 4, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.5, "n_routed_experts": 8}


def make_engine(**kw):
    config = {"model": "tiny-nemotron-h", "dtype": "float32",
              "kv_layout": "paged", "page_size": PAGE, "num_slots": 8,
              "max_seq_len": 512, "seed": 3,
              "sampling": {"temperature": 0.0},
              "mesh": {"data": 1, "model": 1}}
    config.update(kw)
    eng = InferenceEngine.from_config(config)
    eng.ragged_defer_min = 1     # tiny prompts still join as ragged chunks
    return eng


@pytest.fixture(scope="module")
def engine():
    return make_engine()


def tokens_of(seed, n):
    return [int(t) for t in
            np.random.RandomState(seed).randint(3, 250, size=(n,))]


def worst_gap(eng, prompt, served):
    """How far below the reference's maximum the served tokens lie, at
    their own positions, over prompt + what was served before them."""
    seq = prompt + served
    rows = list(range(len(prompt) - 1, len(seq) - 1))
    logits = np.asarray(ref.logits_at(eng.params, PUBLISHED,
                                      np.asarray(seq), rows))
    return max(float(row.max() - row[tok])
               for row, tok in zip(logits, served))


def serve(eng, name, prompt, n=8):
    """-> (tokens the slot committed after the prompt, stats)."""
    _texts, stats = eng.generate_batch_with_stats(
        [(name, prompt)], max_new_tokens=n)
    committed = eng.kv._slots[name].tokens
    assert committed[:len(prompt)] == prompt
    return committed[len(prompt):], stats


def test_prefill_then_decode_through_cache_and_state(engine):
    prompt = [1] + tokens_of(1, 69)
    served, stats = serve(engine, "a", prompt)
    assert len(served) == 7 and stats.prefill_tokens == 70
    assert worst_gap(engine, prompt, served) < GAP
    info = engine.describe()
    assert info["paged_decode"] == "pool-direct"
    assert info["hybrid_state"]["misses"] >= 1
    assert info["moe"]["held"] == 8 and info["moe"]["experts_hit"] > 0
    assert len(engine.kv.pools) == 1        # pools: attention layers only


def test_describe_says_which_grouped_product_served_and_what_it_did(
        engine):
    """The form (off the chip `lax.ragged_dot`, with the reason among
    the declines) and the rows it multiplied beside the rows a loop
    over every held expert would have; every key bound."""
    serve(engine, "rows", [1] + tokens_of(9, 40))
    info = engine.describe()
    moe = info["moe"]
    assert set(moe) == set(telemetry.SURFACE_BINDINGS["engine_moe"])
    assert moe["grouped_product"] == "ragged_dot"
    assert info["declines"]["grouped_product"].startswith("not on a TPU")
    # top-2 of 8 held: a quarter of the loop's rows, pads included, so
    # at least the counted tokens' assignments.
    assert moe["local_assignments"] <= moe["rows_multiplied"]
    assert moe["rows_multiplied"] * 4 == moe["rows_dense"]


def test_own_slot_continuation(engine):
    first = [1] + tokens_of(2, 50)
    served, _ = serve(engine, "cont", first)
    before = engine.hybrid.describe()
    longer = first + served + tokens_of(3, 30)
    again, stats = serve(engine, "cont", longer)
    after = engine.hybrid.describe()
    # The slot's own state stood exactly at what it had consumed: only
    # the 30 new tokens are scanned.
    assert stats.prefill_tokens == 30
    assert after["continued_tokens"] - before["continued_tokens"] \
        == len(first + served)
    assert worst_gap(engine, longer, again) < GAP


def test_snapshot_attach_at_a_page_boundary(engine):
    base = [1] + tokens_of(4, 70)             # crosses pages 16..64
    serve(engine, "donor", base)
    assert engine.hybrid.holds(base, 64)
    # Another slot, same first 64 tokens, then its own: pages by alias,
    # state from the snapshot at 64, and a re-scan of nothing before it.
    other = base[:64] + tokens_of(5, 25)
    before = engine.hybrid.describe()
    served, stats = serve(engine, "taker", other)
    after = engine.hybrid.describe()
    assert stats.prefill_tokens == 25
    assert after["reused_tokens"] - before["reused_tokens"] == 64
    assert worst_gap(engine, other, served) < GAP


def test_divergence_after_a_shared_span(engine):
    """A prompt that leaves the donor's inside a page. The donor's one
    prefill chunk left its snapshot at the LAST boundary it crossed
    (80). A fork at 86 has pages to 80 or beyond and the state at 80:
    it re-scans from there. A fork at 70 has pages to 64 and NO state
    at or below them: its pages were there, its state was not, and it
    scans from zero — never a state used at a position other than its
    own."""
    base = [1] + tokens_of(6, 90)
    serve(engine, "d1", base)
    assert engine.hybrid.holds(base, 80) \
        and not engine.hybrid.holds(base, 64)
    late = base[:86] + tokens_of(7, 20)
    served, stats = serve(engine, "d2", late)
    assert stats.prefill_tokens == len(late) - 80
    assert worst_gap(engine, late, served) < GAP
    early = base[:70] + tokens_of(8, 20)
    before = engine.hybrid.describe()
    served, stats = serve(engine, "d3", early)
    after = engine.hybrid.describe()
    assert stats.prefill_tokens == len(early)
    assert after["rescanned_tokens"] - before["rescanned_tokens"] >= 64
    assert after["misses"] == before["misses"] + 1
    assert worst_gap(engine, early, served) < GAP
    # It re-wrote pages the index already held: at commit it took the
    # index's and gave its copies back (no transcript of duplicates).
    assert engine.kv._slots["d3"].pages[:4] \
        == engine.kv._slots["d1"].pages[:4]
    assert engine.describe()["hybrid_state"]["deduped_pages"] >= 4


def test_a_snapshot_is_evicted_with_its_node(engine):
    base = [1] + tokens_of(8, 40)
    serve(engine, "evict", base)
    key = page_keys(base, PAGE, 32)[-1]
    store = engine.hybrid
    assert key in store._snap
    nodes = engine.prefix_cache.match(base)
    assert nodes[1].snap == key
    engine.kv.release("evict")
    # The index is the pages' only holder now: reclaiming drops the
    # leaf first, and the snapshot bound to it with it.
    evicted = store.evictions
    while key in store._snap:
        assert engine.prefix_cache.reclaim(want=1) == 1
    assert store.evictions > evicted
    # With neither pages nor state the same prompt starts from zero,
    # and is still served right.
    again, stats = serve(engine, "evict2", base)
    assert worst_gap(engine, base, again) < GAP


def test_the_byte_budget_bounds_the_store():
    eng = make_engine(state_snapshot_bytes=3 * 11264, num_slots=4)
    assert eng.hybrid.capacity == 3
    for i in range(4):
        serve(eng, f"k{i}", [1] + tokens_of(20 + i, 40), n=2)
    info = eng.hybrid.describe()
    assert info["snapshots"] == 3 and info["evictions"] >= 1
    assert info["bytes"] <= info["budget"]


# --- through the scheduler -------------------------------------------------

KNIGHTS = ["lancelot", "galahad", "percival"]


def cue(knight, round_no):
    return [3 + ord(c) for c in f"\n[r{round_no}] {knight}: "]


def discussion(sched, eng, sid, opening, rounds=3, new=12, check=True):
    """The benchmark's own traffic at a tiny size: every round each
    knight gets the transcript and its cue; the transcript grows by
    every cue and answer. -> [(prompt, committed answer)...]."""
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
    if check:
        for p, a in served:
            assert worst_gap(eng, p, a) < GAP
    return served


@pytest.fixture(scope="module")
def scheduled():
    eng = make_engine()
    sched = SessionScheduler(eng)
    yield eng, sched
    sched.close()


def test_three_knights_three_rounds_with_joins_mid_decode(scheduled):
    eng, sched = scheduled
    telemetry.arm()
    t_a = time.monotonic()
    results, errors = {}, []

    def run(sid, seed, n_open):
        try:
            results[sid] = discussion(
                sched, eng, sid, [1] + tokens_of(seed, n_open))
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
    assert not errors, errors
    d = sched.describe()
    assert d["failed"] == 0 and d["completed"] == 6
    assert d["ragged_joins"] >= 1       # a round joined live decode rows
    info = eng.hybrid.describe()
    # Round 2 and 3: the first knight continues its own state, the
    # others start from a snapshot of the shared transcript.
    assert info["continued_tokens"] > 0 and info["reused_tokens"] > 0
    # The leader pass: s1's opening passes MIN_SHARED_PREFIX (no later
    # round's new span does, at these answers): its two laggards were
    # handed the leader's state if the round joined s0's live rows, and
    # scanned for themselves if it ran a prologue
    # (tests/test_state_handover.py holds it to two a round).
    assert info["share_handed"] + info["share_declined"] == 2
    admits = [s["attrs"] for s in spans if s["rung"] == "admit"]
    assert admits and all(
        {"state_from", "kv_matched_tokens", "state_reused_tokens",
         "prompt_tokens"} <= set(a) for a in admits)
    assert any(a["state_continue"] for a in admits)
    assert any(a["state_snapshot"] for a in admits)
    segs = [s["attrs"] for s in spans if s["rung"] == "segment"]
    # (The first span after arming only sets the counters' base.)
    assert "experts_hit" not in segs[0] and "snapshot_bytes" in segs[0]
    segs = segs[1:]
    assert segs and all(
        {"experts_hit", "local_assignments", "expert_layer_steps",
         "snapshots_taken", "snapshot_bytes"} <= set(a) for a in segs)
    hit = sum(a["experts_hit"] for a in segs)
    assert 0 < hit <= 8 * sum(a["expert_layer_steps"] for a in segs)
    assert sum(a["experts_hit"] for a in segs) > 0
    assert sum(a["snapshots_taken"] for a in segs) > 0


def test_a_plain_segments_span_carries_its_own_expert_counts(scheduled):
    """The counts are an output of the segment's program, folded when
    that segment has been read: a plain span of `steps` steps carries
    its own steps x expert layers `expert_layer_steps` (a step no row
    was live in counts nothing), pipelined or not, and nothing stays
    queued when the batch has drained."""
    eng, sched = scheduled
    telemetry.arm()
    t_a = time.monotonic()
    discussion(sched, eng, "own", [1] + tokens_of(77, 50), rounds=2,
               new=70, check=False)
    spans = telemetry.spans_between(t_a, time.monotonic())
    telemetry.disarm()
    n_e = len(eng.cfg.expert_layers)
    segs = [s["attrs"] for s in spans if s["rung"] == "segment"][1:]
    plain = [a for a in segs if a["kind"] == "plain"]
    assert len(plain) >= 2
    for a in plain:
        # (A prologue's few prefill dispatches before the span add
        # theirs: an expert layer each.)
        assert (a["steps"] - 1) * n_e <= a["expert_layer_steps"] \
            <= (a["steps"] + 8) * n_e
    assert {a["steps"] for a in plain} >= {6, 64}
    assert not eng.hybrid._counts_pending


def test_describe_reads_host_ints_while_the_scheduler_serves(scheduled):
    """`describe()` from another thread (the benchmark reads it at the
    window's two ends, an operator whenever) touches no device buffer:
    the state tree holds no counter leaf, the totals are host ints."""
    eng, sched = scheduled
    assert set(eng.hybrid.state) == {"ssm", "conv"}
    seen, stop = [], threading.Event()

    def poll():
        while not stop.is_set():
            seen.append(eng.describe()["moe"]["expert_layer_steps"])

    t = threading.Thread(target=poll)
    t.start()
    try:
        discussion(sched, eng, "poll", [1] + tokens_of(78, 60), rounds=2,
                   new=20, check=False)
    finally:
        stop.set()
        t.join()
    assert seen == sorted(seen) and seen[-1] > seen[0]
    assert all(type(v) is int for v in eng.hybrid.moe_totals().values())


def test_counts_fold_oldest_first_and_never_the_one_in_flight():
    from theroundtaible_tpu.engine.hybrid_state import (MOE_COUNTS,
                                                        HybridStateStore)
    from theroundtaible_tpu.engine.models.registry import get_model_config
    store = HybridStateStore(get_model_config("tiny-nemotron-h"), 2, 16, 0)
    names = MOE_COUNTS
    one = np.arange(1, len(names) + 1, dtype=np.int32)
    at = {n: int(v) for n, v in zip(names, one)}

    def times(k):
        return {n: k * v for n, v in at.items()}

    store.note_counts(one, pipelined=False)       # a prologue's prefill
    store.note_counts(10 * one, pipelined=True)   # segment N
    assert store.moe_totals()["experts_hit"] == 0     # nothing read yet
    store.note_counts(100 * one, pipelined=True)  # N+1, before N is read
    assert store.moe_totals() == times(1)
    store.fold_counts(keep=1)                     # N has been read
    assert store.moe_totals()["experts_hit"] == 11
    assert store.moe_delta()["expert_layer_steps"] \
        == 11 * at["expert_layer_steps"]
    store.fold_counts()                           # the batch drained
    assert store.moe_totals()["local_assignments"] == 222
    assert store.moe_totals()["rows_multiplied"] \
        == 111 * at["rows_multiplied"]
    assert store.moe_delta() == times(100)


def test_cache_on_serves_what_cache_off_serves(scheduled):
    """The same discussion with the snapshot store and the prefix index
    off: every admission scans from zero or from its own slot, and the
    tokens are the same."""
    eng, sched = scheduled
    opening = [1] + tokens_of(77, 60)
    on = discussion(sched, eng, "parity", opening, check=False)
    cold = make_engine(state_snapshot_bytes=0, prefix_cache=False)
    cold_sched = SessionScheduler(cold)
    try:
        off = discussion(cold_sched, cold, "parity", opening, check=False)
    finally:
        cold_sched.close()
    assert cold.hybrid.describe()["snapshots"] == 0
    assert [a for _p, a in on] == [a for _p, a in off]


def test_no_compile_in_steady_state_across_occupancy_drift(monkeypatch):
    """Two staggered discussions drift the batch between 3 and 6 rows,
    joins ride ragged dispatches, states continue and attach: after one
    such pass as warm-up, a second compiles nothing."""
    eng = make_engine(num_pages=256)
    eng.warmup(max_prompt_tokens=256, batch_sizes=(1, 3))
    sched = SessionScheduler(eng)

    def drift(tag, seed):
        errors = []

        def run(sid, seed):
            try:
                discussion(sched, eng, sid, [1] + tokens_of(seed, 45),
                           rounds=2, new=70, check=False)
            except BaseException as e:  # noqa: BLE001 — asserted below
                errors.append(e)

        threads = [threading.Thread(target=run,
                                    args=(f"{tag}{i}", seed + i))
                   for i in range(2)]
        for t in threads:
            t.start()
            # (a round's joins take the ragged program whatever the
            # batch holds and compile nothing: a discussion alone is
            # over in a fifth of a second here)
            time.sleep(0.02)
        for t in threads:
            t.join()
        assert not errors, errors

    try:
        # One discussion alone (3 rows: the 4-row decode program), then
        # the staggered pair (up to 6 rows: the 8-row one).
        discussion(sched, eng, "solo", [1] + tokens_of(49, 45), rounds=2,
                   new=70, check=False)
        drift("w", 50)
        sched.declare_warmup_complete()
        monkeypatch.setenv("ROUNDTABLE_RECOMPILE_STRICT", "1")
        before = compile_watch.steady_state_compiles()
        drift("d", 60)
        assert compile_watch.steady_state_compiles() == before, [
            e.get("label") for e in compile_watch.history()[-6:]]
        assert sched.describe()["max_occupancy"] > 3
    finally:
        sched.close()


# --- what declines ---------------------------------------------------------


@pytest.mark.parametrize("feature,config,where", [
    ("spec_decode", {"spec_decode": True}, "spec_reason"),
    ("lora", {"lora": {"max_adapters": 2, "rank": 4}}, "lora_reason"),
    ("kv_quant", {"kv_quant": "int8"}, "kv_quant_reason"),
    ("quant", {"quant": "int8"}, None),
    ("seq_parallel", {"seq_parallel": 2}, None),
    ("kv_offload", {"kv_offload": True}, None),
])
def test_what_cannot_carry_the_state_declines_with_a_reason(
        feature, config, where):
    eng = make_engine(num_slots=2, **config)
    reason = eng.describe()["declines"][feature]
    assert reason.startswith("recurrent-state")
    if where:
        assert getattr(eng, where) == reason
    assert eng.quant == "none" and eng.kv_quant_spec is None
    assert not eng.spec_decode and eng.lora is None
    assert eng.kv_offload is None and eng._ring_prefill_fn is None
    # ... and the engine still serves, right.
    prompt = [1] + tokens_of(9, 20)
    served, _ = serve(eng, "x", prompt, n=3)
    assert worst_gap(eng, prompt, served) < GAP


@pytest.mark.parametrize("config,message", [
    ({"kv_layout": "contiguous"}, "paged"),
    ({"mesh": {"data": 1, "model": 2}}, "mesh"),
    ({"attn": "dense"}, "pool-direct"),
])
def test_what_the_model_cannot_serve_without_fails_at_build(config,
                                                            message):
    with pytest.raises(ValueError, match=message):
        make_engine(num_slots=2, **config)


def test_fleet_estimates_the_state_beside_the_pools():
    from theroundtaible_tpu.engine.fleet import estimate_engine_hbm_bytes
    base = {"model": "tiny-nemotron-h", "kv_layout": "paged",
            "num_slots": 4, "num_pages": 32, "page_size": 16}
    small = estimate_engine_hbm_bytes(dict(base, state_snapshot_bytes=0))
    big = estimate_engine_hbm_bytes(
        dict(base, state_snapshot_bytes=1 << 20))
    assert big - small == 1 << 20
