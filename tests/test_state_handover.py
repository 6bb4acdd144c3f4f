"""The leader pass of a model with recurrent state, on the CPU: the span
a request's rows have in common is scanned ONCE — the leader leaves its
state at the last page boundary under the span's end, pinned in the
snapshot store, and the laggards start from it when they unblock
(kvcache.share_prefixes, hybrid_state.expect, engine.join_laggard,
scheduler._alias_due). One set of cases over the four kinds of state
behind the one store — tiny Nemotron-H (Mamba-2 beside attention pages),
tiny Brumby (retention, pages that hold no bytes), tiny Jamba (scanned
runs of Mamba-1 beside one-kv-head pages), tiny LFM2 (conv tails of two
rows beside packed 64-wide pages) — each through its own serving
test's engine, traffic and plain reference: the mechanism knows no layer
kind, so neither do the cases.

Every case ends in the reference's gap (`worst_gap` under the model's
GAP), the first also in token identity with rows that scan alone."""
import importlib
import os
import sys
import time

import pytest

jax = pytest.importorskip("jax")

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from theroundtaible_tpu.engine.hybrid_state import (  # noqa: E402
    HybridStateStore, page_keys)
from theroundtaible_tpu.engine.models.registry import (  # noqa: E402
    get_model_config)
from theroundtaible_tpu.engine.scheduler import SessionScheduler  # noqa: E402
from theroundtaible_tpu.utils import telemetry  # noqa: E402

MODELS = {"nemotron-h": ("test_hybrid_serving", "tiny-nemotron-h"),
          "brumby": ("test_brumby_serving", "tiny-brumby"),
          "jamba": ("test_jamba_serving", "tiny-jamba"),
          "lfm2": ("test_lfm2_serving", "tiny-lfm2")}
PAGE = 16
NEW = 24      # an answer: a round's new span (3 cues + 3 answers) passes
              # MIN_SHARED_PREFIX, as the benchmark's 128-token ones do


def scheduled(m, **kw):
    """The model's serving-test engine behind a scheduler. A model with
    recurrent state joins the ragged program whatever the batch holds;
    with no floor on what a join must bring (Brumby's and Jamba's by
    build), a tiny round alone on the CPU is a deferred admission."""
    eng = m.make_engine(**kw)
    assert eng.joins_ragged_alone
    eng.ragged_defer_min = 0
    return eng, SessionScheduler(eng)


@pytest.fixture(scope="module", params=list(MODELS))
def served(request):
    m = importlib.import_module(MODELS[request.param][0])
    eng, sched = scheduled(m)
    yield m, eng, sched
    sched.close()


def play_round(m, eng, sched, sid, transcript, r, new=NEW):
    """One round of the benchmark's traffic at a tiny size. -> (turns,
    the answers committed, what the round moved: `handed`, `declined`,
    `scanned` prompt tokens, the store's own sums)."""
    turns = [(k, transcript + m.cue(k, r)) for k in m.KNIGHTS]
    before = dict(eng.hybrid.describe(),
                  scanned=sched.segment_prefill_tokens)
    sched.submit(sid, turns, max_new_tokens=new)
    after = dict(eng.hybrid.describe(),
                 scanned=sched.segment_prefill_tokens)
    answers = []
    for k, p in turns:
        name = next(n for n in eng.kv._slots
                    if n.endswith(k) and n.startswith(sid))
        answers.append(eng.kv._slots[name].tokens[len(p):])
    moved = {key: after[key] - before[key] for key in
             ("share_handed", "share_declined", "scanned",
              "continued_tokens", "reused_tokens", "snapshots_taken")}
    return turns, answers, moved


def grown(m, transcript, turns, answers, r):
    for (k, _p), a in zip(turns, answers):
        transcript = transcript + m.cue(k, r) + a
    return transcript


def hand_over_at(turns):
    """The page boundary under the end of what the prompts share."""
    shared = os.path.commonprefix([p for _k, p in turns])
    return min(len(shared), min(len(p) for _k, p in turns) - 1) \
        // PAGE * PAGE


def test_a_rounds_shared_span_is_scanned_once(served):
    """Three rounds: each hands the leader's state to both laggards and
    declines nothing; the tokens scanned are the leader's span and two
    tails from the hand-over boundary; what is served lies inside the
    reference's gap and is what the same prompts are served with no
    snapshot anywhere and none to be taken — the pass declined, every
    row scanning alone from zero or from its own slot's state."""
    m, eng, sched = served
    store = eng.hybrid
    transcript, own, played = [1] + m.tokens_of(81, 60), 0, []
    for r in (1, 2, 3):
        turns, answers, moved = play_round(m, eng, sched, "once",
                                           transcript, r)
        assert all(len(a) == NEW - 1 for a in answers)   # no eos
        at = hand_over_at(turns)
        assert (moved["share_handed"], moved["share_declined"]) == (2, 0)
        # (the leader: from zero in round one, then from its own state,
        # which stands where its last turn ended)
        tails = [len(p) - at for _k, p in turns[1:]]
        assert moved["scanned"] == len(turns[0][1]) - own + sum(tails)
        assert moved["reused_tokens"] == 2 * at
        assert moved["continued_tokens"] == own
        for (_k, p), a in zip(turns, answers):
            assert m.worst_gap(eng, p, a) < m.GAP
        own = len(turns[0][1]) + len(answers[0])
        played.append((turns, answers, moved["scanned"]))
        transcript = grown(m, transcript, turns, answers, r)
    assert not store._pins
    counters = telemetry.REGISTRY.snapshot()["counters"]
    assert any(k.startswith("roundtable_state_share_handed_total")
               and eng.cfg.name in k and v >= 6
               for k, v in counters.items())
    store.drop_all_snapshots()
    capacity, store.capacity = store.capacity, 0
    try:
        for r, (turns, answers, scanned) in enumerate(played, 1):
            _t, alone, moved = play_round(m, eng, sched, "alone",
                                          turns[0][1][:-len(m.cue(
                                              m.KNIGHTS[0], r))], r)
            assert alone == answers
            # (The index holds every page of these prompts: the leader
            # "covers" the span, pages alias, no state comes with them.)
            assert moved["share_handed"] == moved["reused_tokens"] == 0
            assert moved["scanned"] > 2 * scanned or r == 1
    finally:
        store.capacity = capacity


def test_the_share_span_says_what_was_handed_and_spared(served):
    m, eng, sched = served
    telemetry.arm()
    t_a = time.monotonic()
    try:
        turns, answers, moved = play_round(
            m, eng, sched, "span", [1] + m.tokens_of(82, 70), 1, new=4)
    finally:
        spans = telemetry.spans_between(t_a, time.monotonic())
        telemetry.disarm()
    at = hand_over_at(turns)
    (share,) = [s["attrs"] for s in spans if s["rung"] == "share"]
    assert share["followers"] == share["handed"] == 2
    assert share["tokens_spared"] == share["state_reused_tokens"] == 2 * at
    # (what the laggards scan again, under a page each: the span's end
    # lies past the boundary the state stands at)
    assert 0 <= share["kv_matched_tokens"] - 2 * at < 2 * PAGE
    assert share["state_copy_bytes"] \
        == 2 * eng.hybrid.describe()["bytes_per_state"]
    # A laggard's span counts as reused, as for every other model.
    stats = [s["attrs"] for s in spans if s["rung"] == "admit"]
    assert len(stats) == 1 and stats[0]["deferred"]
    assert moved["scanned"] == len(turns[0][1]) + sum(
        len(p) - at for _k, p in turns[1:])


def test_no_state_left_to_pin_declines_at_admission(served):
    """Every state the store may hold is pinned for someone else: the
    pass is declined where it is planned, nobody blocks, every row
    scans for itself and is served right."""
    m, eng, sched = served
    store = eng.hybrid
    store._pins = {bytes([i]) * 16: 1 for i in range(store.capacity)}
    try:
        turns, answers, moved = play_round(
            m, eng, sched, "full", [1] + m.tokens_of(83, 70), 1, new=6)
    finally:
        store._pins = {}
    assert (moved["share_handed"], moved["share_declined"]) == (0, 2)
    assert moved["scanned"] == sum(len(p) for _k, p in turns)
    for (_k, p), a in zip(turns, answers):
        assert m.worst_gap(eng, p, a) < m.GAP


def test_a_leader_that_fails_takes_its_request_and_leaves_no_pin(
        served, monkeypatch):
    """The leader's dispatch fails for good: the request fails whole
    (no laggard waits on a leader that will not come), its laggards
    count as declined and the pin goes. Submitted again — with a
    dispatch that fails ONCE and is issued again, so the boundary is
    owed again — the state is handed on and the round served right."""
    m, eng, sched = served
    store = eng.hybrid
    program = eng._ragged_step_hybrid
    errors = [TimeoutError("injected: the leader's dispatch hangs"),
              RuntimeError("injected: a transient dispatch error")]

    def failing(*args, **kw):
        if errors:
            raise errors.pop(0)
        return program(*args, **kw)

    monkeypatch.setattr(eng, "_ragged_step_hybrid", failing)
    transcript = [1] + m.tokens_of(84, 70)
    turns = [(k, transcript + m.cue(k, 1)) for k in m.KNIGHTS]
    before = store.describe()
    with pytest.raises(Exception, match="injected: the leader"):
        sched.submit("fail", turns, max_new_tokens=6)
    after = store.describe()
    assert after["share_declined"] - before["share_declined"] == 2
    assert after["share_handed"] == before["share_handed"]
    assert not store._pins and len(errors) == 1
    assert not store.holds(turns[0][1], hand_over_at(turns))
    turns, answers, moved = play_round(m, eng, sched, "fail", transcript,
                                       1, new=6)
    assert not errors
    assert (moved["share_handed"], moved["share_declined"]) == (2, 0)
    assert not store._pins
    for (_k, p), a in zip(turns, answers):
        assert m.worst_gap(eng, p, a) < m.GAP


def test_an_admission_that_is_not_deferred_declines(served):
    """`generate_batch` runs the prologue, which no scheduler stands
    behind: nothing can unblock a laggard, so the pass is declined,
    counted, and every row scans the span itself."""
    m, eng, _sched = served
    transcript = [1] + m.tokens_of(85, 70)
    turns = [(f"pro-{k}", transcript + m.cue(k, 1))
             for k in m.KNIGHTS[:2]]
    before = eng.hybrid.describe()
    _texts, stats = eng.generate_batch_with_stats(turns, max_new_tokens=6)
    after = eng.hybrid.describe()
    assert after["share_declined"] - before["share_declined"] == 1
    assert after["share_handed"] == before["share_handed"]
    assert stats.prefill_tokens == sum(len(p) for _k, p in turns)
    for name, p in turns:
        assert m.worst_gap(eng, p, eng.kv._slots[name].tokens[len(p):]) \
            < m.GAP


def test_a_boundary_owed_to_an_earlier_span_comes_first(served):
    """A new session behind a preamble whose pages are cached and whose
    state is not: the leader's one run crosses the preamble's end and
    the hand-over boundary, and leaves its one snapshot at the FIRST —
    every later session starts from it. Its own laggards find no state
    where they were to start: they fall back to the deepest there is
    (the preamble's), counted. The next session's leader starts from
    the preamble's state and hands its own on."""
    m, eng, sched = served
    store = eng.hybrid
    preamble = [1] + m.tokens_of(86, 3 * PAGE - 1)
    # (a first session: nothing cached, its snapshot is its last page's)
    play_round(m, eng, sched, "pre0", preamble + m.tokens_of(87, 40), 1,
               new=4)
    assert not store.holds(preamble, 3 * PAGE)
    second = preamble + m.tokens_of(88, 70)
    turns, answers, moved = play_round(m, eng, sched, "pre1", second, 1,
                                       new=4)
    at = hand_over_at(turns)
    assert store.holds(preamble, 3 * PAGE) \
        and not store.holds(turns[0][1], at)
    assert (moved["share_handed"], moved["share_declined"]) == (0, 2)
    assert moved["scanned"] == len(turns[0][1]) + sum(
        len(p) - 3 * PAGE for _k, p in turns[1:])
    assert not store._pins
    for (_k, p), a in zip(turns, answers):
        assert m.worst_gap(eng, p, a) < m.GAP
    third = preamble + m.tokens_of(89, 75)
    turns, answers, moved = play_round(m, eng, sched, "pre2", third, 1,
                                       new=4)
    at = hand_over_at(turns)
    assert (moved["share_handed"], moved["share_declined"]) == (2, 0)
    assert moved["scanned"] == len(turns[0][1]) - 3 * PAGE + sum(
        len(p) - at for _k, p in turns[1:])
    for (_k, p), a in zip(turns, answers):
        assert m.worst_gap(eng, p, a) < m.GAP


@pytest.mark.parametrize("model", list(MODELS))
def test_a_pinned_snapshot_survives_a_store_of_three(model):
    """The store alone, three states wide (host records only: what the
    programs would be told). A leader's snapshot at the hand-over
    boundary stays through more captures than the store holds, and
    through the loss of its radix node; with every state pinned a
    capture gets none and a further hand-over is refused; unpinned, it
    is the LRU's again."""
    cfg = get_model_config(MODELS[model][1])
    per = HybridStateStore(cfg, 2, PAGE, 0).bytes_per_state
    store = HybridStateStore(cfg, 8, PAGE, 3 * per)
    assert store.capacity == 3

    def prompt(seed, n=4 * PAGE + 5):
        return [1] + [3 + (seed * 31 + i) % 200 for i in range(n)]

    lead = prompt(1)
    hi = 3 * PAGE + 7                       # the common span's end
    key, at = store.hand_over(lead, PAGE, hi)
    assert (key, at) == (page_keys(lead, PAGE, 3 * PAGE)[-1], 3 * PAGE)
    assert store.hand_over(lead, 3 * PAGE, hi) is None   # passed it
    assert store.plan("lead", lead, 0)[1] == "zero"
    store.expect("lead", key, at, 2)
    # One run over the boundary and a later one: the snapshot is the
    # hand-over's, not the last boundary's.
    cap_len, idx, got = store.capture_slot("lead", 0, len(lead))
    assert (cap_len, got) == (3 * PAGE, key)
    for seed in range(2, 8):                # six captures, three places
        other = prompt(seed)
        store.plan(f"o{seed}", other, 0)
        _n, _i, k = store.capture_slot(f"o{seed}", 0, len(other))
        assert k is not None and k != key
    assert store._snap[key] == idx and store.evictions >= 4
    store.drop(key)                         # its radix node went
    assert store._snap[key] == idx
    # Both laggards find it where the leader left it.
    for name in ("lag1", "lag2"):
        follower = lead[:hi] + prompt(9, 10)
        assert store.plan(name, follower, hi) == (3 * PAGE, "snapshot",
                                                  idx)
        store.unpin(key)
    assert not store._pins
    # Every state pinned: no capture, no further hand-over.
    held = list(store._snap)
    store._pins = {k: 1 for k in held}
    extra = prompt(20)
    store.plan("extra", extra, 0)
    assert store.capture_slot("extra", 0, len(extra)) \
        == (0, store.scratch_snap, None)
    assert store.hand_over(extra, 0, hi) is None
    assert store.hand_over(lead, PAGE, hi) == (key, at)   # pinned already
    store._pins = {}
    assert store.capture_slot("extra", 0, len(extra))[2] is not None
    # A program that failed wrote nothing: a pinned key goes too.
    store.expect("lead", key, at, 1)
    store.drop(key, unwritten=True)
    assert key not in store._snap
