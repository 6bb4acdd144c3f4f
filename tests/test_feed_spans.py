"""The device's starved seconds, named from inside the scheduler
(ISSUE 37).

The loop clock's feed bit in the real scheduler — fed where a runner's
dispatch returns, drained where its read does, pipelined segments and
prologues included — and the four rungs of what a round's start does on
the host: `plan` (an admission's host half, `engine._prepare_batch`),
`page_copy` (`paging._issue_pending`: the flush of the page copies
queued since the pools were last taken, counted by cause — ISSUE 38),
`share` (`_apply_share_plans`, a request whose plans are due) and
`pack` (a segment's host arrays). Each is emitted once per cause with
its attributes and parent, is absent when nothing is due, and nothing is
built for it unarmed. The clock's own arithmetic is in
tests/test_telemetry.py; the readers are in
tests/benchmarks/test_benchmark_feed_readers.py.
"""

import time

import pytest

jax = pytest.importorskip("jax")

from test_index_in_flight import (ROUND, clean_faults,  # noqa: F401
                                  make_engine, serve_round)
from test_paging import make_cache
from theroundtaible_tpu.engine import faults
from theroundtaible_tpu.engine.scheduler import (LOOP_PHASES,
                                                 SessionScheduler)
from theroundtaible_tpu.utils import telemetry

ROUND_START_RUNGS = ("plan", "page_copy", "share", "pack")


@pytest.fixture(scope="module")
def engine():
    eng = make_engine()
    assert eng.ragged_path == "pallas_ragged"
    return eng


def traced_round(engine, tag):
    """ROUND's five two-knight sessions through a fresh scheduler, armed
    and each under a `request` span of its own. → (spans, requests by
    session, the scheduler's describe() after)."""
    was = telemetry.ACTIVE
    telemetry.disarm()
    telemetry.arm()                    # this run's own span buffer
    sched = SessionScheduler(engine)
    t_a = time.monotonic()
    requests = {}
    try:
        sched.pause_admission("line up")
        reqs = {}
        for sid, turns in ROUND.items():
            with telemetry.span("request", stream=tag + sid) as request:
                requests[tag + sid] = request
                reqs[tag + sid] = sched.submit_async(
                    tag + sid, turns, max_new_tokens=24)
        sched.reopen_admission()
        for r in reqs.values():
            sched.wait(r)
        desc = sched.describe()
        clock = sched._clock
        assert (clock.fed, clock._issued) == (False, clock._drained)
    finally:
        sched.close()
        telemetry.ACTIVE = was
    return telemetry.spans_between(t_a, time.monotonic()), requests, desc


@pytest.fixture(scope="module")
def cold_round(engine):
    """A round nothing has been cached for: the first admission runs the
    prologue, the other four join deferred and share their leader's
    span."""
    return traced_round(engine, "cold-")


def another_round(topic):
    """ROUND's shape over another topic: nothing of it is cached."""
    return {sid: [(knight, prompt.replace("castle walls", topic))
                  for knight, prompt in turns]
            for sid, turns in ROUND.items()}


def by_rung(spans, rung):
    return [r for r in spans if r["rung"] == rung]


class TestRoundStartRungs:
    def test_plan_once_an_admission_under_its_admit_span(self, cold_round):
        spans, requests, _desc = cold_round
        admits = {r["span_id"]: r for r in by_rung(spans, "admit")}
        plans = by_rung(spans, "plan")
        assert len(plans) == len(admits) == len(ROUND)
        assert {p["parent_id"] for p in plans} == set(admits)
        for p in plans:
            admit = admits[p["parent_id"]]
            session = admit["attrs"]["session"]
            assert p["trace_id"] == requests[session].trace_id
            at = p["attrs"]
            assert set(at) == {"prompt_tokens", "matched_tokens",
                               "pages_allocated"}
            assert at["prompt_tokens"] == (
                admit["attrs"]["prefill_tokens"]
                + admit["attrs"]["reused_tokens"])
            assert at["matched_tokens"] == admit["attrs"]["reused_tokens"]
            assert at["pages_allocated"] >= 1
            assert admit["t0"] <= p["t0"]
            assert p["t0"] + p["dur_s"] <= admit["t0"] + admit["dur_s"] \
                + 1e-6

    def test_share_once_a_request_whose_plans_are_due(self, cold_round):
        spans, requests, _desc = cold_round
        shares = by_rung(spans, "share")
        deferred = [r["attrs"]["session"] for r in by_rung(spans, "admit")
                    if r["attrs"]["deferred"]]
        assert sorted(s["attrs"]["session"] for s in shares) \
            == sorted(deferred) and len(shares) == len(ROUND) - 1
        for s in shares:
            request = requests[s["attrs"]["session"]]
            assert (s["trace_id"], s["parent_id"]) == (
                request.trace_id, request.span_id)
            at = s["attrs"]
            assert at["followers"] == 1
            # the common span ends inside its first page: nothing to
            # alias whole, the boundary page is copied
            assert (at["pages_aliased"], at["copies"]) == (0, 1)
        # the copies themselves wait on the page cache: no `page_copy`
        # under a `share`, and every follower's copy in some later
        # flush (the prologue's follower took its own in the plan)
        copies = by_rung(spans, "page_copy")
        share_ids = {s["span_id"] for s in shares}
        assert not any(c["parent_id"] in share_ids for c in copies)
        assert sum(c["attrs"]["share"] for c in copies) == len(ROUND)

    def test_page_copy_is_a_flush_of_what_the_round_queued(self, engine,
                                                           cold_round):
        """One `page_copy` span a flush — where a program takes the
        pools — with the copies it gathered by cause: fewer flushes
        than copies, and every pair in one program."""
        spans, _requests, _desc = cold_round
        copies = by_rung(spans, "page_copy")
        assert copies
        for c in copies:
            at = c["attrs"]
            assert set(at) == {"copies", "alias", "share", "cow",
                               "pages", "programs", "path"}
            # the CPU has no Mosaic: XLA's gather and scatter, and why
            assert at["path"] == engine.kv.page_copy_path \
                == engine.declines["page_copy"]
            assert at["copies"] == (at["alias"] + at["share"]
                                    + at["cow"]) >= 1
            assert (at["pages"], at["programs"]) == (at["copies"], 1)
        assert len(copies) < sum(c["attrs"]["copies"] for c in copies)

    def test_pack_once_a_segment_with_what_it_packed(self, cold_round):
        spans, _requests, _desc = cold_round
        segments = {r["span_id"]: r for r in by_rung(spans, "segment")}
        packs = by_rung(spans, "pack")
        kinds = {s["attrs"]["kind"] for s in segments.values()}
        assert {"ragged"} <= kinds
        # one pack a ragged or verify segment; a mini-loop of plain
        # segments packs its first, the rest are carried on the device
        for kind in kinds:
            n_seg = sum(1 for s in segments.values()
                        if s["attrs"]["kind"] == kind)
            n_pack = sum(1 for p in packs if p["attrs"]["kind"] == kind)
            assert 1 <= n_pack <= n_seg
            if kind != "plain":
                assert n_pack == n_seg
        assert len({p["parent_id"] for p in packs}) == len(packs)
        for p in packs:
            seg = segments[p["parent_id"]]
            assert p["trace_id"] == seg["trace_id"]
            at = p["attrs"]
            assert (at["kind"], at["rows"]) == (seg["attrs"]["kind"],
                                                seg["attrs"]["rows"])
            assert at["tokens"] >= at["rows"]
            if at["kind"] == "ragged":
                assert at["tokens"] == (seg["attrs"]["prefill_tokens"]
                                        + seg["attrs"]["decode_tokens"])
            # packed before its segment's stretch begins
            assert p["t0"] + p["dur_s"] <= seg["t0"] + 1e-6

    def test_a_warm_round_shares_and_copies_nothing(self, engine,
                                                    cold_round):
        """The same prompts again: every prompt is cached whole, so no
        plan is deferred-shared and no page is copied — the empty pass
        of `_apply_share_plans` builds no span."""
        spans, _requests, _desc = traced_round(engine, "cold-")
        assert len(by_rung(spans, "plan")) == len(ROUND)
        assert by_rung(spans, "share") == []
        assert by_rung(spans, "page_copy") == []

    def test_every_loop_stretch_is_wholly_fed_or_unfed(self, cold_round):
        spans, _requests, _desc = cold_round
        loops = [r for r in spans if r["rung"].startswith("loop.")]
        assert all(r["attrs"]["fed"] in (0, 1) for r in loops)
        fed_of = {}
        for r in loops:
            fed_of.setdefault(r["rung"], set()).add(r["attrs"]["fed"])
        # a blocking read waits for a program this loop issued; nothing
        # is outstanding while it waits, flushes or retires
        assert fed_of["loop.sync"] == fed_of["loop.admit_sync"] == {1}
        assert fed_of["loop.flush"] == fed_of["loop.retire"] == {0}
        assert fed_of["loop.build"] == {0, 1}    # indexing under flight
        # between a ragged segment's dispatch and the end of its read
        # the loop is fed, but for the instants between the dispatch
        # seam's mark back and the feed itself
        ragged = [s for s in by_rung(spans, "segment")
                  if s["attrs"]["kind"] != "plain"]
        for seg in ragged:
            inside = [r for r in loops
                      if seg["t0"] < r["t0"]
                      and r["t0"] + r["dur_s"] < seg["t0"] + seg["dur_s"]
                      and r["rung"] != "loop.dispatch"]
            assert any(r["attrs"]["fed"] for r in inside)
            assert sum(r["dur_s"] for r in inside
                       if not r["attrs"]["fed"]) < 1e-3


CAUSES = [
    # a shared page about to be written
    ("cow", lambda kv: kv.ensure_capacity("b", 80, write_from=40), 1),
    # alias_span's partial boundary page, between two slots
    ("share", lambda kv: kv.alias_span("a", "c", 0, 40), 1),
    # a prefix-cache attach's boundary page (adopt_span)
    ("alias", lambda kv: (kv.ensure_capacity("c", 8, write_from=0),
                          kv.commit("c", list(range(8))),
                          kv.adopt_span("c", kv._slots["a"].pages, 8, 48)),
     1),
    # whole pages alias by refcount: no copy, no span
    (None, lambda kv: kv.alias_span("a", "c", 0, 48), 0),
]


@pytest.mark.telemetry
@pytest.mark.parametrize("cause,act,copies", CAUSES,
                         ids=[str(c[0]) for c in CAUSES])
def test_page_copy_counts_by_cause_under_whatever_takes_the_pools(
        cause, act, copies):
    kv = make_cache()
    for name in ("a", "b", "c"):
        kv.acquire(name)
    kv.ensure_capacity("a", 48, write_from=0)
    kv.commit("a", list(range(48)))
    assert kv.alias_span("a", "b", 0, 48) == (3, 0)
    telemetry.disarm()
    telemetry.arm()
    t_a = time.monotonic()
    act(kv)                    # queued: no span where nothing is issued
    assert by_rung(telemetry.spans_between(t_a, time.monotonic()),
                   "page_copy") == []
    with telemetry.span("segment") as segment:
        kv.combined_pools()
    recs = by_rung(telemetry.spans_between(t_a, time.monotonic()),
                   "page_copy")
    assert len(recs) == len(kv._recorded_copies) == copies
    for r in recs:
        assert r["attrs"] == {"copies": 1, "alias": 0, "share": 0,
                              "cow": 0, "pages": 1, "programs": 1,
                              "path": "unnamed", cause: 1}
        assert (r["trace_id"], r["parent_id"]) == (segment.trace_id,
                                                   segment.span_id)


KNIGHTS = ("lancelot", "galahad", "percival")
THREE_KNIGHTS = {
    f"t{i}": [(knight, "The round table met at dusk to weigh the "
               f"ferry tolls and the mill race. Discussion {i}: "
               + f"{knight} speaks of the miller's share. " * (i + 1))
              for knight in KNIGHTS]
    for i in range(3)}


def serve_three_knights(eng):
    """THREE_KNIGHTS, nothing of it cached, admitted by one tick.
    -> (texts by session, what describe()["paging"] gained)."""
    before = eng.describe()["paging"]
    sched = SessionScheduler(eng)
    try:
        sched.pause_admission("line up")
        reqs = {sid: sched.submit_async(sid, turns, max_new_tokens=16)
                for sid, turns in THREE_KNIGHTS.items()}
        sched.reopen_admission()
        said = {sid: sched.wait(r)[0] for sid, r in reqs.items()}
    finally:
        sched.close()
    after = eng.describe()["paging"]
    return said, {k: after[k] - before[k]
                  for k in ("page_copies", "page_copy_programs")}


def test_a_three_knight_round_gathers_its_copies_and_says_the_same(
        engine):
    """ISSUE 38 at the scheduler: three knights a session share a
    prefix that ends inside a page, so every follower's boundary page
    is copied — queued on the page cache, and issued with whatever
    else is pending when a program takes the pools. Against an engine
    that issues each copy where it is queued (what the cache did
    before), the same tokens from fewer programs."""
    assert set(engine.describe()["paging"]) == set(
        telemetry.SURFACE_BINDINGS["engine_paging"])
    said, gained = serve_three_knights(engine)
    assert 0 < gained["page_copy_programs"] < gained["page_copies"]

    before = make_engine()
    queue = before.kv._run_page_copy

    def issue_at_once(src, dst, cause):
        queue(src, dst, cause)
        before.kv.combined_pools()

    before.kv._run_page_copy = issue_at_once
    said_before, gained_before = serve_three_knights(before)
    assert said == said_before
    assert gained_before["page_copy_programs"] \
        > gained["page_copy_programs"]


def test_alias_span_counts_what_it_aliased_and_copied():
    kv = make_cache()
    for name in ("a", "b"):
        kv.acquire(name)
    kv.ensure_capacity("a", 64, write_from=0)
    kv.commit("a", list(range(64)))
    took = kv.pages_allocated
    assert kv.alias_span("a", "b", 0, 40) == (2, 1)
    assert kv.pages_allocated == took + 1     # the boundary page's copy


class TestUnarmed:
    def test_a_round_with_shares_builds_no_span_and_no_attributes(
            self, engine, monkeypatch):
        """The NULL_SPAN idiom at every new site: a cold round — plans,
        shares, page copies, ragged, verify and plain segments — with
        the tracer off constructs no Span and emits nothing, and the
        starved totals still move."""
        made = []

        class CountingSpan(telemetry.Span):
            def __init__(self, *args, **kw):
                made.append(args[0])
                super().__init__(*args, **kw)

        monkeypatch.setattr(telemetry, "Span", CountingSpan)
        telemetry.disarm()
        emitted = telemetry.spans_emitted()
        sched = SessionScheduler(engine)
        try:
            before = sched.describe()
            sched.pause_admission("line up")
            reqs = [sched.submit_async("quiet-" + sid, turns,
                                       max_new_tokens=24)
                    for sid, turns in another_round(
                        "granaries and the salt road").items()]
            sched.reopen_admission()
            for r in reqs:
                sched.wait(r)
            after = sched.describe()
            assert sched._clock._open is None
            assert after["events"] and any(
                e["event"] == "share_alias" for e in after["events"])
        finally:
            sched.close()
        assert made == [] and telemetry.spans_emitted() == emitted
        gained = {p: after["loop_starved_seconds"][p]
                  - before["loop_starved_seconds"][p] for p in LOOP_PHASES}
        assert gained["dispatch"] > 0.0 and gained["build"] > 0.0
        assert gained["sync"] == gained["admit_sync"] == 0.0

    def test_the_null_span_takes_what_the_sites_call(self):
        null = telemetry.NULL_SPAN
        null.leave()
        null.end()
        assert not hasattr(null, "attrs")


class TestDescribeAndSeries:
    def test_starved_is_bounded_by_seconds_and_the_series_moves_with_it(
            self, engine):
        name = engine.cfg.name

        def series(phase):
            return telemetry.REGISTRY.counter_total(
                "roundtable_sched_starved_seconds_total", engine=name,
                phase=phase)

        published = {p: series(p) for p in LOOP_PHASES}
        sched = SessionScheduler(engine)
        try:
            a = sched.describe()
            serve_round(sched, "series-")
            time.sleep(0.3)            # the loop goes back to waiting
            b, t_b = sched.describe(), time.monotonic()
            for d in (a, b):
                assert tuple(d["loop_starved_seconds"]) == tuple(
                    d["loop_seconds"]) == LOOP_PHASES
                assert all(d["loop_starved_seconds"][p]
                           <= d["loop_seconds"][p] for p in LOOP_PHASES)
        finally:
            sched.close()
        gained = {p: b["loop_starved_seconds"][p]
                  - a["loop_starved_seconds"][p] for p in LOOP_PHASES}
        # waiting is unfed by definition; a blocked read never is
        assert gained["wait"] == pytest.approx(
            b["loop_seconds"]["wait"] - a["loop_seconds"]["wait"],
            abs=1e-4)
        assert gained["sync"] == 0.0
        for p in LOOP_PHASES:
            moved = series(p) - published[p]
            # one store: the series has seen what describe() gained, up
            # to the phase still open when describe() was read
            assert moved <= b["loop_starved_seconds"][p] + 1e-6, p
            if p not in ("wait", "health"):
                assert moved == pytest.approx(gained[p], abs=1e-4), p
        assert "loop_starved_seconds" in telemetry.SURFACE_BINDINGS[
            "scheduler_describe"]

    def test_page_copies_and_their_programs_one_store_each(self):
        """describe()["paging"]'s counts and the two series have one
        writer each and move together (ISSUE 38): a copy where it is
        queued, by cause; a program where the flush issues it."""
        def series():
            total = telemetry.REGISTRY.counter_total
            return ({c: total("roundtable_page_copies_total",
                              engine="tiny-gemma", cause=c)
                     for c in ("alias", "share", "cow")},
                    total("roundtable_page_copy_programs_total",
                          engine="tiny-gemma", path="unnamed"))

        kv = make_cache()
        for name in ("a", "b", "c"):
            kv.acquire(name)
        kv.ensure_capacity("a", 48, write_from=0)
        kv.commit("a", list(range(48)))
        kv.alias_span("a", "b", 0, 48)
        copies_0, programs_0 = series()
        kv.alias_span("a", "c", 0, 40)                  # share
        kv.ensure_capacity("b", 80, write_from=40)      # cow
        copies_1, programs_1 = series()
        assert {c: copies_1[c] - copies_0[c] for c in copies_0} \
            == {"alias": 0, "share": 1, "cow": 1} \
            == kv.describe()["page_copies_by_cause"]
        assert programs_1 == programs_0         # queued, nothing issued
        kv.combined_pools()
        assert series()[1] - programs_0 == 1 \
            == kv.describe()["page_copy_programs"]
        assert set(kv.describe()) == set(
            telemetry.SURFACE_BINDINGS["engine_paging"])

    @pytest.mark.chaos
    def test_a_failed_dispatch_leaves_the_clock_unfed(self, engine):
        sched = SessionScheduler(engine)
        try:
            serve_round(sched, "warm-")
            faults.arm("dispatch", count=1)
            sched.pause_admission("line up")
            req = sched.submit_async("fails", ROUND["d0"],
                                     max_new_tokens=8)
            sched.reopen_admission()
            try:
                sched.wait(req)
            except Exception:  # noqa: BLE001 — either outcome will do
                pass
            faults.disarm()
            deadline = time.monotonic() + 5.0
            while sched._active and time.monotonic() < deadline:
                time.sleep(0.01)
            clock = sched._clock
            assert (clock.fed, clock._issued) == (False, clock._drained)
            serve_round(sched, "after-")         # and it serves on
            assert (clock.fed, clock._issued) == (False, clock._drained)
        finally:
            faults.disarm()
            sched.close()
