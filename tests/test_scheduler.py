"""Continuous-batching session scheduler suite (ISSUE 4).

Covers the acceptance criteria end to end on the CPU backend:
- session-namespaced slot names at the PagedKVCache layer (the
  cross-session "lancelot" collision fix), with donor scoping;
- >= 3 concurrent 2-knight discussions through one shared engine with
  (a) per-session token parity vs the same discussions run serially,
  (b) batch occupancy > 1 on a decode segment (continuous batching
  actually happened — the conftest `scheduler` guard enforces this for
  every strictly-marked test), and (c) a `hang` fault in one session
  leaving the other sessions' results byte-identical;
- admission backpressure (queue when capacity is pinned, refuse what
  can never fit), drain interplay (queued sessions fail fast with
  DrainingError, fleet_health reports queue state), budget expiry
  isolation, and the adapter ladder riding THROUGH the scheduler;
- SessionMetrics queue-wait / batch-occupancy fields under concurrency.
"""

import threading
import time

import pytest

jax = pytest.importorskip("jax")

from theroundtaible_tpu.engine import deadlines, faults
from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.kvcache import (scoped_slot,
                                               session_of)
from theroundtaible_tpu.engine.models.registry import get_model_config
from theroundtaible_tpu.engine.scheduler import (SchedulerRefused,
                                                 SessionScheduler,
                                                 scheduler_for)

MODEL_KW = dict(max_seq_len=512)


@pytest.fixture(autouse=True)
def clean_faults():
    faults.disarm()
    deadlines.reset_rungs()
    deadlines.disarm_watchdog()
    deadlines.clear_hang_log()
    deadlines.end_drain()
    yield
    faults.disarm()
    deadlines.reset_rungs()
    deadlines.disarm_watchdog()
    deadlines.clear_hang_log()
    deadlines.end_drain()


def make_engine(**kw):
    """The suite's subject is neither the kernels nor speculation
    (tests/test_spec_decode.py schedules it): the gather view (XLA
    alone) over small pages, plain and ragged segments."""
    cfg = get_model_config("tiny-gemma", **MODEL_KW)
    kw.setdefault("num_slots", 8)
    kw.setdefault("attn", "dense")
    kw.setdefault("page_size", 32)
    kw.setdefault("spec_decode", False)
    return InferenceEngine(cfg, **kw)


@pytest.fixture(scope="module")
def shared_engine():
    return make_engine()


@pytest.fixture(scope="module")
def baseline_engine():
    """A separate engine instance for serial baselines, so scheduled
    serving on shared_engine can never contaminate the expected values
    (engines share nothing but compiled-program caches)."""
    return make_engine()


PROMPTS = {
    "s0": [("lancelot", "The round table met at dawn to discuss the "
                        "castle walls and the eastern gate."),
           ("galahad", "The round table met at dawn to discuss the "
                       "castle walls and the eastern gate. Galahad "
                       "raises the matter of the moat.")],
    "s1": [("lancelot", "A different discussion entirely, about dragons "
                        "and the kingdom's gold reserves."),
           ("galahad", "A different discussion entirely, about dragons "
                       "and the kingdom's gold reserves. Galahad "
                       "disagrees sharply.")],
    "s2": [("lancelot", "Third topic: the harvest festival planning "
                        "session and the tournament."),
           ("galahad", "Third topic: the harvest festival planning "
                       "session and the tournament. Galahad volunteers "
                       "to judge.")],
}


def serial_baselines(engine, max_new=70):
    return {sid: engine.generate_batch(turns, max_new_tokens=max_new,
                                       session=sid)
            for sid, turns in PROMPTS.items()}


def run_concurrent(sched, max_new=70, sessions=None):
    results, errors = {}, {}

    def run(sid):
        try:
            results[sid] = sched.submit(sid, PROMPTS[sid],
                                        max_new_tokens=max_new)
        except Exception as e:  # noqa: BLE001 — asserted by callers
            errors[sid] = e

    threads = [threading.Thread(target=run, args=(sid,))
               for sid in (sessions or PROMPTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    return results, errors


# ---------------------------------------------------------------------------
# satellite: session-namespaced slot names at the cache layer
# ---------------------------------------------------------------------------


@pytest.mark.scheduler(allow_serial=True)
class TestSessionNamespace:
    def test_scoped_slot_roundtrip(self):
        assert scoped_slot("s1", "lancelot") == "s1\x1flancelot"
        assert session_of(scoped_slot("s1", "lancelot")) == "s1"
        assert scoped_slot(None, "lancelot") == "lancelot"
        assert scoped_slot("", "lancelot") == "lancelot"
        assert session_of("lancelot") == ""

    @staticmethod
    def _pool():
        from theroundtaible_tpu.engine.paging import PagedKVCache
        return PagedKVCache(get_model_config("tiny-gemma", **MODEL_KW),
                            num_slots=4, max_seq_len=256, page_size=64)

    def test_two_sessions_two_slots(self):
        """THE regression: acquire("lancelot") from two sessions used to
        map to one slot and silently cross-contaminate KV."""
        book = self._pool()
        names = [scoped_slot(s, "lancelot") for s in ("sessA", "sessB")]
        for name in names:
            book.ensure_capacity(name, 100, write_from=0)
        a, b = (book.acquire(name) for name in names)
        assert a is not b and not set(a.pages) & set(b.pages)
        assert len(book.slot_names()) == 2

    def test_reuse_plan_never_crosses_sessions(self):
        book = self._pool()
        tokens = [1, 7, 9, 11, 13, 15]
        book.commit(scoped_slot("sessA", "lancelot"), tokens)
        # Same knight name, same token stream, OTHER session: a fresh
        # slot with zero reuse — not sessA's baked cache.
        _, reuse = book.reuse_plan(scoped_slot("sessB", "lancelot"),
                                   tokens)
        assert reuse == 0
        # The same session DOES reuse its own history.
        _, reuse_same = book.reuse_plan(scoped_slot("sessA", "lancelot"),
                                        tokens)
        assert reuse_same == len(tokens) - 1

    def test_paged_best_donor_intra_session_only(self):
        from theroundtaible_tpu.engine.paging import PagedKVCache
        cfg = get_model_config("tiny-gemma", **MODEL_KW)
        kv = PagedKVCache(cfg, num_slots=4, max_seq_len=256, page_size=64)
        shared = list(range(1, 100))
        kv.acquire(scoped_slot("sessA", "lancelot"))
        kv.commit(scoped_slot("sessA", "lancelot"), shared)
        donor, n = kv.best_donor(scoped_slot("sessB", "galahad"),
                                 shared + [101])
        assert donor is None and n == 0
        donor, n = kv.best_donor(scoped_slot("sessA", "galahad"),
                                 shared + [101])
        assert donor is not None and n == len(shared)

    def test_engine_session_kwarg_namespaces_slots(self):
        engine = make_engine(num_slots=4)
        engine.generate_batch([("lancelot", "A short prompt about walls.")],
                              max_new_tokens=4, session="sA")
        engine.generate_batch([("lancelot", "A short prompt about walls.")],
                              max_new_tokens=4, session="sB")
        names = engine.kv.slot_names()
        assert scoped_slot("sA", "lancelot") in names
        assert scoped_slot("sB", "lancelot") in names
        assert "lancelot" not in names

    def test_failed_session_release_never_frees_shared_pages(self):
        """ISSUE 7 isolation satellite: _fail_request's per-row release
        (and any preemption cleanup) UNREFS — a page the sick session
        shared through the cross-session prefix cache must survive for
        the session still referencing it, bit-for-bit addressable."""
        from theroundtaible_tpu.engine.paging import PagedKVCache
        from theroundtaible_tpu.engine.prefix_cache import PrefixCache
        cfg = get_model_config("tiny-gemma", **MODEL_KW)
        kv = PagedKVCache(cfg, num_slots=4, max_seq_len=256,
                          page_size=64, copy_pages_fn=lambda p, s, d: p)
        kv.prefix_cache = PrefixCache(kv, engine="iso")
        shared = list(range(128))          # 2 complete pages
        a = scoped_slot("sessA", "lancelot")
        b = scoped_slot("sessB", "lancelot")
        kv.acquire(a)
        kv.ensure_capacity(a, 192, write_from=0)
        kv.commit(a, shared)               # indexed cross-session
        kv.acquire(b)
        got = kv.prefix_cache.attach(b, shared + [500])
        assert got == 128
        shared_pages = list(kv._slots[b].pages)
        assert shared_pages == kv._slots[a].pages[:2]
        # session A faults: the scheduler releases its rows' slots
        kv.release(a)
        # B's mapping is intact and the pages are still allocated
        assert kv._slots[b].pages == shared_pages
        for p in shared_pages:
            assert kv.refcount(p) >= 1
            assert p not in kv._free_by_replica[0]
        # and B's own release finally unrefs down to the index's hold
        kv.release(b)
        for p in shared_pages:
            assert kv.refcount(p) == 1     # the index alone
            assert p not in kv._free_by_replica[0]


# ---------------------------------------------------------------------------
# tentpole acceptance: concurrency, parity, occupancy, fault isolation
# ---------------------------------------------------------------------------


class TestContinuousBatching:
    @pytest.mark.scheduler
    def test_three_sessions_token_parity_and_occupancy(
            self, shared_engine, baseline_engine):
        """Acceptance (a)+(b): >= 3 concurrent 2-knight discussions on
        one shared engine — per-session token parity with serial runs,
        and a decode segment with occupancy > 1."""
        serial = serial_baselines(baseline_engine)
        sched = SessionScheduler(shared_engine, admit_hold_s=0.3)
        try:
            results, errors = run_concurrent(sched)
            assert not errors, errors
            for sid in PROMPTS:
                texts, stats = results[sid]
                assert texts == serial[sid], f"{sid} diverged"
                assert stats.sched["occupancy_max"] > 1
                assert stats.sched["sessions_max"] >= 2
                assert stats.decode_tokens > 0
            d = sched.describe()
            assert d["max_occupancy"] > 1
            assert any(o > 1 for o in d["occupancy_recent"])
            assert d["completed"] == 3 and d["failed"] == 0
        finally:
            sched.close()

    @pytest.mark.scheduler
    def test_hang_fault_leaves_other_sessions_byte_identical(
            self, baseline_engine):
        """Acceptance (c): a hang fault during the SHARED decode batch
        preempts the batch into per-session dispatches; with the fault
        exhausted, every session completes byte-identical to serial
        (the sick dispatch never committed anything)."""
        serial = serial_baselines(baseline_engine, max_new=200)
        engine = make_engine()
        sched = SessionScheduler(engine, admit_hold_s=0.3)
        try:
            reqs = {sid: sched.submit_async(sid, PROMPTS[sid],
                                            max_new_tokens=200)
                    for sid in PROMPTS}
            deadline = time.monotonic() + 120
            while sched.admitted < 3 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert sched.admitted == 3, "sessions were never co-admitted"
            # All three sessions are mid-decode in ONE batch: the next
            # dispatch the fault hits is the shared segment.
            faults.arm("hang", count=1, delay_s=0.1)
            out = {sid: sched.wait(req) for sid, req in reqs.items()}
            for sid in PROMPTS:
                assert out[sid][0] == serial[sid], f"{sid} diverged"
            d = sched.describe()
            assert d["preemptions"] >= 1, (
                "hang never hit the shared batch — test raced retirement")
            assert d["failed"] == 0
        finally:
            sched.close()

    @pytest.mark.scheduler
    def test_second_hang_fails_only_one_session(self, baseline_engine):
        """Two hang firings: the shared segment fails, then the FIRST
        per-session isolation dispatch fails too — exactly one session
        climbs to its caller while the others stay byte-identical."""
        serial = serial_baselines(baseline_engine, max_new=200)
        engine = make_engine()
        sched = SessionScheduler(engine, admit_hold_s=0.3)
        try:
            reqs = {sid: sched.submit_async(sid, PROMPTS[sid],
                                            max_new_tokens=200)
                    for sid in PROMPTS}
            deadline = time.monotonic() + 120
            while sched.admitted < 3 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert sched.admitted == 3
            faults.arm("hang", count=2, delay_s=0.1)
            outcomes, failures = {}, {}
            for sid, req in reqs.items():
                try:
                    outcomes[sid] = sched.wait(req)
                except Exception as e:  # noqa: BLE001
                    failures[sid] = e
            assert len(failures) == 1, (
                f"expected exactly one failed session, got {failures}")
            for sid, (texts, _stats) in outcomes.items():
                assert texts == serial[sid], f"{sid} diverged"
            assert sched.describe()["preemptions"] >= 1
        finally:
            sched.close()

    @pytest.mark.scheduler
    def test_transient_dispatch_fault_retries_in_place(
            self, baseline_engine):
        """A retryable dispatch fault is absorbed by the run_dispatch
        retry seam — no preemption, no failures, full parity."""
        serial = serial_baselines(baseline_engine)
        engine = make_engine()
        sched = SessionScheduler(engine, admit_hold_s=0.3)
        try:
            faults.arm("dispatch", count=1)
            results, errors = run_concurrent(sched)
            assert not errors, errors
            for sid in PROMPTS:
                assert results[sid][0] == serial[sid]
            assert sched.describe()["preemptions"] == 0
        finally:
            sched.close()

    @pytest.mark.scheduler
    def test_next_round_reuses_committed_prefix(self, shared_engine):
        """Round 2 of a session extends round 1's transcript: the
        scheduler's retirement commit must feed reuse_plan exactly like
        generate_batch's (delta-only prefill across rounds)."""
        sched = SessionScheduler(shared_engine, admit_hold_s=0.2)
        try:
            r1, errors = run_concurrent(sched, sessions=["s0", "s1"])
            assert not errors
            texts0 = r1["s0"][0]
            round2 = [(name, prompt + " " + texts0[i] + " The discussion "
                       "continues into a second round with new points.")
                      for i, (name, prompt) in enumerate(PROMPTS["s0"])]
            results, errors2 = {}, {}

            def go():
                try:
                    results["s0"] = sched.submit("s0", round2,
                                                 max_new_tokens=40)
                except Exception as e:  # noqa: BLE001
                    errors2["s0"] = e

            def go_other():
                try:
                    results["s1"] = sched.submit("s1", PROMPTS["s1"],
                                                 max_new_tokens=40)
                except Exception as e:  # noqa: BLE001
                    errors2["s1"] = e

            t1, t2 = threading.Thread(target=go), threading.Thread(
                target=go_other)
            t1.start(); t2.start(); t1.join(120); t2.join(120)
            assert not errors2, errors2
            _texts, stats = results["s0"]
            assert stats.reused_tokens > 0, (
                "round 2 re-prefilled everything: retirement commit "
                "broke cross-round prefix reuse")
        finally:
            sched.close()


# ---------------------------------------------------------------------------
# admission queue: backpressure + refusal
# ---------------------------------------------------------------------------


class TestAdmission:
    @pytest.mark.scheduler(allow_serial=True)
    def test_refuses_what_never_fits(self):
        engine = make_engine(num_slots=4)
        sched = SessionScheduler(engine)
        try:
            turns = [(f"k{i}", "prompt") for i in range(5)]
            with pytest.raises(SchedulerRefused):
                sched.submit("big", turns, max_new_tokens=8)
            assert sched.describe()["refused"] == 1
        finally:
            sched.close()

    @pytest.mark.scheduler
    def test_backpressure_queues_then_serves(self):
        """With room for one 2-knight session (max_rows=2), the second
        session queues behind the first and completes after retirement —
        and co-schedules once capacity frees (rows of BOTH sessions in
        one segment via the third session's join)."""
        engine = make_engine()
        sched = SessionScheduler(engine, max_rows=4, admit_hold_s=0.2)
        try:
            a = sched.submit_async("s0", PROMPTS["s0"],
                                   max_new_tokens=200)
            b = sched.submit_async("s1", PROMPTS["s1"],
                                   max_new_tokens=200)
            c = sched.submit_async("s2", PROMPTS["s2"],
                                   max_new_tokens=200)
            outs = [sched.wait(r) for r in (a, b, c)]
            assert all(o is not None for o in outs)
            d = sched.describe()
            assert d["completed"] == 3
            # 3 sessions × 2 rows > max_rows 4: someone waited.
            waits = [o[1].sched["queue_wait_s"] for o in outs]
            assert max(waits) > 0.0
            assert d["max_occupancy"] <= 4
        finally:
            sched.close()

    @pytest.mark.scheduler(allow_serial=True)
    def test_queue_sweep_times_out_non_head(self):
        """A request stuck BEHIND a non-fitting head still dies at its
        own deadline with an honest queue timeout (the sweep covers the
        whole queue, not just the head)."""
        engine = make_engine()
        sched = SessionScheduler(engine, max_rows=2)
        try:
            a = sched.submit_async("s0", PROMPTS["s0"],
                                   max_new_tokens=200)
            deadline = time.monotonic() + 60
            while sched.admitted < 1 and time.monotonic() < deadline:
                time.sleep(0.02)
            b = sched.submit_async("s1", PROMPTS["s1"],
                                   max_new_tokens=40, timeout_s=300)
            c = sched.submit_async("s2", PROMPTS["s2"],
                                   max_new_tokens=40, timeout_s=0.5)
            with pytest.raises(TimeoutError, match="admission queue"):
                sched.wait(c)
            assert sched.wait(a) is not None
            assert sched.wait(b) is not None
        finally:
            sched.close()

    @pytest.mark.scheduler(allow_serial=True)
    def test_pool_exhaustion_requeues_as_backpressure(self):
        """Real pool exhaustion during admission (the page estimate
        under-counted) is BACKPRESSURE while other sessions hold pages:
        the request requeues gated on the batch shrinking, instead of
        hard-failing into the adapter ladder."""
        from theroundtaible_tpu.engine.scheduler import _Request, _Row
        engine = make_engine(num_slots=4, kv_layout="paged",
                             page_size=64)
        sched = SessionScheduler(engine)
        try:
            blocker = _Row(name=scoped_slot("sX", "k"), tokens=[1],
                           sampling=engine.sampling, max_new=4)
            sched._active.append(blocker)
            req = _Request("s9", [("k", "a prompt")], None, 8, 60.0,
                           None, sched._fresh_stats())
            err = RuntimeError(
                "Page pool exhausted on data replica 0: all its pages "
                "pinned by the in-flight batch")
            assert sched._requeue_on_exhaustion(req, err) is True
            assert req.requeues == 1 and req.fits_below == 1
            # Gated until the batch actually shrinks below fits_below.
            assert sched._fits_now(req) is False
            sched._active.clear()
            assert sched._fits_now(req) is True
            # Non-exhaustion errors never requeue.
            sched._active.append(blocker)
            assert sched._requeue_on_exhaustion(
                req, RuntimeError("something else")) is False
            sched._active.clear()
            with sched._cv:
                sched._queue.clear()
        finally:
            sched.close()

    @pytest.mark.scheduler(allow_serial=True)
    def test_replica_plan_bucket_group(self):
        from theroundtaible_tpu.engine.serving_loop import ReplicaGroupPlan
        exact = ReplicaGroupPlan([0, 0, 0], 2)
        assert exact.group == 3 and exact.b_padded == 6
        bucketed = ReplicaGroupPlan([0, 0, 0], 2, bucket_group=True)
        assert bucketed.group == 4 and bucketed.b_padded == 8
        # Row placement still round-trips through pos.
        assert sorted(int(p) for p in bucketed.pos) == [0, 1, 2]

    @pytest.mark.scheduler(allow_serial=True)
    def test_paged_refusal_on_impossible_pages(self):
        cfg = get_model_config("tiny-gemma", **MODEL_KW)
        engine = InferenceEngine(cfg, num_slots=4, kv_layout="paged",
                                 page_size=64, num_pages=10)
        sched = SessionScheduler(engine)
        try:
            turns = [(f"k{i}", "p") for i in range(4)]
            with pytest.raises(SchedulerRefused):
                sched.submit("big", turns, max_new_tokens=200)
        finally:
            sched.close()


# ---------------------------------------------------------------------------
# drain / fleet interplay
# ---------------------------------------------------------------------------


class TestDrainInterplay:
    @pytest.mark.scheduler(allow_serial=True)
    def test_drain_rejects_queued_fast_and_health_reports(self):
        from theroundtaible_tpu.engine import fleet
        engine = make_engine()
        sched = SessionScheduler(engine, max_rows=2)
        try:
            a = sched.submit_async("s0", PROMPTS["s0"],
                                   max_new_tokens=200)
            deadline = time.monotonic() + 60
            while sched.admitted < 1 and time.monotonic() < deadline:
                time.sleep(0.02)
            b = sched.submit_async("s1", PROMPTS["s1"],
                                   max_new_tokens=200)
            health = fleet.fleet_health()
            snap = next(s for s in health["schedulers"]
                        if s["sessions"])
            assert "s0" in snap["sessions"]
            report = fleet.drain(timeout_s=60)
            assert report["queued_sessions_rejected"] >= 1
            # The queued session got a CLEAN DrainingError, immediately.
            with pytest.raises(deadlines.DrainingError):
                sched.wait(b)
            # The in-flight session finished its round normally.
            texts, _stats = sched.wait(a)
            assert texts and all(isinstance(t, str) for t in texts)
            # New submissions are refused at the gate while draining.
            with pytest.raises(deadlines.DrainingError):
                sched.submit_async("s2", PROMPTS["s2"])
        finally:
            fleet.resume()
            sched.close()

    @pytest.mark.scheduler(allow_serial=True)
    def test_budget_expiry_fails_only_that_session(self):
        engine = make_engine()
        sched = SessionScheduler(engine, admit_hold_s=0.2)
        try:
            tight = deadlines.Budget.root(0.0, rung="turn")  # born expired
            # ISSUE 16 deadline propagation: an already-spent budget
            # fails fast AT SUBMIT (its own classified kind, zero
            # prefill consumed) instead of queueing just to time out.
            from theroundtaible_tpu.engine.scheduler import \
                DeadlineExpired
            with pytest.raises(DeadlineExpired):
                sched.submit_async("s0", PROMPTS["s0"],
                                   max_new_tokens=200, budget=tight)
            good = sched.submit_async("s1", PROMPTS["s1"],
                                      max_new_tokens=40)
            texts, _ = sched.wait(good)
            assert texts
        finally:
            sched.close()


# ---------------------------------------------------------------------------
# the adapter ladder THROUGH the scheduler
# ---------------------------------------------------------------------------


class TestAdapterLadder:
    @pytest.mark.scheduler(allow_serial=True)
    def test_kv_corrupt_degrades_to_serial_retry_through_scheduler(self):
        from theroundtaible_tpu.adapters.base import KnightTurn
        from theroundtaible_tpu.adapters.tpu_llm import TpuLlmAdapter
        from theroundtaible_tpu.engine import reset_engines
        reset_engines()
        try:
            adapter = TpuLlmAdapter(
                "tpu-llm", {"model": "tiny-gemma", "max_seq_len": 512,
                            "num_slots": 8,
                            "sampling": {"temperature": 0.0,
                                         "max_new_tokens": 24}})
            engine = adapter._get_engine()
            sched = scheduler_for(engine)
            adapter.attach_scheduler(sched, session="sA")
            faults.arm("kv_corrupt", count=1)
            turns = [KnightTurn(knight_name=n, prompt=p)
                     for n, p in PROMPTS["s0"]]
            with pytest.warns(UserWarning, match="retrying"):
                responses = adapter.execute_round(turns, timeout_ms=120000)
            assert len(responses) == 2
            assert adapter.last_degradation == "serial_retry"
            stats = adapter.last_stats()
            # Serial retries went THROUGH the scheduler: provenance rode
            # the stats like int4_paths does.
            assert stats.get("sched") is not None
            sched.close()
        finally:
            reset_engines()

    @pytest.mark.scheduler
    def test_serve_discussions_two_concurrent_scripted_sessions(
            self, tmp_path):
        """commands/serve end-to-end: two concurrent scripted 2-knight
        discussions through the orchestrator share one engine + one
        scheduler, both reach consensus, and the report carries the
        scheduler's decision provenance."""
        from theroundtaible_tpu.adapters.tpu_llm import TpuLlmAdapter
        from theroundtaible_tpu.commands.serve import serve_discussions
        from theroundtaible_tpu.core.types import (ConsensusBlock,
                                                   KnightConfig,
                                                   RoundtableConfig,
                                                   RulesConfig)
        from theroundtaible_tpu.engine import reset_engines
        from theroundtaible_tpu.adapters import factory
        reset_engines()

        class Scripted(TpuLlmAdapter):
            def parse_consensus(self, response, round_num):
                return ConsensusBlock(
                    knight=self.name, round=round_num,
                    consensus_score=9.5, agrees_with=[],
                    pending_issues=[], proposal="p",
                    files_to_modify=["x.md"])

        engine_cfg = {"model": "tiny-gemma", "max_seq_len": 512,
                      "num_slots": 8,
                      "sampling": {"temperature": 0.0,
                                   "max_new_tokens": 24}}
        config = RoundtableConfig(
            version="1.0", project="t", language="en",
            knights=[KnightConfig(name=f"Knight-{c}", adapter="tpu-llm",
                                  capabilities=[], priority=i + 1)
                     for i, c in enumerate("AB")],
            rules=RulesConfig(max_rounds=1, consensus_threshold=9,
                              timeout_per_turn_seconds=120,
                              escalate_to_user_after=4,
                              auto_execute=False, parallel_rounds=True),
            chronicle="chronicle.md", adapter_config={"tpu-llm": {}})
        (tmp_path / ".roundtable" / "sessions").mkdir(parents=True)

        real_create = factory.create_adapter

        def scripted_create(adapter_id, cfg, timeout_ms):
            if adapter_id.startswith("tpu-llm"):
                return Scripted("tpu-llm", engine_cfg, timeout_ms)
            return real_create(adapter_id, cfg, timeout_ms)

        factory.create_adapter = scripted_create
        try:
            report = serve_discussions(
                ["Topic one for the table", "Topic one for the table"],
                config, str(tmp_path), admit_hold_s=0.4)
        finally:
            factory.create_adapter = real_create
            reset_engines()
        assert all(e["ok"] for e in report["sessions"]), report["sessions"]
        assert all(e["result"].consensus for e in report["sessions"])
        assert len(report["schedulers"]) == 1
        prov = report["schedulers"][0]
        assert prov["admitted"] >= 2
        assert prov["max_occupancy"] > 1
        # Distinct session dirs even for an identical topic (slug dedup).
        paths = {e["session_path"] for e in report["sessions"]}
        assert len(paths) == 2


# ---------------------------------------------------------------------------
# metrics under concurrency
# ---------------------------------------------------------------------------


@pytest.mark.scheduler(allow_serial=True)
class TestMetricsConcurrency:
    def test_turn_records_carry_scheduler_fields(self, tmp_path):
        from theroundtaible_tpu.utils.metrics import SessionMetrics
        m = SessionMetrics(tmp_path)
        m.record_turn("k", 1, 1.0, engine={
            "decode_tokens": 5,
            "sched": {"queue_wait_s": 0.25, "occupancy_mean": 4.0}})
        t = m.rounds[-1].turns[-1]
        assert t.queue_wait_s == 0.25
        assert t.batch_occupancy == 4.0
        m.write()
        import json
        data = json.loads((tmp_path / "metrics.json").read_text())
        turn = data["rounds"][0]["turns"][0]
        assert turn["queue_wait_s"] == 0.25
        assert turn["batch_occupancy"] == 4.0

    def test_concurrent_record_turn_is_safe(self, tmp_path):
        from theroundtaible_tpu.utils.metrics import SessionMetrics
        m = SessionMetrics(tmp_path)
        m.start_round(1)

        def spam(k):
            for _ in range(50):
                m.record_turn(f"k{k}", 1, 0.01)
                m.write()

        threads = [threading.Thread(target=spam, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sum(len(r.turns) for r in m.rounds) == 200
        m.finish("done")


# ---------------------------------------------------------------------------
# ISSUE 6 acceptance: the steady-state recompile sentinel on a live
# scheduler — occupancy drift compiles NOTHING once warmup is declared
# (enforced: conftest arms ROUNDTABLE_RECOMPILE_STRICT for this suite,
# so a mid-serve compile would RAISE into the session errors), and an
# injected non-bucket shape trips strict mode + a flight dump.
# ---------------------------------------------------------------------------


@pytest.mark.scheduler
@pytest.mark.perf_obs
class TestRecompileSentinel:
    def _submit_all(self, sched, sessions, max_new=70):
        results, errors = {}, {}

        def run(sid, turns):
            try:
                results[sid] = sched.submit(sid, turns,
                                            max_new_tokens=max_new)
            except Exception as e:  # noqa: BLE001 — asserted below
                errors[sid] = e

        threads = [threading.Thread(target=run, args=(sid, turns))
                   for sid, turns in sessions.items()]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        return results, errors

    def test_drift_run_compiles_nothing_and_new_shape_trips(
            self, tmp_path, monkeypatch):
        from theroundtaible_tpu.engine import compile_watch
        from theroundtaible_tpu.utils import telemetry

        monkeypatch.setenv("ROUNDTABLE_TELEMETRY_DIR", str(tmp_path))
        monkeypatch.setenv("ROUNDTABLE_PERF_CHIP", "v5e")
        assert compile_watch.install() != "off"
        engine = make_engine()
        # Device-program warmup for every bucket the max_rows=4
        # scheduler can dispatch ({1, 2, 4})...
        engine.warmup(max_prompt_tokens=256, batch_sizes=(1, 2, 4))
        # ...then representative SCHEDULED traffic to compile the
        # scheduler-side shapes (pipelined-segment carries, join with
        # pinned live rows) warmup's direct calls never touch.
        # engine.warmup() declared steady state for DIRECT serving;
        # attaching a scheduler ADDS compile surface, so construction
        # REOPENS the warmup phase (the sanctioned production escape —
        # without it this warm traffic would be false violations).
        assert compile_watch.steady_state_labels() == (engine.cfg.name,)
        sched = SessionScheduler(engine, max_rows=4, admit_hold_s=0.2)
        assert compile_watch.steady_state_labels() == ()
        sched.submit("w-solo", PROMPTS["s0"][:1], max_new_tokens=70)
        sched.submit("w-pair", PROMPTS["s1"], max_new_tokens=70)
        _res, errs = self._submit_all(
            sched, {"s0": PROMPTS["s0"], "s1": PROMPTS["s1"]})
        assert not errs, f"warm pass failed: {errs}"

        # --- steady state: the compile set is now declared closed ---
        sched.declare_warmup_complete()
        assert compile_watch.steady_state_labels() == (
            engine.cfg.name,)
        assert compile_watch.steady_state_compiles() == 0

        # Occupancy-DRIFT run: three fresh 2-knight sessions through a
        # 4-row batch — the third queues, joins as rows retire, rows
        # hit eos at different steps, so the live-row count drifts
        # across segments. STRICT is armed (conftest): any compile
        # would raise RecompileInSteadyState into `errs`.
        results, errs = self._submit_all(
            sched, {"d0": PROMPTS["s0"], "d1": PROMPTS["s1"],
                    "d2": PROMPTS["s2"]})
        assert not errs, f"drift pass recompiled or failed: {errs}"
        assert set(results) == {"d0", "d1", "d2"}
        assert compile_watch.steady_state_compiles() == 0
        desc = sched.describe()
        assert desc["max_occupancy"] >= 3
        # (each session's own view of the batch it rode in;
        # `occupancy_recent` keeps the last 32 segments only)
        seen = {occ for _texts, stats in results.values()
                for occ in (stats.sched["occupancy_max"],
                            stats.sched["occupancy_mean"])}
        assert len(seen) >= 2, \
            "occupancy never drifted — the run proved nothing"

        # Perf gauges rode along (ISSUE 6 tentpole): the per-session
        # KV series, REMOVED at retirement (uuid-tagged session ids
        # would otherwise grow the registry one dead series per session
        # ever served).
        assert telemetry.REGISTRY.gauge_value(
            "roundtable_session_kv_bytes", engine=engine.cfg.name,
            session="d0") is None

        # --- injected NEW shape: a 3-wide batch was never warmed
        # (buckets are {1, 2, 4}; direct generate_batch dispatches the
        # exact row count) — strict mode must fail it LOUD, with a
        # flight-recorder postmortem.
        d0 = telemetry.REGISTRY.counter_total(
            "roundtable_flight_dumps_total",
            trigger="steady_state_compile")
        with pytest.raises(compile_watch.RecompileInSteadyState):
            engine.generate_batch(
                [("x1", "zig"), ("x2", "zag"), ("x3", "zog")],
                max_new_tokens=8, session="inject")
        assert compile_watch.steady_state_compiles() >= 1
        assert telemetry.REGISTRY.counter_total(
            "roundtable_flight_dumps_total",
            trigger="steady_state_compile") == d0 + 1
        sched.close()


# ---------------------------------------------------------------------------
# the loop clock (ISSUE 25): what the scheduler's thread was doing
# ---------------------------------------------------------------------------


class TestLoopClock:
    @pytest.mark.scheduler
    def test_phases_sum_to_the_loops_wall_and_every_phase_is_met(
            self, shared_engine):
        """Over a scheduled three-session run the ten phases telescope
        to the loop thread's wall (within 1 %), each is met at least
        once, and the registry series moves with describe()."""
        from theroundtaible_tpu.engine.scheduler import LOOP_PHASES
        from theroundtaible_tpu.utils import telemetry

        name = shared_engine.cfg.name
        published = {p: telemetry.REGISTRY.counter_total(
            "roundtable_sched_loop_seconds_total", engine=name, phase=p)
            for p in LOOP_PHASES}
        sched = SessionScheduler(shared_engine, admit_hold_s=0.3)
        try:
            a, t_a = sched.describe()["loop_seconds"], time.monotonic()
            results, errors = run_concurrent(sched)
            assert not errors and len(results) == 3
            time.sleep(0.3)            # the loop goes back to waiting
            b, t_b = sched.describe()["loop_seconds"], time.monotonic()
        finally:
            sched.close()
        assert tuple(b) == LOOP_PHASES
        gained = {p: b[p] - a[p] for p in LOOP_PHASES}
        assert sum(gained.values()) == pytest.approx(t_b - t_a, rel=0.01)
        assert all(b[p] > 0.0 for p in LOOP_PHASES), b
        # the device's work shows as the host's blocked phases
        assert gained["sync"] + gained["dispatch"] > gained["flush"]
        for p in ("admit", "build", "accept", "retire"):
            moved = telemetry.REGISTRY.counter_total(
                "roundtable_sched_loop_seconds_total", engine=name,
                phase=p) - published[p]
            assert 0.0 < moved <= b[p] + 1e-6, (p, moved, b[p])

    @pytest.mark.scheduler(allow_serial=True)
    def test_unarmed_a_tick_creates_no_span_and_the_totals_still_move(
            self, shared_engine, monkeypatch):
        from theroundtaible_tpu.utils import telemetry

        made = []

        class CountingSpan(telemetry.Span):
            def __init__(self, *args, **kw):
                made.append(args[0])
                super().__init__(*args, **kw)

        monkeypatch.setattr(telemetry, "Span", CountingSpan)
        assert not telemetry.ACTIVE
        emitted = telemetry.spans_emitted()
        sched = SessionScheduler(shared_engine)
        try:
            before = sched.describe()["loop_seconds"]
            sched.submit("quiet", PROMPTS["s0"], max_new_tokens=70)
            after = sched.describe()["loop_seconds"]
            assert sched._clock._open is None
            assert sched._clock.tick >= 1
        finally:
            sched.close()
        assert made == [] and telemetry.spans_emitted() == emitted
        assert sum(after.values()) > sum(before.values())
        assert after["sync"] + after["dispatch"] > 0.0

    @pytest.mark.scheduler(allow_serial=True)
    @pytest.mark.telemetry
    def test_admit_and_segment_spans_carry_their_counts(self):
        """The `admit` span of a request: caused by, and in the trace
        of, the request's own span; it and the `segment` spans carry
        the counts of the work done at that boundary, and the loop's
        stretches lie around them on the same clock."""
        from theroundtaible_tpu.utils import telemetry

        # A cold admission both times (no index hit): the first round
        # compiles what the traced one then takes.
        engine = make_engine(prefix_cache=False)
        sched = SessionScheduler(engine)
        sched.submit("warm", PROMPTS["s1"], max_new_tokens=70)
        telemetry.disarm()
        telemetry.arm()                # this test's own span buffer
        t_a = time.monotonic()
        try:
            with telemetry.span("request", stream="st") as request:
                texts, stats = sched.submit(
                    "traced", PROMPTS["s1"], max_new_tokens=70)
        finally:
            sched.close()
        spans = telemetry.spans_between(t_a, time.monotonic())
        (admit,) = [r for r in spans if r["rung"] == "admit"]
        assert admit["trace_id"] == request.trace_id
        assert admit["parent_id"] == request.span_id
        at = admit["attrs"]
        assert at["session"] == "traced" and at["rows"] == 2
        assert at["deferred"] is False
        assert at["prefill_tokens"] == stats.prefill_tokens > 0
        assert at["reused_tokens"] == stats.reused_tokens
        assert at["prefix_reused_tokens"] == stats.prefix_reused_tokens
        assert at["queue_wait_s"] >= 0.0
        assert 0.0 < at["sync_s"] <= admit["dur_s"]
        # admission's plan and its dispatches parent under it, in the
        # same trace
        kids = [r for r in spans if r["parent_id"] == admit["span_id"]]
        assert {r["rung"] for r in kids} == {"plan", "dispatch"}
        assert {r["trace_id"] for r in kids} == {request.trace_id}
        segments = [r for r in spans if r["rung"] == "segment"]
        assert segments
        for seg in segments:
            sa = seg["attrs"]
            assert sa["kind"] == "plain" and sa["rows"] == 2
            assert sa["label"] == "decode[b=2,paged]"
            assert sa["decode_tokens"] == sa["steps"] * sa["rows"]
            assert (sa["prefill_tokens"], sa["drafted"],
                    sa["accepted"]) == (0, 0, 0)
            assert sa["tick"] >= 1
        assert sum(s["attrs"]["steps"] for s in segments) >= 69
        # every phase's stretch of that run is on the same clock
        loops = [r for r in spans if r["rung"].startswith("loop.")]
        assert {"loop.admit", "loop.admit_sync", "loop.build",
                "loop.sync", "loop.accept", "loop.retire"} <= {
                    r["rung"] for r in loops}
        sync = [r for r in loops if r["rung"] == "loop.sync"]
        assert any(s["t0"] <= seg["t0"] + seg["dur_s"]
                   and seg["t0"] <= s["t0"] + s["dur_s"]
                   for s in sync for seg in segments)


def test_warm_heap_leaves_the_collectors_sight_until_close(shared_engine):
    """declare_warmup_complete freezes what warm-up built (a full
    collection under traffic then walks traffic's objects alone: the
    chip lost 0.22 s of a window to one, PERF.md Findings PR 42), and
    close() hands it back so a rebuilt engine's predecessor can go."""
    import gc
    sched = SessionScheduler(shared_engine, max_rows=2)
    try:
        alive = len(gc.get_objects())
        sched.declare_warmup_complete()
        frozen = gc.get_freeze_count()
        assert frozen > alive // 2
        # Nothing frozen is walked: a full collection sees what came
        # after, and a cycle made now is still collected.
        assert len(gc.get_objects()) < frozen // 10
        loop = []
        loop.append(loop)
        del loop
        assert gc.collect() >= 1
        # (a frozen object freed by its reference count leaves the count)
        assert gc.get_freeze_count() > frozen // 2
    finally:
        sched.close()
    assert gc.get_freeze_count() == 0


def test_the_loops_frame_opens_a_chunk_that_holds_what_runs_above_it(
        shared_engine):
    """CPython frees a 16 KiB chunk of a thread's frames when the chunk's
    first frame returns, so calls across a chunk's end cost a mmap and
    a munmap each — what tracing and lowering on the loop's thread paid
    (PERF.md, Findings PR 46). The loop's own frame is larger than a
    256 KiB chunk, so CPython opens 512 KiB for it and keeps them while
    the loop runs; what the thread's frames need stays far below the
    room that leaves."""
    import sys

    from theroundtaible_tpu.engine import serving_loop as mod
    from theroundtaible_tpu.engine.engine import InferenceEngine
    code = SessionScheduler._loop.__code__
    assert code.co_stacksize == mod.FRAME_SLOTS > 256 * 1024 // 8
    # ... and so is the frame under which an engine's build traces and
    # lowers its ragged grid, on the thread that builds it (PR 53)
    assert InferenceEngine._warm_ragged.__code__.co_stacksize \
        == mod.FRAME_SLOTS
    room = 512 * 1024 // 8 - mod.FRAME_SLOTS - 1024
    sched = SessionScheduler(shared_engine, max_rows=2)
    try:
        frame = sys._current_frames()[sched._thread.ident]
        names, slots = [], 0
        while frame is not None:
            c = frame.f_code
            names.append(c.co_name)
            if c is not code:
                slots += (len(c.co_varnames) + len(c.co_cellvars)
                          + len(c.co_freevars) + c.co_stacksize + 9)
            frame = frame.f_back
        assert "_loop" in names        # the thread runs inside that frame
        assert slots < room // 10
    finally:
        sched.close()
