"""Engine supervision & crash-consistent session recovery (ISSUE 12).

Acceptance, end to end on the CPU backend:
- CHAOS: `device_lost` armed mid-3-session scheduled discussion — the
  supervisor tears the engine down, rebuilds it, re-attaches the
  scheduler, and every session completes with greedy token parity vs
  the fault-free run, with zero steady-state recompiles under
  ROUNDTABLE_RECOMPILE_STRICT=1 (the post-restart warmup is a
  sanctioned reopen);
- ROLLING: explicit `supervisor.restart()` cycles under scheduled load
  lose zero sessions — idle KV crosses the restart via the
  evacuate → adopt → restore hop and later rounds extend it;
- BUDGET: restart-budget exhaustion marks the engine dead and every
  later submit fails fast with a clean classified error;
- JOURNAL: committed turns are fsynced at retire, torn tails are
  tolerated, and a killed process resumes at the exact committed turn
  by replaying the journal through the normal submit path (including a
  real kill -9 of a serving child process);
- plus the fleet drain→resume→submit regression (satellite: resume()
  must re-open attached schedulers' admission gates) and the
  detection/classification units (device_lost routed to the
  supervisor, never the in-place dispatch retry).
"""

import os
import signal
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import pytest

jax = pytest.importorskip("jax")

from theroundtaible_tpu.core.errors import classify_error, hint_for_kind
from theroundtaible_tpu.engine import (deadlines, faults, fleet,
                                       get_engine, reset_engines)
from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.scheduler import SessionScheduler
from theroundtaible_tpu.engine.session_journal import (SessionJournal,
                                                       prompt_sha,
                                                       replay_turn_prompt,
                                                       replay_turns)
from theroundtaible_tpu.engine.supervisor import (EngineDead,
                                                  EngineSupervisor,
                                                  engine_key,
                                                  set_supervisor,
                                                  supervisor,
                                                  supervisor_snapshot)

CONFIG = {"model": "tiny-gemma", "max_seq_len": 256, "num_slots": 8,
          "kv_layout": "paged", "page_size": 16, "kv_offload": True,
          "mesh": {"data": 1, "model": 1},
          "sampling": {"temperature": 0.0}}

BASE_PROMPTS = [
    "The round table weighs the eastern gate repairs against the "
    "harvest levy.",
    "A separate council entirely, on the dragon sightings near the "
    "northern ford.",
    "Third matter: the tournament seeding and the armory budget.",
]


@pytest.fixture(autouse=True)
def clean_state():
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
    set_supervisor(None)


def make_engine(**over):
    cfg = dict(CONFIG)
    cfg.update(over)
    return InferenceEngine.from_config(cfg)


def run_rounds(sched, *, k=3, rounds=3, max_new=8, retries=0,
               prefix="s", on_round=None):
    """K concurrent scripted sessions × `rounds` multi-round turns
    through the REAL submit path (each round extends the transcript, so
    later rounds reuse committed KV). `retries` is the adapter-ladder
    stand-in: the supervisor's crash path fails active requests into
    their adapters' ladders, whose PR-1 retry resubmits. Returns
    (produced texts per session, errors per session)."""
    produced = {f"{prefix}{i}": [] for i in range(k)}
    errors = {}
    lock = threading.Lock()

    def sess(i):
        sid = f"{prefix}{i}"
        t = BASE_PROMPTS[i % len(BASE_PROMPTS)] + f" Seat {i} speaks."
        for r in range(rounds):
            err = None
            for _attempt in range(retries + 1):
                try:
                    texts, _ = sched.submit(
                        sid, [(f"knight{i}", t)],
                        max_new_tokens=max_new, timeout_s=120)
                    err = None
                    break
                except Exception as e:  # noqa: BLE001 — asserted by callers
                    err = e
                    time.sleep(0.2)
            if err is not None:
                with lock:
                    errors[sid] = err
                return
            with lock:
                produced[sid].append(texts[0])
            if on_round is not None:
                on_round(sid, r)
            t = t + " " + texts[0]

    threads = [threading.Thread(target=sess, args=(i,), daemon=True)
               for i in range(k)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=240)
    return produced, errors


# ---------------------------------------------------------------------------
# detection & classification units
# ---------------------------------------------------------------------------


class TestDetection:
    @pytest.mark.supervision(allow_norestart=True)
    def test_device_lost_classified_first_and_hinted(self):
        """The injected fault message and the real runtime's strings
        both classify as device_lost — BEFORE the generic markers (a
        'DATA_LOSS ... out of memory' combo must still read as the
        stronger verdict)."""
        for msg in ("injected fault: DATA_LOSS: device is lost "
                    "(device_lost)",
                    "INTERNAL: device halted, core dumped",
                    "DATA_LOSS: out of memory replaying device state"):
            assert classify_error(RuntimeError(msg)) == "device_lost", msg
        assert "supervisor" in hint_for_kind("device_lost")

    @pytest.mark.supervision(allow_norestart=True)
    def test_device_lost_never_retried_in_place(self):
        """faults satellite: device_lost is non-retryable-in-place — it
        routes to the supervisor, never the dispatch RetryPolicy."""
        err = RuntimeError("DATA_LOSS: device is lost (device_lost)")
        assert not faults.RetryPolicy().retryable(err)

    @pytest.mark.supervision(allow_norestart=True)
    def test_injection_points_exist_and_classify(self):
        """The deterministic ISSUE 12 points: device_lost raises a
        device_lost-classified fault; engine_wedged carries the hang
        family (repeated firings model 'hangs past the ladder')."""
        assert "device_lost" in faults.POINTS
        assert "engine_wedged" in faults.POINTS
        faults.arm("device_lost", count=1)
        with pytest.raises(faults.FaultInjected) as e:
            faults.maybe_inject("device_lost")
        assert classify_error(e.value) == "device_lost"
        faults.arm("engine_wedged", count=1)
        with pytest.raises(faults.FaultInjected) as e:
            faults.maybe_inject("engine_wedged")
        assert classify_error(e.value) == "hang"

    @pytest.mark.supervision(allow_norestart=True)
    def test_kill_switch_disables_auto_detection(self, monkeypatch):
        monkeypatch.setenv("ROUNDTABLE_SUPERVISOR", "0")
        sup = EngineSupervisor()
        err = RuntimeError("DATA_LOSS: device is lost (device_lost)")
        assert sup.handle_dispatch_failure(None, err) is False

    @pytest.mark.supervision(allow_norestart=True)
    def test_hang_escalation_counts_to_threshold(self):
        """One hang is the watchdog's business; `hang_threshold`
        consecutive hangs mean the ENGINE is wedged. Below threshold the
        failure routes to the normal ladder (returns False); a
        non-hang failure in between resets the count."""
        sup = EngineSupervisor(hang_threshold=2)
        eng = SimpleNamespace(cfg=SimpleNamespace(name="wedgy"),
                              _engine_config=None, _scheduler=None)
        sched = SimpleNamespace(engine=eng, closed=False)
        hang = RuntimeError("watchdog: device dispatch wedged (hang)")
        assert sup.handle_dispatch_failure(sched, hang) is False
        st = sup._state_for(eng)
        assert st.consecutive_hangs == 1
        # a retryable failure in between resets the streak
        assert sup.handle_dispatch_failure(
            sched, RuntimeError("transient dispatch failure")) is False
        assert st.consecutive_hangs == 0
        # two consecutive hangs escalate — but with no rebuild recipe
        # (_engine_config None) the supervisor records the verdict and
        # lets the ladder degrade instead of destroying the engine.
        assert sup.handle_dispatch_failure(sched, hang) is False
        assert st.consecutive_hangs == 1
        assert sup.handle_dispatch_failure(sched, hang) is False
        assert st.consecutive_hangs == 2

    @pytest.mark.supervision(allow_norestart=True)
    def test_engine_key_stability(self):
        eng = SimpleNamespace(cfg=SimpleNamespace(name="alpha"))
        key = engine_key(eng)
        assert key.startswith("direct:alpha@")
        # Sticky: the same instance always maps to the same state...
        assert engine_key(eng) == key
        # ...but a DIFFERENT instance with the same model name never
        # shares it (unrelated engines must not pool hang counts or
        # restart budgets).
        other = SimpleNamespace(cfg=SimpleNamespace(name="alpha"))
        assert engine_key(other) != key
        eng2 = SimpleNamespace(_engine_cache_key="k123",
                               cfg=SimpleNamespace(name="alpha"))
        assert engine_key(eng2) == "k123"


# ---------------------------------------------------------------------------
# the restart cycle (chaos / rolling / budget)
# ---------------------------------------------------------------------------


class TestRestartCycle:
    @pytest.mark.supervision
    @pytest.mark.scheduler
    def test_chaos_device_lost_mid_discussion_token_parity(self):
        """THE chaos acceptance: device_lost fired mid-3-session
        scheduled discussion under ROUNDTABLE_RECOMPILE_STRICT=1 (armed
        by the scheduler marker). The supervisor quiesces, rebuilds,
        re-attaches; the failed round retries through the adapter-ladder
        stand-in; every session completes all rounds with greedy token
        parity vs the fault-free run and ZERO steady-state recompiles
        (the post-restart compiles land in the sanctioned reopened
        warmup phase)."""
        from theroundtaible_tpu.engine import compile_watch

        # fault-free reference on its own engine
        base_eng = make_engine()
        base_sched = SessionScheduler(base_eng, admit_hold_s=0.3)
        try:
            base, berr = run_rounds(base_sched, prefix="b")
            assert not berr, berr
        finally:
            base_sched.close()

        set_supervisor(EngineSupervisor())
        eng = make_engine()
        sched = SessionScheduler(eng, admit_hold_s=0.3)
        try:
            # Warm pass: identical prompts (session ids differ), so the
            # measured pass can serve with the compile set CLOSED.
            warm, werr = run_rounds(sched, prefix="w")
            assert not werr, werr
            sched.declare_warmup_complete()
            assert compile_watch.steady_state_compiles() == 0

            armed = threading.Event()

            def arm_once(_sid, r):
                # Arm the fault once round 1 committed anywhere: the
                # next shared dispatch dies with a lost device.
                if r == 0 and not armed.is_set():
                    armed.set()
                    faults.arm("device_lost", count=1)

            produced, errors = run_rounds(sched, prefix="d",
                                          retries=2, on_round=arm_once)
            assert not errors, errors
            spec = faults.spec_for("device_lost")
            assert spec is not None and spec.fired == 1, \
                "device_lost never fired — the chaos run proved nothing"

            # greedy token parity vs the fault-free run, every round
            for i in range(3):
                assert produced[f"d{i}"] == base[f"b{i}"], \
                    f"session {i} diverged across the restart"

            snap = supervisor_snapshot()
            assert snap["restarts"] == 1
            assert snap["sessions_lost"] == 0
            st = snap["engines"][0]
            assert st["dead"] is False
            assert st["history"][-1]["reason"] == "device_lost"
            assert st["history"][-1]["ok"] is True

            # The scheduler serves a FRESH engine now, and the cycle is
            # visible in its flight ring.
            assert sched.engine is not eng
            events = [e["event"] for e in sched.describe()["events"]]
            for ev in ("pause_admission", "reattach_engine",
                       "reopen_admission"):
                assert ev in events, f"missing {ev} in {events}"

            # STRICT held: nothing recompiled in steady state — the
            # post-restart compiles were a sanctioned warmup reopen.
            assert compile_watch.steady_state_compiles() == 0
        finally:
            sched.close()

    @pytest.mark.supervision
    @pytest.mark.scheduler
    def test_rolling_restart_under_load_zero_loss(self):
        """Rolling-restart acceptance: explicit supervisor.restart()
        cycles fired between rounds of a 3-session scheduled load. The
        quiesce path lets actives retire (nothing is rejected, nothing
        retries), idle KV crosses each restart via evacuate → adopt →
        restore, and later rounds extend it — zero sessions lost, full
        greedy parity vs the uninterrupted run."""
        base_eng = make_engine()
        base_sched = SessionScheduler(base_eng, admit_hold_s=0.3)
        try:
            base, berr = run_rounds(base_sched, prefix="b")
            assert not berr, berr
        finally:
            base_sched.close()

        set_supervisor(EngineSupervisor(max_restarts=5))
        eng = make_engine()
        sched = SessionScheduler(eng, admit_hold_s=0.3)
        try:
            produced = {f"r{i}": [] for i in range(3)}
            committed = {1: threading.Event(), 2: threading.Event()}

            def note(sid, r):
                produced[sid].append(None)  # count only; texts below
                if all(len(v) >= r + 1 for v in produced.values()) \
                        and (r + 1) in committed:
                    committed[r + 1].set()

            results = {}
            errors = {}
            lock = threading.Lock()

            def sess(i):
                sid = f"r{i}"
                t = BASE_PROMPTS[i] + f" Seat {i} speaks."
                out = []
                for r in range(3):
                    try:
                        texts, _ = sched.submit(
                            sid, [(f"knight{i}", t)],
                            max_new_tokens=8, timeout_s=120)
                    except Exception as e:  # noqa: BLE001
                        with lock:
                            errors[sid] = e
                        return
                    out.append(texts[0])
                    t = t + " " + texts[0]
                    note(sid, r)
                with lock:
                    results[sid] = out

            threads = [threading.Thread(target=sess, args=(i,),
                                        daemon=True) for i in range(3)]
            for th in threads:
                th.start()
            walls = []
            for cycle in (1, 2):
                assert committed[cycle].wait(timeout=120), \
                    f"round {cycle} never committed everywhere"
                rep = supervisor().restart(
                    sched.engine, reason=f"rolling_{cycle}",
                    scheduler=sched)
                assert rep["ok"] is True
                walls.append(rep["wall_s"])
            for th in threads:
                th.join(timeout=240)

            assert not errors, errors
            for i in range(3):
                assert results[f"r{i}"] == base[f"b{i}"], \
                    f"session {i} diverged across rolling restarts"
            snap = supervisor_snapshot()
            assert snap["restarts"] == 2
            assert snap["sessions_lost"] == 0
            # idle KV actually crossed the restarts: each cycle
            # evacuated the resident sessions and restored them onto
            # the rebuilt engine.
            assert snap["sessions_recovered"] >= 3
            for entry in snap["engines"][0]["history"]:
                assert entry["ok"] is True
            assert all(w >= 0 for w in walls)
        finally:
            sched.close()

    @pytest.mark.supervision
    def test_restart_budget_exhaustion_fails_clean(self):
        """Budget acceptance: a rebuild that can never succeed burns the
        restart budget, the engine is marked DEAD, active/later submits
        fail fast with the clean classified error (not a timeout), and
        fleet_health says why."""
        set_supervisor(EngineSupervisor(max_restarts=1, build_attempts=1,
                                        backoff_s=0.0))
        eng = make_engine()
        sched = SessionScheduler(eng)
        try:
            texts, _ = sched.submit("pre", [("lancelot",
                                             BASE_PROMPTS[0])],
                                    max_new_tokens=6, timeout_s=120)
            assert texts[0]

            def bad_rebuild():
                raise RuntimeError("rebuild always fails (test)")

            cause = RuntimeError("DATA_LOSS: device is lost "
                                 "(device_lost)")
            with pytest.raises(EngineDead) as e:
                supervisor().restart(eng, reason="device_lost",
                                     cause=cause, scheduler=sched,
                                     rebuild=bad_rebuild)
            assert "restart budget exhausted" in str(e.value)
            # EngineDead is a classified AdapterError — the clean
            # failure shape every adapter ladder already understands.
            from theroundtaible_tpu.core.errors import AdapterError
            assert isinstance(e.value, AdapterError)

            # later submits fail FAST with the same classified reason
            t0 = time.monotonic()
            with pytest.raises(EngineDead, match="dead"):
                sched.submit("late", [("galahad", BASE_PROMPTS[1])],
                             max_new_tokens=6, timeout_s=120)
            assert time.monotonic() - t0 < 5.0, \
                "dead-engine submit waited instead of failing fast"

            sup = fleet.fleet_health()["supervisor"]
            assert sup["dead_engines"] == 1
            st = sup["engines"][0]
            assert st["dead"] is True
            assert "restart budget exhausted" in st["dead_reason"]
            assert "rebuild failed" in st["dead_reason"]
        finally:
            sched.close()


# ---------------------------------------------------------------------------
# fleet drain → resume regression (satellite)
# ---------------------------------------------------------------------------


class TestFleetResume:
    @pytest.fixture(autouse=True)
    def clean_engines(self):
        reset_engines()
        yield
        reset_engines()

    @pytest.mark.supervision(allow_norestart=True)
    def test_drain_resume_submit_admits_again(self):
        """fleet.resume() satellite regression: drain() closes every
        attached scheduler's admission gate; resume() must RE-OPEN it —
        before the fix only the module DRAINING flag flipped and a
        drained scheduler's queue stayed paused forever (post-resume
        submits queued but were never admitted)."""
        cfg = dict(CONFIG, seed=17)
        eng = get_engine(cfg)
        sched = SessionScheduler(eng)
        try:
            texts, _ = sched.submit("d0", [("lancelot",
                                            BASE_PROMPTS[0])],
                                    max_new_tokens=6, timeout_s=120)
            assert texts[0]
            report = fleet.drain(timeout_s=10.0)
            assert report["clean"]
            assert sched.paused == "fleet.drain"
            assert fleet.fleet_health()["draining"] is True
            fleet.resume()
            assert sched.paused is None
            assert fleet.fleet_health()["draining"] is False
            # the regression: this submit must be ADMITTED, not sit in
            # a forever-paused queue until its timeout
            texts2, _ = sched.submit("d0", [("lancelot",
                                             BASE_PROMPTS[0])],
                                     max_new_tokens=6, timeout_s=60)
            assert texts2[0] == texts[0]
        finally:
            sched.close()


# ---------------------------------------------------------------------------
# the durable session journal
# ---------------------------------------------------------------------------


class TestSessionJournal:
    @pytest.mark.supervision(allow_norestart=True)
    def test_record_and_read_roundtrip(self, tmp_path):
        j = SessionJournal(tmp_path)
        rec = j.record_turn(
            "alpha",
            [{"knight": "lancelot", "prompt": "the gate",
              "prompt_tokens": [3, 5, 7], "produced": [11, 13],
              "adapter": "stoic"}],
            consensus=0.75)
        assert rec["turn"] == 0
        assert rec["consensus"] == 0.75
        j.record_turn("alpha", [{"knight": "lancelot",
                                 "prompt_tokens": [3, 5, 7, 11, 13, 2],
                                 "produced": [17]}])
        turns = j.turns("alpha")
        assert [t["turn"] for t in turns] == [0, 1]
        row = turns[0]["rows"][0]
        assert row["prompt_sha256"] == prompt_sha("the gate")
        assert row["prompt_tokens"] == [3, 5, 7]
        assert row["produced"] == [11, 13]
        assert row["adapter"] == "stoic"
        assert j.last_turn("alpha") == 1
        assert j.sessions() == ["alpha"]
        assert replay_turn_prompt(row) == [3, 5, 7, 11, 13]

    @pytest.mark.supervision(allow_norestart=True)
    def test_torn_tail_tolerated_and_numbering_continues(self, tmp_path):
        """The WAL rule: a kill -9 mid-write leaves a partial last line;
        the reader serves every complete record before it, and a resumed
        process continues the turn numbering from the last COMMITTED
        record (the torn turn was never acknowledged)."""
        j = SessionJournal(tmp_path)
        j.record_turn("s", [{"knight": "k", "prompt_tokens": [1],
                             "produced": [2]}])
        j.record_turn("s", [{"knight": "k", "prompt_tokens": [1, 2],
                             "produced": [3]}])
        path = j.path_for("s")
        with open(path, "a", encoding="utf-8") as f:
            f.write('{"v":1,"session":"s","turn":2,"rows":[{"kni')
        # a FRESH journal (the resumed process) reads only the
        # committed records and numbers the next turn after them
        j2 = SessionJournal(tmp_path)
        assert [t["turn"] for t in j2.turns("s")] == [0, 1]
        rec = j2.record_turn("s", [{"knight": "k",
                                    "prompt_tokens": [1, 2, 3],
                                    "produced": [4]}])
        assert rec["turn"] == 2
        # ...and the re-written turn 2 is now a COMPLETE record — but
        # the torn line before it still truncates the read (the reader
        # must never leap a hole), so exactly the committed prefix
        # serves.
        assert [t["turn"] for t in j2.turns("s")] == [0, 1]

    @pytest.mark.supervision(allow_norestart=True)
    def test_replay_suspends_journal_writes(self, tmp_path):
        """Replay drives the normal submit path — without suspension
        every replayed turn would re-journal itself, doubling the file
        on every resume."""
        j = SessionJournal(tmp_path)
        j.record_turn("s", [{"knight": "k", "prompt_tokens": [1, 2],
                             "produced": [3], "adapter": None}])
        j.record_turn("s", [{"knight": "k", "prompt_tokens": [1, 2, 3],
                             "produced": [4], "adapter": "persona-a"}])
        calls = []

        def submit(session, turns, **kw):
            calls.append((session, turns, kw))
            # a replayed turn arriving through the REAL scheduler would
            # hit record_turn — which must no-op while suspended
            assert j.record_turn(session, [{"knight": "k",
                                            "prompt_tokens": [9],
                                            "produced": [9]}]) is None

        n = replay_turns(j, "s", submit)
        assert n == 2
        assert len(calls) == 2
        # the exact committed token streams, 1-token budget
        assert calls[0][1] == [("k", [1, 2, 3])]
        assert calls[1][1] == [("k", [1, 2, 3, 4])]
        assert all(kw["max_new_tokens"] == 1 for _s, _t, kw in calls)
        # adapter-tinted rows replay under their adapter
        assert calls[1][2]["adapters_per_turn"] == ["persona-a"]
        assert "adapters_per_turn" not in calls[0][2]
        # nothing was double-journaled
        assert len(j.turns("s")) == 2

    @pytest.mark.supervision(allow_norestart=True)
    def test_sanitized_names_never_collide(self, tmp_path):
        j = SessionJournal(tmp_path)
        assert j.path_for("a/b") != j.path_for("a_b")

    @pytest.mark.supervision(allow_norestart=True)
    def test_journal_failure_degrades_not_fails(self, tmp_path):
        """A full disk costs durability, never availability."""
        j = SessionJournal(tmp_path)
        j.root = tmp_path / "nonexistent" / "deeper"  # unwritable path
        assert j.record_turn("s", [{"knight": "k", "prompt_tokens": [1],
                                    "produced": [2]}]) is None
        assert j.errors == 1


class TestJournalRecovery:
    @pytest.mark.supervision(allow_norestart=True)
    def test_scheduler_journals_committed_turns(self, tmp_path):
        """The scheduler's retire seam appends one fsynced record per
        committed round — knight names, prompt hash + tokens, produced
        ids, the serving engine."""
        j = SessionJournal(tmp_path)
        eng = make_engine()
        sched = SessionScheduler(eng, journal=j)
        try:
            t = BASE_PROMPTS[0]
            for _r in range(2):
                texts, _ = sched.submit("jrn", [("lancelot", t)],
                                        max_new_tokens=6, timeout_s=120)
                t = t + " " + texts[0]
            turns = j.turns("jrn")
            assert [rec["turn"] for rec in turns] == [0, 1]
            for rec in turns:
                row = rec["rows"][0]
                assert row["knight"] == "lancelot"
                assert len(row["prompt_tokens"]) > 0
                assert len(row["produced"]) > 0
                assert rec["engine"] == eng.cfg.name
            # round 2's prompt extends round 1's committed stream
            assert turns[1]["rows"][0]["prompt_tokens"][:len(
                turns[0]["rows"][0]["prompt_tokens"])] == \
                turns[0]["rows"][0]["prompt_tokens"]
            d = sched.describe()
            assert d["journal_turns"] == 2
            assert d["journal_errors"] == 0
        finally:
            sched.close()

    @pytest.mark.supervision(allow_norestart=True)
    def test_replay_resumes_at_exact_committed_turn(self, tmp_path):
        """In-process crash rehearsal: serve 2 journaled rounds, throw
        the process state away, replay onto a FRESH engine through
        resume_from_journal, and serve round 3 — byte-identical to the
        uninterrupted 3-round run, with the journal numbering
        continuing at the exact committed turn."""
        from theroundtaible_tpu.commands.serve import resume_from_journal

        # uninterrupted reference
        ref_eng = make_engine()
        ref_sched = SessionScheduler(ref_eng)
        try:
            ref, rerr = run_rounds(ref_sched, k=1, rounds=3, max_new=8,
                                   prefix="c")
            assert not rerr, rerr
        finally:
            ref_sched.close()

        # the "crashed" serve: 2 committed rounds, no clean shutdown
        j = SessionJournal(tmp_path)
        eng = make_engine()
        sched = SessionScheduler(eng, journal=j)
        try:
            crash, cerr = run_rounds(sched, k=1, rounds=2, max_new=8,
                                     prefix="c")
            assert not cerr, cerr
            assert crash["c0"] == ref["c0"][:2]
        finally:
            sched.close()  # the KV pool dies with the "process"
        del eng, sched

        # the resumed process: fresh engine, replay the journal
        eng2 = make_engine()
        sched2 = SessionScheduler(eng2)
        try:
            report = resume_from_journal(str(tmp_path), scheduler=sched2)
            assert report["sessions"] == 1
            assert report["turns"] == 2
            assert sched2.journal is not None  # keeps journaling
            # round 3 extends the REPLAYED KV — byte-identical to the
            # uninterrupted run's round 3
            t = (BASE_PROMPTS[0] + " Seat 0 speaks. "
                 + " ".join(ref["c0"][:2]))
            texts, _ = sched2.submit("c0", [("knight0", t)],
                                     max_new_tokens=8, timeout_s=120)
            assert texts[0] == ref["c0"][2], \
                "post-replay round diverged from the uninterrupted run"
            # the journal continued at the exact committed turn
            turns = j.turns("c0")
            assert [rec["turn"] for rec in turns] == [0, 1, 2]
        finally:
            sched2.close()

    @pytest.mark.slow
    @pytest.mark.supervision(allow_norestart=True)
    def test_kill9_serve_resumes_from_journal(self, tmp_path):
        """THE crash acceptance: a serving child process is kill -9'd
        mid-discussion; the parent replays its journal onto a fresh
        engine and resumes at the exact committed turn (the next round
        matches the uninterrupted reference run byte-for-byte)."""
        from theroundtaible_tpu.commands.serve import resume_from_journal

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        jdir = tmp_path / "journal"
        child_src = f"""
import os, sys
sys.path.insert(0, {repo!r})
os.environ.setdefault("ROUNDTABLE_DISABLE_TPU_DETECT", "1")
import jax
jax.config.update("jax_platforms", "cpu")
cache = {os.path.join(repo, ".pytest_xla_cache")!r}
if not os.environ.get("JAX_COMPILATION_CACHE_DIR") and os.path.isdir(cache):
    jax.config.update("jax_compilation_cache_dir", cache)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.scheduler import SessionScheduler
from theroundtaible_tpu.engine.session_journal import SessionJournal
eng = InferenceEngine.from_config({dict(CONFIG)!r})
sched = SessionScheduler(eng, journal=SessionJournal({str(jdir)!r}))
t = {BASE_PROMPTS[0] + " Seat 0 speaks."!r}
for r in range(50):
    texts, _ = sched.submit("c0", [("knight0", t)],
                            max_new_tokens=8, timeout_s=120)
    print("COMMITTED", r, flush=True)
    t = t + " " + texts[0]
"""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.Popen([sys.executable, "-c", child_src],
                                stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True,
                                env=env)
        try:
            committed = 0
            deadline = time.monotonic() + 420
            while committed < 2:
                assert time.monotonic() < deadline, \
                    "child never committed 2 rounds"
                line = proc.stdout.readline()
                if not line:
                    _out, err = proc.communicate(timeout=10)
                    raise AssertionError(
                        f"child died early:\n{err[-2000:]}")
                if line.startswith("COMMITTED"):
                    committed += 1
            os.kill(proc.pid, signal.SIGKILL)     # the actual kill -9
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
        assert proc.returncode == -signal.SIGKILL

        j = SessionJournal(jdir)
        last = j.last_turn("c0")
        assert last is not None and last >= 1, \
            "journal holds fewer turns than the child reported committed"
        n = last + 1

        # uninterrupted reference for n+1 rounds (greedy — identical to
        # what the child was serving)
        ref_eng = make_engine()
        ref_sched = SessionScheduler(ref_eng)
        try:
            ref, rerr = run_rounds(ref_sched, k=1, rounds=n + 1,
                                   max_new=8, prefix="c")
            assert not rerr, rerr
        finally:
            ref_sched.close()

        # resume: replay onto a fresh engine, then serve the NEXT round
        eng2 = make_engine()
        sched2 = SessionScheduler(eng2)
        try:
            report = resume_from_journal(str(jdir), scheduler=sched2)
            assert report["sessions"] == 1
            assert report["turns"] == n
            t = (BASE_PROMPTS[0] + " Seat 0 speaks. "
                 + " ".join(ref["c0"][:n]))
            texts, _ = sched2.submit("c0", [("knight0", t)],
                                     max_new_tokens=8, timeout_s=120)
            assert texts[0] == ref["c0"][n], \
                "resumed round diverged from the uninterrupted run"
            assert j.last_turn("c0") == n  # numbering continued exactly
        finally:
            sched2.close()
