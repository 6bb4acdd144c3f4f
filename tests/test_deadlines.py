"""Deadline & watchdog subsystem (ISSUE 2, engine/deadlines.py): the
hierarchical Budget tree, cooperative cancellation, the watchdog's hang
detection + stale-commit guard, the drain admission gate, and
fleet.drain()'s in-flight/flush semantics — plus the orchestrator's
discussion/round budget derivation."""

import threading
import time

import pytest

jax = pytest.importorskip("jax")

from theroundtaible_tpu.core.errors import classify_error, hint_for_kind
from theroundtaible_tpu.engine import deadlines, faults, fleet, get_engine, \
    reset_engines

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def clean_deadlines():
    deadlines.reset_rungs()
    deadlines.disarm_watchdog()
    deadlines.end_drain()
    deadlines.clear_hang_log()
    faults.disarm()
    yield
    deadlines.reset_rungs()
    deadlines.disarm_watchdog()
    deadlines.end_drain()
    deadlines.clear_hang_log()
    faults.disarm()


# --- Budget tree units ---


class TestBudgetTree:
    def test_child_deadline_never_exceeds_parent(self):
        root = deadlines.Budget.root(10.0, rung="discussion")
        loose = root.child("round", timeout_s=99.0)
        tight = root.child("round", timeout_s=1.0)
        assert loose.deadline <= root.deadline
        assert tight.deadline < loose.deadline
        turn = tight.child("turn")
        assert turn.deadline <= tight.deadline

    def test_unbounded_root(self):
        root = deadlines.Budget.root(None)
        assert root.remaining() == float("inf")
        assert not root.expired
        root.check()  # no raise
        # a bounded child under an unbounded root still bounds
        child = root.child("turn", timeout_s=0.0)
        assert child.expired

    def test_check_raises_budget_exceeded_with_rung(self):
        b = deadlines.Budget.root(0.0, rung="round")
        with pytest.raises(deadlines.BudgetExceeded) as e:
            b.check()
        assert e.value.rung == "round"
        # an exhausted budget classifies as timeout for the ladder
        assert classify_error(e.value) == "timeout"

    def test_split_shares_remaining_evenly(self):
        root = deadlines.Budget.root(9.0, rung="round")
        parts = root.split(3, "turn")
        assert len(parts) == 3
        for p in parts:
            assert p.remaining() <= 3.01
            assert p.deadline <= root.deadline

    def test_rung_caps_bound_children(self):
        deadlines.configure_rungs({"dispatch": 0.5})
        root = deadlines.Budget.root(100.0, rung="turn")
        d = root.child("dispatch")
        assert d.remaining() <= 0.51
        deadlines.configure_rungs({"dispatch": 0})  # remove
        assert deadlines.rung_cap("dispatch") is None

    def test_configure_rejects_unknown_rung(self):
        with pytest.raises(ValueError, match="unknown rung"):
            deadlines.configure_rungs({"nonsense": 1.0})

    def test_env_rung_parsing(self, monkeypatch):
        monkeypatch.setenv("ROUNDTABLE_RUNG_BUDGETS",
                           "dispatch:120, prefill:300")
        deadlines._configure_from_env()
        assert deadlines.rung_cap("dispatch") == 120.0
        assert deadlines.rung_cap("prefill") == 300.0

    def test_env_malformed_entry_warns_not_crashes(self, monkeypatch):
        monkeypatch.setenv("ROUNDTABLE_RUNG_BUDGETS", "dispatch:oops")
        with pytest.warns(UserWarning, match="malformed"):
            deadlines._configure_from_env()


class TestCancelToken:
    def test_parent_cancel_propagates_down_not_up(self):
        root = deadlines.Budget.root(10.0)
        child = root.child("round")
        grand = child.child("turn")
        child.token.cancel("round aborted")
        with pytest.raises(deadlines.Cancelled, match="round aborted"):
            grand.check()
        root.check()  # the parent is untouched
        root.token.cancel("all stop")
        with pytest.raises(deadlines.Cancelled):
            root.check()

    def test_child_created_after_cancel_is_born_cancelled(self):
        tok = deadlines.CancelToken()
        tok.cancel("late")
        assert tok.child().cancelled


# --- watchdog units ---


class TestWatchdog:
    def test_unarmed_is_inline_and_zero_thread(self):
        """Unarmed, watched_wait runs fn in the CALLING thread — the
        zero-overhead contract (no worker, no event, no timer)."""
        b = deadlines.Budget.root(10.0)
        seen = []
        deadlines.watched_wait(
            lambda: seen.append(threading.current_thread()), b)
        assert seen[0] is threading.current_thread()

    def test_armed_returns_value_and_propagates_errors(self):
        deadlines.arm_watchdog()
        b = deadlines.Budget.root(10.0)
        assert deadlines.watched_wait(lambda: 42, b) == 42
        with pytest.raises(ValueError, match="boom"):
            deadlines.watched_wait(
                lambda: (_ for _ in ()).throw(ValueError("boom")), b)

    def test_hang_detected_within_budget(self):
        deadlines.arm_watchdog()
        b = deadlines.Budget.root(0.1, rung="turn")
        t0 = time.monotonic()
        with pytest.raises(deadlines.HangDetected) as e:
            deadlines.watched_wait(lambda: time.sleep(5.0), b, "dispatch")
        assert time.monotonic() - t0 < 2.0   # did NOT wait out the sleep
        assert e.value.rung == "dispatch"
        assert classify_error(e.value) == "hang"
        assert hint_for_kind("hang")
        assert deadlines.hang_log()[-1]["rung"] == "dispatch"

    def test_hang_is_not_retried_in_place(self):
        """Hang joins timeout/oom in the no-blind-retry set: the wait
        already consumed its rung budget (and likely its donated
        buffers) — only the adapter rung's revive + re-prefill helps."""
        assert not faults.DEFAULT_RETRY.retryable(
            deadlines.HangDetected("dispatch", 1.0))

    def test_rung_cap_bounds_the_wait_below_budget(self):
        deadlines.arm_watchdog()
        deadlines.configure_rungs({"dispatch": 0.05})
        b = deadlines.Budget.root(60.0, rung="turn")
        t0 = time.monotonic()
        with pytest.raises(deadlines.HangDetected):
            deadlines.watched_wait(lambda: time.sleep(5.0), b, "dispatch")
        assert time.monotonic() - t0 < 2.0

    def test_commit_guard_discards_abandoned_results(self):
        """An abandoned worker that later completes must not commit:
        commit_guard raises StaleWait inside the worker thread, so the
        dispatch closure never mutates engine KV state."""
        deadlines.arm_watchdog()
        b = deadlines.Budget.root(0.05, rung="turn")
        committed = []
        finished = threading.Event()

        def slow_then_commit():
            time.sleep(0.3)
            try:
                with deadlines.commit_guard():
                    committed.append(True)
            finally:
                finished.set()

        with pytest.raises(deadlines.HangDetected):
            deadlines.watched_wait(slow_then_commit, b, "dispatch")
        assert finished.wait(5.0)
        assert committed == []   # StaleWait fired before the commit

    def test_commit_guard_serializes_against_abandon(self):
        """The abandon decision cannot interleave with an in-progress
        commit: the worker holds the ticket lock across guard+commit,
        so the caller's HangDetected (and the recovery that follows)
        only proceeds AFTER the commit completed — commit-then-revive,
        never revive-then-stale-commit."""
        deadlines.arm_watchdog()
        b = deadlines.Budget.root(0.05, rung="turn")
        order = []
        in_commit = threading.Event()

        def commit_slowly():
            with deadlines.commit_guard():   # guard passes pre-abandon
                in_commit.set()
                time.sleep(0.4)              # caller times out mid-commit
                order.append("commit")

        t0 = time.monotonic()
        with pytest.raises(deadlines.HangDetected):
            deadlines.watched_wait(commit_slowly, b, "dispatch")
        order.append("hang_raised")
        assert in_commit.is_set()
        # the caller blocked on the ticket lock until the commit landed
        assert order == ["commit", "hang_raised"]
        assert time.monotonic() - t0 >= 0.35

    def test_commit_guard_noop_outside_watched_waits(self):
        with deadlines.commit_guard():       # unarmed
            pass
        deadlines.arm_watchdog()
        with deadlines.commit_guard():       # armed, but not in a wait
            pass


# --- drain gate + fleet.drain ---


def _drain_cfg(seed):
    return {"model": "tiny-gemma", "max_seq_len": 256, "num_slots": 2,
            "attn": "dense", "page_size": 32, "seed": seed,
            "sampling": {"temperature": 0.0, "max_new_tokens": 8}}


class TestDrain:
    @pytest.fixture(autouse=True, scope="class")
    def clean_engines(self):
        reset_engines()
        yield
        reset_engines()

    def test_drain_flushes_slots_and_refuses_admission(self):
        eng = get_engine(_drain_cfg(201))
        eng.generate("warm the slot", slot_name="Sage", max_new_tokens=4)
        assert eng.kv.slot_names() == ["Sage"]
        report = fleet.drain(timeout_s=10.0)
        assert report["clean"]
        entry = next(e for e in report["engines"]
                     if e.get("flushed_slots") is not None)
        assert entry["flushed_slots"] >= 1
        assert entry["in_flight_drained"]
        assert eng.kv.slot_names() == []
        assert fleet.fleet_health()["draining"] is True
        # new admissions are refused while draining
        with pytest.raises(deadlines.DrainingError, match="not admitted"):
            eng.generate("refused", slot_name="Late", max_new_tokens=4)
        fleet.resume()
        assert fleet.fleet_health()["draining"] is False
        out = eng.generate("admitted again", slot_name="Sage",
                           max_new_tokens=4)
        assert isinstance(out, str)

    def test_drain_flushes_paged_engine_pages(self):
        """PagedKVCache is a standalone class (not a SlotBook subclass):
        drain's KV flush must release its slots through the paged
        release path — pages decref and free back to their replica
        ranges, not just slot records dropped."""
        cfg = _drain_cfg(202)
        eng = get_engine(cfg)
        eng.generate("warm the paged slot", slot_name="P",
                     max_new_tokens=4)
        assert eng.kv.slot_names() == ["P"]
        assert eng.kv.pages_in_use() > 0
        report = fleet.drain(timeout_s=10.0)
        fleet.resume()
        assert report["clean"]
        assert eng.kv.slot_names() == []
        assert eng.kv.pages_in_use() == 0    # pages actually freed

    def test_drain_waits_for_in_flight_turns(self):
        """In-flight turns complete while new admissions are refused:
        drain blocks on the serve lock (the in-flight proxy), a NEW call
        arriving mid-drain is refused, and once the in-flight work
        releases the lock the drain finishes clean."""
        eng = get_engine(_drain_cfg(201))
        eng._serve_lock.acquire()          # simulate an in-flight turn
        results = []
        t = threading.Thread(
            target=lambda: results.append(fleet.drain(timeout_s=15.0)))
        try:
            t.start()
            time.sleep(0.2)
            assert not results              # still waiting on in-flight
            # a turn arriving DURING the drain is refused at admission
            with pytest.raises(deadlines.DrainingError):
                eng.generate("late arrival", slot_name="L",
                             max_new_tokens=4)
        finally:
            eng._serve_lock.release()
        t.join(15.0)
        assert results and results[0]["clean"]
        fleet.resume()

    def test_drain_times_out_on_stuck_engine(self):
        eng = get_engine(_drain_cfg(201))
        eng._serve_lock.acquire()
        try:
            report = fleet.drain(timeout_s=0.2)
            assert report["clean"] is False
            stuck = [e for e in report["engines"]
                     if not e["in_flight_drained"]]
            assert stuck
        finally:
            eng._serve_lock.release()
            fleet.resume()


# --- orchestrator budget derivation ---


class TestDiscussionBudgets:
    def _config(self, **rules_kw):
        from theroundtaible_tpu.core.types import (KnightConfig,
                                                   RoundtableConfig,
                                                   RulesConfig)
        rules_kw.setdefault("max_rounds", 3)
        rules_kw.setdefault("consensus_threshold", 10)
        rules_kw.setdefault("timeout_per_turn_seconds", 60)
        return RoundtableConfig(
            version="1.0", project="t", language="en",
            knights=[KnightConfig(name="Sage", adapter="fake", priority=1),
                     KnightConfig(name="Oracle", adapter="fake",
                                  priority=2)],
            rules=RulesConfig(**rules_kw),
            chronicle="chronicle.md", adapter_config={"fake": {}})

    def test_exhausted_discussion_budget_returns_partial(self, project_root):
        """A discussion whose budget is already exhausted returns the
        escalated/partial result immediately instead of running rounds
        into a hard kill — 'window died silently' becomes 'partial
        results + named culprit'."""
        from theroundtaible_tpu.adapters.fake import FakeAdapter, \
            scripted_response
        from theroundtaible_tpu.core.orchestrator import Reporter, \
            run_discussion

        warnings_seen = []

        class R(Reporter):
            def verify_event(self, kind, message):
                warnings_seen.append((kind, message))

        fake = FakeAdapter("fake", script=[scripted_response(5)] * 12)
        result = run_discussion(
            "topic", self._config(discussion_budget_seconds=0.000001),
            {"fake": fake}, str(project_root), reporter=R())
        assert result.consensus is False
        assert result.all_rounds == []     # no round ran
        assert any("budget" in m for _k, m in warnings_seen)

    def test_rounds_run_inside_discussion_budget(self, project_root):
        from theroundtaible_tpu.adapters.fake import FakeAdapter, \
            scripted_response
        from theroundtaible_tpu.core.orchestrator import run_discussion

        fake = FakeAdapter("fake", script=[scripted_response(9)] * 4)
        result = run_discussion(
            "topic", self._config(discussion_budget_seconds=120.0,
                                  round_budget_seconds=60.0,
                                  max_rounds=1, consensus_threshold=9),
            {"fake": fake}, str(project_root))
        assert result.rounds == 1
        assert result.consensus

    def test_rules_budget_validation(self):
        from theroundtaible_tpu.core.config import validate_config_dict
        from theroundtaible_tpu.core.errors import ConfigError
        base = {
            "version": "1.0",
            "knights": [{"name": "A", "adapter": "fake",
                         "capabilities": [], "priority": 1}],
            "rules": {"max_rounds": 3, "consensus_threshold": 9,
                      "timeout_per_turn_seconds": 60},
            "adapter_config": {"fake": {}},
        }
        validate_config_dict(base)  # budgets optional
        bad = dict(base, rules=dict(base["rules"],
                                    discussion_budget_seconds=-5))
        with pytest.raises(ConfigError, match="positive"):
            validate_config_dict(bad)
        nested = dict(base, rules=dict(base["rules"],
                                       discussion_budget_seconds=10,
                                       round_budget_seconds=60))
        with pytest.raises(ConfigError, match="nest"):
            validate_config_dict(nested)

    def test_rules_roundtrip_omits_unset_budgets(self):
        from theroundtaible_tpu.core.types import RulesConfig
        d = RulesConfig().to_dict()
        assert "discussion_budget_seconds" not in d
        assert "round_budget_seconds" not in d
        r = RulesConfig.from_dict({"discussion_budget_seconds": 30})
        assert r.discussion_budget_seconds == 30.0
        assert "discussion_budget_seconds" in r.to_dict()
