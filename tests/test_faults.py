"""Fault-tolerance suite (ISSUE 1): injection registry, retry policy and
circuit-breaker units, plus the chaos tests that arm every injection
point and drive a 2-knight discussion end-to-end on the CPU backend —
asserting the DEGRADED path served (gather-view fallback, serial retry,
orchestrator adapter-fallback) instead of an unhandled crash.

ISSUE 2 extends the suite with the TIME ladder's chaos points: `hang`
(a wedged device wait the watchdog must classify within its rung
budget) and `slow_wait` (a slow-but-successful wait), driven through
the same adapter/orchestrator rungs.
"""

import time

import pytest

jax = pytest.importorskip("jax")

from theroundtaible_tpu.adapters.base import KnightTurn
from theroundtaible_tpu.adapters.tpu_llm import TpuLlmAdapter
from theroundtaible_tpu.core.errors import AdapterError
from theroundtaible_tpu.core.orchestrator import run_discussion
from theroundtaible_tpu.core.types import (
    KnightConfig,
    RoundtableConfig,
    RulesConfig,
)
from theroundtaible_tpu.engine import deadlines, faults, get_engine, \
    reset_engines
from theroundtaible_tpu.engine.engine import GenStats
from theroundtaible_tpu.engine.faults import (
    CircuitBreaker,
    FaultInjected,
    RetryPolicy,
)

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def clean_faults():
    faults.disarm()
    deadlines.reset_rungs()
    deadlines.disarm_watchdog()
    deadlines.clear_hang_log()
    yield
    faults.disarm()
    deadlines.reset_rungs()
    deadlines.disarm_watchdog()
    deadlines.clear_hang_log()


@pytest.fixture(autouse=True, scope="module")
def clean_engines():
    reset_engines()
    yield
    reset_engines()


# --- injection registry units ---


class TestFaultRegistry:
    def test_unarmed_by_default(self):
        assert faults.ARMED is False
        # unarmed maybe_inject is a no-op even when called directly
        faults.maybe_inject("dispatch")

    def test_arm_fire_exhaust(self):
        spec = faults.arm("dispatch", count=2)
        assert faults.ARMED is True
        for _ in range(2):
            with pytest.raises(FaultInjected) as e:
                faults.maybe_inject("dispatch")
            assert e.value.point == "dispatch"
        # exhausted: disarms itself and the module flag recomputes
        faults.maybe_inject("dispatch")
        assert spec.fired == 2
        assert faults.ARMED is False

    def test_unlimited_count(self):
        faults.arm("hbm_oom", count=-1)
        for _ in range(3):
            with pytest.raises(FaultInjected):
                faults.maybe_inject("hbm_oom")
        assert faults.ARMED is True
        faults.disarm("hbm_oom")
        assert faults.ARMED is False

    def test_unknown_point_rejected(self):
        with pytest.raises(ValueError, match="unknown fault point"):
            faults.arm("nonsense")

    def test_slow_dispatch_sleeps_instead_of_raising(self):
        faults.arm("slow_dispatch", count=1, delay_s=0.05)
        t0 = time.monotonic()
        faults.maybe_inject("slow_dispatch")   # must NOT raise
        assert time.monotonic() - t0 >= 0.05

    def test_env_arming(self, monkeypatch):
        monkeypatch.setenv("ROUNDTABLE_FAULTS",
                           "dispatch:2, slow_dispatch:1@0.5")
        faults._arm_from_env()
        assert faults.spec_for("dispatch").count == 2
        assert faults.spec_for("slow_dispatch").delay_s == 0.5
        assert faults.ARMED is True

    def test_env_malformed_entry_warns_not_crashes(self, monkeypatch):
        """The chaos knob must never itself take serving down: bad
        entries are skipped with a warning, not an import-time crash."""
        monkeypatch.setenv("ROUNDTABLE_FAULTS", "dispach:2,dispatch:oops")
        with pytest.warns(UserWarning,
                          match="malformed ROUNDTABLE_FAULTS") as rec:
            faults._arm_from_env()
        assert faults.ARMED is False
        # the warning names the ORIGINAL entry, not a stripped fragment
        assert any("'dispatch:oops'" in str(w.message) for w in rec)

    def test_injected_messages_classify_as_their_real_kind(self):
        from theroundtaible_tpu.core.errors import classify_error
        faults.arm("hbm_oom")
        with pytest.raises(FaultInjected) as e:
            faults.maybe_inject("hbm_oom")
        assert classify_error(e.value) == "oom"

    def test_arming_hang_arms_the_watchdog(self):
        """ROUNDTABLE_FAULTS=hang is a one-variable chaos run: arming
        the time-ladder points flips deadlines.ACTIVE too."""
        assert deadlines.ACTIVE is False
        faults.arm("hang", count=1, delay_s=0.1)
        assert deadlines.ACTIVE is True
        faults.disarm()
        deadlines.disarm_watchdog()
        faults.arm("slow_wait", count=1, delay_s=0.01)
        assert deadlines.ACTIVE is True

    def test_watchdog_disarms_when_time_points_exhaust(self):
        """Symmetric teardown: when the chaos run that AUTO-armed the
        watchdog ends (points exhausted or disarmed), the watchdog
        disarms too — no lingering per-wait worker threads on a healthy
        hot path. An explicitly armed watchdog is never torn down from
        here."""
        assert deadlines.ACTIVE is False
        faults.arm("hang", count=1, delay_s=0.01)
        assert deadlines.ACTIVE is True
        with pytest.raises(FaultInjected):
            faults.maybe_inject("hang")
        assert deadlines.ACTIVE is False      # exhausted ⇒ torn down
        deadlines.arm_watchdog()              # operator's explicit arm
        faults.arm("slow_wait", count=1)
        faults.disarm()
        assert deadlines.ACTIVE is True       # explicit arm survives

    def test_hang_env_arming(self, monkeypatch):
        monkeypatch.setenv("ROUNDTABLE_FAULTS", "hang:1@0.2")
        faults._arm_from_env()
        assert faults.spec_for("hang").delay_s == 0.2
        assert deadlines.ACTIVE is True

    def test_hang_message_classifies_as_hang(self):
        from theroundtaible_tpu.core.errors import classify_error
        faults.arm("hang", count=1, delay_s=0.01)
        with pytest.raises(FaultInjected) as e:
            faults.maybe_inject("hang")
        assert classify_error(e.value) == "hang"

    def test_kernel_failure_classification(self):
        assert faults.is_kernel_failure(
            FaultInjected("x", "mosaic_compile"))
        assert not faults.is_kernel_failure(FaultInjected("x", "dispatch"))
        assert faults.is_kernel_failure(
            RuntimeError("Mosaic lowering failed: scratch exceeds VMEM"))
        assert not faults.is_kernel_failure(RuntimeError("plain error"))


# --- retry policy units ---


class TestRetryPolicy:
    def test_retries_transient_then_succeeds(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("transient device dispatch failure")
            return "ok"

        assert RetryPolicy(max_retries=1, backoff_s=0.0).run(flaky) == "ok"
        assert len(calls) == 2

    def test_gives_up_after_max_retries(self):
        calls = []

        def always():
            calls.append(1)
            raise RuntimeError("still broken")

        with pytest.raises(RuntimeError, match="still broken"):
            RetryPolicy(max_retries=2, backoff_s=0.0).run(always)
        assert len(calls) == 3  # 1 initial + 2 retries

    def test_non_retryable_kinds_surface_immediately(self):
        policy = RetryPolicy(max_retries=3, backoff_s=0.0)
        for msg in ("RESOURCE_EXHAUSTED: out of HBM", "request timed out"):
            calls = []

            def fail(msg=msg):
                calls.append(1)
                raise RuntimeError(msg)

            with pytest.raises(RuntimeError):
                policy.run(fail)
            assert len(calls) == 1  # no blind retry of oom/timeout

    def test_backoff_schedule(self):
        policy = RetryPolicy(max_retries=3, backoff_s=0.05, backoff_mult=2.0)
        assert policy.backoff(0) == pytest.approx(0.05)
        assert policy.backoff(1) == pytest.approx(0.10)
        assert policy.backoff(2) == pytest.approx(0.20)

    def test_deadline_stops_retries(self):
        calls = []

        def always():
            calls.append(1)
            raise RuntimeError("transient")

        with pytest.raises(RuntimeError):
            RetryPolicy(max_retries=5, backoff_s=0.0).run(
                always, deadline=time.monotonic() - 1.0)
        assert len(calls) == 1

    def test_deleted_array_not_retried_in_place(self):
        """A donated-then-failed dispatch leaves its buffers deleted, so
        an identical re-dispatch dies on the same dead arrays — the
        policy surfaces it straight to the adapter rung (revive +
        re-prefill) instead of burning a blind retry."""
        calls = []

        def dead():
            calls.append(1)
            raise RuntimeError("Array has been deleted.")

        with pytest.raises(RuntimeError, match="deleted"):
            RetryPolicy(max_retries=3, backoff_s=0.0).run(dead)
        assert len(calls) == 1

    def test_on_retry_callback(self):
        seen = []

        def flaky():
            if not seen:
                raise RuntimeError("transient")
            return "ok"

        RetryPolicy(max_retries=1, backoff_s=0.0).run(
            flaky, on_retry=lambda attempt, e: seen.append((attempt, str(e))))
        assert seen == [(0, "transient")]


# --- circuit breaker units ---


class TestCircuitBreaker:
    def test_trips_after_threshold(self):
        b = CircuitBreaker(threshold=3)
        for _ in range(2):
            b.record_failure(RuntimeError("boom"))
            assert not b.is_open
        b.record_failure(RuntimeError("boom"))
        assert b.is_open
        assert "3 consecutive" in b.reason
        assert "boom" in b.reason

    def test_success_resets_consecutive_count(self):
        b = CircuitBreaker(threshold=2)
        b.record_failure()
        b.record_success()
        b.record_failure()
        assert not b.is_open          # never 2 consecutive
        assert b.total_failures == 2  # history kept for snapshots

    def test_thread_safe_counting(self):
        """The breaker is shared across adapters whose batch groups
        dispatch from a thread pool: concurrent counting must not lose
        increments (the counters are lock-guarded)."""
        import threading as th
        b = CircuitBreaker(threshold=10_000)

        def hammer():
            for _ in range(1000):
                b.record_failure()

        threads = [th.Thread(target=hammer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert b.failures == 8000
        assert b.total_failures == 8000

    def test_reason_none_while_closed(self):
        assert CircuitBreaker(threshold=1).reason is None

    def test_snapshot(self):
        b = CircuitBreaker(threshold=1, name="eng")
        b.record_failure(RuntimeError("sick"))
        snap = b.snapshot()
        assert snap["name"] == "eng" and snap["open"] is True
        assert snap["failures"] == 1 and snap["last_error"] == "sick"


# --- adapter breaker integration, no engine build ---


class _FakeEngine:
    """Stands in for InferenceEngine in pure-unit adapter tests."""

    class cfg:
        name = "fake-engine"

    max_seq_len = 512

    def __init__(self, fail=False):
        self.fail = fail
        self.calls = 0

    class kv:
        @staticmethod
        def release(name):
            pass

    def generate_batch_with_stats(self, turns, **kwargs):
        self.calls += 1
        if self.fail:
            raise RuntimeError("injected engine failure")
        return ["resp" for _ in turns], GenStats()


def _unit_adapter(model_tag, fail=True, threshold=2):
    """Adapter over a fake engine — get_breaker keys on the config, so a
    unique model tag isolates each test's breaker."""
    a = TpuLlmAdapter("knight", {"model": model_tag,
                                 "breaker_threshold": threshold})
    a._engine = _FakeEngine(fail=fail)
    return a


class TestAdapterBreaker:
    def test_is_available_flips_after_k_failures(self):
        a = _unit_adapter("unit-breaker-flip", threshold=2)
        assert a.is_available()
        for _ in range(2):
            with pytest.raises(AdapterError):
                a.execute("prompt")
        assert not a.is_available()
        assert "circuit open" in a.unavailable_reason()

    def test_open_breaker_fails_fast_without_dispatch(self):
        a = _unit_adapter("unit-breaker-fast", threshold=1)
        with pytest.raises(AdapterError):
            a.execute("prompt")
        dispatches = a._engine.calls
        with pytest.raises(AdapterError, match="circuit open"):
            a.execute("prompt")
        assert a._engine.calls == dispatches  # no new device dispatch

    def test_half_open_probe_recloses_breaker(self):
        """An open breaker is not a process-lifetime blacklist: every
        `threshold` fast-failed calls admits one probe dispatch, and a
        recovered engine closes the breaker on the probe's success."""
        a = _unit_adapter("unit-breaker-probe", threshold=1)
        with pytest.raises(AdapterError):
            a.execute("p")                      # opens the breaker
        a._engine.fail = False                  # engine recovers
        with pytest.raises(AdapterError, match="circuit open"):
            a.execute("p")                      # fast-fail, no probe yet
        assert a.execute("p") == "resp"         # probe admitted, closes
        assert a.is_available()
        assert a.breaker().failures == 0

    def test_success_closes_and_reset_reopens_service(self):
        a = _unit_adapter("unit-breaker-heal", threshold=3)
        with pytest.raises(AdapterError):
            a.execute("prompt")
        a._engine.fail = False
        assert a.execute("prompt") == "resp"
        assert a.breaker().failures == 0
        assert a.is_available()

    def test_breaker_shared_across_adapters_of_one_engine(self):
        a1 = _unit_adapter("unit-breaker-shared", threshold=1)
        a2 = _unit_adapter("unit-breaker-shared", threshold=1)
        with pytest.raises(AdapterError):
            a1.execute("prompt")
        # same engine config key ⇒ same breaker ⇒ a2 sees the sickness
        assert not a2.is_available()

    def test_fleet_health_rollup(self):
        from theroundtaible_tpu.engine.fleet import fleet_health
        a = _unit_adapter("unit-breaker-fleet", threshold=1)
        with pytest.raises(AdapterError):
            a.execute("prompt")
        health = fleet_health()
        assert health["open"] >= 1
        assert any(s["open"] for s in health["engines"])

    def test_construction_failure_opens_breaker(self):
        """A checkpoint that won't load is permanently sick: one
        construction failure must OPEN the breaker (fleet_health
        'open'), not leave it eternally one-failure 'degraded'."""
        a = TpuLlmAdapter("knight", {"model": "no-such-model-xyz"})
        assert not a.is_available()
        assert a.breaker().is_open
        assert a.unavailable_reason() is not None

    def test_threshold_mismatch_warns_first_caller_wins(self):
        from theroundtaible_tpu.engine import get_breaker
        cfg = {"model": "unit-breaker-threshold"}
        first = get_breaker(dict(cfg, breaker_threshold=5))
        assert first.threshold == 5
        with pytest.warns(UserWarning, match="first caller wins"):
            second = get_breaker(dict(cfg, breaker_threshold=1))
        assert second is first and second.threshold == 5

    def test_serial_retry_respects_round_deadline(self):
        """A timed-out batch must not buy N fresh per-knight timeouts:
        the serial rung shares the ROUND's deadline, surfaces a
        timeout-kind failure once it has passed — and does so BEFORE
        invalidating the knights' cached KV slots (no time to retry ⇒
        nothing gained by wiping them)."""
        import types
        a = _unit_adapter("unit-deadline", fail=True, threshold=99)
        orig = a._engine.generate_batch_with_stats
        released = []
        a._engine.kv = types.SimpleNamespace(release=released.append)

        def slow_fail(turns, **kw):
            time.sleep(0.03)
            return orig(turns, **kw)    # raises (fail=True)

        a._engine.generate_batch_with_stats = slow_fail
        with pytest.raises(AdapterError, match="deadline passed") as e:
            a.execute_round([KnightTurn("Sage", "p"),
                             KnightTurn("Oracle", "p")],
                            timeout_ms=10)
        assert e.value.kind == "timeout"
        assert released == []   # cached conversation KV survives

    def test_single_turn_failure_revives_dead_kv(self):
        """A failed SINGLE-turn round never reaches _serial_retry's
        revive, so execute_round itself must revive donation-killed KV
        buffers — else the breaker's half-open probes die on 'Array has
        been deleted' for the process lifetime."""
        a = _unit_adapter("unit-single-revive", fail=True, threshold=99)
        revived = []
        a._engine.revive_kv_if_dead = lambda: revived.append(1) or True
        with pytest.raises(AdapterError):
            a.execute("prompt")
        assert revived  # engine left with live buffers for the next call

    def test_execute_for_keys_slot_and_sampling_by_knight(self):
        """A knight degraded off the batched path onto serial turns
        (orchestrator execute_with_fallback) must keep its OWN KV slot
        and per-knight sampling — not collide on the adapter's name."""
        from theroundtaible_tpu.engine.sampling import SamplingParams
        a = _unit_adapter("unit-execute-for", fail=False, threshold=99)
        a.engine_config["knight_sampling"] = {
            "Sage": {"temperature": 0.7, "max_new_tokens": 4}}
        a._engine.sampling = SamplingParams()
        seen = []
        orig = a._engine.generate_batch_with_stats

        def capture(named_prompts, **kw):
            seen.append((named_prompts, kw))
            return orig(named_prompts, **kw)

        a._engine.generate_batch_with_stats = capture
        assert a.execute_for("Sage", "prompt") == "resp"
        named_prompts, kw = seen[0]
        assert named_prompts[0][0] == "Sage"   # knight's slot, not "knight"
        assert kw["sampling_per_turn"][0].temperature == 0.7
        assert kw["max_new_tokens"] == 4

    def test_construction_retried_on_half_open_probe(self, monkeypatch):
        """A memoized construction failure must not outlive the fault:
        the breaker's half-open probe admits a fresh construction
        attempt, and the SAME admitted call dispatches and closes the
        breaker — one probe re-seats the knights."""
        import theroundtaible_tpu.engine as eng
        healthy = _FakeEngine(fail=False)
        attempts = []

        def flaky_get_engine(cfg):
            attempts.append(1)
            if len(attempts) == 1:
                raise RuntimeError("transient OOM while loading ckpt")
            return healthy

        monkeypatch.setattr(eng, "get_engine", flaky_get_engine)
        a = TpuLlmAdapter("knight", {"model": "unit-ctor-probe",
                                     "breaker_threshold": 1})
        with pytest.raises(AdapterError):
            a.execute("p")                      # construction fails, trips
        assert a.breaker().is_open
        with pytest.raises(AdapterError, match="circuit open"):
            a.execute("p")                      # fast-fail window
        assert a.execute("p") == "resp"         # probe rebuilds AND serves
        assert a._engine is healthy
        assert a.is_available()
        assert a.breaker().failures == 0

    def test_serial_retry_is_best_effort_per_knight(self):
        """One knight's pathology must not abandon the rest of the
        round: the serial rung keeps serving the remaining knights and
        the final error names only the knights that actually failed."""
        a = _unit_adapter("unit-best-effort", fail=False, threshold=99)
        calls = []

        def selective(named_prompts, **kw):
            calls.append([n for n, _ in named_prompts])
            if len(named_prompts) > 1:
                raise RuntimeError("batch blew up")
            if named_prompts[0][0] == "Sage":
                raise RuntimeError("Sage's slot is cursed")
            return ["resp"], GenStats()

        a._engine.generate_batch_with_stats = selective
        with pytest.warns(UserWarning, match="retrying 3 knight"):
            with pytest.raises(AdapterError,
                               match=r"knight\(s\) Sage") as e:
                a.execute_round([KnightTurn("Sage", "p"),
                                 KnightTurn("Oracle", "p"),
                                 KnightTurn("Mystic", "p")])
        assert "Oracle" not in str(e.value)
        assert calls[-1] == ["Mystic"]  # served after Sage's failure

    def test_known_unhealthy_is_nonconstructive(self):
        """The orchestrator's batch-grouping health check must not
        trigger lazy engine construction (it runs synchronously while
        forming groups) — only report already-known sickness."""
        a = TpuLlmAdapter("knight", {"model": "unit-known-unhealthy"})
        assert a.known_unhealthy() is False
        assert a._engine is None        # no lazy construction happened
        a.breaker().trip(RuntimeError("sick"))
        assert a.known_unhealthy() is True

    def test_fail_fast_kind_reflects_underlying_error(self):
        """The breaker fast-fail must carry the kind of the failure
        that opened it — an OOM-rooted outage shows the oom hint, not
        the generic backend-error one."""
        a = _unit_adapter("unit-fastfail-kind", fail=False, threshold=1)
        a.breaker().record_failure(
            RuntimeError("RESOURCE_EXHAUSTED: out of memory"))
        with pytest.raises(AdapterError, match="circuit open") as e:
            a.execute("prompt")
        assert e.value.kind == "oom"

    def test_fail_fast_clears_stale_stats(self):
        """The breaker fail-fast must honor 'a failed call leaves no
        stale stats': a status surface reading last_stats() after the
        fast-failed round must not see the previous round's numbers."""
        a = _unit_adapter("unit-breaker-stats", fail=False, threshold=1)
        assert a.execute("prompt") == "resp"
        assert a.last_stats() is not None
        a.breaker().record_failure(RuntimeError("sick"))
        with pytest.raises(AdapterError, match="circuit open"):
            a.execute("prompt")
        assert a.last_stats() is None
        assert a.last_degradation is None


# --- KV revive after donation death (unit, no engine build) ---


class TestKvRevive:
    def _model_cfg(self):
        from theroundtaible_tpu.engine.models.registry import \
            get_model_config
        return get_model_config("tiny-gemma", max_seq_len=64)

    def test_paged_revive_resets_pages(self):
        from theroundtaible_tpu.engine.paging import PagedKVCache
        kv = PagedKVCache(self._model_cfg(), 2, max_seq_len=64,
                          page_size=32)
        assert kv.revive_if_dead() is False
        for k, v in kv.pools:
            k.delete()
            v.delete()
        assert kv.revive_if_dead() is True
        assert not kv.pools[0][0].is_deleted()
        assert kv.slot_names() == []
        assert kv.pages_in_use() == 0


# --- chaos: engine-level degradation on the CPU backend ---


def _tpu_cfg(seed, **extra):
    cfg = {
        "model": "tiny-gemma", "max_seq_len": 512, "num_slots": 4,
        "kv_layout": "paged", "page_size": 32,
        "mesh": {"data": 1, "model": 1},   # 1-device ⇒ pool-direct on CPU
        "seed": seed,
        "sampling": {"temperature": 0.0, "max_new_tokens": 8},
    }
    cfg.update(extra)
    return cfg


def _discussion_config(tpu_cfg, fallback=None):
    return RoundtableConfig(
        version="1.0", project="t", language="en",
        knights=[KnightConfig(name="Sage", adapter="tpu-llm", priority=1,
                              fallback=fallback),
                 KnightConfig(name="Oracle", adapter="tpu-llm", priority=2,
                              fallback=fallback)],
        rules=RulesConfig(max_rounds=1, timeout_per_turn_seconds=600,
                          parallel_rounds=True),
        chronicle="chronicle.md",
        adapter_config={"tpu-llm": tpu_cfg, "fake": {"name": "Backup"}})


class TestEngineChaos:
    def test_mosaic_compile_degrades_to_gather_view(self):
        """Pool-direct kernel fails on chip → the engine permanently
        reroutes onto the layout-agnostic gather-view programs and the
        request in flight is re-dispatched, not crashed."""
        cfg = _tpu_cfg(seed=101)
        adapter = TpuLlmAdapter("Sage", cfg, timeout_ms=600_000)
        engine = get_engine(cfg)
        assert engine.paged_direct
        faults.arm("mosaic_compile", count=1)
        with pytest.warns(UserWarning, match="degraded to gather-view"):
            out = adapter.execute("tell me about fault tolerance")
        assert isinstance(out, str)
        assert engine.paged_direct is False
        assert "injected fault" in engine.paged_degraded_reason
        # degraded engine keeps serving (and no injection remains armed)
        assert isinstance(adapter.execute("and again"), str)
        assert adapter.breaker().failures == 0

    def test_transient_dispatch_failure_retried_in_place(self):
        cfg = _tpu_cfg(seed=102)
        adapter = TpuLlmAdapter("Sage", cfg, timeout_ms=600_000)
        spec = faults.arm("dispatch", count=1)
        out = adapter.execute("a question about retries")
        assert isinstance(out, str)
        assert spec.fired == 1                  # failed once, retry served
        assert adapter.last_degradation is None  # in-place, not degraded
        assert adapter.breaker().failures == 0

    def test_slow_dispatch_completes(self):
        cfg = _tpu_cfg(seed=102)
        adapter = TpuLlmAdapter("Sage", cfg, timeout_ms=600_000)
        spec = faults.arm("slow_dispatch", count=1, delay_s=0.05)
        assert isinstance(adapter.execute("a slow question"), str)
        assert spec.fired == 1

    def test_hbm_oom_surfaces_with_kind_and_breaker_count(self):
        """OOM is NOT blindly retried (the allocation would fail again):
        it surfaces as an oom-kind AdapterError and feeds the breaker."""
        cfg = _tpu_cfg(seed=103)
        adapter = TpuLlmAdapter("Sage", cfg, timeout_ms=600_000)
        faults.arm("hbm_oom", count=1)
        with pytest.raises(AdapterError) as e:
            adapter.execute("a doomed question")
        assert e.value.kind == "oom"
        assert adapter.breaker().failures == 1
        # next call (fault exhausted) serves and closes the breaker
        assert isinstance(adapter.execute("a healthy question"), str)
        assert adapter.breaker().failures == 0

    def test_donation_death_revives_and_serves_serially(self):
        """A dispatch failure that surfaces AFTER donate_argnums consumed
        the KV cache leaves deleted device arrays behind. The serial
        rung must reallocate (revive_kv_if_dead) and re-prefill from
        scratch — not die on the secondary 'Array has been deleted'
        error and blacklist the engine until process restart."""
        cfg = _tpu_cfg(seed=106)
        adapter = TpuLlmAdapter("tpu-llm", cfg, timeout_ms=600_000)
        engine = get_engine(cfg)
        outs = adapter.execute_round(         # warm: slots hold content
            [KnightTurn("Sage", "warm up"),
             KnightTurn("Oracle", "also warm up")])
        assert len(outs) == 2
        for k, v in engine.kv.pools:          # simulate donation death
            k.delete()
            v.delete()
        with pytest.warns(UserWarning, match="reallocated fresh pools"):
            outs = adapter.execute_round(
                [KnightTurn("Sage", "after the crash"),
                 KnightTurn("Oracle", "still here?")])
        assert len(outs) == 2 and all(isinstance(o, str) for o in outs)
        assert adapter.last_degradation == "serial_retry"
        assert not engine.kv.pools[0][0].is_deleted()
        assert adapter.breaker().failures == 0
        # and the revived engine keeps serving batched rounds
        assert isinstance(adapter.execute("fully recovered"), str)

    def test_hang_detected_and_classified_single_turn(self):
        """A wedged dispatch on a single-turn round: the watchdog
        abandons the wait within the dispatch rung budget (NOT the
        injected 8 s sleep), the error surfaces as a hang-kind
        AdapterError, and the breaker counts it."""
        cfg = _tpu_cfg(seed=121)
        adapter = TpuLlmAdapter("Sage", cfg, timeout_ms=600_000)
        adapter.execute("warm the engine first")   # compile outside rung caps
        deadlines.configure_rungs({"dispatch": 0.5})
        faults.arm("hang", count=1, delay_s=8.0)
        t0 = time.monotonic()
        with pytest.raises(AdapterError) as e:
            adapter.execute("a wedged question")
        assert time.monotonic() - t0 < 6.0    # watchdog, not the sleep
        assert e.value.kind == "hang"
        assert adapter.breaker().failures == 1
        assert deadlines.hang_log()
        # fault exhausted: the engine recovers (KV revived by the
        # adapter's failure path) and the breaker closes on success
        deadlines.reset_rungs()
        assert isinstance(adapter.execute("a healthy question"), str)
        assert adapter.breaker().failures == 0

    def test_hang_batch_degrades_to_serial_with_recorded_kind(self):
        """The 2-knight acceptance path at the adapter rung: a hung
        batched dispatch is detected within its rung budget, the round
        degrades to serial per-knight retry, serves, and records the
        hang classification it recovered from."""
        cfg = _tpu_cfg(seed=122)
        adapter = TpuLlmAdapter("tpu-llm", cfg, timeout_ms=600_000)
        adapter.execute_round([KnightTurn("Sage", "warm"),
                               KnightTurn("Oracle", "warm too")])
        # Warm the 1-row programs the serial rung will dispatch: a cold
        # compile inside a tight dispatch cap would itself read as a
        # hang (deliberate semantics — a wedged compile IS a hang — but
        # not what THIS test measures).
        adapter.execute_for("Sage", "warm the single-row path")
        deadlines.configure_rungs({"dispatch": 2.0})
        faults.arm("hang", count=1, delay_s=10.0)
        t0 = time.monotonic()
        with pytest.warns(UserWarning, match="retrying 2 knight"):
            outs = adapter.execute_round(
                [KnightTurn("Sage", "first prompt"),
                 KnightTurn("Oracle", "second prompt")])
        assert time.monotonic() - t0 < 9.0
        assert len(outs) == 2 and all(isinstance(o, str) for o in outs)
        assert adapter.last_degradation == "serial_retry"
        assert adapter.last_recovered_kind == "hang"
        assert adapter.last_stats()["recovered_from"] == "hang"
        assert deadlines.hang_log()[-1]["rung"] == "dispatch"
        assert adapter.breaker().failures == 0  # round ultimately served

    def test_slow_wait_within_budget_completes(self):
        """A slow-but-not-wedged wait finishes inside its rung budget:
        no hang classification, no degradation — the watchdog only
        bites waits that EXCEED the budget."""
        cfg = _tpu_cfg(seed=123)
        adapter = TpuLlmAdapter("Sage", cfg, timeout_ms=600_000)
        adapter.execute("warm")
        deadlines.configure_rungs({"dispatch": 5.0})
        spec = faults.arm("slow_wait", count=1, delay_s=0.05)
        assert isinstance(adapter.execute("a slow question"), str)
        assert spec.fired == 1
        assert deadlines.hang_log() == []
        assert adapter.last_degradation is None

    def test_kv_corrupt_batch_retries_serially(self):
        """Batched fan-out fails → the adapter invalidates the batch's
        KV slots and serves each knight as its own program (best-effort
        round instead of all-or-nothing)."""
        cfg = _tpu_cfg(seed=104)
        adapter = TpuLlmAdapter("tpu-llm", cfg, timeout_ms=600_000)
        faults.arm("kv_corrupt", count=1)
        with pytest.warns(UserWarning, match="retrying 2 knight"):
            outs = adapter.execute_round(
                [KnightTurn("Sage", "first prompt"),
                 KnightTurn("Oracle", "second prompt")])
        assert len(outs) == 2 and all(isinstance(o, str) for o in outs)
        assert adapter.last_degradation == "serial_retry"
        assert adapter.last_stats()["degraded"] == "serial_retry"
        assert adapter.breaker().failures == 0  # the round ultimately served


# --- chaos: every fault end-to-end through run_discussion ---


class TestDiscussionChaos:
    def _run(self, project_root, tpu_cfg, adapters=None, fallback=None):
        config = _discussion_config(tpu_cfg, fallback=fallback)
        if adapters is None:
            adapters = {"tpu-llm": TpuLlmAdapter("tpu-llm", tpu_cfg,
                                                 timeout_ms=600_000)}
        result = run_discussion("chaos topic", config, adapters,
                                str(project_root))
        return result, adapters

    def test_mosaic_compile_discussion_completes_degraded(self, project_root):
        cfg = _tpu_cfg(seed=111)
        get_engine(cfg)  # build before arming: injection is a SERVING fault
        faults.arm("mosaic_compile", count=1)
        with pytest.warns(UserWarning, match="degraded to gather-view"):
            result, _ = self._run(project_root, cfg)
        assert result.rounds == 1
        assert get_engine(cfg).paged_direct is False  # gather-view rung

    def test_dispatch_fault_discussion_completes(self, project_root):
        cfg = _tpu_cfg(seed=112)
        get_engine(cfg)
        spec = faults.arm("dispatch", count=1)
        result, _ = self._run(project_root, cfg)
        assert result.rounds == 1
        assert spec.fired == 1  # retry-in-place rung

    def test_timeout_fault_discussion_completes(self, project_root):
        cfg = _tpu_cfg(seed=112)
        get_engine(cfg)
        spec = faults.arm("slow_dispatch", count=1, delay_s=0.05)
        result, _ = self._run(project_root, cfg)
        assert result.rounds == 1
        assert spec.fired == 1

    def test_kv_corrupt_discussion_serves_serially(self, project_root):
        cfg = _tpu_cfg(seed=113)
        get_engine(cfg)
        faults.arm("kv_corrupt", count=1)
        adapter = TpuLlmAdapter("tpu-llm", cfg, timeout_ms=600_000)
        with pytest.warns(UserWarning, match="retrying 2 knight"):
            result, _ = self._run(project_root, cfg,
                                  adapters={"tpu-llm": adapter})
        assert result.rounds == 1
        assert adapter.last_degradation == "serial_retry"  # serial rung

    def test_hang_discussion_completes_with_recorded_classification(
            self, project_root):
        """ISSUE 2 acceptance: a `hang` fault injected (the
        ROUNDTABLE_FAULTS=hang path — env-style arming flips the
        watchdog on) during a 2-knight CPU run_discussion is detected
        by the watchdog within its rung budget, degrades through the
        existing ladder (serial retry), and the discussion completes
        with a recorded hang classification."""
        cfg = _tpu_cfg(seed=115)
        adapter = TpuLlmAdapter("tpu-llm", cfg, timeout_ms=600_000)
        # Warm both program shapes so the only slow wait is the fault.
        adapter.execute_round([KnightTurn("Sage", "warm"),
                               KnightTurn("Oracle", "warm too")])
        adapter.execute_for("Sage", "warm the single-row path")
        deadlines.configure_rungs({"dispatch": 2.0})
        # Same parse path as ROUNDTABLE_FAULTS="hang:1@10" (arm() is
        # what _arm_from_env calls; arming the point arms the watchdog).
        faults.arm("hang", count=1, delay_s=10.0)
        t0 = time.monotonic()
        with pytest.warns(UserWarning, match="retrying 2 knight"):
            result, _ = self._run(project_root, cfg,
                                  adapters={"tpu-llm": adapter})
        assert time.monotonic() - t0 < 30.0   # not the 10 s sleep x N
        assert result.rounds == 1
        assert len(result.all_rounds) == 2    # both knights spoke
        assert adapter.last_degradation == "serial_retry"
        assert adapter.last_recovered_kind == "hang"   # the record
        assert deadlines.hang_log()[-1]["rung"] == "dispatch"

    def test_persistent_oom_engages_adapter_fallback(self, project_root):
        """The last rung: the engine is terminally sick (unlimited OOM),
        the breaker opens, and the orchestrator's runtime-fallback path
        seats both knights on the configured fallback adapter — the
        discussion completes instead of crashing."""
        cfg = _tpu_cfg(seed=114, breaker_threshold=1)
        get_engine(cfg)
        faults.arm("hbm_oom", count=-1)
        adapter = TpuLlmAdapter("tpu-llm", cfg, timeout_ms=600_000)
        result, adapters = self._run(project_root, cfg,
                                     adapters={"tpu-llm": adapter},
                                     fallback="fake")
        assert result.rounds == 1
        assert adapter.breaker().is_open          # breaker rung tripped
        assert not adapter.is_available()
        # fallback rung engaged: both knights were seated on fakes and
        # their turns recorded, so the discussion continued
        fallbacks = [k for k in adapters if k.startswith("__fallback_")]
        assert set(fallbacks) == {"__fallback_Sage", "__fallback_Oracle"}
        assert result.consensus  # FakeAdapter default script scores 9

    def test_open_breaker_skips_batch_path_next_round(self, project_root):
        """A tripped breaker makes _batch_groups route the knights
        serially (where fallback engages) instead of re-dispatching the
        batch into a sick engine."""
        from theroundtaible_tpu.core.orchestrator import _batch_groups
        cfg = _tpu_cfg(seed=114, breaker_threshold=1)
        adapter = TpuLlmAdapter("tpu-llm", cfg, timeout_ms=600_000)
        adapter.breaker().record_failure(RuntimeError("sick"))
        assert adapter.breaker().is_open
        knights = _discussion_config(cfg).knights
        groups, serial = _batch_groups(knights, {"tpu-llm": adapter})
        assert groups == []
        assert [k.name for k in serial] == ["Sage", "Oracle"]
