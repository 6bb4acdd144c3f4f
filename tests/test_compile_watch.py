"""Compile observatory + steady-state recompile sentinel (ISSUE 6).

Units for engine/compile_watch.py: install modes, label attribution,
registry/flight-recorder publication, the steady-state sentinel's
count/dump/strict behaviors, and the enable_compilation_cache
decision-recording + memoization satellite.
"""

import glob
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from theroundtaible_tpu.engine import compile_watch
from theroundtaible_tpu.utils import telemetry

# Each forced compile uses a FRESH shape from this counter: jit caches
# per (function, shape), and the persistent test XLA cache would turn a
# repeated shape into silence (no backend compile, no retrieval for the
# in-process cache) — the observatory correctly sees nothing then.
_shape = [101]


def force_compile():
    _shape[0] += 1
    return jax.jit(lambda x: x * 2.5 + _shape[0])(
        jnp.ones((_shape[0],)))


@pytest.fixture(autouse=True)
def _installed(tmp_path, monkeypatch):
    monkeypatch.setenv("ROUNDTABLE_TELEMETRY_DIR", str(tmp_path))
    compile_watch.install()
    compile_watch.reset_steady_state()
    yield
    compile_watch.reset_steady_state()


def test_failed_registration_is_an_error(monkeypatch):
    """No silent "off": the installed JAX has jax.monitoring, so a
    registration that fails raises (ISSUE 22)."""
    import jax.monitoring as monitoring

    def refuse(_listener):
        raise RuntimeError("no listeners here")

    monkeypatch.setattr(compile_watch, "_installed_mode", None)
    monkeypatch.setattr(
        monitoring, "register_event_duration_secs_listener", refuse)
    with pytest.raises(RuntimeError, match="no listeners"):
        compile_watch.install()
    assert compile_watch._installed_mode is None


@pytest.mark.perf_obs
class TestObservatory:
    def test_install_idempotent_and_mode(self):
        mode = compile_watch.install()
        assert mode == "monitoring"
        # Second install must not double-register listeners: two
        # installs then one compile must count each event once.
        assert compile_watch.install() == mode
        c0 = compile_watch.compiles_seen()
        force_compile()
        delta = compile_watch.compiles_seen() - c0
        assert delta >= 1
        c1 = compile_watch.compiles_seen()
        force_compile()
        # Same op pattern: a double-registered listener would see ~2x.
        assert compile_watch.compiles_seen() - c1 <= delta + 1

    def test_cache_hit_counts_once(self):
        """jax 0.9.0 times the whole compile-or-get-cached call as
        backend_compile_duration, so a persistent-cache hit fires the
        retrieval event AND, enclosing it, the compile event: one
        compile, recorded as a hit (measured on the v5e, PR 22: 30 hits
        had counted as 60 compiles)."""
        c0 = compile_watch.compiles_seen()
        with compile_watch.label("unit[hit]"):
            compile_watch._on_duration(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.1)
            compile_watch._on_duration(
                "/jax/core/compile/backend_compile_duration", 0.2)
        assert compile_watch.compiles_seen() - c0 == 1
        mine = [e for e in compile_watch.history()
                if e["label"] == "unit[hit]"]
        assert [e["cache_hit"] for e in mine[-1:]] == [True]
        # The mark is spent: a fresh compile right after still counts.
        compile_watch._on_duration(
            "/jax/core/compile/backend_compile_duration", 0.3)
        assert compile_watch.compiles_seen() - c0 == 2

    def test_label_attribution_and_registry(self):
        c0 = telemetry.REGISTRY.counter_total(
            "roundtable_compiles_total", label="unit[labeled]")
        with compile_watch.label("unit[labeled]", engine="t"):
            force_compile()
        assert telemetry.REGISTRY.counter_total(
            "roundtable_compiles_total", label="unit[labeled]") > c0
        recent = [e for e in compile_watch.history()
                  if e["label"] == "unit[labeled]"]
        assert recent and recent[-1]["engine"] == "t"
        assert recent[-1]["steady_state"] is False
        # ...and the flight-recorder ring carries the compile event.
        kinds = [e for e in telemetry.recorder().events()
                 if e["kind"] == "compile"
                 and e.get("label") == "unit[labeled]"]
        assert kinds

    def test_unlabeled_compiles_record_as_unlabeled(self):
        c0 = telemetry.REGISTRY.counter_total(
            "roundtable_compiles_total", label="unlabeled")
        force_compile()
        assert telemetry.REGISTRY.counter_total(
            "roundtable_compiles_total", label="unlabeled") > c0


@pytest.mark.perf_obs
class TestSteadyStateSentinel:
    @staticmethod
    def compile_as(engine_name):
        """Force a compile inside an engine-attributed window — what
        the engines' dispatch seams produce; the sentinel keys on the
        window's engine attr (per-engine enforcement)."""
        with compile_watch.label("unit[seam]", engine=engine_name):
            force_compile()

    def test_pre_steady_compiles_are_not_violations(self):
        self.compile_as("unit-engine")
        assert compile_watch.steady_state_compiles() == 0

    def test_steady_compile_counts_and_dumps_once(self, tmp_path):
        compile_watch.warmup_complete("unit-engine")
        assert compile_watch.steady_state_labels() == ("unit-engine",)
        d0 = telemetry.REGISTRY.counter_total(
            "roundtable_flight_dumps_total",
            trigger="steady_state_compile")
        self.compile_as("unit-engine")
        self.compile_as("unit-engine")
        assert compile_watch.steady_state_compiles() >= 2
        assert telemetry.counter_total(
            "roundtable_steady_state_compiles_total") >= 2
        # ONE postmortem per steady period, not one per violation.
        assert telemetry.REGISTRY.counter_total(
            "roundtable_flight_dumps_total",
            trigger="steady_state_compile") == d0 + 1
        assert glob.glob(
            str(tmp_path / "flight-steady_state_compile-*.json"))

    def test_dump_once_is_per_engine(self):
        """Engine B's first violation still ships its postmortem after
        engine A already dumped — dumped-state is per label, not
        process-global."""
        compile_watch.warmup_complete("engine-a")
        compile_watch.warmup_complete("engine-b")
        d0 = telemetry.REGISTRY.counter_total(
            "roundtable_flight_dumps_total",
            trigger="steady_state_compile")
        self.compile_as("engine-a")
        self.compile_as("engine-a")
        self.compile_as("engine-b")
        assert telemetry.REGISTRY.counter_total(
            "roundtable_flight_dumps_total",
            trigger="steady_state_compile") == d0 + 2

    def test_enforcement_is_per_engine(self, monkeypatch):
        """A multi-engine process (warmup_cmd loops adapters): engine
        A's declaration must not classify engine B's construction and
        warmup compiles — or unattributed eager compiles — as
        violations."""
        monkeypatch.setenv(compile_watch.STRICT_ENV, "1")
        compile_watch.warmup_complete("engine-a")
        self.compile_as("engine-b")   # another engine, still warming
        force_compile()               # unattributed (construction)
        assert compile_watch.steady_state_compiles() == 0
        with pytest.raises(compile_watch.RecompileInSteadyState):
            self.compile_as("engine-a")

    def test_strict_mode_raises_loud(self, monkeypatch):
        compile_watch.warmup_complete("unit-engine")
        monkeypatch.setenv(compile_watch.STRICT_ENV, "1")
        with pytest.raises(compile_watch.RecompileInSteadyState,
                           match="no-mid-serve-recompile"):
            self.compile_as("unit-engine")
        # Leaving steady state ends enforcement.
        compile_watch.reset_steady_state()
        self.compile_as("unit-engine")

    def test_reopen_warmup_reenters_warm_phase(self, monkeypatch):
        compile_watch.warmup_complete("eng-a")
        compile_watch.warmup_complete("eng-b")
        compile_watch.reopen_warmup("eng-a")
        assert compile_watch.steady_state_labels() == ("eng-b",)
        compile_watch.reopen_warmup("eng-b")
        # Fully reopened: compiles are expected again, even STRICT.
        monkeypatch.setenv(compile_watch.STRICT_ENV, "1")
        self.compile_as("eng-a")
        self.compile_as("eng-b")
        assert compile_watch.steady_state_compiles() == 0

    def test_strict_unarmed_does_not_raise(self, monkeypatch):
        monkeypatch.delenv(compile_watch.STRICT_ENV, raising=False)
        compile_watch.warmup_complete("unit-engine")
        self.compile_as("unit-engine")  # counted, dumped, NOT raised
        assert compile_watch.steady_state_compiles() >= 1


class TestCompilationCacheDecision:
    """ISSUE 6 satellite: enable_compilation_cache records its decision
    once and memoizes the CPU no-op (it used to re-probe the backend
    on every call)."""

    def test_cpu_decision_recorded_and_memoized(self, monkeypatch):
        from theroundtaible_tpu import engine as engine_pkg

        assert engine_pkg.enable_compilation_cache() is None
        d = engine_pkg.get_compile_cache_decision()
        assert d == {"enabled": False, "backend": "cpu", "dir": None,
                     "reason": d["reason"]}
        assert "cpu" in d["reason"]
        # Recorded ONCE per process by design — a registry.reset() in
        # an earlier test legitimately wipes the gauge, so only its
        # value (when present) is pinned, not its presence.
        assert telemetry.REGISTRY.gauge_value(
            "roundtable_compile_cache_enabled") in (0.0, None)
        # Memoized: a repeat call must not touch the backend again.
        monkeypatch.setattr(
            jax, "default_backend",
            lambda: (_ for _ in ()).throw(AssertionError("re-probed")))
        assert engine_pkg.enable_compilation_cache() is None

    @pytest.mark.parametrize("env_dir", [True, False],
                             ids=["env-set", "env-unset"])
    def test_directory_placed_from_outside(self, monkeypatch, tmp_path,
                                           env_dir):
        """On an accelerator backend: JAX_COMPILATION_CACHE_DIR set →
        the function sets NO directory (JAX already reads the variable);
        unset → the fixed <checkout>/.xla_cache."""
        from theroundtaible_tpu import engine as engine_pkg

        monkeypatch.setattr(engine_pkg, "_compile_cache_decision", None)
        monkeypatch.setattr(engine_pkg, "_CHECKOUT", str(tmp_path))
        # The process's real (CPU) decision keeps its gauge.
        monkeypatch.setattr(engine_pkg, "_record_cache_decision",
                            lambda: None)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        updates = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.__setitem__(k, v))
        outside = str(tmp_path / "outside")
        if env_dir:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        got = engine_pkg.enable_compilation_cache()
        if env_dir:
            assert got == outside
            assert "jax_compilation_cache_dir" not in updates
        else:
            assert got == str(tmp_path / ".xla_cache")
            assert updates["jax_compilation_cache_dir"] == got
            assert os.path.isdir(got)
        assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
        assert updates["jax_persistent_cache_min_entry_size_bytes"] == 0
        assert engine_pkg.get_compile_cache_decision() == {
            "enabled": True, "backend": "tpu", "dir": got}

    def test_fixed_path_is_the_checkout(self):
        from theroundtaible_tpu import engine as engine_pkg

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert engine_pkg._CHECKOUT == repo

    def test_decision_lands_in_describe(self):
        from theroundtaible_tpu.engine.engine import InferenceEngine
        from theroundtaible_tpu.engine.models.registry import \
            get_model_config

        eng = InferenceEngine(get_model_config("tiny-gemma",
                                               max_seq_len=256),
                              num_slots=2)
        info = eng.describe()
        assert info["compile_cache"]["backend"] == "cpu"
        assert info["compile_observatory"]["mode"] == "monitoring"
        assert info["perf"]["param_bytes"] > 0


# --- a warmed engine serves a scheduled round without a compile ---------


@pytest.mark.parametrize("model", ["gemma", "jamba"])
def test_after_warmup_a_scheduled_round_compiles_nothing(model):
    """`engine.warmup()` calls the step programs with the argument kinds
    serving uses (ISSUE 53): the packed buffer as a numpy array, the
    state a decode segment carries and the key as arrays placed the way
    the programs return them. So two scheduled rounds — a join, first
    segments and the pipelined segments issued from their device
    outputs, greedy and sampled — meet no signature the warm-up has
    not. Before, a pipelined decode segment's committed arguments were
    one the warm-up never met: a second compile of `decode[b=4,paged]`
    in the first round served (on the chip, Jamba: 8 s a bucket)."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import discussion_play as dp

    # The engine's own sampling is sampled, so that warm-up warms both
    # modes (the mode is a static argument of every program).
    eng, knights, cue = dp.build(model, temperature=0.7)
    eng.warmup(max_prompt_tokens=256, batch_sizes=(1, 3, 4))
    for mode in dp.MODES:
        seen, n0 = (compile_watch.compiles_seen(),
                    len(compile_watch.history()))
        dp.play(eng, knights, cue, mode)
        assert compile_watch.compiles_seen() == seen, (
            mode, [e["label"] for e in compile_watch.history()[n0:]])
    d = eng.describe()["dispatch"]
    assert d["host_buffers"] == d["launches"] == d["programs"]
