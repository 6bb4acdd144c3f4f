"""Static-analysis suite (ISSUE 15): the `roundtable lint` AST rule
engine over its seeded-violation fixture corpus AND the live tree, the
allowlist mechanism (reasons required, suppression, staleness), the
device-free jaxpr audit (donation / callback / variant-count checks,
with a seeded static-arg leak proving the extra-jaxpr detection), the
error-kind classification table, and the supervisor gauge-hygiene
bugfix the RT-GAUGE-LEAK rule targets.

Everything runs under JAX_PLATFORMS=cpu with zero devices — tracing
never dispatches.
"""

from functools import partial
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from theroundtaible_tpu.analysis import run_lint, unallowlisted
from theroundtaible_tpu.analysis.astlint import (
    Allowlist,
    LintConfigError,
    ProjectIndex,
    run_rules,
)
from theroundtaible_tpu.analysis.jaxpr_audit import (
    ProgramSpec,
    Variant,
    audit_engine,
    audit_programs,
    collect_programs,
    donation_violations,
    find_callbacks,
)
from theroundtaible_tpu.analysis.rules import ALL_RULES, get_rules
from theroundtaible_tpu.utils import telemetry

pytestmark = pytest.mark.analysis

REPO_ROOT = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).resolve().parent / "fixtures" / "analysis"


@pytest.fixture(autouse=True)
def clean_registry():
    telemetry.REGISTRY.reset()
    yield
    telemetry.REGISTRY.reset()


def rule_findings(rule_id: str, root: Path):
    return run_rules(str(root), get_rules([rule_id]))


# --- fixture corpus: each rule catches its seeded violation and
# --- passes its clean twin ---


CASES = [
    ("RT-GAUGE-LEAK", "gauge_leak"),
    ("RT-LOCK-BUMP", "lock_bump"),
    ("RT-ERROR-KIND", "error_kind"),
    ("RT-SHAPE-VALUE", "shape_value"),
    ("RT-MARKER-REG", "marker_reg"),
    ("RT-ENV-DOC", "env_doc"),
    ("RT-SURFACE-DRIFT", "surface_drift"),
    ("RT-SPAN-LEAK", "span_leak"),
]


class TestFixtureCorpus:
    @pytest.mark.parametrize("rule_id,subdir", CASES,
                             ids=[c[0] for c in CASES])
    def test_bad_fixture_caught(self, rule_id, subdir):
        found = rule_findings(rule_id, FIXTURES / subdir / "bad")
        assert found, f"{rule_id} missed its seeded violation"
        assert all(f.rule == rule_id for f in found)
        assert all(f.line > 0 and f.path for f in found), \
            "findings must carry file/line"

    @pytest.mark.parametrize("rule_id,subdir", CASES,
                             ids=[c[0] for c in CASES])
    def test_good_fixture_clean(self, rule_id, subdir):
        found = rule_findings(rule_id, FIXTURES / subdir / "good")
        assert found == [], [f.render() for f in found]

    def test_env_doc_counts_both_read_forms(self):
        found = rule_findings("RT-ENV-DOC", FIXTURES / "env_doc" / "bad")
        names = {f.message.split()[2] for f in found}
        assert names == {"ROUNDTABLE_FIXTURE_SECRET",
                         "ROUNDTABLE_FIXTURE_ASSIGNED"}

    def test_unknown_rule_id_raises(self):
        with pytest.raises(ValueError, match="RT-TYPO"):
            get_rules(["RT-TYPO"])


# --- allowlist mechanism ---


class TestAllowlist:
    def _write(self, tmp_path, text):
        p = tmp_path / "allowlist.toml"
        p.write_text(text)
        return str(p)

    def test_entry_without_reason_is_config_error(self, tmp_path):
        path = self._write(tmp_path, '[[allow]]\nrule = "RT-GAUGE-LEAK"\n')
        with pytest.raises(LintConfigError, match="no reason"):
            Allowlist.load(path)

    def test_entry_suppresses_and_marks(self, tmp_path):
        path = self._write(
            tmp_path,
            '[[allow]]\nrule = "RT-GAUGE-LEAK"\npath = "*.py"\n'
            'reason = "fixture: bounded label domain"\n')
        found = run_rules(str(FIXTURES / "gauge_leak" / "bad"),
                          get_rules(["RT-GAUGE-LEAK"]),
                          allowlist=Allowlist.load(path))
        assert found and all(f.allowed for f in found)
        assert found[0].allow_reason.startswith("fixture:")
        assert unallowlisted(found) == []

    def test_stale_entry_reported(self, tmp_path):
        path = self._write(
            tmp_path,
            '[[allow]]\nrule = "RT-GAUGE-LEAK"\n'
            'match = "no_such_series_anywhere"\n'
            'reason = "suppresses nothing"\n')
        found = run_rules(str(FIXTURES / "gauge_leak" / "good"),
                          get_rules(["RT-GAUGE-LEAK"]),
                          allowlist=Allowlist.load(path))
        assert [f.rule for f in found] == ["RT-ALLOWLIST-STALE"]
        assert not found[0].allowed

    def test_rules_filter_does_not_go_stale(self):
        # `--rules RT-SHAPE-VALUE` must not report the shipped
        # RT-GAUGE-LEAK suppression stale: its rule never ran this
        # invocation (review finding).
        found = run_lint(str(REPO_ROOT), rule_ids=["RT-SHAPE-VALUE"])
        assert unallowlisted(found) == [], \
            [f.render() for f in unallowlisted(found)]

    def test_jaxpr_findings_ride_the_same_allowlist(self, tmp_path):
        # An audit finding enters the run BEFORE the allowlist applies
        # (review finding): a `<jaxpr:...>` path entry suppresses it,
        # and with --jaxpr's rule ids active, a dead one goes stale.
        from theroundtaible_tpu.analysis.astlint import Finding
        path = self._write(
            tmp_path,
            '[[allow]]\nrule = "RT-JAXPR-CALLBACK"\n'
            'path = "<jaxpr:*>"\nreason = "fixture: known host sync"\n')
        extra = [Finding(rule="RT-JAXPR-CALLBACK",
                         path="<jaxpr:toy>", line=0,
                         message="host callback in decode")]
        found = run_lint(str(FIXTURES / "gauge_leak" / "good"),
                         rule_ids=["RT-GAUGE-LEAK"],
                         allowlist_path=path, extra_findings=extra,
                         extra_active={"RT-JAXPR-CALLBACK"})
        assert unallowlisted(found) == []
        stale = run_lint(str(FIXTURES / "gauge_leak" / "good"),
                         rule_ids=["RT-GAUGE-LEAK"],
                         allowlist_path=path, extra_findings=[],
                         extra_active={"RT-JAXPR-CALLBACK"})
        assert [f.rule for f in stale] == ["RT-ALLOWLIST-STALE"]

    def test_shipped_allowlist_entries_all_carry_reasons(self):
        from theroundtaible_tpu.analysis.astlint import \
            default_allowlist_path
        al = Allowlist.load(default_allowlist_path())
        assert al.entries, "shipped allowlist should not be empty"
        for e in al.entries:
            assert e.reason.strip(), f"entry {e.rule} has no reason"


# --- the PR lands clean: zero unallowlisted findings on the live
# --- tree, with the shipped allowlist ---


class TestLiveTree:
    def test_live_tree_runs_clean(self):
        findings = run_lint(str(REPO_ROOT))
        bad = unallowlisted(findings)
        assert bad == [], "\n".join(f.render() for f in bad)

    def test_fixture_corpus_is_not_scanned_as_live_tree(self):
        index = ProjectIndex(str(REPO_ROOT))
        assert not [p for p in index.files() if "fixtures" in p], \
            "the seeded-violation corpus must be lint INPUT, not tree"

    def test_every_rule_has_id_and_description(self):
        ids = [cls.id for cls in ALL_RULES]
        assert len(ids) == len(set(ids))
        for cls in ALL_RULES:
            assert cls.id.startswith("RT-")
            assert cls.description
            assert cls.severity in ("error", "warning")

    def test_lint_command_json_clean(self, capsys):
        import json

        from theroundtaible_tpu.commands.lint import lint_command
        rc = lint_command(as_json=True, root=str(REPO_ROOT))
        out = json.loads(capsys.readouterr().out)
        assert rc == 0 and out["clean"] is True
        assert out["allowlisted"] >= 1


# --- error-kind classification table (RT-ERROR-KIND's runtime half) ---


class TestErrorKindTable:
    def test_markerless_classes_classify_via_table(self):
        from theroundtaible_tpu.core.errors import classify_error
        from theroundtaible_tpu.engine.deadlines import DrainingError
        from theroundtaible_tpu.engine.scheduler import SchedulerRefused
        assert classify_error(DrainingError("gate shut")) == "draining"
        assert classify_error(
            SchedulerRefused("9 rows > max_rows 4")) == "refused"

    def test_message_sniffing_still_wins_over_table(self):
        # Fault injection crafts messages that classify as their real
        # kind ("hbm" -> oom); the class table must stay a FALLBACK.
        from theroundtaible_tpu.core.errors import classify_error
        from theroundtaible_tpu.engine.faults import FaultInjected
        assert classify_error(FaultInjected(
            "injected hbm allocation failure", "hbm_oom")) == "oom"
        assert classify_error(FaultInjected(
            "injected plain fault", "dispatch")) == "fault_injected"

    def test_table_covers_every_engine_raised_class(self):
        # The static rule's runtime shadow: RT-ERROR-KIND clean on the
        # live tree means this can only fail if someone edits the
        # table without the rule (or vice versa).
        found = rule_findings("RT-ERROR-KIND", REPO_ROOT)
        assert found == [], [f.render() for f in found]


# --- jaxpr audit: check units ---


class TestJaxprChecks:
    def _sds(self, *shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def test_donation_violation_detected(self):
        @partial(jax.jit, donate_argnums=(0,))
        def f(c, x):
            return c + x

        def bad(c, x):
            y = f(c, x)
            return y + c            # donated c read after the call

        def good(c, x):
            return f(c, x) * 2.0

        bad_j = jax.make_jaxpr(bad)(self._sds(4), self._sds(4))
        good_j = jax.make_jaxpr(good)(self._sds(4), self._sds(4))
        assert donation_violations(bad_j)
        assert donation_violations(good_j) == []

    def test_donated_output_passthrough_detected(self):
        @partial(jax.jit, donate_argnums=(0,))
        def f(c, x):
            return c + x

        def leaky(c, x):
            f(c, x)
            return c                # donated buffer returned raw

        j = jax.make_jaxpr(leaky)(self._sds(4), self._sds(4))
        assert any("returned" in v or "read again" in v
                   for v in donation_violations(j))

    def test_callback_found_recursively(self):
        def cb(x):
            return jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct((4,), jnp.float32), x)

        inner = jax.jit(cb)
        j = jax.make_jaxpr(lambda x: inner(x) * 2)(self._sds(4))
        assert find_callbacks(j) == ["pure_callback"]
        clean = jax.make_jaxpr(lambda x: x * 2)(self._sds(4))
        assert find_callbacks(clean) == []

    def test_callback_flagged_only_in_hot_phases(self):
        def cb(x):
            return jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct((4,), jnp.float32), x)

        def spec_for(phase):
            thunk = lambda: jax.make_jaxpr(cb)(self._sds(4))  # noqa: E731
            return ProgramSpec(name="toy", phase=phase, variants=[
                Variant(label="b1", thunk=thunk)])

        hot = audit_programs([spec_for("decode")])
        assert [f.rule for f in hot] == ["RT-JAXPR-CALLBACK"]
        cold = audit_programs([spec_for("prefill")])
        assert cold == []

    def test_seeded_static_arg_leak_fires_extra_jaxpr_detection(self):
        """The acceptance-criterion unit: a toy program whose static
        argument is derived from runtime occupancy produces MORE
        distinct jaxprs than declared variants — flagged; the
        pow2-bucketed twin is clean."""
        from theroundtaible_tpu.engine.serving_loop import pow2_bucket

        @partial(jax.jit, static_argnames=("n",))
        def toy(x, n):
            return x * n

        def variant(occ, leak):
            b = pow2_bucket(occ)
            static = occ if leak else b     # the leak: occ reaches n=

            def thunk():
                return jax.make_jaxpr(
                    lambda x: toy(x, n=static))(self._sds(b))
            return Variant(label=f"b{b}", thunk=thunk,
                           situation=f"occupancy {occ}")

        def spec(leak):
            return ProgramSpec(
                name="toy_decode", phase="decode",
                variants=[variant(3, leak), variant(4, leak)])

        leaked = audit_programs([spec(True)])
        assert [f.rule for f in leaked] == ["RT-JAXPR-VARIANTS"]
        assert "2 DISTINCT jaxprs" in leaked[0].message
        assert audit_programs([spec(False)]) == []

    def test_untraceable_variant_is_loud(self):
        def boom():
            raise RuntimeError("twin drifted")

        out = audit_programs([ProgramSpec(
            name="toy", phase="decode",
            variants=[Variant(label="b1", thunk=boom)])])
        assert [f.rule for f in out] == ["RT-JAXPR-TRACE"]


# --- jaxpr audit: the real serving programs, device-free ---


@pytest.fixture(scope="module")
def paged_engine():
    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.models.registry import get_model_config
    cfg = get_model_config("tiny-gemma", max_seq_len=512)
    return InferenceEngine(
        cfg, num_slots=4, kv_layout="paged",
        mesh_shape={"data": 1, "model": 1},
        spec_decode={"drafter": "ngram",
                     "tree": {"branch": 2, "depth": 2}},
        lora={"rank": 4, "max_adapters": 4})


@pytest.fixture(scope="module")
def hybrid_engine():
    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.models.registry import get_model_config
    return InferenceEngine(get_model_config("tiny-nemotron-h"), num_slots=4,
                           page_size=16,
                           mesh_shape={"data": 1, "model": 1})


class TestEngineAudit:
    def test_paged_engine_covers_every_program_family(self, paged_engine):
        names = {s.name for s in collect_programs(paged_engine)}
        assert names == {"prefill[paged]", "decode[paged]", "ragged",
                         "spec_verify", "spec_propose", "lora_setter"}

    def test_paged_engine_audits_clean(self, paged_engine):
        found = audit_engine(paged_engine)
        assert found == [], "\n".join(f.render() for f in found)

    def test_hybrid_engine_audits_clean(self, hybrid_engine):
        """`lint --jaxpr`'s second engine: the three step programs of a
        model with recurrent state."""
        names = {s.name for s in collect_programs(hybrid_engine)}
        assert names == {"prefill[hybrid]", "decode[hybrid]",
                         "ragged[hybrid]"}
        found = audit_engine(hybrid_engine)
        assert found == [], "\n".join(f.render() for f in found)

    def test_decode_grid_replays_same_bucket_occupancies(self,
                                                         paged_engine):
        # Occupancies 3 and 4 share bucket b4: the variant grid must
        # carry BOTH (that pair is what catches a static-arg leak).
        decode = next(s for s in collect_programs(paged_engine)
                      if s.name == "decode[paged]")
        labels = [v.label for v in decode.variants]
        assert labels.count("b4") == 2


# --- the RT-GAUGE-LEAK rule's first real-world target (ISSUE 15
# --- bugfix satellite): sessions evacuated-then-lost at restart-budget
# --- exhaustion drop their per-session KV gauges ---


class TestSupervisorGaugeHygiene:
    def test_dead_engine_drops_lost_session_gauges(self, paged_engine):
        from theroundtaible_tpu.engine.supervisor import (
            EngineDead,
            EngineSupervisor,
        )
        eng = paged_engine
        name = eng.cfg.name
        # A session's footprint published mid-serve...
        eng.perf.publish_session_kv("s-lost", 512)
        assert telemetry.REGISTRY.gauge_value(
            "roundtable_session_kv_bytes", engine=name,
            session="s-lost") is not None
        # ...then evacuated to the host tier, then the engine exhausts
        # its restart budget: the session never retires through the
        # scheduler, so the supervisor must remove the series itself.
        tier = eng.kv_offload
        assert tier is not None
        tier._spilled["s-lost"] = object()   # evacuated-session record
        sup = EngineSupervisor(max_restarts=0)
        try:
            with pytest.raises(EngineDead):
                sup.restart(eng, reason="budget-exhaustion-test")
        finally:
            tier._spilled.pop("s-lost", None)
        assert telemetry.REGISTRY.gauge_value(
            "roundtable_session_kv_bytes", engine=name,
            session="s-lost") is None
        assert sup.snapshot()["dead_engines"] == 1
