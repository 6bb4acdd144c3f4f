"""Offered-load harness + capacity model suite (ISSUE 19).

Covers the acceptance criteria on the CPU backend:
- seeded arrival processes (Poisson / diurnal / MMPP / closed-loop
  comparison arm): byte-identical schedules per seed, bounds, and the
  open-loop contract;
- WorkloadMix determinism: draw(seed, index) is a pure function, so a
  capacity record names traffic that can be re-offered exactly;
- the capacity record schema + knee fit (monotone in offered load) +
  threshold-derivation rules;
- `Thresholds` precedence, all three layers: explicit ctor arg > env
  var > measured capacity record (ROUNDTABLE_GATEWAY_CAPACITY_FILE) >
  built-in default — and a malformed record degrades LOUDLY (stderr +
  counter) without ever crashing admission;
- a gateway admission controller LOADING and ENFORCING the derived
  thresholds (sheds exactly at the record's inflight cap / p95 SLO);
- a real open-loop sweep through InProcessDriver (+ admission ladder)
  producing a schema-valid frontier record with a shed point;
- the abandonment regression: 20 clients disconnect mid-stream over
  real gateway sockets — zero leaked LoRA refs, zero leaked
  inflight-gauge series, zero attached consumers afterwards.
"""

import json
import time

import pytest

jax = pytest.importorskip("jax")

from theroundtaible_tpu.engine import faults
from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.models.registry import get_model_config
from theroundtaible_tpu.engine.scheduler import SessionScheduler
from theroundtaible_tpu.engine.session_journal import SessionJournal
from theroundtaible_tpu.gateway import Gateway
from theroundtaible_tpu.gateway.admission import (CAPACITY_FILE_ENV,
                                                  AdmissionController,
                                                  Thresholds)
from theroundtaible_tpu.loadgen import (ClosedLoopArrivals,
                                        DiurnalArrivals, GatewayDriver,
                                        InProcessDriver, MMPPArrivals,
                                        PoissonArrivals, SessionSpec,
                                        WorkloadMix, build_record,
                                        fit_knee, make_arrivals,
                                        ramp_rates, run_sweep,
                                        validate_record)
from theroundtaible_tpu.loadgen.capacity import (derive_thresholds,
                                                 extract_thresholds,
                                                 load_record)
from theroundtaible_tpu.loadgen.workload import (default_persona_pool,
                                                 register_personas)
from theroundtaible_tpu.utils import telemetry

MODEL_KW = dict(max_seq_len=512)


def make_engine(**kw):
    """The suite's subject is not the kernels: the gather view (XLA
    alone) over small pages."""
    cfg = get_model_config("tiny-gemma", **MODEL_KW)
    kw.setdefault("num_slots", 8)
    kw.setdefault("attn", "dense")
    kw.setdefault("page_size", 32)
    return InferenceEngine(cfg, **kw)


# ---------------------------------------------------------------------
# Arrival processes
# ---------------------------------------------------------------------


@pytest.mark.loadgen(allow_closed=True)
class TestArrivals:
    def test_poisson_deterministic(self):
        a = PoissonArrivals(seed=3).schedule(rate_rps=5.0,
                                             duration_s=30.0)
        b = PoissonArrivals(seed=3).schedule(rate_rps=5.0,
                                             duration_s=30.0)
        assert a == b and len(a) > 0
        c = PoissonArrivals(seed=4).schedule(rate_rps=5.0,
                                             duration_s=30.0)
        assert a != c

    @pytest.mark.parametrize("cls,kw", [
        (PoissonArrivals, {}),
        (DiurnalArrivals, {"period_s": 20.0, "depth": 0.6}),
        (MMPPArrivals, {"burst_mult": 4.0, "dwell_s": 3.0}),
    ])
    def test_schedules_sorted_bounded_and_near_rate(self, cls, kw):
        sched = cls(seed=7, **kw).schedule(rate_rps=5.0,
                                           duration_s=60.0)
        assert sched == sorted(sched)
        assert all(0.0 <= t < 60.0 for t in sched)
        # Mean rate within loose bounds — all three are normalized to
        # offer `rate_rps` on average.
        assert 0.4 * 300 < len(sched) < 2.0 * 300

    def test_open_loop_flags_and_closed_arm(self):
        assert PoissonArrivals(0).open_loop is True
        closed = ClosedLoopArrivals(concurrency=3)
        assert closed.open_loop is False
        assert closed.schedule(rate_rps=9.0, duration_s=5.0) == [0.0] * 3

    def test_factory_and_validation(self):
        assert make_arrivals("mmpp", 5).kind == "mmpp"
        assert make_arrivals("closed", None, concurrency=2).kind \
            == "closed"
        with pytest.raises(ValueError, match="unknown arrival"):
            make_arrivals("uniform", 1)
        with pytest.raises(ValueError, match="rate_rps"):
            PoissonArrivals(0).schedule(rate_rps=0.0, duration_s=1.0)
        with pytest.raises(ValueError, match="harness bound"):
            PoissonArrivals(0).schedule(rate_rps=1e9, duration_s=10.0)
        with pytest.raises(ValueError, match="depth"):
            DiurnalArrivals(0, depth=1.5)

    def test_describe_names_parameters(self):
        d = MMPPArrivals(2, burst_mult=8.0).describe()
        assert d["kind"] == "mmpp" and d["burst_mult"] == 8.0
        assert d["open_loop"] is True


# ---------------------------------------------------------------------
# Workload mixes
# ---------------------------------------------------------------------


@pytest.mark.loadgen(allow_closed=True)
class TestWorkload:
    def test_draw_is_pure_in_seed_and_index(self):
        mix = WorkloadMix(persona_pool=default_persona_pool(5),
                          persona_churn=0.6, deadline_frac=0.4,
                          abandon_frac=0.4)
        a = [mix.draw(11, i) for i in range(40)]
        b = mix.draw_many(11, 40)
        assert a == b
        # Draw i does not depend on how many sessions were drawn.
        assert mix.draw(11, 17) == a[17]
        assert mix.draw(12, 17) != a[17]

    def test_session_names_unique_per_seed_and_index(self):
        mix = WorkloadMix()
        names = {mix.draw(s, i).session
                 for s in (1, 2) for i in range(20)}
        assert len(names) == 40

    def test_mix_axes_all_exercised(self):
        mix = WorkloadMix(max_turns=3,
                          persona_pool=default_persona_pool(4),
                          persona_churn=0.7, deadline_frac=0.5,
                          abandon_frac=0.5)
        specs = mix.draw_many(5, 80)
        assert {s.priority for s in specs} >= {"high", "normal", "low"}
        assert any(s.deadline_s is not None for s in specs)
        assert any(s.abandon_after_tokens is not None for s in specs)
        assert any(s.rows() > 1 for s in specs)
        adapters = {a for s in specs
                    for a in (s.adapters_per_turn or []) if a}
        assert len(adapters) >= 3  # churn cycles through the pool

    def test_register_personas_idempotent(self):
        engine = make_engine(lora={"rank": 4, "max_adapters": 3})
        pool = default_persona_pool(4)
        assert register_personas(engine, pool) == 4
        assert register_personas(engine, pool) == 0  # already there


# ---------------------------------------------------------------------
# Capacity record: schema, knee fit, derived thresholds
# ---------------------------------------------------------------------


def synth_point(rate, *, shed_rate=0.0, p95=0.4, tok_s=None, peak=4):
    n = max(int(rate * 10), 1)
    shed = int(n * shed_rate)
    return {
        "offered_rps": float(rate), "duration_s": 10.0,
        "arrivals": n, "admitted": n - shed, "shed": shed,
        "shed_rate": round(shed / n, 4),
        "ttft_p50_s": p95 * 0.5, "ttft_p95_s": p95,
        "ttft_p99_s": p95 * 1.2,
        "accepted_tok_s": float(tok_s if tok_s is not None
                                else rate * 6),
        "peak_concurrent_sessions": peak,
        "sessions_per_chip": float(peak),
    }


def synth_record(**kw):
    points = kw.pop("points", None) or [
        synth_point(1), synth_point(2), synth_point(4),
        synth_point(8, shed_rate=0.4, p95=2.5, peak=8)]
    return build_record(points=points,
                        arrival={"kind": "poisson", "seed": 7},
                        workload={"max_new_tokens": 4}, seed=7, **kw)


@pytest.mark.loadgen(allow_closed=True)
class TestCapacityModel:
    def test_record_round_trip_validates(self, tmp_path):
        rec = synth_record()
        assert validate_record(rec) == []
        p = tmp_path / "cap.json"
        p.write_text(json.dumps(rec), encoding="utf-8")
        assert load_record(str(p))["knee"] == rec["knee"]

    def test_validate_catches_each_defect(self):
        assert validate_record("nope")
        assert any("schema" in e
                   for e in validate_record({"schema": "v0"}))
        rec = synth_record()
        bad = dict(rec, points=[dict(rec["points"][0])])
        del bad["points"][0]["accepted_tok_s"]
        assert any("accepted_tok_s" in e for e in validate_record(bad))
        unsorted = dict(rec, points=[rec["points"][2],
                                     rec["points"][0]])
        assert any("sorted" in e for e in validate_record(unsorted))
        noknee = dict(rec)
        del noknee["knee"]
        assert any("knee" in e for e in validate_record(noknee))
        badth = dict(rec, derived_thresholds={"max_inflight": -1})
        assert validate_record(badth)

    def test_knee_is_highest_absorbed_rate(self):
        rec = synth_record()
        # Point at 4/s is the last one with low shed + sane p95.
        assert rec["knee"]["rate"] == 4.0
        assert "highest rate" in rec["knee"]["reason"]

    def test_knee_monotone_in_offered_load(self):
        pts = [synth_point(1), synth_point(2), synth_point(4)]
        base = fit_knee(pts)["rate"]
        # Appending a BAD higher-rate point never moves the knee down.
        worse = pts + [synth_point(8, shed_rate=0.5, p95=4.0)]
        assert fit_knee(worse)["rate"] == base
        # Appending a GOOD higher-rate point only moves it up.
        better = pts + [synth_point(8)]
        assert fit_knee(better)["rate"] >= base

    def test_threshold_derivation_rules(self):
        pts = [synth_point(2, p95=0.5, peak=4),
               synth_point(4, p95=0.8, peak=8)]
        knee = fit_knee(pts)
        th = derive_thresholds(pts, knee)
        assert th["max_inflight"] == 10          # ceil(8 * 1.25)
        assert th["max_queue_depth"] == 7        # ceil(4*0.8 * 2.0)
        assert th["p95_slo_s"] == pytest.approx(1.2)   # 0.8 * 1.5
        assert th["rules"]["slo_margin"] == 1.5

    def test_extract_thresholds_accepts_bench_wrapper(self):
        rec = synth_record()
        wrapped = {"metric": "capacity_frontier_knee",
                   "detail": {"frontier": rec}}
        assert extract_thresholds(wrapped) == rec["derived_thresholds"]
        with pytest.raises(ValueError, match="malformed"):
            extract_thresholds({"detail": {"frontier": {"schema": 1}}})

    def test_ramp_rates(self):
        assert ramp_rates(1.0, 2.0, 4) == [1.0, 2.0, 4.0, 8.0]
        with pytest.raises(ValueError):
            ramp_rates(0.0, 2.0, 3)


# ---------------------------------------------------------------------
# Thresholds precedence: ctor > env > capacity record > built-in
# ---------------------------------------------------------------------


class _StubSource:
    """Signal provider that never sheds on its own — isolates the
    threshold under test."""

    def drain_state(self):
        return None

    def dead_reason(self):
        return None

    def queue_depth(self):
        return 0

    def kv_pressure(self, headroom):
        return False

    def adapters_busy(self, adapters):
        return False


_THRESHOLD_ENVS = ("ROUNDTABLE_GATEWAY_MAX_INFLIGHT",
                   "ROUNDTABLE_GATEWAY_MAX_QUEUE_DEPTH",
                   "ROUNDTABLE_GATEWAY_PAGE_HEADROOM",
                   "ROUNDTABLE_GATEWAY_P95_SLO_S",
                   "ROUNDTABLE_GATEWAY_RETRY_AFTER_S",
                   CAPACITY_FILE_ENV)


@pytest.fixture()
def clean_env(monkeypatch):
    for name in _THRESHOLD_ENVS:
        monkeypatch.delenv(name, raising=False)
    return monkeypatch


@pytest.fixture()
def record_file(tmp_path):
    rec = synth_record()
    p = tmp_path / "CAPACITY_r19.json"
    p.write_text(json.dumps(rec), encoding="utf-8")
    return str(p), rec["derived_thresholds"]


@pytest.mark.loadgen(allow_closed=True)
class TestThresholdPrecedence:
    def test_layer_default(self, clean_env):
        th = Thresholds.resolve()
        assert th.source == "default" and th.record_path is None
        assert th.max_inflight == 32 and th.max_queue_depth == 16
        assert th.env_overrides == ()

    def test_layer_capacity_record(self, clean_env, record_file):
        path, derived = record_file
        clean_env.setenv(CAPACITY_FILE_ENV, path)
        th = Thresholds.resolve()
        assert th.source == "capacity_record"
        assert th.record_path == path
        assert th.max_inflight == derived["max_inflight"]
        assert th.max_queue_depth == derived["max_queue_depth"]
        assert th.p95_slo_s == pytest.approx(derived["p95_slo_s"])

    def test_layer_env_beats_record(self, clean_env, record_file):
        path, derived = record_file
        clean_env.setenv(CAPACITY_FILE_ENV, path)
        clean_env.setenv("ROUNDTABLE_GATEWAY_MAX_INFLIGHT", "3")
        th = Thresholds.resolve()
        assert th.max_inflight == 3
        assert th.env_overrides == ("max_inflight",)
        # The other fields still come from the record layer.
        assert th.source == "capacity_record"
        assert th.max_queue_depth == derived["max_queue_depth"]

    def test_unparsable_env_falls_through(self, clean_env,
                                          record_file):
        path, derived = record_file
        clean_env.setenv(CAPACITY_FILE_ENV, path)
        clean_env.setenv("ROUNDTABLE_GATEWAY_MAX_INFLIGHT", "banana")
        th = Thresholds.resolve()
        assert th.max_inflight == derived["max_inflight"]
        assert th.env_overrides == ()

    def test_ctor_arg_beats_env_and_record(self, clean_env,
                                           record_file):
        path, _ = record_file
        clean_env.setenv(CAPACITY_FILE_ENV, path)
        clean_env.setenv("ROUNDTABLE_GATEWAY_MAX_INFLIGHT", "3")
        ac = AdmissionController(None, source=_StubSource(),
                                 max_inflight=9)
        assert ac.max_inflight == 9

    @pytest.mark.parametrize("content", [
        "{not json",
        json.dumps({"schema": "wrong.schema", "points": []}),
        json.dumps({"detail": {"frontier": {"schema": 1}}}),
    ])
    def test_malformed_record_degrades_loudly(self, clean_env,
                                              tmp_path, capsys,
                                              content):
        p = tmp_path / "bad.json"
        p.write_text(content, encoding="utf-8")
        clean_env.setenv(CAPACITY_FILE_ENV, str(p))
        before = telemetry.REGISTRY.counter_total(
            "roundtable_gateway_capacity_record_errors_total")
        th = Thresholds.resolve()          # must NOT raise
        assert th.source == "default" and th.max_inflight == 32
        assert telemetry.REGISTRY.counter_total(
            "roundtable_gateway_capacity_record_errors_total") \
            == before + 1
        err = capsys.readouterr().err
        assert CAPACITY_FILE_ENV in err and "falling back" in err

    def test_missing_record_file_degrades_loudly(self, clean_env,
                                                 tmp_path, capsys):
        clean_env.setenv(CAPACITY_FILE_ENV,
                         str(tmp_path / "nope.json"))
        th = Thresholds.resolve()
        assert th.source == "default"
        assert "falling back" in capsys.readouterr().err


@pytest.mark.loadgen(allow_closed=True)
class TestAdmissionEnforcesDerived:
    """The loop actually closes: admission LOADS the record's derived
    thresholds and ENFORCES them in decide()."""

    def test_sheds_at_derived_inflight_cap(self, clean_env,
                                           record_file):
        path, derived = record_file
        clean_env.setenv(CAPACITY_FILE_ENV, path)
        ac = AdmissionController(None, source=_StubSource())
        assert ac.thresholds.source == "capacity_record"
        cap = derived["max_inflight"]
        ok = ac.decide(rows=1, inflight=cap - 1)
        assert ok.admit
        shed = ac.decide(rows=1, inflight=cap)
        assert not shed.admit and shed.reason == "inflight_cap"
        assert shed.status == 429

    def test_enforces_derived_p95_slo(self, clean_env, record_file):
        path, derived = record_file
        clean_env.setenv(CAPACITY_FILE_ENV, path)
        ac = AdmissionController(None, source=_StubSource())
        slo = derived["p95_slo_s"]
        assert ac.p95_slo_s == pytest.approx(slo)
        for _ in range(16):
            ac.note_ttft(slo * 2)          # measured latency over SLO
        shed = ac.decide(rows=1, inflight=0)
        assert not shed.admit and shed.reason == "slo_p95"
        # High priority bypasses the soft signal.
        assert ac.decide(rows=1, inflight=0, priority="high").admit

    def test_describe_names_provenance(self, clean_env, record_file):
        path, _ = record_file
        clean_env.setenv(CAPACITY_FILE_ENV, path)
        caps = AdmissionController(
            None, source=_StubSource()).describe()["caps"]
        assert caps["source"] == "capacity_record"
        assert caps["record_path"] == path


# ---------------------------------------------------------------------
# Real open-loop sweep (InProcessDriver + admission ladder)
# ---------------------------------------------------------------------


@pytest.mark.loadgen
def test_open_loop_sweep_builds_valid_frontier(tmp_path):
    """Fast tier-1 sweep: a real engine, open-loop Poisson arrivals
    ramped until the tight admission caps shed — the frontier record
    validates against the schema and carries both sides of the knee."""
    engine = make_engine()
    sched = SessionScheduler(engine,
                             journal=SessionJournal(str(tmp_path)))
    admission = AdmissionController(sched, max_inflight=3,
                                    max_queue_depth=2)
    driver = InProcessDriver(sched, admission=admission)
    mix = WorkloadMix(max_new_tokens=2, max_turns=1,
                      prompt_words=(3, 6))
    try:
        points = run_sweep(driver, PoissonArrivals(seed=7), mix,
                           [6.0, 12.0, 24.0, 48.0], duration_s=1.0,
                           seed=7, stop_shed_rate=0.3, min_points=2,
                           settle_s=0.1)
    finally:
        sched.close()
    assert len(points) >= 2
    assert any(pt["shed"] > 0 for pt in points), \
        "the ramp never reached the shed point"
    assert any(pt["admitted"] > 0 for pt in points)
    shed_reasons = {r for pt in points
                    for r in pt["shed_reasons"]}
    assert shed_reasons <= {"inflight_cap", "queue_full",
                            "kv_pressure", "adapters_busy", "slo_p95"}
    rec = build_record(points=points,
                       arrival=PoissonArrivals(7).describe(),
                       workload=mix.describe(), seed=7)
    assert validate_record(rec) == []
    assert rec["knee"]["rate"] in [pt["offered_rps"] for pt in points]


# ---------------------------------------------------------------------
# Abandonment regression: mid-stream disconnects leak NOTHING
# ---------------------------------------------------------------------


@pytest.mark.loadgen
def test_abandoned_streams_leak_nothing(tmp_path, monkeypatch):
    """20 clients disconnect after their first token over REAL gateway
    sockets. The abandonment seam (ROUNDTABLE_GATEWAY_ABANDON_S linger
    -> request.abandoned -> scheduler health check) must release every
    LoRA ref, retire every inflight-gauge series, and leave zero
    attached consumers — a walked-away client must not burn capacity
    or leak observability state."""
    monkeypatch.setenv("ROUNDTABLE_GATEWAY_ABANDON_S", "0.1")
    engine = make_engine(lora={"rank": 4, "max_adapters": 3})
    pool = default_persona_pool(3)
    register_personas(engine, pool)
    sched = SessionScheduler(engine,
                             journal=SessionJournal(str(tmp_path)))
    admission = AdmissionController(sched, max_inflight=64,
                                    max_queue_depth=64, p95_slo_s=0.0)
    gw = Gateway(sched, port=0, intent_dir=str(tmp_path),
                 admission=admission)
    port = gw.start_in_thread()
    abandoned0 = telemetry.REGISTRY.counter_total(
        "roundtable_gateway_abandoned_streams_total")
    try:
        specs = [SessionSpec(
            index=i, session=f"walkaway-{i}",
            turns=[("galahad", f"the {i}th discussion of the walls")],
            max_new_tokens=360,  # long round: the disconnect + linger
                                 # expire MID-round, so the reap (not
                                 # natural completion) must clean up
            adapters_per_turn=[pool[i % len(pool)]],
            abandon_after_tokens=1) for i in range(20)]
        offsets = [0.05 * i for i in range(20)]
        records = GatewayDriver(port).run(specs, offsets,
                                          open_loop=True,
                                          timeout_s=90.0)
        assert len(records) == 20
        outcomes = {r["outcome"] for r in records}
        assert outcomes <= {"abandoned", "completed"}, records
        assert sum(1 for r in records
                   if r["outcome"] == "abandoned") >= 15

        # Every stream must reach a terminal state once the linger
        # timers fire and the scheduler reaps the abandoned rounds.
        deadline = time.monotonic() + 60.0
        def leaked():
            series = telemetry.REGISTRY.snapshot_compact()
            gauges = [k for k in series
                      if k.split("{", 1)[0]
                      == "roundtable_gateway_inflight_streams"]
            refs = engine.lora.describe()["refs"]
            attached = sum(st.attached()
                           for st in gw.streams.values())
            return gauges, refs, attached

        while time.monotonic() < deadline:
            gauges, refs, attached = leaked()
            if not gauges and not refs and attached == 0:
                break
            time.sleep(0.25)
        gauges, refs, attached = leaked()
        assert gauges == [], f"leaked inflight series: {gauges}"
        assert refs == {}, f"leaked LoRA refs: {refs}"
        assert attached == 0
        assert telemetry.REGISTRY.counter_total(
            "roundtable_gateway_abandoned_streams_total") > abandoned0
    finally:
        gw.stop()
        sched.close()
        faults.disarm()


# ---------------------------------------------------------------------
# Surfaces: status --capacity + CLI wiring
# ---------------------------------------------------------------------


@pytest.mark.loadgen(allow_closed=True)
class TestSurfaces:
    def test_capacity_surface_matches_bindings(self):
        from theroundtaible_tpu.commands.status import capacity_surface
        surf = capacity_surface(synth_record(), "x.json", {})
        assert set(surf) == set(
            telemetry.SURFACE_BINDINGS["capacity_status"])

    def test_status_capacity_renders_record(self, tmp_path, capsys,
                                            monkeypatch):
        from theroundtaible_tpu.commands.status import capacity_status
        monkeypatch.delenv(CAPACITY_FILE_ENV, raising=False)
        rec = synth_record()
        (tmp_path / "CAPACITY_r19.json").write_text(
            json.dumps(rec), encoding="utf-8")
        assert capacity_status(str(tmp_path)) == 0
        out = capsys.readouterr().out
        assert "Knee: 4.00 sessions/s" in out
        assert "Derived admission thresholds" in out
        assert "Live gateway" in out

    def test_status_capacity_without_record(self, tmp_path, capsys,
                                            monkeypatch):
        from theroundtaible_tpu.commands.status import capacity_status
        monkeypatch.delenv(CAPACITY_FILE_ENV, raising=False)
        assert capacity_status(str(tmp_path)) == 0
        assert "No capacity record" in capsys.readouterr().out

    def test_cli_parses_loadgen_and_capacity(self):
        from theroundtaible_tpu.cli import build_parser
        args = build_parser().parse_args(
            ["loadgen", "--smoke", "--arrival", "mmpp"])
        assert args.command == "loadgen" and args.smoke
        assert args.arrival == "mmpp"
        st = build_parser().parse_args(["status", "--capacity"])
        assert st.capacity
