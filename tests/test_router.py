"""Multi-replica session routing suite (ISSUE 17).

Covers the tentpole + satellites on the CPU backend:
- routing units: load-score ordering, sticky assignment, journal
  affinity after a process restart, fleet-wide admission signals
  (FleetSignals), the N=1 provider identity (SchedulerSignals, with
  byte-identical unlabeled counters), replica retirement removing every
  replica-labeled series (RT-GAUGE-LEAK), and the `status --fleet`
  renderer;
- cross-replica handoff parity: a mid-discussion session evacuated off
  replica A, adopted onto replica B over the host-RAM tier, and resumed
  there with greedy token parity vs the unmigrated run — including
  int8-quantized pages (moved at stored width) and a LoRA-persona
  session whose adapter follows it;
- rolling restart: `router.roll()` drains one replica, migrates its
  idle sessions to the peer, supervises the rebuild under the PR-12
  budget, and re-admits — zero lost sessions, token parity across the
  roll;
- failure containment (chaos): `device_lost` kills one replica under 3
  concurrent gateway streams; every client reconnects via Last-Event-ID
  and is served from the survivor with zero lost and zero duplicated
  tokens (router failover + the PR-16 resume ladder).
"""

import threading
import time
from types import SimpleNamespace

import pytest

jax = pytest.importorskip("jax")

from theroundtaible_tpu.engine import deadlines, faults
from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.session_journal import SessionJournal
from theroundtaible_tpu.engine.supervisor import (EngineSupervisor,
                                                  set_supervisor)
from theroundtaible_tpu.gateway import Gateway
from theroundtaible_tpu.gateway.admission import (AdmissionController,
                                                  SchedulerSignals)
from theroundtaible_tpu.router import (NoLiveReplica, Replica,
                                       SessionRouter, build_replicas,
                                       set_active_router)
from theroundtaible_tpu.router.signals import FleetSignals
from theroundtaible_tpu.utils import telemetry

from test_gateway import read_stream, row_tokens  # noqa: E402

CONFIG = {"model": "tiny-gemma", "max_seq_len": 256, "num_slots": 8,
          "kv_layout": "paged", "page_size": 16, "kv_offload": True,
          "mesh": {"data": 1, "model": 1},
          "sampling": {"temperature": 0.0, "max_new_tokens": 8}}

PROMPT = ("The round table convened at dawn to weigh the eastern gate "
          "repairs against the harvest levy.")


@pytest.fixture(autouse=True)
def clean_state():
    faults.disarm()
    deadlines.end_drain()
    set_supervisor(None)
    yield
    faults.disarm()
    deadlines.end_drain()
    set_supervisor(None)


def make_fleet(jdir, n=2, **over):
    cfg = dict(CONFIG)
    cfg.update(over)
    journal = SessionJournal(jdir)
    eng = InferenceEngine.from_config(cfg)
    reps = build_replicas(eng, n, journal=journal)
    return SessionRouter(reps, journal=journal)


def close_fleet(router):
    router.close()
    for rep in router.replicas:
        if getattr(rep, "owned_scheduler", False):
            try:
                rep.scheduler.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    r = make_fleet(tmp_path_factory.mktemp("router-journal"))
    yield r
    close_fleet(r)


def run_two_turns(router, session, pin, *, move_to=None, adapters=None):
    """Two-turn greedy session pinned to `pin`, optionally migrated to
    `move_to` between turns. Returns (text1, text2)."""
    router.migrate(session, dst=pin)   # src None: assignment only
    sched = router.scheduler_for(session, adapters)
    t1, _ = sched.submit(session, [("lancelot", PROMPT)],
                         max_new_tokens=8, adapters_per_turn=adapters)
    if move_to is not None:
        router.migrate(session, dst=move_to)
    sched = router.scheduler_for(session, adapters)
    t2, _ = sched.submit(session,
                         [("lancelot", PROMPT + " " + t1[0])],
                         max_new_tokens=8, adapters_per_turn=adapters)
    return t1[0], t2[0]


# ---------------------------------------------------------------------
# routing units (no KV ever crosses: allow_local)
# ---------------------------------------------------------------------


@pytest.mark.router(allow_local=True)
class TestRoutingUnits:
    def test_load_score_prefers_open_replica(self, fleet):
        r0, r1 = fleet.replicas
        assert fleet.load_score(r0) != float("inf")
        r0.scheduler.pause_admission("unit.test")
        try:
            assert fleet.load_score(r0) > fleet.load_score(r1) + 100
            assert fleet.replica_for("unit-cold") is r1
        finally:
            r0.scheduler.reopen_admission()

    def test_sticky_assignment(self, fleet):
        rep = fleet.replica_for("unit-sticky")
        for _ in range(3):
            assert fleet.replica_for("unit-sticky") is rep

    def test_journal_affinity_survives_process_restart(self, fleet):
        """A fresh router (empty assignment map — the post-restart
        state) routes a returning session to the replica stamped on
        its last committed turn, not by load."""
        fleet.journal.record_turn(
            "unit-aff", [{"knight": "k", "prompt_tokens": [1],
                          "produced": [2]}],
            engine="t", replica="r1")
        fresh = SessionRouter(fleet.replicas, journal=fleet.journal)
        try:
            assert fresh.replica_for("unit-aff").name == "r1"
        finally:
            fresh.close()

    def test_fleet_signals_shed_only_when_whole_fleet_closed(self,
                                                             fleet):
        sig = fleet.signals()
        assert isinstance(sig, FleetSignals)
        assert sig.drain_state() is None
        assert sig.dead_reason() is None
        assert sig.queue_depth() == 0
        assert sig.kv_pressure(0.05) is False   # host tier present
        assert sig.adapters_busy(["x"]) is False  # no LoRA store
        r0, r1 = fleet.replicas
        r0.scheduler.pause_admission("unit.one")
        try:
            # one closed replica never sheds the front door…
            assert sig.drain_state() is None
            r1.scheduler.pause_admission("unit.two")
            # …the whole fleet closed does.
            assert sig.drain_state() == "paused:unit.one"
        finally:
            r0.scheduler.reopen_admission()
            r1.scheduler.reopen_admission()

    def test_admission_n1_provider_byte_identical(self):
        """Single-engine gateways read the same signals through
        SchedulerSignals — same decisions, same UNLABELED counter
        series (no replica key appears anywhere at N=1)."""
        sched = SimpleNamespace(
            paused=None,
            engine=SimpleNamespace(lora=None),
            journal=None,
            describe=lambda: {"admission": {"queued": 0}})
        adm = AdmissionController(sched, max_inflight=4,
                                  max_queue_depth=4)
        assert isinstance(adm.source, SchedulerSignals)
        before = telemetry.REGISTRY.counter_total(
            "roundtable_gateway_admitted_total", reason="ok")
        adm.note_admitted()
        assert telemetry.REGISTRY.counter_total(
            "roundtable_gateway_admitted_total",
            reason="ok") == before + 1
        assert adm.decide(rows=1, inflight=0).admit
        sched.paused = "quiesce"
        d = adm.decide(rows=1, inflight=0)
        assert (not d.admit and d.reason == "paused:quiesce"
                and d.status == 503)

    def test_retire_removes_replica_labeled_series(self):
        """RT-GAUGE-LEAK across the fleet dimension: a retired replica
        takes every series labeled with it to the grave."""
        def fake_replica(name, tname):
            eng = SimpleNamespace(
                cfg=SimpleNamespace(name="tiny-gemma"))
            sched = SimpleNamespace(
                _tname=tname, replica=None, engine=eng,
                describe=lambda: {"admission": {"paused": None,
                                                "queued": 0},
                                  "active_rows": 0})
            sched.set_replica = lambda n, s=sched: setattr(
                s, "replica", n)
            return Replica(name, eng, sched)

        router = SessionRouter([fake_replica("r0", "t0"),
                                fake_replica("r1", "t1")])
        try:
            telemetry.set_gauge("roundtable_sched_queue_depth", 1,
                                engine="t1", replica="r1")
            telemetry.set_gauge("roundtable_sched_active_rows", 1,
                                engine="t1", replica="r1")
            telemetry.set_gauge("roundtable_engine_dead", 1,
                                engine="tiny-gemma", replica="r1")
            assert telemetry.REGISTRY.gauge_value(
                "roundtable_router_sessions", replica="r1") == 0
            router.retire("r1")
            for name, labels in [
                    ("roundtable_router_sessions", {"replica": "r1"}),
                    ("roundtable_engine_dead",
                     {"engine": "tiny-gemma", "replica": "r1"}),
                    ("roundtable_sched_queue_depth",
                     {"engine": "t1", "replica": "r1"}),
                    ("roundtable_sched_active_rows",
                     {"engine": "t1", "replica": "r1"})]:
                assert telemetry.REGISTRY.gauge_value(
                    name, **labels) is None, name
            assert router.replica_for("after-retire").name == "r0"
            router.retire("r0")
            with pytest.raises(NoLiveReplica):
                router.replica_for("nowhere")
        finally:
            router.close()

    def test_build_replicas_validates(self):
        with pytest.raises(ValueError, match="rebuild recipe"):
            build_replicas(SimpleNamespace(), 2)
        with pytest.raises(ValueError, match="at least 1"):
            build_replicas(SimpleNamespace(), 0)

    def test_status_fleet_renders_and_health_rollup(self, fleet,
                                                    capsys):
        set_active_router(fleet)
        from theroundtaible_tpu.commands.status import fleet_status
        from theroundtaible_tpu.engine.fleet import fleet_health
        fleet_status()
        out = capsys.readouterr().out
        assert "r0" in out and "r1" in out
        health = fleet_health()
        assert set(health["router"]["replicas"]) >= {"r0", "r1"}


# ---------------------------------------------------------------------
# cross-replica KV handoff (satellite 3: parity over the host tier)
# ---------------------------------------------------------------------


@pytest.mark.router
class TestHandoffParity:
    def _assert_handoff(self, router, mig, ref):
        """Run `mig` with a mid-discussion r0→r1 migration and `ref`
        unmigrated on r0; assert the pages really crossed AND the
        tokens match turn for turn."""
        r0, r1 = router.replicas
        router.migrate(mig, dst="r0")
        sched = router.scheduler_for(mig)
        t1, _ = sched.submit(mig, [("lancelot", PROMPT)],
                             max_new_tokens=8)
        # the scheduler stamps the serving replica on the committed turn
        assert router.journal.last_replica(mig) == "r0"
        router.migrate(mig, dst="r1")
        # evacuated off r0, host-resident on r1 until the next dispatch
        assert r1.tier.has(mig) and not r0.tier.has(mig)
        sched = router.scheduler_for(mig)
        assert sched is r1.scheduler
        restores = r1.tier.describe()["restores"]
        t2, _ = sched.submit(mig, [("lancelot",
                                    PROMPT + " " + t1[0])],
                             max_new_tokens=8)
        assert r1.tier.describe()["restores"] == restores + 1
        assert router.journal.last_replica(mig) == "r1"
        rt1, rt2 = run_two_turns(router, ref, "r0")
        assert (t1[0], t2[0]) == (rt1, rt2), \
            "cross-replica handoff lost greedy token parity"

    def test_handoff_token_parity_bf16(self, fleet):
        self._assert_handoff(fleet, "mig-bf16", "ref-bf16")
        assert fleet.migrations >= 1
        assert telemetry.REGISTRY.counter_total(
            "roundtable_router_migrations_total", replica="r1") >= 1

    def test_handoff_int8_pages_move_at_stored_width(self, tmp_path):
        router = make_fleet(tmp_path / "j-int8", kv_quant="int8")
        try:
            assert router.replicas[1].engine.kv_quant_spec is not None
            self._assert_handoff(router, "mig-i8", "ref-i8")
        finally:
            close_fleet(router)

    def test_handoff_lora_persona_session(self, tmp_path):
        router = make_fleet(
            tmp_path / "j-lora",
            lora={"rank": 4, "max_adapters": 3,
                  "adapters": {"galahad": {"seed": 1,
                                           "init_std": 0.6}}})
        try:
            ads = ["galahad"]
            t1, t2 = run_two_turns(router, "mig-lora", "r0",
                                   move_to="r1", adapters=ads)
            assert router.replica_for("mig-lora", ads).name == "r1"
            # the persona is live on the destination's own store
            assert "galahad" in router.replicas[1].engine.lora.resident()
            rt1, rt2 = run_two_turns(router, "ref-lora", "r0",
                                     adapters=ads)
            assert (t1, t2) == (rt1, rt2), \
                "LoRA-persona handoff lost greedy token parity"
        finally:
            close_fleet(router)

    def test_migrate_refuses_inflight_session(self, fleet):
        """Only idle sessions migrate — a mid-turn handoff would move
        pages out from under live rows."""
        done = threading.Event()
        hold = threading.Thread(
            target=lambda: (fleet.replicas[0].scheduler.submit(
                "mig-busy", [("lancelot", PROMPT)],
                max_new_tokens=24), done.set()),
            daemon=True)
        fleet.migrate("mig-busy", dst="r0")
        hold.start()
        try:
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and not done.is_set():
                state = fleet.replicas[0].snapshot_sessions().get(
                    "mig-busy", "")
                if state.startswith(("queued", "active")):
                    with pytest.raises(RuntimeError,
                                       match="in-flight"):
                        fleet.migrate("mig-busy", dst="r1")
                    break
                time.sleep(0.01)
        finally:
            hold.join(timeout=60)
        # settled sessions migrate fine afterwards (also the marked
        # crossing for this test)
        assert done.is_set()
        fleet.migrate("mig-busy", dst="r1")
        assert fleet.replicas[1].tier.has("mig-busy")


# ---------------------------------------------------------------------
# rolling restart (tentpole piece 3)
# ---------------------------------------------------------------------


@pytest.mark.router
class TestRollingRestart:
    def test_roll_migrates_sessions_rebuilds_and_readmits(self,
                                                          tmp_path):
        router = make_fleet(tmp_path / "j-roll")
        try:
            router.migrate("roll-s", dst="r0")
            sched = router.scheduler_for("roll-s")
            t1, _ = sched.submit("roll-s", [("lancelot", PROMPT)],
                                 max_new_tokens=8)
            reports = router.roll("r0")
            assert len(reports) == 1 and reports[0]["ok"], reports
            assert reports[0]["migrated"] == 1
            # zero lost sessions: the session lives on the peer and
            # its next turn extends the same transcript
            rep = router.replica_for("roll-s")
            assert rep.name == "r1"
            t2, _ = rep.scheduler.submit(
                "roll-s", [("lancelot", PROMPT + " " + t1[0])],
                max_new_tokens=8)
            rt1, rt2 = run_two_turns(router, "roll-ref", "r1")
            assert (t1[0], t2[0]) == (rt1, rt2), \
                "roll lost greedy token parity"
            # the rolled replica rebuilt, reopened, and serves again
            r0 = router.replicas[0]
            assert r0.dead_reason() is None
            assert r0.scheduler.paused is None
            cold, _ = r0.scheduler.submit(
                "roll-cold", [("lancelot", PROMPT)], max_new_tokens=4)
            assert cold[0]
            assert router.rolls == 1
            assert telemetry.REGISTRY.counter_total(
                "roundtable_router_rolls_total", replica="r0") >= 1
        finally:
            close_fleet(router)


# ---------------------------------------------------------------------
# failure containment chaos (satellite 4)
# ---------------------------------------------------------------------


def _row0_tokens(ev):
    if ev["type"] == "tokens":
        return ev["tokens"]
    return ev["rows"]["0"]["tokens"]   # coalesced summary


def run_stream_with_reconnect(port, body, attempts=8):
    """Open the stream; on a replica-failure terminal, reconnect with
    Last-Event-ID until retired. Returns (tokens, reconnects)."""
    meta, toks, terminal = read_stream(port, "/v1/discussions", body)
    stream_id = meta["stream"]
    got, last_id = [], None
    for eid, ev in toks:
        got.extend(_row0_tokens(ev))
        last_id = eid
    reconnects = 0
    while terminal is None or terminal["type"] == "failed":
        reconnects += 1
        assert reconnects <= attempts, \
            f"stream {stream_id} never recovered: {terminal}"
        time.sleep(0.5)
        headers = {"Last-Event-ID": last_id} if last_id else None
        try:
            _m, toks, terminal = read_stream(
                port, f"/v1/streams/{stream_id}", method="GET",
                headers=headers)
        except AssertionError:
            # failover still settling (shed with Retry-After) — retry
            terminal = {"type": "failed"}
            continue
        for eid, ev in toks:
            got.extend(_row0_tokens(ev))
            last_id = eid
    assert terminal["type"] == "retired"
    return got, reconnects


@pytest.mark.router
@pytest.mark.chaos
def test_device_lost_failover_streams_reconnect_no_loss(tmp_path):
    """THE containment acceptance: one replica dies (device_lost, no
    restart budget) under 3 concurrent gateway streams — every client
    reconnects via Last-Event-ID, is served from the survivor, and the
    spliced streams reproduce the fault-free run token for token."""
    jdir = tmp_path / "j-chaos"
    router = make_fleet(jdir)
    gw = Gateway(router.replicas[0].scheduler, port=0,
                 intent_dir=str(jdir), router=router)
    gw.start_in_thread()
    try:
        bodies = [{"session": f"chaos-{i}", "max_new_tokens": 8,
                   "turns": [{"knight": "lancelot",
                              "prompt": PROMPT + f" Seat {i}."}]}
                  for i in range(3)]
        # fault-free reference: greedy serving must reproduce these
        # exact tokens across the failure
        ref = []
        for i, b in enumerate(bodies):
            rb = dict(b)
            rb["session"] = f"ref-{i}"
            _m, toks, term = read_stream(gw.port, "/v1/discussions",
                                         rb)
            assert term["type"] == "retired"
            ref.append(row_tokens(toks, 1)[0])

        # the next replica to dispatch dies for good: zero restart
        # budget turns device_lost into an unplanned dead replica
        set_supervisor(EngineSupervisor(max_restarts=0))
        faults.arm("device_lost", count=1)
        results = [None] * 3

        def client(i):
            results[i] = run_stream_with_reconnect(gw.port, bodies[i])

        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True) for i in range(3)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=180)
        assert all(r is not None for r in results), \
            "a chaos stream never finished"
        for i, (got, _rc) in enumerate(results):
            assert got == ref[i], \
                f"stream {i} lost or duplicated tokens across failover"
        assert any(rc > 0 for _g, rc in results), \
            "no stream crossed the replica failure"
        dead = [r for r in router.replicas if r.dead_reason()]
        assert len(dead) == 1, "exactly one replica should have died"
        assert router.failovers >= 1
        assert telemetry.REGISTRY.counter_total(
            "roundtable_router_failovers_total",
            replica=dead[0].name) >= 1
        # containment: the survivor admits new sessions immediately
        _m, toks, term = read_stream(
            gw.port, "/v1/discussions",
            {"session": "post-chaos", "max_new_tokens": 4,
             "turns": [{"knight": "lancelot", "prompt": PROMPT}]})
        assert term["type"] == "retired"
    finally:
        gw.stop()
        close_fleet(router)
