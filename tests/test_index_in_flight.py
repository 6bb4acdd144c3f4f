"""The n-gram prompt index an admission no longer builds (ISSUE 30).

Indexing a row's prompt for its n-gram drafter is most of a join's host
time at transcript lengths, and nothing needs it before the row's first
draft. So a row is admitted without it (`RowSpec(kind=)`, `drafter is
None`), and the scheduler builds what rows owe between a segment's
dispatch and its blocking read, a row at a time while that segment
still runs (`_index_in_flight`, `describe()["indexed_in_flight"]`); a
row no segment got to is indexed at its first draft (`_spec_drafts`,
`describe()["indexed_at_draft"]`).

Everything is deterministic on the CPU: where a test needs a segment
that is still running, or one that has ended, it hands
`_index_in_flight` a stand-in for the device array.
"""

import time

import pytest

jax = pytest.importorskip("jax")

from theroundtaible_tpu.engine import deadlines, faults
from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.models.registry import get_model_config
from theroundtaible_tpu.engine.scheduler import SessionScheduler
from theroundtaible_tpu.utils import telemetry

MODEL_KW = dict(max_seq_len=512)


@pytest.fixture(autouse=True)
def clean_faults():
    faults.disarm()
    deadlines.reset_rungs()
    deadlines.disarm_watchdog()
    deadlines.end_drain()
    yield
    faults.disarm()
    deadlines.reset_rungs()
    deadlines.disarm_watchdog()
    deadlines.end_drain()


def make_engine(**kw):
    cfg = get_model_config("tiny-gemma", **MODEL_KW)
    kw.setdefault("num_slots", 12)       # a round of five, two rows each
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("mesh_shape", {"data": 1, "model": 1})
    kw.setdefault("num_pages", 96)
    eng = InferenceEngine(cfg, **kw)
    # Tiny prompts are warm joins under the production threshold: force
    # deferral so a join fills through ragged segments.
    eng.ragged_defer_min = 1
    return eng


@pytest.fixture(scope="module")
def ragged_engine():
    eng = make_engine()
    assert eng.ragged_path == "pallas_ragged" and eng.spec_decode
    return eng


@pytest.fixture(scope="module")
def direct_engine():
    """The same model with the ragged seam off: the baseline that serves
    each request alone, through the prologue, and never drafts."""
    return make_engine(ragged_attn=False)


TOPIC = ("The round table met at dawn to discuss the castle walls and "
         "the eastern gate. ")
ROUND = {f"d{i}": [("lancelot", TOPIC + f"Discussion {i} turns to "
                    + "the matter of the moat and its keepers. " * (i + 1)),
                   ("galahad", TOPIC + f"Discussion {i} turns to "
                    + "the price of oats this winter. " * (i + 1))]
         for i in range(5)}


def direct(engine, sid, turns, max_new):
    return engine.generate_batch(turns, max_new_tokens=max_new,
                                 session=sid)


def serve_round(sched, tag, max_new=24):
    """ROUND, all five queued behind a closed gate and admitted by one
    tick. → {session: (texts, stats)} in ROUND's order."""
    sched.pause_admission("line up")
    reqs = {tag + sid: sched.submit_async(tag + sid, turns,
                                          max_new_tokens=max_new)
            for sid, turns in ROUND.items()}
    sched.reopen_admission()
    return {name: sched.wait(r) for name, r in reqs.items()}


class Segment:
    """A device array's `is_ready`, for a segment that ends after the
    host has asked `asks` times."""

    def __init__(self, asks):
        self.asks = asks

    def is_ready(self):
        self.asks -= 1
        return self.asks < 0


def rows_of(kinds):
    from theroundtaible_tpu.engine.sampling import SamplingParams
    from theroundtaible_tpu.engine.scheduler import _Row
    from theroundtaible_tpu.engine.spec_decode import RowSpec

    return [_Row(name=f"u{i}", tokens=[5, 6, 7, 9 + i, 5, 6],
                 sampling=SamplingParams(temperature=0.0), max_new=4,
                 spec=RowSpec(kind=kind) if kind else None)
            for i, kind in enumerate(kinds)]


@pytest.fixture
def idle_sched(ragged_engine):
    """A scheduler's methods alone: its loop has been stopped."""
    sched = SessionScheduler(ragged_engine)
    sched.close()
    return sched


class TestIndexInFlight:
    def test_a_row_spec_without_a_prompt_has_no_index(self):
        from theroundtaible_tpu.engine.spec_decode import RowSpec

        assert RowSpec(kind="ngram").drafter is None
        assert RowSpec(kind="model").drafter is None
        assert len(RowSpec([]).drafter) == 0
        assert RowSpec([1, 2, 3]).drafter.draft(2) == []

    @pytest.mark.parametrize("asks,built", [(0, 0), (1, 1), (2, 2),
                                            (9, 3)])
    def test_oldest_first_while_the_segment_runs(self, idle_sched, asks,
                                                 built):
        """A row at a time, for as long as the segment in flight has not
        ended; a device drafter's row and a spec-less row owe none."""
        rows = rows_of(["ngram", "model", "ngram", None, "ngram"])
        idle_sched._active = rows
        owing = [rows[0], rows[2], rows[4]]
        assert idle_sched._owes_index() == owing
        idle_sched._index_in_flight(Segment(asks))
        assert idle_sched._owes_index() == owing[built:]
        assert idle_sched.describe()["indexed_in_flight"] == built
        for r in owing[:built]:
            assert len(r.spec.drafter) == len(r.tokens)
            assert r.spec.drafter.draft(1) == [7]     # after `5, 6`

    def test_the_index_is_a_copy_of_the_prompt(self, idle_sched):
        (row,) = idle_sched._active = rows_of(["ngram"])
        idle_sched._index_in_flight(Segment(9))
        row.spec.drafter.extend([7])
        assert row.tokens == [5, 6, 7, 9, 5, 6]

    def test_what_is_no_device_array_has_ended(self, idle_sched):
        """A handle that cannot say whether its segment runs (a test's
        numpy stand-in) indexes nothing: the first draft will."""
        idle_sched._active = rows_of(["ngram"])
        idle_sched._index_in_flight(object())
        assert len(idle_sched._owes_index()) == 1
        assert idle_sched.describe()["indexed_in_flight"] == 0

    def test_nothing_owed_leaves_the_loop_clock_alone(self, idle_sched):
        idle_sched._active = rows_of(["model", None])
        before = idle_sched._clock.phase
        asked = Segment(9)
        idle_sched._index_in_flight(asked)
        assert asked.asks == 9 and idle_sched._clock.phase == before

    def test_the_runners_phase_comes_back(self, idle_sched):
        idle_sched._active = rows_of(["ngram", "ngram"])
        idle_sched._clock.mark("build")
        a = idle_sched._clock.snapshot()["admit"]
        idle_sched._index_in_flight(Segment(9))
        assert idle_sched._clock.phase == "build"
        assert idle_sched._clock.snapshot()["admit"] > a


class TestServedRound:
    @pytest.mark.scheduler
    @pytest.mark.ragged_attn
    @pytest.mark.parametrize("asks,where", [
        pytest.param(1 << 30, "indexed_in_flight", id="under-a-segment"),
        pytest.param(0, "indexed_at_draft", id="at-the-first-draft")])
    def test_admission_builds_no_index_and_the_tokens_are_the_same(
            self, ragged_engine, direct_engine, monkeypatch, asks, where):
        """Rows leave `_start_request` owing their index. With segments
        that still run when the host looks, every index is built under
        one; with segments that have always ended, every index is built
        by the row's first draft. Either way the greedy tokens are the
        ones the direct engine gives the same requests one at a time."""
        tag = f"{where[-5:]}-"
        want = {tag + sid: direct(direct_engine, tag + sid, turns, 24)
                for sid, turns in ROUND.items()}
        sched = SessionScheduler(ragged_engine)
        start, index = sched._start_request, sched._index_in_flight
        owed_after_admission = []

        def started(req, admit=None):
            start(req, admit)
            owed_after_admission.extend(
                r.spec.drafter is None for r in req.rows)

        monkeypatch.setattr(sched, "_start_request", started)
        monkeypatch.setattr(sched, "_index_in_flight",
                            lambda handle: index(Segment(asks)))
        try:
            got = serve_round(sched, tag)
            d = sched.describe()
        finally:
            sched.close()
        assert owed_after_admission == [True] * 10
        other = ({"indexed_in_flight", "indexed_at_draft"} - {where}).pop()
        assert d[where] == 10 and d[other] == 0
        assert d["spec_segments"] > 0 and d["failed"] == 0
        assert {name: texts for name, (texts, _s) in got.items()} == want

    @pytest.mark.scheduler
    @pytest.mark.ragged_attn
    def test_every_drafting_row_is_indexed_once(self, ragged_engine):
        """Wherever the machine's speed puts each build, a row is
        indexed exactly once."""
        sched = SessionScheduler(ragged_engine)
        try:
            serve_round(sched, "once-")
            d = sched.describe()
        finally:
            sched.close()
        assert d["indexed_in_flight"] + d["indexed_at_draft"] == 10
        assert d["completed"] == 5

    @pytest.mark.scheduler
    @pytest.mark.ragged_attn
    @pytest.mark.telemetry
    def test_the_loop_clock_still_telescopes(self, ragged_engine,
                                             monkeypatch):
        """With indices built under segments in flight the ten phases
        still sum to the loop thread's wall, and each such stretch lies
        in a `loop.admit` span between a dispatch and a read."""
        from theroundtaible_tpu.engine.scheduler import LOOP_PHASES

        telemetry.disarm()
        telemetry.arm()
        sched = SessionScheduler(ragged_engine)
        index = sched._index_in_flight
        built = []

        def indexed(handle):
            t0, n0 = time.monotonic(), sched.indexed_in_flight
            index(Segment(1 << 30))
            if sched.indexed_in_flight > n0:
                built.append((t0, time.monotonic()))

        monkeypatch.setattr(sched, "_index_in_flight", indexed)
        try:
            a, t_a = sched.describe()["loop_seconds"], time.monotonic()
            serve_round(sched, "clock-")
            time.sleep(0.3)            # the loop goes back to waiting
            b, t_b = sched.describe()["loop_seconds"], time.monotonic()
        finally:
            sched.close()
        gained = {p: b[p] - a[p] for p in LOOP_PHASES}
        assert sum(gained.values()) == pytest.approx(t_b - t_a, rel=0.01)
        assert built
        loops = [r for r in telemetry.spans_between(t_a, t_b)
                 if r["rung"] == "loop.admit"]
        for t0, t1 in built:
            assert any(t0 <= s["t0"] and s["t0"] + s["dur_s"] <= t1 + 1e-6
                       for s in loops)

    @pytest.mark.scheduler
    @pytest.mark.ragged_attn
    def test_a_join_that_fails_at_its_alias_is_packed_by_no_segment(self):
        """A pool too small for the round: the third discussion's
        laggard finds no page when its leader's span is aliased in, and
        fails alone — the tick that failed it packs none of its rows
        (they had left `_row_req`), and the rest of the round is
        served."""
        engine = make_engine(num_slots=8, num_pages=17)
        sched = SessionScheduler(engine)
        errors = {}
        try:
            sched.pause_admission("line up")
            reqs = {sid: sched.submit_async(sid, turns, max_new_tokens=24)
                    for sid, turns in ROUND.items()}
            sched.reopen_admission()
            for sid, r in reqs.items():
                try:
                    assert sched.wait(r)[0]
                except RuntimeError as e:
                    errors[sid] = str(e)
            d = sched.describe()
        finally:
            sched.close()
        assert list(errors) == ["d2"] and "exhausted" in errors["d2"]
        assert d["completed"] == 4 and d["failed"] == 1
        assert not [e for e in d["events"] if e["event"] == "loop_error"]


@pytest.mark.parametrize("counter", ["indexed_in_flight",
                                     "indexed_at_draft"])
def test_the_counters_are_bound_to_their_series(ragged_engine, counter):
    """A `describe()` key and its registry series are one store (the
    `_bump` rule), declared in SURFACE_BINDINGS."""
    series = f"roundtable_sched_{counter}_total"
    assert telemetry.SURFACE_BINDINGS["scheduler_describe"][counter] \
        == series
    name = ragged_engine.cfg.name
    before = telemetry.REGISTRY.counter_total(series, engine=name)
    sched = SessionScheduler(ragged_engine)
    try:
        sched._bump(counter)
        assert sched.describe()[counter] == 1
    finally:
        sched.close()
    assert telemetry.REGISTRY.counter_total(series, engine=name) \
        == before + 1
