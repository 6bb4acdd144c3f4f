"""`sched.prologue_admit_host_ms`: the admissions that ran the prologue,
on a span list made by hand and with nothing to read. The helpers are
the loop readers' own."""
import pytest

import test_benchmark_loop_readers as loop_readers
from theroundtaible_tpu.utils import telemetry

METRIC = "sched.prologue_admit_host_ms"


def admit(t0, dur_s, sync_s, deferred):
    attrs = {"sync_s": sync_s}
    if deferred is not None:
        attrs["deferred"] = deferred
    return {"rung": "admit", "t0": t0, "dur_s": dur_s, "trace_id": "r",
            "attrs": attrs}


# Two rounds in a 10-16 s slice: each opens with a prologue admission
# and goes on with deferred joins; one admission failed before it knew
# which it was, and one prologue began before the slice.
SPANS = [
    admit(9.8, 0.30, 0.02, False),
    admit(10.5, 0.20, 0.02, False), admit(10.8, 0.01, 0.0, True),
    admit(10.9, 0.02, 0.0, True), admit(11.0, 0.5, 0.0, None),
    admit(12.5, 0.10, 0.04, False), admit(12.7, 0.03, 0.0, True),
    admit(14.5, 0.05, 0.01, False),
    {"rung": "segment", "t0": 13.0, "dur_s": 2.8, "trace_id": "s",
     "attrs": {"deferred": False}},
]


@pytest.fixture
def read(monkeypatch):
    def read(spans, **over):
        monkeypatch.setattr(
            telemetry, "spans_between",
            lambda a, b: [r for r in spans if a <= r["t0"] < b])
        monkeypatch.setattr(telemetry, "spans_dropped", lambda: 0)
        return loop_readers.reader(METRIC)(loop_readers.ctx(**over))
    return read


@pytest.mark.parametrize("spans,expected", [
    pytest.param(SPANS, 60.0, id="median-of-180-60-40"),
    pytest.param(SPANS[:5], 180.0, id="one-prologue"),
    pytest.param([r for r in SPANS if r["attrs"].get("deferred")],
                 None, id="deferred-joins-only"),
    pytest.param([admit(11.0, 0.01, 0.02, False)], 0.0,
                 id="sync-rounded-past-the-span"),
    pytest.param([], None, id="no-admission"),
])
def test_prologue_admissions_on_a_hand_made_span_list(read, spans,
                                                      expected):
    got = read(spans)
    assert got == (expected if expected is None
                   else pytest.approx(expected))


def test_nothing_to_read_returns_nothing(read, monkeypatch):
    assert read(SPANS, slice=None) is None
    monkeypatch.setattr(telemetry, "spans_dropped", lambda: 3)
    assert loop_readers.reader(METRIC)(loop_readers.ctx()) is None
    monkeypatch.delattr(telemetry, "spans_between")
    assert loop_readers.reader(METRIC)(loop_readers.ctx()) is None


def test_the_scheduler_marks_a_prologue_admission_not_deferred():
    """The span and the attribute the reader takes, on the program's own
    tracer: an admission into an empty batch."""
    import time

    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.models.registry import get_model_config
    from theroundtaible_tpu.engine.scheduler import SessionScheduler

    engine = InferenceEngine(
        get_model_config("tiny-gemma", max_seq_len=256), num_slots=2,
        kv_layout="paged")
    was = telemetry.ACTIVE
    telemetry.disarm()
    telemetry.arm()
    sched = SessionScheduler(engine)
    try:
        t_a = time.monotonic()
        sched.submit("s", [("k", "a knight opens the round")],
                     max_new_tokens=2)
        t_b = time.monotonic()
        got = loop_readers.reader(METRIC)(
            loop_readers.ctx(slice={"start": t_a, "end": t_b}))
        admits = [r for r in telemetry.spans_between(t_a, t_b)
                  if r["rung"] == "admit"]
    finally:
        sched.close()
        telemetry.disarm()
        if was:
            telemetry.arm()
    assert [r["attrs"]["deferred"] for r in admits] == [False]
    want = 1e3 * (admits[0]["dur_s"] - admits[0]["attrs"]["sync_s"])
    assert got == pytest.approx(want) and got > 0.0
