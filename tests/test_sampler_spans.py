"""How often the sampler's candidate pool engages, counted where the
scheduler ends a segment (ISSUE 41).

The step programs draw the pool only when a sampled row of the batch set
`top_k` or `top_p` (tests/test_engine.py: TestConditionalPool). The host
counts such rows as it ends each segment: `filtered_rows` on the
`segment` span, lifetime totals in `engine.describe()["sampler"]`, one
series.
"""

import time

import pytest

jax = pytest.importorskip("jax")

from test_index_in_flight import clean_faults, make_engine  # noqa: F401
from theroundtaible_tpu.engine.sampling import SamplingParams
from theroundtaible_tpu.engine.scheduler import SessionScheduler
from theroundtaible_tpu.utils import telemetry

TOPIC = "The round table met at dawn to weigh the harvest tithe. "
KNIGHTS = [("lancelot", TOPIC + "Lancelot speaks of the mill."),
           ("galahad", TOPIC + "Galahad speaks of the granary."),
           ("percival", TOPIC + "Percival speaks of the river toll.")]
PLAIN = SamplingParams(temperature=0.7)
FILTERED = SamplingParams(temperature=0.7, top_p=0.9)


def served(engine, session, sampling):
    """One three-knight session through a fresh scheduler, armed. → the
    scheduler's `segment` spans of that stretch."""
    was = telemetry.ACTIVE
    telemetry.disarm()
    telemetry.arm()
    sched = SessionScheduler(engine)
    t_a = time.monotonic()
    try:
        texts, _stats = sched.submit(session, KNIGHTS, max_new_tokens=12,
                                     sampling_per_turn=sampling)
        assert len(texts) == len(KNIGHTS)
    finally:
        sched.close()
        telemetry.ACTIVE = was
    return [r for r in telemetry.spans_between(t_a, time.monotonic())
            if r["rung"] == "segment" and r["attrs"].get("scheduled")]


def test_one_filtered_knight_counts_on_its_segments_and_no_other():
    engine = make_engine()
    name = engine.cfg.name

    def series():
        return telemetry.REGISTRY.counter_total(
            "roundtable_sampler_filtered_rows_total", engine=name)

    assert engine.describe()["sampler"] == {
        "segments": 0, "filtered_segments": 0, "filtered_rows": 0}
    base = series()

    plain = served(engine, "plain", [PLAIN] * 3)
    assert plain and all(s["attrs"]["filtered_rows"] == 0 for s in plain)
    assert engine.describe()["sampler"] == {
        "segments": len(plain), "filtered_segments": 0,
        "filtered_rows": 0}
    assert series() == base

    mixed = served(engine, "mixed", [PLAIN, FILTERED, PLAIN])
    counts = [s["attrs"]["filtered_rows"] for s in mixed]
    # one knight set top_p: its row, in every segment it was alive in
    assert set(counts) <= {0, 1} and sum(counts) > 0
    sampler = engine.describe()["sampler"]
    assert sampler == {
        "segments": len(plain) + len(mixed),
        "filtered_segments": sum(1 for c in counts if c),
        "filtered_rows": sum(counts)}
    assert series() - base == sampler["filtered_rows"]
    assert set(sampler) == set(
        telemetry.SURFACE_BINDINGS["engine_sampler"])
