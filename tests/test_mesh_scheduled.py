"""SessionScheduler over InferenceEngine on a (data, model) mesh it
names: continuous batching with joins mid-decode, n-gram speculation,
the cross-session prefix cache and quantised pages, on the three meshes
of tests/test_mesh_serving.py and held to the one-device engine token
for token.

What a mesh declines is part of the case: with a data axis the ragged
join — and speculation, which verifies on it — stands down
(`ragged_reason == "mesh:data-axis"`); where the kv heads do not divide
the model axis the flat buffer is XLA's and joins keep the prologue
(`fallback_reason == "heads:model-axis"`). The case then asserts the
reason and parity on the path that serves instead.
"""

import threading

import pytest

jax = pytest.importorskip("jax")

from test_mesh_serving import (ONE_DEVICE, SHARED, build,  # noqa: F401
                               clean_faults, mesh, partitions, tag)
from test_spec_decode import PROMPTS, _join_mid_decode

from theroundtaible_tpu.engine import faults
from theroundtaible_tpu.engine.scheduler import SessionScheduler

MAX_NEW = 70  # past one DECODE_SEGMENT: later sessions find live rows


def build_sched(mesh, **kw):
    eng = build(mesh, **kw)
    eng.ragged_defer_min = 1  # tiny prompts must still defer (PR 8)
    return eng


def direct(engine):
    """What each session's round reads served alone, unscheduled."""
    return {sid: engine.generate_batch(turns, max_new_tokens=MAX_NEW,
                                       session=sid)
            for sid, turns in PROMPTS.items()}


def joined(engine):
    """s0, then s1 and s2 once it has live rows: (texts by session, the
    scheduler's describe())."""
    sched = SessionScheduler(engine)
    try:
        results, errors = _join_mid_decode(sched, ["s0", "s1", "s2"],
                                           max_new=MAX_NEW)
        assert not errors, errors
        return {sid: r[0] for sid, r in results.items()}, sched.describe()
    finally:
        sched.close()


@pytest.fixture(scope="module")
def eng(mesh):
    return build_sched(mesh)


@pytest.fixture(scope="module")
def ref():
    return build_sched(ONE_DEVICE)


@pytest.fixture(scope="module")
def want(ref):
    return direct(ref)


@pytest.fixture(scope="module")
def served(eng):
    return joined(eng)


@pytest.fixture(scope="module")
def ref_int8_pages():
    return direct(build_sched(ONE_DEVICE, kv_quant="int8"))


def has_data_axis(mesh) -> bool:
    return mesh.get("data", 1) > 1


class TestJoins:
    def test_sessions_that_join_mid_decode_match_one_device(
            self, served, want):
        texts, sched = served
        assert texts == want
        assert sched["completed"] == 3 and sched["failed"] == 0
        assert sched["max_occupancy"] > 1

    def test_joins_ride_the_ragged_path_or_say_why_not(self, eng, served,
                                                       mesh):
        _, sched = served
        info = eng.ragged_describe()
        if has_data_axis(mesh):
            assert not info["enabled"]
            assert info["reason"] == "mesh:data-axis"
            assert sched["ragged_joins"] == 0 and not info["dispatches"]
        elif not partitions(mesh):
            # The kernel cannot split 2 kv heads four ways: the flat
            # buffer is served by XLA (speculation's verify still rides
            # it) and a join keeps the prologue.
            assert info["enabled"] and info["path"] == "xla_ragged"
            assert info["fallback_reason"] == "heads:model-axis"
            assert sched["ragged_joins"] == 0
            assert set(info["dispatches"]) == {"xla_ragged"}
        else:
            assert info["enabled"] and info["path"] == "pallas_ragged"
            assert info["fallback_reason"] is None
            assert sched["ragged_joins"] >= 1
            assert set(info["dispatches"]) == {"pallas_ragged"}

    def test_ngram_speculation_on_against_off(self, eng, served, mesh):
        """The same three sessions with the drafter off read the same
        tokens; on a mesh where it runs, it really accepted some."""
        off = build_sched(mesh, spec_decode=False)
        texts_off, sched_off = joined(off)
        texts_on, sched_on = served
        assert texts_on == texts_off
        assert sched_off["spec_segments"] == 0
        assert off.spec_describe()["verify_dispatches"] == 0
        info = eng.spec_describe()
        if has_data_axis(mesh):
            assert not info["enabled"]
            assert info["reason"] == "ragged:mesh:data-axis"
            assert sched_on["spec_segments"] == 0
        else:
            assert info["enabled"] and info["reason"] is None
            assert sched_on["spec_segments"] >= 1
            assert info["accepted_tokens"] > 0

    def test_int8_pages_match_the_one_device_int8_pages(
            self, mesh, ref_int8_pages):
        q = build_sched(mesh, kv_quant="int8")
        assert q.kv_quant_spec is not None, q.kv_quant_reason
        assert q.kv.pools[0][0].dtype == jax.numpy.int8
        texts, sched = joined(q)
        assert texts == ref_int8_pages
        assert sched["failed"] == 0
        assert sum(q.kv_quant_describe()["dispatches"].values()) > 0


class TestSessions:
    def test_prefix_cache_hit_across_sessions(self, eng, ref, mesh):
        """A session admitted after another has retired takes the
        preamble's pages from the index."""
        a = [("percival", tag(mesh) + SHARED + "Percival files the "
                                                "first scouting report.")]
        b = [("bors", tag(mesh) + SHARED + "Bors demands a second "
                                           "opinion on the walls.")]
        hits = eng.prefix_cache.hits
        sched = SessionScheduler(eng)
        try:
            sched.submit("pcA", a, max_new_tokens=12)
            texts, stats = sched.submit("pcB", b, max_new_tokens=12)
        finally:
            sched.close()
        assert stats.prefix_reused_tokens >= 64
        assert eng.prefix_cache.hits > hits
        assert texts == ref.generate_batch(b, max_new_tokens=12,
                                           session="pcB")

    def test_a_second_round_prefills_only_the_delta(self, eng, ref, mesh):
        base = tag(mesh) + "round one says the store needs an event log."
        ext = base + " round two asks for sizing estimates."
        sched = SessionScheduler(eng)
        try:
            sched.submit("rounds", [("gawain", base)], max_new_tokens=12)
            texts, stats = sched.submit("rounds", [("gawain", ext)],
                                        max_new_tokens=12)
        finally:
            sched.close()
        assert stats.reused_tokens > 0
        assert texts == ref.generate_batch([("gawain", ext)],
                                           max_new_tokens=12,
                                           session="rounds-fresh")

    @pytest.mark.chaos
    def test_dispatch_fault_retried_in_place(self, eng, ref, mesh):
        turns = [("kay", tag(mesh) + "a round whose first dispatch "
                                     "fails"),
                 ("bedivere", tag(mesh) + "and the knight beside it")]
        spec = faults.arm("dispatch", count=1)
        sched = SessionScheduler(eng)
        try:
            texts, _ = sched.submit("faulted", turns, max_new_tokens=12)
            assert sched.describe()["failed"] == 0
        finally:
            sched.close()
        assert spec.fired == 1
        assert texts == ref.generate_batch(turns, max_new_tokens=12,
                                           session="faulted")

    def test_concurrent_sessions_share_a_decode_batch(self, eng, ref,
                                                      mesh):
        rounds = {f"cc{i}": [(f"knight{i}", tag(mesh) + f"session {i} "
                              "asks its own question of the table")]
                  for i in range(3)}
        sched = SessionScheduler(eng, admit_hold_s=0.3)
        results, errors = {}, {}

        def run(sid):
            try:
                results[sid] = sched.submit(sid, rounds[sid],
                                            max_new_tokens=24)
            except Exception as e:  # noqa: BLE001 — asserted below
                errors[sid] = e

        try:
            threads = [threading.Thread(target=run, args=(sid,))
                       for sid in rounds]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=240)
            assert not errors, errors
            assert sched.describe()["max_occupancy"] > 1
        finally:
            sched.close()
        for sid, turns in rounds.items():
            assert results[sid][0] == ref.generate_batch(
                turns, max_new_tokens=24, session=sid)
