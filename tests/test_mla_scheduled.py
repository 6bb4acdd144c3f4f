"""A model with latent pages and held experts through the scheduler, on
the CPU (the engine alone: tests/test_mla_serving.py): three knights
over three rounds with ragged joins mid-decode over a shared prefix, the
segment spans' expert counts and `latent_positions`, the prefix index
and offload tier on against off, and no compile in steady state.

Every served token is compared with the plain reference
(benchmarks/configs/mla_moe_reference.py, the EXPANDED form over whole
sequences) on the engine's own weights, as in test_mla_serving.py."""
import threading
import time

import pytest

from test_mla_serving import (make_engine, tokens_of,  # noqa: E402
                              worst_gap)

from theroundtaible_tpu.engine import compile_watch  # noqa: E402
from theroundtaible_tpu.engine.scheduler import SessionScheduler  # noqa: E402
from theroundtaible_tpu.utils import telemetry  # noqa: E402

GAP = 1e-3

KNIGHTS = ["lancelot", "galahad", "percival"]


def cue(knight, round_no):
    return [3 + ord(c) for c in f"\n[r{round_no}] {knight}: "]


def discussion(sched, eng, sid, opening, rounds=3, new=12, check=True):
    """The benchmark's own traffic at a tiny size: every round each
    knight gets the transcript and its cue; the transcript grows by
    every cue and answer. -> [(prompt, committed answer)...]."""
    transcript, served = list(opening), []
    for r in range(1, rounds + 1):
        turns = [(k, transcript + cue(k, r)) for k in KNIGHTS]
        sched.submit(sid, turns, max_new_tokens=new)
        for k, p in turns:
            name = next(n for n in eng.kv._slots
                        if n.endswith(k) and sid in n)
            answer = eng.kv._slots[name].tokens[len(p):]
            served.append((p, answer))
            transcript = transcript + cue(k, r) + answer
    if check:
        for p, a in served:
            assert worst_gap(eng, p, a) < GAP
    return served


@pytest.fixture(scope="module")
def scheduled():
    eng = make_engine()
    sched = SessionScheduler(eng)
    yield eng, sched
    sched.close()


def test_three_knights_three_rounds_with_joins_over_a_shared_prefix(
        scheduled):
    eng, sched = scheduled
    telemetry.arm()
    t_a = time.monotonic()
    results, errors = {}, []

    def run(sid, seed, n_open):
        try:
            results[sid] = discussion(
                sched, eng, sid, [1] + tokens_of(seed, n_open))
        except BaseException as e:  # noqa: BLE001 — asserted below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(f"s{i}", 30 + i,
                                                  40 + 30 * i))
               for i in range(2)]
    for t in threads:
        t.start()
        time.sleep(0.3)
    for t in threads:
        t.join()
    spans = telemetry.spans_between(t_a, time.monotonic())
    telemetry.disarm()
    assert not errors, errors
    d = sched.describe()
    assert d["failed"] == 0 and d["completed"] == 6
    assert d["ragged_joins"] >= 1       # a round joined live decode rows
    # Rounds 2 and 3 prefill the knights' deltas alone: the transcript
    # so far has pages (own slot, leader pass, prefix index).
    prompts = sum(len(p) for served in results.values()
                  for p, _a in served)
    assert d["segment_prefill_tokens"] < prompts / 2
    assert eng.hybrid.describe()["share_declined"] == 0
    segs = [s["attrs"] for s in spans if s["rung"] == "segment"]
    assert all("latent_positions" in a for a in segs)
    segs = segs[1:]     # (the first span after arming sets the base)
    assert segs and all(
        {"experts_hit", "local_assignments", "expert_layer_steps",
         "latent_positions"} <= set(a) for a in segs)
    hit = sum(a["experts_hit"] for a in segs)
    assert 0 < hit <= 8 * sum(a["expert_layer_steps"] for a in segs)
    # A plain segment of `steps` steps over rows that end at `valid`
    # positions read sum(valid) + sum(valid - 1) + ... of them.
    plain = [a for a in segs if a["kind"] == "plain" and a["steps"] > 1]
    assert plain and all(
        a["latent_positions"] >= a["steps"] * a["rows"] for a in plain)
    assert eng.describe()["mla"]["latent_positions"] \
        >= sum(a["latent_positions"] for a in segs)
    assert telemetry.REGISTRY.counter_total(
        "roundtable_mla_latent_positions_total") > 0


def test_cache_on_serves_what_cache_off_serves(scheduled):
    eng, sched = scheduled
    opening = [1] + tokens_of(77, 60)
    on = discussion(sched, eng, "parity", opening, rounds=2, check=False)
    cold = make_engine(prefix_cache=False, kv_offload=False)
    cold_sched = SessionScheduler(cold)
    try:
        off = discussion(cold_sched, cold, "parity", opening, rounds=2,
                         check=False)
    finally:
        cold_sched.close()
    assert [a for _p, a in on] == [a for _p, a in off]


def test_no_compile_in_steady_state_across_occupancy_drift(monkeypatch):
    eng = make_engine(num_pages=256)
    eng.warmup(max_prompt_tokens=256, batch_sizes=(1, 3))
    sched = SessionScheduler(eng)

    def drift(tag, seed):
        errors = []

        def run(sid, seed):
            try:
                discussion(sched, eng, sid, [1] + tokens_of(seed, 45),
                           rounds=2, new=24, check=False)
            except BaseException as e:  # noqa: BLE001 — asserted below
                errors.append(e)

        threads = [threading.Thread(target=run,
                                    args=(f"{tag}{i}", seed + i))
                   for i in range(2)]
        for t in threads:
            t.start()
            time.sleep(0.2)
        for t in threads:
            t.join()
        assert not errors, errors

    try:
        # One discussion alone (3 rows: the 4-row decode program), then
        # the staggered pair (up to 6 rows: the 8-row one).
        discussion(sched, eng, "solo", [1] + tokens_of(49, 45), rounds=2,
                   new=24, check=False)
        drift("w", 50)
        sched.declare_warmup_complete()
        monkeypatch.setenv("ROUNDTABLE_RECOMPILE_STRICT", "1")
        before = compile_watch.steady_state_compiles()
        drift("d", 60)
        assert compile_watch.steady_state_compiles() == before, [
            e.get("label") for e in compile_watch.history()[-6:]]
        assert sched.describe()["max_occupancy"] > 3
    finally:
        sched.close()
