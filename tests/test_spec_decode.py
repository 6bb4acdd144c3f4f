"""Speculative decoding on the shared batch (ISSUE 9 + ISSUE 13).

Covers the tentpole end to end: the n-gram self-drafter, the acceptance
rule, the static-width verify program on the PR-8 ragged seam, the
scheduler's speculative phase, the adaptive throttle, and the
acceptance-criteria sweep — greedy token parity spec-on vs spec-off vs
direct (including a mid-run join, a hang-preemption with other
sessions' accepted history intact, and a prefix-cache attach of a
transcript partially produced by accepted drafts), STRICT no-compile
across acceptance drift, and the kill-switch's zero-spec-dispatch
restoration.

ISSUE 13 adds: the `spec_decode:` dict resolution (drafter + tree
shape), the Drafter protocol (draft_paths root-branching), the tree
acceptance walk, the device-batched model/LoRA drafters on the shared
engine, tree verify through the scheduler with loaned-page private
tables (multi-node acceptance + parity + loan settlement), the
throttle's re-probe hysteresis, EOS/budget accepted-token accounting
on tree walks, and STRICT across drafter hot-swap.
"""

import threading
import time

import numpy as np
import pytest

from theroundtaible_tpu.engine import deadlines, faults
from theroundtaible_tpu.engine import spec_decode as sd
from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.kvcache import scoped_slot
from theroundtaible_tpu.engine.models.registry import get_model_config
from theroundtaible_tpu.engine.sampling import SamplingParams
from theroundtaible_tpu.engine.scheduler import SessionScheduler
from theroundtaible_tpu.engine.serving_loop import (DECODE_SEGMENT,
                                                    RaggedSeq,
                                                    build_ragged_batch,
                                                    eos_trim)
from theroundtaible_tpu.engine.spec_decode import (NGramDrafter, RowSpec,
                                                   accept_prefix)
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
    kw.setdefault("num_slots", 8)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("mesh_shape", {"data": 1, "model": 1})
    eng = InferenceEngine(cfg, **kw)
    eng.ragged_defer_min = 1  # tiny prompts must still defer (PR 8)
    return eng


@pytest.fixture(scope="module")
def spec_engine():
    eng = make_engine()
    assert eng.spec_decode, eng.spec_reason
    return eng


@pytest.fixture(scope="module")
def nospec_engine():
    """spec_decode=False config — the ROUNDTABLE_SPEC_DECODE=0
    kill-switch baseline (1-token decode, PR-8 behavior)."""
    eng = make_engine(spec_decode=False)
    assert not eng.spec_decode
    assert eng.spec_reason == "disabled:config/env"
    return eng


PROMPTS = {
    "s0": [("lancelot", "The round table met at dawn to discuss the "
                        "castle walls and the eastern gate.")],
    "s1": [("galahad", "A different discussion entirely, about dragons "
                       "and the kingdom's gold reserves."),
           ("percival", "A different discussion entirely, about dragons "
                        "and the kingdom's gold reserves. Percival "
                        "counts the coins.")],
    "s2": [("tristan", "Third topic: the harvest festival planning "
                       "session and the tournament.")],
}


def _join_mid_decode(sched, sessions, max_new=70, first_max_new=None,
                     **submit_kw):
    """Later sessions submit only once the first has LIVE rows — a
    deterministic mid-decode join (the test_ragged_attn pattern).

    `first_max_new`: the first session's own budget. With speculation
    off a lone row's plain segments are pipelined — 64 steps, and the
    next issued before the first is read — so a 70-token row may have
    its whole answer in flight before a joiner's 5 ms poll has seen it
    live, and whether the two then ever share a segment is the
    machine's speed. A test whose claim needs them to share one gives
    the first row several segments to outlive the join."""
    results, errors = {}, {}

    def run(sid, wait_active):
        try:
            if wait_active:
                deadline = time.monotonic() + 60
                while not sched._active and time.monotonic() < deadline:
                    time.sleep(0.005)
            results[sid] = sched.submit(
                sid, PROMPTS[sid],
                max_new_tokens=(max_new if wait_active
                                else first_max_new or max_new),
                **submit_kw)
        except Exception as e:  # noqa: BLE001 — asserted by callers
            errors[sid] = e

    threads = [threading.Thread(target=run, args=(sid, i > 0))
               for i, sid in enumerate(sessions)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    return results, errors


# ---------------------------------------------------------------------------
# drafter / acceptance / throttle units (host-only)
# ---------------------------------------------------------------------------


class TestDrafter:
    def test_prompt_lookup_continuation(self):
        d = NGramDrafter([1, 2, 3, 4, 5, 1, 2, 3])
        # tail gram (1,2,3) last occurred ending at 3 → what followed.
        assert d.draft(4) == [4, 5, 1, 2]
        assert d.draft(2) == [4, 5]

    def test_backoff_to_shorter_grams(self):
        d = NGramDrafter([7, 1, 9, 2, 9])
        # (2,9) never occurred before; (9,) did, ending at 3 → [2, 9].
        assert d.draft(3) == [2, 9]

    def test_tail_self_occurrence_needs_prior(self):
        # The tail gram's own occurrence carries no continuation — a
        # corpus where it never occurred earlier must not draft.
        d = NGramDrafter([1, 2, 3])
        assert d.draft(4) == []

    def test_incremental_sync_matches_fresh_build(self):
        base = [5, 6, 7, 5, 6]
        inc = NGramDrafter(base)
        inc.sync_parts(base, [7, 8, 5, 6])
        fresh = NGramDrafter(base + [7, 8, 5, 6])
        for n in (1, 2, 3, 4):
            assert inc.draft(n) == fresh.draft(n)

    @pytest.mark.parametrize("vocab,built,said", [
        (5, 40, 30), (300, 700, 64), (32000, 2500, 128), (9, 31, 12),
        (9, 32, 0), (2 ** 21 - 1, 64, 16), (2 ** 22, 64, 16)])
    def test_a_corpus_built_whole_reads_as_the_loop_indexed_it(
            self, monkeypatch, vocab, built, said):
        """ISSUE 57: the tokens a drafter is built on are indexed as
        sorted arrays from `_PACK_MIN` up; every gram of the corpus
        then reads the two ends the dict's loop holds, and every
        draft along the way is the loop's — also across the seam,
        with a gram the dict saw once and the arrays before, and
        where a token does not pack (the loop indexes it all)."""
        import random
        rng = random.Random(vocab * 1000 + built)
        toks = [rng.randrange(vocab) for _ in range(built + said)]
        if vocab > 2 ** 21:
            toks[3] = vocab                      # one that does not pack
        fast = NGramDrafter(toks[:built])
        assert (fast._sorted is not None) == (
            built >= sd._PACK_MIN and vocab <= 2 ** 21)
        monkeypatch.setattr(sd, "_PACK_MIN", 10 ** 9)
        loop = NGramDrafter(toks[:built])
        assert loop._sorted is None and len(loop._index) > 0
        for end in range(built, built + said + 1):
            fast.sync(toks[:end])
            loop.sync(toks[:end])
            assert fast.draft(4) == loop.draft(4)
            assert fast.draft_paths(4, 3) == loop.draft_paths(4, 3)
        assert all(fast._ends(g) == ends
                   for g, ends in loop._index.items())
        assert fast._ends((vocab + 1,)) is None

    def test_empty_and_bounds(self):
        assert NGramDrafter([]).draft(4) == []
        assert NGramDrafter([1, 1]).draft(0) == []
        # Single repeated token: (1,) ends at 1 (prior) → continuation.
        assert NGramDrafter([1, 1]).draft(3) == [1]


class TestAcceptance:
    def test_accept_prefix_rules(self):
        # Full acceptance rides the bonus token.
        assert accept_prefix([4, 5], [4, 5, 9]) == ([4, 5, 9], 2)
        # First mismatch emits the correction, drops the tail.
        assert accept_prefix([4, 5, 9], [4, 5, 1, 7]) == ([4, 5, 1], 2)
        # No drafts: plain 1-token decode.
        assert accept_prefix([], [7]) == ([7], 0)
        # Immediate mismatch: exactly the 1-token-decode output.
        assert accept_prefix([4], [8, 3]) == ([8], 0)

    def test_throttle_trips_below_floor_once(self):
        rs = RowSpec([1, 2, 3])
        tripped = []
        for _ in range(sd.SPEC_MIN_DISPATCHES + 2):
            tripped.append(rs.note(4, 0))
        assert tripped.count(True) == 1, "throttle must trip exactly once"
        assert rs.disabled
        assert rs.rate() == 0.0

    def test_throttle_spares_accepting_rows(self):
        rs = RowSpec([1, 2, 3])
        for _ in range(sd.SPEC_WINDOW):
            assert not rs.note(4, 3)
        assert not rs.disabled
        assert rs.rate() == pytest.approx(0.75)

    def test_zero_draft_dispatches_do_not_count(self):
        rs = RowSpec([])
        for _ in range(20):
            assert not rs.note(0, 0)
        assert not rs.disabled and not rs.recent


# ---------------------------------------------------------------------------
# batch builder: the static-width score gather
# ---------------------------------------------------------------------------


class TestScoreRows:
    def _batch(self, seqs, score_width, t_budget=64, s_max=5):
        table = np.zeros(4, np.int32)
        for s in seqs:
            s.table = table
        return build_ragged_batch(
            seqs, t_budget=t_budget, s_max=s_max, pages_per_seq=4,
            scratch_page=0, pad_id=0, page_size=16,
            score_width=score_width)

    def test_sample_rows_point_at_trailing_tokens(self):
        seqs = [RaggedSeq([9, 4, 5, 6, 7], 0, None, n_scores=5),
                RaggedSeq([3], 2, None, n_scores=1),
                RaggedSeq([1, 2, 3], 1, None, n_scores=2)]
        b = self._batch(seqs, score_width=5)
        sr = b["sample_rows"]
        assert sr.shape == (5, 5)  # (s_max, score_width) ALONE
        assert list(sr[0]) == [0, 1, 2, 3, 4]
        # 1-token row at flat row 8: pad columns repeat the last row.
        assert list(sr[1]) == [8] * 5
        # n_scores=2 of a 3-token run at rows 16..18: last two rows.
        assert list(sr[2]) == [17, 18, 18, 18, 18]
        assert b["score_width"] == 5

    def test_shape_is_composition_independent(self):
        one = self._batch([RaggedSeq([9, 4], 0, None, n_scores=2)], 5)
        many = self._batch([RaggedSeq([9, 4, 5, 6, 7], 0, None,
                                      n_scores=5),
                            RaggedSeq([3], 2, None)], 5)
        for key in ("tokens", "sample_rows", "tables", "kv_valid"):
            assert one[key].shape == many[key].shape, key

    def test_plain_batch_carries_no_sample_rows(self):
        b = self._batch([RaggedSeq([9, 4], 0, None)], 0)
        assert "sample_rows" not in b and b["score_width"] == 0

    def test_n_scores_validation(self):
        with pytest.raises(ValueError, match="n_scores"):
            self._batch([RaggedSeq([9], 0, None, n_scores=2)], 5)
        with pytest.raises(ValueError, match="score_width"):
            self._batch([RaggedSeq([9] * 8, 0, None, n_scores=7)], 5)


# ---------------------------------------------------------------------------
# engine resolution / kill-switch plumbing
# ---------------------------------------------------------------------------


class TestResolution:
    def test_spec_describe_on_paged_engine(self, spec_engine):
        info = spec_engine.describe()["spec_decode"]
        assert info["enabled"] and info["reason"] is None
        assert info["drafter"] == "ngram"
        assert info["max_draft"] == sd.DEFAULT_MAX_DRAFT

    def test_kill_switch_config(self, nospec_engine):
        info = nospec_engine.describe()["spec_decode"]
        assert not info["enabled"]
        assert info["reason"] == "disabled:config/env"

    def test_env_kill_switch_decision(self, monkeypatch):
        monkeypatch.setenv("ROUNDTABLE_SPEC_DECODE", "0")
        assert not sd.spec_enabled(None)
        assert sd.spec_enabled(True)  # explicit config wins over env
        monkeypatch.delenv("ROUNDTABLE_SPEC_DECODE")
        assert sd.spec_enabled(None)  # default ON

    def test_spec_max_draft_validation(self):
        cfg = get_model_config("tiny-gemma", **MODEL_KW)
        for bad in (0, 8):
            with pytest.raises(ValueError, match="spec_max_draft"):
                InferenceEngine(cfg, num_slots=2, kv_layout="paged",
                                mesh_shape={"data": 1, "model": 1},
                                spec_max_draft=bad)

    def test_from_config_zero_draft_surfaces_error(self):
        # spec_max_draft: 0 must raise like the constructor does, not
        # silently run with the default (falsy-check review finding).
        with pytest.raises(ValueError, match="spec_max_draft"):
            InferenceEngine.from_config({
                "model": "tiny-gemma", "max_seq_len": 512,
                "kv_layout": "paged", "num_slots": 2,
                "mesh": {"data": 1, "model": 1}, "spec_max_draft": 0})

    def test_accept_floor_env_override(self, monkeypatch):
        monkeypatch.setenv("ROUNDTABLE_SPEC_ACCEPT_FLOOR", "0.9")
        rs = RowSpec([1, 2, 3])
        # 50% acceptance sits above the default floor but below 0.9:
        # the raised floor throttles (the high-RTT operator lever).
        tripped = [rs.note(4, 2) for _ in range(sd.SPEC_MIN_DISPATCHES)]
        assert tripped[-1] is True and rs.disabled
        monkeypatch.setenv("ROUNDTABLE_SPEC_ACCEPT_FLOOR", "bogus")
        assert sd.accept_floor() == sd.SPEC_ACCEPT_FLOOR


# ---------------------------------------------------------------------------
# the scheduled speculative phase
# ---------------------------------------------------------------------------


class TestScheduledSpec:
    def _direct(self, engine, max_new=70):
        return {sid: engine.generate_batch(turns, max_new_tokens=max_new,
                                           session=sid)
                for sid, turns in PROMPTS.items()}

    @pytest.mark.scheduler
    @pytest.mark.spec_decode
    def test_greedy_parity_on_vs_off_and_direct(self, spec_engine,
                                                nospec_engine):
        """The acceptance-criteria core: 3 sessions (later ones JOIN
        mid-decode), speculation on vs off vs direct generate_batch —
        byte-identical greedy outputs, with real multi-token
        acceptance recorded in the provenance sink."""
        direct = self._direct(nospec_engine)
        sched_off = SessionScheduler(nospec_engine)
        try:
            off, err = _join_mid_decode(sched_off, ["s0", "s1", "s2"])
            assert not err, err
        finally:
            sched_off.close()
        sched_on = SessionScheduler(spec_engine)
        try:
            on, err = _join_mid_decode(sched_on, ["s0", "s1", "s2"])
            assert not err, err
            for sid in PROMPTS:
                assert on[sid][0] == off[sid][0], f"{sid} on/off diverged"
                assert on[sid][0] == direct[sid], f"{sid} vs direct"
            d = sched_on.describe()
            assert d["spec_segments"] >= 1
            assert d["completed"] == 3 and d["failed"] == 0
            info = spec_engine.spec_describe()
            assert info["accepted_tokens"] > 0
            assert info["verify_dispatches"] >= d["spec_segments"]
            # Per-request provenance rode the stats out.
            spec_stats = [on[sid][1].sched.get("spec") for sid in PROMPTS]
            assert any(s and s["accepted"] > 0 for s in spec_stats)
            # The acceptance-rate gauge is live in the registry.
            snap = telemetry.REGISTRY.snapshot_compact()
            assert any(k.startswith("roundtable_spec_acceptance_rate")
                       for k in snap), snap.keys()
        finally:
            sched_on.close()

    @pytest.mark.scheduler
    def test_kill_switch_serves_zero_spec_dispatches(self,
                                                     nospec_engine):
        """spec_decode off: ZERO verify dispatches, zero spec segments,
        no spec entries in the ragged provenance — current (PR-8)
        dispatch behavior restored exactly."""
        before = dict(nospec_engine._ragged_dispatches)
        sched = SessionScheduler(nospec_engine)
        try:
            # Two single-row sessions: the scheduler marker's guard
            # needs them in one segment, so s0 outlives s2's join.
            results, err = _join_mid_decode(
                sched, ["s0", "s2"], first_max_new=70 + 4 * 64)
            assert not err, err
            assert sched.describe()["spec_segments"] == 0
        finally:
            sched.close()
        info = nospec_engine.spec_describe()
        assert info["verify_dispatches"] == 0
        assert info["drafted_tokens"] == 0
        # Every ragged dispatch this run issued was a PLAIN one: the
        # spec flag never appears in the recent ring.
        assert all("spec" not in e
                   for e in nospec_engine.ragged_describe()["recent"])
        # ... and whatever path is new in the provenance sink after
        # this run is a plain ragged one (`before` is empty when this
        # test is the first to serve on the module's engine).
        new = nospec_engine._ragged_dispatches.keys() - before.keys()
        assert new <= {"pallas_ragged", "xla_ragged"}, new

    @pytest.mark.scheduler
    @pytest.mark.spec_decode
    def test_strict_no_compile_across_acceptance_drift(self):
        """Verify shapes come from the existing ragged token-budget
        grid + the STATIC score_width: after warmup + warm spec
        traffic, a run with different prompts (different acceptance
        patterns, mixed draft widths, throttle-eligible rows) compiles
        NOTHING (STRICT armed by the scheduler marker)."""
        from theroundtaible_tpu.engine import compile_watch

        assert compile_watch.install() != "off"
        engine = make_engine(num_slots=4)
        engine.warmup(max_prompt_tokens=256, batch_sizes=(1, 2, 4))
        sched = SessionScheduler(engine, max_rows=4)
        try:
            warm, errs = _join_mid_decode(sched, ["s0", "s1"])
            assert not errs, f"warm pass failed: {errs}"
            sched.declare_warmup_complete()
            assert compile_watch.steady_state_compiles() == 0
            results, errs = _join_mid_decode(sched, ["s0", "s1", "s2"])
            assert not errs, f"drift pass recompiled or failed: {errs}"
            assert compile_watch.steady_state_compiles() == 0
            assert sched.describe()["spec_segments"] >= 1
        finally:
            sched.close()

    @pytest.mark.scheduler
    @pytest.mark.spec_decode
    @pytest.mark.chaos
    def test_hang_preemption_keeps_accepted_history(self, spec_engine,
                                                    nospec_engine):
        """A hang fault during the speculative phase preempt-isolates
        exactly like a decode failure: the drafts in flight are
        discarded, every session re-dispatches from intact host state —
        including tokens earlier verify dispatches ACCEPTED — and the
        final outputs stay byte-identical to spec-off serving."""
        serial = {}
        for sid in ("s0", "s1"):
            serial[sid] = nospec_engine.generate_batch(
                PROMPTS[sid], max_new_tokens=150, session=sid)
        sched = SessionScheduler(spec_engine, admit_hold_s=0.3)
        try:
            reqs = {sid: sched.submit_async(sid, PROMPTS[sid],
                                            max_new_tokens=150)
                    for sid in ("s0", "s1")}
            deadline = time.monotonic() + 120
            while sched.admitted < 2 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert sched.admitted == 2, "sessions never co-admitted"
            # Let speculation make progress, then wedge one dispatch.
            while (sched.spec_segments < 2
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            faults.arm("hang", count=1, delay_s=0.1)
            out = {sid: sched.wait(req) for sid, req in reqs.items()}
            for sid in ("s0", "s1"):
                assert out[sid][0] == serial[sid], f"{sid} diverged"
            d = sched.describe()
            assert d["failed"] == 0
            assert d["preemptions"] >= 1, (
                "hang never hit a shared dispatch — raced retirement")
        finally:
            sched.close()

    @pytest.mark.scheduler
    @pytest.mark.spec_decode
    @pytest.mark.telemetry
    def test_segment_spans_say_which_kind_ran_and_what_it_committed(
            self, spec_engine):
        """ISSUE 25: armed, every scheduler segment is a span of kind
        plain | ragged | spec carrying the counts its fold produced —
        summed over the run they are the scheduler's own totals, and a
        deferred join's `admit` span says so."""
        telemetry.disarm()
        telemetry.arm()                # this test's own span buffer
        sched = SessionScheduler(spec_engine)
        t_a = time.monotonic()
        try:
            _out, err = _join_mid_decode(sched, ["s0", "s1", "s2"])
            assert not err, err
            d = sched.describe()
        finally:
            sched.close()
        spans = telemetry.spans_between(t_a, time.monotonic())
        segs = [r["attrs"] for r in spans if r["rung"] == "segment"]
        kinds = {a["kind"] for a in segs}
        assert {"ragged", "spec"} <= kinds <= {"plain", "ragged", "spec"}
        count = {k: sum(1 for a in segs if a["kind"] == k)
                 for k in ("plain", "ragged", "spec")}
        assert count == {"plain": d["segments"],
                         "ragged": d["ragged_segments"],
                         "spec": d["spec_segments"]}
        assert sum(a["decode_tokens"] for a in segs) == \
            d["segment_decode_tokens"]
        assert sum(a["prefill_tokens"] for a in segs) == \
            d["segment_prefill_tokens"] > 0
        info = spec_engine.spec_describe()
        spec = [a for a in segs if a["kind"] == "spec"]
        assert sum(a["accepted"] for a in spec) > 0
        assert all(a["accepted"] <= a["drafted"] for a in spec)
        assert sum(a["drafted"] for a in spec) <= info["drafted_tokens"]
        for a in segs:
            assert a["label"].startswith(
                "decode[b=" if a["kind"] == "plain" else "ragged[t=")
            assert 0 < a["pages_in_use"] <= spec_engine.kv.usable_pages()
            assert a["steps"] >= 1 and a["rows"] >= 1
        admits = [r["attrs"] for r in spans if r["rung"] == "admit"]
        assert len(admits) == 3
        assert sum(1 for a in admits if a["deferred"]) == \
            d["ragged_joins"] >= 1

    @pytest.mark.scheduler
    @pytest.mark.spec_decode
    @pytest.mark.prefix_cache
    def test_prefix_cache_attach_of_drafted_transcript(self,
                                                       spec_engine,
                                                       nospec_engine):
        """A transcript partially PRODUCED by accepted drafts commits
        pages the cross-session prefix cache may serve — and a new
        session attaching them decodes byte-identical to the spec-off
        world (no stale rejected bytes can be attached: commit only
        publishes pages covered by the literal committed tokens)."""
        def two_phase(engine):
            sched = SessionScheduler(engine)
            try:
                first, err = _join_mid_decode(sched, ["s1"], max_new=60)
                assert not err, err
                # The committed transcript (prompt + fed outputs) of
                # one knight — on the spec engine much of it was
                # written by verify dispatches.
                committed = list(engine.kv._slots[
                    scoped_slot("s1", "galahad")].tokens)
                follow, err = {}, {}

                def go():
                    try:
                        follow["x"] = sched.submit(
                            "fresh", [("newknight", committed)],
                            max_new_tokens=40)
                    except Exception as e:  # noqa: BLE001
                        err["x"] = e

                t = threading.Thread(target=go)
                t.start()
                t.join(timeout=240)
                assert not err, err
                return committed, follow["x"]
            finally:
                sched.close()

        committed_on, (texts_on, stats_on) = two_phase(spec_engine)
        committed_off, (texts_off, _off) = two_phase(nospec_engine)
        assert committed_on == committed_off, \
            "spec changed the committed transcript"
        assert texts_on == texts_off
        assert stats_on.prefix_reused_tokens > 0, \
            "the drafted transcript's pages never attached"
        assert spec_engine.spec_describe()["accepted_tokens"] > 0

    @pytest.mark.scheduler
    @pytest.mark.spec_decode(allow_cold=True)
    def test_throttle_disables_non_accepting_row(self, monkeypatch):
        """A drafter that is always wrong trips the per-row adaptive
        throttle: a flight-recorder event fires, the row falls back to
        1-token decode, and the output is still byte-identical (every
        correction token IS the plain-decode token)."""
        engine = make_engine(num_slots=4)
        baseline = engine.generate_batch(PROMPTS["s0"],
                                         max_new_tokens=90,
                                         session="base")
        bad = engine.cfg.vocab_size - 1

        def wrong_draft(self, max_n):
            return [bad] * max_n if len(self) else []

        monkeypatch.setattr(NGramDrafter, "draft", wrong_draft)
        events = []
        rec = telemetry.recorder()
        orig = rec.record

        def spy(kind, **fields):
            if kind == "spec_throttle":
                events.append(fields)
            return orig(kind, **fields)

        monkeypatch.setattr(rec, "record", spy)
        sched = SessionScheduler(engine)
        try:
            out, err = _join_mid_decode(sched, ["s0", "s2"], max_new=90)
            assert not err, err
            assert out["s0"][0] == baseline, "corrections diverged"
        finally:
            sched.close()
        info = engine.spec_describe()
        assert info["throttled_rows"] >= 1, "throttle never tripped"
        assert info["accepted_tokens"] == 0
        assert events, "no spec_throttle flight event"
        assert sd.accepted_seen() == 0  # allow_cold justified

    @pytest.mark.scheduler
    @pytest.mark.spec_decode(allow_cold=True)
    def test_sampled_mode_serves_through_verify(self, spec_engine):
        """Non-greedy rows run the exact-rejection-sampling verify
        program (per-position sample_token_batch) — the run completes
        and the spec path was exercised; distribution preservation is
        the module docstring's point-mass argument, asserted here only
        as 'serves without parity violations or recompiles'."""
        sp = [SamplingParams(temperature=0.8, top_k=20,
                             max_new_tokens=40)]
        sched = SessionScheduler(spec_engine)
        try:
            out, err = _join_mid_decode(
                sched, ["s0", "s2"], max_new=40,
                sampling_per_turn=sp)
            assert not err, err
            assert all(out[s][0] for s in out)
        finally:
            sched.close()


# ---------------------------------------------------------------------------
# ISSUE 13: spec_decode dict resolution / drafter protocol / tree walk
# ---------------------------------------------------------------------------


class TestSpecOptions:
    def test_bool_config_resolves_to_ngram_chain(self):
        opts = sd.SpecOptions.resolve(True)
        assert opts.drafter == "ngram" and opts.tree is None

    def test_dict_config_resolves_drafter_and_tree(self):
        opts = sd.SpecOptions.resolve(
            {"drafter": "model", "tree": {"branch": 3, "depth": 2},
             "max_draft": 5, "draft_checkpoint": "/x"})
        assert opts.drafter == "model"
        assert opts.tree == {"branch": 3, "depth": 2}
        assert opts.max_draft == 5 and opts.draft_checkpoint == "/x"

    def test_unknown_drafter_raises(self):
        with pytest.raises(ValueError, match="drafter"):
            sd.SpecOptions.resolve({"drafter": "oracle"})

    def test_tree_validation(self):
        with pytest.raises(ValueError, match="branch"):
            sd.SpecOptions.resolve({"tree": {"branch": 1}})
        with pytest.raises(ValueError, match="depth"):
            sd.SpecOptions.resolve({"tree": {"branch": 2, "depth": 0}})
        with pytest.raises(ValueError, match="tree"):
            sd.SpecOptions.resolve({"tree": [2, 2]})

    def test_lora_drafter_needs_adapter_name(self):
        with pytest.raises(ValueError, match="adapter"):
            sd.SpecOptions.resolve({"drafter": "lora"})

    def test_enabled_key_keeps_kill_switch_live(self, monkeypatch):
        monkeypatch.setenv("ROUNDTABLE_SPEC_DECODE", "0")
        assert not sd.spec_enabled({"drafter": "model"})
        assert sd.spec_enabled({"drafter": "model", "enabled": True})
        monkeypatch.delenv("ROUNDTABLE_SPEC_DECODE")
        assert not sd.spec_enabled({"enabled": False})

    def test_engine_rejects_tree_deeper_than_score_width(self):
        cfg = get_model_config("tiny-gemma", **MODEL_KW)
        with pytest.raises(ValueError, match="depth"):
            InferenceEngine(cfg, num_slots=2, kv_layout="paged",
                            mesh_shape={"data": 1, "model": 1},
                            spec_max_draft=2,
                            spec_decode={"tree": {"branch": 2,
                                                  "depth": 3}})

    def test_dict_max_draft_feeds_engine_static(self):
        cfg = get_model_config("tiny-gemma", **MODEL_KW)
        eng = InferenceEngine(cfg, num_slots=2, kv_layout="paged",
                              mesh_shape={"data": 1, "model": 1},
                              spec_decode={"max_draft": 2})
        assert eng.spec_max_draft == 2

    def test_tree_statics_are_config_functions(self):
        cfg = get_model_config("tiny-gemma", **MODEL_KW)
        eng = InferenceEngine(cfg, num_slots=4, kv_layout="paged",
                              mesh_shape={"data": 1, "model": 1},
                              spec_decode={"tree": {"branch": 2,
                                                    "depth": 3}})
        assert eng.spec_branch == 2
        assert eng.spec_s_max == 4 * 2 + 1
        assert eng.spec_copy_slots == 4 * (2 - 1)
        # Chain engines keep the PR-9 shapes exactly.
        chain = InferenceEngine(cfg, num_slots=4, kv_layout="paged",
                                mesh_shape={"data": 1, "model": 1})
        assert chain.spec_s_max == 5 and chain.spec_copy_slots == 0


class TestDraftPaths:
    def test_path0_is_byte_identical_to_chain_draft(self):
        d = NGramDrafter([1, 2, 3, 4, 5, 1, 2, 3])
        assert d.draft_paths(4, 1) == [d.draft(4)]

    def test_branches_have_distinct_roots(self):
        # The tail trigram (7,1,2) proposes -> 4 (its prior
        # occurrence); bigram backoff (1,2) proposes -> 9 — two
        # root-distinct candidate paths for the tree.
        d = NGramDrafter([7, 1, 2, 4, 1, 2, 9, 7, 1, 2])
        paths = d.draft_paths(3, 2)
        assert len(paths) == 2
        roots = [p[0] for p in paths]
        assert set(roots) == {4, 9}
        # Path 0 stays the chain draft exactly.
        assert paths[0] == d.draft(3)

    def test_single_continuation_yields_single_path(self):
        d = NGramDrafter([1, 2, 3, 4, 1, 2])
        paths = d.draft_paths(3, 3)
        assert len(paths) == 1 and paths[0][0] == 3

    def test_protocol_conformance(self):
        assert isinstance(NGramDrafter([]), sd.Drafter)


class TestAcceptTree:
    def test_greedy_walk_descends_matching_path(self):
        # Two root branches; device tokens follow path 1 for two edges
        # then diverge -> 3 committed tokens (2 accepted + correction).
        paths = [[5, 6], [9, 7]]
        props = [[9, 1, 2], [9, 7, 4]]
        emit, a, cur = sd.accept_tree(paths, props)
        assert emit == [9, 7, 4]
        assert a == 2 and cur == 1

    def test_no_matching_root_emits_correction_only(self):
        paths = [[5], [9]]
        props = [[3, 1], [3, 2]]
        emit, a, cur = sd.accept_tree(paths, props)
        assert emit == [3] and a == 0 and cur == 0

    def test_trunk_win_matches_chain_rule(self):
        paths = [[4, 5, 6]]
        props = [[4, 5, 1, 7]]
        emit, a, cur = sd.accept_tree(paths, props)
        assert (emit, a) == accept_prefix(paths[0], props[0])[0:2] \
            or (emit, a) == (list(accept_prefix(paths[0], props[0])[0]),
                             accept_prefix(paths[0], props[0])[1])
        assert emit == [4, 5, 1] and a == 2 and cur == 0

    def test_deeper_alternate_beats_short_trunk(self):
        # The trunk dies at the root; the depth-1 alternate matches and
        # its own next position provides the bonus token.
        paths = [[5, 6, 7], [8]]
        props = [[8, 0, 0, 0], [8, 2]]
        emit, a, cur = sd.accept_tree(paths, props)
        assert emit == [8, 2] and a == 1 and cur == 1


class TestReprobeHysteresis:
    def _tripped(self):
        rs = RowSpec([1, 2, 3])
        # Exactly the tripping dispatch count: a disabled row's later
        # note()s run the probe branch and would skew the module
        # reprobe counters the tests below measure relatively.
        for _ in range(sd.SPEC_MIN_DISPATCHES):
            rs.note(4, 0)
        assert rs.disabled
        return rs

    def test_throttled_row_reprobes_after_interval(self, monkeypatch):
        monkeypatch.setenv("ROUNDTABLE_SPEC_REPROBE", "4")
        rs = self._tripped()
        rs.mark_idle(10)
        assert not rs.should_draft(11)
        assert not rs.should_draft(13)
        assert rs.should_draft(14), "interval elapsed: probe must fire"
        # Armed until note(): the scheduler's probe + real call agree.
        assert rs.should_draft(14)

    def test_successful_probe_recovers_with_fresh_window(self,
                                                         monkeypatch):
        monkeypatch.setenv("ROUNDTABLE_SPEC_REPROBE", "4")
        before = sd.reprobe_recoveries_seen()
        rs = self._tripped()
        rs.mark_idle(0)
        assert rs.should_draft(4)
        rs.note(4, 3)  # probe's own acceptance clears the floor
        assert not rs.disabled, "probe must re-enable the row"
        # Fresh window: the stale all-zero history must not re-trip.
        assert rs.rate() == pytest.approx(0.75)
        assert not rs.note(4, 3)
        assert sd.reprobe_recoveries_seen() == before + 1

    def test_failed_probe_waits_a_whole_interval(self, monkeypatch):
        monkeypatch.setenv("ROUNDTABLE_SPEC_REPROBE", "4")
        before = sd.reprobes_seen()
        rs = self._tripped()
        rs.mark_idle(0)
        assert rs.should_draft(4)
        rs.note(4, 0)  # probe fails
        assert rs.disabled
        assert sd.reprobes_seen() == before + 1
        rs.mark_idle(4)
        assert not rs.should_draft(6), "failed probe must not re-arm"
        # ... and, having accepted nothing, twice as long (ISSUE 43).
        assert not rs.should_draft(8)
        assert rs.should_draft(12)


def _probe_points(th, upto, accepted=0):
    """Walk a throttled owner's clock a unit at a time, as the
    scheduler would, and answer every probe that comes due with
    `accepted` of four drafted tokens: the marks the probes came at."""
    points = []
    for n in range(1, upto + 1):
        if isinstance(th, sd.BatchThrottle):
            th.advance(1)
        if th.should_draft(n):
            points.append(n)
            th.note(4, accepted)
            th.mark_idle(n)
    return points


def _tripped(th):
    for _ in range(sd.SPEC_MIN_DISPATCHES):
        th.note(4, 0)
    assert th.disabled
    th.mark_idle(0)
    return th


BACKOFF_TURNS = [[("lancelot", "The round table met at dawn to discuss "
                               "the castle walls.")],
                 [("lancelot", "And then the eastern gate, and who "
                               "should keep its keys.")]]
MIXED_TURNS = [("lancelot", "The round table met at dawn to discuss "
                            "the castle walls."),
               ("galahad", "Who keeps the keys of the eastern gate, "
                           "and who the ledger of the granary?"),
               ("percival", "Name the three roads that lead from the "
                            "castle to the sea.")]
SEG_KEYS = ("kind", "label", "drafted", "accepted", "probes",
            "probes_accepted_none")
LIVE = []   # the scheduler _rounds is serving with, for a draft to ask


def _rounds(engine, rounds, max_new, parent=False, shared=None):
    """Requests through one scheduler, one after the other; `rounds`
    is a list of (session, turns, draft) and each `draft` is patched
    onto the n-gram drafter for its round. `parent`: the controller as
    it was before ISSUE 43 — the batch's throttle never bars a row and
    no interval grows. `shared`: the batch throttle the engine starts
    with, a fresh one if none. Returns, a round, the rows' tokens,
    the rows' `RowSpec`s and the `segment` spans' SEG_KEYS."""
    mp = pytest.MonkeyPatch()
    if parent:
        mp.setattr(sd.BatchThrottle, "asks", lambda self, *a, **k: True)
        mp.setattr(sd, "SPEC_REPROBE_CEILING", 0)
    engine.spec_batch = shared or sd.BatchThrottle(DECODE_SEGMENT)
    telemetry.disarm()
    telemetry.arm()
    sched = SessionScheduler(engine)
    LIVE[:] = [sched]
    served = []
    try:
        for session, turns, draft in rounds:
            if draft is not None:
                mp.setattr(NGramDrafter, "draft", draft)
            t_a = time.monotonic()
            req = sched.submit_async(session, turns,
                                     max_new_tokens=max_new)
            sched.wait(req)
            outs = [eos_trim(list(r.produced), engine.tokenizer.eos_id,
                             max_new) for r in req.rows]
            specs = [r.spec for r in req.rows]
            segs = [tuple(r["attrs"][k] for k in SEG_KEYS)
                    for r in telemetry.spans_between(t_a,
                                                     time.monotonic())
                    if r["rung"] == "segment"]
            served.append((outs, specs, segs))
    finally:
        sched.close()
        telemetry.disarm()
        mp.undo()
    return served


def _oracle(answers, lands=lambda k, said: True, bad=0):
    """A `draft` that knows what the model will say: for a row whose
    committed tokens are the start of one of `answers` (key -> tokens)
    it proposes that answer's continuation where `lands(key,
    len(said))`, and `bad` tokens everywhere else."""
    def draft(self, n):
        if not len(self):
            return []
        said = self._toks[self._plen:]
        for k, out in answers.items():
            if out[:len(said)] == said and lands(k, len(said)):
                return out[len(said):len(said) + n] or [bad] * n
        return [bad] * n
    return draft


class _Marked(NGramDrafter):
    """An n-gram drafter that remembers where its prompt ended."""
    __slots__ = ("_plen",)

    def __init__(self, tokens=None):
        super().__init__(tokens)
        self._plen = len(self)


class TestBackoff:
    """ISSUE 43: a throttled owner's re-probe backs off while its
    probes accept nothing, any accepted draft puts it back, and the
    rows with no verdict of their own are judged together by a
    throttle that is the engine's — it outlives the request."""

    @pytest.fixture(autouse=True)
    def marked(self, monkeypatch, spec_engine):
        monkeypatch.setattr(sd, "NGramDrafter", _Marked)
        yield
        spec_engine.spec_batch = sd.BatchThrottle(DECODE_SEGMENT)

    @staticmethod
    def _wrong(engine):
        bad = engine.cfg.vocab_size - 1
        return lambda self, n: [bad] * n if len(self) else []

    @pytest.mark.parametrize("case", [
        "never_lands", "always_lands", "success_resets",
        "survives_the_request", "lands_sometimes", "lands_among_wrong",
        "lands_after_backoff"])
    def test_backoff(self, case, spec_engine, nospec_engine):
        getattr(self, "_" + case)(spec_engine, nospec_engine)

    def _never_lands(self, spec_engine, nospec_engine):
        # A throttled row's probes, in committed tokens from the trip:
        # 16 on, then 32, 64, 128, then the ceiling of 256.
        row = _tripped(RowSpec([1, 2, 3]))
        assert _probe_points(row, 1100) == [16, 48, 112, 240, 496, 752,
                                            1008]
        assert row.interval() == sd.SPEC_REPROBE_CEILING == 256
        # The batch's, in decode steps: a segment on, then 128, 256.
        shared = _tripped(sd.BatchThrottle(DECODE_SEGMENT))
        assert _probe_points(shared, 1000) == [64, 192, 448, 704, 960]
        assert shared.counts == {"probes": 5, "probes_accepted_none": 5,
                                 "probes_backed_off": 0}
        assert not shared.asks(7, 512) and not shared.asks(7, 512)
        assert shared.counts["probes_backed_off"] == 1
        # Its ceiling is the shortest answer among the rows it is
        # asked for, so a row is asked at least once an answer; and a
        # segment in flight counts towards the interval.
        assert shared.interval() == 256
        assert not shared.asks(8, 128) and shared.interval() == 128
        shared.advance(64)
        assert not shared.asks(9, 128) and shared.asks(9, 128, ahead=64)
        # Through the scheduler: a drafter that is always wrong costs
        # verifies and changes no token — the plain scheduler's, token
        # for token — and the window of SPEC_MIN_DISPATCHES judges it
        # as it did; the probe that follows doubles the row's interval.
        # (A lone row's probes wait for a segment boundary under either
        # controller: _survives_the_request counts the verifies saved.)
        wrong, seen = self._wrong(spec_engine), []

        def peeking(drafter, n):
            seen.append(LIVE[0].describe()["probe_intervals"])
            return wrong(drafter, n)

        turns = BACKOFF_TURNS[0]
        [([base], _p, _s)] = _rounds(nospec_engine,
                                     [("nl-base", turns, None)], 400)
        [([p_got], _p, p_segs)] = _rounds(
            spec_engine, [("nl-parent", turns, wrong)], 400, parent=True)
        [([got], [row], segs)] = _rounds(
            spec_engine, [("nl", turns, peeking)], 400)
        assert got == base == p_got, "verify corrections diverged"
        spec = [a for a in segs if a[0] == "spec"]
        p_spec = [a for a in p_segs if a[0] == "spec"]
        assert row.accepted == 0 < row.drafted and row.disabled
        assert sd.SPEC_MIN_DISPATCHES < len(spec) <= len(p_spec)
        assert row.interval() == sd.SPEC_REPROBE_DISPATCHES << (
            len(spec) - sd.SPEC_MIN_DISPATCHES)
        # The scheduler's histogram of its live rows' own intervals.
        assert seen[0] == {0: 1} and seen[-1] == {16: 1}
        # The row's own window judged it, so its probes are its own
        # and none is the batch's.
        assert all(a[4:] == (0, 0) for a in spec)
        info = spec_engine.spec_describe()
        assert info["probe_interval"] == DECODE_SEGMENT
        assert info["probes"] == 0

    def _always_lands(self, spec_engine, nospec_engine):
        # Greedy, and the drafter proposes what the model will say: no
        # throttle ever engages and the programs dispatched are the
        # parent's, call for call, over both requests.
        plain = [("al-base", t, None) for t in BACKOFF_TURNS]
        answers = {i: r[0][0] for i, r in enumerate(
            _rounds(nospec_engine, plain, 40))}
        draft = _oracle(answers)
        change = _rounds(spec_engine,
                         [("al", t, draft) for t in BACKOFF_TURNS], 40)
        info = spec_engine.spec_describe()
        parent = _rounds(spec_engine,
                         [("al-parent", t, draft) for t in BACKOFF_TURNS],
                         40, parent=True)
        for i, (got, p_got) in enumerate(zip(change, parent)):
            assert got[0] == [answers[i]] == p_got[0]
            decode = [a for a in got[2] if a[0] != "ragged"]
            assert decode == [a for a in p_got[2] if a[0] != "ragged"]
            assert decode and all(a[0] == "spec" and a[2] == a[3] > 0
                                  and a[4:] == (0, 0) for a in decode)
        assert [info[k] for k in ("probes", "probes_accepted_none",
                                  "probes_backed_off", "probe_interval",
                                  "throttled_rows")][:4] == [0, 0, 0, 0]

    def _success_resets(self, spec_engine, nospec_engine):
        row = _tripped(RowSpec([1, 2, 3]))
        assert _probe_points(row, 112) == [16, 48, 112]
        assert row.interval() == 128
        # One accepted draft of a probe's twenty is under the floor:
        # the row stays throttled, and its interval is 16 again.
        assert not row.should_draft(239) and row.should_draft(240)
        assert not row.note(20, 1)
        assert row.disabled and row.interval() == 16
        row.mark_idle(240)
        assert not row.should_draft(255) and row.should_draft(256)
        # A probe at the floor recovers, with a fresh window.
        row.note(4, 1)
        assert not row.disabled and row.accepting()
        assert list(row.recent) == [(4, 1)]

    def _survives_the_request(self, spec_engine, nospec_engine):
        wrong = self._wrong(spec_engine)
        base = _rounds(nospec_engine,
                       [("sr-base", t, None) for t in BACKOFF_TURNS], 150)
        first, second = _rounds(
            spec_engine, [("sr", t, wrong) for t in BACKOFF_TURNS], 150)
        assert [first[0], second[0]] == [r[0] for r in base]
        # The first request fills the window and trips the row's
        # throttle and the batch's with it; the second request's row
        # is new and has no verdict, so it waits on the batch's
        # probes — every verify of it is one, and accepts nothing.
        spec1, spec2 = ([a for a in r[2] if a[0] == "spec"]
                        for r in (first, second))
        assert len(spec1) >= sd.SPEC_MIN_DISPATCHES
        assert all(a[4:] == (0, 0) for a in spec1)
        assert spec2 and all(a[4:] == (1, 1) for a in spec2)
        info = spec_engine.spec_describe()
        # The parent's new row fills its own window first.
        _p1, p_second = _rounds(
            spec_engine, [("sr-p", t, wrong) for t in BACKOFF_TURNS], 150,
            parent=True)
        assert len(spec2) < sd.SPEC_MIN_DISPATCHES <= sum(
            a[0] == "spec" for a in p_second[2])
        assert info["probes"] == len(spec2) == info[
            "probes_accepted_none"]
        # ... each doubling the interval, up to the answer's length.
        assert info["probe_interval"] == min(
            DECODE_SEGMENT << len(spec2), 150) == 150
        assert info["probes_backed_off"] > 0
        assert set(info) <= set(
            telemetry.SURFACE_BINDINGS["engine_spec_decode"]) | {
                "enabled", "reason", "max_draft", "recent"}

    @staticmethod
    def _third(_key, n):
        return n % 3 == 0

    def _lands_sometimes(self, spec_engine, nospec_engine):
        # A lone row whose drafts land on some verifies and miss on
        # others, above the floor: the controller never engages, and
        # drafts, acceptances and programs are the parent's.
        turns = BACKOFF_TURNS[0]
        [([base], _p, _s)] = _rounds(nospec_engine,
                                     [("ls-base", turns, None)], 120)
        draft = _oracle({0: base}, self._third,
                        spec_engine.cfg.vocab_size - 1)
        [change] = _rounds(spec_engine, [("ls", turns, draft)], 120)
        info = spec_engine.spec_describe()
        [parent] = _rounds(spec_engine, [("ls-parent", turns, draft)],
                           120, parent=True)
        assert change[0] == [base] == parent[0]
        [row], [p_row] = change[1], parent[1]
        assert 0.2 <= row.accepted / row.drafted < 0.8
        assert (row.drafted, row.accepted) == (p_row.drafted,
                                               p_row.accepted)
        assert change[2] == parent[2]
        assert info["probes_backed_off"] == 0 == info["probe_interval"]

    def _lands_among_wrong(self, spec_engine, nospec_engine):
        # The same row in one batch with two rows whose drafts never
        # land: their windows throttle them (and the batch's with
        # them), and the row whose drafts land loses nothing — it
        # drafts and accepts what it did under the parent.
        [(base, _p, _s)] = _rounds(nospec_engine,
                                   [("lw-base", MIXED_TURNS, None)], 120)
        firsts = [b[0] for b in base]
        i = next(i for i, f in enumerate(firsts) if firsts.count(f) == 1)
        draft = _oracle({i: base[i]}, self._third,
                        spec_engine.cfg.vocab_size - 1)
        [change] = _rounds(spec_engine, [("lw", MIXED_TURNS, draft)], 120)
        info = spec_engine.spec_describe()
        [parent] = _rounds(spec_engine,
                           [("lw-parent", MIXED_TURNS, draft)], 120,
                           parent=True)
        assert change[0] == base == parent[0]
        row, p_row = change[1][i], parent[1][i]
        assert (row.drafted, row.accepted) == (p_row.drafted,
                                               p_row.accepted)
        assert row.accepted > 0 and not row.disabled
        assert all(r.accepted == 0 < r.drafted and r.disabled
                   for r in change[1] if r is not row)
        assert info["probe_interval"] > 0
        n_spec, p_spec = (sum(a[0] == "spec" for a in r[2])
                          for r in (change, parent))
        assert n_spec <= p_spec

    def _lands_after_backoff(self, spec_engine, nospec_engine):
        # What a row with no verdict can lose: it joins an engine whose
        # batch throttle non-accepting traffic has backed off a moment
        # ago, and though every draft of it would land it is not
        # asked before the batch's next probe. That
        # probe lands, the throttle recovers, and from there it is the
        # parent's row.
        turns = BACKOFF_TURNS[1]
        [([base], _p, _s)] = _rounds(nospec_engine,
                                     [("la-base", turns, None)], 400)
        draft = _oracle({0: base})
        shared = _tripped(sd.BatchThrottle(DECODE_SEGMENT))
        shared.level = 1
        wait = shared.interval()
        assert wait == 2 * DECODE_SEGMENT
        [change] = _rounds(spec_engine, [("la", turns, draft)], 400,
                           shared=shared)
        info = spec_engine.spec_describe()
        [parent] = _rounds(spec_engine, [("la-p", turns, draft)], 400,
                           parent=True)
        assert change[0] == [base] == parent[0]
        decode = [a for a in change[2] if a[0] != "ragged"]
        first = next(i for i, a in enumerate(decode) if a[0] == "spec")
        assert decode[first][4:] == (1, 0), "the batch's probe, landed"
        assert all(a[0] == "plain" for a in decode[:first])
        assert all(a[0] == "spec" and a[2] == a[3]
                   for a in decode[first:])
        assert info["probe_interval"] == 0, "recovered"
        # The loss: the interval's tokens, decoded one a step, which
        # the parent's row drafted for from its first tick.
        assert all(a[0] == "spec" for a in parent[2] if a[0] != "ragged")
        assert sum(a[0] == "plain" for a in decode) * DECODE_SEGMENT == wait
        lost = parent[1][0].accepted - change[1][0].accepted
        assert 0 < lost <= wait


class TestTreeBatchBuilder:
    def _batch(self, seqs, copy_pairs=None, copy_slots=0):
        table = np.zeros(4, np.int32)
        for s in seqs:
            if s.table is None:
                s.table = table
        return build_ragged_batch(
            seqs, t_budget=64, s_max=5, pages_per_seq=4,
            scratch_page=0, pad_id=0, page_size=16,
            score_width=5, copy_pairs=copy_pairs, copy_slots=copy_slots)

    def test_copy_pairs_pad_with_scratch_self_copies(self):
        b = self._batch([RaggedSeq([9, 4], 0, None, n_scores=2)],
                        copy_pairs=[(3, 7)], copy_slots=3)
        assert list(b["copy_src"]) == [3, 0, 0]
        assert list(b["copy_dst"]) == [7, 0, 0]

    def test_copy_shape_is_composition_independent(self):
        one = self._batch([RaggedSeq([9, 4], 0, None, n_scores=2)],
                          copy_pairs=[], copy_slots=3)
        many = self._batch([RaggedSeq([9, 4], 0, None, n_scores=2),
                            RaggedSeq([3], 2, None)],
                           copy_pairs=[(1, 2), (3, 4)], copy_slots=3)
        assert one["copy_src"].shape == many["copy_src"].shape
        # Zero live pairs is still the SAME program: arrays present,
        # all scratch self-copies.
        assert list(one["copy_src"]) == [0, 0, 0]

    def test_copy_validation(self):
        with pytest.raises(ValueError, match="copy_slots"):
            self._batch([RaggedSeq([9], 0, None)],
                        copy_pairs=[(1, 2), (3, 4)], copy_slots=1)
        with pytest.raises(ValueError, match="copy_pairs"):
            self._batch([RaggedSeq([9], 0, None)],
                        copy_pairs=[(1, 2)], copy_slots=0)


# ---------------------------------------------------------------------------
# the scheduled tree-verify phase (ISSUE 13)
# ---------------------------------------------------------------------------


class TestScheduledTree:
    TREE = {"branch": 2, "depth": 3}

    def _run(self, spec, sessions=("s0", "s2"), max_new=70,
             num_slots=4, **kw):
        engine = make_engine(num_slots=num_slots, spec_decode=spec, **kw)
        sched = SessionScheduler(engine)
        try:
            out, err = _join_mid_decode(sched, list(sessions),
                                        max_new=max_new)
            assert not err, err
        finally:
            sched.close()
        return out, engine

    @pytest.mark.scheduler
    @pytest.mark.spec_decode(tree=True)
    def test_model_tree_multi_node_acceptance_and_parity(self):
        """The ISSUE 13 acceptance core: the draft-model proposer with
        tree verify serves byte-identical greedy outputs while
        accepting MULTI-NODE tree paths (the conftest tree guard), with
        draft dispatches and tree provenance on record."""
        off, _ = self._run(False)
        on, eng = self._run({"drafter": "model", "tree": self.TREE})
        for sid in ("s0", "s2"):
            assert on[sid][0] == off[sid][0], f"{sid} diverged"
        info = eng.spec_describe()
        assert info["drafter"] == "model"
        assert info["tree"] == self.TREE
        assert info["tree_rows"] > 0
        assert info["tree_nodes"] > info["tree_rows"]
        assert info["draft_dispatches"] > 0
        assert info["accepted_tokens"] > 0
        assert sd.tree_accepted_paths_seen() > 0
        # The drafter-labeled tree series is live in the registry.
        snap = telemetry.REGISTRY.snapshot_compact()
        assert any(k.startswith("roundtable_spec_tree_nodes_total")
                   and "drafter=model" in k for k in snap), snap.keys()

    @pytest.mark.scheduler
    @pytest.mark.spec_decode(tree=True)
    def test_lora_drafter_as_hot_swappable_adapter(self):
        """Drafting as an adapter (ISSUE 13): the draft head is a LoRA
        pair in the PR-10 store (init_std 0 -> delta exactly zero, the
        distilled-head placeholder whose proposals equal base greedy),
        resolved at construction with a residency ref, serving
        byte-identical outputs with multi-node tree acceptance."""
        off, _ = self._run(False)
        spec = {"drafter": "lora", "adapter": "drafthead",
                "tree": self.TREE}
        on, eng = self._run(
            spec, lora={"adapters": {"drafthead": {"seed": 3,
                                                   "init_std": 0.0}}})
        assert eng.spec_drafter == "lora", eng.spec_drafter_reason
        for sid in ("s0", "s2"):
            assert on[sid][0] == off[sid][0], f"{sid} diverged"
        info = eng.spec_describe()
        assert info["drafter"] == "lora"
        assert info["accepted_tokens"] > 0
        assert sd.tree_accepted_paths_seen() > 0
        # Hot-swap away releases the draft head's residency ref.
        assert eng.lora.slot_of("drafthead") is not None
        eng.set_spec_drafter("ngram")
        assert eng.spec_drafter == "ngram"
        assert eng.lora._refs.get("drafthead", 0) == 0

    @pytest.mark.spec_decode(allow_cold=True)
    def test_lora_drafter_without_store_falls_back_to_ngram(self):
        eng = make_engine(num_slots=2,
                          spec_decode={"drafter": "lora",
                                       "adapter": "ghost"})
        assert eng.spec_decode
        assert eng.spec_drafter == "ngram"
        assert "lora" in (eng.spec_drafter_reason or "")
        info = eng.spec_describe()
        assert info["drafter"] == "ngram"
        assert info["drafter_reason"] == eng.spec_drafter_reason

    @pytest.mark.scheduler
    @pytest.mark.spec_decode(tree=True)
    def test_strict_across_drafter_hot_swap_and_tree_drift(self):
        """STRICT acceptance line (ISSUE 13): warmup compiles the tree
        verify + propose programs; steady-state serving across a
        drafter hot-swap (model -> ngram -> model) and acceptance drift
        compiles NOTHING — drafter identity, tree composition and
        acceptance patterns are pure values."""
        from theroundtaible_tpu.engine import compile_watch

        assert compile_watch.install() != "off"
        engine = make_engine(num_slots=4,
                             spec_decode={"drafter": "model",
                                          "tree": self.TREE})
        engine.warmup(max_prompt_tokens=256, batch_sizes=(1, 2, 4))
        sched = SessionScheduler(engine, max_rows=4)
        try:
            warm, errs = _join_mid_decode(sched, ["s0", "s1"])
            assert not errs, f"warm pass failed: {errs}"
            sched.declare_warmup_complete()
            assert compile_watch.steady_state_compiles() == 0
            engine.set_spec_drafter("ngram")
            r1, errs = _join_mid_decode(sched, ["s2"])
            assert not errs, errs
            engine.set_spec_drafter("model")
            r2, errs = _join_mid_decode(sched, ["s0", "s1", "s2"])
            assert not errs, errs
            assert compile_watch.steady_state_compiles() == 0, \
                "drafter hot-swap or tree drift recompiled mid-serve"
        finally:
            sched.close()

    @pytest.mark.scheduler
    @pytest.mark.spec_decode
    def test_budget_truncation_counts_only_committed(self):
        """Regression mirror of the PR-9 min(a, len(emit)) fix for the
        tree walk: a row whose turn budget truncates an accepted path
        must count only COMMITTED tokens — accepted_tokens can never
        exceed the decode tokens actually served."""
        off, _ = self._run(False, max_new=5)
        on, eng = self._run({"drafter": "model", "tree": self.TREE},
                            max_new=5)
        for sid in ("s0", "s2"):
            assert on[sid][0] == off[sid][0], f"{sid} diverged"
        info = eng.spec_describe()
        # Each row commits 5 tokens total, 1 of them at admission: at
        # most 4 decode-committed tokens per row can be accepted
        # drafts.
        assert 0 < info["accepted_tokens"] <= 4 * 2

    @pytest.mark.scheduler(allow_serial=True)
    @pytest.mark.spec_decode(tree=True)
    def test_eos_inside_tree_counts_only_committed(self, monkeypatch):
        """EOS-inside-tree accounting (ISSUE 13 satellite): an accepted
        path truncated by EOS commits only the tokens up to it, and
        roundtable_spec_accepted_tokens_total moves by exactly that
        count (crafted drafter + device tokens through the REAL
        _run_spec_segment, including the loaned-page settlement of the
        winning non-trunk path)."""
        from theroundtaible_tpu.engine.sampling import SamplingParams
        from theroundtaible_tpu.engine.scheduler import _Row
        from theroundtaible_tpu.engine.spec_decode import RowSpec

        engine = make_engine(num_slots=2,
                             spec_decode={"tree": self.TREE})
        eos = engine.tokenizer.eos_id
        sched = SessionScheduler(engine)
        try:
            name = "eosrow"
            prompt = [engine.tokenizer.bos_id, 5, 6, 7]
            engine.kv.ensure_capacity(name, len(prompt) + 64,
                                      write_from=0)
            r = _Row(name=name, tokens=prompt,
                     sampling=SamplingParams(temperature=0.0),
                     max_new=20, produced=[9], last=9,
                     valid=len(prompt))
            r.spec = RowSpec(list(prompt))
            monkeypatch.setattr(
                NGramDrafter, "draft_paths",
                lambda self, n, branch=1: [[11, 12, 13],
                                           [14, eos, 15]])
            free_before = sum(len(f)
                              for f in engine.kv._free_by_replica)

            def fake_dispatch(batch):
                sw = batch["score_width"]
                out = np.zeros((engine.spec_s_max, sw), np.int32)
                # seq 0 = trunk run [9, 11, 12, 13]: the device's root
                # token is 14 -> the trunk dies immediately.
                out[0, 0] = 14
                # seq 1 = alt run [9, 14, eos, 15]: the device follows
                # the path through eos and past it.
                out[1, :4] = [14, eos, 15, 99]
                return out

            monkeypatch.setattr(engine, "_ragged_dispatch",
                                fake_dispatch)
            assert sched._run_spec_segment([r])
            # Walk accepted 3 edges on path 1, EOS truncates to 2
            # committed tokens: [14, eos].
            assert r.produced == [9, 14, eos]
            assert r.done and r.valid == len(prompt) + 2
            info = engine.spec_describe()
            assert info["accepted_tokens"] == 2, (
                "accepted must equal COMMITTED tokens, not walked "
                "edges")
            assert info["tree_nodes"] == 6 and info["tree_rows"] == 1
            # Loan settlement: the winning path's page swapped in, the
            # rest returned — no page leaked.
            free_after = sum(len(f)
                             for f in engine.kv._free_by_replica)
            assert free_after == free_before
        finally:
            sched.close()

    @pytest.mark.scheduler(allow_serial=True)
    @pytest.mark.spec_decode(allow_cold=True)
    def test_throttled_row_reprobes_through_scheduler(self, monkeypatch):
        """Throttle hysteresis satellite: an always-wrong drafter trips
        the throttle, and the row RE-PROBES every
        ROUNDTABLE_SPEC_REPROBE committed tokens instead of decoding
        1-token for the rest of its turn — outputs stay byte-identical
        (every probe's correction IS the plain-decode token)."""
        monkeypatch.setenv("ROUNDTABLE_SPEC_REPROBE", "4")
        engine = make_engine(num_slots=4)
        # Long enough that a segment BOUNDARY lands past the re-probe
        # interval with > DECODE_SEGMENT budget remaining — the probe
        # check only runs at boundaries the pipelined mini-loop
        # exposes (throttle trips at ~7 tokens; the next boundary sits
        # one 64-token segment later).
        baseline = engine.generate_batch(PROMPTS["s0"],
                                         max_new_tokens=160,
                                         session="base")
        bad = engine.cfg.vocab_size - 1
        monkeypatch.setattr(
            NGramDrafter, "draft",
            lambda self, n: [bad] * n if len(self) else [])
        before = sd.reprobes_seen()
        sched = SessionScheduler(engine)
        try:
            out, err = _join_mid_decode(sched, ["s0"], max_new=160)
            assert not err, err
            assert out["s0"][0] == baseline, "probe corrections diverged"
        finally:
            sched.close()
        assert engine.spec_describe()["throttled_rows"] >= 1, \
            "throttle never tripped"
        assert sd.reprobes_seen() > before, \
            "throttled row never re-probed"
        assert sd.reprobe_recoveries_seen() == 0

    def test_empty_probe_resolves_and_waits_interval(self, monkeypatch):
        """A probe whose drafter proposes NOTHING must resolve FAILED
        (review finding): `probing` cannot stay armed forever, or the
        row pays per-tick draft host work for the rest of its turn."""
        monkeypatch.setenv("ROUNDTABLE_SPEC_REPROBE", "4")
        rs = RowSpec([1, 2, 3])
        for _ in range(sd.SPEC_MIN_DISPATCHES):
            rs.note(4, 0)
        assert rs.disabled
        rs.mark_idle(0)
        before = sd.reprobes_seen()
        assert rs.should_draft(4) and rs.probing
        rs.probe_failed(4)  # drafter returned [] — no dispatch ran
        assert not rs.probing
        assert sd.reprobes_seen() == before + 1
        assert not rs.should_draft(6), "failed empty probe must wait"
        assert rs.should_draft(8)
        # No-op on unthrottled rows.
        fresh = RowSpec([1, 2, 3])
        fresh.probe_failed(10)
        assert not fresh.disabled and sd.reprobes_seen() == before + 1
