"""Cross-session prefix cache + host-RAM KV offload tier (ISSUE 7).

Covers the acceptance criteria end to end on the CPU backend:
- radix-tree index invariants at the allocator layer: content-addressed
  insert/match, refcount-held pages surviving slot release, LRU eviction
  over refcount-0 nodes ONLY, reclaim-under-pool-pressure, flush/drain
  dropping the index via unref;
- engine-level token parity: sessions sharing a prefix serve
  byte-identical to cache-off runs while `prefix_reused_tokens` > 0 and
  the memory ledger reports shared pages counted once;
- scheduled 3-session × 2-knight parity (cache on vs off) through the
  continuous-batching scheduler, plus fault isolation: a hang preempting
  one session never invalidates pages another session still references;
- spill/restore round trip: an idle session spilled to host RAM resumes
  with NO re-prefill (prefill token counter unchanged vs never-spilled)
  and byte-identical outputs; under ROUNDTABLE_RECOMPILE_STRICT=1 the
  restore path compiles nothing in steady state;
- prompt assembly prefix-stability (satellite): two knights' token
  streams share the full shared-preamble prefix — without this the
  radix tree could never match across knights.
"""

import threading
import time

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
import numpy as np

from test_paging import issued, recording_copier
from theroundtaible_tpu.engine import deadlines, faults
from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.kvcache import scoped_slot
from theroundtaible_tpu.engine.models.registry import get_model_config
from theroundtaible_tpu.engine.paging import PagedKVCache
from theroundtaible_tpu.engine.prefix_cache import PrefixCache
from theroundtaible_tpu.engine.sampling import SamplingParams
from theroundtaible_tpu.engine.scheduler import SessionScheduler

MODEL_KW = dict(max_seq_len=512)
PS = 32


@pytest.fixture(autouse=True)
def clean_faults():
    faults.disarm()
    deadlines.reset_rungs()
    deadlines.disarm_watchdog()
    deadlines.clear_hang_log()
    deadlines.end_drain()
    yield
    faults.disarm()
    deadlines.reset_rungs()
    deadlines.disarm_watchdog()
    deadlines.clear_hang_log()
    deadlines.end_drain()


def make_cache(num_slots=4, max_seq=128, num_pages=None, data_size=1,
               max_pages=None):
    cfg = get_model_config("tiny-gemma", max_seq_len=max_seq)
    recorded = []
    kv = PagedKVCache(cfg, num_slots, max_seq, jnp.float32,
                      page_size=16, num_pages=num_pages,
                      copy_pages_fn=recording_copier(recorded, []),
                      data_size=data_size)
    kv._recorded_copies = recorded
    cache = PrefixCache(kv, engine="unit", max_pages=max_pages)
    kv.prefix_cache = cache
    return kv, cache


def make_engine(**kw):
    cfg = get_model_config("tiny-gemma", **MODEL_KW)
    kw.setdefault("num_slots", 8)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("page_size", PS)
    kw.setdefault("sampling",
                  SamplingParams(temperature=0.0, max_new_tokens=24))
    return InferenceEngine(cfg, **kw)


@pytest.fixture(scope="module")
def cached_engine():
    return make_engine()


@pytest.fixture(scope="module")
def plain_engine():
    """Cache-off, offload-off twin for byte-parity baselines."""
    return make_engine(prefix_cache=False, kv_offload=False)


# ~220 chars ≈ 220 byte-tokenizer tokens: comfortably inside the prompt
# budget at max_new<=96 (512-seq engines truncate past 383 there — a
# truncated head would silently destroy the shared prefix this suite
# exists to exercise) while spanning ~7 complete 32-token pages.
PREAMBLE = ("The round table convened at dawn. The rules of order are "
            "strict: every knight states a proposal, scores consensus "
            "from one to ten, and names the open points that remain. "
            "Honor the order of speech and keep the record true. ")

SESSIONS = {
    "s0": [("lancelot", PREAMBLE + "Lancelot opens on the castle walls."),
           ("galahad", PREAMBLE + "Galahad raises the matter of the "
                                  "moat and the eastern gate.")],
    "s1": [("lancelot", PREAMBLE + "Lancelot turns to the dragon "
                                   "reports from the north."),
           ("galahad", PREAMBLE + "Galahad disputes the gold-reserve "
                                  "figures sharply.")],
    "s2": [("lancelot", PREAMBLE + "Lancelot proposes a harvest "
                                   "festival tournament."),
           ("galahad", PREAMBLE + "Galahad volunteers to judge the "
                                  "melee himself.")],
}


# ---------------------------------------------------------------------------
# unit: the radix index over the allocator
# ---------------------------------------------------------------------------


@pytest.mark.prefix_cache(allow_cold=True)
class TestRadixIndex:
    def test_insert_and_match_complete_pages(self):
        kv, cache = make_cache()
        kv.acquire("a")
        kv.ensure_capacity("a", 48, write_from=0)
        kv.commit("a", list(range(40)))      # 2 complete pages of 16
        assert cache.page_count() == 2
        nodes = cache.match(list(range(40)))
        assert [n.page for n in nodes] == kv._slots["a"].pages[:2]
        # content-addressed: a diverging block matches only the prefix
        assert len(cache.match(list(range(16)) + [999] * 24)) == 1
        assert cache.match([7] * 40) == []

    def test_pages_survive_slot_release(self):
        """THE decoupling: the index holds its own pool references, so a
        retiring session unrefs — the bytes stay for the next session."""
        kv, cache = make_cache()
        kv.acquire("a")
        kv.ensure_capacity("a", 48, write_from=0)
        kv.commit("a", list(range(32)))
        pages = list(kv._slots["a"].pages)
        kv.release("a")
        assert kv.pages_in_use() == 2        # index still holds them
        for p in pages:
            assert kv.refcount(p) == 1       # exactly the index's ref

    def test_attach_aliases_into_fresh_slot(self):
        kv, cache = make_cache()
        kv.acquire("a")
        kv.ensure_capacity("a", 48, write_from=0)
        kv.commit("a", list(range(40)))
        kv.release("a")
        tokens = list(range(40)) + [500, 501]
        kv.acquire("b")
        got = cache.attach("b", tokens)
        assert got == 32                     # 2 complete pages
        assert kv._slots["b"].tokens == tokens[:32]
        assert cache.hits == 1 and cache.reused_tokens == 32
        # pure aliasing — no device copies at page-aligned lo=0
        assert not issued(kv)

    def test_attach_respects_feed_one_token_rule(self):
        kv, cache = make_cache()
        kv.acquire("a")
        kv.ensure_capacity("a", 48, write_from=0)
        kv.commit("a", list(range(32)))
        kv.release("a")
        kv.acquire("b")
        # exactly the cached stream: coverage must stop short of len
        got = cache.attach("b", list(range(32)))
        assert got == 16                     # cap // ps pages only

    def test_cow_page_primitive(self):
        """The public COW primitive (ISSUE 7: paging grows
        ref/unref/cow_page): a cross-slot share forks via device copy,
        an index-only share goes exclusive by forgetting the node, and
        an exclusive page is a no-op — pinned against drift since the
        inline COW paths share its rules."""
        kv, cache = make_cache()
        kv.acquire("a")
        kv.ensure_capacity("a", 48, write_from=0)
        kv.commit("a", list(range(32)))
        kv.acquire("b")
        cache.attach("b", list(range(32)) + [7, 8])  # alias page 0
        shared = kv._slots["b"].pages[0]
        assert kv.refcount(shared) == 3          # a + b + index
        # cross-slot share: b gets a device-copied fork
        fresh = kv.cow_page("b", 0)
        assert fresh != shared and kv._slots["b"].pages[0] == fresh
        assert kv._slots["a"].pages[0] == shared
        assert len(issued(kv)) == 1
        # index-only share: a releases; its remaining index-shared page
        # goes exclusive via forget, no copy, same id
        kv.release("b")
        p0 = kv._slots["a"].pages[0]
        assert kv.refcount(p0) == 2              # a + index
        assert kv.cow_page("a", 0) == p0
        assert not cache.holds_page(p0)
        assert len(issued(kv)) == 1              # no new copy
        # exclusive: no-op
        assert kv.cow_page("a", 0) == p0

    def test_eviction_lru_refcount0_only(self):
        kv, cache = make_cache(num_slots=4)
        kv.acquire("a")
        kv.ensure_capacity("a", 48, write_from=0)
        kv.commit("a", list(range(32)))      # a still maps its pages
        reclaimed = cache.reclaim(want=8)
        assert reclaimed == 0                # live slot refs: untouchable
        kv.release("a")
        assert cache.reclaim(want=8) == 2    # now refcount-0: evictable
        assert kv.pages_in_use() == 0
        assert cache.page_count() == 0

    def test_max_pages_cap_evicts_lru(self):
        kv, cache = make_cache(max_pages=2)
        kv.acquire("a")
        kv.ensure_capacity("a", 48, write_from=0)
        kv.commit("a", list(range(32)))
        kv.release("a")                       # a's 2 nodes: refcount-0
        kv.acquire("b")
        kv.ensure_capacity("b", 64, write_from=0)
        kv.commit("b", [900 + i for i in range(48)])  # 3 fresh pages
        # over cap: the LRU refcount-0 nodes (a's) evicted; b's own
        # nodes are live-referenced and stay
        assert cache.evictions >= 2
        assert cache.match(list(range(32))) == []
        assert len(cache.match([900 + i for i in range(48)])) == 3

    def test_alloc_pressure_reclaims_cache_pages(self):
        """_alloc_page must reclaim refcount-0 index pages before
        declaring pool exhaustion — the index borrows idle capacity, it
        never causes an OOM a cache-off run would not have had."""
        kv, cache = make_cache(num_slots=4, num_pages=9)  # 8 usable
        kv.acquire("a")
        kv.ensure_capacity("a", 64, write_from=0)         # 4 pages
        kv.commit("a", list(range(64)))
        kv.release("a")                      # 4 pages now index-only
        kv.acquire("b")
        kv.ensure_capacity("b", 128, write_from=0, pinned=("b",))
        assert len(kv._slots["b"].pages) == 8
        assert cache.page_count() < 4        # reclaimed under pressure

    def test_flush_drops_index_via_unref(self):
        """ISSUE 7 satellite: fleet.drain's flush releases slots AND the
        index — everything unrefs, pages_in_use reaches zero, nothing is
        force-freed out from under a holder."""
        kv, cache = make_cache()
        kv.acquire("a")
        kv.ensure_capacity("a", 48, write_from=0)
        kv.commit("a", list(range(40)))
        assert kv.flush() == 1
        assert kv.pages_in_use() == 0
        assert cache.page_count() == 0

    def test_ledger_counts_shared_pages_once(self):
        kv, cache = make_cache()
        kv.acquire("a")
        kv.ensure_capacity("a", 48, write_from=0)
        kv.commit("a", list(range(40)))
        kv.acquire("b")
        cache.attach("b", list(range(40)) + [7, 8, 9])
        led = kv.memory_ledger()
        # a and b alias 2 pages; pool-level in_use counts them once
        assert led["pages_in_use"] == 3
        assert led["shared_pages"] == 2
        assert led["exclusive_pages"] == 1
        assert led["prefix_cache_pages"] == 2
        # refcount-aware fragmentation: cells counted over DISTINCT
        # pages (3 pages × 16 cells, 40 covered) — not per-slot sums
        assert led["fragmentation"] == round(1.0 - 40 / 48, 3)

    def test_revive_clears_index_without_unref(self):
        kv, cache = make_cache()
        kv.acquire("a")
        kv.ensure_capacity("a", 48, write_from=0)
        kv.commit("a", list(range(32)))
        for k, v in kv.pools:
            k.delete()
            v.delete()
        assert kv.revive_if_dead() is True
        assert cache.page_count() == 0
        assert cache.match(list(range(32))) == []


# ---------------------------------------------------------------------------
# engine-level: cross-session parity + divergence COW
# ---------------------------------------------------------------------------


class TestEngineCrossSession:
    @pytest.mark.prefix_cache
    def test_cross_session_reuse_byte_identical(self, cached_engine,
                                                plain_engine):
        """A second session whose prompt shares the preamble serves from
        the index — prefix_reused_tokens > 0 — and stays byte-identical
        to the cache-off twin."""
        eng, ref = cached_engine, plain_engine
        p1 = PREAMBLE + "Percival files the first scouting report."
        p2 = PREAMBLE + "Bors demands a second opinion on the walls."
        a = eng.generate(p1, slot_name=scoped_slot("pcA", "percival"))
        ra = ref.generate(p1, slot_name=scoped_slot("pcA", "percival"))
        assert a == ra
        texts, st = eng.generate_batch_with_stats(
            [(scoped_slot("pcB", "bors"), p2)])
        rtexts, rst = ref.generate_batch_with_stats(
            [(scoped_slot("pcB", "bors"), p2)])
        assert texts == rtexts
        assert st.prefix_reused_tokens > 0
        assert st.prefill_tokens < rst.prefill_tokens
        from theroundtaible_tpu.utils import telemetry
        snap = telemetry.REGISTRY.snapshot_compact()
        assert any(k.startswith("roundtable_prefix_reused_tokens_total")
                   and v > 0 for k, v in snap.items())

    @pytest.mark.prefix_cache
    def test_divergent_write_forks_not_corrupts(self, cached_engine,
                                                plain_engine):
        """Two sessions share the preamble then diverge; the second
        session's decode writes COW — replaying the FIRST session
        afterwards still serves byte-identical (its pages were never
        written through the alias)."""
        eng, ref = cached_engine, plain_engine
        p1 = PREAMBLE + "Kay recounts the northern campaign in detail."
        p2 = PREAMBLE + "Tristan objects and proposes a naval route."
        n1, n2 = scoped_slot("divA", "kay"), scoped_slot("divB",
                                                         "tristan")
        a1 = eng.generate(p1, slot_name=n1)
        _ = eng.generate(p2, slot_name=n2)       # attaches + diverges
        # replay session A from a FRESH slot: its cached pages must be
        # bit-intact after B's COW'd writes
        a2 = eng.generate(p1, slot_name=scoped_slot("divA2", "kay"))
        r1 = ref.generate(p1, slot_name=n1)
        assert a1 == r1 and a2 == r1

    @pytest.mark.prefix_cache(allow_cold=True)
    def test_ledger_shared_pages_visible(self, cached_engine):
        led = cached_engine.kv.memory_ledger()
        assert led["prefix_cache_pages"] > 0
        d = cached_engine.describe()
        assert d["prefix_cache"]["hits"] >= 1
        assert d["prefix_cache"]["pages"] == led["prefix_cache_pages"]


# ---------------------------------------------------------------------------
# scheduled acceptance: 3 sessions × 2 knights, cache on vs off
# ---------------------------------------------------------------------------


class TestScheduledParity:
    @pytest.mark.scheduler
    @pytest.mark.prefix_cache
    def test_three_sessions_cache_on_off_parity(self, plain_engine):
        """ISSUE 7 acceptance: a 3-session × 2-knight scheduled run with
        the cache enabled produces byte-identical outputs to cache-off,
        with prefix reuse recorded and shared pages in the ledger.

        Arrival shape matters and is pinned DETERMINISTICALLY: the index
        serves sessions admitted after an earlier session COMMITTED
        (retired), so s0 runs to completion first (seeding the index)
        and s1+s2 then arrive concurrently — both attach s0's pages
        while still co-scheduling in one decode batch. Simultaneous
        cold arrivals legitimately record zero hits (nothing committed
        yet); that regime is the offered-load bench's stagger knob, not
        this test's subject."""
        baseline = {
            sid: plain_engine.generate_batch(turns, max_new_tokens=48,
                                             session=sid)
            for sid, turns in SESSIONS.items()}
        engine = make_engine()
        sched = SessionScheduler(engine, admit_hold_s=0.3)
        try:
            results, errors = {}, {}

            def run(sid):
                try:
                    results[sid] = sched.submit(sid, SESSIONS[sid],
                                                max_new_tokens=48)
                except Exception as e:  # noqa: BLE001
                    errors[sid] = e

            run("s0")                      # seeds the index at retire
            threads = [threading.Thread(target=run, args=(sid,))
                       for sid in ("s1", "s2")]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=240)
            assert not errors, errors
            reused = 0
            for sid in SESSIONS:
                texts, stats = results[sid]
                assert texts == baseline[sid], f"{sid} diverged"
                reused += stats.prefix_reused_tokens
            assert reused > 0, "no session served from the index"
            for sid in ("s1", "s2"):
                assert results[sid][1].prefix_reused_tokens > 0, (
                    f"{sid} arrived after s0's commit but never "
                    "attached")
            led = engine.kv.memory_ledger()
            assert led["shared_pages"] > 0
            assert led["prefix_cache_pages"] > 0
        finally:
            sched.close()

    @pytest.mark.scheduler
    @pytest.mark.prefix_cache
    def test_hang_preemption_never_invalidates_shared_pages(
            self, plain_engine):
        """ISSUE 7 satellite: sessions SHARING index pages, a hang
        preempting one — the others' aliased pages survive intact and
        their outputs stay byte-identical to cache-off serial runs."""
        baseline = {
            sid: plain_engine.generate_batch(turns, max_new_tokens=96,
                                             session=sid)
            for sid, turns in SESSIONS.items()}
        engine = make_engine()
        sched = SessionScheduler(engine, admit_hold_s=0.3)
        try:
            reqs = {sid: sched.submit_async(sid, SESSIONS[sid],
                                            max_new_tokens=96)
                    for sid in SESSIONS}
            deadline = time.monotonic() + 120
            while sched.admitted < 3 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert sched.admitted == 3, "sessions were never co-admitted"
            faults.arm("hang", count=1, delay_s=0.1)
            out = {sid: sched.wait(req) for sid, req in reqs.items()}
            for sid in SESSIONS:
                assert out[sid][0] == baseline[sid], f"{sid} diverged"
            d = sched.describe()
            assert d["preemptions"] >= 1, (
                "hang never hit the shared batch — test raced "
                "retirement")
            assert d["failed"] == 0
        finally:
            sched.close()


# ---------------------------------------------------------------------------
# host-RAM offload tier
# ---------------------------------------------------------------------------


class TestHostOffload:
    @pytest.mark.prefix_cache(allow_cold=True)
    def test_spill_restore_round_trip(self):
        """ISSUE 7 acceptance: spill an idle session, resume on its next
        turn with NO re-prefill (prefill counter unchanged vs a
        never-spilled twin) and byte-identical output."""
        eng = make_engine(prefix_cache=False)   # isolate the tier
        ref = make_engine(prefix_cache=False, kv_offload=False)
        sid = "off0"
        name = scoped_slot(sid, "lancelot")
        p1 = PREAMBLE + "Lancelot surveys the outer wall at length."
        p2 = p1 + " He returns at dusk with the mason's tally."
        a1 = eng.generate(p1, slot_name=name)
        r1 = ref.generate(p1, slot_name=name)
        assert a1 == r1
        pages_before = eng.kv.pages_in_use()
        assert eng.kv_offload.spill_session(sid) == 1
        assert eng.kv.pages_in_use() < pages_before
        assert name not in eng.kv.slot_names()
        assert eng.kv_offload.has(sid)
        # next turn: restored transparently inside _prepare_batch
        _, st = eng.generate_batch_with_stats([(name, p2)])
        _, rst = ref.generate_batch_with_stats([(name, p2)])
        assert st.prefill_tokens == rst.prefill_tokens, (
            "restore re-prefilled the committed prefix")
        out = eng.generate_batch([(name, p2 + " More follows.")])
        rout = ref.generate_batch([(name, p2 + " More follows.")])
        assert out == rout
        assert not eng.kv_offload.has(sid)
        assert eng.kv_offload.describe()["restores"] == 1

    @pytest.mark.prefix_cache(allow_cold=True)
    def test_spilled_bytes_round_trip_exactly(self):
        """The restored pool pages carry the SAME bytes the spilled
        pages held — checked directly on the device arrays."""
        eng = make_engine(prefix_cache=False)
        sid = "offbytes"
        name = scoped_slot(sid, "kay")
        eng.generate(PREAMBLE + "Kay takes the floor.", slot_name=name)
        state = eng.kv._slots[name]
        idx = np.asarray(state.pages)
        before = [(np.asarray(k[idx]), np.asarray(v[idx]))
                  for k, v in eng.kv.pools]
        tokens = list(state.tokens)
        eng.kv_offload.spill_session(sid)
        eng.kv_offload.restore_session(sid)
        state = eng.kv._slots[name]
        assert state.tokens == tokens
        idx = np.asarray(state.pages)
        for (kb, vb), (k, v) in zip(before, eng.kv.pools):
            np.testing.assert_array_equal(kb, np.asarray(k[idx]))
            np.testing.assert_array_equal(vb, np.asarray(v[idx]))

    @pytest.mark.prefix_cache(allow_cold=True)
    def test_restore_compiles_nothing_in_steady_state(self, monkeypatch):
        """ISSUE 7 acceptance: under ROUNDTABLE_RECOMPILE_STRICT=1 the
        spill/restore cycle is compile-free once warmup declared steady
        state (the fetch/write programs are ONE warmed shape each)."""
        monkeypatch.setenv("ROUNDTABLE_RECOMPILE_STRICT", "1")
        from theroundtaible_tpu.engine import compile_watch
        eng = make_engine(prefix_cache=False)
        sid = "offstrict"
        name = scoped_slot(sid, "bors")
        p1 = PREAMBLE + "Bors reads the levy rolls aloud."
        eng.generate(p1, slot_name=name)        # traces serving shapes
        eng.warmup(max_prompt_tokens=256, batch_sizes=(1,))
        s0 = compile_watch.summary()["steady_state_compiles"]
        eng.kv_offload.spill_session(sid)
        eng.kv_offload.restore_session(sid)
        out = eng.generate_batch([(name, p1)])
        assert isinstance(out[0], str)
        assert compile_watch.summary()["steady_state_compiles"] == s0

    @pytest.mark.prefix_cache(allow_cold=True)
    def test_intra_session_alias_survives_round_trip(self):
        """Pages aliased between a session's own knights spill their
        bytes ONCE and restore into ONE shared fresh page — the
        intra-session dedup survives instead of inflating into
        per-knight copies (review finding: sibling mappings must not
        count as external holders, or shared spans never leave HBM)."""
        eng = make_engine(prefix_cache=False)  # isolate sibling aliasing
        sid = "alias0"
        a = scoped_slot(sid, "lancelot")
        b = scoped_slot(sid, "galahad")
        shared = PREAMBLE + "The span both knights share verbatim here."
        eng.generate_batch([(a, shared + " Lancelot's own tail."),
                            (b, shared + " Galahad's rebuttal tail.")])
        kv = eng.kv
        alias = [p for p in kv._slots[a].pages
                 if p in kv._slots[b].pages]
        assert alias, "knights never aliased the shared span"
        before = kv.pages_in_use()
        assert eng.kv_offload.spill_session(sid) == 2
        # intra-session shares + index-only shares actually left HBM
        assert kv.pages_in_use() < before - len(alias)
        eng.kv_offload.restore_session(sid)
        re_alias = [p for p in kv._slots[a].pages
                    if p in kv._slots[b].pages]
        assert len(re_alias) == len(alias), (
            "restore duplicated the intra-session shared span")

    @pytest.mark.prefix_cache(allow_cold=True)
    def test_stale_record_restore_never_leaks_pages(self):
        """Review regression: a slot repopulated while its spill record
        is filed (stale) must not leak fresh pool pages at restore —
        and a RE-SPILL over the stale record must serve the NEW bytes,
        never the superseded row's (store rows are identity, old page
        ids are not)."""
        eng = make_engine(prefix_cache=False)
        ref = make_engine(prefix_cache=False, kv_offload=False)
        sid = "stale0"
        name = scoped_slot(sid, "kay")
        p1 = PREAMBLE + "Kay's first account of the border patrol."
        p2 = PREAMBLE + "Kay's second, different account entirely."
        eng.generate(p1, slot_name=name)
        eng.kv_offload.spill_session(sid)
        # repopulate the slot while the record is filed (stale record)
        out2 = eng.generate(p2, slot_name=name)
        assert out2 == ref.generate(p2, slot_name=name)
        # re-spill: supersedes the stale record with p2's bytes
        eng.kv_offload.spill_session(sid)
        baseline = eng.kv.pages_in_use()
        eng.kv_offload.restore_session(sid)
        # restored content is p2's (same-prompt repeat = full reuse)
        _, st = eng.generate_batch_with_stats([(name, p2)])
        _, rst = ref.generate_batch_with_stats([(name, p2)])
        assert st.prefill_tokens == rst.prefill_tokens
        # release everything: every page must come back to the pool
        eng.kv.flush()
        assert eng.kv.pages_in_use() == 0, "restore leaked pool pages"
        assert baseline >= 0  # anchor: baseline computed pre-restore

    @pytest.mark.prefix_cache(allow_cold=True)
    def test_drain_evacuates_kept_pages(self):
        """fleet.drain on a paged engine with spilled sessions ends at
        ZERO pages in use: the tier's kept-resident holds evacuate to
        host RAM during the flush, and the sessions still restore."""
        from theroundtaible_tpu.engine import fleet
        eng = make_engine(prefix_cache=False)
        s_idle, s_live = "evac0", "evac1"
        shared = PREAMBLE + "A span two sessions happen to share."
        n_idle = scoped_slot(s_idle, "kay")
        n_live = scoped_slot(s_live, "kay")
        out1 = eng.generate(shared, slot_name=n_idle)
        eng.generate(shared, slot_name=n_live)
        # donor sharing is intra-session only, so force a cross-session
        # alias through the allocator to create a genuinely kept page
        kv = eng.kv
        kv.adopt_span(n_live, kv._slots[n_idle].pages[:2], 0, 64,
                      pinned=(n_idle, n_live))
        eng.kv_offload.spill_session(s_idle)
        desc = eng.kv_offload.describe()
        assert desc["spilled_sessions"] == 1
        # flush (what fleet.drain does per engine) + evacuate
        assert kv.flush() >= 1
        manifest = eng.kv_offload.evacuate()
        assert kv.pages_in_use() == 0, "drain left pages resident"
        assert manifest["pages_moved"] >= 1
        assert s_idle in manifest["sessions"]
        # the evacuated session still restores byte-identical
        eng.kv_offload.restore_session(s_idle)
        out2 = eng.generate(shared, slot_name=n_idle)
        ref = make_engine(prefix_cache=False, kv_offload=False)
        assert out2 == ref.generate(shared, slot_name=n_idle)
        assert out1 == out2

    @pytest.mark.prefix_cache(allow_cold=True)
    def test_evacuate_subset_selector_byte_identity(self):
        """ISSUE 12 satellite: evacuate() with a per-session selector
        moves ONLY the targeted sessions fully to host RAM (the
        supervisor's per-engine evacuation, not fleet.drain's
        all-or-nothing shape) and returns a restorable manifest; the
        evacuated subset restores byte-identical while the untargeted
        session's pool state is untouched."""
        eng = make_engine(prefix_cache=False)
        ref = make_engine(prefix_cache=False, kv_offload=False)
        prompts = {
            "sub0": PREAMBLE + "Bedivere recounts the northern ford.",
            "sub1": PREAMBLE + "Tristan recounts the harbor watch.",
            "sub2": PREAMBLE + "Gawain recounts the long portage.",
        }
        names = {s: scoped_slot(s, "kay") for s in prompts}
        outs = {s: eng.generate(p, slot_name=names[s])
                for s, p in prompts.items()}
        kv = eng.kv
        pages_before = {s: list(kv._slots[names[s]].pages)
                        for s in prompts}
        manifest = eng.kv_offload.evacuate(["sub0", "sub1"])
        # Only the targeted subset moved: manifest names exactly them,
        # with their full host footprint accounted.
        assert sorted(manifest["sessions"]) == ["sub0", "sub1"]
        assert manifest["slots_spilled"] == 2
        assert manifest["host_bytes"] > 0
        for s in ("sub0", "sub1"):
            assert eng.kv_offload.has(s)
            assert manifest["sessions"][s]["host_rows"] > 0
        # The untargeted session never left the pool.
        assert not eng.kv_offload.has("sub2")
        assert kv._slots[names["sub2"]].pages == pages_before["sub2"]
        # The evacuated records are fully host-resident (adoptable by a
        # rebuilt engine's tier): no "kept" pool-page holds remain.
        for s in ("sub0", "sub1"):
            rec = eng.kv_offload._spilled[s]
            assert not any(kind == "kept"
                           for srec in rec.slots.values()
                           for kind, _p in srec.entries)
        # Restore the subset: byte-identical serving vs the cache-off
        # twin AND vs the pre-evacuation outputs.
        for s in ("sub0", "sub1"):
            assert eng.kv_offload.restore_session(s) >= 1
            out2 = eng.generate(prompts[s], slot_name=names[s])
            assert out2 == outs[s]
            assert out2 == ref.generate(prompts[s], slot_name=names[s])

    @pytest.mark.scheduler(allow_serial=True)
    @pytest.mark.prefix_cache(allow_cold=True)
    def test_scheduler_idle_spill_and_resume(self):
        """The scheduler's idle policy: a session idle past idle_spill_s
        spills on a tick; its next submit restores and serves with full
        prefix reuse (no re-prefill of the committed transcript)."""
        engine = make_engine(prefix_cache=False)
        sched = SessionScheduler(engine, idle_spill_s=0.3)
        try:
            sid = "idle0"
            turns = [("lancelot", PREAMBLE + "Lancelot opens round 1.")]
            texts, st1 = sched.submit(sid, turns, max_new_tokens=24)
            deadline = time.monotonic() + 30
            while (not engine.kv_offload.has(sid)
                   and time.monotonic() < deadline):
                with sched._cv:
                    sched._cv.notify_all()
                time.sleep(0.05)
            assert engine.kv_offload.has(sid), "idle session never spilled"
            assert sched.describe()["spills"] >= 1
            # resume: the committed prefix must NOT re-prefill
            turns2 = [("lancelot",
                       turns[0][1] + texts[0]
                       + " Lancelot continues in round 2.")]
            _t2, st2 = sched.submit(sid, turns2, max_new_tokens=24)
            assert not engine.kv_offload.has(sid)
            assert st2.reused_tokens > 0
        finally:
            sched.close()

    @pytest.mark.scheduler(allow_serial=True)
    @pytest.mark.prefix_cache(allow_cold=True)
    def test_pressure_spill_instead_of_eviction(self):
        """Admission under page pressure spills the least-recently-active
        idle session (its KV survives in host RAM) instead of letting
        the allocator destroy it."""
        engine = make_engine(num_slots=6, num_pages=40,
                             prefix_cache=False)
        sched = SessionScheduler(engine)
        try:
            long = PREAMBLE + "A very long opening statement. " * 6
            sched.submit("pr0", [("lancelot", long)], max_new_tokens=24)
            sched.submit("pr1", [("galahad", long)], max_new_tokens=24)
            free0 = engine.kv.free_pages()
            # a request whose estimate exceeds the free pool triggers
            # the pressure valve at admission
            sched.submit("pr2", [("bors", long), ("kay", long)],
                         max_new_tokens=24)
            spilled = engine.kv_offload.spilled_sessions()
            assert spilled, (
                f"no idle session spilled (free was {free0})")
            assert sched.describe()["spills"] >= 1
            # the spilled session still resumes cleanly
            sid = spilled[0]
            texts, st = sched.submit(
                sid, [("lancelot" if sid == "pr0" else "galahad",
                       long + " Another word.")], max_new_tokens=8)
            assert isinstance(texts[0], str)
        finally:
            sched.close()


# ---------------------------------------------------------------------------
# satellite: `roundtable status --kv` render
# ---------------------------------------------------------------------------


class TestStatusKvRender:
    def test_renders_ledger_cache_and_offload(self, tmp_path, capsys):
        import json as _json  # noqa: F401 — parity with sibling render tests
        sess = tmp_path / ".roundtable" / "sessions" / "sess-001"
        (sess / "telemetry").mkdir(parents=True)
        (sess / "telemetry" / "metrics.prom").write_text(
            'roundtable_kv_pages_in_use{engine="knight"} 12\n'
            'roundtable_kv_shared_pages{engine="knight"} 7\n'
            'roundtable_kv_exclusive_pages{engine="knight"} 5\n'
            'roundtable_prefix_cache_pages{engine="knight"} 7\n'
            'roundtable_prefix_cache_hits_total{engine="knight"} 4\n'
            'roundtable_kv_spilled_sessions{engine="knight"} 2\n'
            'roundtable_kv_host_bytes{engine="knight"} 1048576\n'
            'roundtable_session_kv_bytes{engine="knight",'
            'session="s0"} 4194304\n')
        from theroundtaible_tpu.commands.status import status_command
        rc = status_command(project_root=str(tmp_path), kv_view=True)
        out = capsys.readouterr().out
        assert rc == 0
        assert "KV tiers" in out
        assert "Memory ledger" in out
        assert "roundtable_kv_shared_pages" in out
        assert "Prefix cache" in out
        assert "roundtable_prefix_cache_hits_total" in out
        assert "Host-RAM offload tier" in out
        assert "roundtable_kv_spilled_sessions" in out
        assert "Per-session KV footprint" in out

    def test_quiet_without_any_capture(self, tmp_path, capsys):
        (tmp_path / ".roundtable" / "sessions" / "s1").mkdir(
            parents=True)
        from theroundtaible_tpu.commands.status import status_command
        rc = status_command(project_root=str(tmp_path), kv_view=True)
        assert rc == 0
        assert "KV tiers" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# satellite: prompt assembly is prefix-stable across knights
# ---------------------------------------------------------------------------


class TestPromptPrefixStability:
    def test_two_knights_share_preamble_token_prefix(self):
        """Without shared-preamble-first assembly the radix tree can
        never match across knights: the two token streams must share at
        least the full tokenized preamble."""
        from theroundtaible_tpu.core.prompt import (build_shared_preamble,
                                                    build_system_prompt)
        from theroundtaible_tpu.core.types import KnightConfig
        from theroundtaible_tpu.engine.tokenizer import load_tokenizer
        from theroundtaible_tpu.native import lcp

        knights = [
            KnightConfig(name="Claude", adapter="tpu-llm",
                         capabilities=["architecture"], priority=1),
            KnightConfig(name="GPT", adapter="tpu-llm",
                         capabilities=["shipping"], priority=2)]
        topic = "Should the session store move to an event log?"
        chronicle = "Earlier: the apply pipeline landed."
        rounds: list = []
        pre = build_shared_preamble(topic, chronicle, rounds)
        prompts = [build_system_prompt(k, knights, topic, chronicle,
                                       rounds) for k in knights]
        for p in prompts:
            assert p.startswith(pre), "knight material leaked ahead of " \
                                      "the shared preamble"
        tok = load_tokenizer(None)
        streams = [tok.encode(p) for p in prompts]
        shared = lcp(streams[0], streams[1])
        # the common token prefix covers the whole preamble (minus a
        # boundary token that may merge across the seam)
        n_pre = len(tok.encode(pre))
        assert shared >= n_pre - 1, (
            f"common prefix {shared} tokens < preamble {n_pre}")

    def test_orchestrator_turn_prompts_share_prefix(self):
        """The orchestrator's _build_turn_prompt lays the WHOLE shared
        block (preamble + shared context) ahead of every knight tail —
        pin it so a refactor cannot quietly interleave per-knight
        material into the head the radix tree matches on."""
        from types import SimpleNamespace

        from theroundtaible_tpu.core import orchestrator
        from theroundtaible_tpu.core.prompt import build_shared_preamble
        from theroundtaible_tpu.core.types import KnightConfig

        knights = [
            KnightConfig(name="Claude", adapter="tpu-llm",
                         capabilities=["architecture"], priority=1),
            KnightConfig(name="GPT", adapter="tpu-llm",
                         capabilities=["shipping"], priority=2)]
        config = SimpleNamespace(knights=knights, language="en")
        context = SimpleNamespace(
            chronicle="Earlier: the apply pipeline landed.",
            git_branch="main", git_diff="", recent_commits="",
            key_file_contents="", source_file_contents="")
        state = SimpleNamespace(all_rounds=[], resolved_files="",
                                resolved_commands="")
        topic = "Should the session store move to an event log?"
        prompts = [orchestrator._build_turn_prompt(
            k, config, topic, context, "manifest summary", "", "",
            state) for k in knights]
        expected_shared = (build_shared_preamble(
            topic, context.chronicle, [], "manifest summary", "", "en")
            + "\n" + orchestrator.assemble_shared_context(
                "", context, "", "", "en"))
        for p in prompts:
            assert p.startswith(expected_shared), (
                "knight material leaked ahead of the shared block")
        assert prompts[0] != prompts[1]  # tails actually differ
