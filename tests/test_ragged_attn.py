"""Ragged paged attention suite (ISSUE 8).

Covers the tentpole end to end on the CPU backend:
- kernel numerics: the flat-buffer ragged kernel against a dense
  reference AND against the batched paged prefill/decode kernels it
  replaces (same online-softmax accumulate, so near-exact agreement);
- the XLA fallback path (forward_ragged attn_path="xla") agreeing with
  the kernel path, and machine-readable decline reasons;
- scheduled serving: a session JOINING mid-decode-segment admits as
  ragged prefill chunks interleaved with the live decode rows — token
  parity with direct generate_batch, TTFT recorded, mixed-segment
  token-split provenance populated;
- the ROUNDTABLE_RAGGED_ATTN=0 kill-switch restoring the PR-4 prologue
  path with byte-identical outputs;
- ROUNDTABLE_RECOMPILE_STRICT staying green across an occupancy-drift +
  concurrent-admission run (prefill joins compile nothing in steady
  state — the one-compiled-shape property of the flat buffer);
- a Mosaic-failure fault degrading the ragged path to the XLA fallback
  without failing the decode batch's sessions.
"""

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from theroundtaible_tpu.engine import deadlines, faults
from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.models.registry import get_model_config
from theroundtaible_tpu.engine.pallas import attention as pattn
from theroundtaible_tpu.engine.scheduler import SessionScheduler
from theroundtaible_tpu.engine.serving_loop import (RAGGED_BLOCK_Q,
                                                    RaggedSeq,
                                                    build_ragged_batch)

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
    # Single-device mesh: the conftest exposes 8 virtual CPU devices
    # and tiny-gemma's 4 heads don't partition an 8-way model axis —
    # the kernel path would (correctly) decline. The SPMD variant is
    # covered by test_pallas_tpu_lowering's head-sharded lowering.
    kw.setdefault("mesh_shape", {"data": 1, "model": 1})
    eng = InferenceEngine(cfg, **kw)
    # Tiny test prompts would resolve back to the prologue under the
    # production defer threshold (warm joins keep the prologue) —
    # force deferral so the suite exercises the ragged path.
    eng.ragged_defer_min = 1
    return eng


@pytest.fixture(scope="module")
def ragged_engine():
    eng = make_engine()
    assert eng.ragged_enabled and eng.ragged_path == "pallas_ragged"
    return eng


@pytest.fixture(scope="module")
def prologue_engine():
    """Same config with the ragged seam killed — the PR-4 prologue
    path, the kill-switch parity baseline AND the direct baseline."""
    return make_engine(ragged_attn=False)


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


def _join_mid_decode(sched, sessions, max_new=70):
    """Submit `sessions` so later ones JOIN while the first is
    mid-decode: each non-first submitter waits until the scheduler has
    LIVE rows (the first session admitted and decoding) before
    submitting — deterministic joins instead of sleep-raced staggers.
    Returns ({sid: (texts, stats)}, {sid: err})."""
    results, errors = {}, {}

    def run(sid, wait_active):
        try:
            if wait_active:
                deadline = time.monotonic() + 60
                while not sched._active and time.monotonic() < deadline:
                    time.sleep(0.005)
            results[sid] = sched.submit(sid, PROMPTS[sid],
                                        max_new_tokens=max_new)
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
# kernel numerics
# ---------------------------------------------------------------------------


class TestRaggedKernel:
    PS, KH, G, D = 16, 2, 2, 32

    def _pool(self, rng, pages=12):
        k = jnp.asarray(rng.standard_normal(
            (pages, self.PS, self.KH, self.D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal(
            (pages, self.PS, self.KH, self.D)), jnp.float32)
        return k, v

    @pytest.mark.ragged_attn
    @pytest.mark.parametrize("softcap,window", [(None, None),
                                                (30.0, None),
                                                (None, 24)])
    def test_mixed_rows_match_dense_reference(self, softcap, window):
        """One prefill chunk + one decode row in one dispatch, checked
        per real row against a dense softmax over the gather view."""
        rng = np.random.default_rng(0)
        kpool, vpool = self._pool(rng)
        h = self.KH * self.G
        pp = 4
        tables = np.zeros((3, pp), np.int32)
        tables[0, :2] = [1, 2]
        tables[1, :3] = [3, 4, 5]
        t = 24
        q = jnp.asarray(rng.standard_normal((t, h, self.D)), jnp.float32)
        seq_of_block = np.array([0, 0, 1], np.int32)
        block_qstart = np.array([0, 8, 0], np.int32)
        query_offsets = np.array([5, 20, 0], np.int32)
        kv_valid = np.array([15, 21, 1], np.int32)

        out = np.asarray(pattn.ragged_paged_attention(
            q, kpool, vpool, jnp.asarray(tables),
            jnp.asarray(seq_of_block), jnp.asarray(block_qstart),
            jnp.asarray(query_offsets), jnp.asarray(kv_valid),
            sliding_window=window, softcap=softcap))

        def ref_row(qrow, seq, pos):
            length = pp * self.PS
            kg = np.asarray(kpool)[tables[seq]].reshape(
                length, self.KH, self.D)
            vg = np.asarray(vpool)[tables[seq]].reshape(
                length, self.KH, self.D)
            rows = []
            for hi in range(h):
                khi = hi // self.G
                s = kg[:, khi] @ qrow[hi]
                if softcap is not None:
                    s = softcap * np.tanh(s / softcap)
                lpos = np.arange(length)
                mask = (lpos <= pos) & (lpos < kv_valid[seq])
                if window is not None:
                    mask &= lpos > pos - window
                s = np.where(mask, s, -1e30)
                p = np.exp(s - s.max())
                p /= p.sum()
                rows.append(p @ vg[:, khi])
            return np.stack(rows)

        for row0, seq, pos0, n in [(0, 0, 5, 10), (16, 1, 20, 1)]:
            for j in range(n):
                ref = ref_row(np.asarray(q)[row0 + j], seq, pos0 + j)
                np.testing.assert_allclose(out[row0 + j], ref,
                                           atol=2e-5, rtol=2e-5)

    @pytest.mark.ragged_attn
    def test_matches_batched_paged_kernels(self):
        """The ragged kernel and the batched paged prefill/decode
        kernels share _prefill_accumulate page-by-page, so a chunk row
        and a decode row agree near-exactly with the kernels the
        prologue path dispatches — the numeric core of scheduled-vs-
        direct token parity."""
        rng = np.random.default_rng(1)
        kpool, vpool = self._pool(rng)
        h = self.KH * self.G
        pp = 4
        tables = np.zeros((3, pp), np.int32)
        tables[0, :2] = [1, 2]
        tables[1, :3] = [3, 4, 5]
        chunk_t, chunk_off = 8, 8      # chunk rows [8, 16) of seq 0
        q_chunk = jnp.asarray(rng.standard_normal((1, chunk_t, h, self.D)),
                              jnp.float32)
        q_dec = jnp.asarray(rng.standard_normal((1, 1, h, self.D)),
                            jnp.float32)

        ref_chunk = np.asarray(pattn.paged_prefill_attention(
            q_chunk, kpool, vpool, jnp.asarray(tables[:1]),
            jnp.asarray([chunk_off]), jnp.asarray([16])))[0]
        ref_dec = np.asarray(pattn.paged_decode_attention(
            q_dec, kpool, vpool, jnp.asarray(tables[1:2]),
            jnp.asarray([21])))[0, 0]

        # flat layout: chunk rows [0, 8), the decode row opens block 1
        # at row 8 (7 pad rows behind it), block 2 is inert.
        pad = RAGGED_BLOCK_Q * 3 - chunk_t - 1
        flat_q = jnp.concatenate(
            [q_chunk[0],
             q_dec[0],
             jnp.zeros((pad, h, self.D), jnp.float32)], axis=0)
        out = np.asarray(pattn.ragged_paged_attention(
            flat_q, kpool, vpool, jnp.asarray(tables),
            jnp.asarray(np.array([0, 1, 2], np.int32)),
            jnp.asarray(np.array([0, 0, 0], np.int32)),
            jnp.asarray(np.array([chunk_off, 20, 0], np.int32)),
            jnp.asarray(np.array([16, 21, 1], np.int32))))
        np.testing.assert_allclose(out[:chunk_t], ref_chunk,
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(out[chunk_t], ref_dec,
                                   atol=1e-5, rtol=1e-5)

    # -- the walk's freedom (ISSUE 32): the kernel's query block is not
    # the packing's 8, a run's pages are walked once a block, a tile of
    # products can overlap a neighbour's rows ------------------------

    WALK_PS = 16
    # (tokens, first position): runs of 1, 7, 8, 9, 44, 129, 240 and
    # 1000 rows; contexts ending on a page boundary (7 + 9 = 16,
    # 44 + 20 = 64), one before it (8 + 23 = 31, 240 + 15 = 255) and
    # one after (9 + 8 = 17, 129 + 0 = 129); decode rows deep in their
    # context; two verify tiles (four rows scored)
    WALK_RUNS = [(1, 0), (7, 9), (8, 23), (9, 8), (44, 20), (129, 0),
                 (240, 15), (1, 300), (1000, 40), (1, 47), (4, 77),
                 (4, 63)]
    WALK_CASES = {
        # kh, group, d, and what else the call is given
        "8-kv-heads": dict(kh=8, group=2, d=16),
        "2-kv-heads": dict(kh=2, group=4, d=32),
        "head-major-3": dict(kh=3, group=2, d=32),
        "head-major-1": dict(kh=1, group=4, d=32),
        "latent-512": dict(kh=1, group=2, d=640, v_dim=512),
        "window": dict(kh=2, group=2, d=32, sliding_window=24),
        "softcap": dict(kh=2, group=2, d=32, softcap=30.0),
        "int8-pool": dict(kh=2, group=2, d=32, int8=True),
        # bfloat16 pages are copied as the 32-bit words their pairs of
        # heads are stored in, and parted by shifts
        "bf16-8-kv-heads": dict(kh=8, group=2, d=32, bf16=True),
        "bf16-2-kv-heads": dict(kh=2, group=4, d=32, bf16=True),
    }

    def _walk_batch(self, runs, t_budget, s_max):
        ps = self.WALK_PS
        pps = 80
        seqs, page = [], 1
        for n, pos in runs:
            need = -(-(pos + n) // ps)
            table = np.zeros(pps, np.int32)
            table[:need] = np.arange(page, page + need)
            page += need
            seqs.append(RaggedSeq(list(range(1, n + 1)), pos, table,
                                  n_scores=4 if n == 4 else 1))
        batch = build_ragged_batch(
            seqs, t_budget=t_budget, s_max=s_max,
            pages_per_seq=pps, scratch_page=0, pad_id=0, page_size=ps,
            score_width=4)
        return batch, page

    def _walk_call(self, batch, q, pools, case, scales=(None, None)):
        meta = [jnp.asarray(batch[k]) for k in (
            "tables", "seq_of_block", "block_qstart", "query_offsets",
            "kv_valid")]
        kw = {k: case[k] for k in ("sliding_window", "softcap", "v_dim")
              if k in case}
        return np.asarray(pattn.ragged_paged_attention(
            q, pools[0], pools[1], *meta, k_scale=scales[0],
            v_scale=scales[1], **kw))

    @staticmethod
    def _dense(q, keys, vals, pos, case):
        """Dense float32 attention of q rows [n, H, D] at positions
        `pos` over a sequence's keys / values [L, K, *]."""
        kh = keys.shape[1]
        group = q.shape[1] // kh
        s = np.einsum("nkgd,lkd->nkgl",
                      q.reshape(len(q), kh, group, -1), keys)
        if case.get("softcap"):
            s = case["softcap"] * np.tanh(s / case["softcap"])
        lpos = np.arange(keys.shape[0])
        mask = lpos[None] <= pos[:, None]
        if case.get("sliding_window"):
            mask &= lpos[None] > pos[:, None] - case["sliding_window"]
        s = np.where(mask[:, None, None], s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        out = np.einsum("nkgl,lkd->nkgd", w, vals)
        return out.reshape(len(q), kh * group, -1)

    @pytest.mark.ragged_attn
    @pytest.mark.parametrize("name", list(WALK_CASES))
    def test_walk_matches_dense_reference(self, name):
        """Runs of every length in ONE buffer, beside decode rows,
        verify tiles and inert pad tiles, against a dense float32
        softmax over each sequence's own pages, every real row."""
        case = self.WALK_CASES[name]
        kh, group, d = case["kh"], case["group"], case["d"]
        latent = "v_dim" in case
        rng = np.random.default_rng(32)
        batch, pages = self._walk_batch(self.WALK_RUNS, 1664, 13)
        assert batch["n_tokens"] == sum(n for n, _ in self.WALK_RUNS)
        shape = (pages, self.WALK_PS) + (() if latent else (kh,)) + (d,)
        kpool = rng.standard_normal(shape).astype(np.float32)
        vpool = None if latent else \
            rng.standard_normal(shape).astype(np.float32)
        q = jnp.asarray(
            rng.standard_normal((1664, kh * group, d)) * d ** -0.5,
            jnp.float32)
        pools, scales = (kpool, vpool), (None, None)
        if case.get("bf16"):
            # the reference reads the SAME rounded values
            q = q.astype(jnp.bfloat16)
            pools = tuple(jnp.asarray(p, jnp.bfloat16) for p in pools)
            kpool, vpool = (np.asarray(p.astype(jnp.float32))
                            for p in pools)
        if case.get("int8"):
            from theroundtaible_tpu.engine import kv_quant
            spec = kv_quant.KVQuantSpec(bits=8)
            (kq, ks), (vq, vs) = (kv_quant.quantize_cells(
                jnp.asarray(p), spec) for p in pools)
            pools, scales = (kq, vq), (ks, vs)
            # the reference reads the SAME dequantized values
            kpool, vpool = (np.asarray(kv_quant.dequantize_cells(
                a, b, spec, jnp.float32)) for a, b in ((kq, ks), (vq, vs)))
        out = self._walk_call(
            batch, q, [None if p is None else jnp.asarray(p)
                       for p in pools], case, scales)
        assert out.shape == (1664, kh * group, case.get("v_dim", d))
        assert np.isfinite(np.asarray(out, np.float32)).all()   # pads too
        row = 0
        for i, (n, pos) in enumerate(self.WALK_RUNS):
            table, length = batch["tables"][i], pos + n
            keys = kpool[table].reshape((-1,) + kpool.shape[2:])[:length]
            if latent:
                keys = keys[:, None]
                vals = keys[..., :case["v_dim"]]
            else:
                vals = vpool[table].reshape(
                    (-1,) + vpool.shape[2:])[:length]
            want = self._dense(
                np.asarray(q.astype(jnp.float32))[row:row + n], keys,
                vals, pos + np.arange(n), case)
            tol = 2e-2 if case.get("bf16") else 3e-5
            np.testing.assert_allclose(
                np.asarray(out[row:row + n], np.float32), want,
                atol=tol, rtol=tol, err_msg=f"run {i}: {n} at {pos}")
            row += -(-n // RAGGED_BLOCK_Q) * RAGGED_BLOCK_Q
        # the inert pad tiles behind the last run: the walk skips all
        # but the first, and the block's state was zero (the grid
        # kernel of quantized pools computes every one)
        assert case.get("int8") or not out[row + RAGGED_BLOCK_Q:].any()

    @pytest.mark.ragged_attn
    @pytest.mark.parametrize("absent", ["the-run-before",
                                        "the-run-after"])
    def test_a_neighbours_rows_are_its_own(self, absent):
        """A tile of products overlaps the next sequence's rows (a
        44-row run ends 48 rows into a 64-row tile; its neighbour
        starts there): each neighbour's output is bit for bit what it
        is with the other run absent, its tiles inert."""
        rng = np.random.default_rng(7)
        case = dict(kh=2, group=2, d=32)
        runs = [(44, 20), (60, 5)]
        batch, pages = self._walk_batch(runs, 128, 3)
        assert pattn._ragged_tile_rows(2, 128) == 64
        pools = [jnp.asarray(rng.standard_normal(
            (pages, self.WALK_PS, 2, 32)), jnp.float32) for _ in "kv"]
        q = jnp.asarray(rng.standard_normal((128, 4, 32)) * 0.2,
                        jnp.float32)
        both = self._walk_call(batch, q, pools, case)
        lone = dict(batch)
        tiles = slice(0, 6) if absent == "the-run-before" else \
            slice(6, 14)
        lone["seq_of_block"] = batch["seq_of_block"].copy()
        lone["block_qstart"] = batch["block_qstart"].copy()
        lone["seq_of_block"][tiles] = 2             # the inert sequence
        lone["block_qstart"][tiles] = 0
        alone = self._walk_call(lone, q, pools, case)
        kept = slice(48, 108) if absent == "the-run-before" else \
            slice(0, 44)
        assert np.array_equal(both[kept], alone[kept])
        assert np.abs(both[kept]).max() > 0

    def test_segments_and_page_visits_by_hand(self):
        """The kernel's segment map and the host's page-visit counts,
        for a 240-row run over 24 pages beside three decode rows:
        page_visits = 2 blocks x 24 pages + 3 x 24 + the first inert
        tile's one; by eights, the run's first 14 blocks end in page
        22 and its last 16 in page 23: 14 x 23 + 16 x 24 + 3 x 24 + 1."""
        ps, pps = 128, 32
        table = np.arange(pps, dtype=np.int32)
        seqs = [RaggedSeq([1] * 240, 24 * ps - 240, table)] + [
            RaggedSeq([1], 24 * ps - 1, table) for _ in range(3)]
        batch = build_ragged_batch(seqs, t_budget=512, s_max=5,
                                   pages_per_seq=pps, scratch_page=0,
                                   pad_id=0, page_size=ps)
        seg = np.asarray(pattn._ragged_segments(
            jnp.asarray(batch["seq_of_block"]),
            jnp.asarray(batch["block_qstart"]), 16))
        want = np.zeros(64, np.int32)
        want[[0, 16]] = 16, 14         # the run, cut at the 128-row block
        want[[30, 31, 32]] = 1         # decode rows
        want[33] = 1                   # the first inert tile; 34.. skipped
        assert np.array_equal(seg, want)
        assert pattn.ragged_page_visits(
            batch, page_size=ps, block_q=128) == (
                2 * 24 + 3 * 24 + 1, 14 * 23 + 16 * 24 + 3 * 24 + 1)
        # under a window of two pages, against the pages each block's
        # rows can see, row by row
        window, want = 2 * ps, 0
        for n, pos in [(240, 24 * ps - 240)] + [(1, 24 * ps - 1)] * 3 \
                + [(1, 0)]:
            for blk in range(-(-n // 8)):
                rows = pos + 8 * blk + np.arange(8)
                seen = {p for r in rows
                        for p in range(max(0, r - window + 1) // ps,
                                       min(r, pos + n - 1) // ps + 1)}
                want += len(seen)
        assert pattn.ragged_page_visits(
            batch, page_size=ps, block_q=8,
            sliding_window=window)[1] == want

    def test_decline_reasons_are_machine_readable(self):
        assert pattn.ragged_decline_reason(16, 32) is None
        assert pattn.ragged_decline_reason(48, 32).startswith(
            "page_size:")
        assert pattn.ragged_decline_reason(512, 512, 16, 16).startswith(
            "vmem:")
        with pytest.raises(ValueError, match="page_size"):
            pattn.ragged_paged_attention(
                jnp.zeros((8, 4, 32), jnp.float32),
                jnp.zeros((4, 48, 2, 32), jnp.float32),
                jnp.zeros((4, 48, 2, 32), jnp.float32),
                jnp.zeros((2, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
                jnp.zeros((1,), jnp.int32), jnp.zeros((2,), jnp.int32),
                jnp.ones((2,), jnp.int32))


# ---------------------------------------------------------------------------
# forward_ragged: XLA fallback path
# ---------------------------------------------------------------------------


@pytest.mark.ragged_attn(allow_fallback=True)
def test_xla_fallback_matches_kernel_path():
    """forward_ragged's dense per-token fallback agrees with the kernel
    path on the same flat buffer — the degrade rung serves the same
    tokens, just slower."""
    from theroundtaible_tpu.engine.models.common import init_params
    from theroundtaible_tpu.engine.paged_forward import forward_ragged

    cfg = get_model_config("tiny-gemma", max_seq_len=256)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    ps = 16
    pages = 8
    pools = [(jnp.zeros((pages, ps, cfg.num_kv_heads, cfg.head_dim),
                        jnp.float32),
              jnp.zeros((pages, ps, cfg.num_kv_heads, cfg.head_dim),
                        jnp.float32))
             for _ in range(cfg.num_layers)]
    seqs = [RaggedSeq([2, 5, 9, 11, 5, 7, 9, 4, 6, 3], 0,
                      np.array([1, 2, 0, 0], np.int32)),
            RaggedSeq([8], 0, np.array([3, 0, 0, 0], np.int32))]
    batch = build_ragged_batch(seqs, t_budget=32, s_max=4,
                               pages_per_seq=4, scratch_page=7,
                               pad_id=0, page_size=ps)

    def run(path):
        args = (jnp.asarray(batch["tokens"]),
                jnp.asarray(batch["positions"]), pools,
                jnp.asarray(batch["tables"]),
                jnp.asarray(batch["seq_of_block"]),
                jnp.asarray(batch["block_qstart"]),
                jnp.asarray(batch["query_offsets"]),
                jnp.asarray(batch["kv_valid"]),
                jnp.asarray(batch["token_pages"]),
                jnp.asarray(batch["token_offs"]),
                jnp.asarray(batch["token_seq"]),
                jnp.asarray(batch["last_rows"]))
        return forward_ragged(params, cfg, *args, attn_path=path)

    logits_k, _ = run("kernel")
    logits_x, _ = run("xla")
    # Real sequences agree across paths; the inert pad sequence (last
    # slot) carries garbage on both and is excluded.
    np.testing.assert_allclose(np.asarray(logits_k)[:2],
                               np.asarray(logits_x)[:2],
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# scheduled serving: join mid-decode, kill-switch, STRICT
# ---------------------------------------------------------------------------


class TestScheduledRagged:
    def _direct(self, engine, max_new=70):
        return {sid: engine.generate_batch(turns, max_new_tokens=max_new,
                                           session=sid)
                for sid, turns in PROMPTS.items()}

    @pytest.mark.scheduler
    @pytest.mark.ragged_attn
    def test_join_mid_decode_token_parity(self, ragged_engine,
                                          prologue_engine):
        """A session submitting while another is mid-decode admits as
        ragged prefill chunks interleaved with the live decode segment
        — and every session's tokens are byte-identical to direct
        generate_batch (greedy)."""
        from theroundtaible_tpu.utils import telemetry
        direct = self._direct(prologue_engine)
        sched = SessionScheduler(ragged_engine)
        try:
            telemetry.arm()
            t_a = time.monotonic()
            results, errors = _join_mid_decode(sched,
                                               ["s0", "s1", "s2"])
            spans = telemetry.spans_between(t_a, time.monotonic())
            telemetry.disarm()
            assert not errors, errors
            # every ragged dispatch's `segment` span says what its
            # attention read, in page visits (ISSUE 32)
            segs = [s["attrs"] for s in spans if s["rung"] == "segment"
                    and s["attrs"]["kind"] == "ragged"]
            assert segs and all(
                1 <= a["page_visits"] <= a["page_visits_by_eights"]
                for a in segs)
            assert ragged_engine.describe()["ragged"]["page_visits"] \
                >= sum(a["page_visits"] for a in segs)
            for sid in PROMPTS:
                texts, stats = results[sid]
                assert texts == direct[sid], f"{sid} diverged"
                assert stats.sched.get("ttft_s") is not None
            d = sched.describe()
            assert d["ragged_joins"] >= 1, \
                "no join ever deferred — the prologue served everything"
            assert d["ragged_segments"] >= 1
            assert d["segment_prefill_tokens"] > 0
            assert d["segment_decode_tokens"] > 0
            assert d["completed"] == 3 and d["failed"] == 0
            rag = ragged_engine.ragged_describe()
            assert rag["dispatches"].get("pallas_ragged", 0) >= 1
            assert all(e["path"] == "pallas_ragged"
                       for e in rag["recent"])
        finally:
            sched.close()

    @pytest.mark.scheduler
    def test_kill_switch_restores_prologue_byte_identically(
            self, ragged_engine, prologue_engine):
        """ROUNDTABLE_RAGGED_ATTN=0 (here: ragged_attn=False config)
        serves the same staggered workload through the PR-4 prologue —
        same tokens, zero ragged dispatches."""
        sched_on = SessionScheduler(ragged_engine)
        try:
            on, err_on = _join_mid_decode(sched_on, ["s0", "s1"])
            assert not err_on, err_on
        finally:
            sched_on.close()
        assert prologue_engine.ragged_enabled is False
        assert prologue_engine.ragged_reason == "disabled:config/env"
        sched_off = SessionScheduler(prologue_engine)
        try:
            off, err_off = _join_mid_decode(sched_off, ["s0", "s1"])
            assert not err_off, err_off
            for sid in ("s0", "s1"):
                assert on[sid][0] == off[sid][0], f"{sid} diverged"
            d = sched_off.describe()
            assert d["ragged_joins"] == 0
            assert d["ragged_segments"] == 0
            assert prologue_engine.ragged_describe()["dispatches"] == {}
        finally:
            sched_off.close()

    @pytest.mark.scheduler
    @pytest.mark.ragged_attn
    def test_strict_no_compile_across_concurrent_admission(
            self, monkeypatch):
        """The flat buffer is ONE compiled shape per sampling mode:
        after warmup + warm scheduled traffic (including a ragged join)
        and declare_warmup_complete, an occupancy-drift + concurrent-
        admission run compiles NOTHING (STRICT is armed by the
        scheduler marker — any compile raises into the errors dict)."""
        from theroundtaible_tpu.engine import compile_watch

        assert compile_watch.install() != "off"
        engine = make_engine(num_slots=4)
        engine.warmup(max_prompt_tokens=256, batch_sizes=(1, 2, 4))
        sched = SessionScheduler(engine, max_rows=4)
        # Warm pass: the same staggered shape the drift run uses, so
        # the scheduler-side programs (pipelined carries, ragged join)
        # all trace before steady state is declared.
        warm, errs = _join_mid_decode(sched, ["s0", "s1"])
        assert not errs, f"warm pass failed: {errs}"
        sched.declare_warmup_complete()
        assert compile_watch.steady_state_compiles() == 0

        results, errs = _join_mid_decode(sched, ["s0", "s1", "s2"])
        assert not errs, f"drift pass recompiled or failed: {errs}"
        assert set(results) == {"s0", "s1", "s2"}
        assert compile_watch.steady_state_compiles() == 0
        d = sched.describe()
        assert d["ragged_joins"] >= 1
        sched.close()

    @pytest.mark.ragged_attn(allow_fallback=True)
    @pytest.mark.chaos
    def test_mosaic_failure_degrades_to_xla_fallback(self):
        """A kernel failure on a ragged dispatch degrades the engine to
        the XLA ragged path permanently — the dispatch in flight
        re-runs on the fallback (fallback_reason recorded per dispatch)
        instead of failing the batch's sessions."""
        engine = make_engine(num_slots=4)
        name = "__warmup_0"
        engine.kv.ensure_capacity(name, 32, write_from=0,
                                  pinned=(name,))
        table = engine.kv.table_for([name])[0]
        batch = build_ragged_batch(
            [RaggedSeq([2] * 24, 0, table)],
            t_budget=engine.ragged_tokens,
            s_max=engine.kv.num_slots + 1,
            pages_per_seq=engine.kv.pages_per_seq,
            scratch_page=engine.kv.scratch_page(0),
            pad_id=engine.tokenizer.pad_id,
            page_size=engine.kv.page_size)
        try:
            faults.arm("mosaic_compile", count=1)
            nxt = engine._ragged_dispatch(batch)
            np.asarray(nxt)  # completes on the fallback path
        finally:
            faults.disarm()
        assert engine.ragged_path == "xla_ragged"
        assert engine.ragged_fallback_reason.startswith("degraded:")
        rag = engine.ragged_describe()
        assert rag["dispatches"] == {"xla_ragged": 1}
        assert rag["recent"][-1]["fallback_reason"].startswith(
            "degraded:")
        # a second dispatch stays on the fallback, no re-injection left
        nxt = engine._ragged_dispatch(batch)
        np.asarray(nxt)
        assert engine.ragged_describe()["dispatches"] == {
            "xla_ragged": 2}
        engine._release_warm_slots()


# ---------------------------------------------------------------------------
# engine-level resolution + provenance surfaces
# ---------------------------------------------------------------------------


class TestRaggedResolution:
    def test_describe_carries_ragged_block(self, ragged_engine):
        info = ragged_engine.describe()
        assert info["ragged"]["enabled"] is True
        assert info["ragged"]["path"] == "pallas_ragged"
        assert info["ragged"]["tokens_budget"] >= 256

    def test_page_visits_count_what_the_kernel_reads(self, ragged_engine):
        """describe()["ragged"] and the roundtable_ragged_* series move
        by what one dispatch's attention read, for a hand-built batch:
        a run of 120 rows at position 200 beside three decode rows at
        300, pages of 128. The walk's block is 128 rows: the run is one
        segment and reads 3 pages, each decode row 3, the first inert
        tile 1; of the run's fifteen 8-row blocks the first seven end
        in the second page."""
        from theroundtaible_tpu.utils import telemetry
        eng = ragged_engine
        ps = eng.kv.page_size
        assert ps == 128 and eng.cfg.sliding_window is None
        table = np.arange(eng.kv.pages_per_seq, dtype=np.int32)
        seqs = [RaggedSeq([1] * 120, 200, table)] + [
            RaggedSeq([1], 300, table) for _ in range(3)]
        batch = build_ragged_batch(
            seqs, t_budget=256, s_max=eng.kv.num_slots + 1,
            pages_per_seq=eng.kv.pages_per_seq, scratch_page=0,
            pad_id=0, page_size=ps)
        before = dict(eng.describe()["ragged"])
        series = [telemetry.REGISTRY.counter_total(
            f"roundtable_ragged_{k}_total")
            for k in ("page_visits", "page_visits_by_eights")]
        eng._note_page_visits(batch, kernel=True)
        walk = 3 + 3 * 3 + 1
        eights = 7 * 2 + 8 * 3 + 3 * 3 + 1
        assert (batch["page_visits"], batch["page_visits_by_eights"]) \
            == (walk, eights)
        after = eng.describe()["ragged"]
        assert after["page_visits"] - before["page_visits"] == walk
        assert after["page_visits_by_eights"] \
            - before["page_visits_by_eights"] == eights
        assert [telemetry.REGISTRY.counter_total(
            f"roundtable_ragged_{k}_total") - was for k, was in zip(
                ("page_visits", "page_visits_by_eights"), series)] \
            == [walk, eights]
        # a dispatch the XLA path served read no pages through a kernel
        # block: both counts are the packing's
        eng._note_page_visits(batch, kernel=False)
        assert batch["page_visits"] == eights
        assert set(after) == set(telemetry.SURFACE_BINDINGS["engine_ragged"])

    def test_dense_attn_resolves_xla_path(self):
        eng = make_engine(num_slots=2, attn="dense")
        assert eng.ragged_enabled is True
        assert eng.ragged_path == "xla_ragged"
        assert eng.ragged_fallback_reason == "attn=dense"

    def test_builder_rejects_overflow_and_misuse(self):
        table = np.zeros(4, np.int32)
        with pytest.raises(ValueError, match="overflow"):
            build_ragged_batch(
                [RaggedSeq(list(range(1, 20)), 0, table)],
                t_budget=16, s_max=4, pages_per_seq=4, scratch_page=0,
                pad_id=0, page_size=16)
        with pytest.raises(ValueError, match="inert"):
            build_ragged_batch(
                [RaggedSeq([1], 0, table)], t_budget=16, s_max=1,
                pages_per_seq=4, scratch_page=0, pad_id=0, page_size=16)
