"""Quantized KV pages suite (ISSUE 11).

Covers the tentpole end to end on the CPU backend:
- quantize/dequantize round-trip units with PINNED rms bounds and the
  exact requantization-stability property (repeated gather/scatter
  round trips are byte-stable — the property host spill/restore and
  the gather-view scatter seam both lean on);
- kernel numerics: the batched paged decode/prefill kernels and the
  ragged kernel consuming quantized pages (in-kernel dequant) against
  the same kernels on a pre-dequantized pool — the two dequant sites
  must apply identical math;
- serving parity: greedy token parity quant-on vs quant-off on the
  gather-view path, the pool-direct kernel path, int4, scheduled
  serving with a mid-run join, and the prefix-cache attach /
  host-offload tiers riding quantized pages;
- ROUNDTABLE_KV_QUANT=0 kill-switch restoring bf16 serving
  byte-identically (pool dtype, pool bytes, tokens);
- STRICT no-recompile across occupancy drift on a quantized pool;
- chipless Mosaic lowering of the quantized kernel variants and the
  machine-readable decline table (no dispatch can reach a Mosaic
  failure on chip — the int4mm plan/decline discipline);
- ledger / perfmodel / admission units: the resident-vs-logical byte
  split, the hand-computed int8-vs-bf16 decode-ceiling ratio, the
  quant-aware fleet estimate, and page-demand invariance while pool
  supply scales with the cell width.
"""

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from theroundtaible_tpu.engine import kv_quant as kvq
from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.kvcache import scoped_slot
from theroundtaible_tpu.engine.models.registry import get_model_config
from theroundtaible_tpu.engine.paging import PagedKVCache
from theroundtaible_tpu.engine.pallas import attention as pattn
from theroundtaible_tpu.engine.sampling import SamplingParams
from theroundtaible_tpu.engine.scheduler import SessionScheduler
from theroundtaible_tpu.utils import perfmodel

MODEL_KW = dict(max_seq_len=256)
PS = 32


def make_engine(max_seq=None, **kw):
    cfg = get_model_config("tiny-gemma",
                           max_seq_len=max_seq or MODEL_KW["max_seq_len"])
    kw.setdefault("num_slots", 6)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("page_size", PS)
    # 1-device mesh: tiny-gemma's heads don't partition the 8-way
    # virtual model axis, and pool-direct (the kernel-dequant path) is
    # the seam under test here; the SPMD variants are covered by the
    # chipless lowering class below.
    kw.setdefault("mesh_shape", {"data": 1, "model": 1})
    kw.setdefault("sampling",
                  SamplingParams(temperature=0.0, max_new_tokens=8))
    return InferenceEngine(cfg, **kw)


@pytest.fixture(scope="module")
def quant_engine():
    eng = make_engine(kv_quant="int8")
    assert eng.kv_quant_spec is not None and eng.paged_direct
    return eng


@pytest.fixture(scope="module")
def bf16_engine():
    return make_engine()


PREAMBLE = ("The round table convened at dawn. The rules of order are "
            "strict: every knight states a proposal, scores consensus "
            "from one to ten, and names the open points that remain. ")


# ---------------------------------------------------------------------------
# unit: the quantize/dequantize pair
# ---------------------------------------------------------------------------


class TestQuantCells:
    def _x(self, shape=(64, 4, 128), seed=0):
        rng = np.random.default_rng(seed)
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def test_int8_round_trip_rms_pinned(self):
        x = self._x()
        spec = kvq.KVQuantSpec(bits=8)
        q, s = kvq.quantize_cells(x, spec)
        assert q.shape == x.shape and q.dtype == jnp.int8
        assert s.shape == x.shape[:-1] + (1,)
        y = np.asarray(kvq.dequantize_cells(q, s, spec, jnp.float32))
        rel = np.sqrt(((y - np.asarray(x)) ** 2).mean()) \
            / np.sqrt((np.asarray(x) ** 2).mean())
        # Empirical ~0.0065 for unit-normal cells; the PIN is the
        # acceptance rule BENCH_NOTES.md records for attach parity.
        assert rel < 0.01

    def test_int4_round_trip_rms_pinned(self):
        x = self._x()
        spec = kvq.KVQuantSpec(bits=4, group=32)
        q, s = kvq.quantize_cells(x, spec)
        assert q.shape == x.shape[:-1] + (64,)      # packed nibbles
        assert s.shape == x.shape[:-1] + (4,)       # 128/32 groups
        y = np.asarray(kvq.dequantize_cells(q, s, spec, jnp.float32))
        rel = np.sqrt(((y - np.asarray(x)) ** 2).mean()) \
            / np.sqrt((np.asarray(x) ** 2).mean())
        assert rel < 0.15                            # empirical ~0.098

    @pytest.mark.parametrize("bits", [8, 4])
    def test_requantization_is_byte_stable(self, bits):
        """quantize(dequantize(q, s)) == (q, s) EXACTLY — the absmax
        element lands on the grid (it defines the scale), so the
        gather-view scatter seam and host spill round trips cannot
        drift a cell that was not rewritten."""
        spec = kvq.KVQuantSpec(bits=bits)
        q, s = kvq.quantize_cells(self._x(), spec)
        y = kvq.dequantize_cells(q, s, spec, jnp.float32)
        q2, s2 = kvq.quantize_cells(y, spec)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s2))

    def test_int4_nibble_order_even_low(self):
        """The packing contract _dequant_kv mirrors in-kernel: even
        element in the LOW nibble (quant.py's order)."""
        x = jnp.asarray([[3.0, -2.0, 1.0, -4.0]], jnp.float32)
        spec = kvq.KVQuantSpec(bits=4, group=4)
        q, s = kvq.quantize_cells(x, spec)
        vals = np.asarray(kvq.unpack_int4(q))[0]
        step = float(np.asarray(s)[0, 0])
        np.testing.assert_array_equal(
            vals, np.round(np.asarray(x)[0] / step).astype(np.int8))

    def test_zero_cells_round_trip_to_zero(self):
        spec = kvq.KVQuantSpec(bits=8)
        q, s = kvq.quantize_cells(jnp.zeros((3, 2, 16)), spec)
        assert not np.asarray(q).any()
        y = kvq.dequantize_cells(q, s, spec, jnp.float32)
        assert not np.asarray(y).any()

    def test_cell_bytes_closed_form(self):
        int8 = kvq.KVQuantSpec(bits=8)
        assert int8.cell_bytes(128) == 128 + 4.0          # payload + s
        int4 = kvq.KVQuantSpec(bits=4, group=32)
        assert int4.cell_bytes(128) == 64 + 4.0 * 4
        # ~1.94 quantized pages per bf16 page at D=128 — the pool-
        # sizing multiplier behind the >= 1.8x sessions acceptance bar.
        assert 1.9 < kvq.page_ratio(int8, 128) < 2.0
        cfg = get_model_config("tiny-gemma")
        assert kvq.cell_bytes_per_token(cfg, None, 2) == \
            cfg.num_layers * 2 * cfg.num_kv_heads * cfg.head_dim * 2

    def test_resolve_spec_config_forms(self, monkeypatch):
        monkeypatch.delenv("ROUNDTABLE_KV_QUANT", raising=False)
        assert kvq.resolve_spec(None) == (None, "disabled:config")
        assert kvq.resolve_spec("none") == (None, "disabled:config")
        spec, reason = kvq.resolve_spec("int8")
        assert spec == kvq.KVQuantSpec(bits=8) and reason is None
        spec, _ = kvq.resolve_spec({"bits": 4, "group": 16})
        assert spec == kvq.KVQuantSpec(bits=4, group=16)
        with pytest.raises(ValueError, match="int8"):
            kvq.resolve_spec("float8")
        with pytest.raises(ValueError, match="bits"):
            kvq.resolve_spec({"bits": 5})

    def test_resolve_spec_env_kill_switch_wins(self, monkeypatch):
        monkeypatch.setenv("ROUNDTABLE_KV_QUANT", "0")
        assert kvq.resolve_spec("int8") == (None, "disabled:env")


# ---------------------------------------------------------------------------
# kernel numerics: in-kernel dequant vs the XLA dequant twin
# ---------------------------------------------------------------------------


class TestKernelDequantParity:
    """The Pallas kernels' in-kernel dequant must agree with
    kv_quant.dequantize_cells — proven by running the SAME kernel on
    (quantized pool + scales) vs (pre-dequantized pool, no scales)."""

    KH, G, D = 2, 2, 32
    PAGES, PP = 12, 4

    def _pool(self, seed=0, bits=8):
        rng = np.random.default_rng(seed)
        spec = kvq.KVQuantSpec(bits=bits, group=16)
        k = jnp.asarray(rng.standard_normal(
            (self.PAGES, PS, self.KH, self.D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal(
            (self.PAGES, PS, self.KH, self.D)), jnp.float32)
        kq, ks = kvq.quantize_cells(k, spec)
        vq, vs = kvq.quantize_cells(v, spec)
        kd = kvq.dequantize_cells(kq, ks, spec, jnp.float32)
        vd = kvq.dequantize_cells(vq, vs, spec, jnp.float32)
        return spec, (kq, ks, vq, vs), (kd, vd)

    @pytest.mark.parametrize("bits", [8, 4])
    def test_paged_decode_kernel(self, bits):
        spec, (kq, ks, vq, vs), (kd, vd) = self._pool(bits=bits)
        rng = np.random.default_rng(1)
        b, h = 3, self.KH * self.G
        q = jnp.asarray(rng.standard_normal((b, 1, h, self.D)),
                        jnp.float32)
        table = jnp.asarray(rng.integers(0, self.PAGES,
                                         (b, self.PP)), jnp.int32)
        valid = jnp.asarray([17, 60, 128], jnp.int32)
        quant = pattn.paged_decode_attention(
            q, kq, vq, table, valid, k_scale=ks, v_scale=vs,
            kv_bits=spec.bits)
        ref = pattn.paged_decode_attention(q, kd, vd, table, valid)
        np.testing.assert_allclose(np.asarray(quant), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_paged_prefill_kernel(self):
        spec, (kq, ks, vq, vs), (kd, vd) = self._pool()
        rng = np.random.default_rng(2)
        b, t, h = 2, 64, self.KH * self.G
        q = jnp.asarray(rng.standard_normal((b, t, h, self.D)),
                        jnp.float32)
        table = jnp.asarray(rng.integers(0, self.PAGES,
                                         (b, self.PP)), jnp.int32)
        offsets = jnp.asarray([0, 32], jnp.int32)
        valid = jnp.asarray([64, 96], jnp.int32)
        quant = pattn.paged_prefill_attention(
            q, kq, vq, table, offsets, valid, k_scale=ks, v_scale=vs,
            kv_bits=spec.bits)
        ref = pattn.paged_prefill_attention(q, kd, vd, table, offsets,
                                            valid)
        np.testing.assert_allclose(np.asarray(quant), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_ragged_kernel(self):
        spec, (kq, ks, vq, vs), (kd, vd) = self._pool()
        rng = np.random.default_rng(3)
        h = self.KH * self.G
        t = 3 * pattn.RAGGED_BLOCK_Q
        q = jnp.asarray(rng.standard_normal((t, h, self.D)),
                        jnp.float32)
        tables = jnp.asarray(rng.integers(0, self.PAGES, (3, self.PP)),
                             jnp.int32)
        seq_of_block = jnp.asarray([0, 0, 1], jnp.int32)
        block_qstart = jnp.asarray([0, 8, 0], jnp.int32)
        query_offsets = jnp.asarray([5, 20, 0], jnp.int32)
        kv_valid = jnp.asarray([15, 21, 1], jnp.int32)
        args = (tables, seq_of_block, block_qstart, query_offsets,
                kv_valid)
        quant = pattn.ragged_paged_attention(
            q, kq, vq, *args, k_scale=ks, v_scale=vs,
            kv_bits=spec.bits)
        ref = pattn.ragged_paged_attention(q, kd, vd, *args)
        # Inert pad rows carry finite garbage on both paths; real rows
        # (the first two sequences' tokens) must agree.
        np.testing.assert_allclose(np.asarray(quant)[:21],
                                   np.asarray(ref)[:21],
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# serving parity: quant-on vs quant-off, every dispatch seam
# ---------------------------------------------------------------------------


class TestServingParity:
    @pytest.mark.kv_quant
    def test_kernel_path_greedy_parity(self, quant_engine, bf16_engine):
        """Pool-direct serving (in-kernel dequant on prefill + decode)
        emits the same greedy tokens as the bf16 twin."""
        p = PREAMBLE + "Lancelot opens on the castle walls."
        assert (quant_engine.generate(p, slot_name="kp", max_new_tokens=8)
                == bf16_engine.generate(p, slot_name="kp",
                                        max_new_tokens=8))
        d = quant_engine.kv_quant_describe()
        assert d["enabled"] and d["dtype"] == "int8"
        assert d["dispatches"].get("prefill:kernel_dequant", 0) >= 1
        assert d["dispatches"].get("decode:kernel_dequant", 0) >= 1

    @pytest.mark.kv_quant
    def test_gather_view_greedy_parity(self):
        """The default 8-device mesh declines pool-direct for
        tiny-gemma — serving dequantizes AT THE GATHER (the XLA read
        seam) and must still match bf16 greedy tokens, with the
        machine-readable fallback provenance recorded."""
        q = make_engine(kv_quant="int8", mesh_shape=None)
        b = make_engine(mesh_shape=None)
        assert not q.paged_direct
        p = PREAMBLE + "Galahad raises the matter of the moat."
        assert (q.generate(p, slot_name="gv", max_new_tokens=8)
                == b.generate(p, slot_name="gv", max_new_tokens=8))
        d = q.kv_quant_describe()
        assert d["dispatches"].get("decode:xla_dequant", 0) >= 1
        assert all("fallback_reason" in e for e in d["recent"]
                   if e["path"] == "xla_dequant")

    @pytest.mark.kv_quant
    def test_int4_greedy_parity(self, bf16_engine):
        eng = make_engine(kv_quant="int4")
        assert eng.kv_quant_spec.bits == 4
        p = PREAMBLE + "Tristan plans the harvest tournament."
        assert (eng.generate(p, slot_name="i4", max_new_tokens=8)
                == bf16_engine.generate(p, slot_name="i4",
                                        max_new_tokens=8))
        # int4 packs nibbles: payload pool is D/2 wide.
        k0, _ = eng.kv.pools[0]
        assert k0.shape[-1] == eng.cfg.head_dim // 2

    @pytest.mark.kv_quant
    def test_multiturn_delta_prefill_parity(self, quant_engine,
                                            bf16_engine):
        """A second turn re-enters committed quantized pages through
        the reuse plan — the requant-stability property end to end."""
        base = PREAMBLE + "Round one establishes the shared context."
        ext = base + " Round two adds arguments and asks for a score."
        outs = []
        for eng in (quant_engine, bf16_engine):
            eng.generate(base, slot_name="mt", max_new_tokens=8)
            outs.append(eng.generate(ext, slot_name="mt",
                                     max_new_tokens=8))
            assert eng.last_stats.reused_tokens > 0
        assert outs[0] == outs[1]

    @pytest.mark.kv_quant
    @pytest.mark.scheduler
    def test_scheduled_mid_run_join_parity(self):
        """Scheduled serving on quantized pages: a session joining
        while another decodes (ragged chunk-interleaved admission)
        stays token-identical to the bf16 twin's schedule."""
        outs = {}
        for tag, kvq_cfg in (("q", "int8"), ("b", None)):
            eng = make_engine(max_seq=512, num_slots=8,
                              kv_quant=kvq_cfg)
            eng.ragged_defer_min = 1
            sched = SessionScheduler(eng)
            results, errors = {}, {}

            def run(sid, prompt, wait):
                try:
                    if wait:
                        deadline = time.monotonic() + 60
                        while (not sched._active
                               and time.monotonic() < deadline):
                            time.sleep(0.005)
                    results[sid] = sched.submit(
                        sid, [("kn", prompt)], max_new_tokens=16)[0]
                except Exception as e:  # noqa: BLE001 — asserted below
                    errors[sid] = e

            try:
                threads = [
                    threading.Thread(target=run, args=(
                        f"s{i}", PREAMBLE + f"Knight {i} argues.",
                        i > 0)) for i in range(3)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=240)
                assert not errors, errors
                outs[tag] = results
                if kvq_cfg:
                    disp = eng.kv_quant_describe()["dispatches"]
                    assert disp.get("ragged:kernel_dequant", 0) >= 1
            finally:
                sched.close()
        assert outs["q"] == outs["b"]

    @pytest.mark.kv_quant(allow_bf16=True)
    def test_kill_switch_restores_bf16_byte_identically(
            self, monkeypatch):
        """ROUNDTABLE_KV_QUANT=0 beats `kv_quant: int8`: the pool is
        bf16 (same dtype, same page count, same bytes after the same
        serve) and the tokens match the never-configured engine's —
        and ZERO quantized dispatches are recorded (the guard's
        allow_bf16 case, exercised on purpose)."""
        monkeypatch.setenv("ROUNDTABLE_KV_QUANT", "0")
        killed = make_engine(kv_quant="int8")
        plain = make_engine()
        assert killed.kv_quant_spec is None
        assert killed.kv_quant_reason == "disabled:env"
        assert killed.kv_quant_describe()["enabled"] is False
        assert killed.kv.num_pages == plain.kv.num_pages
        assert killed.kv.scales is None
        p = PREAMBLE + "Kay reads the mason's tally."
        assert (killed.generate(p, slot_name="ks", max_new_tokens=8)
                == plain.generate(p, slot_name="ks", max_new_tokens=8))
        for (k1, v1), (k2, v2) in zip(killed.kv.pools, plain.kv.pools):
            assert k1.dtype == k2.dtype
            np.testing.assert_array_equal(np.asarray(k1),
                                          np.asarray(k2))
            np.testing.assert_array_equal(np.asarray(v1),
                                          np.asarray(v2))
        assert kvq.quant_dispatches() == 0

    @pytest.mark.kv_quant
    def test_strict_no_recompile_across_occupancy_drift(
            self, quant_engine, monkeypatch):
        """Quantize-on-write is value-in/value-out at fixed shapes —
        occupancy drift on a quantized pool compiles NOTHING once
        steady state is declared (the PR-6 sentinel, armed hard)."""
        from theroundtaible_tpu.engine import compile_watch

        assert compile_watch.install() != "off"
        monkeypatch.setenv("ROUNDTABLE_RECOMPILE_STRICT", "1")
        # Warm pass at the shapes the drift pass revisits.
        for i, nm in enumerate(("w1", "w2")):
            quant_engine.generate(
                PREAMBLE + f"Warm knight {i} speaks at length.",
                slot_name=nm, max_new_tokens=8)
        compile_watch.warmup_complete("kv_quant_test")
        try:
            for i, nm in enumerate(("d1", "d2", "w1")):
                quant_engine.generate(
                    PREAMBLE + f"Drift knight {i} answers briefly.",
                    slot_name=nm, max_new_tokens=8)
            assert compile_watch.steady_state_compiles() == 0
        finally:
            compile_watch.reset_steady_state()


# ---------------------------------------------------------------------------
# sharing tiers: prefix cache, COW, host offload
# ---------------------------------------------------------------------------


class TestSharingTiers:
    @pytest.mark.kv_quant
    @pytest.mark.prefix_cache
    def test_prefix_attach_on_quantized_pages(self, quant_engine,
                                              bf16_engine):
        """Cross-session attach ALIASES quantized pages (scales ride
        the page axis) — the attach parity rule is greedy token parity
        vs the bf16 twin, not byte-identity (BENCH_NOTES.md)."""
        p1 = PREAMBLE + "Bors states the first proposal plainly."
        p2 = PREAMBLE + "Ector answers with the second proposal."
        outs = []
        for eng in (quant_engine, bf16_engine):
            eng.generate(p1, slot_name=scoped_slot("pqA", "bors"),
                         max_new_tokens=8)
            outs.append(eng.generate(
                p2, slot_name=scoped_slot("pqB", "ector"),
                max_new_tokens=8))
            assert eng.last_stats.reused_tokens > 0, \
                "prefix attach never happened"
        assert outs[0] == outs[1]

    @pytest.mark.kv_quant(allow_bf16=True)
    def test_cow_page_carries_scales(self):
        """A COW'd quantized page must copy payload AND scales in one
        dispatch — a fork that dropped scales would dequantize garbage
        for the writer."""
        cfg = get_model_config("tiny-gemma", max_seq_len=128)
        spec = kvq.KVQuantSpec(bits=8)

        def copy_fn(combined, src, dst):
            return [(k.at[dst].set(k[src]), v.at[dst].set(v[src]))
                    for k, v in combined]

        kv = PagedKVCache(cfg, 4, 128, jnp.bfloat16, page_size=16,
                          copy_pages_fn=copy_fn, kv_quant=spec)
        kv.acquire("a")
        kv.ensure_capacity("a", 16, write_from=0)
        page = kv._slots["a"].pages[0]
        rng = np.random.default_rng(7)
        for li in range(cfg.num_layers):
            k, v = kv.pools[li]
            ks, vs = kv.scales[li]
            kv.pools[li] = (
                k.at[page].set(jnp.asarray(rng.integers(
                    -127, 127, k.shape[1:]), jnp.int8)), v)
            kv.scales[li] = (
                ks.at[page].set(jnp.asarray(rng.random(
                    ks.shape[1:]), jnp.float32)), vs)
        # Share the page (refcount 2), then COW it for "a".
        kv.acquire("b")
        kv.adopt_span("b", [page], 0, 16)
        fresh = kv.cow_page("a", 0, pinned=("a", "b"))
        assert fresh != page
        kv.combined_pools()        # the queued copy goes out here
        for li in range(cfg.num_layers):
            k, _ = kv.pools[li]
            ks, _ = kv.scales[li]
            np.testing.assert_array_equal(np.asarray(k[fresh]),
                                          np.asarray(k[page]))
            np.testing.assert_array_equal(np.asarray(ks[fresh]),
                                          np.asarray(ks[page]))

    @pytest.mark.kv_quant
    def test_spill_restore_round_trip_exact(self):
        """Host spill/restore of quantized pages is EXACTLY lossless:
        int8 payload + f32 scales round-trip byte-identically (half
        the spill bandwidth of bf16 pages, same guarantee)."""
        eng = make_engine(kv_quant="int8", prefix_cache=False)
        sid = "offq"
        name = scoped_slot(sid, "kay")
        eng.generate(PREAMBLE + "Kay takes the floor.", slot_name=name,
                     max_new_tokens=8)
        state = eng.kv._slots[name]
        idx = np.asarray(state.pages)
        before = [(np.asarray(k[idx]), np.asarray(v[idx]))
                  for k, v in eng.kv.pools]
        before_s = [(np.asarray(ks[idx]), np.asarray(vs[idx]))
                    for ks, vs in eng.kv.scales]
        tokens = list(state.tokens)
        assert eng.kv_offload.spill_session(sid) == 1
        eng.kv_offload.restore_session(sid)
        state = eng.kv._slots[name]
        assert state.tokens == tokens
        idx = np.asarray(state.pages)
        for (kb, vb), (k, v) in zip(before, eng.kv.pools):
            np.testing.assert_array_equal(kb, np.asarray(k[idx]))
            np.testing.assert_array_equal(vb, np.asarray(v[idx]))
        for (kb, vb), (ks, vs) in zip(before_s, eng.kv.scales):
            np.testing.assert_array_equal(kb, np.asarray(ks[idx]))
            np.testing.assert_array_equal(vb, np.asarray(vs[idx]))


# ---------------------------------------------------------------------------
# decline table + chipless Mosaic lowering
# ---------------------------------------------------------------------------


class TestDeclineAndLowering:
    H, K, D = 8, 4, 256
    PAGE = 128

    def test_decline_reasons_machine_readable(self):
        ok = pattn.kv_quant_decline_reason(self.PAGE, self.D, self.K,
                                           self.H // self.K)
        assert ok is None
        r = pattn.kv_quant_decline_reason(512, 512, 16, 16)
        assert r is not None and r.startswith("vmem:")
        r = pattn.kv_quant_decline_reason(96, self.D, self.K,
                                          self.H // self.K)
        assert r is not None and r.startswith("page_size:")
        r = pattn.kv_quant_decline_reason(self.PAGE, 129, 1, 1, bits=4)
        assert r is not None and r.startswith("int4_head_dim:")
        r = pattn.kv_quant_decline_reason(self.PAGE, self.D, 1, 1,
                                          bits=5)
        assert r == "kv_bits:5"

    def _quant_pool(self, bits=8):
        spec = kvq.KVQuantSpec(bits=bits, group=32)
        pool_pages = 16
        kp = jnp.zeros((pool_pages, self.PAGE, self.K,
                        spec.packed_dim(self.D)), jnp.int8)
        ks = jnp.zeros((pool_pages, self.PAGE, self.K,
                        spec.num_groups(self.D)), jnp.float32)
        return spec, kp, ks

    @pytest.mark.parametrize("bits", [8, 4])
    def test_quantized_paged_kernels_lower_chipless(self, bits):
        """jit(...).lower(lowering_platforms=("tpu",)) — Mosaic
        validates the quantized block shapes (scale operands on the kv
        index map, in-kernel unpack/dequant ops) without a chip."""
        spec, kp, ks = self._quant_pool(bits)
        b, pp = 2, 4
        q = jnp.zeros((b, 1, self.H, self.D), jnp.bfloat16)
        table = jnp.zeros((b, pp), jnp.int32)
        valid = jnp.full((b,), 100, jnp.int32)

        def decode(q, kp, ks, table, valid):
            return pattn.paged_decode_attention(
                q, kp, kp, table, valid, k_scale=ks, v_scale=ks,
                kv_bits=spec.bits, interpret=False)

        jax.jit(decode).trace(q, kp, ks, table, valid).lower(
            lowering_platforms=("tpu",))

        qp = jnp.zeros((b, 128, self.H, self.D), jnp.bfloat16)
        offs = jnp.zeros((b,), jnp.int32)

        def prefill(q, kp, ks, table, offs, valid):
            return pattn.paged_prefill_attention(
                q, kp, kp, table, offs, valid, k_scale=ks, v_scale=ks,
                kv_bits=spec.bits, interpret=False)

        jax.jit(prefill).trace(qp, kp, ks, table, offs, valid).lower(
            lowering_platforms=("tpu",))

    def test_quantized_ragged_kernel_lowers_chipless(self):
        spec, kp, ks = self._quant_pool()
        t = 4 * pattn.RAGGED_BLOCK_Q
        q = jnp.zeros((t, self.H, self.D), jnp.bfloat16)
        tables = jnp.zeros((3, 4), jnp.int32)
        seq_of_block = jnp.asarray([0, 0, 1, 2], jnp.int32)
        block_qstart = jnp.asarray([0, 8, 0, 0], jnp.int32)
        query_offsets = jnp.asarray([128, 200, 0], jnp.int32)
        kv_valid = jnp.asarray([144, 201, 1], jnp.int32)

        def f(q, kp, ks, *meta):
            return pattn.ragged_paged_attention(
                q, kp, kp, *meta, k_scale=ks, v_scale=ks,
                kv_bits=spec.bits, interpret=False)

        jax.jit(f).trace(q, kp, ks, tables, seq_of_block, block_qstart,
                         query_offsets, kv_valid).lower(
            lowering_platforms=("tpu",))


# ---------------------------------------------------------------------------
# accounting: ledger, perfmodel, fleet estimate, admission
# ---------------------------------------------------------------------------


class TestAccounting:
    def test_ledger_resident_vs_logical_split(self, quant_engine,
                                              bf16_engine):
        led = quant_engine.kv.memory_ledger()
        assert led["kv_dtype"] == "int8" and led["kv_quant_bits"] == 8
        assert led["kv_bytes_resident"] < led["kv_bytes_logical"]
        assert led["kv_quant_bytes_saved"] == (
            led["kv_bytes_logical"] - led["kv_bytes_resident"])
        assert led["hbm_bytes"] == led["kv_bytes_resident"]
        led_b = bf16_engine.kv.memory_ledger()
        assert led_b["kv_dtype"] == "bf16"
        assert led_b["kv_bytes_resident"] == led_b["kv_bytes_logical"]
        assert led_b["kv_quant_bytes_saved"] == 0

    def test_ledger_gauges_published(self, quant_engine):
        from theroundtaible_tpu.engine import trace_hooks
        from theroundtaible_tpu.utils import telemetry

        trace_hooks.publish_memory_ledger(quant_engine)
        name = quant_engine.cfg.name
        reg = telemetry.REGISTRY
        assert reg.gauge_value("roundtable_kv_quant_bits",
                               engine=name) == 8
        saved = reg.gauge_value("roundtable_kv_quant_bytes_saved",
                                engine=name)
        logical = reg.gauge_value("roundtable_kv_bytes_logical",
                                  engine=name)
        assert saved and logical and saved < logical

    def test_default_pool_page_ratio_meets_sessions_bar(
            self, quant_engine, bf16_engine):
        """Same byte budget, page_ratio x the pages — the pool-supply
        half of the >= 1.8x max-resident-sessions acceptance bar
        (demand per session is in PAGES and dtype-independent). The
        ratio is head_dim-dependent: tiny-gemma's D=16 pays the f32
        scale on every 16 payload bytes (1.6x); serving head_dims
        amortize it past the bar — pinned in closed form here, hit
        end-to-end by the bench A/B's D=64 model."""
        spec = quant_engine.kv_quant_spec
        d = quant_engine.cfg.head_dim
        q_pages = quant_engine.kv.num_pages - 1      # minus scratch
        b_pages = bf16_engine.kv.num_pages - 1
        assert q_pages == int(b_pages * kvq.page_ratio(spec, d))
        assert q_pages >= 1.5 * b_pages              # D=16 floor
        assert kvq.page_ratio(spec, 64) >= 1.8       # bench model
        assert kvq.page_ratio(spec, 256) >= 1.9      # gemma-2b-it
        # ... in no more bytes than the bf16 pool (scale overhead
        # included):
        assert quant_engine.kv.hbm_bytes() <= bf16_engine.kv.hbm_bytes()

    def test_page_demand_is_dtype_independent(self, quant_engine,
                                              bf16_engine):
        """Admission charges requests in PAGES; the dtype lives in the
        pool's supply. The same request needs the same page count on
        both engines while the quantized pool offers ~2x the pages."""
        sq = SessionScheduler.__new__(SessionScheduler)
        sq.engine = quant_engine
        sb = SessionScheduler.__new__(SessionScheduler)
        sb.engine = bf16_engine
        turns = [("kn", "a prompt of modest length for the estimate")]
        need_q = SessionScheduler._pages_needed(sq, turns, 16)
        need_b = SessionScheduler._pages_needed(sb, turns, 16)
        assert need_q == need_b
        assert quant_engine.kv.usable_pages() \
            >= 1.5 * bf16_engine.kv.usable_pages()

    def test_estimate_hbm_charges_configured_dtype(self, monkeypatch):
        from theroundtaible_tpu.engine.fleet import \
            estimate_engine_hbm_bytes

        monkeypatch.delenv("ROUNDTABLE_KV_QUANT", raising=False)
        base = {"model": "tiny-gemma", "num_slots": 4,
                "kv_layout": "paged", "page_size": 32,
                "num_pages": 64}
        bf16 = estimate_engine_hbm_bytes(dict(base))
        int8 = estimate_engine_hbm_bytes(dict(base, kv_quant="int8"))
        assert int8 < bf16
        cfg = get_model_config("tiny-gemma")
        spec = kvq.KVQuantSpec(bits=8)
        # The delta is exactly the KV term's cell-width change.
        assert bf16 - int8 == int(
            64 * 32 * (kvq.cell_bytes_per_token(cfg, None, 2)
                       - kvq.cell_bytes_per_token(cfg, spec, 2)))
        # Kill-switch at plan time matches construction.
        monkeypatch.setenv("ROUNDTABLE_KV_QUANT", "0")
        assert estimate_engine_hbm_bytes(
            dict(base, kv_quant="int8")) == bf16

    def test_decode_ceiling_ratio_hand_computed(self):
        """Hand-computed int8-vs-bf16 ceiling (the satellite's pin):
        1 GB params + 1 GB bf16 KV stream → 819e9/2e9 = 409.5 tok/s;
        int8 KV streams 132/256 of those bytes (128 B payload + 4 B
        scale per 256 B bf16 cell) → 819e9/1.515625e9 = 540.37 tok/s —
        a 1.3196x ceiling lift from the same chip."""
        chip = perfmodel.V5E
        bf16 = perfmodel.decode_ceiling_tps(
            1_000_000_000, chip, kv_stream_bytes=1_000_000_000)
        assert bf16 == pytest.approx(409.5)
        int8_kv = 1_000_000_000 * 132 // 256
        int8 = perfmodel.decode_ceiling_tps(
            1_000_000_000, chip, kv_stream_bytes=int8_kv)
        assert int8 == pytest.approx(540.37, abs=0.01)
        assert int8 / bf16 == pytest.approx(512 / 388, abs=1e-3)

    def test_roofline_block_carries_kv_term(self):
        block = perfmodel.roofline_block(
            param_bytes=1_000_000_000, num_params=500_000_000,
            chip=perfmodel.V5E, kv_stream_bytes=1_000_000_000,
            kv_dtype="int8")
        assert block["kv_stream_bytes_per_token"] == 1_000_000_000
        assert block["kv_dtype"] == "int8"
        assert block["decode_ceiling_tps"] == pytest.approx(409.5)
        # kv_stream_bytes=0 keeps the historical block byte-identical
        # (the drift pin in test_perfmodel stays authoritative).
        base = perfmodel.roofline_block(
            param_bytes=1_000_000_000, num_params=500_000_000,
            chip=perfmodel.V5E)
        assert "kv_stream_bytes_per_token" not in base

    def test_engine_perf_charges_quantized_cells(self, quant_engine,
                                                 bf16_engine):
        cfg = quant_engine.cfg
        spec = quant_engine.kv_quant_spec
        assert quant_engine.perf.kv_token_bytes == \
            perfmodel.kv_bytes_per_token(cfg, quant_spec=spec)
        assert quant_engine.perf.kv_token_bytes \
            < bf16_engine.perf.kv_token_bytes
        # set_kv_decode_context folds the streamed-KV term in: the
        # quantized engine's ceiling is HIGHER at the same context.
        pq = perfmodel.EnginePerf(
            "uq", param_bytes=10**9, num_params=5 * 10**8,
            chip=perfmodel.V5E,
            kv_token_bytes=quant_engine.perf.kv_token_bytes)
        pb = perfmodel.EnginePerf(
            "ub", param_bytes=10**9, num_params=5 * 10**8,
            chip=perfmodel.V5E,
            kv_token_bytes=bf16_engine.perf.kv_token_bytes)
        for p in (pq, pb):
            p.set_kv_decode_context(100_000)
        assert pq._decode_ceiling() > pb._decode_ceiling()
        pq.set_kv_decode_context(0)
        assert pq._decode_ceiling() == pq.decode_ceiling

    def test_describe_embeds_kv_quant_provenance(self, quant_engine):
        info = quant_engine.describe()
        kvi = info["kv_quant"]
        assert kvi["enabled"] and kvi["dtype"] == "int8"
        assert kvi["fallback_reason"] is None
        assert "bytes_saved" in kvi and kvi["bytes_saved"] > 0
        assert quant_engine.kv.memory_ledger()["kv_dtype"] == "int8"
