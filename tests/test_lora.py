"""Multi-LoRA knight personas (ISSUE 10).

Coverage map (the issue's satellite list):
- grouped XLA apply vs a per-row reference; Pallas BGMV vs XLA
  agreement (interpret mode) + spmd col/row parity on a virtual mesh;
- chipless Mosaic lowering of the kernel + plan decline units;
- adapter store load/evict/LRU/refcount + int8 quantize-aware pairs;
- engine serving: persona changes outputs deterministically,
  mixed-adapter batch token parity vs serving each adapter alone,
  ROUNDTABLE_LORA=0 kill-switch byte-identity, provenance surfaces;
- sharing-correctness gates: mixed-adapter share suppression, the
  prefix cache neither fed by nor serving persona rows, adapter-flip
  slot release;
- scheduler: mixed-adapter co-batched decode parity vs direct serving,
  refusal past store capacity, STRICT no-compile across hot-swaps,
  composition with ragged admission + speculative decode.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theroundtaible_tpu.engine import lora as lora_mod
from theroundtaible_tpu.engine.engine import InferenceEngine
from theroundtaible_tpu.engine.lora import (LoraStore, _xla_grouped,
                                            lora_dims, save_pair_tree)
from theroundtaible_tpu.engine.models.registry import get_model_config
from theroundtaible_tpu.engine.pallas import lora as plora

MESH1 = {"data": 1, "model": 1}

PERSONAS = {"galahad": {"seed": 1, "init_std": 0.6},
            "percival": {"seed": 7, "init_std": 0.6},
            "lancelot": {"seed": 9, "init_std": 0.6}}
LORA_CFG = {"rank": 4, "max_adapters": 3, "scale": 4.0,
            "adapters": PERSONAS}

PROMPT = "the knights debate the session store design at the roundtable"


def _cfg(max_seq_len=256):
    return get_model_config("tiny-gemma", max_seq_len=max_seq_len)


@pytest.fixture(scope="module")
def engine():
    """One LoRA engine on the gather view (XLA alone; `paged_engine`
    takes the kernels) shared by the direct-serving tests (greedy
    sampling → deterministic parity)."""
    return InferenceEngine(_cfg(), num_slots=6, mesh_shape=MESH1,
                           attn="dense", page_size=32,
                           lora=dict(LORA_CFG))


@pytest.fixture(scope="module")
def paged_engine():
    """One paged LoRA engine (ragged + spec on) shared by the
    scheduler/composition tests."""
    return InferenceEngine(_cfg(), num_slots=6, kv_layout="paged",
                           page_size=32, num_pages=64, mesh_shape=MESH1,
                           lora=dict(LORA_CFG))


# ---------------------------------------------------------------------
# grouped apply: XLA baseline + Pallas kernel
# ---------------------------------------------------------------------


def _per_row_reference(x2, a_t, b_s, ids):
    out = np.zeros((x2.shape[0], b_s.shape[2]), np.float32)
    for i, sl in enumerate(np.asarray(ids)):
        xa = np.asarray(x2)[i] @ np.asarray(a_t)[sl].T
        out[i] = xa @ np.asarray(b_s)[sl]
    return out


@pytest.mark.lora(allow_single=True)
def test_xla_grouped_matches_per_row_reference():
    rng = np.random.default_rng(0)
    m, c, r, o, s = 6, 64, 4, 96, 4
    x2 = jnp.asarray(rng.normal(size=(m, c)), jnp.float32)
    a_t = jnp.asarray(rng.normal(size=(s, r, c)), jnp.float32)
    b_s = jnp.asarray(rng.normal(size=(s, r, o)), jnp.float32)
    ids = jnp.asarray([0, 1, 3, 1, 2, 0], jnp.int32)
    got = np.asarray(_xla_grouped(x2, a_t, b_s, ids))
    ref = _per_row_reference(x2, a_t, b_s, ids)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    # slot-0 rows (the base adapter) see the stack's zero slot ONLY
    # through the mask — a zeroed slot plus the mask is belt-and-braces
    zero = _xla_grouped(x2, a_t.at[0].set(0.0), b_s.at[0].set(0.0), ids)
    assert np.allclose(np.asarray(zero)[0], 0.0) == bool(
        np.allclose(ref[0] * 0, 0))


@pytest.mark.lora(allow_single=True)
def test_kernel_matches_xla_interpret(monkeypatch):
    monkeypatch.setenv("ROUNDTABLE_LORA_MM", "1")
    rng = np.random.default_rng(1)
    m, c, r, o, s = 8, 256, 8, 512, 4
    x2 = jnp.asarray(rng.normal(size=(m, c)), jnp.float32)
    a_t = jnp.asarray(rng.normal(size=(s, r, c)), jnp.float32)
    b_s = jnp.asarray(rng.normal(size=(s, r, o)), jnp.float32)
    ids = jnp.asarray([0, 1, 1, 2, 3, 0, 2, 1], jnp.int32)
    y, reason = plora.lora_bgmv_or_reason(x2, a_t, b_s, ids)
    assert reason is None
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_xla_grouped(x2, a_t, b_s,
                                                       ids)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.lora(allow_single=True)
def test_kernel_plan_declines():
    # stable machine-readable reasons — the engine's lora_paths
    # fallback_reason surface (the int4mm plan_reason contract)
    assert plora.plan_bgmv(200, 256, 8, 512) == (None, "rows:prefill-m")
    assert plora.plan_bgmv(8, 100, 8, 512) == \
        (None, "dims:contract-misaligned")
    assert plora.plan_bgmv(8, 256, 8, 100) == \
        (None, "dims:out-misaligned")
    assert plora.plan_bgmv(8, 256, 1024, 512) == \
        (None, "rank:unsupported")
    plan, reason = plora.plan_bgmv(8, 256, 8, 512)
    assert reason is None and plan == (512,)


@pytest.mark.lora(allow_single=True)
@pytest.mark.parametrize("tp", ["col", "row"])
def test_kernel_spmd_matches_xla(monkeypatch, tp):
    monkeypatch.setenv("ROUNDTABLE_LORA_MM", "1")
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(1, 4), ("data", "model"))
    rng = np.random.default_rng(2)
    m, c, r, o, s = 8, 512, 8, 512, 3
    x2 = jnp.asarray(rng.normal(size=(m, c)), jnp.float32)
    a_t = jnp.asarray(rng.normal(size=(s, r, c)), jnp.float32)
    b_s = jnp.asarray(rng.normal(size=(s, r, o)), jnp.float32)
    ids = jnp.asarray([0, 2, 1, 1, 0, 2, 1, 0], jnp.int32)

    def run(x2, a_t, b_s, ids):
        y, reason = plora.lora_bgmv_spmd(mesh, x2, a_t, b_s, ids, tp=tp)
        assert reason is None, reason
        return y

    got = jax.jit(run)(x2, a_t, b_s, ids)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_xla_grouped(x2, a_t, b_s,
                                                       ids)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.lora(allow_single=True)
@pytest.mark.parametrize("tp", ["col", "row", None])
def test_chipless_mosaic_lowering(tp):
    """Mosaic compiles at lowering time: `.lower(("tpu",))` on the CPU
    box surfaces TPU block/op violations without a chip — the
    test_pallas_tpu_lowering discipline for the new kernel."""
    # 512-sized dims stay 128-aligned PER SHARD on the 4-way mesh
    m, c, r, o, s = 8, 512, 8, 512, 4
    x2 = jnp.zeros((m, c), jnp.bfloat16)
    a_t = jnp.zeros((s, r, c), jnp.bfloat16)
    b_s = jnp.zeros((s, r, o), jnp.bfloat16)
    ids = jnp.zeros((m,), jnp.int32)
    if tp is None:
        def f(ids, x2, a_t, b_s):
            return plora._bgmv(ids, x2, a_t, b_s, 512, False)

        jax.jit(f).trace(ids, x2, a_t, b_s).lower(
            lowering_platforms=("tpu",))
        return
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(1, 4), ("data", "model"))

    def f(ids, x2, a_t, b_s):
        y, reason = plora.lora_bgmv_spmd(mesh, x2, a_t, b_s, ids, tp=tp)
        assert reason is None, reason
        return y

    jax.jit(f).trace(ids, x2, a_t, b_s).lower(
        lowering_platforms=("tpu",))


# ---------------------------------------------------------------------
# the adapter store
# ---------------------------------------------------------------------


@pytest.mark.lora(allow_single=True)
def test_store_load_evict_lru():
    store = LoraStore(_cfg(), max_adapters=2, rank=4,
                      adapters=dict(PERSONAS), engine_name="t")
    s1 = store.load("galahad")
    s2 = store.load("percival")
    assert sorted((s1, s2)) == [1, 2]
    assert store.resident() == ["galahad", "percival"]
    # full store: loading a third evicts the LRU unreferenced adapter
    s3 = store.load("lancelot")
    assert s3 == s1 and "galahad" not in store.resident()
    # refs pin against eviction
    store.acquire(["percival"])
    with pytest.raises(RuntimeError, match="reference"):
        store.evict("percival")
    store.acquire(["lancelot"])
    with pytest.raises(RuntimeError, match="exhausted"):
        store.load("galahad")
    store.release(["percival", "lancelot"])
    assert store.can_admit(["galahad"])
    assert store.load("galahad") in (1, 2)
    # accounting: one adapter's bytes = rank * (in+out) across targets
    per = store.adapter_bytes()
    dims = lora_dims(_cfg())
    assert per == sum(4 * (c + o) * 2 for c, o, _tp in dims.values())
    assert store.resident_bytes() == 2 * per


@pytest.mark.lora(allow_single=True)
def test_acquire_refs_resident_before_loading():
    """A full store acquiring [new, resident] must never LRU-evict the
    list's OWN resident adapter to make room for the new one — the
    resident pass refs first (review regression)."""
    store = LoraStore(_cfg(), max_adapters=2, rank=4,
                      adapters=dict(PERSONAS))
    # X resident via an EXPLICIT pair tree (weights not re-derivable
    # from its registered spec), Y fills the other slot
    x_tree = store.make_pair_tree("galahad")
    store.load("galahad", x_tree)
    store.load("percival")
    x_slot = store.slot_of("galahad")
    slots = store.acquire(["lancelot", "galahad"])
    # galahad kept its slot (percival was the LRU victim); a one-pass
    # acquire would have evicted galahad first and reloaded it from
    # its seed spec, silently discarding the explicit weights
    assert slots[1] == x_slot
    assert "percival" not in store.resident()
    assert store.describe()["refs"] == {"lancelot": 1, "galahad": 1}
    store.release(["lancelot", "galahad"])


@pytest.mark.lora(allow_single=True)
def test_stack_bytes_for_matches_store():
    from theroundtaible_tpu.engine.lora import stack_bytes_for
    for quant in ("none", "int8"):
        cfg_block = {"rank": 4, "max_adapters": 3, "quant": quant}
        store = LoraStore(_cfg(), rank=4, max_adapters=3, quant=quant)
        est = stack_bytes_for(_cfg(), cfg_block)
        real = store.stack_bytes()
        # int8 stacks also hold per-(slot, rank-row) scales the
        # closed form omits — tiny, but the fp form must be exact
        if quant == "none":
            assert est == real
        else:
            assert est <= real <= int(est * 1.2)
    # targets restriction honored (the fleet-plan drift regression)
    est_qv = stack_bytes_for(_cfg(), {"rank": 4, "max_adapters": 3,
                                      "targets": ["q_proj", "v_proj"]})
    store_qv = LoraStore(_cfg(), rank=4, max_adapters=3,
                         targets=["q_proj", "v_proj"])
    assert est_qv == store_qv.stack_bytes()


@pytest.mark.lora(allow_single=True)
def test_store_rejects_bad_config():
    with pytest.raises(ValueError, match="max_adapters"):
        LoraStore(_cfg(), max_adapters=0)
    with pytest.raises(ValueError, match="rank"):
        LoraStore(_cfg(), rank=0)
    with pytest.raises(ValueError, match="quant"):
        LoraStore(_cfg(), quant="int4")
    with pytest.raises(ValueError, match="unknown lora targets"):
        LoraStore(_cfg(), targets=["router"])
    store = LoraStore(_cfg(), adapters=dict(PERSONAS))
    with pytest.raises(KeyError, match="unknown lora adapter"):
        store.make_pair_tree("mordred")


@pytest.mark.lora(allow_single=True)
def test_store_int8_quantized_pairs():
    """`lora: {quant: int8}` stores the stacked pairs at one byte per
    element (quantize-aware A·B pairs); the dequantized apply stays
    close to the fp path and the kernel declines the int8 stack."""
    fp = LoraStore(_cfg(), rank=4, adapters=dict(PERSONAS))
    q8 = LoraStore(_cfg(), rank=4, quant="int8",
                   adapters=dict(PERSONAS))
    fp.load("galahad")
    q8.load("galahad")
    assert q8.adapter_bytes() * 2 == fp.adapter_bytes()
    from theroundtaible_tpu.engine.lora import _dequant_stack
    for key in fp.stacked:
        a_fp = np.asarray(fp.stacked[key]["a"], np.float32)
        a_q = np.asarray(_dequant_stack(q8.stacked[key]["a"],
                                        jnp.float32))
        scale = max(np.abs(a_fp).max(), 1e-6)
        assert np.max(np.abs(a_fp - a_q)) / scale < 0.02
    # the grouped kernel must decline int8 stacks with a stable reason
    eng_q = InferenceEngine(
        _cfg(), num_slots=2, mesh_shape=MESH1,
        lora={**LORA_CFG, "quant": "int8"})
    eng_q.generate_batch([("a", PROMPT)], max_new_tokens=4,
                         adapters_per_turn=["galahad"])
    paths = eng_q.lora_describe()["lora_paths"]
    assert paths["pallas_grouped"] == []
    reasons = {e.get("fallback_reason")
               for e in paths["xla_grouped_bmm"]}
    assert "quant:int8-stack" in reasons


@pytest.mark.lora(allow_single=True)
def test_pair_tree_npz_roundtrip(tmp_path):
    store = LoraStore(_cfg(), rank=4, adapters=dict(PERSONAS))
    tree = store.make_pair_tree("galahad")
    path = tmp_path / "galahad.npz"
    save_pair_tree(str(path), tree)
    store.register("from_disk", {"path": str(path)})
    loaded = store.make_pair_tree("from_disk")
    for key in tree:
        np.testing.assert_array_equal(tree[key][0], loaded[key][0])
        np.testing.assert_array_equal(tree[key][1], loaded[key][1])


@pytest.mark.lora(allow_single=True)
def test_lora_dims_families():
    dims = lora_dims(_cfg())
    assert set(dims) == {"q_proj", "k_proj", "v_proj", "o_proj",
                         "gate_proj", "up_proj", "down_proj"}
    e = _cfg().embed_dim
    assert dims["q_proj"][:2] == (e, _cfg().num_heads * _cfg().head_dim)
    assert dims["o_proj"][2] == "row" and dims["q_proj"][2] == "col"
    # MoE: expert matmuls have no tagged seam — attention-only targets
    moe = lora_dims(get_model_config("tiny-mixtral"))
    assert set(moe) == {"q_proj", "k_proj", "v_proj", "o_proj"}


# ---------------------------------------------------------------------
# engine serving
# ---------------------------------------------------------------------


@pytest.mark.lora(allow_single=True)
def test_persona_changes_output_deterministically(engine):
    base = engine.generate_batch([("k", PROMPT)], max_new_tokens=12,
                                 session="d0")[0]
    gal = engine.generate_batch([("k", PROMPT)], max_new_tokens=12,
                                session="d1",
                                adapters_per_turn=["galahad"])[0]
    gal2 = engine.generate_batch([("k", PROMPT)], max_new_tokens=12,
                                 session="d2",
                                 adapters_per_turn=["galahad"])[0]
    per = engine.generate_batch([("k", PROMPT)], max_new_tokens=12,
                                session="d3",
                                adapters_per_turn=["percival"])[0]
    assert gal == gal2           # same persona = same greedy stream
    assert len({base, gal, per}) == 3   # personas genuinely diverge


@pytest.mark.lora
def test_mixed_adapter_batch_token_parity(engine):
    """≥3 knights with distinct personas in ONE batched program,
    token-parity vs serving each adapter alone — the acceptance
    criterion's direct-serving half."""
    ads = [None, "galahad", "percival"]
    alone = [engine.generate_batch(
        [("k", PROMPT)], max_new_tokens=12, session=f"alone{i}",
        adapters_per_turn=[a])[0] for i, a in enumerate(ads)]
    mixed = engine.generate_batch(
        [("k0", PROMPT), ("k1", PROMPT), ("k2", PROMPT)],
        max_new_tokens=12, session="mixed", adapters_per_turn=ads)
    assert mixed == alone
    assert len(set(mixed)) == 3


@pytest.mark.lora(allow_single=True)
def test_kill_switch_byte_identity(monkeypatch):
    monkeypatch.setenv("ROUNDTABLE_LORA", "0")
    off = InferenceEngine(_cfg(), num_slots=2, mesh_shape=MESH1,
                          lora=dict(LORA_CFG))
    assert off.lora is None and off.lora_reason == "disabled:env"
    plain = InferenceEngine(_cfg(), num_slots=2, mesh_shape=MESH1)
    got = off.generate_batch([("a", PROMPT)], max_new_tokens=12,
                             adapters_per_turn=["galahad"])[0]
    want = plain.generate_batch([("a", PROMPT)], max_new_tokens=12)[0]
    assert got == want   # kill-switch restores base serving, verbatim


@pytest.mark.lora(allow_single=True)
def test_lora_declines_on_seq_parallel():
    eng = InferenceEngine(_cfg(), num_slots=2, mesh_shape=MESH1,
                          seq_parallel=2, lora=dict(LORA_CFG))
    assert eng.lora is None
    assert eng.lora_reason == "seq_parallel:ring-prefill"


@pytest.mark.lora
def test_describe_and_lora_paths(engine):
    engine.generate_batch(
        [("p0", PROMPT), ("p1", PROMPT)], max_new_tokens=4,
        session="paths", adapters_per_turn=["galahad", "percival"])
    info = engine.describe()["lora"]
    assert info["enabled"] and info["reason"] is None
    assert info["apply_tokens"] > 0
    store = info["store"]
    assert set(PERSONAS) >= set(store["resident"])
    paths = info["lora_paths"]
    # tiny-gemma dims are lane-misaligned, so every dispatch records an
    # XLA route with a machine-readable decline — never silence
    assert paths["xla_grouped_bmm"], paths
    for entry in paths["xla_grouped_bmm"]:
        assert entry["fallback_reason"]
        assert entry["leaf"] in lora_dims(_cfg())


@pytest.mark.lora(allow_single=True)
def test_unknown_adapter_raises(engine):
    with pytest.raises(ValueError, match="unknown lora adapters"):
        engine.generate_batch([("a", PROMPT)], max_new_tokens=4,
                              adapters_per_turn=["mordred"])
    with pytest.raises(ValueError, match="entries for"):
        engine.generate_batch([("a", PROMPT)], max_new_tokens=4,
                              adapters_per_turn=["galahad", None])


@pytest.mark.lora
def test_share_suppressed_for_mixed_adapters(engine):
    """Cross-knight prefix sharing moves K/V between slots — wrong
    across adapters, so mixed-adapter batches suppress the share
    passes (and say so in provenance)."""
    before = engine._lora_share_suppressed
    shared = ("the knights share a very long common preamble "
              * 8)
    engine.generate_batch(
        [("s0", shared + " galahad speaks"),
         ("s1", shared + " percival speaks")],
        max_new_tokens=4, session="mix",
        adapters_per_turn=["galahad", "percival"])
    assert engine._lora_share_suppressed == before + 1
    assert engine.lora_describe()["share_suppressed"] >= 1


@pytest.mark.lora(allow_single=True)
def test_prefix_cache_gated_to_base_rows():
    """Persona rows must neither FEED nor CONSUME the cross-session
    prefix cache: its content is base-adapter K/V."""
    eng = InferenceEngine(_cfg(), num_slots=4, kv_layout="paged",
                          page_size=32, num_pages=64, mesh_shape=MESH1,
                          lora=dict(LORA_CFG))
    assert eng.prefix_cache is not None
    prompt = "a long shared preamble all sessions repeat " * 6
    # adapter row commits — must NOT enter the index
    eng.generate_batch([("k", prompt)], max_new_tokens=4, session="a",
                       adapters_per_turn=["galahad"])
    assert eng.prefix_cache.page_count() == 0
    # base row commits — indexed; a second base session reuses it
    _, st0 = eng.generate_batch_with_stats(
        [("k", prompt)], max_new_tokens=4, session="b")
    assert eng.prefix_cache.page_count() > 0
    _, st1 = eng.generate_batch_with_stats(
        [("k", prompt)], max_new_tokens=4, session="c")
    assert st1.prefix_reused_tokens > 0
    # ... but a PERSONA row with the same prompt must serve cold
    _, st2 = eng.generate_batch_with_stats(
        [("k", prompt)], max_new_tokens=4, session="d",
        adapters_per_turn=["percival"])
    assert st2.prefix_reused_tokens == 0


@pytest.mark.lora(allow_single=True)
def test_adapter_flip_releases_stale_kv(engine):
    """A knight re-served under a DIFFERENT adapter must not reuse K/V
    baked under the old one: the flip forces a fresh prefill, so the
    output equals a cold serve under the new adapter."""
    cold = engine.generate_batch([("k", PROMPT)], max_new_tokens=12,
                                 session="flip-cold")[0]
    gal_cold = engine.generate_batch([("k", PROMPT)], max_new_tokens=12,
                                     session="flip-gcold",
                                     adapters_per_turn=["galahad"])[0]
    # persona → base
    engine.generate_batch([("k", PROMPT)], max_new_tokens=12,
                          session="flip",
                          adapters_per_turn=["galahad"])
    flipped = engine.generate_batch([("k", PROMPT)], max_new_tokens=12,
                                    session="flip")[0]
    assert flipped == cold
    # base → persona (the subtle direction: base rows label None, and
    # "never seen" must be a DISTINCT state or this flip would reuse
    # base-baked K/V under the persona delta — review regression)
    engine.generate_batch([("k", PROMPT)], max_new_tokens=12,
                          session="flip2")
    flipped2 = engine.generate_batch([("k", PROMPT)], max_new_tokens=12,
                                     session="flip2",
                                     adapters_per_turn=["galahad"])[0]
    assert flipped2 == gal_cold


@pytest.mark.lora(allow_single=True)
def test_adapter_flip_across_spill_gap():
    """The flip guard must fire AFTER the offload restore: a persona
    flip across a spill gap would otherwise release a non-resident
    name (no-op) and the restore would resurrect the old adapter's
    K/V bytes under the new delta — review regression."""
    eng = InferenceEngine(_cfg(), num_slots=4, kv_layout="paged",
                          page_size=32, num_pages=64, mesh_shape=MESH1,
                          lora=dict(LORA_CFG))
    assert eng.kv_offload is not None
    cold = eng.generate_batch([("k", PROMPT)], max_new_tokens=12,
                              session="spcold",
                              adapters_per_turn=["percival"])[0]
    eng.generate_batch([("k", PROMPT)], max_new_tokens=12, session="sp",
                       adapters_per_turn=["galahad"])
    assert eng.kv_offload.spill_session("sp") > 0
    flipped = eng.generate_batch([("k", PROMPT)], max_new_tokens=12,
                                 session="sp",
                                 adapters_per_turn=["percival"])[0]
    assert flipped == cold


@pytest.mark.lora(allow_single=True)
def test_direct_path_refuses_too_many_distinct(engine):
    engine.lora.register("gawain", {"seed": 31})
    engine.lora.register("bors", {"seed": 32})
    with pytest.raises(ValueError, match="distinct lora"):
        engine.generate_batch(
            [(f"k{i}", PROMPT) for i in range(4)], max_new_tokens=4,
            session="wide",
            adapters_per_turn=["galahad", "percival", "gawain",
                               "bors"])


# ---------------------------------------------------------------------
# observability / planning satellites
# ---------------------------------------------------------------------


@pytest.mark.lora(allow_single=True)
def test_fleet_estimate_counts_lora():
    from theroundtaible_tpu.engine.fleet import estimate_engine_hbm_bytes
    base = estimate_engine_hbm_bytes({"model": "tiny-gemma"})
    with_lora = estimate_engine_hbm_bytes(
        {"model": "tiny-gemma", "lora": {"rank": 8, "max_adapters": 8}})
    dims = lora_dims(get_model_config("tiny-gemma"))
    want = 9 * 8 * sum(c + o for c, o, _tp in dims.values()) * 2
    assert with_lora - base == want
    q8 = estimate_engine_hbm_bytes(
        {"model": "tiny-gemma",
         "lora": {"rank": 8, "max_adapters": 8, "quant": "int8"}})
    assert q8 - base == want // 2


@pytest.mark.lora(allow_single=True)
def test_memory_ledger_and_gauges(engine):
    from theroundtaible_tpu.engine import trace_hooks
    from theroundtaible_tpu.utils import telemetry
    ledger = trace_hooks.publish_memory_ledger(engine)
    assert ledger["lora_adapter_bytes"] == engine.lora.adapter_bytes()
    assert ledger["lora_stack_bytes"] == engine.lora.stack_bytes()
    snap = telemetry.REGISTRY.snapshot_compact()
    assert any(k.startswith("roundtable_lora_resident_adapters")
               for k in snap)
    # per-adapter bytes gauge dies with the adapter (gauge-leak lesson)
    # — matched on BOTH labels (other tests' stores share the registry)
    def mine(k):
        return (k.startswith("roundtable_lora_adapter_bytes")
                and "adapter=lancelot" in k
                and f"engine={engine.cfg.name}" in k)

    engine.lora.load("lancelot")
    assert any(mine(k) for k in telemetry.REGISTRY.snapshot_compact())
    engine.lora.evict("lancelot")
    assert not any(mine(k)
                   for k in telemetry.REGISTRY.snapshot_compact())


@pytest.mark.lora(allow_single=True)
def test_perfmodel_lora_ceiling():
    from theroundtaible_tpu.utils.perfmodel import V5E, EnginePerf
    perf = EnginePerf("t", param_bytes=1000, num_params=500, chip=V5E)
    base = perf._decode_ceiling()
    assert base == perf.decode_ceiling
    # per-sample override: adapter bytes fold into the streamed total
    assert perf._decode_ceiling(1000) == pytest.approx(base / 2)
    perf.set_lora_row_bytes(1000)
    assert perf._decode_ceiling() == pytest.approx(base / 2)
    assert perf._decode_ceiling(0) == base
    assert perf.describe()["lora_row_bytes"] == 1000


@pytest.mark.lora(allow_single=True)
def test_cache_key_and_public_imports():
    from theroundtaible_tpu.engine import _cache_key
    assert _cache_key({"model": "tiny-gemma"}) != _cache_key(
        {"model": "tiny-gemma", "lora": {"rank": 4}})
    import theroundtaible_tpu.engine as eng_pkg
    assert eng_pkg.LoraStore is LoraStore
    assert eng_pkg.lora_dims is lora_dims
    with pytest.raises(AttributeError):
        eng_pkg.not_a_thing


@pytest.mark.lora(allow_single=True)
def test_tpu_adapter_persona_map():
    from theroundtaible_tpu.adapters.base import KnightTurn
    from theroundtaible_tpu.adapters.tpu_llm import TpuLlmAdapter
    ad = TpuLlmAdapter("a", {
        "model": "tiny-gemma", "lora_adapter": "galahad",
        "knight_adapters": {"skeptic": "percival"}})
    assert ad.persona_adapter == "galahad"
    turns = [KnightTurn(knight_name="skeptic", prompt="x"),
             KnightTurn(knight_name="builder", prompt="y")]
    assert ad._adapters_for(turns) == ["percival", "galahad"]
    plain = TpuLlmAdapter("b", {"model": "tiny-gemma"})
    assert plain._adapters_for(turns) is None


# ---------------------------------------------------------------------
# scheduler: adapter-aware co-batching
# ---------------------------------------------------------------------


@pytest.mark.lora
@pytest.mark.scheduler
def test_scheduled_mixed_adapter_parity(paged_engine):
    """The acceptance criterion's scheduled half: one engine serves 3
    knights with distinct personas in a single mixed-adapter decode
    segment, token-parity vs serving each adapter alone."""
    from theroundtaible_tpu.engine.scheduler import SessionScheduler
    eng = paged_engine
    sched = SessionScheduler(eng, admit_hold_s=0.25)
    try:
        ads = [None, "galahad", "percival"]
        results: dict = {}
        errors: list = []

        def run(i, a):
            try:
                results[i] = sched.submit(
                    f"sess{i}", [("k", PROMPT)], max_new_tokens=16,
                    adapters_per_turn=[a])
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append(e)

        threads = [threading.Thread(target=run, args=(i, a))
                   for i, a in enumerate(ads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        texts = [results[i][0][0] for i in range(3)]
        assert len(set(texts)) == 3
        for i, a in enumerate(ads):
            alone = eng.generate_batch(
                [("k", PROMPT)], max_new_tokens=16,
                session=f"solo{i}", adapters_per_turn=[a])[0]
            assert alone == texts[i], f"adapter {a} diverged"
        # residency refs released at retirement
        assert not eng.lora.describe()["refs"]
    finally:
        sched.close()


@pytest.mark.lora(allow_single=True)
def test_scheduler_refuses_over_capacity(paged_engine):
    from theroundtaible_tpu.engine.scheduler import (SchedulerRefused,
                                                     SessionScheduler)
    sched = SessionScheduler(paged_engine)
    try:
        turns = [(f"k{i}", PROMPT) for i in range(4)]
        paged_engine.lora.register("extra", {"seed": 11})
        with pytest.raises(SchedulerRefused, match="distinct lora"):
            sched.submit("over", turns, max_new_tokens=4,
                         adapters_per_turn=["galahad", "percival",
                                            "lancelot", "extra"])
        with pytest.raises(ValueError, match="unknown lora"):
            sched.submit("unk", [("k", PROMPT)], max_new_tokens=4,
                         adapters_per_turn=["mordred"])
    finally:
        sched.close()


@pytest.mark.lora
@pytest.mark.scheduler
def test_strict_no_compile_across_adapter_swaps(monkeypatch):
    """Adapter hot-swaps and mixed-adapter recomposition are VALUES:
    after warmup declares steady state, loads/evicts/mixed batches
    compile nothing (the scheduler marker arms
    ROUNDTABLE_RECOMPILE_STRICT=1, so any recompile RAISES)."""
    from theroundtaible_tpu.engine.scheduler import SessionScheduler
    eng = InferenceEngine(
        _cfg(128), num_slots=4, mesh_shape=MESH1,
        lora={**LORA_CFG, "adapters": {**PERSONAS,
                                       "gawain": {"seed": 21,
                                                  "init_std": 0.6}}})
    eng.warmup(max_prompt_tokens=64, batch_sizes=(1, 2, 4))
    sched = SessionScheduler(eng, admit_hold_s=0.25)
    try:
        # warm the scheduler's own composition surface, then declare
        results: dict = {}
        errors: list = []

        def run(tag, ads):
            def go(i, a):
                try:
                    results[f"{tag}{i}"] = sched.submit(
                        f"{tag}{i}", [("k", PROMPT)], max_new_tokens=8,
                        adapters_per_turn=[a])
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            threads = [threading.Thread(target=go, args=(i, a))
                       for i, a in enumerate(ads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)

        run("w", [None, "galahad", "percival"])
        assert not errors, errors
        sched.declare_warmup_complete()
        # hot-swap: loading the 4th persona evicts the LRU resident,
        # then a mixed batch serves through the swapped slots — under
        # STRICT, a single recompile here raises.
        run("s", ["gawain", "lancelot", None])
        assert not errors, errors
        assert len({r[0][0] for r in results.values()}) >= 3
    finally:
        sched.close()


@pytest.mark.lora
@pytest.mark.spec_decode
@pytest.mark.parametrize("dtype", [None, jnp.float32],
                         ids=["serving_dtype", "float32"])
def test_spec_and_ragged_composition(dtype, monkeypatch):
    """LoRA composes with PR-8 ragged admission and PR-9 speculative
    decode: persona rows draft/verify through the SAME flat-buffer
    programs (per-token adapter ids), join mid-decode as ragged
    chunks, and the emitted streams match spec-off serving — token for
    token in float32. In the serving dtype the verify program and the
    decode step are two programs, and the CPU's compiler rounds a
    bfloat16 sum where it fused each: there the streams match up to a
    TIE — the first token that differs is the other program's runner-up,
    under two bfloat16 steps of the logit behind, in both programs (read
    off the logits each greedy pick saw). With a layer one body a
    program (ISSUE 55) the 18th token of `galahad` is such a tie: 470
    over 411 by 0.012 at 3.34 in the decode step, 411 over 470 by 0.003
    in the verify program; with the layers in place 470 led by 0.03 and
    0.05 in the two, two or three bfloat16 steps."""
    monkeypatch.setenv("ROUNDTABLE_RAGGED_DEFER_MIN", "16")
    from theroundtaible_tpu.engine.scheduler import SessionScheduler

    def build(spec_on):
        return InferenceEngine(
            _cfg(), num_slots=6, kv_layout="paged", page_size=32,
            num_pages=64, mesh_shape=MESH1, lora=dict(LORA_CFG),
            spec_decode=spec_on, **({"dtype": dtype} if dtype else {}))

    # repetitive prompt: the n-gram drafter proposes, greedy accepts
    rep = ("the scribe repeats the ruling verbatim. "
           "the scribe repeats the ruling verbatim. " * 3)

    def serve(eng):
        sched = SessionScheduler(eng, admit_hold_s=0.25)
        try:
            results: dict = {}
            errors: list = []

            def run(i, a, prompt):
                try:
                    results[i] = sched.submit(
                        f"c{i}", [("k", prompt)], max_new_tokens=24,
                        adapters_per_turn=[a])
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            threads = [
                threading.Thread(target=run, args=(0, "galahad", rep)),
                threading.Thread(target=run, args=(1, "percival", rep)),
                threading.Thread(target=run, args=(2, None, rep))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            assert not errors, errors
            return [results[i][0][0] for i in range(3)]
        finally:
            sched.close()

    if dtype is not None:
        on = serve(build(True))
        off = serve(build(False))
        assert on == off   # speculation is output-invariant under personas
        return
    # The serving dtype: every greedy pick also hands its two best
    # logits to the host (the tap lives in these two engines' programs).
    picks: list = []
    pick = jnp.argmax

    def tapped(x, axis=None, **kw):
        if axis == -1 and getattr(x, "ndim", 0) >= 2:
            best, ids = jax.lax.top_k(x.astype(jnp.float32), 2)
            jax.debug.callback(
                lambda v, i: picks.append((np.asarray(v).reshape(-1, 2),
                                           np.asarray(i).reshape(-1, 2))),
                best, ids)
        return pick(x, axis=axis, **kw)

    monkeypatch.setattr(jnp, "argmax", tapped)
    streams, seen = [], []
    for spec_on in (True, False):
        eng = build(spec_on)
        ids: dict = {}

        def decode(tokens, ids=ids, decode=eng.tokenizer.decode):
            return ids.setdefault(tuple(tokens), decode(tokens))

        monkeypatch.setattr(eng.tokenizer, "decode", decode)
        texts = serve(eng)
        streams.append([next(list(t) for t, text in ids.items()
                             if text == want) for want in texts])
        seen.append((np.concatenate([v for v, _ in picks]),
                     np.concatenate([i for _, i in picks])))
        picks.clear()
    step = float(jnp.finfo(jnp.bfloat16).eps)
    for on, off in zip(*streams):
        assert len(on) == len(off) == 24
        if on == off:
            continue
        at = next(i for i in range(24) if on[i] != off[i])
        pair = sorted((on[at], off[at]))
        for best, ids in seen:       # in the verify program AND the step
            tied = np.all(np.sort(ids, axis=1) == pair, axis=1)
            assert tied.any(), (at, pair)
            gap = (best[tied, 0] - best[tied, 1]) / np.abs(best[tied, 0])
            assert gap.min() < 2 * step, (at, pair, gap.min())


@pytest.mark.lora(allow_single=True)
def test_ragged_batch_carries_token_adapters():
    from theroundtaible_tpu.engine.serving_loop import (RaggedSeq,
                                                        build_ragged_batch)
    table = np.zeros(4, np.int32)
    seqs = [RaggedSeq([5, 6, 7], 0, table, adapter=2),
            RaggedSeq([9], 3, table, adapter=0),
            RaggedSeq([4, 4], 0, table, adapter=1)]
    batch = build_ragged_batch(seqs, t_budget=32, s_max=4,
                               pages_per_seq=4, scratch_page=3,
                               pad_id=0, page_size=32)
    ta = batch["token_adapter"]
    assert ta.shape == (32,)
    assert list(ta[:3]) == [2, 2, 2]
    assert ta[8] == 0                 # second seq's run
    assert list(ta[16:18]) == [1, 1]  # third seq's run
    assert ta[3:8].sum() == 0         # pad rows ride the base adapter
