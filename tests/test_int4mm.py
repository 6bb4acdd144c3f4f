"""Fused w4a16 Pallas matmul (engine/pallas/int4mm.py) — semantic parity
with the XLA dequant path, exercised in interpret mode on CPU (the same
strategy the attention kernels use; the kernels' PERFORMANCE claim is
validated on hardware by bench_microquant.py / bench.py int4).

The kernels compute bit-identical dequantized weights (same nibble
extraction, same grouped scale in the activation dtype); only the f32
accumulation ORDER differs (blocked), so comparisons allow float-order
tolerance, and greedy token parity must hold end to end.

Shard-aware coverage (ISSUE 3): einsum_int4_spmd parity on virtual
(data, model) meshes across even AND uneven shard counts, non-dividing
group sizes, and every decode-hot projection spec — plus the
shard-aligned group selection quantize_params emits. Kernel-claiming
tests carry @pytest.mark.quant_kernels: the conftest guard fails them
loud on any silent XLA fallback.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theroundtaible_tpu.engine.models.common import (Int4Leaf, ModelConfig,
                                                     dequant_int4,
                                                     init_params, forward)
from theroundtaible_tpu.engine.pallas import int4mm
from theroundtaible_tpu.engine.quant import (_int4_group_for,
                                             _quantize_leaf_int4,
                                             quantize_params)


@pytest.fixture(autouse=True)
def _force_kernel(monkeypatch):
    monkeypatch.setenv("ROUNDTABLE_INT4_MM", "1")


def _leaf(shape, group=64, dtype=jnp.float32, seed=0) -> Int4Leaf:
    w = jax.random.normal(jax.random.PRNGKey(seed), shape,
                          dtype=jnp.float32) * 0.1
    leaf = _quantize_leaf_int4(w.astype(dtype), (0,), dtype, False, group)
    assert isinstance(leaf, Int4Leaf)
    return leaf


def _xla_ref(spec, a, leaf):
    return jnp.einsum(spec, a,
                      dequant_int4(leaf.q4, leaf.s4, leaf.axis,
                                   leaf.group, a.dtype),
                      preferred_element_type=jnp.float32)


# Every serving einsum shape class: mlp up/gate, mlp down, qkv (2 kept
# dims), o_proj (2 contracted dims), lm head (contracted pack axis).
CASES = [
    ("bte,ef->btf", (2, 3, 256), (256, 512)),
    ("btf,fe->bte", (2, 3, 512), (512, 256)),
    # c_dim 1024 → bc 512 → TWO contraction blocks: numerically
    # exercises the set/add/flush accumulation across c, which every
    # other case (bc == c_dim) leaves untested
    ("btf,fe->bte", (2, 3, 1024), (1024, 256)),
    ("bte,ehd->bthd", (1, 3, 256), (256, 4, 128)),
    ("bthd,hde->bte", (1, 3, 4, 128), (4, 128, 256)),
    ("bte,ve->btv", (2, 1, 256), (512, 256)),
]


@pytest.mark.quant_kernels
@pytest.mark.parametrize("spec,ashape,wshape", CASES)
def test_kernel_matches_xla_dequant(spec, ashape, wshape):
    leaf = _leaf(wshape)
    a = jax.random.normal(jax.random.PRNGKey(1), ashape,
                          dtype=jnp.float32)
    got = int4mm.einsum_int4(spec, a, leaf)
    assert got is not None, f"kernel declined supported case {spec}"
    want = _xla_ref(spec, a, leaf)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.quant_kernels
def test_bf16_activations_match():
    spec, ashape, wshape = CASES[0]
    leaf = _leaf(wshape, dtype=jnp.bfloat16)
    a = (jax.random.normal(jax.random.PRNGKey(2), ashape) * 0.5) \
        .astype(jnp.bfloat16)
    got = int4mm.einsum_int4(spec, a, leaf)
    want = _xla_ref(spec, a, leaf)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_declines_unblockable_and_moe():
    # MoE expert spec: weight dims are kept+cont+kept — not a prefix or
    # suffix split, must fall back to the XLA path.
    leaf = _leaf((2, 256, 512))
    a = jax.random.normal(jax.random.PRNGKey(3), (1, 3, 256))
    assert int4mm.einsum_int4("bte,xef->btxf", a, leaf) is None
    # tiny router: last dim too small to block
    tiny = _leaf((256, 8), group=8)
    assert int4mm.einsum_int4("bte,ex->btx", a, tiny) is None


@pytest.mark.quant_kernels
def test_tpu_mosaic_lowering(monkeypatch):
    """Cross-lower every kernel shape class for the TPU platform WITHOUT
    a chip: Mosaic runs in jaxlib at lowering time, so layout/op-support
    violations (lane-aligned block minors, repeat/interleave lowering)
    surface here instead of burning a hardware window. This is the test
    that caught the scale-block minor-dim violation pre-flight."""
    monkeypatch.setattr(int4mm, "_interpret", lambda: False)
    # Lowering is one step short of the compile the v5e's compiler
    # refuses (int4mm.MOSAIC_REFUSAL, tests/test_chip_compile.py): lift
    # the plan-time gate so this keeps guarding what the repair needs.
    monkeypatch.setattr(int4mm, "MOSAIC_REFUSAL", {})
    rng = np.random.default_rng(0)
    cases = [
        ("be,ef->bf", (1, 2048), (2048, 16384)),      # mlp up/gate
        ("bf,fe->be", (1, 16384), (16384, 2048)),     # mlp down
        ("be,ehd->bhd", (1, 2048), (2048, 8, 256)),   # qkv
        ("bhd,hde->be", (1, 8, 256), (8, 256, 2048)),  # o_proj
        ("be,ve->bv", (1, 2048), (32768, 2048)),      # lm head
    ]
    for spec, ashape, wshape in cases:
        w = jnp.asarray(rng.standard_normal(wshape).astype(np.float32)
                        * 0.02, jnp.bfloat16)
        leaf = _quantize_leaf_int4(w, (0,), jnp.bfloat16, False, 64)
        a = jnp.asarray(rng.standard_normal(ashape).astype(np.float32),
                        jnp.bfloat16)

        def f(a, q4, s4, leaf=leaf, spec=spec):
            y = int4mm.einsum_int4(
                spec, a, Int4Leaf(q4=q4, s4=s4, axis=leaf.axis,
                                  group=leaf.group))
            assert y is not None, f"kernel declined {spec}"
            return y

        jax.jit(f).trace(a, leaf.q4, leaf.s4).lower(
            lowering_platforms=("tpu",))


BLOCKABLE = ModelConfig(
    name="int4mm-test", vocab_size=512, num_layers=2, embed_dim=256,
    num_heads=4, num_kv_heads=2, head_dim=128, mlp_dim=512,
    max_seq_len=64, tie_embeddings=True)


@pytest.mark.quant_kernels
def test_engine_serving_token_parity(monkeypatch):
    """The kernels inside the REAL serving path — engine build, slot
    cache, jitted decode while_loop with donated buffers — not just a
    bare forward: greedy generations must be identical with the kernel
    forced on vs off. Dims chosen so every matmul takes the kernel path
    (registry tiny models decline on block sizes, which would make this
    vacuous). Mesh pinned to one device — the sharded serving path has
    its own test below."""
    import dataclasses

    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.sampling import SamplingParams

    cfg = dataclasses.replace(BLOCKABLE, max_seq_len=128)
    outs = {}
    for flag in ("1", "0"):
        monkeypatch.setenv("ROUNDTABLE_INT4_MM", flag)
        eng = InferenceEngine(
            cfg, num_slots=2, quant="int4",
            mesh_shape={"data": 1, "model": 1},
            sampling=SamplingParams(temperature=0.0, max_new_tokens=8))
        outs[flag] = eng.generate("knights debate the packed nibbles",
                                  slot_name="k", max_new_tokens=8)
    assert outs["1"] == outs["0"]


# --- shard-aware dispatch (einsum_int4_spmd, ISSUE 3) ---


def _mesh(shape, axes=("data", "model")):
    n = int(np.prod(shape))
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices")
    return jax.sharding.Mesh(
        np.array(jax.devices()[:n]).reshape(shape), axes)


# Every decode-hot projection spec with its TP convention; dims sized so
# per-shard blocks exist up to a 4-way model axis (local lane dim 128).
SPMD_CASES = [
    ("bte,ef->btf", "col", (2, 3, 256), (256, 1024)),     # gate/up
    ("btf,fe->bte", "row", (2, 3, 1024), (1024, 256)),    # down (+psum)
    ("bte,ehd->bthd", "col", (1, 3, 256), (256, 8, 128)),  # qkv
    ("bthd,hde->bte", "row", (1, 3, 8, 128), (8, 128, 256)),  # o (+psum)
    ("bte,ve->btv", "col", (2, 1, 256), (512, 256)),      # tied lm head
]


@pytest.mark.quant_kernels
@pytest.mark.parametrize("spec,tp,ashape,wshape", SPMD_CASES)
@pytest.mark.parametrize("mesh_shape", [(1, 2), (2, 2), (1, 4)])
def test_spmd_kernel_matches_xla_dequant(spec, tp, ashape, wshape,
                                         mesh_shape):
    mesh = _mesh(mesh_shape)
    shards = mesh_shape[1]
    w = jax.random.normal(jax.random.PRNGKey(0), wshape,
                          dtype=jnp.float32) * 0.1
    leaf = _quantize_leaf_int4(w, (0,), jnp.float32, False, 64, shards)
    assert isinstance(leaf, Int4Leaf)
    a = jax.random.normal(jax.random.PRNGKey(1), ashape,
                          dtype=jnp.float32)
    got, reason = int4mm.einsum_int4_spmd(mesh, spec, a, leaf, tp=tp)
    assert got is not None, f"spmd dispatch declined: {reason}"
    want = _xla_ref(spec, a, leaf)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.quant_kernels
@pytest.mark.parametrize("group", [64, 32, 16])
def test_spmd_kernel_non_dividing_groups(group):
    """Group sizes that don't divide 128-lane blocks evenly into shards
    still serve on the kernel (the plan checks bp % gp per shard)."""
    mesh = _mesh((1, 2))
    w = jax.random.normal(jax.random.PRNGKey(2), (256, 512)) * 0.1
    leaf = _quantize_leaf_int4(w, (0,), jnp.float32, False, group, 2)
    a = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 256))
    got, reason = int4mm.einsum_int4_spmd(mesh, "bte,ef->btf", a, leaf,
                                          tp="col")
    assert got is not None, reason
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_xla_ref("bte,ef->btf", a,
                                                   leaf)),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.quant_kernels
def test_spmd_kernel_uneven_shard_count():
    """A model axis that does NOT divide the weight's shard axis (8
    heads over 3 shards) replicates — matching _fallback_replicated's
    placement — and still runs the kernel, not the XLA fallback."""
    mesh = _mesh((1, 3))
    spec, tp, ashape, wshape = SPMD_CASES[2]
    w = jax.random.normal(jax.random.PRNGKey(4), wshape) * 0.1
    leaf = _quantize_leaf_int4(w, (0,), jnp.float32, False, 64, 3)
    a = jax.random.normal(jax.random.PRNGKey(5), ashape)
    got, reason = int4mm.einsum_int4_spmd(mesh, spec, a, leaf, tp=tp)
    assert got is not None, reason
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_xla_ref(spec, a, leaf)),
                               rtol=3e-5, atol=3e-5)


def test_spmd_declines_with_reason():
    """Declines surface machine-readable reasons — prefill-M rows, MoE
    expert specs, and per-shard blocks too small to serve."""
    mesh = _mesh((1, 2))
    leaf = _leaf((256, 1024))
    big_a = jax.random.normal(jax.random.PRNGKey(6), (2, 64, 256))
    y, reason = int4mm.einsum_int4_spmd(mesh, "bte,ef->btf", big_a, leaf,
                                        tp="col")
    assert y is None and "prefill-m" in reason
    moe = _leaf((2, 256, 512))
    a = jax.random.normal(jax.random.PRNGKey(7), (1, 3, 256))
    y, reason = int4mm.einsum_int4_spmd(mesh, "bte,xef->btxf", a, moe)
    assert y is None and reason.startswith("spec:")
    # per-shard kept dim below the smallest block on an 8-way axis
    mesh8 = _mesh((1, 8))
    small = _leaf((256, 512))
    y, reason = int4mm.einsum_int4_spmd(mesh8, "bte,ef->btf",
                                        jax.random.normal(
                                            jax.random.PRNGKey(8),
                                            (2, 3, 256)),
                                        small, tp="col")
    assert y is None and "sharded" in reason


def test_shard_aligned_group_selection():
    """quantize_params(model_shards=m) must emit groups dividing the
    PER-SHARD pack dim for leaves whose pack axis is model-sharded
    (dense gate/up), so no group straddles a shard boundary."""
    assert _int4_group_for(512, 64, 1) == 64
    assert _int4_group_for(512, 64, 4) == 64    # 128 per shard
    assert _int4_group_for(768, 64, 4) == 64    # 192 per shard → 64 | 192
    assert _int4_group_for(768, 40, 4) == 32    # largest even g | 192
    assert _int4_group_for(8, 64, 4) == 2
    cfg = BLOCKABLE
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    qp = quantize_params(params, cfg, act_dtype=jnp.float32, bits=4,
                         model_shards=2)
    gate = qp["layers"][0]["gate_proj"]
    assert isinstance(gate, Int4Leaf)
    assert (cfg.mlp_dim // 2) % gate.group == 0
    # q4/s4 both divide on the sharded pack axis — co-partitionable
    assert gate.q4.shape[-1] % 2 == 0 and gate.s4.shape[-1] % 2 == 0


SHARDED = ModelConfig(
    name="int4mm-spmd-test", vocab_size=512, num_layers=2, embed_dim=256,
    num_heads=4, num_kv_heads=4, head_dim=128, mlp_dim=512,
    max_seq_len=128, tie_embeddings=True)


@pytest.mark.quant_kernels(allow=("rows:prefill-m",))
def test_engine_sharded_serving_token_parity(monkeypatch):
    """The tentpole end to end on the MAIN engine: a real TP mesh
    (model=2), int4 params quantized shard-aligned, decode through the
    jitted while_loop — greedy tokens identical with the kernels forced
    on vs off, and the path-provenance report shows every decode-hot
    projection on the kernel path (guard: any non-prefill-M fallback
    fails loud)."""
    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.sampling import SamplingParams

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    outs, eng = {}, None
    for flag in ("1", "0"):
        monkeypatch.setenv("ROUNDTABLE_INT4_MM", flag)
        e = InferenceEngine(
            SHARDED, num_slots=2, quant="int4",
            mesh_shape={"data": 1, "model": 2},
            sampling=SamplingParams(temperature=0.0, max_new_tokens=8))
        outs[flag] = e.generate("knights shard the packed nibbles",
                                slot_name="k", max_new_tokens=8)
        if flag == "1":
            eng = e
    assert outs["1"] == outs["0"]
    rep = eng.int4_path_report()
    kernel_specs = {x["spec"] for x in rep["pallas_w4a16"]}
    for s in ("bte,ehd->bthd", "bte,ekd->btkd", "bthd,hde->bte",
              "bte,ef->btf", "btf,fe->bte", "bte,ve->btv"):
        assert s in kernel_specs, (s, rep)
    assert eng.describe()["int4_paths"] == rep
    # stats plumbing: the per-call snapshot carries the same report
    _, stats = eng.generate_batch_with_stats(
        [("k", "and continue the debate")], max_new_tokens=4)
    assert stats.int4_paths["pallas_w4a16"]


@pytest.mark.quant_kernels
def test_model_forward_token_parity(monkeypatch):
    """Full int4 forward with the kernel on vs off: same greedy tokens,
    close logits. Dims chosen so every matmul takes the kernel path.
    Runs under an announced 1-device mesh — the only context in which
    `_einsum` emits the kernel (engine jits always announce theirs)."""
    from theroundtaible_tpu.engine.models.common import spmd_mesh

    params = init_params(BLOCKABLE, jax.random.PRNGKey(0), jnp.float32)
    qp = quantize_params(params, BLOCKABLE, act_dtype=jnp.float32, bits=4)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0, 512)
    positions = jnp.broadcast_to(jnp.arange(8)[None], (2, 8))
    valid = jnp.full((2,), 8, jnp.int32)
    mesh1 = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("one",))

    with spmd_mesh(mesh1):
        logits_k, _ = forward(qp, BLOCKABLE, tokens, positions, None,
                              None, valid)
    monkeypatch.setenv("ROUNDTABLE_INT4_MM", "0")
    with spmd_mesh(mesh1):
        logits_x, _ = forward(qp, BLOCKABLE, tokens, positions, None,
                              None, valid)
    np.testing.assert_allclose(np.asarray(logits_k),
                               np.asarray(logits_x),
                               rtol=1e-4, atol=1e-4)
    assert jnp.array_equal(jnp.argmax(logits_k, -1),
                           jnp.argmax(logits_x, -1))
