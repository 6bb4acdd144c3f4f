"""Weight quantization for serving: int8 (w8a16) and grouped int4 (w4a16).

Decode throughput is weight-streaming-bound: every generated token reads
every parameter from HBM once, so bf16 weights cap a v5e-1 at roughly
bandwidth / (2 · params) tok/s. Symmetric per-output-channel int8 halves
the bytes streamed — close to 2× the decode ceiling — while activations
stay bf16 (the int8→bf16 convert fuses into the matmul operand on the
MXU). This also mirrors what the reference's serving stack actually does:
Ollama/llama.cpp serves quantized GGUF by default (reference
src/adapters/local-llm.ts reaches 4-bit llama.cpp kernels), so bf16-only
serving would be racing a quantized baseline with one leg tied.

Representations (consumers must handle BOTH — `quantized()` is the
predicate):
- bits=8: each big matmul weight leaf becomes a dict
  {"q": int8[w.shape], "s": act_dtype[kept axes]}
  where `s` = absmax/127 over the einsum-CONTRACTED axes (w ≈ q * s with
  s broadcast over the kept/output axes). models/common.py's `_einsum`
  and `embed_tokens` dequantize by scaling the matmul OUTPUT — a fusable
  elementwise multiply — never materializing a bf16 copy of the weight.
- bits=4: an Int4Leaf (models/common.py) — two SIGNED nibbles packed per
  int8 byte along the weight's LAST axis, per-`group` absmax/7 scales
  (axis/group are static pytree metadata). Dequant is a bitcast
  (int8 → 2×int4, minor-most expansion) + convert + grouped scale that
  fuses into the consuming matmul operand on TPU; a leaf whose last dim
  cannot group falls back to the int8 dict form, so bits=4 trees are
  MIXED by design.
Norm weights stay untouched (tiny, accuracy-critical), and so does the
MoE router (tiny, and its top-k expert SELECTION amplifies quantization
error discontinuously — see the _SCALE_AXES note).

Quantization runs AFTER shard_params: q/s are computed with jnp ops on
the already-sharded weights, so XLA propagates the NamedShardings (q
inherits the weight's, s keeps the kept axes') and no separate spec tree
is needed. Absmax over a sharded contracted axis costs one all-reduce at
load time.

Scope: every serving path — InferenceEngine (dense + flash attention,
paged KV, MoE) and the ring/Ulysses sequence-parallel
prefill — all of which reach weights exclusively through the
quant-aware _einsum/embed_tokens accessors.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from .models.common import ModelConfig, Params

# Per weight key: the axes KEPT by the scale (the einsum's non-contracted
# weight axes, which land trailing in the matmul output).
_SCALE_AXES: dict[str, tuple[int, ...]] = {
    "q_proj": (1, 2),      # [E, H, D] → s[H, D]
    "k_proj": (1, 2),      # [E, K, D] → s[K, D]
    "v_proj": (1, 2),
    "o_proj": (2,),        # [H, D, E] → s[E]
    "gate_proj": (1,),     # dense [E, F] → s[F]
    "up_proj": (1,),
    "down_proj": (1,),     # dense [F, E] → s[E]
    # NOTE: the MoE "router" is deliberately ABSENT — it stays full
    # precision. Router logits pick top-k experts, a DISCONTINUOUS
    # decision: near-tied logits flip expert selection under
    # fraction-of-a-step perturbations, and a flipped expert changes
    # the output by whole-activation magnitudes (tests/test_quant.py
    # measures exactly this amplification on tiny-mixtral — even
    # embedding-quant noise upstream of an fp router can flip a
    # near-tied choice on random weights). Quantizing the decision-maker
    # itself invites those flips for E×X params of savings — bytes-
    # irrelevant — so it stays fp, which is standard MoE deployment
    # practice.
    "embedding": (0,),     # [V, E] → s[V] (row scale: lookup AND lm head)
    "lm_head": (0,),
}
_EXPERT_SCALE_AXES = {
    "gate_proj": (0, 2),   # [X, E, F] → s[X, F]  ("bte,xef->btxf")
    "up_proj": (0, 2),
    "down_proj": (2,),     # [X, F, E] → s[E]     ("btxf,xfe->bte")
}


# The int4 packer always groups/packs along the weight's LAST axis: any
# axis is mathematically valid (int4 dequant is a full elementwise
# multiply before the contraction), but only the minor-most axis lets
# the unpack be a bitcast whose nibble pair expands in place — the
# layout XLA/Mosaic fuses into the matmul operand on TPU. Packing the
# contracted axis (the llama.cpp convention, used in an earlier
# revision) forced an interleaving stack+reshape that broke operand
# fusion on real TPU and decoded slower than bf16 (measured once before
# PR 1; not re-measured). Scales remain per-group ×
# per-every-other-coordinate, so grouping along a
# kept axis changes only which direction group error correlates.


def quantized(leaf: Any) -> bool:
    from .models.common import Int4Leaf
    return (isinstance(leaf, dict) and "q" in leaf and "s" in leaf) \
        or isinstance(leaf, Int4Leaf)


def _quantize_leaf(w, scale_axes: tuple[int, ...], act_dtype,
                   free_source: bool) -> dict[str, Any]:
    scale_axes = tuple(a % w.ndim for a in scale_axes)
    reduce_axes = tuple(a for a in range(w.ndim) if a not in scale_axes)
    w32 = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=reduce_axes)
    s = jnp.maximum(absmax, 1e-8) / 127.0
    s_full = jnp.expand_dims(s, reduce_axes)
    q = jnp.clip(jnp.round(w32 / s_full), -127, 127).astype(jnp.int8)
    out = {"q": q, "s": s.astype(act_dtype)}
    if free_source and isinstance(w, jax.Array):
        # Free each source leaf the moment its int8 replacement exists:
        # quantizing a 7B-class model then peaks at bf16-total + ONE
        # leaf's q instead of bf16-total + int8-total — the difference
        # between fitting and OOMing a 16 GB chip during engine build.
        jax.block_until_ready(out)
        w.delete()
    return out


def _int4_group_for(dim: int, group: int, shards: int = 1) -> int:
    """Largest even divisor of `dim` that is <= group (0 = no valid
    grouping; the leaf then falls back to int8). When the pack axis is
    TP-sharded over `shards` devices, the group must divide the
    PER-SHARD dim so no group (and no packed byte) ever straddles a
    shard boundary — the shard-aware kernel dispatch (pallas/int4mm
    einsum_int4_spmd) partitions q4/s4 along that axis with whole
    groups per shard, and a straddling group would need cross-shard
    scale reads mid-kernel. g | dim/shards implies g | dim, so the
    full-axis grouping below stays valid."""
    if shards > 1 and dim % shards == 0:
        dim = dim // shards
    for g in range(min(group, dim), 1, -1):
        if g % 2 == 0 and dim % g == 0:
            return g
    return 0


def _quantize_leaf_int4(w, scale_axes: tuple[int, ...],
                        act_dtype, free_source: bool,
                        group: int, pack_shards: int = 1) -> Any:
    """Symmetric per-group int4 (w ≈ q4 * s4, |q4| <= 7), two nibbles
    packed per int8 byte along the LAST axis (even element → low
    nibble — the order `lax.bitcast_convert_type` unpacks, see
    dequant_int4). `pack_shards` > 1 aligns the grouping to the TP
    shard boundary (see _int4_group_for) for leaves whose pack axis is
    model-sharded. A last dim that can't group falls back to that leaf
    staying int8 — mixed trees serve fine (the einsum seam dispatches
    per leaf)."""
    from .models.common import Int4Leaf

    dim = w.shape[-1]
    g = _int4_group_for(dim, group, pack_shards)
    if g < 2:
        return _quantize_leaf(w, scale_axes, act_dtype, free_source)
    w32 = w.astype(jnp.float32)
    wg = w32.reshape(w.shape[:-1] + (dim // g, g))
    absmax = jnp.max(jnp.abs(wg), axis=-1, keepdims=True)
    s = jnp.maximum(absmax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(wg / s), -8, 7).astype(jnp.int8)
    q2 = q.reshape(w.shape[:-1] + (dim // 2, 2))
    even, odd = q2[..., 0], q2[..., 1]
    packed = (((odd.astype(jnp.int32) & 0xF) << 4)
              | (even.astype(jnp.int32) & 0xF)).astype(jnp.int8)
    s4 = jnp.squeeze(s, axis=-1).astype(act_dtype)
    out = Int4Leaf(q4=packed, s4=s4, axis=w.ndim - 1, group=g)
    if free_source and isinstance(w, jax.Array):
        jax.block_until_ready((out.q4, out.s4))
        w.delete()
    return out


def quantize_params(params: Params, cfg: ModelConfig,
                    act_dtype=jnp.bfloat16,
                    free_source: bool = False, bits: int = 8,
                    group: int = 64, model_shards: int = 1) -> Params:
    """Quantize the big matmul weights; returns a new tree (norms and any
    unrecognized leaves pass through untouched).

    bits=8 → per-output-channel int8 dicts; bits=4 → per-`group` packed
    Int4Leaf (a leaf whose pack dim can't group falls back to int8).

    model_shards (bits=4): the mesh's model-axis size. Leaves whose PACK
    axis is the model-sharded axis per sharding.param_specs (dense
    gate/up: [E, F] packed AND sharded on F) get their group aligned to
    the per-shard dim, so the shard-aware kernel dispatch partitions
    scales with whole groups per shard (sharding.int4_shard_axis /
    pallas/int4mm einsum_int4_spmd). Every other leaf packs an
    unsharded axis and is unaffected.

    free_source=True deletes each source weight buffer as soon as its
    quantized replacement is materialized — the caller must own `params`
    (every serving engine does: the init/load tree is not referenced
    after quantization). Pass-through leaves are never deleted."""
    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits}")

    pack_specs = None
    if bits == 4 and model_shards > 1:
        from .sharding import param_specs
        pack_specs = param_specs(cfg)

    def _pack_shards(value, key, expert):
        """model_shards when this leaf's LAST (pack) axis is the
        model-sharded axis and divides, else 1 — mirroring
        _fallback_replicated's placement decision."""
        if pack_specs is None:
            return 1
        from .sharding import MODEL_AXIS
        layer0 = pack_specs["layers"][0]
        spec = (layer0.get("experts", {}).get(key) if expert
                else pack_specs.get(key, layer0.get(key)))
        if spec is None:
            return 1
        entries = tuple(spec)
        if (len(entries) == value.ndim and entries[-1] == MODEL_AXIS
                and value.shape[-1] % model_shards == 0):
            return model_shards
        return 1

    def one(value, key, expert=False):
        scale_axes = (_EXPERT_SCALE_AXES if expert else _SCALE_AXES)[key]
        if bits == 4:
            return _quantize_leaf_int4(value, scale_axes,
                                       act_dtype, free_source, group,
                                       _pack_shards(value, key, expert))
        return _quantize_leaf(value, scale_axes, act_dtype, free_source)

    out: Params = {}
    for key, value in params.items():
        if key in ("embedding", "lm_head"):
            out[key] = one(value, key)
        elif key == "layers":
            out[key] = [_quantize_layer(layer, act_dtype, free_source,
                                        one)
                        for layer in value]
        else:
            out[key] = value
    return out


def _quantize_layer(layer: dict[str, Any], act_dtype,
                    free_source: bool, one) -> dict[str, Any]:
    new: dict[str, Any] = {}
    for key, value in layer.items():
        if key == "experts":
            new[key] = {k: one(v, k, expert=True)
                        for k, v in value.items()}
        elif key in _SCALE_AXES and "norm" not in key:
            new[key] = one(value, key)
        else:
            new[key] = value
    return new


def quantize_lora_stack(stack: jax.Array, act_dtype) -> dict[str, Any]:
    """Symmetric int8 quantization of a STACKED LoRA tensor [S, r, X]
    (ISSUE 10 quantize-aware adapter store): per-(slot, rank-row)
    absmax scales over the last axis, the same w ≈ q·s contract as the
    int8 weight dicts above — so a K-adapter store streams half the
    delta bytes. The all-zero base slot quantizes to zeros exactly
    (absmax floor only guards division). Apply-side dequant
    (engine/lora._dequant_stack) materializes the tiny tensors; the
    grouped Pallas kernel declines int8 stacks ("quant:int8-stack")."""
    w32 = stack.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=-1)
    s = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w32 / s[..., None]), -127, 127)
    return {"q": q.astype(jnp.int8), "s": s.astype(act_dtype)}


def quantize_lora_slot(leaf: dict[str, Any], slot, value32,
                       set_slot) -> dict[str, Any]:
    """Hot-swap ONE slot of an int8-quantized LoRA stack: quantize the
    incoming f32 [r, X] rows with the same per-rank-row absmax rule and
    write q/s through the store's compiled setter (values only — the
    stacked shapes never change, so swaps compile nothing)."""
    absmax = jnp.max(jnp.abs(value32), axis=-1)
    s = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(value32 / s[..., None]), -127, 127)
    return {"q": set_slot(leaf["q"], slot, q),
            "s": set_slot(leaf["s"], slot, s)}
