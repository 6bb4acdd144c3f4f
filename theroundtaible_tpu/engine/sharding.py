"""Mesh construction and parameter/cache partition specs.

The scale-out design from SURVEY.md §2.3 / §5.8: shardings are expressed with
`jax.sharding.Mesh` + `NamedSharding(PartitionSpec)`, XLA inserts the
collectives (all-reduce for TP activations over ICI), nothing is hand-NCCL'd.

Axes:
- "data"  — batch/slot parallelism (DP): each replica serves different slots
- "model" — tensor parallelism (TP): attention heads and MLP hidden sharded
- ("seq" is introduced by the ring-attention path in longcontext.py)

The same spec tree works on 1 device (everything replicated), a v5e-8, or a
virtual 8-CPU mesh (tests / dryrun_multichip).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .models.common import ModelConfig, Params, init_params

DATA_AXIS = "data"
MODEL_AXIS = "model"


def build_mesh(mesh_shape: Optional[dict[str, int]] = None,
               devices: Optional[list] = None,
               dcn_axis: Optional[str] = None) -> Mesh:
    """Build a (data, model) mesh. mesh_shape like {"data": 1, "model": 8};
    -1 means "all remaining devices". Default: all devices on the model
    axis (TP-first serving — weights are the big thing to split).

    dcn_axis (multi-slice/multi-host): which mesh axis spans the DCN
    granules — slices when the backend reports them, else processes. The
    device array then comes from mesh_utils.create_hybrid_device_mesh,
    so the OTHER axis stays inside a granule on ICI. Put "data" across
    DCN (DP exchanges nothing per token) and keep "model" inside a slice
    (TP all-reduces every layer) — the module-docstring guidance, now a
    config surface. Ignored (with identical single-granule behavior)
    when there is only one granule, so the same config dryruns
    single-process."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    shape = dict(mesh_shape or {})
    unknown = sorted(set(shape) - {DATA_AXIS, MODEL_AXIS})
    if unknown:
        raise ValueError(
            f"mesh axis {', '.join(map(repr, unknown))} is not one this "
            f"engine has (a mesh is {DATA_AXIS!r} x {MODEL_AXIS!r}): shard "
            f'a model over N chips with mesh {{"{MODEL_AXIS}": N}}')
    data = shape.get(DATA_AXIS, 1)
    model = shape.get(MODEL_AXIS, -1)
    if model == -1:
        model = n // max(data, 1)
    if data == -1:
        data = n // max(model, 1)
    if data * model > n:
        raise ValueError(
            f"mesh {data}x{model} needs {data * model} devices, have {n}")
    if dcn_axis:
        if dcn_axis not in (DATA_AXIS, MODEL_AXIS):
            raise ValueError(
                f"dcn_axis must be {DATA_AXIS!r} or {MODEL_AXIS!r}, "
                f"got {dcn_axis!r}")
        dev_array = _hybrid_device_array(devices[:data * model],
                                         data, model, dcn_axis)
        if dev_array is not None:
            return Mesh(dev_array, (DATA_AXIS, MODEL_AXIS))
    # A strict subset is allowed — heterogeneous serving partitions the pod
    # into per-model submeshes (SURVEY.md §2.3 "heterogeneous multi-model
    # scheduler"); callers pass disjoint device lists.
    dev_array = np.array(devices[:data * model]).reshape(data, model)
    return Mesh(dev_array, (DATA_AXIS, MODEL_AXIS))


def _hybrid_device_array(devices: list, data: int, model: int,
                         dcn_axis: str):
    """Device array for a DCN-aware mesh, or None when a single granule
    makes the plain contiguous reshape equivalent.

    Granule = slice where devices report distinct slice_index values
    (real multi-slice TPU), else process (multi-host CPU/TPU pods where
    every host is its own DCN island)."""
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    if None not in slice_ids and len(slice_ids) > 1:
        n_granules, process_is_granule = len(slice_ids), False
    else:
        n_granules = len({d.process_index for d in devices})
        process_is_granule = True
    if n_granules <= 1:
        return None
    sizes = {DATA_AXIS: data, MODEL_AXIS: model}
    if sizes[dcn_axis] % n_granules:
        raise ValueError(
            f"dcn_axis={dcn_axis!r} size {sizes[dcn_axis]} must divide "
            f"into the {n_granules} DCN granules (slices/processes)")
    per = dict(sizes)
    per[dcn_axis] //= n_granules
    dcn = {a: (n_granules if a == dcn_axis else 1)
           for a in (DATA_AXIS, MODEL_AXIS)}
    from jax.experimental import mesh_utils
    return mesh_utils.create_hybrid_device_mesh(
        (per[DATA_AXIS], per[MODEL_AXIS]),
        (dcn[DATA_AXIS], dcn[MODEL_AXIS]),
        devices=devices, process_is_granule=process_is_granule)


def param_specs(cfg: ModelConfig) -> Params:
    """PartitionSpec tree matching init_params' structure.

    TP sharding: q/o on query heads, k/v on kv heads, MLP on hidden.
    Embedding sharded on vocab (big tables, cheap all-gather of one row).
    A model with `layer_kinds` serves on one device (the engine
    declines a wider mesh for it): every leaf is replicated.
    """
    if cfg.layer_kinds is not None:
        import jax
        from .models.common import init_params
        shapes = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0)))
        return jax.tree_util.tree_map(lambda _: P(), shapes)
    layer = {
        "q_proj": P(None, MODEL_AXIS, None),    # [E, H, D] heads sharded
        "k_proj": P(None, MODEL_AXIS, None),    # [E, K, D]
        "v_proj": P(None, MODEL_AXIS, None),
        "o_proj": P(MODEL_AXIS, None, None),    # [H, D, E] contract sharded
        "input_norm": P(None),
        "pre_mlp_norm": P(None),
    }
    if cfg.attn_bias:
        layer["q_bias"] = P(MODEL_AXIS, None)   # [H, D] heads sharded
        layer["k_bias"] = P(MODEL_AXIS, None)   # [K, D]
        layer["v_bias"] = P(MODEL_AXIS, None)
    if cfg.num_experts:
        # EP: experts ride the model axis — each device computes its local
        # experts for all tokens; the combine contraction over the sharded
        # expert axis becomes one all-reduce (models/common.py moe_mlp)
        layer["router"] = P(None, None)
        layer["experts"] = {
            "gate_proj": P(MODEL_AXIS, None, None),   # [X, E, F]
            "up_proj": P(MODEL_AXIS, None, None),
            "down_proj": P(MODEL_AXIS, None, None),   # [X, F, E]
        }
    else:
        layer.update({
            "gate_proj": P(None, MODEL_AXIS),   # [E, F]
            "up_proj": P(None, MODEL_AXIS),
            "down_proj": P(MODEL_AXIS, None),   # [F, E]
        })
    if cfg.post_attn_norm:
        layer["post_attn_norm"] = P(None)
    if cfg.post_mlp_norm:
        layer["post_mlp_norm"] = P(None)
    specs: Params = {
        "embedding": P(MODEL_AXIS, None),       # [V, E] vocab sharded
        "layers": [dict(layer) for _ in range(cfg.num_layers)],
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(MODEL_AXIS, None)
    return specs


def model_axis_size(mesh: Mesh) -> int:
    """Model-axis (TP) shard count of a mesh — 1 when the axis is
    absent. The `model_shards` quantize_params needs to emit the
    shard-aligned int4 pack layout, and the shard count the int4 spmd
    kernel dispatch partitions against."""
    return dict(mesh.shape).get(MODEL_AXIS, 1)


def int4_shard_axis(tp: Optional[str], w_ndim: int, n_cont: int,
                    mode: str) -> tuple[Optional[int], bool]:
    """Which weight axis carries the model shards for a packed-int4
    kernel matmul — the partition-spec rule for packed leaves, kept HERE
    so it mirrors param_specs above and the two cannot drift. Returns
    (weight_axis | None, needs_psum).

    tp="col" — megatron column-parallel (q/k/v, gate/up, the lm head):
    param_specs puts MODEL on the first KEPT axis (heads / mlp hidden /
    vocab), each shard computes its own output slice, no collective.
    tp="row" — row-parallel (o_proj, down_proj): MODEL rides the first
    CONTRACTED axis, partial sums combine with one psum over the model
    axis — exactly the all-reduce the XLA path's sharded einsum inserts.
    `mode` is the kernel's pack classification ("out": weight dims are
    contracted-prefix + kept with the pack axis kept-minor; "contract":
    kept + one contracted pack axis — the tied lm head, where "row"
    would shard the packed contracted axis, a layout no weight uses →
    replicate). None/unknown tp replicates: the kernel still fuses, the
    partitioning is just not attempted."""
    if tp == "col":
        return (n_cont if mode == "out" else 0), False
    if tp == "row" and mode == "out":
        return 0, True
    return None, False


def lora_shard_axis(tp: Optional[str]) -> Optional[str]:
    """Which STACKED-LoRA axis carries the model shards for a target
    projection — kept HERE next to param_specs/int4_shard_axis so the
    base weight's placement and the LoRA stack's partitioning can
    never drift (ISSUE 10). tp="col" (q/k/v, gate/up): the delta's
    OUTPUT axis is the model-sharded one, so B's last axis shards and
    each device computes its own delta slice with no collective.
    tp="row" (o_proj, down_proj): the CONTRACTION axis is sharded, so
    A's last axis shards and per-shard partial deltas combine with one
    psum over "model" — the same all-reduce the base matmul inserts.
    Returns "out" | "in" | None (replicate)."""
    if tp == "col":
        return "out"
    if tp == "row":
        return "in"
    return None


def lora_stack_specs(tp: Optional[str]) -> tuple[P, P]:
    """(a_spec, b_spec) for the stacked LoRA tensors a_t [S, r, C] /
    b [S, r, O] of a target with TP convention `tp` — the resident
    placement lora_bgmv_spmd's in_specs must match (a mismatch would
    regather the stack per dispatch)."""
    which = lora_shard_axis(tp)
    a_spec = P(None, None, MODEL_AXIS if which == "in" else None)
    b_spec = P(None, None, MODEL_AXIS if which == "out" else None)
    return a_spec, b_spec


def shardable(cfg: ModelConfig, mesh: Mesh) -> bool:
    """True when every TP/EP dimension divides by the model-axis size."""
    m = mesh.shape[MODEL_AXIS]
    mlp_ok = (cfg.num_experts % m == 0 if cfg.num_experts
              else cfg.mlp_dim % m == 0)
    return (cfg.num_heads % m == 0 and cfg.num_kv_heads % m == 0
            and mlp_ok and cfg.vocab_size % m == 0)


def _fallback_replicated(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Replace axis names whose size doesn't divide the dim with None."""
    fixed = []
    for dim, axis in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axis is None:
            fixed.append(None)
        elif dim % mesh.shape[axis] == 0:
            fixed.append(axis)
        else:
            fixed.append(None)
    return P(*fixed)


def param_shardings(cfg: ModelConfig, mesh: Mesh, shapes: Params) -> Params:
    """NamedSharding tree for a param tree of these shapes (arrays or
    ShapeDtypeStructs): param_specs, with any dimension that doesn't
    divide its mesh axis falling back to replication (e.g. 1 kv head on
    an 8-way model axis)."""
    # tree_map flattens the spec tree up to `shapes`' treedef, so each
    # PartitionSpec (a tuple subclass) arrives whole at its matching leaf.
    return jax.tree_util.tree_map(
        lambda x, spec: NamedSharding(
            mesh, _fallback_replicated(spec, x.shape, mesh)),
        shapes, param_specs(cfg))


def shard_params(params: Params, cfg: ModelConfig, mesh: Mesh) -> Params:
    """device_put the param tree with its sharding tree. A leaf that
    already sits where it belongs (init_sharded_params' output) is
    returned as is — no copy, no transfer."""
    def place(x, sharding):
        have = getattr(x, "sharding", None)
        if have is not None and have.is_equivalent_to(sharding, x.ndim):
            return x
        return jax.device_put(x, sharding)

    return jax.tree_util.tree_map(
        place, params, param_shardings(cfg, mesh, params))


def init_sharded_params(cfg: ModelConfig, key: jax.Array, dtype,
                        mesh: Mesh) -> Params:
    """Random init born sharded: init_params under jit with the tree's
    out_shardings, so every device generates its own shard (threefry is
    partitionable) and none ever holds a whole leaf — a model that only
    fits split across the mesh can be built on it."""
    init = functools.partial(init_params, cfg, dtype=dtype)
    shardings = param_shardings(cfg, mesh, jax.eval_shape(init, key))
    return jax.jit(init, out_shardings=shardings)(key)


def logical_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)
