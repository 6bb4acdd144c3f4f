"""Pipeline parallelism — GPipe-style microbatched prefill over a "pipe"
mesh axis.

SURVEY.md §2.3: "PP — only needed for models too large for one TP group;
design the mesh abstraction to allow a (pipeline, tensor, data) axis split
even if v0 uses PP=1." This module is that design, shipped working and
tested on the virtual CPU mesh: layers are split into contiguous stages
(one per pipe-axis device, stage parameters stacked and sharded on a
leading stage axis), microbatches flow through the classic
(n_stages + n_micro - 1)-step schedule, and activations move stage→stage
with lax.ppermute over ICI — XLA overlaps the permute with the next
step's compute.

v0 scope: full-sequence prefill compute (logits), the piece PP exists for
(weights too big for one TP group). Decode keeps TP/EP: per-token PP
bubbles dominate at batch sizes this orchestrator produces, so the engine
does not enable PP for its slot-persistent serving loop yet. The module
is the documented seam to widen (stage-local KV caches are the follow-up:
each stage would keep its layer range's slots exactly as kvcache.py does
globally).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .compat import pcast, shard_map

from .models.common import (
    ModelConfig, Params, init_params, make_attention_mask, rms_norm,
    transformer_block)

PIPE_AXIS = "pipe"


def build_pipe_mesh(n_stages: int, devices: Optional[list] = None,
                    n_model: int = 1) -> Mesh:
    """(pipe,) mesh, or a (pipe, model) mesh when n_model > 1 — each
    stage's weights then shard over a TP group of n_model devices (the
    SURVEY §2.3 "(pipeline, tensor, data)" axis split; PP programs stay
    manual over "pipe" and leave "model" to the compiler, so the same
    stage code serves both shapes)."""
    import numpy as np
    devices = devices if devices is not None else jax.devices()
    need = n_stages * n_model
    if len(devices) < need:
        raise ValueError(f"need {need} devices "
                         f"(pipe {n_stages} x model {n_model}), "
                         f"have {len(devices)}")
    if n_model == 1:
        return Mesh(np.array(devices[:n_stages]), (PIPE_AXIS,))
    from .sharding import MODEL_AXIS
    return Mesh(np.array(devices[:need]).reshape(n_stages, n_model),
                (PIPE_AXIS, MODEL_AXIS))


def stack_stage_params(params: Params, cfg: ModelConfig, n_stages: int,
                       mesh: Mesh) -> tuple[Params, Params]:
    """Split the per-layer param list into n_stages contiguous stages.

    Returns (shared, staged): `shared` = embedding/final_norm/lm_head
    (replicated over the pipe axis; sharded over the model axis per
    sharding.param_specs when the mesh has one); `staged` = each layer
    tensor stacked to [n_stages, layers_per_stage, ...], sharded on the
    leading stage axis so each pipe device holds exactly its own layers
    — and, on a (pipe, model) mesh, TP-sharded inside the stage on the
    same dims the main engine shards (param_specs shifted by the two
    stacking dims). Quantized {"q","s"} leaves place via
    quant.quantized_specs. Any dim that doesn't divide its mesh axis
    falls back to replication (sharding._fallback_replicated).
    """
    from .quant import quantized, quantized_specs
    from .sharding import param_specs
    specs = param_specs(cfg)
    if any(quantized(l) for l in
           jax.tree_util.tree_leaves(params, is_leaf=quantized)):
        specs = quantized_specs(specs, params)
    shared, stacked = _stack_stages(params, cfg, n_stages)
    return jax.device_put(
        (shared, stacked), _stage_shardings(shared, stacked, specs, mesh))


def _stack_stages(params: Params, cfg: ModelConfig,
                  n_stages: int) -> tuple[Params, Params]:
    """(shared, stacked) with no placement: the non-layer leaves, and
    each layer tensor stacked to [n_stages, layers_per_stage, ...]."""
    if cfg.num_layers % n_stages != 0:
        raise ValueError(
            f"{cfg.num_layers} layers do not split into {n_stages} stages")
    per = cfg.num_layers // n_stages
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves).reshape(
            (n_stages, per) + leaves[0].shape),
        *params["layers"])
    shared = {k: v for k, v in params.items() if k != "layers"}
    return shared, stacked


def _stage_shardings(shared: Params, stacked: Params, specs: Params,
                     mesh: Mesh) -> tuple[Params, Params]:
    """NamedSharding trees for _stack_stages' output (arrays or
    ShapeDtypeStructs) under the param_specs tree `specs`."""
    from .sharding import _fallback_replicated
    has_model = len(mesh.axis_names) > 1

    def stage_place(x, spec):
        tp = tuple(spec) if has_model else ()
        full = P(PIPE_AXIS, None, *tp)
        return NamedSharding(mesh,
                             _fallback_replicated(full, x.shape, mesh))

    def shared_place(x, spec):
        full = spec if has_model else P()
        return NamedSharding(mesh,
                             _fallback_replicated(full, x.shape, mesh))

    shared_specs = {k: specs.get(k, jax.tree_util.tree_map(
        lambda _: P(), v)) for k, v in shared.items()}
    return (jax.tree_util.tree_map(shared_place, shared, shared_specs),
            jax.tree_util.tree_map(stage_place, stacked,
                                   specs["layers"][0]))


def init_stage_params(cfg: ModelConfig, key: jax.Array, dtype,
                      n_stages: int, mesh: Mesh) -> tuple[Params, Params]:
    """Random init born staged: init + stacking under one jit with the
    (shared, staged) out_shardings, so each pipe device generates only
    its own stage's layers (the twin of sharding.init_sharded_params)."""
    from .sharding import param_specs

    def build(k):
        return _stack_stages(init_params(cfg, k, dtype), cfg, n_stages)

    shardings = _stage_shardings(*jax.eval_shape(build, key),
                                 param_specs(cfg), mesh)
    return jax.jit(build, out_shardings=shardings)(key)


def make_pp_prefill(cfg: ModelConfig, mesh: Mesh, n_micro: int):
    """Build jit'd fn(shared, staged, tokens [B,T]) → logits [B,T,V].

    B must divide into n_micro microbatches. Schedule: at step i, stage s
    works on microbatch i-s (when 0 ≤ i-s < n_micro); stage 0 injects
    embeddings, the last stage banks its outputs, ppermute advances the
    ring. The rotating-buffer trick keeps shapes static: every stage
    computes every step (idle steps process garbage that is never banked).
    """
    n_stages = mesh.shape[PIPE_AXIS]
    if cfg.num_layers % n_stages != 0:
        raise ValueError(
            f"{cfg.num_layers} layers do not split into {n_stages} stages")

    def stage_compute(stage_layers, x, positions, valid):
        """Run this stage's `per` layers (scan over stacked params)."""
        mask = make_attention_mask(positions, x.shape[1], valid,
                                   cfg.sliding_window)

        def body(h, layer):
            h, _cache = transformer_block(h, layer, cfg, positions, None,
                                          None, mask, kv_valid=valid)
            return h, None

        x, _ = jax.lax.scan(body, x, stage_layers)
        return x

    def pp_fn(shared, staged, tokens, positions, valid):
        # [B,T] → [n_micro, mb, T]
        b, t = tokens.shape
        mb = b // n_micro
        tok_mb = tokens.reshape(n_micro, mb, t)
        pos_mb = positions.reshape(n_micro, mb, t)
        valid_mb = valid.reshape(n_micro, mb)

        # follows the param dtype (bf16 serving, f32 parity tests) — same
        # rule as models/common.py forward
        emb = shared["embedding"][tok_mb]
        if cfg.scale_embeddings:
            emb = emb * jnp.sqrt(
                jnp.float32(cfg.embed_dim)).astype(emb.dtype)

        def per_stage(stage_layers, emb, pos_mb, valid_mb):
            # under shard_map: stage_layers [1, per, ...] — this stage only
            stage_layers = jax.tree_util.tree_map(
                lambda x: x[0], stage_layers)
            stage = jax.lax.axis_index(PIPE_AXIS)
            n_steps = n_stages + n_micro - 1

            # initial carries must be typed as varying over the pipe axis
            # (each stage's loop state diverges immediately)
            state = pcast(jnp.zeros_like(emb[0]), (PIPE_AXIS,),
                          to="varying")
            banked = pcast(jnp.zeros_like(emb), (PIPE_AXIS,),
                           to="varying")

            def step(i, carry):
                state, banked = carry
                # stage 0 injects microbatch i (clamped; only banked when
                # in schedule), others take the permuted activation
                inject = emb[jnp.clip(i, 0, n_micro - 1)]
                x_in = jnp.where(stage == 0,
                                 jnp.where(i < n_micro, inject, state),
                                 state)
                my_mb = jnp.clip(i - stage, 0, n_micro - 1)
                pos = pos_mb[my_mb]
                vld = valid_mb[my_mb]
                out = stage_compute(stage_layers, x_in, pos, vld)
                # last stage banks microbatch j = i - (n_stages-1)
                j = i - (n_stages - 1)
                bank_now = (stage == n_stages - 1) & (j >= 0)
                banked = jnp.where(
                    bank_now,
                    banked.at[jnp.clip(j, 0, n_micro - 1)].set(out),
                    banked)
                state = jax.lax.ppermute(
                    out, PIPE_AXIS,
                    [(s, (s + 1) % n_stages) for s in range(n_stages)])
                return state, banked

            _state, banked = jax.lax.fori_loop(
                0, n_steps, step, (state, banked))
            # replicate the last stage's banked outputs to every stage
            banked = jax.lax.psum(
                jnp.where(stage == n_stages - 1, banked, 0.0)
                .astype(jnp.float32),
                PIPE_AXIS).astype(banked.dtype)
            return banked

        hidden = shard_map(
            per_stage, mesh=mesh,
            in_specs=(P(PIPE_AXIS), P(), P(), P()),
            out_specs=P(),
        )(staged, emb, pos_mb, valid_mb)

        hidden = hidden.reshape(b, t, cfg.embed_dim)
        hidden = rms_norm(hidden, shared["final_norm"], cfg.norm_eps,
                          cfg.rmsnorm_unit_offset)
        head = (shared["embedding"] if cfg.tie_embeddings
                else shared["lm_head"])
        logits = jnp.einsum("bte,ve->btv", hidden, head,
                            preferred_element_type=jnp.float32)
        if cfg.final_logit_softcap is not None:
            logits = cfg.final_logit_softcap * jnp.tanh(
                logits / cfg.final_logit_softcap)
        return logits

    jitted = jax.jit(pp_fn)

    def call(shared, staged, tokens, positions, valid):
        if tokens.shape[0] % n_micro != 0:
            raise ValueError(
                f"batch {tokens.shape[0]} does not split into "
                f"{n_micro} microbatches")
        return jitted(shared, staged, tokens, positions, valid)

    return call
