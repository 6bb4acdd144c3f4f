"""Pipeline-parallel SERVING — stage-local KV caches, prefill + decode.

Completes the PP story pipeline.py opens (VERDICT r1 #7: "wire PP into
serving"): an engine for checkpoints too large for one chip/TP group,
reachable from the tpu-llm adapter config as `mesh: {"pipe": N}`. Layers
split into N contiguous stages (params stacked on a leading stage axis,
sharded over the "pipe" mesh axis — stack_stage_params); each stage owns
the KV cache for ITS layers only (`[n_stages, per, slots, S, K, D]`,
stage-sharded), so no device ever holds the whole model or the whole
cache — the memory-capacity property PP exists for.

- Prefill: GPipe microbatch schedule (pipeline.py's rotating-buffer
  design) extended to thread per-layer stage-local caches through the
  steps; bubble steps compute garbage that is masked out of both the
  banked logits and the cache writes.
- Decode: one ppermute hop per stage per token — stages fire in
  sequence, each applying its layers against its local cache at the
  row's current position. Inactive stages run masked compute (the
  static-shape price of SPMD; PP decode is a memory-capacity play, its
  serial latency is inherent to the layer dependency).
- Slots: SlotBook (kvcache.py) gives PP the same per-knight LCP delta
  prefill as the main engine; per-row sampling params and int8 w8a16
  quant work as in the main engine (quantized {"q","s"} leaves stack
  and stage-shard like any other layer leaf). Cross-knight prefix
  sharing (donor + leader passes) copies spans on the stage-sharded
  caches — the slot axis is unsharded, so each stage copies its own
  layers' span with no cross-stage traffic.
- kv_layout="paged": a stage-stacked page pool [st, per, P, ps, K, D]
  managed by the main engine's PagedKVCache allocator (one page table
  for every layer; page aliasing replaces span copies for prefix
  sharing). Serving is POOL-DIRECT: prefill chunks and decode steps
  scatter into the rows' pages and attend through the page-table-aware
  Pallas kernels, so the position-aligned gather view (which would
  temporarily recreate the full contiguous HBM budget — precisely on
  the models PP exists for) is never built. Under TP-in-stage the
  kernels run through the paged SPMD wrappers as a NESTED shard_map
  over the auto "model" axis; attn="dense" (or a non-partitionable
  head layout) keeps the gather-view fallback.
- Attention inside stages: the Pallas flash kernels — raw single-device
  calls on pipe-only meshes (the stage body is fully manual, so
  per-stage arrays are local and full-size); under TP-in-stage the
  main engine's spmd wrappers run as a nested shard_map that
  manualizes only the still-auto "model" axis (the context mesh has
  "pipe" Manual already). Dense XLA einsums remain the opt-out and the
  non-partitionable-heads fallback.

The reference has no counterpart (its models fit one GPU via Ollama);
SURVEY.md §2.3 "PP" row is the requirement this file closes.
"""

from __future__ import annotations

import threading
import time
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..utils import telemetry
from . import deadlines, faults, trace_hooks
from .compat import pcast, shard_map
from .engine import GenStats
from .kvcache import SlotBook
from .serving_loop import (DECODE_SEGMENT, PREFILL_BUCKETS, bucket_for,
                           chunked_prefill, decode_segments,
                           finalize_outputs, host_sync, prompt_budget)
from .models.common import (ModelConfig, _einsum, _softcap, embed_tokens,
                            gather_rows, init_params, make_attention_mask,
                            param_count, project_qkv, rms_norm,
                            spmd_mesh, transformer_block)
from .pipeline import (PIPE_AXIS, build_pipe_mesh, init_stage_params,
                       stack_stage_params)
from .sampling import (SamplingParams, sample_token_batch, sampling_arrays)
from .tokenizer import load_tokenizer


class PPEngine:
    """Pipeline-parallel serving engine (stage-local weights AND KV)."""

    def __init__(self, model_cfg: ModelConfig, *, checkpoint: str = "",
                 n_stages: int = 2, n_model: int = 1, n_micro: int = 2,
                 num_slots: int = 4,
                 dtype=jnp.bfloat16, quant: str = "none",
                 kv_layout: str = "contiguous", page_size: int = 128,
                 num_pages: Optional[int] = None, attn: str = "auto",
                 sampling: Optional[SamplingParams] = None, seed: int = 0,
                 devices: Optional[list[int]] = None,
                 prefix_cache: Optional[bool] = None,
                 prefix_cache_pages: Optional[int] = None):
        import dataclasses

        if quant not in ("none", "int8", "int4"):
            raise ValueError(
                f"quant must be none|int8|int4, got {quant!r}")
        if kv_layout not in ("contiguous", "paged"):
            raise ValueError(
                f"kv_layout must be contiguous|paged, got {kv_layout!r}")
        if attn not in ("auto", "flash", "dense"):
            raise ValueError(f"attn must be auto|flash|dense, got {attn!r}")

        from . import compile_watch, enable_compilation_cache
        from .distributed import maybe_init_distributed
        maybe_init_distributed()
        enable_compilation_cache()
        compile_watch.install()
        # Attention inside the stages (VERDICT r3 missing #4 — the PP
        # engine used to force dense): on a pipe-only mesh the stage body
        # is fully manual, every array is stage-local and full-size, so
        # the RAW single-device Pallas kernels apply directly
        # (the stage context announces LOCAL_MESH — size 1 — so
        # models/common.attention takes its single-device kernel branch
        # with per-shape supported() fallback, and the int4 kernels
        # dispatch single-device too). On a (pipe, model) mesh the kernels run
        # through the same spmd wrappers the main engine uses, as a
        # NESTED shard_map: the stage body is manual over "pipe" only, so
        # the wrapper manualizes the remaining auto "model" axis
        # (pallas/attention._manual_axes) — heads must divide the model
        # axis exactly as on the main engine (explicit flash on a
        # non-divisible layout raises; auto falls back to dense).
        from .pallas.attention import spmd_partitionable
        heads_divide = spmd_partitionable(
            model_cfg.num_heads, model_cfg.num_kv_heads, n_model)
        if attn == "flash" and n_model > 1 and not heads_divide:
            raise ValueError(
                f"attn='flash' on a {n_model}-way model axis needs head "
                f"counts divisible by it (got H={model_cfg.num_heads}, "
                f"K={model_cfg.num_kv_heads}) — use attn='auto' or "
                "'dense'")
        if attn == "auto":
            # Mirror the main engine's auto rule: kernels on TPU with
            # lane-aligned head_dim (and a partitionable head layout
            # when TP runs inside the stages), dense elsewhere.
            resolved = ("flash" if jax.default_backend() == "tpu"
                        and model_cfg.head_dim % 128 == 0
                        and (n_model == 1 or heads_divide) else "dense")
        else:
            resolved = attn
        model_cfg = dataclasses.replace(model_cfg, attn_impl=resolved)
        self.cfg = model_cfg
        self.max_seq_len = model_cfg.max_seq_len
        self.sampling = sampling or SamplingParams()
        self.tokenizer = load_tokenizer(checkpoint or None)
        self.n_stages = n_stages
        self.n_model = n_model
        self.n_micro = n_micro
        device_list = None
        if devices:
            all_devices = jax.devices()
            device_list = [all_devices[i] for i in devices]
        # n_model > 1: a (pipe, model) mesh — each stage's weights/KV
        # shard over a TP group. The PP programs are shard_map-manual
        # over "pipe" only (axis_names below); "model" stays an auto
        # axis, so XLA inserts the same TP collectives inside each stage
        # that the main engine's jit path gets from param PartitionSpecs
        # (SURVEY §2.3's (pipeline, tensor, data) requirement).
        self.mesh = build_pipe_mesh(n_stages, device_list, n_model)

        self.quant = quant
        if not checkpoint and quant == "none":
            # Born staged: no device ever holds more than its stage.
            self.shared, self.staged = init_stage_params(
                model_cfg, jax.random.PRNGKey(seed), dtype, n_stages,
                self.mesh)
            self.num_params = (param_count(self.shared)
                               + param_count(self.staged))
        else:
            if checkpoint:
                from .checkpoint import load_hf_checkpoint
                params = load_hf_checkpoint(checkpoint, model_cfg, dtype)
            else:
                # Quantization wants whole per-layer leaves BEFORE
                # stacking, so this tree still lands on the default
                # device (ROADMAP D2) — under jit, so its values are
                # the born-sharded paths' bit for bit.
                params = jax.jit(partial(init_params, model_cfg,
                                         dtype=dtype))(
                    jax.random.PRNGKey(seed))
            self.num_params = param_count(params)
            if quant in ("int8", "int4"):
                # PP is the engine for checkpoints too big for one chip
                # — exactly where shrinking streamed weight bytes
                # matters most. Quantize BEFORE stacking: the {"q","s"}
                # dict / Int4Leaf leaves stack and shard like any other
                # layer leaf, and the stage programs reach them only
                # through _einsum/embed_tokens (which dequantize
                # fusably, see engine/quant.py). model_shards: int4
                # grouping aligns to the in-stage TP shard boundary so
                # the shard-aware kernel dispatch partitions scales
                # with whole groups per shard.
                from .quant import quantize_params
                params = quantize_params(params, model_cfg,
                                         act_dtype=dtype,
                                         free_source=True,
                                         bits=8 if quant == "int8" else 4,
                                         model_shards=n_model)
            self.shared, self.staged = stack_stage_params(
                params, model_cfg, n_stages, self.mesh)

        per = model_cfg.num_layers // n_stages
        # Caches [st, per, slots|pages, S|ps, K, D]: stage axis over
        # "pipe"; on a (pipe, model) mesh the KV-head dim additionally
        # shards over "model" (falling back to replicated when K doesn't
        # divide, e.g. MQA) — same layout rule as kv_cache_spec.
        from .sharding import MODEL_AXIS, _fallback_replicated
        kv_spec = P(PIPE_AXIS, None, None, None,
                    MODEL_AXIS if n_model > 1 else None, None)

        def cache_sharding_for(shape):
            return NamedSharding(
                self.mesh, _fallback_replicated(kv_spec, shape, self.mesh))

        self.kv_layout = kv_layout
        kd = (model_cfg.num_kv_heads, model_cfg.head_dim)
        # Pool-direct paged serving (VERDICT r3 missing #4): prefill
        # chunks and decode steps scatter into the rows' pages and attend
        # through the page-table-aware kernels — the [B, S, K, D] gather
        # view (which temporarily recreates the full contiguous HBM
        # budget, precisely on the models PP exists for) is never built.
        # Same gating as the main engine: attn="dense" is an explicit
        # opt-out of every Pallas kernel ("auto" still takes pool-direct
        # on CPU, where the kernel runs in interpret mode). TP-in-stage
        # meshes take the paged SPMD wrappers as a nested shard_map over
        # the auto "model" axis (head layout must partition; otherwise
        # the gather view remains).
        self._pool_direct = False
        if kv_layout == "paged":
            from .pallas.attention import paged_pool_direct_supported
            from .serving_loop import MAX_PREFILL_CHUNK
            kh_l = model_cfg.num_kv_heads
            if n_model > 1 and kh_l % n_model == 0:
                kh_l //= n_model   # kernel sees the local shard
            group = model_cfg.num_heads // model_cfg.num_kv_heads
            self._pool_direct = (
                attn != "dense"
                and paged_pool_direct_supported(
                    MAX_PREFILL_CHUNK, page_size, model_cfg.head_dim,
                    kh_l, group)
                and (n_model == 1 or heads_divide))
        if kv_layout == "paged":
            # Stage-stacked page pool [st, per, P, ps, K, D]: ONE
            # allocator manages the page axis (a slot's page mapping is
            # identical for every layer, exactly like the main engine's
            # per-layer pools sharing one table), while the leading stage
            # axis shards so each pipe device holds only its own layers'
            # pages. Serving gathers pool[table] into the same
            # [st, per, B, S, K, D] view the contiguous programs use —
            # the stage programs are layout-agnostic.
            from .paging import PagedKVCache

            def pool_factory(n_pages):
                shape = (n_stages, per, n_pages, page_size) + kd
                sh = cache_sharding_for(shape)
                return [(jax.device_put(jnp.zeros(shape, dtype), sh),
                         jax.device_put(jnp.zeros(shape, dtype), sh))]

            @partial(jax.jit, donate_argnums=(0,))
            def copy_pages(pools, src_ids, dst_ids):
                k6, v6 = pools[0]
                return [(k6.at[:, :, dst_ids].set(k6[:, :, src_ids]),
                         v6.at[:, :, dst_ids].set(v6[:, :, src_ids]))]

            from .paging import make_padded_copier
            self.kv = PagedKVCache(
                model_cfg, num_slots, self.max_seq_len, dtype,
                page_size=page_size, num_pages=num_pages,
                copy_pages_fn=make_padded_copier(copy_pages),
                pool_factory=pool_factory)
            self.kc = self.vc = None
            n_pages_seq = self.max_seq_len // page_size

            @jax.jit
            def gather_view(pools, tables):
                k6, v6 = pools[0]
                b = tables.shape[0]
                kc = k6[:, :, tables].reshape(
                    n_stages, per, b, self.max_seq_len, *kd)
                vc = v6[:, :, tables].reshape(
                    n_stages, per, b, self.max_seq_len, *kd)
                return kc, vc

            @partial(jax.jit, donate_argnums=(0, 2, 3))
            def scatter_view(pools, tables, kc, vc):
                # Duplicate table entries (pages aliased across rows)
                # only ever carry identical bytes: aliased pages sit
                # below every row's COW'd write range, so the rows' view
                # contents agree there (engine.py scatter_view contract).
                k6, v6 = pools[0]
                b = tables.shape[0]
                k7 = kc.reshape(n_stages, per, b, n_pages_seq,
                                page_size, *kd)
                v7 = vc.reshape(n_stages, per, b, n_pages_seq,
                                page_size, *kd)
                return [(k6.at[:, :, tables].set(k7),
                         v6.at[:, :, tables].set(v7))]

            self._gather_view = gather_view
            self._scatter_view = scatter_view
            # Cross-session prefix cache (ISSUE 7): the stage-stacked
            # pool is still one PagedKVCache page space, so the
            # content-addressed index works unchanged — commit inserts,
            # the prepare path attaches, _alloc_page reclaims. The host
            # offload tier stays main-engine-only (its idle policy lives
            # in the session scheduler, which serves InferenceEngine).
            from .prefix_cache import PrefixCache, cache_enabled
            self.prefix_cache = None
            if cache_enabled(prefix_cache):
                self.prefix_cache = PrefixCache(
                    self.kv, engine=model_cfg.name,
                    max_pages=prefix_cache_pages)
                self.kv.prefix_cache = self.prefix_cache
        else:
            cache_shape = (n_stages, per, num_slots,
                           self.max_seq_len) + kd
            sh = cache_sharding_for(cache_shape)
            # Kept for revive_kv_if_dead: reallocation after a failed
            # donated dispatch deleted the stage-stacked caches.
            self._make_contig = lambda: jax.device_put(
                jnp.zeros(cache_shape, dtype), sh)
            self.kc = self._make_contig()
            self.vc = self._make_contig()
            self.kv = SlotBook(num_slots)
            self.prefix_cache = None

        self._key = jax.random.PRNGKey(seed + 1)
        self._chars_per_token: Optional[float] = None
        self.last_stats = GenStats()
        self._serve_lock = threading.Lock()
        # int4 path-provenance sink (models/common._record_int4) —
        # every stage/head mesh context below carries it.
        self._int4_dispatches: dict = {}
        # Shared dispatch retry policy (engine/faults.py), same seam as
        # the main engine: transient dispatch failures retry in place.
        self.retry = faults.DEFAULT_RETRY
        # Per-engine roofline model (ISSUE 6): streamed bytes from the
        # stage-stacked (possibly quantized) tree + chip ceilings —
        # same construction seam as the main engine.
        from ..utils import perfmodel
        self.perf = perfmodel.EnginePerf.from_engine(
            self, params=(self.shared, self.staged),
            kv_itemsize=jnp.dtype(dtype).itemsize)

        cfg = model_cfg
        mesh = self.mesh
        s_len = self.max_seq_len
        # Stage bodies trace under the CONTEXT AbstractMesh whenever a
        # "model" axis exists (pipe already Manual there): the flash spmd
        # wrappers need it to run as a nested shard_map over the auto
        # "model" axis, and the int4 kernel dispatch re-partitions its
        # matmuls over the same axis (einsum_int4_spmd). On pipe-ONLY
        # meshes the stage body is FULLY manual — every array is
        # device-local and full-size — so the context announces the
        # LOCAL_MESH sentinel: the int4 kernels then dispatch
        # single-device (lifting the old "unset context → XLA dequant"
        # fallback inside PP stages, ISSUE 3) while "no announcement"
        # elsewhere still safely means the XLA path.
        mesh_in_stage = n_model > 1

        def _stage_mesh_ctx():
            from .models.common import LOCAL_MESH, spmd_mesh
            if not mesh_in_stage:
                return spmd_mesh(LOCAL_MESH,
                                 int4_sink=self._int4_dispatches)
            # The trace-context AbstractMesh carries the Manual "pipe"
            # axis the nested spmd wrappers subtract via axis_types.
            return spmd_mesh(jax.sharding.get_abstract_mesh(),
                             int4_sink=self._int4_dispatches)

        def stage_scan(stage_layers, kc_l, vc_l, h, positions, valid,
                       offsets, slot_idx, write_ok):
            """This stage's layers over h, threading per-layer caches.

            kc_l/vc_l: [per, slots, S, K, D]. write_ok masks cache writes
            (False during schedule bubbles / inactive decode hops)."""
            mask = make_attention_mask(positions, s_len, valid,
                                       cfg.sliding_window)

            def body(h, xs):
                layer, kc1, vc1 = xs
                cache = (kc1[slot_idx], vc1[slot_idx])
                h, (nk, nv) = transformer_block(
                    h, layer, cfg, positions, cache, offsets, mask,
                    kv_valid=valid)
                kc1 = kc1.at[slot_idx].set(
                    jnp.where(write_ok, nk, kc1[slot_idx]))
                vc1 = vc1.at[slot_idx].set(
                    jnp.where(write_ok, nv, vc1[slot_idx]))
                return h, (kc1, vc1)

            with _stage_mesh_ctx():
                h, (kc_l, vc_l) = jax.lax.scan(
                    body, h, (stage_layers, kc_l, vc_l))
            return h, kc_l, vc_l

        def make_pp_programs(scan_step):
            """Build the (prefill, decode) jit programs for one cache
            layout. The GPipe microbatch schedule, per-token ring
            decode, banking/psum epilogue and sampling bookkeeping exist
            ONCE here; layouts differ only in `scan_step` and in what
            `caches`/`extra` mean — contiguous threads the slot-indexed
            (kc, vc) caches with extra = slot_idx [B]; paged threads the
            stage-stacked (k6, v6) page pools with extra = tables
            [B, pages_per_seq]. (One shell, two instantiations: a
            near-verbatim second copy of these programs is exactly the
            drift hazard serving_loop.py was extracted to prevent.)

            scan_step(stage_layers, c1_l, c2_l, h, positions, valid,
            offsets_row, extra_row, write_ok) -> (h, c1_l, c2_l)."""

            @partial(jax.jit, donate_argnums=(2,))
            def pp_prefill(shared, staged, caches, extra, tokens,
                           offsets, lengths):
                c1, c2 = caches
                b, t = tokens.shape
                n_mb = self.n_micro if b % self.n_micro == 0 else 1
                mb = b // n_mb
                tok_mb = tokens.reshape(n_mb, mb, t)
                offs_mb = offsets.reshape(n_mb, mb)
                len_mb = lengths.reshape(n_mb, mb)
                extra_mb = extra.reshape((n_mb, mb) + extra.shape[1:])

                emb = embed_tokens(shared["embedding"], tok_mb)
                if cfg.scale_embeddings:
                    emb = emb * jnp.sqrt(
                        jnp.float32(cfg.embed_dim)).astype(emb.dtype)

                def per_stage(staged, c1, c2, emb, offs_mb, len_mb,
                              extra_mb):
                    stage_layers = jax.tree_util.tree_map(
                        lambda x: x[0], staged)
                    c1_l, c2_l = c1[0], c2[0]
                    stage = jax.lax.axis_index(PIPE_AXIS)
                    n_steps = self.n_stages + n_mb - 1

                    state = pcast(jnp.zeros_like(emb[0]),
                                          (PIPE_AXIS,), to="varying")
                    banked = pcast(jnp.zeros_like(emb),
                                           (PIPE_AXIS,), to="varying")
                    c1_l = pcast(c1_l, (PIPE_AXIS,), to="varying")
                    c2_l = pcast(c2_l, (PIPE_AXIS,), to="varying")

                    def step(i, carry):
                        state, banked, c1_l, c2_l = carry
                        inject = emb[jnp.clip(i, 0, n_mb - 1)]
                        x_in = jnp.where(stage == 0,
                                         jnp.where(i < n_mb, inject,
                                                   state),
                                         state)
                        my = jnp.clip(i - stage, 0, n_mb - 1)
                        in_sched = (i - stage >= 0) & (i - stage < n_mb)
                        positions = (offs_mb[my][:, None]
                                     + jnp.arange(t)[None, :])
                        valid = offs_mb[my] + len_mb[my]
                        out, c1_l, c2_l = scan_step(
                            stage_layers, c1_l, c2_l, x_in, positions,
                            valid, offs_mb[my], extra_mb[my], in_sched)
                        j = i - (self.n_stages - 1)
                        bank_now = (stage == self.n_stages - 1) & (j >= 0)
                        banked = jnp.where(
                            bank_now,
                            banked.at[jnp.clip(j, 0, n_mb - 1)].set(out),
                            banked)
                        state = jax.lax.ppermute(
                            out, PIPE_AXIS,
                            [(s, (s + 1) % self.n_stages)
                             for s in range(self.n_stages)])
                        return state, banked, c1_l, c2_l

                    _s, banked, c1_l, c2_l = jax.lax.fori_loop(
                        0, n_steps, step, (state, banked, c1_l, c2_l))
                    banked = jax.lax.psum(
                        jnp.where(stage == self.n_stages - 1, banked, 0.0)
                        .astype(jnp.float32), PIPE_AXIS) \
                        .astype(banked.dtype)
                    return banked, c1_l[None], c2_l[None]

                hidden, c1, c2 = shard_map(
                    per_stage, mesh=mesh,
                    in_specs=(P(PIPE_AXIS), P(PIPE_AXIS), P(PIPE_AXIS),
                              P(), P(), P(), P()),
                    out_specs=(P(), P(PIPE_AXIS), P(PIPE_AXIS)),
                    # Manual over "pipe" only; any "model" axis stays
                    # auto so XLA inserts the in-stage TP collectives.
                    axis_names={PIPE_AXIS},
                    check_vma=False,
                )(staged, c1, c2, emb, offs_mb, len_mb, extra_mb)

                hidden = hidden.reshape(b, t, cfg.embed_dim)
                hidden = rms_norm(hidden, shared["final_norm"],
                                  cfg.norm_eps, cfg.rmsnorm_unit_offset)
                # Gather each row's last valid hidden state BEFORE the
                # lm head: full-sequence [B,T,V] logits on a 256k vocab
                # are a multi-GB temp (see models/common.forward).
                hidden = gather_rows(hidden, lengths - 1)
                head = (shared["embedding"] if cfg.tie_embeddings
                        else shared["lm_head"])
                # The head matmul runs OUTSIDE the stage shard_map, under
                # plain jit/GSPMD over the (pipe[, model]) mesh — announce
                # that mesh so an int4 head dispatches the shard-aware
                # kernel (post-gather M = B rows, decode-kernel legal)
                # instead of the old silent XLA fallback.
                with spmd_mesh(mesh, int4_sink=self._int4_dispatches):
                    logits = _einsum("bte,ve->btv", hidden, head,
                                     tp="col")
                logits = _softcap(logits, cfg.final_logit_softcap)
                return logits[:, 0], (c1, c2)

            @partial(jax.jit, donate_argnums=(2,),
                     static_argnames=("max_new", "greedy"))
            def pp_decode(shared, staged, caches, extra, first_token,
                          start_valid, key, budget, temps, top_ks,
                          top_ps, row_budgets, done_in, max_new, greedy):
                c1, c2 = caches
                b = first_token.shape[0]
                eos = jnp.int32(self.tokenizer.eos_id)
                head = (shared["embedding"] if cfg.tie_embeddings
                        else shared["lm_head"])

                def per_stage(staged, c1, c2, first_token, start_valid,
                              key, budget, temps, top_ks, top_ps,
                              row_budgets, done_in, extra, embedding,
                              head, final_norm):
                    stage_layers = jax.tree_util.tree_map(
                        lambda x: x[0], staged)
                    c1_l = pcast(c1[0], (PIPE_AXIS,),
                                         to="varying")
                    c2_l = pcast(c2[0], (PIPE_AXIS,),
                                         to="varying")
                    stage = jax.lax.axis_index(PIPE_AXIS)
                    out0 = jnp.zeros((b, max_new), jnp.int32)
                    # done carries ACROSS segments (decode_segments
                    # threads it) — all-done speculative segments exit
                    # at the cond
                    done0 = done_in

                    def cond(state):
                        step, _, _, done, _, _, _, _ = state
                        return ((step < max_new) & (step < budget)
                                & ~jnp.all(done))

                    def tok_body(state):
                        step, last, valid, done, out, c1_l, c2_l, key = \
                            state
                        h = embed_tokens(embedding, last[:, None])
                        if cfg.scale_embeddings:
                            h = h * jnp.sqrt(jnp.float32(
                                cfg.embed_dim)).astype(h.dtype)
                        h = pcast(h, (PIPE_AXIS,), to="varying")
                        positions = valid[:, None]

                        def hop(s, carry):
                            h, c1_l, c2_l = carry
                            active = stage == s
                            h_new, c1_l, c2_l = scan_step(
                                stage_layers, c1_l, c2_l, h, positions,
                                valid + 1, valid, extra, active)
                            h = jnp.where(active, h_new, h)
                            h = jax.lax.ppermute(
                                h, PIPE_AXIS,
                                [(x, (x + 1) % self.n_stages)
                                 for x in range(self.n_stages)])
                            return h, c1_l, c2_l

                        h, c1_l, c2_l = jax.lax.fori_loop(
                            0, self.n_stages, hop, (h, c1_l, c2_l))
                        # after n_stages hops the final hidden wrapped
                        # back to stage 0; broadcast it to every stage
                        # for sampling
                        h = jax.lax.psum(
                            jnp.where(stage == 0, h, 0.0)
                            .astype(jnp.float32), PIPE_AXIS) \
                            .astype(h.dtype)
                        h = rms_norm(h, final_norm, cfg.norm_eps,
                                     cfg.rmsnorm_unit_offset)
                        # Decode lm head INSIDE the stage region (manual
                        # over "pipe"): the stage context routes an int4
                        # head onto the kernel — single-device via
                        # LOCAL_MESH on pipe-only meshes, nested
                        # shard_map over "model" under TP-in-stage.
                        with _stage_mesh_ctx():
                            logits = _einsum("bte,ve->btv", h, head,
                                             tp="col")
                        if cfg.final_logit_softcap is not None:
                            logits = cfg.final_logit_softcap * jnp.tanh(
                                logits / cfg.final_logit_softcap)
                        key, sub = jax.random.split(key)
                        row_logits = logits[:, 0]
                        if greedy:
                            nxt = jnp.argmax(row_logits, axis=-1) \
                                .astype(jnp.int32)
                        else:
                            nxt = sample_token_batch(
                                row_logits, sub, temps, top_ks,
                                top_ps).astype(jnp.int32)
                        nxt = jnp.where(done | (step >= row_budgets),
                                        eos, nxt)
                        out = out.at[:, step].set(nxt)
                        new_done = done | (nxt == eos)
                        valid = jnp.where(done, valid, valid + 1)
                        return (step + 1, nxt, valid, new_done, out,
                                c1_l, c2_l, key)

                    state = (jnp.int32(0), first_token, start_valid,
                             done0, out0, c1_l, c2_l, key)
                    step, last, valid, done, out, c1_l, c2_l, _ = \
                        jax.lax.while_loop(cond, tok_body, state)
                    return (out, step[None], last, valid, done,
                            c1_l[None], c2_l[None])

                out, step, last, valid, done, c1, c2 = shard_map(
                    per_stage, mesh=mesh,
                    in_specs=(P(PIPE_AXIS), P(PIPE_AXIS), P(PIPE_AXIS),
                              P(), P(), P(), P(), P(), P(), P(), P(),
                              P(), P(), P(), P(), P()),
                    out_specs=(P(), P(PIPE_AXIS), P(), P(), P(),
                               P(PIPE_AXIS), P(PIPE_AXIS)),
                    axis_names={PIPE_AXIS},
                    check_vma=False,
                )(staged, c1, c2, first_token, start_valid, key, budget,
                  temps, top_ks, top_ps, row_budgets, done_in, extra,
                  shared["embedding"], head, shared["final_norm"])
                return out, step[0], last, valid, done, (c1, c2)

            return pp_prefill, pp_decode

        self._pp_prefill, self._pp_decode = make_pp_programs(stage_scan)

        if self._pool_direct:
            from .pallas import attention as pattn

            def stage_scan_paged(stage_layers, kp_l, vp_l, h, positions,
                                 valid, _offsets, table, write_ok):
                """This stage's layers over h, POOL-DIRECT: kp_l/vp_l
                [per, P, ps, K, D] — each layer scatters its K/V into the
                rows' pages (masked to a same-bytes rewrite during
                schedule bubbles / inactive decode hops) and attends
                through the page-table-aware kernels, so the
                position-aligned gather view is never built. `valid`
                counts entries INCLUDING this call (kernel contract);
                write exclusivity per engine/paged_forward.py: COW +
                slot-owned frontier pages. `_offsets` (the contiguous
                layout's cache write offset) is unused: pages encode
                the position. Chunk shapes are always kernel-legal in
                serving: prompt_budget reserves ≥ DECODE_SEGMENT+1
                positions of cache tail, so chunked_prefill's bucket is
                always a power of two ≥ 8 (same contract as
                engine.paged_direct / forward_paged)."""
                b_ = h.shape[0]
                ps = kp_l.shape[2]
                pages = table[jnp.arange(b_)[:, None], positions // ps]
                offs_in = positions % ps

                def body(h, xs):
                    layer, kp1, vp1 = xs

                    def attn_fn(hh, lyr):
                        q, k, v = project_qkv(hh, lyr, cfg, positions)
                        cur_k = kp1[pages, offs_in]
                        cur_v = vp1[pages, offs_in]
                        kp2 = kp1.at[pages, offs_in].set(
                            jnp.where(write_ok, k, cur_k))
                        vp2 = vp1.at[pages, offs_in].set(
                            jnp.where(write_ok, v, cur_v))
                        if n_model > 1:
                            # TP-in-stage: the paged kernels as a nested
                            # shard_map over the auto "model" axis (the
                            # context mesh has "pipe" already Manual).
                            # The build-time gate guarantees the head
                            # layout partitions, so None cannot happen
                            # (and guarantees native shard_map, so the
                            # context AbstractMesh is real).
                            ctx = jax.sharding.get_abstract_mesh()
                            if hh.shape[1] == 1:
                                out = pattn.paged_decode_spmd(
                                    ctx, q, kp2, vp2, table, valid,
                                    sliding_window=cfg.sliding_window,
                                    softcap=cfg.attn_logit_softcap)
                            else:
                                out = pattn.paged_prefill_spmd(
                                    ctx, q, kp2, vp2, table,
                                    positions[:, 0], valid,
                                    sliding_window=cfg.sliding_window,
                                    softcap=cfg.attn_logit_softcap)
                            if out is None:
                                # The build gate already guarantees the
                                # head layout partitions, so the only
                                # reachable cause is an unsupported
                                # chunk/pool shape.
                                raise ValueError(
                                    "paged pool-direct under TP-in-stage "
                                    "could not serve this dispatch: "
                                    f"chunk T={hh.shape[1]} / page_size="
                                    f"{ps} / head_dim={q.shape[-1]} is "
                                    "not kernel-legal (or the head "
                                    "layout stopped partitioning)")
                        elif hh.shape[1] == 1:
                            out = pattn.paged_decode_attention(
                                q, kp2, vp2, table, valid,
                                sliding_window=cfg.sliding_window,
                                softcap=cfg.attn_logit_softcap)
                        else:
                            out = pattn.paged_prefill_attention(
                                q, kp2, vp2, table, positions[:, 0],
                                valid,
                                sliding_window=cfg.sliding_window,
                                softcap=cfg.attn_logit_softcap)
                        out = _einsum("bthd,hde->bte", out,
                                      lyr["o_proj"],
                                      tp="row").astype(hh.dtype)
                        return out, (kp2, vp2)

                    # (no kv_valid: with attn_fn set transformer_block
                    # ignores it — valid-length masking happens inside
                    # the paged kernels, same contract as forward_paged)
                    h, (kp1, vp1) = transformer_block(
                        h, layer, cfg, positions, None, None, None,
                        attn_fn=attn_fn)
                    return h, (kp1, vp1)

                # Same mesh context as the contiguous stage_scan: the
                # projections/MLP _einsums inside the blocks route int4
                # onto the kernel path (LOCAL_MESH on pipe-only meshes,
                # the abstract mesh under TP-in-stage).
                with _stage_mesh_ctx():
                    h, (kp_l, vp_l) = jax.lax.scan(
                        body, h, (stage_layers, kp_l, vp_l))
                return h, kp_l, vp_l

            self._pp_prefill_paged, self._pp_decode_paged = \
                make_pp_programs(stage_scan_paged)

        @partial(jax.jit, donate_argnums=(0, 1))
        def pp_copy_spans(kc, vc, src_idx, dst_idx, lo, hi):
            # Cross-knight prefix sharing, stage-sharded edition: copy K/V
            # positions [lo_i, hi_i) from slot src_idx[i] into dst_idx[i]
            # across EVERY stage's layer range. The slot axis (dim 2) is
            # unsharded, so the gather/scatter stays stage-local — no
            # cross-stage traffic (each stage copies its own layers' span).
            s_len = kc.shape[3]
            pos = jnp.arange(s_len).reshape(1, 1, 1, s_len, 1, 1)
            lo_b = lo.reshape(1, 1, -1, 1, 1, 1)
            hi_b = hi.reshape(1, 1, -1, 1, 1, 1)
            span = (pos >= lo_b) & (pos < hi_b)
            nk = jnp.where(span, kc[:, :, src_idx], kc[:, :, dst_idx])
            nv = jnp.where(span, vc[:, :, src_idx], vc[:, :, dst_idx])
            return kc.at[:, :, dst_idx].set(nk), \
                vc.at[:, :, dst_idx].set(nv)

        self._pp_copy_spans = pp_copy_spans

    # --- construction from adapter config ---

    @classmethod
    def from_config(cls, config: dict[str, Any]) -> "PPEngine":
        import dataclasses
        from .models.registry import resolve_model_config
        model_cfg = resolve_model_config(config)
        if model_cfg.layer_kinds is not None:
            raise ValueError(
                f"{model_cfg.name}: the pipeline engine declines a model "
                "with layer_kinds (reason: recurrent-state — its stage "
                "programs carry pages only); serve it with the main "
                "engine on one device")
        if config.get("max_seq_len"):
            model_cfg = dataclasses.replace(
                model_cfg, max_seq_len=int(config["max_seq_len"]))
        dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
                 "float16": jnp.float16}[config.get("dtype", "bfloat16")]
        sampling_cfg = config.get("sampling", {})
        sampling = SamplingParams(
            temperature=float(sampling_cfg.get("temperature", 0.7)),
            top_k=int(sampling_cfg.get("top_k", 0)),
            top_p=float(sampling_cfg.get("top_p", 1.0)),
            max_new_tokens=int(sampling_cfg.get("max_new_tokens", 1024)),
        )
        mesh = config.get("mesh", {})
        # Refuse configs this engine would otherwise silently serve
        # differently than asked (the "silent config drop" class): a
        # data axis means DP inside stages (unimplemented), and
        # seq-parallel is a main-engine feature. "model" composes:
        # mesh={"pipe": N, "model": M} runs TP inside each stage.
        extra_axes = sorted(set(mesh) - {"pipe", "model"})
        if extra_axes:
            raise ValueError(
                f"mesh axes {extra_axes} are not supported alongside "
                "'pipe' — the PP engine supports mesh={'pipe': N} or "
                "mesh={'pipe': N, 'model': M} (TP inside stages); use a "
                "(data, model) mesh on the main engine for DP")
        if config.get("seq_parallel"):
            raise ValueError(
                "seq_parallel is not supported on the PP engine — use a "
                "(data, model) mesh for ring/Ulysses long-context")
        engine = cls(
            model_cfg,
            checkpoint=config.get("checkpoint", "") or "",
            n_stages=int(mesh.get("pipe", 2)),
            n_model=int(mesh.get("model", 1)),
            n_micro=int(config.get("n_micro", 2)),
            num_slots=int(config.get("num_slots", 4)),
            dtype=dtype, quant=config.get("quant", "none"),
            kv_layout=config.get("kv_layout", "contiguous"),
            page_size=int(config.get("page_size", 128)),
            num_pages=(int(config["num_pages"])
                       if config.get("num_pages") else None),
            attn=config.get("attn") or "auto",
            sampling=sampling,
            seed=int(config.get("seed", 0)),
            devices=config.get("devices"),
            prefix_cache=config.get("prefix_cache"),
            prefix_cache_pages=(int(config["prefix_cache_pages"])
                                if config.get("prefix_cache_pages")
                                else None),
        )
        # Fleet auto-degrade marker — surfaced via describe() (advisor r3).
        engine.quant_auto_degraded = bool(
            config.get("_quant_auto_degraded"))
        if "dispatch_retries" in config:
            from .faults import RetryPolicy
            engine.retry = RetryPolicy(
                max_retries=max(0, int(config["dispatch_retries"])))
        return engine

    # --- serving (same surface the adapter uses on InferenceEngine) ---

    def int4_path_report(self) -> Optional[dict]:
        """InferenceEngine.int4_path_report's PP counterpart — same
        trace-time provenance (stage matmuls AND the in-stage decode /
        post-gather prefill lm-head dispatches)."""
        if self.quant != "int4":
            return None
        from .engine import summarize_int4_paths
        return summarize_int4_paths(self._int4_dispatches)

    def revive_kv_if_dead(self) -> bool:
        """InferenceEngine.revive_kv_if_dead's PP counterpart: paged
        pools live in the allocator; contiguous stage-stacked caches
        live here next to their SlotBook."""
        if self.kv_layout == "paged":
            # Branch on the LAYOUT, not `self.kc is None`: a dispatch
            # that failed inside the gather→scatter window leaves a
            # deleted gather view behind (the finally's scatter raised
            # before resetting kc/vc). Drop the view — the pools are
            # the source of truth — then let the allocator revive them
            # if the failure consumed the pools too.
            self.kc = self.vc = None
            return self.kv.revive_if_dead()
        if not self.kc.is_deleted():
            return False
        self.kc = self._make_contig()
        self.vc = self._make_contig()
        self.kv.forget_all()
        return True

    def chars_per_token(self) -> float:
        if self._chars_per_token is None:
            sample = ("The quick brown fox jumps over the lazy dog. "
                      "def main(args): return 0  # typical source text\n" * 4)
            n = len(self.tokenizer.encode(sample, add_bos=False))
            self._chars_per_token = max(len(sample) / max(n, 1), 0.25)
        return self._chars_per_token

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def warmup(self, max_prompt_tokens: int = 256,
               batch_sizes: tuple[int, ...] = (1,)) -> float:
        """Compile every (batch, bucket) prefill program ≤ the prompt
        limit plus the decode segment, twice each for the donated-buffer
        layout fixpoint — same discipline as InferenceEngine.warmup, so
        real prompts hitting smaller buckets (or multi-chunk prefills)
        never compile mid-serve on a cold cache."""
        t0 = time.monotonic()
        # Re-warm is always sanctioned — same contract as the main
        # engine's warmup (reopen first, declare at the end).
        from . import compile_watch
        compile_watch.reopen_warmup(self.cfg.name)
        limit = min(max_prompt_tokens,
                    self.max_seq_len - DECODE_SEGMENT - 1)
        buckets = [x for x in PREFILL_BUCKETS if x <= bucket_for(limit)]
        for b in batch_sizes:
            if b > self.kv.num_slots:
                continue
            for bucket in buckets:
                n = min(bucket, limit)
                turns = [(f"__warmup_{i}",
                          [self.tokenizer.bos_id] + [5 + i] * (n - 1))
                         for i in range(b)]
                for _ in range(2):
                    for name, _p in turns:
                        self.kv.release(name)
                    self.generate_batch(turns, max_new_tokens=1)
        # Warm the shared-prefix copy program (ONE shape thanks to
        # _apply_copies' padding) and the layout fixpoint of the programs
        # that consume the copied kc/vc — otherwise the first real round
        # with a shared preamble compiles mid-serve (same discipline as
        # InferenceEngine.warmup).
        from .engine import MIN_SHARED_PREFIX
        if self.kv.num_slots >= 2 and limit > MIN_SHARED_PREFIX + 8:
            shared = [self.tokenizer.bos_id] + [7] * (MIN_SHARED_PREFIX + 4)
            turns = [(f"__warmup_{i}", shared + [9 + i] * 4)
                     for i in range(2)]
            for _ in range(2):
                for name, _p in turns:
                    self.kv.release(name)
                self.generate_batch(turns, max_new_tokens=1)
        for i in range(max(max(batch_sizes), 2)):
            self.kv.release(f"__warmup_{i}")
        # Steady-state declaration (ISSUE 6): any later compile is a
        # recorded mid-serve recompile — same contract as the main
        # engine's warmup.
        from . import compile_watch
        compile_watch.warmup_complete(self.cfg.name)
        return time.monotonic() - t0

    def generate(self, prompt, slot_name: str = "default",
                 max_new_tokens: Optional[int] = None,
                 timeout_s: float = 600.0, session=None) -> str:
        return self.generate_batch([(slot_name, prompt)],
                                   max_new_tokens=max_new_tokens,
                                   timeout_s=timeout_s, session=session)[0]

    def generate_batch(self, turns, max_new_tokens=None,
                       timeout_s: float = 600.0,
                       sampling_per_turn=None, budget=None,
                       session=None) -> list[str]:
        return self.generate_batch_with_stats(
            turns, max_new_tokens=max_new_tokens, timeout_s=timeout_s,
            sampling_per_turn=sampling_per_turn, budget=budget,
            session=session)[0]

    def generate_batch_with_stats(self, turns, max_new_tokens=None,
                                  timeout_s: float = 600.0,
                                  sampling_per_turn=None, budget=None,
                                  session=None):
        # Session-namespaced slot names — same cross-session collision
        # fix as the main engine (kvcache.scoped_slot): concurrent
        # discussions sharing a PP engine keep disjoint slot lineages.
        if session:
            from .kvcache import scoped_slot
            turns = [(scoped_slot(session, name), prompt)
                     for name, prompt in turns]
        # Admission gate (fleet.drain) — same contract as the main
        # engine: one flag check per call, in-flight turns complete.
        deadlines.check_admission()
        with self._serve_lock:
            # "turn" span — same rung as the main engine (ISSUE 5) —
            # and the call-level compile-attribution window (ISSUE 6):
            # PP's stage dispatches funnel through run_dispatch, whose
            # rung-level fallback label carries no engine attr, so this
            # outer window is what makes a PP compile attributable to
            # THIS engine (and sentinel-enforceable once warm).
            from ..utils import telemetry
            from . import compile_watch
            with compile_watch.label(f"pp_serve[b={len(turns)}]",
                                     engine=self.cfg.name):
                if telemetry.ACTIVE:
                    with telemetry.span("turn", engine=self.cfg.name,
                                        rows=len(turns),
                                        session=session or "", pp=True):
                        return self._generate_locked(
                            turns, max_new_tokens, timeout_s,
                            sampling_per_turn, budget)
                return self._generate_locked(turns, max_new_tokens,
                                             timeout_s,
                                             sampling_per_turn, budget)

    def _chunked_rows(self, slot_ids, token_lists, offsets,
                      deadline, budget=None) -> jax.Array:
        """Chunked bucketed prefill of the given rows through the PP step
        program; returns last-token logits [B, V]."""
        slot_idx = jnp.asarray(slot_ids, jnp.int32)

        def prefill_dispatch(chunk, offs, lengths):
            last, caches = self._pp_prefill(
                self.shared, self.staged, (self.kc, self.vc), slot_idx,
                jnp.asarray(chunk), jnp.asarray(offs, jnp.int32),
                jnp.asarray(lengths))
            # Late completion of a watchdog-abandoned wait must not
            # clobber caches the recovery path revived (deadlines.py).
            with deadlines.commit_guard():
                self.kc, self.vc = caches
            return last

        return chunked_prefill(prefill_dispatch, token_lists, offsets,
                               self.max_seq_len, self.tokenizer.pad_id,
                               deadline, retry=self.retry, budget=budget)

    def _apply_copies(self, copies) -> None:
        """Dispatch queued (src_slot, dst_slot, lo, hi) span copies —
        padded to num_slots rows so pp_copy_spans compiles exactly ONE
        shape (same recompile guard as InferenceEngine._apply_copies);
        pad rows self-copy an empty span of a non-destination slot (dst
        indices stay distinct: scatter order among duplicates is
        unspecified)."""
        if not copies:
            return
        width = self.kv.num_slots
        if len(copies) < width:
            used = {c[1] for c in copies}
            pad_dst = next(i for i in range(width) if i not in used)
            copies = copies + [(pad_dst, pad_dst, 0, 0)] * (width -
                                                            len(copies))
        src, dst, lo, hi = (jnp.asarray(x, jnp.int32)
                            for x in zip(*copies))
        self.kc, self.vc = self._pp_copy_spans(self.kc, self.vc, src, dst,
                                               lo, hi)

    def _chunked_rows_pool_direct(self, token_lists, offsets, tables,
                                  deadline, budget=None) -> jax.Array:
        """Chunked bucketed prefill straight off the stage-stacked page
        pools (no gather view); returns last-token logits [B, V]."""
        def prefill_dispatch(chunk, offs, lengths):
            last, pools0 = self._pp_prefill_paged(
                self.shared, self.staged, self.kv.pools[0], tables,
                jnp.asarray(chunk), jnp.asarray(offs, jnp.int32),
                jnp.asarray(lengths))
            with deadlines.commit_guard():
                self.kv.pools = [pools0]
            return last

        return chunked_prefill(prefill_dispatch, token_lists, offsets,
                               self.max_seq_len, self.tokenizer.pad_id,
                               deadline, retry=self.retry, budget=budget)

    def _prefill_rows_paged(self, names_sub, token_spans, offsets_sub,
                            deadline, pinned, budget=None) -> None:
        """Prefill rows against the pool — pool-direct when the kernels
        are active, else the gather→chunked-prefill→scatter fallback.
        Either way the paged leader pass must land in the pool BEFORE
        laggards alias its pages."""
        for name, toks, off in zip(names_sub, token_spans, offsets_sub):
            self.kv.ensure_capacity(name, off + len(toks), write_from=off,
                                    pinned=pinned)
        tables = jnp.asarray(self.kv.table_for(list(names_sub)))
        if self._pool_direct:
            self._chunked_rows_pool_direct(token_spans, offsets_sub,
                                           tables, deadline, budget)
            return
        self.kc, self.vc = self._gather_view(self.kv.pools, tables)
        try:
            self._chunked_rows(list(range(len(names_sub))), token_spans,
                               offsets_sub, deadline, budget)
        finally:
            self.kv.pools = self._scatter_view(self.kv.pools, tables,
                                               self.kc, self.vc)
            self.kc = self.vc = None

    def _share_prefixes(self, names, slot_ids, all_tokens, offsets,
                        deadline, budget=None):
        """Cross-knight shared-prefix reuse on the stage-local caches —
        kvcache.share_prefixes (the same two-pass algorithm the main
        engine runs) with PP device mechanics: stage-sharded span copies
        (contiguous) or page aliasing (paged), and chunked leader
        prefill."""
        from .engine import MIN_SHARED_PREFIX
        from .kvcache import share_prefixes
        paged = self.kv_layout == "paged"
        pinned = tuple(names)
        copies: list[tuple[int, int, int, int]] = []

        def add_share(donor, i, lo, hi):
            if paged:
                self.kv.alias_span(donor.name, names[i], lo, hi, pinned)
            else:
                copies.append((donor.slot_id, slot_ids[i], lo, hi))

        def flush_shares():
            self._apply_copies(copies)
            copies.clear()

        def prefill_span(m, lo, hi):
            if paged:
                self._prefill_rows_paged(
                    [names[m]], [all_tokens[m][lo:hi]], [lo], deadline,
                    pinned, budget)
            else:
                self._chunked_rows([slot_ids[m]], [all_tokens[m][lo:hi]],
                                   [lo], deadline, budget)

        return share_prefixes(
            self.kv, names, all_tokens, offsets,
            min_shared=MIN_SHARED_PREFIX, add_share=add_share,
            flush_shares=flush_shares, prefill_span=prefill_span)

    def _prepare_batch(self, turns, max_new_padded, deadline, pre_budget,
                       stats) -> dict:
        """The PP pre-PREFILL phase — tokenize + tail-truncate →
        own-slot reuse_plan → prefix-cache attach → cross-knight
        share_prefixes → paged capacity/COW + tables/gather-view — as
        ONE seam mirroring InferenceEngine._prepare_batch's
        defer_prefill contract (ISSUE 8, the mixed-dispatch seam): the
        returned suffixes (all_tokens[i][offsets[i]:]) are NOT yet
        prefilled, so a caller can feed them through a mixed dispatch
        instead of the blocking prologue. _generate_locked is today's
        only consumer (PP's stage-pipelined programs have no ragged
        program yet) and runs the chunked prologue over the same dict."""
        pinned = tuple(name for name, _ in turns)
        slot_ids, offsets, all_tokens = [], [], []
        for name, prompt in turns:
            tokens = (list(prompt) if isinstance(prompt, list)
                      else self.tokenizer.encode(prompt))
            budget_tok = prompt_budget(self.max_seq_len, max_new_padded)
            if len(tokens) > budget_tok:
                tokens = (tokens[:1]
                          + tokens[len(tokens) - budget_tok + 1:])
            slot_id, reuse = self.kv.reuse_plan(name, tokens, pinned)
            slot_ids.append(slot_id)
            offsets.append(reuse)
            all_tokens.append(tokens)

        # Cross-session prefix cache (ISSUE 7): same consult the main
        # engine's _prepare_batch runs (prefix_cache.attach_rows — one
        # definition, so the warmup-exclusion rule and accounting can
        # never drift between the serving paths).
        prefix_reused = 0
        if getattr(self, "prefix_cache", None) is not None:
            prefix_reused = self.prefix_cache.attach_rows(
                list(pinned), all_tokens, offsets, pinned)

        offsets, extra_prefill = self._share_prefixes(
            list(pinned), slot_ids, all_tokens, offsets, deadline,
            budget=pre_budget)
        # Copied donor spans count as reused (same accounting as the main
        # engine); the leader's extra span was genuinely prefilled.
        stats.reused_tokens = sum(offsets) - extra_prefill
        stats.prefix_reused_tokens = prefix_reused
        stats.prefill_tokens = extra_prefill + sum(
            len(t) - o for t, o in zip(all_tokens, offsets))

        tables = None
        gathered = False
        if self.kv_layout == "paged":
            # Allocate pages for the whole call (prompt + padded decode),
            # COW any shared page in the write range. Pool-direct mode
            # serves straight off the stage-stacked pool through the
            # page-table-aware kernels; otherwise gather the pool into
            # the position-aligned view every PP program uses. Either
            # way the row index IS the batch index.
            for i, name in enumerate(pinned):
                self.kv.ensure_capacity(
                    name, len(all_tokens[i]) + max_new_padded,
                    write_from=offsets[i], pinned=pinned)
            tables = jnp.asarray(self.kv.table_for(list(pinned)))
            if not self._pool_direct:
                self.kc, self.vc = self._gather_view(self.kv.pools,
                                                     tables)
                gathered = True
            slot_ids = list(range(len(turns)))
        return {"pinned": pinned, "slot_ids": slot_ids,
                "offsets": offsets, "all_tokens": all_tokens,
                "tables": tables, "gathered": gathered}

    def _generate_locked(self, turns, max_new_tokens, timeout_s,
                         sampling_per_turn=None, budget=None):
        stats = GenStats()
        # Turn budget node (engine/deadlines.py) — same rung structure
        # as the main engine; the float deadline feeds the legacy
        # checks.
        turn_budget = budget if budget is not None \
            else deadlines.Budget.root(timeout_s, rung="turn")
        deadline = min(turn_budget.deadline, time.monotonic() + timeout_s)
        pre_budget = turn_budget.child("prefill")
        from .serving_loop import clamp_max_new
        max_new, max_new_padded = clamp_max_new(
            max_new_tokens or self.sampling.max_new_tokens,
            self.max_seq_len)

        prep = self._prepare_batch(turns, max_new_padded, deadline,
                                   pre_budget, stats)
        pinned = prep["pinned"]
        slot_ids = prep["slot_ids"]
        offsets = prep["offsets"]
        all_tokens = prep["all_tokens"]
        tables = prep["tables"]
        gathered = prep["gathered"]

        try:
            # Chunked bucketed prefill (shared serving_loop host loop
            # with the PP step program).
            t0 = time.monotonic()
            spans = [t[o:] for t, o in zip(all_tokens, offsets)]
            with telemetry.span("prefill", engine=self.cfg.name,
                                pp=True):
                if tables is not None and self._pool_direct:
                    last_logits = self._chunked_rows_pool_direct(
                        spans, offsets, tables, deadline, pre_budget)
                else:
                    last_logits = self._chunked_rows(slot_ids, spans,
                                                     offsets, deadline,
                                                     pre_budget)
                # Blocking scalar fetch → the deadline seam (a wedged
                # prefill program freezes the host loop exactly here).
                host_sync(lambda: float(last_logits[0, 0]), pre_budget,
                          "prefill")
            stats.prefill_seconds = time.monotonic() - t0
            slot_idx = jnp.asarray(slot_ids, jnp.int32)

            per_row = sampling_per_turn or [self.sampling] * len(turns)
            if len(per_row) != len(turns):
                raise ValueError(
                    f"sampling_per_turn has {len(per_row)} entries for "
                    f"{len(turns)} turns")
            temps, top_ks, top_ps = sampling_arrays(per_row)
            greedy = all(p.temperature <= 0.0 for p in per_row)
            if greedy:
                first = jnp.argmax(last_logits.astype(jnp.float32),
                                   axis=-1).astype(jnp.int32)
            else:
                first = sample_token_batch(
                    last_logits.astype(jnp.float32), self._next_key(),
                    temps, top_ks, top_ps).astype(jnp.int32)
            first_np = host_sync(lambda: np.asarray(first), pre_budget,
                                 "prefill")
            cur_valid = jnp.asarray([len(t) for t in all_tokens],
                                    jnp.int32)

            t1 = time.monotonic()
            # Decode rung budget derived at decode start, so a
            # configured "decode" cap times the decode phase alone.
            dec_budget = turn_budget.child("decode")
            # Per-row decode budgets (knight_sampling max_new_tokens) —
            # serving_loop.row_budget_fn, one definition for both engines.
            from .serving_loop import row_budget_fn
            row_remaining = row_budget_fn(per_row, sampling_per_turn,
                                          max_new)

            if tables is not None and self._pool_direct:
                def decode_dispatch(cur_last, valid, budget, done0):
                    row_budgets = row_remaining(budget)
                    out, steps, last, valid, done, pools0 = \
                        self._pp_decode_paged(
                            self.shared, self.staged, self.kv.pools[0],
                            tables, cur_last, valid, self._next_key(),
                            budget, temps, top_ks, top_ps, row_budgets,
                            done0, max_new=DECODE_SEGMENT, greedy=greedy)
                    with deadlines.commit_guard():
                        self.kv.pools = [pools0]
                    return out, steps, last, valid, done
            else:
                def decode_dispatch(cur_last, valid, budget, done0):
                    row_budgets = row_remaining(budget)
                    out, steps, last, valid, done, caches = \
                        self._pp_decode(
                            self.shared, self.staged, (self.kc, self.vc),
                            slot_idx, cur_last, valid, self._next_key(),
                            budget, temps, top_ks, top_ps, row_budgets,
                            done0, max_new=DECODE_SEGMENT, greedy=greedy)
                    with deadlines.commit_guard():
                        self.kc, self.vc = caches
                    return out, steps, last, valid, done

            with telemetry.span("decode", engine=self.cfg.name,
                                pp=True):
                out_np = decode_segments(decode_dispatch, first,
                                         cur_valid,
                                         self.tokenizer.eos_id, max_new,
                                         deadline, timeout_s,
                                         retry=self.retry,
                                         budget=dec_budget)
            stats.decode_seconds = time.monotonic() - t1
        finally:
            # Scatter back even on a mid-serve timeout: otherwise the
            # gathered view (the full contiguous-size budget paging
            # avoids) stays resident and every prefilled token is lost.
            # Slot records stay truncated until commit, so a partial
            # scatter only under-claims. (Pool-direct mode writes the
            # pool incrementally per dispatch — nothing to scatter.)
            if gathered:
                self.kv.pools = self._scatter_view(self.kv.pools, tables,
                                                   self.kc, self.vc)
                self.kc = self.vc = None

        results = finalize_outputs(
            turns, first_np, out_np, all_tokens, max_new,
            self.tokenizer.eos_id, self.kv.commit, self.tokenizer.decode,
            stats)
        stats.int4_paths = self.int4_path_report()
        # Unified registry publish (ISSUE 5) — same seam as the main
        # engine, so PP serving's counters land in the one store too.
        trace_hooks.publish_gen_stats(stats, self.cfg.name,
                                      perf=self.perf)
        trace_hooks.publish_int4_paths(stats.int4_paths, self.cfg.name)
        trace_hooks.publish_memory_ledger(self)
        self.last_stats = stats
        return results, stats

    # --- introspection ---

    def describe(self) -> dict[str, Any]:
        info = {
            "model": self.cfg.name,
            "params": self.num_params,
            "max_seq_len": self.max_seq_len,
            "mesh": ({"pipe": self.n_stages, "model": self.n_model}
                     if self.n_model > 1 else {"pipe": self.n_stages}),
            "n_micro": self.n_micro,
            "num_slots": self.kv.num_slots,
            "kv_layout": (f"stage-local {self.kv_layout}"
                          + (" (pool-direct)" if self._pool_direct
                             else (" (gather-view)"
                                   if self.kv_layout == "paged" else ""))),
            "attn": self.cfg.attn_impl,
            "quant": (self.quant + " (auto-degraded)"
                      if getattr(self, "quant_auto_degraded", False)
                      else self.quant),
            "scope": "PP serving: prefill + decode with stage-local KV "
                     "(contiguous or paged pool; pool-direct "
                     "page-table kernels, incl. TP-in-stage via nested "
                     "shard_map over the model axis); flash kernels "
                     "inside stages (raw on pipe-only meshes, spmd "
                     "wrappers under TP-in-stage; dense only by opt-out "
                     "or non-partitionable heads); own-slot LCP reuse; "
                     "cross-knight donor + leader prefix sharing (page "
                     "aliasing when paged); per-row sampling; int8 "
                     "w8a16; int4 w4a16 on the fused kernels inside "
                     "stages (LOCAL_MESH / nested shard_map)",
            "devices": [str(d) for d in self.mesh.devices.flatten()],
        }
        if self.quant == "int4":
            info["int4_paths"] = self.int4_path_report()
        # ISSUE 7: cross-session prefix-cache state (paged layouts).
        if getattr(self, "prefix_cache", None) is not None:
            info["prefix_cache"] = self.prefix_cache.describe()
        # ISSUE 5: the unified registry's per-engine view.
        info["telemetry"] = trace_hooks.engine_telemetry_view(
            self.cfg.name)
        # ISSUE 6: live perf attribution (same surface as the main
        # engine's describe()).
        from . import compile_watch, get_compile_cache_decision
        info["perf"] = self.perf.describe()
        info["compile_cache"] = get_compile_cache_decision()
        info["compile_observatory"] = compile_watch.summary()
        return info
