"""InferenceEngine — sharded prefill + decode over a paged KV pool.

The TPU-native serving stack replacing Ollama/LM Studio llama.cpp
(SURVEY.md §3.4): tokenize → chunked, bucketed prefill (delta-only thanks to
per-knight slot reuse) → jit'd while_loop decode → detokenize.

XLA discipline:
- prefill chunk lengths are bucketed (powers of two) so transcript growth
  across rounds does NOT trigger recompiles (SURVEY.md §7.3 hard part 5)
- the decode loop is ONE device program (lax.while_loop with an on-device
  all-done predicate), not a Python token loop — no per-token dispatch
- the page pools are donated, so page writes are in-place on HBM
- batch rows = knight slots; generate_batch serves N knights in the same
  programs with per-row offsets (SURVEY.md §7 Phase 5)
"""

from __future__ import annotations

import threading
import time
import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import ENGINE_CONFIG_KEYS, deadlines, dispatch_pack, faults
from .models.common import ModelConfig, forward, param_count, spmd_mesh
from .models.registry import resolve_model_config
from .sampling import (SamplingParams, row_filtered, sample_token_batch,
                       sampling_arrays)
from .serving_loop import (DECODE_SEGMENT, MAX_PREFILL_CHUNK,
                           PREFILL_BUCKETS, ReplicaGroupPlan,
                           bucket_for as _bucket,
                           chunked_prefill, decode_segments,
                           finalize_outputs, host_sync, new_dispatch_totals,
                           note_issue, prompt_budget, roomy_frame)
from .sharding import build_mesh, init_sharded_params, shard_params
from .tokenizer import load_tokenizer

# Cross-slot K/V copies are bandwidth-cheap but still a program dispatch;
# below this many shared tokens a plain prefill is faster than the copy.
MIN_SHARED_PREFIX = 64


def summarize_int4_paths(dispatches: dict) -> dict:
    """Fold the trace-time int4 dispatch log (models/common._record_int4
    entries) into the path-provenance report describe()/stats expose:
    {"pallas_w4a16": [entry...], "xla_dequant": [entry...]} with each
    entry carrying spec/shapes (and `fallback_reason` on the XLA side)."""
    kernel, fallback = [], []
    for e in dispatches.values():
        (kernel if e["path"] == "pallas_w4a16" else fallback).append(e)

    def order(e):
        return (e["spec"], e["a_shape"])

    return {"pallas_w4a16": sorted(kernel, key=order),
            "xla_dequant": sorted(fallback, key=order)}


@dataclass
class GenStats:
    prefill_tokens: int = 0
    reused_tokens: int = 0
    # Of reused_tokens, how many the CROSS-SESSION prefix cache served
    # (ISSUE 7) — own-slot LCP hits and intra-session donation make up
    # the rest. 0 on cache-off engines.
    prefix_reused_tokens: int = 0
    decode_tokens: int = 0
    prefill_seconds: float = 0.0
    decode_seconds: float = 0.0
    # int4 path provenance (ISSUE 3): which path each compiled einsum
    # dispatch took — {"pallas_w4a16": [...], "xla_dequant": [...]}.
    # Populated at trace time, snapshotted per call; None on non-int4
    # engines.
    int4_paths: Optional[dict] = None
    # Scheduler provenance (ISSUE 4): set only on calls served through
    # the continuous-batching session scheduler — queue_wait_s,
    # occupancy_mean/max (decode-batch rows while this call's rows were
    # active), segments, sessions_max. None on direct engine calls.
    sched: Optional[dict] = None

    @property
    def prefill_tps(self) -> float:
        return self.prefill_tokens / self.prefill_seconds \
            if self.prefill_seconds else 0.0

    @property
    def decode_tps(self) -> float:
        return self.decode_tokens / self.decode_seconds \
            if self.decode_seconds else 0.0


class InferenceEngine:
    """One resident model + its page pool + compiled step programs."""

    def __init__(self, model_cfg: ModelConfig, *, checkpoint: str = "",
                 mesh_shape: Optional[dict[str, int]] = None,
                 num_slots: int = 8, dtype=jnp.bfloat16,
                 sampling: Optional[SamplingParams] = None,
                 seed: int = 0, seq_parallel: int = 0,
                 long_threshold: int = 2048,
                 long_scheme: str = "ring", attn: str = "auto",
                 devices: Optional[list[int]] = None,
                 kv_layout: str = "paged", page_size: int = 128,
                 num_pages: Optional[int] = None, quant: str = "none",
                 dcn_axis: Optional[str] = None,
                 prefix_cache: Optional[bool] = None,
                 prefix_cache_pages: Optional[int] = None,
                 kv_offload: Optional[bool] = None,
                 ragged_attn: Optional[bool] = None,
                 ragged_tokens: Optional[int] = None,
                 spec_decode: Optional[bool] = None,
                 spec_max_draft: Optional[int] = None,
                 lora: Optional[dict] = None,
                 kv_quant: Any = None,
                 state_snapshot_bytes: Optional[int] = None):
        if kv_layout != "paged":
            # One KV layout. The key stays accepted with its one value
            # until the benchmark's files stop passing it (ROADMAP D1b).
            raise ValueError(
                f"kv_layout {kv_layout!r} is not served: every model "
                "serves through kv_layout 'paged' only (the contiguous "
                "layout was removed in PR 46)")
        # Multi-host: join the process group BEFORE any backend/device
        # call when ROUNDTABLE_COORDINATOR is set (engine/distributed.py);
        # jax.devices() below then spans every host's chips.
        from .distributed import maybe_init_distributed
        maybe_init_distributed()
        # Persistent XLA compile cache: first-ever run compiles, every
        # later process deserializes (SURVEY.md §7.3 hard part 5).
        from . import enable_compilation_cache
        enable_compilation_cache()
        # Compile observatory (ISSUE 6): every compile this process does
        # from here on is recorded (label, duration, cache hit/miss)
        # and checked against the steady-state recompile sentinel.
        from . import compile_watch
        compile_watch.install()
        # devices: indices into jax.devices() — the fleet planner assigns
        # disjoint per-model submeshes this way (engine/fleet.py)
        device_list = None
        if devices:
            all_devices = jax.devices()
            device_list = [all_devices[i] for i in devices]
        self.mesh = build_mesh(mesh_shape, device_list, dcn_axis=dcn_axis)
        model_cfg = self._resolve_attn(model_cfg, attn, self.mesh)
        self.cfg = model_cfg
        # A model with layer_kinds (models/hybrid.py) serves through the
        # step programs that carry a second tree beside the pools and
        # return what the expert layers counted. What cannot carry
        # recurrent state, latent pages or the held experts' leaves yet
        # declines HERE, each with a reason describe() reports — never
        # as a failure at trace time. What the model cannot be served
        # without fails now.
        self.declines: dict[str, str] = {}
        self._latent_positions = 0
        self._sampler = {"segments": 0, "filtered_segments": 0,
                         "filtered_rows": 0}
        if model_cfg.layer_kinds is not None:
            # Without recurrent state everything that addresses pages by
            # id stays on (the leader pass, the prefix cache, offload);
            # what declines is what reads inside a page or a weight leaf.
            why = ("recurrent-state" if model_cfg.recurrent
                   else "latent-pages" if model_cfg.latent
                   else "attn-layers" if model_cfg.attn_layers
                   else "layer-kinds")
            if self.mesh.devices.size > 1:
                raise ValueError(
                    f"{model_cfg.name} has layer_kinds: a mesh over "
                    f"{self.mesh.devices.size} devices is not supported "
                    "yet (the state tree and the experts' product are "
                    "single-device); give it one device")
            if quant != "none":
                self.declines["quant"] = f"{why}:quant-leaves"
                quant = "none"
            if kv_quant and kv_quant != "none":
                self.declines["kv_quant"] = (
                    f"{why}:cells-are-per-head" if model_cfg.latent
                    else why if model_cfg.recurrent
                    else f"{why}:step-programs-carry-no-scale-pools")
                kv_quant = None
            if seq_parallel and seq_parallel > 1:
                self.declines["seq_parallel"] = why
                seq_parallel = 0
            if lora:
                self.declines["lora"] = (
                    why if model_cfg.recurrent else f"{why}:no-lora-targets")
                lora = None
            if model_cfg.recurrent:
                if kv_offload:
                    self.declines["kv_offload"] = why
                kv_offload = False
            # A rejected draft cannot be un-consumed from a recurrent
            # state; without one, the programs of a model with
            # layer_kinds still have no verify shape (score_width).
            self.declines["spec_decode"] = (
                why if model_cfg.recurrent else f"{why}:no-verify-program")
            spec_decode = False
            if model_cfg.expert_layers:
                # The routed experts' grouped product: the Pallas kernel,
                # or lax.ragged_dot where it declines (off the chip; a
                # width under a lane row) — one rule, pallas/grouped.py.
                from .pallas import grouped
                reason = grouped.decline_reason(
                    model_cfg.embed_dim, model_cfg.expert_dim, dtype)
                if reason is not None:
                    self.declines["grouped_product"] = reason
            if model_cfg.retention_layers:
                # The decode step of a retention layer: one Pallas pass
                # over a state, or the jax.numpy recurrence where that
                # declines — one rule, pallas/retention.py. What else a
                # state of this size rules out is said here too.
                from .pallas import retention as pret
                reason = pret.decline_reason(model_cfg.head_dim,
                                             model_cfg.kv_repeat)
                if reason is not None:
                    self.declines["retention_step"] = reason
            if model_cfg.mamba1_layers:
                # The selective scan of a join: one Pallas call a layer,
                # or the jax.numpy recurrence where that declines — one
                # rule, pallas/mamba1.py.
                from .pallas import mamba1 as pm1
                reason = pm1.decline_reason(model_cfg.mamba1_dim,
                                            model_cfg.ssm_state)
                if reason is not None:
                    self.declines["mamba1_scan"] = reason
            if model_cfg.retention_layers or model_cfg.mamba1_layers:
                # (a state that lives whole on the slot arrays)
                self.declines["evacuation"] = (
                    f"{why}:no-host-copy-of-a-state")
        self.max_seq_len = model_cfg.max_seq_len
        self.sampling = sampling or SamplingParams()
        self.tokenizer = load_tokenizer(checkpoint or None)

        if quant not in ("none", "int8", "int4"):
            raise ValueError(
                f"quant must be none|int8|int4, got {quant!r}")
        self.quant = quant
        self.dtype = dtype
        # int4 path-provenance sink: the trace-time dispatch log every
        # spmd_mesh context below carries (models/common._record_int4) —
        # populated as each (batch, bucket) program traces, summarized
        # by int4_path_report()/describe().
        self._int4_dispatches: dict = {}
        # Multi-LoRA provenance sink (ISSUE 10): the trace-time lora
        # routing log (engine/lora.apply_current records into it via
        # the lora_scope every jit program below opens) — the
        # int4_paths pattern, summarized by lora_describe(). The store
        # itself resolves AFTER the compiled closures are defined (it
        # needs the sharded mesh + quant mode); self.lora stays None
        # on lora-off engines and every `lora=` program argument is
        # then None, keeping those programs byte-identical.
        self._lora_dispatches: dict = {}
        self._lora_quant = "none"
        self.lora = None
        self.lora_reason: Optional[str] = None
        self._lora_tokens = 0
        self._lora_share_suppressed = 0
        # adapter-id label per slot NAME (engine-side): prefix sharing
        # and the cross-session cache must never move K/V between
        # slots served under different adapters (the bytes differ).
        self._slot_adapters: dict[str, Optional[str]] = {}

        # The build's own steps are phases of the set-up table (ISSUE
        # 54): wall seconds, beside the stages the compile hooks hear.
        with compile_watch.phase("init"):
            if checkpoint:
                from .checkpoint import load_hf_checkpoint
                # The loader still lands the whole tree on the default
                # device before this reshard (ROADMAP): an unsharded
                # copy whose reference dies with this expression.
                self.params = shard_params(
                    load_hf_checkpoint(checkpoint, model_cfg, dtype),
                    model_cfg, self.mesh)
            else:
                # Born sharded: no device ever holds more than its
                # shard.
                self.params = init_sharded_params(
                    model_cfg, jax.random.PRNGKey(seed), dtype,
                    self.mesh)
        if quant in ("int8", "int4"):
            # AFTER sharding: q/s are jnp ops on the sharded weights, so
            # XLA propagates the NamedShardings (engine/quant.py).
            # free_source: nothing references the bf16 tree after this, so
            # each source leaf is freed as its q lands — 7B-class int8
            # builds peak near bf16-total instead of bf16+int8.
            # model_shards: int4 packing aligns groups to the TP shard
            # boundary so the shard-aware kernel dispatch can partition
            # scales with whole groups per shard (engine/quant.py).
            from .quant import quantize_params
            from .sharding import model_axis_size
            with compile_watch.phase("quantize"):
                self.params = quantize_params(
                    self.params, model_cfg, act_dtype=dtype,
                    free_source=True, bits=8 if quant == "int8" else 4,
                    model_shards=model_axis_size(self.mesh))
        self.num_params = param_count(self.params)

        # Quantized KV pages (ISSUE 11): resolve the `kv_quant:` config
        # against the ROUNDTABLE_KV_QUANT kill-switch BEFORE the pool is
        # built — the pool's dtype, its scale arrays, and its
        # byte-budget-equal default page count all follow the spec; the
        # reason is machine-readable like every other path decision.
        from .kv_quant import resolve_spec as _kvq_resolve
        self.kv_quant_spec = None
        self.kv_quant_reason: Optional[str] = None
        self.kv_quant_fallback_reason: Optional[str] = None
        self._kv_quant_dispatches: dict[str, int] = {}
        from collections import deque as _dq
        self._kv_quant_recent = _dq(maxlen=32)
        self.kv_quant_spec, self.kv_quant_reason = _kvq_resolve(kv_quant)
        if "kv_quant" in self.declines:
            self.kv_quant_reason = self.declines["kv_quant"]

        from jax.sharding import NamedSharding, PartitionSpec as P
        from .pallas import page_copy
        from .paging import PagedKVCache
        from .sharding import DATA_AXIS, MODEL_AXIS, _fallback_replicated
        data_size = dict(self.mesh.shape).get("data", 1)
        pool_sharding = None
        if self.mesh.devices.size > 1:
            # Per-replica pools (VERDICT r3 #7): the PAGE axis shards
            # over "data" (the allocator rounds num_pages to a
            # multiple of data_size and keeps every slot's pages on
            # one replica), kv heads over "model" — each device holds
            # pages/data x heads/model, not a full replicated pool.
            spec = _fallback_replicated(
                P(DATA_AXIS if data_size > 1 else None, None,
                  MODEL_AXIS, None),
                (data_size, page_size, model_cfg.num_kv_heads,
                 model_cfg.head_dim),
                self.mesh)
            pool_sharding = NamedSharding(self.mesh, spec)

        @partial(jax.jit, donate_argnums=(0,))
        def scatter_pages(pools, src_ids, dst_ids):
            # Whole-page copies as XLA has them: every source
            # gathered, then scattered — what runs where the DMA
            # copier declines. Over some layouts the scatter
            # re-lays the whole pool out and back (PERF.md, PR 45).
            return [tuple(p.at[dst_ids].set(p[src_ids]) for p in layer)
                    for layer in pools]

        def copy_pages(pools, src_ids, dst_ids):
            # Whole-page copies (copy-on-write + alias boundaries):
            # the cache queues its copies and issues them here as
            # numpy ids padded to one of paging.COPY_WIDTHS, so
            # this compiles those shapes and no other (a pad row
            # names a scratch page twice and moves no byte that
            # differs); kv.warm_copier compiles each in warmup().
            # DMAs in place where the pools allow them, one rule:
            # pallas/page_copy.py.
            copier = (page_copy.copy_pages
                      if self.kv.page_copy_path == page_copy.PATH
                      else scatter_pages)
            return copier(pools, src_ids, dst_ids)

        # Default pool: HALF of num_slots sequences at max_seq_len — and
        # since the page axis shards over "data", that is the TOTAL across
        # replicas (each device holds total/data), not a replicated
        # per-device cost. Worst case that FITS the default:
        # ceil(num_slots/2) sequences simultaneously resident at full
        # max_seq_len, spread over the replicas their slots pin to. A
        # batch pinning MORE than that, all near max_seq_len, exhausts
        # a replica's range mid-serve with an actionable RuntimeError
        # ("raise num_pages / lower max_new_tokens") — set num_pages
        # explicitly (up to num_slots*max_seq_len/page_size +
        # data_size: every slot at full length) when every knight
        # runs long.
        with compile_watch.phase("pools"):
            self.kv = PagedKVCache(
                model_cfg, num_slots, self.max_seq_len, dtype,
                pool_sharding, page_size=page_size, num_pages=num_pages,
                copy_pages_fn=copy_pages, data_size=data_size,
                kv_quant=self.kv_quant_spec)
        reason = page_copy.decline_reason(
            jax.tree.leaves(self.kv.combined_pools()))
        if reason is not None:
            self.declines["page_copy"] = reason
        self.kv.page_copy_path = reason or page_copy.PATH

        # The sampler's key chain (ISSUE 53). `_keys` is
        # `jax.random.split(chain key)`, [2, 2]: row 0 the chain's next
        # key, row 1 the key of the next program that draws — the pair
        # `_next_key` left on the host, one eager split a dispatch,
        # before. A program that draws takes the pair, draws from row 1
        # and returns `split(row 0)`, the next pair (`chain_key` below):
        # the same splits in the same order of programs, so the draws
        # are the same. Why a pair, and not the chain key split at the
        # program's head: with the split at its head Jamba's decode
        # program came out of the compiler moving a scanned run's whole
        # state between memory spaces every step (PERF.md, PR 53); a
        # program whose loop starts from an argument does not. The pair
        # is placed as the programs return it, so the first dispatch
        # and every later one meet ONE signature.
        from jax.sharding import NamedSharding, PartitionSpec
        self._replicated = NamedSharding(self.mesh, PartitionSpec())
        self._keys = jax.device_put(
            jax.random.split(jax.random.PRNGKey(seed + 1)),
            self._replicated)
        # What the step seams sent and issued (serving_loop.note_issue).
        self._dispatch_totals = new_dispatch_totals()
        # The first decode segment's stand-in for the state a segment
        # carries to the next, a rows bucket each (`_decode_carry`).
        self._carry0: dict[int, tuple] = {}
        self._chars_per_token: Optional[float] = None
        self.last_stats = GenStats()
        # Serving mutates the page pools (donated buffers): one generation
        # at a time per engine. Distinct engines (fleet submeshes) still
        # run concurrently — each has its own lock.
        self._serve_lock = threading.Lock()
        # Dispatch retry policy (engine/faults.py): a transient device
        # dispatch failure retries in place before surfacing to the
        # adapter's degradation ladder. from_config overrides via the
        # "dispatch_retries" key.
        self.retry = faults.DEFAULT_RETRY

        # Sequence-parallel long-context prefill (SURVEY.md §7 Phase 6):
        # ring attention (or Ulysses) over a ("seq",) mesh for fresh long
        # prompts; decode + delta prefills stay on the chunked path.
        self.long_threshold = long_threshold
        self.seq_mesh = None
        self._ring_prefill_fn = None
        if seq_parallel and seq_parallel > 1:
            from .longcontext import build_seq_mesh, make_ring_prefill
            # The seq mesh must span EXACTLY the engine mesh's devices
            # (params live there; jit reshards them into the ring program),
            # so the ring width is the engine mesh size and seq_parallel
            # acts as the opt-in. Pick the width via mesh_shape.
            devs = list(self.mesh.devices.flatten())
            self.seq_mesh = build_seq_mesh(len(devs), devs)
            self._ring_prefill_fn = make_ring_prefill(
                model_cfg, self.seq_mesh, scheme=long_scheme)

        # compiled closures (per (batch, bucket) shapes, cached by jit)
        cfg = model_cfg

        mesh = self.mesh

        # Small program outputs the HOST loop reads (logits rows, token
        # ids, flags) are pinned REPLICATED: on a multi-host mesh every
        # process can then np.asarray its addressable copy and all
        # processes' host loops stay in lockstep — without this, GSPMD
        # may shard an output across hosts and the read raises. On one
        # process the constraint is a no-op.
        from jax.sharding import NamedSharding as _NS, PartitionSpec as _P
        _rep = _NS(mesh, _P())

        def host_read(*xs):
            out = tuple(jax.lax.with_sharding_constraint(x, _rep)
                        for x in xs)
            return out if len(out) > 1 else out[0]

        def chain_key(keys):
            """-> (the engine's next pair, this program's key), from the
            pair the engine holds (`_keys`): what `_next_key` did on the
            host before ISSUE 53 — `key, sub = split(key)` — with the
            split made one program ahead, by the program before."""
            return host_read(jax.random.split(keys[0])), keys[1]

        @partial(jax.jit, static_argnames=("layout", "greedy"))
        def first_token(last_logits, keys, buf, layout, greedy):
            # The prologue's first token from ONE program per ([B, V],
            # greedy) — the same cast, argmax and sampler the decode
            # loop's body runs. Outside jit the sampler is some fifty
            # one-operation dispatches and its lax.cond recompiles on
            # every call (sampling.sample_token_batch). `buf`: the
            # rows' sampling parameters (dispatch_pack.sampler_layout).
            # -> (tokens, the next pair of keys); a greedy batch draws
            # none and hands back the pair it was given.
            row_logits = last_logits.astype(jnp.float32)
            if greedy:
                nxt = jnp.argmax(row_logits, axis=-1)
            else:
                f = layout.unpack(buf)
                keys, sub = chain_key(keys)
                nxt = sample_token_batch(row_logits, sub, f["temps"],
                                         f["top_ks"], f["top_ps"])
            return host_read(nxt.astype(jnp.int32)), keys

        self._first_token = first_token

        def decode_while(step_fn, caches, first_token, start_valid, key,
                         budget, temps, top_ks, top_ps, row_budgets,
                         done0, max_new, greedy, lora=None,
                         pass_active=False):
            """The decode while_loop, ONCE for every family of step
            programs (gather view, pool-direct, hybrid) —
            `step_fn(last, valid, caches) -> (logits [B,1,V], caches)` is
            the only family-specific piece. max_new is the STATIC segment
            size (one compiled program per value — always DECODE_SEGMENT
            in serving); budget is the DYNAMIC number of tokens actually
            wanted from this segment, so short tails exit early without a
            fresh compile. Sampling params AND per-row token budgets are
            per-ROW dynamic arrays (heterogeneous knight personas: a row
            whose own max_new_tokens is exhausted goes done — emitting
            eos — while hungrier rows keep decoding; no recompile per
            config) — except the all-greedy common case, where the
            STATIC greedy flag keeps the hot path a single argmax
            instead of the sampler's divide and draw over the vocabulary
            (one extra compiled variant total, not one per config; what
            a sampled batch's filters cost is decided on the device:
            sampling.sample_token_batch).
            row_budgets count REMAINING tokens at this segment's start
            (the host loop decrements across segments)."""
            b = first_token.shape[0]
            out = jnp.zeros((b, max_new), jnp.int32)
            # done carries ACROSS segments (decode_segments threads it):
            # rows already at eos / their row budget skip the whole
            # segment (cond false when all are), instead of decoding
            # trimmed-away garbage — and the pipelined speculative
            # segment after an all-done one costs microseconds.
            done = done0
            eos = jnp.int32(self.tokenizer.eos_id)

            def cond(state):
                step, _, _, done, _, _, _ = state
                return (step < max_new) & (step < budget) & ~jnp.all(done)

            def body(state):
                step, last, valid, done, out, caches, key = state
                if pass_active:
                    # A recurrent state must consume exactly the tokens
                    # whose successors are real: not a finished row's
                    # filler, not the step past a row's budget.
                    logits, caches = step_fn(
                        last, valid, caches,
                        ~done & (step < row_budgets))
                else:
                    logits, caches = step_fn(last, valid, caches)
                key, sub = jax.random.split(key)
                row_logits = logits[:, 0].astype(jnp.float32)
                if greedy:
                    nxt = jnp.argmax(row_logits, axis=-1).astype(jnp.int32)
                else:
                    nxt = sample_token_batch(
                        row_logits, sub, temps, top_ks,
                        top_ps).astype(jnp.int32)
                nxt = jnp.where(done | (step >= row_budgets), eos, nxt)
                out = out.at[:, step].set(nxt)
                new_done = done | (nxt == eos)
                valid = jnp.where(done, valid, valid + 1)
                return step + 1, nxt, valid, new_done, out, caches, key

            state = (jnp.int32(0), first_token, start_valid, done, out,
                     caches, key)
            with spmd_mesh(mesh, int4_sink=self._int4_dispatches), \
                    self._lora_scope(lora):
                step, last, valid, done, out, caches, _ = \
                    jax.lax.while_loop(cond, body, state)
            step, last, valid, done, out = host_read(
                step, last, valid, done, out)
            return out, step, last, valid, done, caches

        def decode_inputs(layout, buf, carry, lora):
            """A decode program's head: the packed buffer cut apart
            (dispatch_pack.decode_layout), the rows' state taken from
            `carry` — what the segment before handed on, on the device
            — where the word `carried` says so and from the buffer on
            a first segment, and the lora pair the scope takes.
            -> (fields, (last, valid, done, budgets), lora)."""
            f = layout.unpack(buf)
            state = tuple(
                jnp.where(f["carried"], c, f[name])
                for c, (name, _kind) in zip(carry, dispatch_pack.CARRY))
            if lora is not None:
                lora = (lora, f["lora_ids"])
            return f, state, lora

        def budgets_left(budgets, step):
            # (what `scheduler._advance` computed in a program of its
            # own: the rows' budgets after this segment's steps)
            return host_read(jnp.maximum(budgets - step, 0))

        def cached_step(params):
            """step_fn over the position-aligned [B, S, K, D] gather
            view."""
            def step(last, valid, caches_b):
                return forward(params, cfg, last[:, None], valid[:, None],
                               caches_b, valid, valid + 1)
            return step

        # --- the step programs ---
        # Gather view: pool[table] materializes a position-aligned
        # [B, S, K, D] view of the batch's pages, which forward() and
        # the flash kernels read as a plain cache; the
        # updated view scatters back through the same table. Aliased
        # (shared-prefix) pages are never in any row's write range
        # (ensure_capacity copy-on-writes them), so duplicate-index
        # scatters only ever rewrite identical bytes.
        # Decode: POOL-DIRECT where supported — the page-table-aware
        # kernel reads only pages below each row's frontier and the
        # gather view (every row's whole max_seq_len span, copied out
        # of the pool and back) is never built
        # (engine/paged_forward.py). On multi-device meshes the kernel
        # runs under shard_map (kv heads on "model", matching the pool's
        # sharding; pallas.paged_decode_spmd); head layouts that don't
        # partition keep the gather view.
        self.paged_direct = False
        self.paged_degraded_reason: Optional[str] = None
        self._paged_replicas = 1
        from .pallas.attention import (paged_pool_direct_supported,
                                       spmd_partitionable)
        # attn="dense" is an explicit opt-out of every Pallas kernel
        # (the _resolve_attn contract) — the pool-direct decode IS a
        # Pallas kernel, so it honors the same switch. "auto" still
        # takes pool-direct even where auto resolves the view path to
        # dense (CPU): there is no dense pool-direct equivalent, and
        # the kernel runs in interpret mode there.
        n_model = dict(self.mesh.shape).get("model", 1)
        # data > 1 (VERDICT r4 #4): the pool's page axis is
        # data-sharded and the spmd kernel shards BATCH rows over
        # "data" — generate_batch groups rows by their slot's
        # replica (ReplicaGroupPlan) so each shard_map block reads
        # only its local pages; the kernels rebase tables to the
        # local range via axis_index. No gather view on any mesh.
        kh_l = model_cfg.page_heads
        if self.mesh.devices.size > 1 and kh_l % max(n_model, 1) == 0:
            kh_l //= max(n_model, 1)   # kernel sees the local shard
        group = model_cfg.num_heads // model_cfg.page_heads
        # Every geometry the attention layers have (one, but for a
        # model with attn_layers) must fit both kernels.
        groups = sorted({h // model_cfg.page_heads for h, _w, _n
                         in model_cfg.attention_classes})
        self.paged_direct = (
            attn != "dense"
            and all(paged_pool_direct_supported(
                MAX_PREFILL_CHUNK, page_size, model_cfg.page_width,
                kh_l, g) for g in groups)
            and (self.mesh.devices.size == 1
                 or spmd_partitionable(model_cfg.num_heads,
                                       model_cfg.num_kv_heads,
                                       n_model)))
        # Quantized pages (ISSUE 11): can the Pallas kernels
        # dequantize this pool shape IN-KERNEL? A decline (int4
        # packing/grouping on this head_dim) routes serving to the
        # XLA dequant paths — gather view for the batched
        # programs — with the machine-readable reason recorded,
        # the int4mm plan/decline discipline: no dispatch can
        # reach a Mosaic failure on chip.
        if self.kv_quant_spec is not None:
            from .pallas.attention import kv_quant_decline_reason
            self.kv_quant_fallback_reason = kv_quant_decline_reason(
                page_size, model_cfg.head_dim, kh_l, group,
                self.kv_quant_spec.bits, self.kv_quant_spec.group)
            if (self.kv_quant_fallback_reason is not None
                    and self.paged_direct):
                self.paged_direct = False
                self.paged_degraded_reason = (
                    f"kv_quant:{self.kv_quant_fallback_reason}")
        self._paged_replicas = data_size if self.paged_direct else 1
        n_pages_seq = self.max_seq_len // page_size
        _kvq_spec = self.kv_quant_spec
        _n_layers = model_cfg.num_layers
        from .kv_quant import (dequantize_cells as _kvq_deq,
                               quantize_cells as _kvq_q,
                               split_combined as _kvq_split)

        def gather_view(combined, tables, b):
            # Combined pools (+ scales when quantized) -> the
            # position-aligned bf16 [B, S, K, D] view forward()
            # consumes — quantized pools dequantize AT THE GATHER
            # (kv_quant.dequantize_cells, the XLA read seam).
            pools, scales = _kvq_split(combined, _n_layers)
            caches_b = []
            for li, (k_pool, v_pool) in enumerate(pools):
                if scales is not None:
                    ks, vs = scales[li]
                    kb = _kvq_deq(k_pool[tables], ks[tables],
                                  _kvq_spec, dtype)
                    vb = _kvq_deq(v_pool[tables], vs[tables],
                                  _kvq_spec, dtype)
                    tail = (k_pool.shape[2], model_cfg.head_dim)
                else:
                    kb, vb = k_pool[tables], v_pool[tables]
                    # (a pool's own cell may hold two heads a lane
                    # row: ModelConfig.lane_pack)
                    tail = (model_cfg.num_kv_heads, model_cfg.head_dim)
                caches_b.append(
                    (kb.reshape(b, n_pages_seq * page_size, *tail),
                     vb.reshape(b, n_pages_seq * page_size, *tail)))
            return caches_b

        def scatter_view(combined, tables, new_b, b):
            # The inverse write seam: the updated bf16 view
            # RE-QUANTIZES cell-by-cell before scattering back.
            # Unwritten cells round-trip exactly (requantizing a
            # dequantized cell reproduces its payload and scale —
            # the pinned stability property), so repeated
            # gather/scatter segments cannot drift.
            pools, scales = _kvq_split(combined, _n_layers)
            out_p, out_s = [], []
            for li, ((k_pool, v_pool), (nk, nv)) in enumerate(
                    zip(pools, new_b)):
                if scales is not None:
                    ks, vs = scales[li]
                    nk_q, nk_s = _kvq_q(nk, _kvq_spec)
                    nv_q, nv_s = _kvq_q(nv, _kvq_spec)
                    qtail = k_pool.shape[2:]
                    stail = ks.shape[2:]
                    out_p.append((
                        k_pool.at[tables].set(nk_q.reshape(
                            b, n_pages_seq, page_size, *qtail)),
                        v_pool.at[tables].set(nv_q.reshape(
                            b, n_pages_seq, page_size, *qtail))))
                    out_s.append((
                        ks.at[tables].set(nk_s.reshape(
                            b, n_pages_seq, page_size, *stail)),
                        vs.at[tables].set(nv_s.reshape(
                            b, n_pages_seq, page_size, *stail))))
                else:
                    tail = k_pool.shape[2:]
                    nk5 = nk.reshape(b, n_pages_seq, page_size,
                                     *tail)
                    nv5 = nv.reshape(b, n_pages_seq, page_size,
                                     *tail)
                    out_p.append((k_pool.at[tables].set(nk5),
                                  v_pool.at[tables].set(nv5)))
            return out_p + out_s

        def prefill_inputs(layout, buf, lora):
            f = layout.unpack(buf)
            if lora is not None:
                lora = (lora, f["lora_ids"])
            return (f["tables"], f["tokens"], f["offsets"], f["lengths"],
                    lora)

        @partial(jax.jit, donate_argnums=(1,), static_argnames=("layout",))
        def prefill_step_paged(params, pools, buf, layout, lora=None):
            tables, tokens, offsets, lengths, lora = prefill_inputs(
                layout, buf, lora)
            with spmd_mesh(mesh, int4_sink=self._int4_dispatches), \
                    self._lora_scope(lora):
                b, t = tokens.shape
                caches_b = gather_view(pools, tables, b)
                positions = offsets[:, None] + jnp.arange(t)[None, :]
                valid = offsets + lengths
                logits, new_b = forward(params, cfg, tokens, positions,
                                        caches_b, offsets, valid,
                                        last_pos=lengths - 1)
                new_pools = scatter_view(pools, tables, new_b, b)
                return host_read(logits[:, 0]), new_pools

        @partial(jax.jit, donate_argnums=(1,), static_argnames=("layout",))
        def prefill_step_paged_direct(params, pools, buf, layout,
                                      lora=None):
            from .paged_forward import forward_paged
            tables, tokens, offsets, lengths, lora = prefill_inputs(
                layout, buf, lora)
            with spmd_mesh(mesh, int4_sink=self._int4_dispatches), \
                    self._lora_scope(lora):
                t = tokens.shape[1]
                positions = offsets[:, None] + jnp.arange(t)[None, :]
                valid = offsets + lengths
                pools_l, scales_l = _kvq_split(pools, _n_layers)
                logits, new_pools = forward_paged(
                    params, cfg, tokens, positions, pools_l, tables,
                    valid, pool_replicas=data_size,
                    last_pos=lengths - 1,
                    scales=scales_l, quant_spec=_kvq_spec)
                return host_read(logits[:, 0]), new_pools

        # Keep BOTH compiled-closure pairs: the gather-view programs
        # are the runtime degradation target when a pool-direct
        # kernel fails on chip (_degrade_paged_direct).
        self._prefill_step_paged_gather = prefill_step_paged
        self._prefill_step_paged = (prefill_step_paged_direct
                                    if self.paged_direct
                                    else prefill_step_paged)

        @partial(jax.jit, donate_argnums=(1,),
                 static_argnames=("layout", "max_new", "greedy"))
        def decode_loop_paged(params, pools, buf, carry, key, layout,
                              max_new, greedy, lora=None):
            f, (first_token, start_valid, done0, row_budgets), lora = \
                decode_inputs(layout, buf, carry, lora)
            tables = f["tables"]
            key, sub = chain_key(key)
            b = first_token.shape[0]

            # All-done guard: skip the full gather view + scatter
            # (the view's whole-cache copy), not just the while_loop —
            # an all-done segment (the pipelined speculative dispatch's
            # discard case) would otherwise still copy the batch's KV.
            def run(pools):
                caches_b = gather_view(pools, tables, b)
                out, step, last, valid, done, caches_b = decode_while(
                    cached_step(params), caches_b, first_token,
                    start_valid, sub, f["budget"], f["temps"],
                    f["top_ks"], f["top_ps"], row_budgets, done0,
                    max_new, greedy, lora=lora)
                new_pools = scatter_view(pools, tables, caches_b, b)
                return out, step, last, valid, done, new_pools

            def skip(pools):
                return (jnp.zeros((b, max_new), jnp.int32),
                        jnp.int32(0), first_token, start_valid,
                        done0, pools)

            out, step, last, valid, done, new_pools = jax.lax.cond(
                jnp.all(done0), skip, run, pools)
            return (out, step, last, valid, done,
                    budgets_left(row_budgets, step), new_pools, key)

        @partial(jax.jit, donate_argnums=(1,),
                 static_argnames=("layout", "max_new", "greedy"))
        def decode_loop_paged_direct(params, pools, buf, carry, key,
                                     layout, max_new, greedy, lora=None):
            from .paged_forward import forward_paged
            f, (first_token, start_valid, done0, row_budgets), lora = \
                decode_inputs(layout, buf, carry, lora)
            tables = f["tables"]
            key, sub = chain_key(key)

            def step_fn(last, valid, pools):
                pools_l, scales_l = _kvq_split(pools, _n_layers)
                return forward_paged(
                    params, cfg, last[:, None], valid[:, None],
                    pools_l, tables, valid + 1,
                    pool_replicas=data_size,
                    scales=scales_l, quant_spec=_kvq_spec)

            out, step, last, valid, done, new_pools = decode_while(
                step_fn, pools, first_token, start_valid, sub,
                f["budget"], f["temps"], f["top_ks"], f["top_ps"],
                row_budgets, done0, max_new, greedy, lora=lora)
            return (out, step, last, valid, done,
                    budgets_left(row_budgets, step), new_pools, key)

        self._decode_loop_paged_gather = decode_loop_paged
        self._decode_loop_paged = (decode_loop_paged_direct
                                   if self.paged_direct
                                   else decode_loop_paged)

        @partial(jax.jit, donate_argnums=(0,))
        def scatter_kv_paged(pools, tables, new_layers):
            # Ring-prefill writeback: whole-sequence K/V [B, Tp, K, D]
            # (Tp a multiple of page_size — _prefill enforces it)
            # scattered through each row's page table. Rows' pages are
            # write-exclusive (ensure_capacity COW'd the offset-0
            # write range); table entries past a row's allocation are
            # the scratch page, which absorbs the pad-tail garbage and
            # is never read — same contract as scatter_view.
            # Quantized pools quantize-on-write here too (ISSUE 11).
            pools_l, scales_l = _kvq_split(pools, _n_layers)
            out_p, out_s = [], []
            for li, ((k_pool, v_pool), (nk, nv)) in enumerate(
                    zip(pools_l, new_layers)):
                b, t = nk.shape[0], nk.shape[1]
                n = t // page_size
                if scales_l is not None:
                    ks, vs = scales_l[li]
                    nk_q, nk_s = _kvq_q(nk.astype(dtype), _kvq_spec)
                    nv_q, nv_s = _kvq_q(nv.astype(dtype), _kvq_spec)
                    qtail = k_pool.shape[2:]
                    stail = ks.shape[2:]
                    out_p.append((
                        k_pool.at[tables[:, :n]].set(
                            nk_q.reshape(b, n, page_size, *qtail)),
                        v_pool.at[tables[:, :n]].set(
                            nv_q.reshape(b, n, page_size, *qtail))))
                    out_s.append((
                        ks.at[tables[:, :n]].set(
                            nk_s.reshape(b, n, page_size, *stail)),
                        vs.at[tables[:, :n]].set(
                            nv_s.reshape(b, n, page_size, *stail))))
                else:
                    tail = k_pool.shape[2:]
                    nk5 = nk.reshape(b, n, page_size, *tail) \
                        .astype(k_pool.dtype)
                    nv5 = nv.reshape(b, n, page_size, *tail) \
                        .astype(v_pool.dtype)
                    out_p.append((k_pool.at[tables[:, :n]].set(nk5),
                                  v_pool.at[tables[:, :n]].set(nv5)))
            return out_p + out_s

        self._scatter_kv_paged = scatter_kv_paged

        # Cross-session prefix cache + host-RAM offload tier (ISSUE 7):
        # the cache attaches to the pool (commit-inserts, alloc-reclaims
        # ride the kv object); the tier needs the engine (mesh, compile
        # labels), so it lives here.
        self.prefix_cache = None
        self.kv_offload = None
        from .prefix_cache import PrefixCache, cache_enabled
        if cache_enabled(prefix_cache):
            self.prefix_cache = PrefixCache(
                self.kv, engine=model_cfg.name,
                max_pages=prefix_cache_pages)
            self.kv.prefix_cache = self.prefix_cache
        from .kv_offload import HostOffloadTier, offload_enabled
        if offload_enabled(kv_offload):
            self.kv_offload = HostOffloadTier(self)

        # Ragged paged attention (ISSUE 8): mixed prefill/decode in ONE
        # dispatch over a flat token buffer — the scheduler's chunk-
        # interleaved admission path. Paged pools only (the flat buffer
        # addresses pages); data-sharded pools decline (a flat buffer
        # cannot mix replicas' rows) with the reason recorded. Within an
        # enabled engine, the KERNEL path needs the pool shape + head
        # layout to fit — otherwise every ragged dispatch runs the XLA
        # fallback and records `fallback_reason`, the int4_paths
        # pattern. ROUNDTABLE_RAGGED_ATTN=0 kills the whole seam: the
        # scheduler then serves the PR-4 admission prologue unchanged.
        from collections import deque as _deque
        self.ragged_enabled = False
        self.ragged_path: Optional[str] = None
        self.ragged_reason: Optional[str] = None
        self.ragged_fallback_reason: Optional[str] = None
        self.ragged_tokens = 0
        self.ragged_shapes: tuple[int, ...] = ()
        self.ragged_defer_min = 0
        self._ragged_dispatches: dict[str, int] = {}
        self._ragged_recent = _deque(maxlen=32)
        # (page_visits, page_visits_by_eights) over every dispatch, and
        # the pool as the ragged kernel sees it (ragged_decline_reason's
        # arguments)
        self._ragged_visits = [0, 0]
        self._ragged_pool_shape: tuple = ()
        self.joins_ragged_alone = False
        # ... and what every segment's attention read by layer class,
        # for a model whose attention layers differ (attn_layers)
        self._window_reads = {"page_visits_full": 0,
                              "page_visits_window": 0,
                              "pages_held": 0, "pages_behind_window": 0}
        from .prefix_cache import env_flag
        from .pallas import attention as _pattn
        from .serving_loop import ragged_token_budget
        # (n_model, kh_l, group: the local pool shape the kernels see,
        # as the step programs above took it)
        if not env_flag(ragged_attn, "ROUNDTABLE_RAGGED_ATTN"):
            self.ragged_reason = "disabled:config/env"
        elif dict(self.mesh.shape).get("data", 1) > 1:
            # The pool's page axis shards over "data" on these
            # meshes; a flat buffer mixing replicas' rows cannot.
            self.ragged_reason = "mesh:data-axis"
        else:
            from .serving_loop import (ragged_defer_min,
                                       ragged_shape_grid)
            self.ragged_enabled = True
            self.ragged_tokens = ragged_token_budget(
                num_slots, int(ragged_tokens or 0),
                hybrid=model_cfg.layer_kinds is not None)
            self.ragged_shapes = ragged_shape_grid(self.ragged_tokens)
            self.ragged_defer_min = ragged_defer_min()
            if attn == "dense":
                decline = "attn=dense"
            elif (self.mesh.devices.size > 1
                  and not _pattn.spmd_partitionable(
                      model_cfg.num_heads, model_cfg.num_kv_heads,
                      n_model)):
                decline = "heads:model-axis"
            else:
                self._ragged_pool_shape = (
                    (page_size, model_cfg.page_width, kh_l, group),
                    dict(dv=(model_cfg.kv_lora_rank if model_cfg.latent
                             else model_cfg.head_dim),
                         itemsize=(1 if self.kv_quant_spec is not None
                                   else jnp.dtype(dtype).itemsize),
                         q_itemsize=jnp.dtype(dtype).itemsize,
                         latent=model_cfg.latent,
                         quantized=self.kv_quant_spec is not None))
                # ... and every other geometry the attention
                # layers have: the first that declines decides.
                decline = next(
                    (r for r in (
                        _pattn.ragged_decline_reason(
                            *self._ragged_class_shape(h),
                            **self._ragged_pool_shape[1])
                        for h, _w, _n in model_cfg.attention_classes)
                     if r is not None), None)
            if (decline is None
                    and self.kv_quant_fallback_reason is not None):
                # Quantized pool the kernel cannot dequantize
                # in-kernel (ISSUE 11): ragged dispatches serve the
                # XLA dense path with the quant decline recorded.
                decline = f"kv_quant:{self.kv_quant_fallback_reason}"
            self.ragged_path = ("pallas_ragged" if decline is None
                                else "xla_ragged")
            self.ragged_fallback_reason = decline
            if (model_cfg.retention_layers or model_cfg.mamba1_layers) \
                    and decline is None:
                # State on the slot arrays: a prologue's [B, T]
                # program is the ragged program's chunked runs again
                # (the same kernel a page of a run), at a shape a
                # batch size a bucket — nothing a join gains, and a
                # compile each: 35 s of a warm set-up, more of a
                # cold one, 9.3 s inside a window at a bucket no
                # warm-up met (PERF.md, PR 42). So the scheduler's
                # joins take the ragged program whatever the batch
                # holds and however few tokens they bring; the
                # prologue serves generate_batch, which no scheduler
                # stands behind.
                self.joins_ragged_alone = True
                self.ragged_defer_min = 0
            elif model_cfg.recurrent and decline is None:
                # State beside pages (Mamba-2): the prologue stays for
                # a join that brings little, but only a deferred
                # admission can hand a leader's state to its laggards
                # (kvcache.share_prefixes) — a round admitted into an
                # empty batch by the prologue scans its shared span
                # three times. So a join may take the ragged program
                # whatever the batch holds; whether it does is decided
                # after the state plan (_plan_batch: a recorded share,
                # or ragged_defer_min tokens left to scan).
                self.joins_ragged_alone = True
            if decline is not None and model_cfg.attn_layers:
                # one of the layers' geometries does not fit
                self.declines["ragged_kernel"] = decline

        @partial(jax.jit, donate_argnums=(1,),
                 static_argnames=("layout", "greedy", "attn_path",
                                  "score_width", "propose_width"))
        def ragged_step(params, pools, buf, key, layout, greedy=True,
                        attn_path="kernel", score_width=0, lora=None,
                        propose_width=0):
            # `buf`: dispatch_pack.ragged_layout — the flat buffer's
            # maps, the sequences' tables and sampling parameters and,
            # where the layout has them, the verify's score rows
            # (score_width), the tree's page-copy pairs and the tokens'
            # adapter slots.
            from .paged_forward import forward_ragged
            f = layout.unpack(buf)
            key, sub = chain_key(key)
            temps, top_ks, top_ps = f["temps"], f["top_ks"], f["top_ps"]
            if lora is not None:
                lora = (lora, f["token_adapter"])
            with spmd_mesh(mesh, int4_sink=self._int4_dispatches), \
                    self._lora_scope(lora):
                pools_l, scales_l = _kvq_split(pools, _n_layers)
                logits, new_pools = forward_ragged(
                    params, cfg,
                    f["tokens"], f["positions"], pools_l, f["tables"],
                    f["seq_of_block"], f["block_qstart"],
                    f["query_offsets"], f["kv_valid"],
                    f["token_pages"], f["token_offs"], f["token_seq"],
                    f["last_rows"],
                    attn_path=attn_path,
                    sample_rows=(f["sample_rows"] if score_width
                                 else None),
                    scales=scales_l, quant_spec=_kvq_spec,
                    copy_src=f.get("copy_src"),
                    copy_dst=f.get("copy_dst"))
                lf = logits.astype(jnp.float32)
                if score_width:
                    # Speculative verify (ISSUE 9): per-position
                    # tokens [S, R] — greedy argmax, or an exact
                    # per-position sample through the SAME
                    # sample_token_batch the decode loop uses (one
                    # categorical key draws S*R independent rows).
                    s, r, v = lf.shape
                    if greedy:
                        nxt = jnp.argmax(lf, axis=-1)
                    else:
                        nxt = sample_token_batch(
                            lf.reshape(s * r, v), sub,
                            jnp.repeat(temps, r),
                            jnp.repeat(top_ks, r),
                            jnp.repeat(top_ps, r)).reshape(s, r)
                    nxt = nxt.astype(jnp.int32)
                elif greedy:
                    nxt = jnp.argmax(lf, axis=-1).astype(jnp.int32)
                else:
                    nxt = sample_token_batch(
                        lf, sub, temps, top_ks,
                        top_ps).astype(jnp.int32)
            if propose_width:
                # Draft-model propose dispatch (ISSUE 13): alongside
                # the greedy next token, the top-`propose_width` ids
                # of each row's tip distribution seed the root
                # branches of the token tree. score_width==0 here
                # (propose batches are plain ragged dispatches), so
                # lf is [S, V].
                tops = jax.lax.top_k(
                    lf, propose_width)[1].astype(jnp.int32)
                return host_read(nxt, tops), new_pools, key
            return host_read(nxt), new_pools, key

        self._ragged_step = ragged_step

        # Recurrent state beside the pools (models/hybrid.py,
        # engine/hybrid_state.py): a model with layer_kinds serves
        # through three programs of its own that carry a second donated
        # tree — every slot's Mamba-2 state and the expert counters —
        # and, where a snapshot is taken, the snapshot store.
        self.hybrid = None
        if model_cfg.layer_kinds is not None:
            self._build_hybrid_programs(
                model_cfg, mesh, host_read, decode_while, chain_key,
                decode_inputs, budgets_left, num_slots, page_size,
                state_snapshot_bytes)

        # Speculative decoding (ISSUE 9): self-drafting verify folded
        # into the scheduler's ragged segment loop. The verify dispatch
        # IS a ragged dispatch (a draft run is a short multi-token row
        # in the flat buffer), so spec resolves ON only where the
        # ragged seam did — the scheduler then drafts per row on the
        # host and the static score_width program scores every draft
        # position in one forward. ROUNDTABLE_SPEC_DECODE=0 /
        # spec_decode: False restores 1-token decode byte-identically.
        from .spec_decode import (DEFAULT_MAX_DRAFT, BatchThrottle,
                                  SpecOptions, spec_enabled)
        self.spec_decode = False
        self.spec_reason: Optional[str] = None
        # The resolved `spec_decode:` block (ISSUE 13): dict configs
        # choose the drafter + tree shape; the PR-9 bool path resolves
        # to the ngram chain defaults. Validation raises HERE so
        # from_config and the constructor fail identically.
        self.spec_options = SpecOptions.resolve(spec_decode)
        if spec_max_draft is None and self.spec_options.max_draft \
                is not None:
            spec_max_draft = self.spec_options.max_draft
        self.spec_max_draft = (DEFAULT_MAX_DRAFT if spec_max_draft is None
                               else int(spec_max_draft))
        from .serving_loop import RAGGED_BLOCK_Q
        if not 1 <= self.spec_max_draft <= RAGGED_BLOCK_Q - 1:
            # draft+1 must fit one flat-buffer tile, so a speculating
            # batch packs exactly like a plain ragged decode batch and
            # the overflow rules stay one rule.
            raise ValueError(
                f"spec_max_draft must be 1..{RAGGED_BLOCK_Q - 1} "
                f"(verify run = drafts+1 tokens in one "
                f"{RAGGED_BLOCK_Q}-row block), got {self.spec_max_draft}")
        if (self.spec_options.tree is not None
                and self.spec_options.tree["depth"] > self.spec_max_draft):
            # Every root-to-leaf run is 1 + depth tokens and the static
            # score gather is spec_max_draft + 1 wide — a deeper tree
            # would need a new compiled width.
            raise ValueError(
                f"spec_decode tree depth {self.spec_options.tree['depth']}"
                f" exceeds spec_max_draft {self.spec_max_draft} (the "
                f"static score_width must cover every root-to-leaf run)")
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._spec_throttled = 0
        # The throttle of the rows that have no verdict of their own
        # (ISSUE 43): the scheduler's loop writes it, spec_describe
        # reads it.
        self.spec_batch = BatchThrottle(DECODE_SEGMENT)
        self._spec_dispatches = 0
        self._spec_tree_nodes = 0
        self._spec_tree_rows = 0
        # drafter kind -> [drafted, accepted] (per-proposer attribution
        # for the labeled acceptance-rate gauge).
        self._spec_by_drafter: dict[str, list[int]] = {}
        self._spec_recent = _deque(maxlen=32)
        if "spec_decode" in self.declines:
            # A rejected draft cannot be un-consumed from a recurrent
            # state: chain, tree and draft rows all decline.
            self.spec_reason = self.declines["spec_decode"]
        elif not spec_enabled(spec_decode):
            self.spec_reason = "disabled:config/env"
        elif not self.ragged_enabled:
            self.spec_reason = f"ragged:{self.ragged_reason}"
        else:
            self.spec_decode = True
        # Tree-verify statics (ISSUE 13): on a tree-configured engine
        # EVERY verify dispatch carries branch-times row capacity and a
        # fixed block of page-copy slots — how many tree rows (0
        # included) actually use them is a VALUE, so chain/tree/no-spec
        # mixes and acceptance drift never compile a new program. Chain
        # engines keep the PR-9 shapes exactly (branch 1, zero copy
        # slots — build_ragged_batch then adds no arrays at all).
        self.spec_tree = (self.spec_options.tree
                          if self.spec_decode else None)
        self.spec_branch = (self.spec_tree["branch"]
                            if self.spec_tree else 1)
        self.spec_s_max = num_slots * self.spec_branch + 1
        self.spec_copy_slots = num_slots * (self.spec_branch - 1)

        # Per-engine roofline model (ISSUE 6): streamed bytes from the
        # ACTUAL (quantized) tree + chip ceilings, published at event
        # rate by generate/scheduler seams and embedded in describe().
        from ..utils import perfmodel
        self.perf = perfmodel.EnginePerf.from_engine(self)

        # Multi-LoRA knight personas (ISSUE 10): K personas as LoRA
        # deltas over this ONE resident base. The store holds stacked
        # per-target A/B tensors whose SHAPES are config-static; every
        # serving program above takes (stacked, adapter ids) as a
        # VALUE argument, so mixed-adapter batches, hot-swaps and
        # occupancy drift compile nothing. Requires an explicit
        # `lora:` config block; ROUNDTABLE_LORA=0 restores base-only
        # serving byte-identically (the programs get lora=None and the
        # tagged _einsum sites short-circuit on the inert scope).
        from .lora import (DEFAULT_MAX_ADAPTERS, DEFAULT_RANK,
                           DEFAULT_SCALE, LoraStore, lora_enabled)
        if "lora" in self.declines:
            self.lora_reason = self.declines["lora"]
        elif not lora:
            self.lora_reason = "disabled:config"
        elif not lora_enabled(lora):
            self.lora_reason = "disabled:env"
        elif seq_parallel and seq_parallel > 1:
            # The ring prefill program has no lora seam: serving a
            # persona row through it would bake UN-lora'd K/V that
            # decode then reads — a silent parity break, so the whole
            # feature declines instead (the decline table names it).
            self.lora_reason = "seq_parallel:ring-prefill"
        else:
            lora_cfg = lora if isinstance(lora, dict) else {}
            self.lora = LoraStore(
                model_cfg, self.mesh,
                max_adapters=int(lora_cfg.get("max_adapters",
                                              DEFAULT_MAX_ADAPTERS)),
                rank=int(lora_cfg.get("rank", DEFAULT_RANK)),
                scale=float(lora_cfg.get("scale", DEFAULT_SCALE)),
                dtype=dtype,
                quant=lora_cfg.get("quant", "none"),
                adapters=lora_cfg.get("adapters"),
                targets=lora_cfg.get("targets"),
                engine_name=model_cfg.name, perf=self.perf)
            self._lora_quant = self.lora.quant

        # Drafter resolution (ISSUE 13): which proposer actually serves
        # the speculative phase. Config VALIDATION raised above; drafter
        # AVAILABILITY falls back to the ngram chain with the reason
        # recorded (the decline-table discipline) — a missing LoRA store
        # or unreadable draft checkpoint must degrade serving, never
        # kill the engine. Resolution runs AFTER the LoRA store exists
        # so the `lora` drafter can pin its adapter slot.
        self.spec_drafter = "ngram" if self.spec_decode else None
        self.spec_drafter_reason: Optional[str] = None
        self.spec_device_drafter = None
        if self.spec_decode and self.spec_options.drafter != "ngram":
            try:
                self._install_drafter(self.spec_options.drafter,
                                      adapter=self.spec_options.adapter,
                                      checkpoint=self.spec_options
                                      .draft_checkpoint)
            except Exception as e:  # noqa: BLE001 — degrade, record
                self.spec_drafter_reason = (
                    f"{self.spec_options.drafter}:{str(e)[:120]}")

    def _build_hybrid_programs(self, cfg, mesh, host_read, decode_while,
                               chain_key, decode_inputs, budgets_left,
                               num_slots, page_size,
                               state_snapshot_bytes) -> None:
        """The step programs of a model with layer_kinds, and its state
        store. Same four seams as every model (prefill_step,
        decode_loop, ragged_step; first_token is shared as is), with
        the slot states donated beside the pools."""
        from .hybrid_state import MOE_COUNTS, HybridStateStore
        from .paged_forward import (forward_paged_hybrid,
                                    forward_ragged_hybrid)
        if not self.paged_direct:
            raise ValueError(
                f"{cfg.name} has layer_kinds: it serves pool-direct only "
                "(attn must not be 'dense', and the page and head "
                "shapes must suit the paged kernels)")
        if state_snapshot_bytes is None:
            # Default: room for four snapshots a slot.
            from .models.hybrid import state_bytes_per_sequence
            state_snapshot_bytes = (4 * num_slots
                                    * state_bytes_per_sequence(cfg,
                                                               self.dtype))
        from . import compile_watch
        with compile_watch.phase("pools"):
            self.hybrid = HybridStateStore(
                cfg, num_slots, page_size, state_snapshot_bytes,
                engine=cfg.name, dtype=self.dtype)
        from .models.hybrid import ROW_PARTS

        # ROW_PARTS are gathered to the batch's rows and scattered back;
        # the others (hybrid.SLOT_PARTS) ride whole: their layers
        # address the slot array by row and write captures into the
        # store themselves.
        def rows_of(state, rows):
            return {p: [a[rows] for a in v] if p in ROW_PARTS else v
                    for p, v in state.items()}

        def put_rows(state, rows, new):
            return {p: [a.at[rows].set(n) for a, n in zip(v, new[p])]
                    if p in ROW_PARTS else new[p]
                    for p, v in state.items()}

        def put_snaps(snaps, idx, cap):
            return {p: [s.at[idx].set(c) for s, c in zip(v, cap[p])]
                    if p in ROW_PARTS else cap[p]
                    for p, v in snaps.items()}

        @partial(jax.jit, donate_argnums=(1, 2, 3),
                 static_argnames=("layout",))
        def prefill_step_hybrid(params, pools, state, snaps, buf, layout):
            # `buf` (dispatch_pack.prefill_layout, hybrid): beside the
            # chunk, rows [B]: each batch row's state row (pads:
            # scratch); cap_len / snap_idx [B]: the snapshot this chunk
            # yields (0 / the scratch snapshot: none).
            f = layout.unpack(buf)
            tables, tokens, offsets, lengths = (
                f["tables"], f["tokens"], f["offsets"], f["lengths"])
            rows, cap_len, snap_idx = f["rows"], f["cap_len"], f["snap_idx"]
            with spmd_mesh(mesh):
                t = tokens.shape[1]
                positions = offsets[:, None] + jnp.arange(t)[None, :]
                logits, new_pools, new, cap, counts = forward_paged_hybrid(
                    params, cfg, tokens, positions, pools, tables,
                    offsets + lengths, rows_of(state, rows),
                    lengths=lengths, cap_len=cap_len,
                    last_pos=jnp.maximum(lengths - 1, 0),
                    page_size=page_size, rows=rows, snaps=snaps,
                    snap_idx=snap_idx)
                return (host_read(logits[:, 0]), new_pools,
                        put_rows(state, rows, new),
                        put_snaps(snaps, snap_idx, cap), host_read(counts))

        self._prefill_step_hybrid = prefill_step_hybrid

        @partial(jax.jit, donate_argnums=(1, 2),
                 static_argnames=("layout", "max_new", "greedy"))
        def decode_loop_hybrid(params, pools, state, buf, carry, key,
                               layout, max_new, greedy):
            # The rows' states are gathered once, carried through the
            # loop in batch order, and scattered back once.
            f, (first_token, start_valid, done0, row_budgets), _ = \
                decode_inputs(layout, buf, carry, None)
            tables, rows = f["tables"], f["rows"]
            key, sub = chain_key(key)

            def step_fn(last, valid, caches, active):
                pools_c, st, counts = caches
                logits, pools_c, st, _cap, c = forward_paged_hybrid(
                    params, cfg, last[:, None], valid[:, None], pools_c,
                    tables, valid + 1, st, active=active,
                    page_size=page_size, rows=rows)
                return logits, (pools_c, st, counts + c)

            out, step, last, valid, done, caches = decode_while(
                step_fn, (pools, rows_of(state, rows),
                          jnp.zeros((len(MOE_COUNTS),), jnp.int32)),
                first_token, start_valid, sub, f["budget"], f["temps"],
                f["top_ks"], f["top_ps"], row_budgets, done0, max_new,
                greedy, pass_active=True)
            new_pools, st, counts = caches
            return (out, step, last, valid, done,
                    budgets_left(row_budgets, step), new_pools,
                    put_rows(state, rows, st), host_read(counts), key)

        self._decode_loop_hybrid = decode_loop_hybrid

        @partial(jax.jit, donate_argnums=(1, 2, 3),
                 static_argnames=("layout", "greedy", "attn_path"))
        def ragged_step_hybrid(params, pools, state, snaps, buf, key,
                               layout, greedy=True, attn_path="kernel"):
            # `buf` (dispatch_pack.ragged_layout, hybrid): beside the
            # flat buffer's maps, each sequence's state row and the
            # snapshot its run leaves.
            f = layout.unpack(buf)
            key, sub = chain_key(key)
            snap_idx = f["snap_idx"]
            with spmd_mesh(mesh):
                logits, new_pools, new, cap, counts = \
                    forward_ragged_hybrid(
                        params, cfg, f["tokens"], f["positions"], pools,
                        f["tables"], f["seq_of_block"], f["block_qstart"],
                        f["query_offsets"], f["kv_valid"],
                        f["token_pages"], f["token_offs"], f["token_seq"],
                        f["last_rows"], state, f["seq_slot"], f["cap_n"],
                        attn_path=attn_path, page_size=page_size,
                        snaps=snaps, snap_idx=snap_idx)
                lf = logits.astype(jnp.float32)
                if greedy:
                    nxt = jnp.argmax(lf, axis=-1).astype(jnp.int32)
                else:
                    nxt = sample_token_batch(
                        lf, sub, f["temps"], f["top_ks"],
                        f["top_ps"]).astype(jnp.int32)
            return (host_read(nxt), new_pools, new,
                    put_snaps(snaps, snap_idx, cap), host_read(counts),
                    key)

        self._ragged_step_hybrid = ragged_step_hybrid
        if self.prefix_cache is not None and cfg.recurrent:
            self.prefix_cache.state_store = self.hybrid

    def _install_drafter(self, kind: str, adapter: Optional[str] = None,
                         checkpoint: Optional[str] = None) -> None:
        """Build (or hot-swap to) the `kind` drafter. Drafting is pure
        VALUES through already-compiled programs — a draft-model params
        override shares the engine pytree shapes, a LoRA draft head is
        one more slot in the stacked store — so steady-state swaps
        compile nothing (the STRICT acceptance line). Raises when the
        drafter's dependency is missing; callers record the reason and
        keep the ngram chain."""
        from .spec_decode import DRAFTER_KINDS, DeviceDrafter
        if kind not in DRAFTER_KINDS:
            raise ValueError(
                f"drafter must be one of {DRAFTER_KINDS}, got {kind!r}")
        if kind == "ngram":
            self.spec_device_drafter = None
            self.spec_drafter = "ngram"
            self.spec_drafter_reason = None
            return
        if kind == "model":
            draft_params = None
            if checkpoint:
                draft_params = self._load_draft_params(checkpoint)
            self.spec_device_drafter = DeviceDrafter(
                "model", params=draft_params)
        else:  # lora
            if self.lora is None:
                raise RuntimeError(
                    f"lora drafter needs a `lora:` store "
                    f"({self.lora_reason or 'disabled:config'})")
            if not adapter:
                raise ValueError("lora drafter needs an adapter name")
            if not self.lora.resolvable(adapter):
                self.lora.register(adapter)
            # Residency ref held for the drafter's lifetime (swap to a
            # different drafter releases it) — the draft head must not
            # be LRU-evicted under an in-flight propose dispatch.
            slot = self.lora.acquire([adapter])[0]
            self.spec_device_drafter = DeviceDrafter(
                "lora", adapter_slot=slot)
            self.spec_device_drafter.adapter_id = adapter
        self.spec_drafter = kind
        self.spec_drafter_reason = None

    def set_spec_drafter(self, kind: str,
                         adapter: Optional[str] = None,
                         checkpoint: Optional[str] = None) -> None:
        """Hot-swap the active drafter per workload (ISSUE 13: drafting
        as an adapter). Values-only — no program recompiles; the old
        LoRA draft head's residency ref releases so the store can evict
        it. Raises (state unchanged) when the new drafter's dependency
        is missing or speculation is off on this engine."""
        if not self.spec_decode:
            raise RuntimeError(
                f"spec_decode is off on this engine ({self.spec_reason})")
        old = self.spec_device_drafter
        self._install_drafter(kind, adapter=adapter, checkpoint=checkpoint)
        if old is not None and old is not self.spec_device_drafter:
            if (old.kind == "lora" and self.lora is not None
                    and getattr(old, "adapter_id", None)):
                self.lora.release([old.adapter_id])
            # The outgoing device drafter's shadow slots die with it:
            # _drop_request only releases draft slots while a device
            # drafter is INSTALLED, so swapping away would otherwise
            # orphan every live row's draft pages until slot-pressure
            # eviction (free-list depletion degrades tree verify and
            # shrinks prefix-cache capacity meanwhile).
            self._release_draft_slots()

    def _release_draft_slots(self) -> None:
        """Release every shadow draft slot in the paged pool (hot-swap
        away from a device drafter; the per-row path at retire is the
        scheduler's _drop_request)."""
        from .spec_decode import DRAFT_SCOPE
        for name in list(self.kv._slots):
            if name.startswith(DRAFT_SCOPE):
                self.kv.release(name)

    def _load_draft_params(self, checkpoint: str):
        """Load + shard (+ quantize, matching the engine) a draft
        checkpoint onto the SAME ModelConfig shapes — the `params`
        override must be pytree-identical to self.params or the shared
        ragged program would retrace."""
        from .checkpoint import load_hf_checkpoint
        params = load_hf_checkpoint(checkpoint, self.cfg, self.dtype)
        from .sharding import shard_params
        params = shard_params(params, self.cfg, self.mesh)
        if self.quant in ("int8", "int4"):
            from .quant import quantize_params
            from .sharding import model_axis_size
            params = quantize_params(
                params, self.cfg, act_dtype=self.dtype,
                free_source=True, bits=8 if self.quant == "int8" else 4,
                model_shards=model_axis_size(self.mesh))
        return params

    @staticmethod
    def _resolve_attn(model_cfg: ModelConfig, attn: str,
                      mesh) -> ModelConfig:
        """Pick the attention implementation (SURVEY.md §7.3 hard part 1).

        "auto" enables the Pallas kernels on TPU with a lane-aligned
        page width (head_dim; a latent entry in whole lane rows). On a
        multi-device mesh they run under shard_map with kv
        heads partitioned on the "model" axis (pallas/attention.py
        flash_attention_spmd), which requires both head counts to divide
        the model-axis size — otherwise auto stays dense (matching
        _fallback_replicated's cache layout). Explicit "flash"/"dense"
        always wins; explicit "flash" on a non-divisible mesh raises."""
        import dataclasses
        if attn not in ("auto", "flash", "dense"):
            raise ValueError(
                f"attn must be auto|flash|dense, got {attn!r}")
        from .pallas.attention import spmd_partitionable
        n_model = dict(mesh.shape).get("model", 1)
        heads_divide = spmd_partitionable(
            model_cfg.num_heads, model_cfg.num_kv_heads, n_model)
        if attn == "flash" and mesh.devices.size > 1 and not heads_divide:
            raise ValueError(
                f"attn='flash' on a {n_model}-way model axis needs head "
                f"counts divisible by it (got H={model_cfg.num_heads}, "
                f"K={model_cfg.num_kv_heads}) — use attn='auto' or 'dense'")
        if attn in ("flash", "dense"):
            return dataclasses.replace(model_cfg, attn_impl=attn)
        if (jax.default_backend() == "tpu"
                and model_cfg.page_width % 128 == 0
                and (mesh.devices.size == 1 or heads_divide)):
            return dataclasses.replace(model_cfg, attn_impl="flash")
        return dataclasses.replace(model_cfg, attn_impl="dense")

    # --- construction from adapter config ---

    @classmethod
    def from_config(cls, config: dict[str, Any]) -> "InferenceEngine":
        # The build sees the config through ENGINE_CONFIG_KEYS only —
        # the tuple the engine cache key is made of — so a key read
        # below and missing there never arrives, instead of arriving
        # and letting two different engines share one cache entry.
        cfg = {k: config[k] for k in ENGINE_CONFIG_KEYS if k in config}
        model_cfg = resolve_model_config(cfg)
        if cfg.get("max_seq_len"):
            model_cfg = dataclasses.replace(
                model_cfg, max_seq_len=int(cfg["max_seq_len"]))
        dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
                 "float16": jnp.float16}[cfg.get("dtype", "bfloat16")]
        sampling_cfg = cfg.get("sampling", {})
        sampling = SamplingParams(
            temperature=float(sampling_cfg.get("temperature", 0.7)),
            top_k=int(sampling_cfg.get("top_k", 0)),
            top_p=float(sampling_cfg.get("top_p", 1.0)),
            max_new_tokens=int(sampling_cfg.get("max_new_tokens", 1024)),
        )
        engine = cls(
            model_cfg,
            checkpoint=cfg.get("checkpoint", "") or "",
            mesh_shape=cfg.get("mesh"),
            num_slots=int(cfg.get("num_slots", 8)),
            dtype=dtype,
            sampling=sampling,
            seed=int(cfg.get("seed", 0)),
            seq_parallel=int(cfg.get("seq_parallel", 0)),
            long_threshold=int(cfg.get("long_threshold", 2048)),
            long_scheme=cfg.get("long_scheme", "ring"),
            attn=cfg.get("attn", "auto"),
            devices=cfg.get("devices"),
            kv_layout=cfg.get("kv_layout", "paged"),
            page_size=int(cfg.get("page_size", 128)),
            num_pages=(int(cfg["num_pages"])
                       if cfg.get("num_pages") else None),
            quant=cfg.get("quant", "none"),
            dcn_axis=cfg.get("dcn_axis"),
            prefix_cache=cfg.get("prefix_cache"),
            prefix_cache_pages=(int(cfg["prefix_cache_pages"])
                                if cfg.get("prefix_cache_pages")
                                else None),
            kv_offload=cfg.get("kv_offload"),
            ragged_attn=cfg.get("ragged_attn"),
            ragged_tokens=cfg.get("ragged_tokens"),
            spec_decode=cfg.get("spec_decode"),
            # `is not None`, not truthiness: spec_max_draft: 0 must
            # surface the constructor's ValueError, not silently run
            # with the default.
            spec_max_draft=(int(cfg["spec_max_draft"])
                            if cfg.get("spec_max_draft") is not None
                            else None),
            lora=cfg.get("lora"),
            kv_quant=cfg.get("kv_quant"),
            state_snapshot_bytes=(int(cfg["state_snapshot_bytes"])
                                  if cfg.get("state_snapshot_bytes")
                                  is not None else None),
        )
        # Set by fleet.check_fleet_fits when it flips an unpinned config
        # to int8: surfaced via describe() so the degrade is visible
        # after the fact, not only in the warning stream (advisor r3).
        engine.quant_auto_degraded = bool(
            config.get("_quant_auto_degraded"))
        # Rebuild recipe (ISSUE 12): the supervisor reconstructs a dead
        # engine from exactly this config — captured here so engines
        # built outside the get_engine cache (tests, benches) are
        # supervisable too.
        engine._engine_config = dict(config)
        if engine.ragged_enabled:
            # A model with recurrent state compiles its ragged grid at
            # build: a join re-scans from where its state stands, so
            # which flat-buffer shape it takes depends on the batch's
            # composition, and traffic alone may first meet one of the
            # shapes long after start-up (a 21 s compile in the window:
            # my chip run, PR 27). A plain decoder's joins compile as
            # traffic meets them — until its grid has TWO shapes from
            # the floor up: which of them a burst's want picks is then
            # the traffic's chance (ragged_pick_shape), warm-up traffic
            # may meet one and the window the other (an 18-21 s compile
            # in the window, three runs of calls 2 and 3: my chip runs,
            # PR 57), so its join program is warmed here too — alone and
            # in the engine's own mode: the whole warm-up is eleven
            # programs more than traffic compiles, 1.2 s each warm and
            # 20 s cold on Mistral's cell (PERF.md, Findings PR 57).
            from . import compile_watch
            from .serving_loop import RAGGED_FLOOR_TOKENS
            whole = engine.hybrid is not None
            if whole or engine.ragged_shapes[-1] > RAGGED_FLOOR_TOKENS:
                with compile_watch.phase("warm_programs"):
                    engine._warm_ragged(whole=whole)
        if "dispatch_retries" in config:
            from .faults import RetryPolicy
            engine.retry = RetryPolicy(
                max_retries=max(0, int(config["dispatch_retries"])))
        return engine

    # --- serving ---

    def warmup(self, max_prompt_tokens: int = MAX_PREFILL_CHUNK,
               batch_sizes: tuple[int, ...] = (1,)) -> float:
        """Compile-and-stabilize every serving program.

        Each (batch, bucket) prefill program and the decode segment are run
        TWICE: the first run compiles, but its donated cache outputs come
        back in XLA's preferred layout — different from the fresh
        jnp.zeros layout — so the very next serving call would recompile
        (~seconds). The second run reaches the layout fixpoint, making
        steady-state serving dispatch ~1ms. Returns seconds spent.
        """
        t0 = time.monotonic()
        # Warming is ALWAYS a sanctioned compile phase: reopen this
        # label first, so a second same-model engine's warmup (the
        # sentinel label is the model name — warmup_cmd loops engines
        # in one process) or a deliberate re-warm never counts its own
        # compiles as steady-state violations.
        from . import compile_watch
        compile_watch.reopen_warmup(self.cfg.name)
        # (a phase of the set-up table; warmup_complete below ends it)
        compile_watch.phase("warm_programs").begin()
        # Warm the adapter store's slot setters FIRST (ISSUE 10): a
        # steady-state hot-swap must compile nothing under STRICT, and
        # the serving warms below should trace against setter-produced
        # stacked layouts — exactly what steady-state swaps feed them.
        if self.lora is not None:
            self.lora.warm()
        if self.paged_direct and self._paged_replicas > 1:
            # Replica-grouped padding makes the device batch shape
            # R * max(group) — a function of batch COMPOSITION, not just
            # size: a k-row batch skewed onto one replica pads to R*k
            # even though a balanced one pads to R*ceil(k/R). Warm every
            # reachable padded shape via balanced batches of that size
            # (acquire keeps per-replica slot counts within ceil(S/R),
            # bounding the worst-case group), so no composition compiles
            # mid-serve.
            R = self._paged_replicas
            cap = -(-self.kv.num_slots // R)
            sizes = set(batch_sizes)
            for k in tuple(sizes):
                for g in range(1, min(k, cap) + 1):
                    # The balanced warm batch producing padded shape R*g
                    # is R*g rows — capped at num_slots, whose balanced
                    # composition (groups of ceil(S/R) = g for g == cap)
                    # still pads to R*g.
                    sizes.add(min(R * g, self.kv.num_slots))
            batch_sizes = tuple(sorted(sizes))
        limit = min(max_prompt_tokens,
                    self.max_seq_len - DECODE_SEGMENT - 1)
        # Warm the CHUNKED programs with the ring path disabled — with
        # seq_parallel on, warmup's offset-0 long runs would otherwise be
        # hijacked by the ring program and delta prefills (offset>0, long
        # suffix) would hit an unwarmed chunked bucket mid-serve.
        ring_fn, self._ring_prefill_fn = self._ring_prefill_fn, None
        try:
            for b in batch_sizes:
                if b > self.kv.num_slots:
                    continue
                # The pool (default: half of every slot at full length)
                # can't pin every batch size at the full prompt limit —
                # cap the warm length at what it can hold, exactly like real
                # serving: prompts past the cap exhaust the pool at THIS
                # batch size anyway, so their buckets are unreachable and
                # need no warming.
                limit_b = min(limit, self._warm_prompt_cap(b))
                if limit_b < 2:
                    continue
                buckets = [x for x in PREFILL_BUCKETS
                           if x <= _bucket(limit_b)]
                for bucket in buckets:
                    n = min(bucket, limit_b)  # lands exactly in `bucket`
                    # Rows diverge at position 1 so cross-slot prefix
                    # sharing can't collapse the batch — warmup must
                    # compile the REAL (b, bucket) prefill programs.
                    turns = [(f"__warmup_{i}",
                              [self.tokenizer.bos_id] + [5 + i] * (n - 1))
                             for i in range(b)]
                    for _ in range(2):
                        self._release_warm_slots()
                        self.generate_batch(turns, max_new_tokens=1)
        finally:
            self._ring_prefill_fn = ring_fn

        # Ring programs are whole-prompt-sized, so their buckets run up to
        # the cache cap (not max_prompt_tokens): threshold, 2×, ... cap.
        ring_limit = self.max_seq_len - DECODE_SEGMENT - 1
        if ring_fn is not None and ring_limit >= self.long_threshold:
            for b in batch_sizes:
                if b > self.kv.num_slots:
                    continue
                cap_b = min(ring_limit, self._warm_prompt_cap(b))
                if cap_b < self.long_threshold:
                    continue
                length = self.long_threshold
                while True:
                    n = min(length, cap_b)
                    turns = [(f"__warmup_{i}",
                              [self.tokenizer.bos_id] + [5 + i] * (n - 1))
                             for i in range(b)]
                    for _ in range(2):
                        self._release_warm_slots()
                        self.generate_batch(turns, max_new_tokens=1)
                    if length >= cap_b:
                        break
                    length *= 2
        # Warm the shared-prefix path (the leader's span prefill, the
        # laggards' alias and boundary-page copy) and the layout fixpoint
        # of the prefill/decode programs that run right after it —
        # otherwise the first real round with a shared preamble compiles
        # mid-serve.
        if (self.kv.num_slots >= 2
                and min(limit, self._warm_prompt_cap(2))
                > MIN_SHARED_PREFIX + 8):
            shared = [self.tokenizer.bos_id] + [7] * (MIN_SHARED_PREFIX + 4)
            turns = [(f"__warmup_{i}", shared + [9 + i] * 4)
                     for i in range(2)]
            for _ in range(2):
                self._release_warm_slots()
                self.generate_batch(turns, max_new_tokens=1)
        self._release_warm_slots()
        # Warm the ragged mixed-dispatch program (ISSUE 8): ONE compiled
        # shape per (budget, sampling mode) serves every prefill/decode
        # composition, so two dispatches reach its layout fixpoint and
        # scheduler joins compile nothing in steady state.
        if self.ragged_enabled:
            self._warm_ragged()
        # Every width of the page copier (ISSUE 38): the queue's first
        # long flush must not be the one that compiles it.
        self.kv.warm_copier()
        # Warm the offload tier's fetch/write programs (ONE fixed shape
        # each, ISSUE 7): a first idle-session spill/restore in steady
        # state must compile nothing under ROUNDTABLE_RECOMPILE_STRICT.
        if self.kv_offload is not None:
            self.kv_offload.warm()
        # Warmup IS this engine's steady-state declaration (ISSUE 6):
        # from here on, any compile is a recorded mid-serve recompile —
        # counted + flight-dumped always, fatal under
        # ROUNDTABLE_RECOMPILE_STRICT=1.
        from . import compile_watch
        compile_watch.warmup_complete(self.cfg.name)
        return time.monotonic() - t0

    @roomy_frame
    def _warm_ragged(self, whole: bool = True) -> None:
        """Compile-and-stabilize the ragged mixed dispatch: a two-seq
        flat buffer (one prefill chunk + one decode-shaped row) through
        the REAL _ragged_dispatch seam, twice for the donated-pool
        layout fixpoint — in the engine-default sampling mode plus
        greedy (the scheduler's parity/STRICT mode) when they differ.
        The decode-shaped row attends warm garbage; outputs are
        discarded, the compiled program is the point. Not `whole`: the
        join program alone, in the engine's own mode — what a plain
        decoder's traffic compiles anyway (`from_config`)."""
        from .serving_loop import RaggedSeq, build_ragged_batch
        names = ("__warmup_0", "__warmup_1")
        if self.kv.num_slots < 2:
            return
        self._release_warm_slots()
        pinned = names
        self.kv.ensure_capacity(names[0], 32, write_from=0,
                                pinned=pinned)
        self.kv.ensure_capacity(names[1], 16, write_from=0,
                                pinned=pinned)
        t0 = self.kv.table_for([names[0]])[0]
        t1 = self.kv.table_for([names[1]])[0]
        bos = self.tokenizer.bos_id
        own = self.sampling.temperature <= 0.0
        modes = {True, own} if whole else {own}
        for greedy in sorted(modes, reverse=True):
            temp = 0.0 if greedy else max(self.sampling.temperature, 0.1)
            seqs = [RaggedSeq([bos] + [5] * 23, 0, t0, temperature=temp),
                    RaggedSeq([7], 8, t1, temperature=temp)]
            batches = [(seqs, 0, self.kv.num_slots + 1, 0, 0)]
            if self.spec_decode and whole:
                # Speculative verify programs (ISSUE 9 + 13): ONE extra
                # compiled variant per (shape, mode) — score_width is
                # the static spec_max_draft+1 and, on a tree-configured
                # engine, s_max/copy_slots are the static branch-scaled
                # values, so acceptance drift, throttle flips AND
                # chain/tree composition changes are values in steady
                # state (chain engines: spec_s_max == num_slots+1 and
                # zero copy slots — the PR-9 program exactly).
                r = self.spec_max_draft + 1
                batches.append((
                    [RaggedSeq([7] * r, 8, t1, temperature=temp,
                               n_scores=r),
                     RaggedSeq([9], 4, t0, temperature=temp,
                               n_scores=1)], r,
                    self.spec_s_max, self.spec_copy_slots, 0))
                if self.spec_branch > 1:
                    # The propose variant (top-k root seeding) the
                    # DeviceDrafter issues under tree config — warmed
                    # whenever the tree SHAPE exists, independent of
                    # which drafter is currently installed, so a
                    # post-warmup set_spec_drafter('model'|'lora')
                    # hot-swap stays values-only (no mid-serve
                    # compile).
                    batches.append((
                        [RaggedSeq([7], 8, t1, temperature=temp),
                         RaggedSeq([9], 4, t0, temperature=temp)],
                        0, self.kv.num_slots + 1, 0, self.spec_branch))
            for warm_seqs, score_width, s_max, copy_slots, pw in batches:
                for shape in self.ragged_shapes:
                    batch = build_ragged_batch(
                        warm_seqs, t_budget=shape,
                        s_max=s_max,
                        pages_per_seq=self.kv.pages_per_seq,
                        scratch_page=self.kv.scratch_page(0),
                        pad_id=self.tokenizer.pad_id,
                        page_size=self.kv.page_size,
                        score_width=score_width,
                        copy_slots=copy_slots)
                    if pw:
                        batch["propose_width"] = pw
                    # (Both warm runs start at their slot's first or
                    # second sequence; a model with recurrent state
                    # finds the runs' states by these names.)
                    batch["seq_names"] = list(names)
                    for _ in range(2):
                        nxt = self._ragged_dispatch(batch)
                        jax.tree_util.tree_map(np.asarray, nxt)
        self._release_warm_slots()

    def _release_warm_slots(self) -> None:
        """Release every __warmup_* slot so each warm batch re-acquires
        from empty per-replica counts — the acquire balancer then spreads
        the batch ceil(b/R) per replica, which is exactly what
        _warm_prompt_cap assumes. A leftover slot from a previous warm
        stage otherwise skews the free-pages tie-break (observed: both
        rows of the shared-prefix warm pinned to one replica, exhausting
        its page range)."""
        for i in range(self.kv.num_slots):
            self.kv.release(f"__warmup_{i}")
            if self.hybrid is not None:
                self.hybrid.forget(f"__warmup_{i}")

    def _warm_prompt_cap(self, b: int) -> int:
        """Longest prompt a b-row warm batch can pin without exhausting
        the paged pool (each row pins ceil((len + DECODE_SEGMENT) /
        page_size) pages; warm slots balance over replicas, so the
        tightest replica hosts ceil(b / data) rows). Real serving past
        this length exhausts the pool at this batch size with the
        allocator's actionable RuntimeError — warming those buckets would
        crash warmup for shapes serving can never reach."""
        rows = -(-b // max(self.kv.data_size, 1))
        return ((self.kv.pages_per_replica() // max(rows, 1))
                * self.kv.page_size - DECODE_SEGMENT)

    def _lora_scope(self, lora):
        """The trace-time lora context every compiled program opens
        (engine/lora.lora_scope): inert when `lora` is None — lora-off
        engines and base-only dispatches trace exactly as before."""
        from .lora import lora_scope
        return lora_scope(lora, sink=self._lora_dispatches,
                          quant=self._lora_quant)

    def _lora_ids(self, ids):
        """One dispatch's adapter ids as the packed buffer takes them
        (the programs pair them with `lora.stacked` themselves), or
        None on lora-off engines. `ids` is per-ROW for batched programs
        and per-TOKEN for ragged dispatches; the module test counter
        records each dispatch's adapter mix for the conftest `lora`
        guard."""
        if self.lora is None:
            return None
        from . import lora as lora_mod
        ids_np = np.asarray(ids, np.int32)
        lora_mod.note_dispatch_ids(ids_np)
        return ids_np

    def _count_issue(self, **what) -> None:
        """What a step seam has just sent and issued
        (serving_loop.note_issue; one program, one buffer and one
        launch where nothing else is said)."""
        note_issue(self._dispatch_totals, self.cfg.name, **what)

    def _decode_carry(self, b: int) -> tuple:
        """A first decode segment's `carry` argument over a rows bucket
        of `b`: nothing is carried yet (the program reads the packed
        buffer: `carried` is 0), but the argument has to be there, and
        placed as a segment's own outputs are, for the first segment
        and the pipelined ones to be ONE compiled program."""
        carry = self._carry0.get(b)
        if carry is None:
            carry = self._carry0[b] = tuple(
                jax.device_put(np.zeros((b,), kind), self._replicated)
                for _name, kind in dispatch_pack.CARRY)
        return carry

    def note_lora_tokens(self, n: int) -> None:
        """Account tokens served THROUGH a persona adapter (ISSUE 10
        telemetry satellite) — bumped by the serving paths where they
        already count tokens, so the counter moves with real work."""
        if n <= 0:
            return
        self._lora_tokens += n
        from ..utils import telemetry
        telemetry.inc("roundtable_lora_apply_tokens_total", n,
                      engine=self.cfg.name)

    def lora_describe(self) -> dict[str, Any]:
        """Multi-LoRA provenance (ISSUE 10): the resolved state, the
        adapter store's residency/accounting, per-leaf routing paths
        (grouped kernel vs XLA grouped BMM, with machine-readable
        decline reasons) — embedded in describe() the way
        int4_paths/ragged/spec_decode are."""
        from .lora import summarize_lora_paths
        info: dict[str, Any] = {
            "enabled": self.lora is not None,
            "reason": self.lora_reason,
            "apply_tokens": self._lora_tokens,
            "share_suppressed": self._lora_share_suppressed,
        }
        if self.lora is not None:
            info["store"] = self.lora.describe()
            info["lora_paths"] = summarize_lora_paths(
                self._lora_dispatches)
        return info

    def int4_path_report(self) -> Optional[dict]:
        """Which path each int4 einsum dispatch COMPILED to (ISSUE 3):
        {"pallas_w4a16": [...], "xla_dequant": [{..., "fallback_reason"}]}
        keyed by (spec, shapes). Populated at trace time — warmup or the
        first serve of each (batch, bucket) shape — so bench windows can
        attribute their numbers to the kernel, not a silent fallback.
        None on non-int4 engines."""
        if self.quant != "int4":
            return None
        return summarize_int4_paths(self._int4_dispatches)

    def revive_kv_if_dead(self) -> bool:
        """Reallocate KV buffers killed by a failed donated dispatch
        (the adapter's serial-retry rung calls this so 'batched → serial'
        recovery also holds for failures that surface AFTER donation
        consumed the cache). True iff fresh buffers were allocated."""
        revived = self.kv.revive_if_dead()
        if self.hybrid is not None:
            # The slot states and the snapshot store are donated through
            # the same dispatches; either tree dead means neither the
            # pools' pages nor the states can be trusted together.
            if self.hybrid.revive_if_dead() or revived:
                self.hybrid.forget_all()
                self.hybrid.drop_all_snapshots()
                revived = True
        if revived and self.kv_offload is not None:
            # Spilled records reference pages of the DEAD pools (kept
            # shared pages) — they cannot be restored into the fresh
            # ones. Host bytes go with them: revive semantics are "all
            # cached content lost", tiers included.
            self.kv_offload.drop_all()
        return revived

    def _degrade_paged_direct(self, reason: str) -> bool:
        """Route paged serving off the pool-direct Pallas kernels onto
        the layout-agnostic gather-view programs, permanently for this
        engine. The degradation rung for a kernel that compiled-checked
        clean but fails on chip (Mosaic compile failure, VMEM overrun):
        the request in flight re-dispatches through the gather view and
        every later call skips the kernels entirely. Returns False when
        already degraded / never pool-direct (caller re-raises)."""
        if not self.paged_direct or self.hybrid is not None:
            # (A model with recurrent state has no gather-view programs
            # to degrade to: the failure surfaces to the retry ladder.)
            return False
        import warnings
        warnings.warn(
            f"paged pool-direct serving degraded to gather-view: {reason}",
            stacklevel=3)
        from ..utils import telemetry
        telemetry.inc("roundtable_degradations_total",
                      rung="gather_view")
        telemetry.recorder().record(
            "ladder_escalation", rung="gather_view",
            engine=self.cfg.name, error=reason[:200])
        self.paged_direct = False
        self.paged_degraded_reason = reason
        self._prefill_step_paged = self._prefill_step_paged_gather
        self._decode_loop_paged = self._decode_loop_paged_gather
        return True

    def _degrade_ragged(self, reason: str) -> bool:
        """Route ragged dispatches off the Pallas kernel onto the XLA
        fallback path, permanently for this engine — the same rung as
        _degrade_paged_direct for a kernel that compile-checked clean
        but fails on chip. Returns False when already on the fallback
        (caller re-raises)."""
        if self.ragged_path != "pallas_ragged":
            return False
        import warnings
        warnings.warn(
            f"ragged paged attention degraded to XLA fallback: {reason}",
            stacklevel=3)
        from ..utils import telemetry
        telemetry.inc("roundtable_degradations_total",
                      rung="ragged_xla")
        telemetry.recorder().record(
            "ladder_escalation", rung="ragged_xla",
            engine=self.cfg.name, error=reason[:200])
        self.ragged_path = "xla_ragged"
        self.ragged_fallback_reason = f"degraded:{reason[:120]}"
        return True

    # --- the step seams of a model with recurrent state -------------------

    def _live_slots(self) -> set:
        return set(self.kv.slot_names())

    def _hybrid_prefill(self, tables, chunk, offs, takes, rows):
        """One prefill chunk: the rows' states advance with it, and the
        last page boundary each row crosses leaves a snapshot."""
        hy = self.hybrid
        b = chunk.shape[0]
        row_name = hy.names_by_row()
        cap_len = np.zeros((b,), np.int32)
        snap_idx = np.full((b,), hy.scratch_snap, np.int32)
        rows_np = np.full((b,), hy.scratch_row, np.int32)
        keys = []
        for i in range(b):
            row = rows[i] if i < len(rows) else None
            if row is None or row < 0 or not takes[i]:
                continue
            rows_np[i] = row
            cap_len[i], snap_idx[i], key = hy.capture_slot(
                row_name[row], int(offs[i]), int(takes[i]))
            keys.append(key)
        layout = dispatch_pack.prefill_layout(
            b, chunk.shape[1], tables.shape[1], hybrid=True)
        try:
            last, pools, state, snaps, counts = self._prefill_step_hybrid(
                self.params, self.kv.combined_pools(), hy.state, hy.snaps,
                layout.pack({"tables": tables, "tokens": chunk,
                             "offsets": offs, "lengths": takes,
                             "rows": rows_np, "cap_len": cap_len,
                             "snap_idx": snap_idx}), layout=layout)
            self._count_issue()
        except Exception:
            for key in keys:
                hy.drop(key, unwritten=True)
            raise
        with deadlines.commit_guard():
            hy.commit_state(state)
            hy.commit_snaps(snaps)
        hy.note_counts(counts, pipelined=False)
        live = [i for i in range(b) if rows_np[i] != hy.scratch_row]
        fed = sum(int(takes[i]) for i in live)
        hy.note_scan(fed)
        hy.note_join(fed, len(live))
        hy.note_shared_reads(fed + sum(int(offs[i]) for i in live))
        return last, pools

    def _hybrid_decode(self, fields, carry, names, max_new, greedy):
        hy = self.hybrid
        if names is None:
            raise ValueError(
                f"{self.cfg.name} keeps recurrent state: a decode "
                "dispatch needs the rows' slot names")
        b, pps = fields["tables"].shape
        layout = dispatch_pack.decode_layout(b, pps, rows=True)
        rows = hy.rows_for(list(names), b, self._live_slots())
        out, steps, l2, v2, d2, left, pools, state, counts, key = \
            self._decode_loop_hybrid(
                self.params, self.kv.combined_pools(), hy.state,
                layout.pack(dict(fields, rows=rows)), carry, self._keys,
                layout=layout, max_new=max_new, greedy=greedy)
        with deadlines.commit_guard():
            hy.commit_state(state)
        hy.note_counts(counts, pipelined=True)
        return out, steps, l2, v2, d2, left, pools, key

    def _hybrid_ragged(self, batch: dict, path: str):
        """One ragged dispatch: every sequence's run advances its slot's
        state; a joining run that crosses a page boundary leaves a
        snapshot at the last one."""
        hy = self.hybrid
        names = batch.get("seq_names")
        if names is None:
            raise ValueError(
                f"{self.cfg.name} keeps recurrent state: a ragged batch "
                "needs `seq_names` (the slot of every sequence)")
        if int(batch.get("score_width", 0) or 0) \
                or batch.get("copy_src") is not None:
            raise ValueError("speculative verify is declined for a model "
                             "with recurrent state")
        s_max = batch["tables"].shape[0]
        seq_slot = hy.rows_for(list(names), s_max, self._live_slots())
        cap_n = np.zeros((s_max,), np.int32)
        snap_idx = np.full((s_max,), hy.scratch_snap, np.int32)
        starts = np.asarray(batch["query_offsets"])
        ends = np.asarray(batch["kv_valid"])
        keys = []
        for i, name in enumerate(names):
            cap_n[i], snap_idx[i], key = hy.capture_slot(
                name, int(starts[i]), int(ends[i] - starts[i]))
            keys.append(key)
        layout = self._ragged_layout(batch)
        try:
            nxt, pools, state, snaps, counts, key = \
                self._ragged_step_hybrid(
                    self.params, self.kv.combined_pools(), hy.state,
                    hy.snaps,
                    layout.pack(dict(batch, seq_slot=seq_slot, cap_n=cap_n,
                                     snap_idx=snap_idx)),
                    self._keys, layout=layout, greedy=batch["greedy"],
                    attn_path=("kernel" if path == "pallas_ragged"
                               else "xla"))
        except Exception:
            for key in keys:
                hy.drop(key, unwritten=True)
            raise
        with deadlines.commit_guard():
            hy.commit_state(state)
            hy.commit_snaps(snaps)
        hy.note_counts(counts, pipelined=False)
        fed = int((ends - starts)[:len(names)].sum())
        hy.note_scan(fed)
        hy.note_join(fed, len(names))
        hy.note_shared_reads(int(ends[:len(names)].sum()))
        return nxt, pools, key

    def _ragged_layout(self, batch: dict) -> dispatch_pack.Layout:
        """The packed form of a ragged batch: a function of the shapes
        serving_loop.build_ragged_batch gave its arrays."""
        s_max, pps = batch["tables"].shape
        copy_src = batch.get("copy_src")
        return dispatch_pack.ragged_layout(
            len(batch["tokens"]), len(batch["seq_of_block"]), s_max, pps,
            score_width=int(batch.get("score_width", 0) or 0),
            copy_slots=0 if copy_src is None else len(copy_src),
            hybrid=self.hybrid is not None, lora=self.lora is not None)

    def _ragged_dispatch(self, batch: dict):
        """One mixed prefill/decode dispatch over a flat token buffer
        (serving_loop.build_ragged_batch output) — the scheduler's
        chunk-interleaved admission seam. Runs the resolved ragged path
        (Pallas kernel, or the XLA fallback with its recorded reason)
        through the kernel-degradation rung, commits the donated pools
        under commit_guard, and records per-dispatch provenance into
        the engine's ragged sink (the int4_paths pattern). Returns the
        per-sequence next-token DEVICE array [S_max]; the caller
        host-reads it through its own watchdog seam."""
        from .pallas import attention as pattn

        score_width = int(batch.get("score_width", 0) or 0)
        propose_width = int(batch.get("propose_width", 0) or 0)
        # Draft-model dispatches (ISSUE 13) ride the SAME compiled
        # programs with a params VALUE override — the draft checkpoint
        # shares the engine's pytree shapes by construction.
        params = (batch["draft_params"]
                  if batch.get("draft_params") is not None
                  else self.params)

        def run(path):
            if path == "pallas_ragged" and faults.ARMED:
                faults.maybe_inject("mosaic_compile")
            if self.hybrid is not None:
                return self._hybrid_ragged(batch, path)
            layout = self._ragged_layout(batch)
            if self.lora is not None:
                self._lora_ids(batch["token_adapter"])
            return self._ragged_step(
                params, self.kv.combined_pools(), layout.pack(batch),
                self._keys, layout=layout, greedy=batch["greedy"],
                attn_path=("kernel" if path == "pallas_ragged"
                           else "xla"),
                score_width=score_width,
                lora=self.lora.stacked if self.lora is not None else None,
                propose_width=propose_width)

        from . import compile_watch
        with compile_watch.label(
                f"ragged[t={len(batch['tokens'])}]",
                engine=self.cfg.name):
            try:
                nxt, pools, key = run(self.ragged_path)
            except Exception as e:
                if not (faults.is_kernel_failure(e)
                        and self._degrade_ragged(str(e))):
                    raise
                nxt, pools, key = run(self.ragged_path)
        self._count_issue()
        # A watchdog-abandoned dispatch completing late must NOT commit
        # onto pools the recovery path may have revived — nor move the
        # key chain the retry has to draw from.
        with deadlines.commit_guard():
            self.kv.set_combined(pools)
            self._keys = key
        path = self.ragged_path
        self._note_kv_quant("ragged", kernel=path == "pallas_ragged")
        self._ragged_dispatches[path] = \
            self._ragged_dispatches.get(path, 0) + 1
        entry = {"path": path, "tokens": int(batch["n_tokens"]),
                 "seqs": int(batch["n_seqs"])}
        if score_width:
            entry["spec"] = True
        if batch.get("draft"):
            # Draft-model/LoRA proposal dispatch (ISSUE 13): provenance
            # distinguishes drafting cost from verify cost.
            entry["draft"] = True
        if path != "pallas_ragged":
            entry["fallback_reason"] = (self.ragged_fallback_reason
                                        or "unknown")
        self._ragged_recent.append(entry)
        pattn.note_ragged_dispatch(kernel=path == "pallas_ragged")
        self._note_page_visits(batch, kernel=path == "pallas_ragged")
        return nxt

    def _ragged_class_shape(self, num_heads: int) -> tuple:
        """ragged_decline_reason's positional arguments
        (`_ragged_pool_shape[0]`) for attention layers of `num_heads`."""
        page_size, width, kh_l, _group = self._ragged_pool_shape[0]
        return page_size, width, kh_l, num_heads // self.cfg.page_heads

    def _note_page_visits(self, batch: dict, kernel: bool) -> None:
        """Count what this dispatch's attention read, in page visits
        (pallas.attention.ragged_page_visits): as the kernel that
        served it blocks the runs, and at the packing's 8-row blocks —
        of one layer at the model's own geometry (for a model whose
        attention layers differ: the model-level fields, a layer
        without a window). One writer for the lifetime totals, their
        series and the two fields the scheduler puts on the dispatch's
        `segment` span. A model with `attn_layers` also counts every
        layer by its class (`_note_window_reads`)."""
        from ..utils import telemetry
        from .pallas import attention as pattn
        made: dict = {}

        def visits(heads: int, window) -> tuple[int, int]:
            # (a class with the model-level geometry is counted once)
            if (heads, window) not in made:
                block = pattn.RAGGED_BLOCK_Q
                if kernel and self._ragged_pool_shape:
                    block = pattn.ragged_query_block(
                        len(batch["tokens"]),
                        *self._ragged_class_shape(heads),
                        **self._ragged_pool_shape[1])
                made[heads, window] = pattn.ragged_page_visits(
                    batch, page_size=self.kv.page_size, block_q=block,
                    sliding_window=window)
            return made[heads, window]

        own = visits(self.cfg.num_heads, self.cfg.sliding_window)
        for i, name in enumerate(("page_visits",
                                  "page_visits_by_eights")):
            batch[name] = own[i]
            self._ragged_visits[i] += own[i]
            telemetry.inc(f"roundtable_ragged_{name}_total", own[i],
                          engine=self.cfg.name)
        if self.cfg.attn_layers is None:
            return
        reads = {"page_visits_full": 0, "page_visits_window": 0}
        for heads, window, layers in self.cfg.attention_classes:
            reads["page_visits_full" if window is None
                  else "page_visits_window"] += \
                visits(heads, window)[0] * layers
        batch["window_reads"] = self._note_window_reads(reads)

    def plain_window_reads(self, steps: int, read_to: tuple) -> dict:
        """What the attention layers of a plain decode segment read, by
        layer class, in page visits (the decode walk's own span of a
        row: pallas.attention._paged_decode_kernel), from how many
        positions each row's cache held at its last step."""
        ps = self.kv.page_size
        valid = (np.asarray(read_to, np.int64)[:, None]
                 - np.arange(steps)[None, :])               # [rows, steps]
        valid = np.maximum(valid, 0)
        hi = np.maximum(valid - 1, 0) // ps
        reads = {"page_visits_full": 0, "page_visits_window": 0}
        for _heads, window, layers in self.cfg.attention_classes:
            lo = 0 if window is None else np.maximum(
                0, (valid - window) // ps)
            reads["page_visits_full" if window is None
                  else "page_visits_window"] += layers * int(
                np.where(valid > 0, hi - lo + 1, 0).sum())
        return self._note_window_reads(reads)

    def note_plain_shared_reads(self, steps: int, read_to: tuple) -> None:
        """What the cross layers of a plain decode segment read of the
        pool they share, in positions: every step of every row reads the
        row's whole cache (`read_to`: positions held at the last step)."""
        if self.hybrid is None or not self.cfg.cross_layers:
            return
        valid = (np.asarray(read_to, np.int64)[:, None]
                 - np.arange(steps)[None, :])               # [rows, steps]
        self.hybrid.note_shared_reads(int(np.maximum(valid, 0).sum()))

    def window_page_holdings(self, read_to: tuple) -> dict:
        """What a segment's live rows HOLD, in pages x attention layers
        (`pages_held`), and how much of it lies wholly behind a row's
        window on the window layers (`pages_behind_window`: pages whose
        every position is more than the window back from the row's
        frontier — the pages the decode walk starts after), from how
        many positions each row's cache held at the segment's last
        step. Window layers keep whole pages under the one page table;
        the quotient is what a frontier a layer class would free."""
        ps = self.kv.page_size
        valid = np.maximum(np.asarray(read_to, np.int64), 0)
        held = -(-valid // ps)
        n_layers = len(self.cfg.attention_layers)
        behind = sum(
            layers * int((np.maximum(valid - window, 0) // ps).sum())
            for _heads, window, layers in self.cfg.attention_classes
            if window is not None)
        return self._note_window_reads({
            "pages_held": int(held.sum()) * n_layers,
            "pages_behind_window": behind})

    def _note_window_reads(self, reads: dict) -> dict:
        """The one writer of the lifetime totals of a segment's reads
        by layer class, of what its rows hold, and of their series."""
        from ..utils import telemetry
        for name, n in reads.items():
            self._window_reads[name] += n
            telemetry.inc(
                telemetry.SURFACE_BINDINGS["engine_attention"][name], n,
                engine=self.cfg.name)
        return reads

    def attention_describe(self) -> dict[str, Any]:
        """Attention layers that differ from one another
        (ModelConfig.attn_layers): each layer's geometry, the kernels'
        lowerings the classes need, and what the segments' attention
        read by class, in page visits (`_note_page_visits`,
        `plain_window_reads`)."""
        from .pallas import attention as pattn
        cfg = self.cfg
        itemsize = self.kv.pools[0][0].dtype.itemsize
        return {
            "kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "gate": "per-head" if cfg.attn_gate else None,
            "layers": [
                {"layer": li, "heads": v.num_heads,
                 "window": v.sliding_window,
                 "rope_theta": v.rope_theta,
                 "rotary_dim": v.rotary_dim or cfg.head_dim,
                 "rope_yarn": (None if v.rope_yarn is None
                               else list(v.rope_yarn)),
                 "rope_attention_factor": v.rope_attention_factor}
                for li, v in zip(cfg.attention_layers,
                                 cfg.attention_views)],
            "classes": [
                {"heads": h, "window": w, "layers": n,
                 "decode_decline": pattn.paged_decode_decline_reason(
                     self.kv.page_size, cfg.page_width, cfg.page_heads,
                     h // cfg.page_heads, itemsize=itemsize),
                 "ragged_decline": (
                     pattn.ragged_decline_reason(
                         *self._ragged_class_shape(h),
                         **self._ragged_pool_shape[1])
                     if self._ragged_pool_shape else None)}
                for h, w, n in cfg.attention_classes],
            **self._window_reads,
        }

    def ragged_describe(self) -> dict[str, Any]:
        """Ragged-path provenance (ISSUE 8): the resolved path, why the
        seam or the kernel declined, the per-dispatch counts and the
        recent-dispatch ring — embedded in describe() and bench
        records the way int4_paths is."""
        return {
            "enabled": self.ragged_enabled,
            "path": self.ragged_path,
            "reason": self.ragged_reason,
            "fallback_reason": self.ragged_fallback_reason,
            "tokens_budget": self.ragged_tokens,
            "shapes": list(self.ragged_shapes),
            "defer_min_tokens": self.ragged_defer_min,
            "dispatches": dict(self._ragged_dispatches),
            "page_visits": self._ragged_visits[0],
            "page_visits_by_eights": self._ragged_visits[1],
            "recent": list(self._ragged_recent)[-8:],
        }

    def _note_kv_quant(self, seam: str, kernel: bool) -> None:
        """Record one serving dispatch that CONSUMED quantized pages
        (ISSUE 11): engine-owned provenance sink + the module test
        counter the conftest `kv_quant` guard reads — the
        int4_paths/ragged pattern. `kernel` = the dequant ran inside a
        Pallas kernel (pool-direct / pallas_ragged); False = the XLA
        dequant fallback (gather view / ragged dense path) served, with
        the machine-readable reason recorded per entry."""
        if self.kv_quant_spec is None:
            return
        from . import kv_quant as kvq_mod
        kvq_mod.note_quant_dispatch(kernel)
        path = "kernel_dequant" if kernel else "xla_dequant"
        key = f"{seam}:{path}"
        self._kv_quant_dispatches[key] = \
            self._kv_quant_dispatches.get(key, 0) + 1
        entry: dict[str, Any] = {"seam": seam, "path": path}
        if not kernel:
            entry["fallback_reason"] = (
                self.kv_quant_fallback_reason
                or self.paged_degraded_reason
                or (self.ragged_fallback_reason if seam == "ragged"
                    else None)
                or "gather_view:pool-direct-off")
        self._kv_quant_recent.append(entry)

    def kv_quant_describe(self) -> dict[str, Any]:
        """Quantized-KV provenance (ISSUE 11): the resolved spec, why
        the feature is off (reason) or why the kernels declined
        in-kernel dequant (fallback_reason), the per-seam dispatch
        counts and the recent-dispatch ring — embedded in describe()
        and bench records the way int4_paths/ragged/spec are."""
        spec = self.kv_quant_spec
        info: dict[str, Any] = {
            "enabled": spec is not None,
            "dtype": spec.dtype_name if spec is not None else None,
            "bits": spec.bits if spec is not None else None,
            "reason": self.kv_quant_reason,
            "fallback_reason": self.kv_quant_fallback_reason,
            "dispatches": dict(self._kv_quant_dispatches),
            "recent": list(self._kv_quant_recent)[-8:],
        }
        if spec is not None:
            info["group"] = spec.effective_group(self.cfg.head_dim)
            info["bytes_saved"] = max(
                self.kv.hbm_bytes_logical() - self.kv.hbm_bytes(), 0)
        return info

    def note_spec_dispatch(self, drafted: int, accepted: int,
                           rows: int, tree_nodes: int = 0,
                           tree_rows: int = 0) -> None:
        """Record one verify dispatch's acceptance outcome (the
        scheduler computes it host-side after the read): engine-owned
        provenance sink + the registry counter/gauge series — the
        int4_paths/ragged pattern, ISSUE 9 telemetry satellite. The
        counters carry a `drafter` label (ISSUE 13) so an acceptance
        collapse attributes to the PROPOSER, not the throttle, and tree
        dispatches additionally count their packed nodes."""
        from . import spec_decode as _sd
        self._spec_drafted += drafted
        self._spec_accepted += accepted
        self._spec_dispatches += 1
        self._spec_tree_nodes += tree_nodes
        self._spec_tree_rows += tree_rows
        drafter = self.spec_drafter or "ngram"
        # Per-DRAFTER accumulators: the labeled acceptance-rate gauge
        # must report THIS drafter's rate, not the lifetime blend — a
        # collapsing post-hot-swap drafter hiding behind a healthy
        # predecessor's rate is exactly the misattribution the label
        # exists to prevent.
        d_tot = self._spec_by_drafter.setdefault(drafter, [0, 0])
        d_tot[0] += drafted
        d_tot[1] += accepted
        entry = {"drafted": drafted, "accepted": accepted,
                 "rows": rows, "path": self.ragged_path,
                 "drafter": drafter}
        if tree_rows:
            entry["tree_rows"] = tree_rows
            entry["tree_nodes"] = tree_nodes
        self._spec_recent.append(entry)
        _sd.note_spec_dispatch(drafted, accepted)
        from ..utils import telemetry
        name = self.cfg.name
        if drafted:
            telemetry.inc("roundtable_spec_drafted_tokens_total",
                          drafted, engine=name, drafter=drafter)
            telemetry.inc("roundtable_spec_rejected_tokens_total",
                          drafted - accepted, engine=name,
                          drafter=drafter)
        if accepted:
            telemetry.inc("roundtable_spec_accepted_tokens_total",
                          accepted, engine=name, drafter=drafter)
        if tree_nodes:
            telemetry.inc("roundtable_spec_tree_nodes_total",
                          tree_nodes, engine=name, drafter=drafter)
        if d_tot[0]:
            telemetry.set_gauge(
                "roundtable_spec_acceptance_rate",
                d_tot[1] / d_tot[0], engine=name, drafter=drafter)

    def note_spec_throttle(self) -> None:
        self._spec_throttled += 1

    def spec_describe(self) -> dict[str, Any]:
        """Speculative-decoding provenance (ISSUE 9 + 13): the resolved
        state, the ACTIVE drafter (+ why a configured one fell back),
        the tree shape, cumulative drafted/accepted counts and the
        recent per-dispatch ring — embedded in describe() and bench
        records the way int4_paths/ragged are."""
        rate = (self._spec_accepted / self._spec_drafted
                if self._spec_drafted else None)
        dd = self.spec_device_drafter
        return {
            "enabled": self.spec_decode,
            "reason": self.spec_reason,
            "drafter": self.spec_drafter,
            "drafter_reason": self.spec_drafter_reason,
            "tree": (dict(self.spec_tree) if self.spec_tree else None),
            "max_draft": self.spec_max_draft,
            "verify_dispatches": self._spec_dispatches,
            "drafted_tokens": self._spec_drafted,
            "accepted_tokens": self._spec_accepted,
            "rejected_tokens": self._spec_drafted - self._spec_accepted,
            "acceptance_rate": (round(rate, 3)
                                if rate is not None else None),
            "throttled_rows": self._spec_throttled,
            **self.spec_batch.counts,
            "probe_interval": (self.spec_batch.interval()
                               if self.spec_batch.disabled else 0),
            "by_drafter": {k: {"drafted": v[0], "accepted": v[1]}
                           for k, v in self._spec_by_drafter.items()},
            "tree_nodes": self._spec_tree_nodes,
            "tree_rows": self._spec_tree_rows,
            "draft_dispatches": (dd.draft_dispatches
                                 if dd is not None else 0),
            "recent": list(self._spec_recent)[-8:],
        }

    def chars_per_token(self) -> float:
        if self._chars_per_token is None:
            sample = ("The quick brown fox jumps over the lazy dog. "
                      "def main(args): return 0  # typical source text\n" * 4)
            n = len(self.tokenizer.encode(sample, add_bos=False))
            self._chars_per_token = max(len(sample) / max(n, 1), 0.25)
        return self._chars_per_token

    def _prefill(self, state_rows: list[int],
                 token_lists: list[list[int]], offsets: list[int],
                 tables: np.ndarray, deadline: float = float("inf"),
                 budget=None, lora_ids=None) -> jax.Array:
        """Prefill dispatch: fresh long prompts go to the sequence-parallel
        ring program; everything else (short prompts, delta prefills on a
        reused prefix) takes the chunked bucketed path."""
        if (self._ring_prefill_fn is not None
                and all(o == 0 for o in offsets)
                and max(len(t) for t in token_lists) >= self.long_threshold):
            from .longcontext import SEQ_AXIS, pad_to_ring
            n_seq = self.seq_mesh.shape[SEQ_AXIS]
            tpad = pad_to_ring(max(len(t) for t in token_lists), n_seq,
                               self.kv.max_seq_len)
            # The writeback scatters whole pages, so the padded length
            # must also land on a page boundary — when the bucket doesn't
            # (tpad below page_size for near-threshold prompts, or the
            # cache-cap clamp), chunked prefill is the correct fallback,
            # not an error.
            if tpad and tpad % self.kv.page_size == 0:
                # (lora engines never build a ring program — the
                # constructor declines the feature on seq-parallel
                # engines, so lora_ids cannot reach this branch.)
                return self._prefill_ring(token_lists, tpad, tables)
        return self._prefill_chunked(state_rows, token_lists, offsets,
                                     tables, deadline, budget,
                                     lora_ids=lora_ids)

    def _prefill_ring(self, token_lists: list[list[int]], tpad: int,
                      tables: np.ndarray) -> jax.Array:
        """One sequence-parallel program prefills the whole batch; the
        full-sequence K/V is scattered through the page tables so decode
        and later delta-prefills continue on the normal path. Under
        data>1 pool-direct the caller passes replica-padded
        token_lists/tables, so B comes from the rows."""
        b = len(token_lists)
        tokens = np.full((b, tpad), self.tokenizer.pad_id, np.int32)
        for i, t in enumerate(token_lists):
            tokens[i, :len(t)] = t
        positions = np.broadcast_to(np.arange(tpad, dtype=np.int32),
                                    (b, tpad))
        lengths = np.asarray([len(t) for t in token_lists], np.int32)
        from . import compile_watch
        with compile_watch.label(f"ring_prefill[b={b},t={tpad}]",
                                 engine=self.cfg.name):
            logits, caches = self._ring_prefill_fn(
                self.params, jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(lengths))
        self.kv.set_combined(self._scatter_kv_paged(
            self.kv.combined_pools(), jnp.asarray(tables), caches))
        # Left unpacked (ISSUE 53): the ring program's inputs are
        # sharded over the sequence axis and its writeback is a program
        # of its own — three arrays and the tables, two launches.
        self._count_issue(host_buffers=4, launches=2)
        return logits

    def _prefill_chunked(self, state_rows: list[int],
                         token_lists: list[list[int]], offsets: list[int],
                         tables: np.ndarray,
                         deadline: float = float("inf"),
                         budget=None, lora_ids=None) -> jax.Array:
        """Chunked, bucketed prefill for B rows (serving_loop loop with
        this engine's step program). Returns last-token logits [B, V].

        `tables` is the caller-built page table for the whole call
        (capacity is ensured before any prefill dispatch; under data>1
        pool-direct it is already replica-grouped and padded).
        `state_rows`: each row's state row of a model with layer_kinds
        (hybrid_state; -1: none, the scratch row)."""
        tables = np.asarray(tables)
        # Per-row adapter slots for the whole call (ISSUE 10): chunk
        # composition varies, the ids do not — every chunk's buffer
        # carries the same ones.
        lora_np = None
        if self.lora is not None:
            lora_np = self._lora_ids(
                lora_ids if lora_ids is not None
                else [0] * len(token_lists))

        ends = [o + len(t) for o, t in zip(offsets, token_lists)]

        def paged_prefill(chunk, offs, lengths):
            if self.paged_direct and faults.ARMED:
                faults.maybe_inject("mosaic_compile")
            if self.hybrid is not None:
                # The tokens each row really feeds (an exhausted row's
                # filler pad must not reach its recurrent state).
                takes = [max(min(e - o, chunk.shape[1]), 0)
                         for e, o in zip(ends, offs)]
                return self._hybrid_prefill(tables, chunk, offs, takes,
                                            state_rows)
            layout = dispatch_pack.prefill_layout(
                *chunk.shape, tables.shape[1], lora=lora_np is not None)
            out = self._prefill_step_paged(
                self.params, self.kv.combined_pools(),
                layout.pack({"tables": tables, "tokens": chunk,
                             "offsets": offs, "lengths": lengths,
                             "lora_ids": lora_np}), layout=layout,
                lora=self.lora.stacked if lora_np is not None else None)
            self._count_issue()
            return out

        from . import compile_watch

        def dispatch(chunk, offs, lengths):
            # Compile-attribution window (ISSUE 6): a compile fired by
            # this chunk's program records under its (batch, bucket).
            with compile_watch.label(
                    f"prefill[b={chunk.shape[0]},bucket={chunk.shape[1]}]",
                    engine=self.cfg.name):
                try:
                    last, pools = paged_prefill(chunk, offs, lengths)
                except Exception as e:
                    # Kernel-path failure on a pool-direct engine:
                    # degrade to the gather-view programs and
                    # re-dispatch this chunk (inputs are host arrays,
                    # pools were not consumed by a failed compile).
                    # Anything else goes to the retry policy / the
                    # adapter ladder.
                    if not (faults.is_kernel_failure(e)
                            and self._degrade_paged_direct(str(e))):
                        raise
                    last, pools = paged_prefill(chunk, offs, lengths)
                # A watchdog-abandoned dispatch completing late must
                # NOT commit onto pools the recovery path may have
                # revived (the guard holds the ticket lock across
                # the commit).
                with deadlines.commit_guard():
                    self.kv.set_combined(pools)
                self._note_kv_quant("prefill", kernel=self.paged_direct)
                return last

        return chunked_prefill(dispatch, token_lists, offsets,
                               self.kv.max_seq_len, self.tokenizer.pad_id,
                               deadline, retry=self.retry, budget=budget,
                               note=self._count_issue)

    def _share_prefixes(self, names: list[str],
                        all_tokens: list[list[int]], offsets: list[int],
                        deadline: float, budget=None,
                        extra_pinned: tuple[str, ...] = (),
                        defer_span=None, row_adapters=None,
                        row_lora_slots=None) -> tuple[list[int], int]:
        """Cross-knight shared-prefix reuse (SURVEY.md §7.3 hard part 2;
        reference prompt assembly src/orchestrator.ts:397-425 makes all
        knights share the giant context+transcript preamble, which the
        orchestrator here lays out as a common PREFIX).

        Two mechanisms, both sharing position-aligned K/V between slots:
        (a) donor pass — a slot committed by an earlier call (another
            knight's turn) that shares a longer token prefix than this
            row's own history donates its K/V span;
        (b) leader pass — within one batch of fresh rows, the row with the
            most cache coverage prefills the batch-wide common span ONCE
            (ring-eligible when long) and the others take it.

        Returns (updated offsets, leader-prefilled token count). Prefill
        FLOPs for the shared span are paid once instead of N times, and
        its whole pages are held once. The pass structure itself lives in
        kvcache.share_prefixes; this method provides the device
        mechanics: a share ALIASES the donor's whole pages (refcount,
        zero copy; partial boundary pages are device-copied), and the
        leader span prefills via _prefill so a fresh long shared span
        takes the ring path on sequence-parallel engines."""
        from .kvcache import share_prefixes
        pinned = tuple(names) + tuple(extra_pinned)

        def add_share(donor, i, lo, hi):
            self.kv.alias_span(donor.name, names[i], lo, hi, pinned)

        def prefill_span(m, lo, hi):
            l_ids = ([row_lora_slots[m]] if row_lora_slots is not None
                     else None)
            self.kv.ensure_capacity(names[m], hi, write_from=lo,
                                    pinned=pinned)
            table = self.kv.table_for([names[m]])
            toks, offs = [all_tokens[m][lo:hi]], [lo]
            if self.paged_direct and self._paged_replicas > 1:
                # Single-row leader prefill under data>1 pool-direct
                # pads to one row per replica, like generate_batch.
                p = ReplicaGroupPlan(
                    [self.kv.replica_of(names[m])],
                    self._paged_replicas)
                table = p.pad_table(table, self.kv.scratch_page)
                toks = p.scatter_list(toks, [self.tokenizer.pad_id])
                offs = p.scatter_list(offs, 0)
                if l_ids is not None:
                    l_ids = p.scatter_list(l_ids, 0)
            # (no state row: a model with recurrent state takes the
            # leader pass deferred or not at all)
            self._prefill([-1], toks, offs, table, deadline,
                          budget=budget, lora_ids=l_ids)

        # Adapter-identity donor filter (ISSUE 10): K/V baked under one
        # adapter is WRONG under another, so a donor only serves rows
        # whose adapter label matches — conservative (a filtered best
        # donor is dropped rather than re-searched; the prefill it
        # saves is small next to serving wrong bytes).
        donor_ok = None
        if row_adapters is not None:
            labels = self._slot_adapters

            def donor_ok(donor, i):
                return labels.get(donor.name) == row_adapters[i]

        decline_leader = None
        if self.cfg.recurrent:
            def decline_leader(n_laggards):
                self.hybrid.note_declined(
                    n_laggards, "prologue" if defer_span is None
                    else "no-state-to-hand")

        return share_prefixes(
            self.kv, names, all_tokens, offsets,
            min_shared=MIN_SHARED_PREFIX, add_share=add_share,
            prefill_span=prefill_span,
            extra_pinned=extra_pinned, defer_span=defer_span,
            donor_ok=donor_ok, decline_leader=decline_leader)

    def note_latent_positions(self, n: int) -> None:
        """Positions of latent pages the rows of a segment read (the
        scheduler's fold; `describe()["mla"]`, the segment span)."""
        self._latent_positions += n
        from ..utils import telemetry
        telemetry.inc("roundtable_mla_latent_positions_total", n,
                      engine=self.cfg.name)

    def note_sampler_segment(self, filtered_rows: int) -> None:
        """One scheduled segment and how many of its rows engaged the
        sampler's filters (sampling.row_filtered: the rows that take
        sample_token_batch's pool branch on the device). The one writer
        of `describe()["sampler"]` and its series; the segment span
        carries its own count."""
        self._sampler["segments"] += 1
        if filtered_rows:
            self._sampler["filtered_segments"] += 1
            self._sampler["filtered_rows"] += filtered_rows
            from ..utils import telemetry
            telemetry.inc(
                telemetry.SURFACE_BINDINGS["engine_sampler"][
                    "filtered_rows"],
                filtered_rows, engine=self.cfg.name)

    def mla_describe(self) -> dict[str, Any]:
        """Latent pages and the kernels that read them (models/mla.py,
        engine/paging.py): the second page shape's provenance."""
        from .pallas import attention as pattn
        cfg, pool = self.cfg, self.kv.pools[0][0]
        itemsize = pool.dtype.itemsize
        entry = cfg.kv_lora_rank + cfg.qk_rope_dim
        group = cfg.num_heads // cfg.page_heads
        return {
            "pool_shape": list(pool.shape),
            "pools_per_layer": len(self.kv.pools[0]),
            "layers": len(self.kv.pools),
            "entry_width": entry, "page_width": cfg.page_width,
            "bytes_per_position_published": entry * itemsize,
            "bytes_per_position_stored": cfg.page_width * itemsize,
            # Every path that reads pages computes the absorbed form,
            # the prologue's first chunk too (one form, one kernel
            # family); the expanded form is the whole-sequence forward.
            "form": "absorbed", "prologue_form": "absorbed",
            "paged_decode": ("mla_paged_decode" if self.paged_direct
                             else "gather-view"),
            "paged_prefill": ("mla_paged_prefill" if self.paged_direct
                              else "gather-view"),
            "ragged": ("mla_ragged"
                       if self.ragged_path == "pallas_ragged"
                       else self.ragged_path),
            "decode_decline": pattn.paged_decode_decline_reason(
                self.kv.page_size, cfg.page_width, 1, group,
                itemsize=itemsize, latent=True),
            "ragged_decline": self.ragged_fallback_reason,
            "latent_positions": self._latent_positions,
        }

    def _plan_states(self, names, all_tokens, offsets,
                     blocked=frozenset()) -> dict:
        """The joint reuse plan of one admission (hybrid_state.plan): each
        row starts where BOTH its pages and a state stand. Lowers
        `offsets` in place to that position, drops the slot's pages
        beyond it (the attention layers re-write theirs from there),
        and copies the states in. The rows in `blocked` wait for a
        leader (a deferred share): theirs is planned when they unblock
        (`join_laggard`), behind what the leader leaves.
        -> what the admit span reports."""
        hy = self.hybrid
        plans = []
        out = {"continue": 0, "snapshot": 0, "zero": 0,
               "kv_matched_tokens": 0, "state_reused_tokens": 0,
               "prompt_tokens": sum(len(t) for t in all_tokens)}
        for i, name in enumerate(names):
            if i in blocked:
                continue
            start, source, snap = hy.plan(name, all_tokens[i], offsets[i])
            out[source] += 1
            out["kv_matched_tokens"] += offsets[i]
            out["state_reused_tokens"] += start
            if start < offsets[i]:
                self._rewind_slot(name, start)
                offsets[i] = start
            plans.append((name, source, snap))
        before = hy.copy_bytes["restore"]
        live = self._live_slots()
        hy.attach(plans, live)
        out["state_copy_bytes"] = hy.copy_bytes["restore"] - before
        out["rows"] = [hy.row_of(n, live) for n in names]
        return out

    def _rewind_slot(self, name: str, start: int) -> None:
        """The slot's state stands at `start`, under its pages' end:
        drop what lies beyond (the attention layers re-write theirs as
        the re-scan passes)."""
        slot = self.kv.acquire(name)
        slot.tokens = slot.tokens[:start]
        self.kv._trim_pages(slot, start)

    def join_laggard(self, leader: str, name: str, tokens: list[int],
                     lo: int, hi: int, upto: int, pinned: tuple,
                     hand: Optional[tuple[bytes, int]] = None) -> dict:
        """A deferred share falls due for one laggard: the leader has
        written the common span [.., hi) and row `name`, whose own pages
        stand at `lo`, unblocks. Its state is planned NOW — what the
        leader's run left at the hand-over boundary (`hand`:
        hybrid_state.hand_over's key and boundary) is ahead of this in
        program order, and `plan` finds it by its key like any other
        snapshot — the leader's pages alias in up to where the row
        starts (whole pages: a state stands at a boundary), its tail to
        `upto` is allocated, and the state is copied in; then the row
        no longer waits for that snapshot. A model without recurrent
        state starts at `hi`. -> `start`, the pages `aliased` and
        `copies` queued, the restore's `state_copy_bytes`, and whether
        the leader's state was `handed` (False: the row scans from the
        deepest state it found, counted as a decline)."""
        hy = self.hybrid
        start = hi
        if hy is not None:
            start, source, snap = hy.plan(name, tokens, hi)
        aliased = copies = 0
        if start > lo:
            aliased, copies = self.kv.alias_span(leader, name, lo, start,
                                                 pinned)
        else:
            self._rewind_slot(name, start)
        # Tail capacity (deferred from admission so the span pages
        # arrive SHARED, not as transient exclusive allocations the
        # alias would replace).
        self.kv.ensure_capacity(name, upto, write_from=start,
                                pinned=pinned)
        copied, handed = 0, False
        if hy is not None:
            before = hy.copy_bytes["restore"]
            hy.attach([(name, source, snap)], self._live_slots())
            copied = hy.copy_bytes["restore"] - before
        if hand is not None:
            key, at = hand
            hy.unpin(key)
            handed = start == at
            if handed:
                hy.note_handed()
            else:
                hy.note_declined(1, "state-not-left")
        return {"start": start, "aliased": aliased, "copies": copies,
                "state_copy_bytes": copied, "handed": handed}

    def _prepare_batch(self, turns, max_new_padded, deadline, pre_budget,
                       sampling_per_turn=None,
                       extra_pinned: tuple[str, ...] = (),
                       defer_prefill: bool = False,
                       adapters=None) -> dict:
        """The pre-decode phase, ONE definition shared by
        generate_batch and the session scheduler's admission
        (engine/scheduler.py) so the two can never drift on token
        parity: tokenize + tail-truncate → own-slot reuse_plan →
        cross-knight share_prefixes → paged capacity/COW + replica
        plan → chunked/ring prefill → first-token sample.

        `extra_pinned` names survive every eviction this phase can
        trigger (the scheduler pins its actively-decoding rows).
        Returns a dict with: names, state_rows, all_tokens, offsets
        (post-share), plan, tables_np (plan-padded when plan is set),
        per_row, temps/top_ks/top_ps (plan-scattered), greedy,
        first_np (ORIGINAL row order), prefill_tokens, reused_tokens.

        `adapters` (ISSUE 10): per-turn LoRA adapter ids (None =
        base), already acquire()'d by the caller so residency cannot
        change under this call. Drives the per-row slot ids the
        compiled programs consume, the adapter-flip slot guard, the
        prefix-cache base-rows-only filter and the mixed-adapter
        share suppression.

        `defer_prefill` (ISSUE 8, the mixed-dispatch seam): stop after
        the host/aliasing work — everything above EXCEPT the chunked
        prefill and first-token sample. The per-row suffixes
        (all_tokens[i][offsets[i]:]) stay unprefilled; the scheduler
        feeds them through ragged mixed dispatches interleaved with the
        live decode segment instead of this blocking prologue
        (first_np is None in the returned dict). Paged, replica-free
        engines only — the flat buffer cannot mix pool replicas."""
        from ..utils import telemetry
        # The `plan` span (ISSUE 37): the host work of an admission up
        # to its prologue — under the scheduler's `admit` span on its
        # thread, under a turn's on any other; unarmed, the null span.
        with telemetry.span("plan") as span:
            took = self.kv.pages_allocated
            prep = self._plan_batch(
                turns, max_new_padded, deadline, pre_budget,
                sampling_per_turn, extra_pinned, defer_prefill, adapters)
            if span is not telemetry.NULL_SPAN:
                span.attrs.update(
                    prompt_tokens=sum(len(t) for t in prep["all_tokens"]),
                    matched_tokens=prep["reused_tokens"],
                    pages_allocated=self.kv.pages_allocated - took)
        if prep.pop("deferred"):
            return prep
        return self._prologue(prep, deadline, pre_budget)

    def _plan_batch(self, turns, max_new_padded, deadline, pre_budget,
                    sampling_per_turn, extra_pinned, defer_prefill,
                    adapters) -> dict:
        """_prepare_batch's host half: everything up to the prologue's
        first program of its own (the leader pass of an undeferred
        batch dispatches its span's prefill in here) — reuse plan,
        prefix attach, shares, the joint state plan, page allocation.
        -> _prepare_batch's dict with `first_np` None, and `deferred`:
        whether it stays so."""
        pinned = tuple(name for name, _ in turns) + tuple(extra_pinned)
        if self.kv_offload is not None:
            # A spilled session resumes HERE, before reuse_plan acquires
            # its slots: the restored tokens/pages make the LCP pass see
            # the full committed prefix, so the turn prefills only its
            # real delta — no re-prefill across the idle gap (ISSUE 7).
            self.kv_offload.restore_for([n for n, _ in turns], pinned)
        ad: Optional[list] = None
        lora_slots: Optional[list[int]] = None
        if self.lora is not None:
            ad = (list(adapters) if adapters is not None
                  else [None] * len(turns))
            if len(ad) != len(turns):
                raise ValueError(
                    f"adapters has {len(ad)} entries for "
                    f"{len(turns)} turns")
            lora_slots = []
            for a in ad:
                if a is None:
                    lora_slots.append(0)
                    continue
                slot = self.lora.slot_of(a)
                if slot is None:
                    raise RuntimeError(
                        f"lora adapter {a!r} is not resident — callers "
                        "acquire() adapters before _prepare_batch")
                lora_slots.append(slot)
            # Adapter-flip guard: a slot re-served under a DIFFERENT
            # adapter must never reuse K/V computed under the old one
            # (the bytes differ) — release forces a fresh prefill.
            # AFTER the offload restore above, or a flip across a
            # spill gap would release a non-resident name (no-op) and
            # the restore would resurrect the old adapter's bytes.
            # Base rows label None, so "never seen" needs a distinct
            # sentinel: base→persona flips must release too, while a
            # genuinely fresh slot must not.
            unset = object()
            for (name, _p), a in zip(turns, ad):
                prev = self._slot_adapters.get(name, unset)
                if prev is not unset and prev != a:
                    self.kv.release(name)
                self._slot_adapters[name] = a
            if len(self._slot_adapters) > 4 * self.kv.num_slots:
                # Keep labels whose K/V still EXISTS anywhere — pool
                # slots, this batch, or sessions parked in the offload
                # tier (their slots leave kv.slot_names() but their
                # bytes come back via restore_for, and a label dropped
                # here would make a later flip undetectable).
                from .kvcache import session_of
                live = set(self.kv.slot_names()) \
                    | {name for name, _ in turns}
                spilled = (set(self.kv_offload.spilled_sessions())
                           if self.kv_offload is not None else set())
                self._slot_adapters = {
                    n: a_ for n, a_ in self._slot_adapters.items()
                    if n in live or session_of(n) in spilled}
        offsets, all_tokens = [], []
        for name, prompt in turns:
            # A list of ids is accepted as a pre-tokenized prompt (warmup
            # uses this to hit exact bucket shapes).
            tokens = (list(prompt) if isinstance(prompt, list)
                      else self.tokenizer.encode(prompt))
            budget_tok = prompt_budget(self.max_seq_len, max_new_padded)
            if len(tokens) > budget_tok:
                # Keep the tail — the turn ask and latest transcript live
                # there (head truncation mirrors context budgeting
                # intent).
                tokens = (tokens[:1]
                          + tokens[len(tokens) - budget_tok + 1:])
            offsets.append(self.kv.reuse_plan(name, tokens, pinned)[1])
            all_tokens.append(tokens)

        names = [name for name, _ in turns]
        # Cross-SESSION prefix cache (ISSUE 7): the content-addressed
        # index extends each row's reuse frontier past its own slot
        # history by aliasing pages committed by ANY earlier session —
        # the radix match is exact token equality, so this can never
        # serve wrong bytes. Warmup rows are excluded: they are crafted
        # to defeat sharing so the real prefill programs compile.
        prefix_reused = 0
        if self.prefix_cache is not None:
            if lora_slots is None or not any(lora_slots):
                prefix_reused = self.prefix_cache.attach_rows(
                    names, all_tokens, offsets, pinned)
            else:
                # Cross-session cache content is BASE-adapter K/V: a
                # persona row must neither consume it nor feed it
                # (commit gates the feed side symmetrically), so only
                # the base rows of this batch consult the index.
                base_idx = [i for i, sl in enumerate(lora_slots)
                            if sl == 0]
                if base_idx:
                    sub_off = [offsets[i] for i in base_idx]
                    prefix_reused = self.prefix_cache.attach_rows(
                        [names[i] for i in base_idx],
                        [all_tokens[i] for i in base_idx],
                        sub_off, pinned)
                    for j, i in enumerate(base_idx):
                        offsets[i] = sub_off[j]
        defer_by_state = defer_prefill and self.cfg.recurrent
        if defer_prefill and not defer_by_state:
            # Deferral pays off only for COLD prefills: after own-slot
            # reuse and the prefix-cache attach, a warm join's leftover
            # is often a few dozen tokens — one tiny bucket dispatch,
            # cheaper blocking than spread across segment-gated ragged
            # ticks. Resolve the mode HERE (callers read first_np is
            # None); the share passes below then defer (or not) with it.
            est = sum(len(t) - o for t, o in zip(all_tokens, offsets))
            if est < self.ragged_defer_min:
                defer_prefill = False
        # Cross-knight shared-prefix reuse raises offsets by aliasing
        # other slots' pages; only the per-knight deltas
        # remain to prefill. Under defer_prefill the LEADER pass defers
        # too (ISSUE 8 — it was the last blocking prologue dispatch):
        # the span is recorded here and the scheduler aliases the
        # laggards once the leader's ragged chunks have written it.
        share_plan: list[dict] = []
        defer_span = None
        if defer_prefill:
            def defer_span(m, lo, hi, followers):  # noqa: F811
                hand = None
                if self.cfg.recurrent:
                    # The laggards need the leader's STATE: deferred
                    # only if the store can keep it for them.
                    hand = self.hybrid.hand_over(all_tokens[m], lo, hi)
                    if hand is None:
                        return False
                share_plan.append({"leader": m, "lo": lo, "hi": hi,
                                   "followers": followers, "hand": hand})
        if lora_slots is not None and len(set(lora_slots)) > 1:
            # Mixed-adapter batch: no donor/leader span is valid
            # across rows with different adapters, so the share passes
            # are suppressed outright (lora_describe() counts it).
            self._lora_share_suppressed += 1
            leader_prefill = 0
        else:
            offsets, leader_prefill = self._share_prefixes(
                names, all_tokens, offsets, deadline,
                budget=pre_budget, extra_pinned=tuple(extra_pinned),
                defer_span=defer_span, row_adapters=ad,
                row_lora_slots=lora_slots)
        state_plan = None
        state_rows = [-1] * len(names)
        deferred_followers = {i for p in share_plan
                              for i, _lo in p["followers"]}
        if self.hybrid is not None:
            state_plan = self._plan_states(names, all_tokens, offsets,
                                           deferred_followers)
            state_rows = state_plan.pop("rows")
            if defer_by_state:
                # What a join has to scan is decided by where its STATE
                # stands, not its pages: the cold-or-warm question
                # above, asked after the joint plan. An admission whose
                # laggards wait for a leader stays deferred whatever is
                # left to scan: a prologue cannot unblock them.
                est = sum(len(t) - o for t, o in zip(all_tokens, offsets))
                defer_prefill = (bool(share_plan)
                                 or est >= self.ragged_defer_min)
        plan = None
        # Allocate pages for the whole call (prompt + padded decode)
        # and copy-on-write any shared page in the write range, so
        # the jit'd programs below never allocate or touch aliased
        # pages. Deferred-share LAGGARDS skip this: their span pages
        # arrive by ALIAS once the leader's chunks write them —
        # allocating exclusive pages now would transiently demand
        # more pool than the prologue path ever did (the alias
        # would immediately replace them), and their tail capacity
        # is ensured at alias time (join_laggard).
        for i, name in enumerate(names):
            if i in deferred_followers:
                continue
            self.kv.ensure_capacity(
                name, len(all_tokens[i]) + max_new_padded,
                write_from=offsets[i], pinned=pinned)
        tables_np = self.kv.table_for(names)
        if self.paged_direct and self._paged_replicas > 1:
            # Pool-direct under data>1 (VERDICT r4 #4): shard_map
            # splits batch rows into adjacent per-data-index
            # blocks, so rows are permuted into the block of the
            # replica owning their slot's pages; pad rows point at
            # that replica's scratch page and start done.
            plan = ReplicaGroupPlan(
                [self.kv.replica_of(n) for n in names],
                self._paged_replicas)
            tables_np = plan.pad_table(tables_np,
                                       self.kv.scratch_page)
        suffixes = [t[o:] for t, o in zip(all_tokens, offsets)]
        prefill_tokens = leader_prefill + sum(len(s) for s in suffixes)
        # "reused" counts both own-slot LCP hits and copied donor spans.
        reused_tokens = sum(len(t) for t in all_tokens) - prefill_tokens
        if defer_prefill and plan is not None:
            raise RuntimeError(
                "defer_prefill requires a replica-free paged pool "
                "(the ragged flat buffer cannot mix pool replicas)")
        per_row = sampling_per_turn or [self.sampling] * len(turns)
        if len(per_row) != len(turns):
            raise ValueError(
                f"sampling_per_turn has {len(per_row)} entries for "
                f"{len(turns)} turns")
        return {
            "names": names, "state_rows": state_rows,
            "all_tokens": all_tokens, "offsets": offsets,
            "plan": plan, "tables_np": tables_np,
            "per_row": per_row, "temps": None, "top_ks": None,
            "top_ps": None,
            "greedy": all(p.temperature <= 0.0 for p in per_row),
            "first_np": None, "prefill_tokens": prefill_tokens,
            "reused_tokens": reused_tokens,
            "prefix_reused_tokens": prefix_reused,
            "share_plan": share_plan,
            "lora_slots": lora_slots, "adapters": ad,
            "state_plan": state_plan, "deferred": defer_prefill,
        }

    def _prologue(self, prep: dict, deadline, pre_budget) -> dict:
        """_prepare_batch's device half for a batch that is not
        deferred: chunked/ring prefill of each row's suffix → the
        first-token sample → ONE blocking read. -> `prep` with
        `first_np` (ORIGINAL row order) and the sampling arrays."""
        plan, offsets = prep["plan"], prep["offsets"]
        suffixes = [t[o:] for t, o in zip(prep["all_tokens"], offsets)]
        p_offsets = offsets
        p_lora = prep["lora_slots"]
        if plan is not None:
            suffixes = plan.scatter_list(suffixes,
                                         [self.tokenizer.pad_id])
            p_offsets = plan.scatter_list(offsets, 0)
            if p_lora is not None:
                p_lora = plan.scatter_list(p_lora, 0)
        temps, top_ks, top_ps = sampling_arrays(prep["per_row"])
        greedy = prep["greedy"]
        if plan is not None:
            # The whole decode phase runs in padded replica-grouped row
            # order; callers read back through plan.pos.
            temps = plan.scatter_rows(temps, 1.0)
            top_ks = plan.scatter_rows(top_ks, 0)
            top_ps = plan.scatter_rows(top_ps, 1.0)
        last_logits = self._prefill(prep["state_rows"], suffixes,
                                    p_offsets, prep["tables_np"],
                                    deadline=deadline,
                                    budget=pre_budget, lora_ids=p_lora)
        from ..utils import telemetry
        from . import compile_watch
        with compile_watch.label(
                f"prefill[b={last_logits.shape[0]},first_token]",
                engine=self.cfg.name):
            # One buffer (the rows' sampling parameters) and one
            # launch; a sampled batch splits the key inside and hands
            # the next one back, a greedy one draws none, as before.
            layout = dispatch_pack.sampler_layout(len(temps))
            first, key = self._first_token(
                last_logits, self._keys,
                layout.pack({"temps": temps, "top_ks": top_ks,
                             "top_ps": top_ps}),
                layout=layout, greedy=greedy)
            self._count_issue()
            if not greedy:
                self._keys = key
        # On a clocked thread (the scheduler's) the device holds the
        # prologue's programs — each prefill chunk fed the loop clock
        # where it was issued (serving_loop.chunked_prefill), the
        # sampler here — until the read below has drained them.
        clock = telemetry.loop_clock()
        if clock is not None:
            clock.feed()
        # ONE blocking read for the whole prologue: it waits on the
        # prefill's logits, so it also pins prefill's time before decode
        # (a PJRT transport may return from block_until_ready before the
        # computation finishes) — and it goes through the deadline seam
        # (a wedged prefill program freezes the host exactly here).
        first_np = host_sync(lambda: np.asarray(first), pre_budget,
                             "prefill")
        if clock is not None:
            clock.drain()
        if plan is not None:
            first_np = first_np[plan.pos]
        prep.update(temps=temps, top_ks=top_ks, top_ps=top_ps,
                    first_np=first_np)
        return prep

    def _decode_dispatch_paged(self, fields: dict, carry=None, *, greedy,
                               max_new=DECODE_SEGMENT, names=None):
        """One paged decode-segment dispatch through the kernel-
        degradation rung (mosaic chaos point; pool-direct → gather-view
        on kernel failure, re-dispatching this segment), committing the
        donated pools and the key the program hands back under
        commit_guard. Shared by generate_batch's segment loop and the
        session scheduler.

        `fields`: what the host built for the segment, as numpy values
        under dispatch_pack.decode_layout's names (`tables`, `last`,
        `valid`, `done`, `budgets`, `temps`, `top_ks`, `top_ps`, the
        segment's step `budget`, and `lora_ids` on a lora engine) — it
        travels as ONE buffer. `carry`: the (last, valid, done,
        budgets) the segment before returned, still on the device (a
        pipelined segment: the buffer's own four are then not read);
        None on a first segment. `names`: the rows' slot names (pad
        rows left out) — a model with recurrent state finds each row's
        state by it. -> (out, steps, last, valid, done, budgets left)."""
        b, pps = fields["tables"].shape
        fields = dict(fields, carried=carry is not None)
        if carry is None:
            carry = self._decode_carry(b)

        def run():
            if self.paged_direct and faults.ARMED:
                faults.maybe_inject("mosaic_compile")
            if self.hybrid is not None:
                return self._hybrid_decode(fields, carry, names, max_new,
                                           greedy)
            layout = dispatch_pack.decode_layout(
                b, pps, lora=self.lora is not None)
            return self._decode_loop_paged(
                self.params, self.kv.combined_pools(), layout.pack(fields),
                carry, self._keys, layout=layout, max_new=max_new,
                greedy=greedy,
                lora=self.lora.stacked if self.lora is not None else None)

        from . import compile_watch
        with compile_watch.label(
                f"decode[b={b},paged]", engine=self.cfg.name):
            try:
                out, steps, l2, v2, d2, left, pools, key = run()
            except Exception as e:
                if not (faults.is_kernel_failure(e)
                        and self._degrade_paged_direct(str(e))):
                    raise
                out, steps, l2, v2, d2, left, pools, key = run()
        self._count_issue()
        # A watchdog-abandoned dispatch completing late must NOT commit
        # onto pools the recovery path may have revived — nor move the
        # key chain the retry has to draw from.
        with deadlines.commit_guard():
            self.kv.set_combined(pools)
            self._keys = key
        self._note_kv_quant("decode", kernel=self.paged_direct)
        return out, steps, l2, v2, d2, left

    def generate(self, prompt: str, slot_name: str = "default",
                 max_new_tokens: Optional[int] = None,
                 timeout_s: float = 600.0, session: Optional[str] = None,
                 ) -> str:
        return self.generate_batch([(slot_name, prompt)],
                                   max_new_tokens=max_new_tokens,
                                   timeout_s=timeout_s, session=session)[0]

    def generate_batch(self, turns: list[tuple[str, str]],
                       max_new_tokens: Optional[int] = None,
                       timeout_s: float = 600.0,
                       sampling_per_turn: Optional[
                           list[SamplingParams]] = None,
                       budget=None,
                       session: Optional[str] = None,
                       adapters_per_turn: Optional[
                           list[Optional[str]]] = None) -> list[str]:
        return self.generate_batch_with_stats(
            turns, max_new_tokens=max_new_tokens, timeout_s=timeout_s,
            sampling_per_turn=sampling_per_turn, budget=budget,
            session=session, adapters_per_turn=adapters_per_turn)[0]

    def generate_batch_with_stats(
            self, turns: list[tuple[str, str]],
            max_new_tokens: Optional[int] = None,
            timeout_s: float = 600.0,
            sampling_per_turn: Optional[list[SamplingParams]] = None,
            budget=None,
            session: Optional[str] = None,
            adapters_per_turn: Optional[list[Optional[str]]] = None,
    ) -> tuple[list[str], GenStats]:
        """Serve N (slot_name, prompt) turns as one batched program pair.

        sampling_per_turn: per-row SamplingParams (heterogeneous knight
        personas); None = the engine default for every row. `budget`: a
        turn-rung deadlines.Budget threaded down from the adapter (the
        time ladder); None builds a local root from `timeout_s`, so
        direct engine callers get the same rung structure. `session`
        namespaces the slot names (kvcache.scoped_slot) so two concurrent
        discussions' same-named knights never collide in the LRU — the
        cross-session-contamination fix (ISSUE 4 satellite).
        `adapters_per_turn` (ISSUE 10): per-row LoRA persona adapter
        ids (None = base); a mixed list serves every persona in ONE
        batched program. Silently ignored on lora-off engines — the
        ROUNDTABLE_LORA=0 kill-switch must restore base serving
        byte-identically, not start raising. Returns
        (responses, this call's stats) — callers needing stats must take
        them from the return value, not from `last_stats`, which is a
        convenience field that concurrent callers may overwrite."""
        if session:
            from .kvcache import scoped_slot
            turns = [(scoped_slot(session, name), prompt)
                     for name, prompt in turns]
        # Admission gate (fleet.drain): one module-flag check per CALL,
        # nothing on the per-token path. In-flight generations (already
        # past this check, possibly waiting on the serve lock) complete.
        deadlines.check_admission()
        with self._serve_lock:
            # Adapter residency refs for the duration of the call —
            # under the serve lock, so a swap can never race a
            # concurrent dispatch's argument capture (ISSUE 10).
            acquired = None
            if self.lora is not None and adapters_per_turn:
                self.lora.validate(adapters_per_turn, len(turns))
                # acquire() is exception-atomic; `acquired` is set
                # only AFTER it took the refs, so the finally below
                # releases exactly what this call holds.
                self.lora.acquire(adapters_per_turn)
                acquired = list(adapters_per_turn)
            elif self.lora is None:
                adapters_per_turn = None
            try:
                # The "turn" rung of the span tree (ISSUE 5) — same
                # node the turn Budget bounds; session/engine attrs
                # make concurrent discussions separable in one trace.
                from ..utils import telemetry
                if telemetry.ACTIVE:
                    with telemetry.span("turn", engine=self.cfg.name,
                                        rows=len(turns),
                                        session=session or "",
                                        knights=[n for n, _ in turns]):
                        return self._generate_batch_locked(
                            turns, max_new_tokens, timeout_s,
                            sampling_per_turn, budget,
                            adapters_per_turn)
                return self._generate_batch_locked(
                    turns, max_new_tokens, timeout_s, sampling_per_turn,
                    budget, adapters_per_turn)
            finally:
                if acquired:
                    self.lora.release(acquired)

    def _generate_batch_locked(self, turns, max_new_tokens, timeout_s,
                               sampling_per_turn=None, budget=None,
                               adapters_per_turn=None):
        if faults.ARMED and len(turns) > 1:
            # Chaos point for the batched-round degradation ladder: a
            # "corrupted KV slot" fails the fan-out before any slot
            # bookkeeping mutates; the adapter invalidates the batch's
            # slots and retries the knights serially (tpu_llm.py).
            faults.maybe_inject("kv_corrupt")
        stats = GenStats()
        # The turn's budget node: adapters thread one down (round →
        # turn); direct callers get a local root bounded by timeout_s.
        # The float deadline stays the single source for the legacy
        # time checks — always <= every ancestor's deadline. (`budget`
        # is re-bound below for the prompt-token budget — the Budget
        # node keeps its own name.)
        turn_budget = budget if budget is not None \
            else deadlines.Budget.root(timeout_s, rung="turn")
        deadline = min(turn_budget.deadline, time.monotonic() + timeout_s)
        pre_budget = turn_budget.child("prefill")
        # One clamp definition for engines + scheduler (serving_loop
        # .clamp_max_new): drift here desynchronizes admission page
        # estimates, row budgets, and retirement output caps.
        from .serving_loop import clamp_max_new
        max_new, max_new_padded = clamp_max_new(
            max_new_tokens or self.sampling.max_new_tokens,
            self.max_seq_len)

        from ..utils import telemetry
        t0 = time.monotonic()
        with telemetry.span("prefill", engine=self.cfg.name) as _psp:
            prep = self._prepare_batch(turns, max_new_padded, deadline,
                                       pre_budget, sampling_per_turn,
                                       adapters=adapters_per_turn)
            _psp.set_attr("prefill_tokens", prep["prefill_tokens"])
            _psp.set_attr("reused_tokens", prep["reused_tokens"])
        stats.prefill_tokens = prep["prefill_tokens"]
        stats.reused_tokens = prep["reused_tokens"]
        stats.prefix_reused_tokens = prep["prefix_reused_tokens"]
        stats.prefill_seconds = time.monotonic() - t0

        plan = prep["plan"]
        all_tokens = prep["all_tokens"]
        first_np = prep["first_np"]
        per_row = prep["per_row"]
        temps, top_ks, top_ps = (prep["temps"], prep["top_ks"],
                                 prep["top_ps"])
        greedy = prep["greedy"]
        # first_np comes back in ORIGINAL row order; the decode phase
        # runs in plan order (padded replica-grouped rows) when a plan
        # exists, so scatter it back — pad rows open at eos (done).
        first = first_np.astype(np.int32)
        cur_valid = np.asarray([len(t) for t in all_tokens], np.int32)
        if plan is not None:
            first = plan.scatter_rows(first,
                                      np.int32(self.tokenizer.eos_id))
            cur_valid = plan.scatter_rows(cur_valid, 1)

        t1 = time.monotonic()
        # Decode rung budget is derived NOW, not at call start, so a
        # configured "decode" cap times the decode phase alone.
        dec_budget = turn_budget.child("decode")
        # Per-row decode budgets (knight_sampling max_new_tokens): a row
        # whose own budget is smaller than the batch's stops early (goes
        # done, emits eos) while the rest keep decoding
        # (serving_loop.row_budget_fn — one definition for both engines).
        from .serving_loop import row_budget_fn
        row_budgets = row_budget_fn(per_row, sampling_per_turn, max_new)
        if plan is not None:
            row_budgets = plan.scatter_rows(row_budgets, 0)
        lora_slots = prep.get("lora_slots")
        # What every segment's buffer holds; a first segment takes its
        # rows' state from it too (the first tokens, the prompts'
        # lengths, the budgets), a pipelined one from what the segment
        # before carried.
        fields = {"tables": prep["tables_np"], "last": first,
                  "valid": cur_valid,
                  "done": first == np.int32(self.tokenizer.eos_id),
                  "budgets": row_budgets, "temps": temps,
                  "top_ks": top_ks, "top_ps": top_ps}
        if self.lora is not None:
            dec_ids = list(lora_slots if lora_slots is not None
                           else [0] * len(all_tokens))
            if plan is not None:
                dec_ids = plan.scatter_list(dec_ids, 0)
            fields["lora_ids"] = self._lora_ids(dec_ids)

        def decode_dispatch(budget, carry):
            return self._decode_dispatch_paged(
                dict(fields, budget=budget), carry, greedy=greedy,
                names=prep["names"])

        with telemetry.span("decode", engine=self.cfg.name,
                            max_new=max_new):
            out_np = decode_segments(decode_dispatch, len(first), max_new,
                                     deadline, timeout_s, retry=self.retry,
                                     budget=dec_budget,
                                     filtered_rows=sum(
                                         row_filtered(p) for p in per_row))
        stats.decode_seconds = time.monotonic() - t1
        if plan is not None:
            out_np = out_np[plan.pos]

        commit = self.kv.commit
        ad = prep.get("adapters")
        if ad is not None and any(a is not None for a in ad):
            # Persona rows must not FEED the cross-session prefix
            # cache: their pages hold adapter-tinted K/V no other
            # adapter (or the base) may alias (ISSUE 10).
            idx_of = {name: (a is None)
                      for (name, _p), a in zip(turns, ad)}

            def commit(name, toks, _kv=self.kv, _idx=idx_of):
                _kv.commit(name, toks, index=_idx.get(name, True))

        if self.hybrid is not None:
            # A row that ended on a sampled eos has consumed one token
            # more than it commits: its state cannot be continued.
            eos_id = self.tokenizer.eos_id
            ended = {name: eos_id in ([int(first_np[i])]
                                      + [int(x) for x in out_np[i]]
                                      )[:max_new]
                     for i, (name, _p) in enumerate(turns)}
            kv_commit = commit
            self.hybrid.fold_counts()    # every dispatch has been read

            def commit(name, toks):  # noqa: F811
                kv_commit(name, toks)
                self.hybrid.on_commit(name, toks, exact=not ended[name])

        results = finalize_outputs(
            turns, first_np, out_np, all_tokens, max_new,
            self.tokenizer.eos_id, commit, self.tokenizer.decode,
            stats)
        if self.lora is not None and lora_slots and any(lora_slots):
            from .serving_loop import eos_trim
            n = 0
            for i, sl in enumerate(lora_slots):
                if not sl:
                    continue
                ids_row = eos_trim(
                    [int(first_np[i])] + [int(x) for x in out_np[i]],
                    self.tokenizer.eos_id, max_new)
                n += len(ids_row) + len(all_tokens[i]) \
                    - prep["offsets"][i]
            self.note_lora_tokens(n)
        stats.int4_paths = self.int4_path_report()
        # Publish this call into the unified registry (ISSUE 5): token/
        # throughput counters plus the int4 path-provenance view — the
        # engine-stats store metrics.json/bench already read stays the
        # return value; the registry is the shared spine.
        from . import trace_hooks
        trace_hooks.publish_gen_stats(stats, self.cfg.name)
        trace_hooks.publish_int4_paths(stats.int4_paths, self.cfg.name)
        # Memory ledger at the call boundary (ISSUE 6): slot/page
        # occupancy, fragmentation, HBM — event-rate host math only.
        trace_hooks.publish_memory_ledger(self)
        self.last_stats = stats
        return results, stats

    # --- introspection ---

    def describe(self) -> dict[str, Any]:
        info = {
            "model": self.cfg.name,
            "params": self.num_params,
            "max_seq_len": self.max_seq_len,
            "mesh": dict(self.mesh.shape),
            "num_slots": self.kv.num_slots,
            "kv_layout": "paged",
            "quant": (self.quant + " (auto-degraded)"
                      if getattr(self, "quant_auto_degraded", False)
                      else self.quant),
            "devices": [str(d) for d in self.mesh.devices.flatten()],
        }
        if self.quant == "int4":
            info["int4_paths"] = self.int4_path_report()
        info["page_size"] = self.kv.page_size
        info["num_pages"] = self.kv.num_pages
        info["kv_hbm_bytes"] = self.kv.hbm_bytes()
        info["paged_decode"] = ("pool-direct" if self.paged_direct
                                else "gather-view")
        # ISSUE 38: pages handed out, page copies queued and the
        # programs that issued them.
        info["paging"] = self.kv.describe()
        # ISSUE 53: what the step seams sent and issued — one buffer
        # and one launch a program where a dispatch is packed.
        info["dispatch"] = dict(self._dispatch_totals)
        # ISSUE 7: the cross-session sharing subsystems' state.
        if self.prefix_cache is not None:
            info["prefix_cache"] = self.prefix_cache.describe()
        if self.kv_offload is not None:
            info["kv_offload"] = self.kv_offload.describe()
        # ISSUE 8: ragged mixed-dispatch path provenance.
        info["ragged"] = self.ragged_describe()
        # ISSUE 9: speculative-decoding provenance (drafter,
        # per-dispatch drafted/accepted, throttle state).
        info["spec_decode"] = self.spec_describe()
        # ISSUE 11: quantized-KV-page provenance (spec, per-seam
        # dispatch paths, kernel-decline reason, bytes saved).
        info["kv_quant"] = self.kv_quant_describe()
        if self.hybrid is not None:
            # Recurrent state beside the pools, and the chip's share of
            # the experts (models/hybrid.py, engine/hybrid_state.py).
            info["hybrid_state"] = self.hybrid.describe()
            if self.prefix_cache is not None:
                info["hybrid_state"]["deduped_pages"] = \
                    self.prefix_cache.deduped_pages
            info["moe"] = {"held": self.cfg.experts_held,
                           "published": self.cfg.routed_experts,
                           "offset": self.cfg.expert_offset,
                           "top_k": self.cfg.moe_top_k,
                           "router_rule": self.cfg.router_rule,
                           "shared_expert": bool(
                               self.cfg.shared_expert_dim),
                           "expert_layers": len(self.cfg.expert_layers),
                           "grouped_product": (
                               "ragged_dot" if "grouped_product"
                               in self.declines else "kernel"),
                           **self.hybrid.moe_totals()}
        if self.cfg.retention_layers:
            from .models import retention
            d = self.cfg.head_dim
            info["retention"] = {
                "power": retention.POWER,
                "layers": len(self.cfg.retention_layers),
                "state_rows": retention.state_rows(d),
                "state_rows_min": retention.state_rows_min(d),
                "bytes_per_state": retention.bytes_per_state(self.cfg),
                "kernel": ("jnp" if "retention_step" in self.declines
                           else "retention_step"),
            }
        if self.cfg.mamba1_layers:
            from .models import mamba1
            n, g, w, _k1 = mamba1.dims(self.cfg)
            info["mamba1"] = {
                "layers": len(self.cfg.mamba1_layers),
                "d_inner": self.cfg.mamba1_dim, "d_state": n,
                "bytes_per_state": mamba1.bytes_per_state(self.cfg),
                "state_layout": f"[rows, run_layers, {n}, {g}, {w}] "
                                "float32 a run",
                "kernel": ("jnp" if "mamba1_scan" in self.declines
                           else "mamba1_scan"),
                "scan_runs": list(self.cfg.scan_runs),
                "scan_tokens": self.hybrid.scan_tokens,
            }
        if self.cfg.shortconv_layers:
            from .models import shortconv
            info["shortconv"] = {
                "layers": len(self.cfg.shortconv_layers),
                "channels": self.cfg.embed_dim,
                "taps": self.cfg.conv_kernel,
                "bytes_per_state": shortconv.bytes_per_state(
                    self.cfg, self.dtype),
                "state_dtype": jnp.dtype(self.dtype).name,
                "conv_tokens": self.hybrid.conv_tokens,
            }
        if self.cfg.last_token_from is not None:
            info["seam"] = {
                "from_layer": self.cfg.last_token_from,
                "layers_above": (self.cfg.num_layers
                                 - self.cfg.last_token_from),
                "cross_layers": len(self.cfg.cross_layers),
                "memory_layer": self.cfg.memory_layer,
                **self.hybrid.seam,
            }
        if self.cfg.attn_layers is not None:
            info["attention"] = self.attention_describe()
        if self.cfg.latent:
            info["mla"] = self.mla_describe()
        # ISSUE 41: the scheduler's segments, and those in which a
        # sampled row's top_k / top_p engaged the candidate pool.
        info["sampler"] = dict(self._sampler)
        # What this model declined at build time, each with its reason.
        info["declines"] = dict(self.declines)
        # ISSUE 10: multi-LoRA persona provenance — the resolved
        # state, adapter store residency, per-leaf routing paths.
        info["lora"] = self.lora_describe()
        # Continuous-batching scheduler provenance (ISSUE 4): attached by
        # engine/scheduler.SessionScheduler — admit/queue/refuse counts,
        # queue depth, per-segment batch occupancy.
        sched = getattr(self, "_scheduler", None)
        if sched is not None:
            info["scheduler"] = sched.describe()
        # ISSUE 5: this engine's slice of the unified registry + flight
        # recorder state — describe() is a VIEW of the one store, not a
        # fifth parallel truth.
        from . import trace_hooks
        info["telemetry"] = trace_hooks.engine_telemetry_view(
            self.cfg.name)
        # ISSUE 6: live perf attribution — roofline ceilings, the
        # compile-cache decision, and the compile observatory's state.
        from . import compile_watch, get_compile_cache_decision
        info["perf"] = self.perf.describe()
        info["compile_cache"] = get_compile_cache_decision()
        info["compile_observatory"] = compile_watch.summary()
        # ISSUE 54: the collector's pauses since the hooks went in.
        info["gc"] = compile_watch.gc_report()
        return info


# ---------------------------------------------------------------------------
# static-analysis program registration (ISSUE 15)
# ---------------------------------------------------------------------------

from ..analysis.jaxpr_audit import (ProgramSpec, Variant,  # noqa: E402
                                    analysis_register)


def _audit_buf(layout):
    """A dispatch's packed buffer, as the trace takes it."""
    return jax.ShapeDtypeStruct((layout.size,), jnp.int32)


def _audit_carry(b: int) -> tuple:
    """What a decode segment carries to the next, over `b` rows."""
    return tuple(jax.ShapeDtypeStruct((b,), kind)
                 for _name, kind in dispatch_pack.CARRY)


def _audit_sds(x):
    """Pytree of ShapeDtypeStructs — the device-free trace argument:
    make_jaxpr abstracts by aval, so no buffer is ever materialized."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), x)


@analysis_register("engine_core")
def _analysis_engine_programs(engine) -> list:
    """Prefill + decode serving programs for the jaxpr audit
    (`roundtable lint --jaxpr`).

    The variant grid replays runtime drift the way SERVING computes its
    shapes: prefill batches are per-(batch, bucket) programs; decode
    occupancies map through `pow2_bucket` onto the warmed batch grid —
    so two occupancies in one bucket MUST trace to one jaxpr, and a
    static argument leaking occupancy shows up as an extra distinct
    jaxpr under that label (the RECOMPILE_STRICT invariant, proven
    without a device). Argument construction mirrors
    `_prefill`/`_decode_dispatch_paged`; drift between the twins fails the
    audit's trace step loudly rather than silently auditing nothing.
    """
    if not isinstance(engine, InferenceEngine) \
            or engine.hybrid is not None:
        return []       # (a hybrid engine: _analysis_hybrid_programs)
    from .serving_loop import pow2_bucket
    params = _audit_sds(engine.params)
    pools = _audit_sds(engine.kv.combined_pools())
    key = jax.random.split(jax.random.PRNGKey(0))
    num_slots = engine.kv.num_slots
    pps = engine.kv.pages_per_seq
    lora = (_audit_sds(engine.lora.stacked)
            if engine.lora is not None else None)

    def prefill_variant(b: int, bucket: int) -> Variant:
        def thunk():
            layout = dispatch_pack.prefill_layout(
                b, bucket, pps, lora=lora is not None)
            fn = engine._prefill_step_paged
            return jax.make_jaxpr(
                lambda p, pl, buf, lo: fn(p, pl, buf, layout=layout,
                                          lora=lo))(
                params, pools, _audit_buf(layout), lora)
        return Variant(label=f"b{b}x{bucket}", thunk=thunk,
                       situation=f"batch {b}, bucket {bucket}")

    def decode_variant(occ: int) -> Variant:
        b = pow2_bucket(occ)

        def thunk():
            # The packed buffer, what a segment carries, the key —
            # _decode_dispatch_paged's order.
            layout = dispatch_pack.decode_layout(
                b, pps, lora=lora is not None)
            fn = engine._decode_loop_paged
            return jax.make_jaxpr(
                lambda p, pl, buf, c, k, lo: fn(
                    p, pl, buf, c, k, layout=layout,
                    max_new=DECODE_SEGMENT, greedy=True, lora=lo))(
                params, pools, _audit_buf(layout), _audit_carry(b), key,
                lora)
        return Variant(label=f"b{b}", thunk=thunk,
                       situation=f"occupancy {occ}")

    bucket = PREFILL_BUCKETS[0]
    prefill = ProgramSpec(
        name="prefill[paged]", phase="prefill",
        variants=[prefill_variant(b, bucket)
                  for b in (1, 2) if b <= num_slots])
    decode = ProgramSpec(
        name="decode[paged]", phase="decode",
        variants=[decode_variant(occ)
                  for occ in (1, 2, 3, 4) if occ <= num_slots])
    return [prefill, decode]


@analysis_register("engine_hybrid")
def _analysis_hybrid_programs(engine) -> list:
    """The three step programs of a model with recurrent state
    (`_build_hybrid_programs`), on the same variant grids as their
    plain twins: prefill per (batch, bucket), decode occupancies through
    `pow2_bucket`, the ragged flat buffer per warmed shape with one and
    two sequences. Slot rows, snapshot indices and capture points are
    VALUES: every composition of a label must trace to one jaxpr.
    Argument construction mirrors `_hybrid_prefill` / `_hybrid_decode` /
    `_hybrid_ragged`."""
    if not isinstance(engine, InferenceEngine) or engine.hybrid is None:
        return []
    from .paged_forward import analysis_warm_seqs
    from .serving_loop import build_ragged_batch, pow2_bucket
    hy, kv = engine.hybrid, engine.kv
    params = _audit_sds(engine.params)
    pools = _audit_sds(kv.combined_pools())
    state, snaps = _audit_sds(hy.state), _audit_sds(hy.snaps)
    key = jax.random.split(jax.random.PRNGKey(0))
    pps = kv.pages_per_seq

    def prefill_variant(b: int, bucket: int) -> Variant:
        def thunk():
            layout = dispatch_pack.prefill_layout(b, bucket, pps,
                                                  hybrid=True)
            fn = engine._prefill_step_hybrid
            return jax.make_jaxpr(
                lambda p, pl, st, sn, buf: fn(p, pl, st, sn, buf,
                                              layout=layout))(
                params, pools, state, snaps, _audit_buf(layout))
        return Variant(label=f"b{b}x{bucket}", thunk=thunk,
                       situation=f"batch {b}, bucket {bucket}")

    def decode_variant(occ: int) -> Variant:
        b = pow2_bucket(occ)

        def thunk():
            layout = dispatch_pack.decode_layout(b, pps, rows=True)
            fn = engine._decode_loop_hybrid
            return jax.make_jaxpr(
                lambda p, pl, st, buf, c, k: fn(
                    p, pl, st, buf, c, k, layout=layout,
                    max_new=DECODE_SEGMENT, greedy=True))(
                params, pools, state, _audit_buf(layout), _audit_carry(b),
                key)
        return Variant(label=f"b{b}", thunk=thunk,
                       situation=f"occupancy {occ}")

    def ragged_variant(shape: int, n_seqs: int) -> Variant:
        def thunk():
            b = build_ragged_batch(
                analysis_warm_seqs(engine, n_seqs), t_budget=shape,
                s_max=kv.num_slots + 1, pages_per_seq=pps,
                scratch_page=kv.scratch_page(0),
                pad_id=engine.tokenizer.pad_id, page_size=kv.page_size)
            layout = engine._ragged_layout(b)
            fn = engine._ragged_step_hybrid
            return jax.make_jaxpr(
                lambda p, pl, st, sn, buf, k: fn(
                    p, pl, st, sn, buf, k, layout=layout, greedy=True,
                    attn_path=("kernel" if engine.ragged_path
                               == "pallas_ragged" else "xla")))(
                params, pools, state, snaps, _audit_buf(layout), key)
        return Variant(label=f"t{shape}", thunk=thunk,
                       situation=f"{n_seqs} seq(s) in shape {shape}")

    num_slots = kv.num_slots
    specs = [
        ProgramSpec(name="prefill[hybrid]", phase="prefill",
                    variants=[prefill_variant(b, PREFILL_BUCKETS[0])
                              for b in (1, 2) if b <= num_slots]),
        ProgramSpec(name="decode[hybrid]", phase="decode",
                    variants=[decode_variant(occ)
                              for occ in (1, 2, 3, 4) if occ <= num_slots]),
    ]
    if engine.ragged_enabled:
        specs.append(ProgramSpec(
            name="ragged[hybrid]", phase="ragged",
            variants=[ragged_variant(shape, n)
                      for shape in engine.ragged_shapes for n in (1, 2)]))
    return specs
