"""Transformer core shared by Gemma / Llama / Mistral — pure functional JAX.

This is the TPU-native replacement for the llama.cpp compute the reference
reaches through Ollama/LM Studio (reference src/adapters/local-llm.ts;
SURVEY.md §2.3). Design rules (SURVEY.md §7, pallas_guide):

- params are plain nested-dict pytrees (no framework state), so sharding is
  a pure tree_map of NamedSharding over the same structure
- everything below `jit` is static-shape, scan/cond only — no Python control
  flow on data
- matmuls run in bf16 with f32 accumulation (preferred_element_type), norms
  and softmax in f32: MXU-friendly, numerically safe
- attention is GQA with an explicit KV-cache slot axis; decode attends with
  a length mask instead of dynamic shapes
- architecture differences (GeGLU vs SiLU, embedding scaling, RMSNorm +1,
  sliding window, logit softcap) are ModelConfig flags, not subclasses
"""

from __future__ import annotations

import dataclasses
import functools
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

Params = dict[str, Any]

# Masked-attention-logit sentinel — finite (not -inf) so a fully-masked row
# softmaxes to uniform instead of NaN. Shared with the Pallas kernels so
# dense and flash masking semantics cannot drift apart.
MASK_VALUE = -2.3819763e38

# SPMD mesh context: the engine sets this (at trace time, inside its jit'd
# programs) so attention() can wrap the Pallas kernels in shard_map on a
# multi-device mesh. A trace-time Python context, not a traced value — the
# mesh is static per compiled program. Thread-local because distinct
# engines (fleet submeshes) trace concurrently from different threads —
# a shared stack would hand one engine's mesh to another's trace.
import threading as _threading

_MESH_CTX = _threading.local()


class spmd_mesh:
    """Context manager announcing the mesh the enclosing jit traces
    under. `int4_sink`, when given, is a dict the int4 einsum dispatch
    records path provenance into at TRACE time (one entry per distinct
    (spec, shapes) dispatch — see _record_int4): engines pass their own
    dict so describe()/stats can report which path each compiled
    dispatch actually took."""

    def __init__(self, mesh, int4_sink=None):
        self.mesh = mesh
        self.int4_sink = int4_sink

    def __enter__(self):
        stack = getattr(_MESH_CTX, "stack", None)
        if stack is None:
            stack = _MESH_CTX.stack = []
        stack.append((self.mesh, self.int4_sink))
        return self.mesh

    def __exit__(self, *exc):
        _MESH_CTX.stack.pop()
        return False


def current_spmd_mesh():
    stack = getattr(_MESH_CTX, "stack", None)
    return stack[-1][0] if stack else None


def _current_int4_sink():
    stack = getattr(_MESH_CTX, "stack", None)
    return stack[-1][1] if stack else None


# --- a layer's body is a function `jax.jit` has seen (ISSUE 55) ---
#
# A step program walks its layers in Python. Traced where they stand,
# n equal blocks are n traces of the same norms, projections, mixer and
# kernel wrapper, and n lowerings of every `pallas_call` among them — a
# program's set-up grows with its depth (PERF.md, Findings PR 54, 55).
# Behind `layer_body` a block is ONE jitted function of (the residual
# stream, that layer's leaves, that layer's pools or state, the
# dispatch's arrays) with all that is static about it passed as static,
# hashable arguments: JAX's trace cache returns the first layer's jaxpr
# for every later layer of the same signature, the lowering emits one
# private function and calls it, and XLA inlines the calls — the
# compiled program, the trees, the pools and the donation stay what
# they were.
#
# THE RULE A NEW BLOCK KIND FOLLOWS: its body closes over nothing a
# layer owns and nothing an enclosing trace made (no tracer, no
# per-layer closure: a fresh closure a layer is a fresh cache key);
# whatever differs between two layers is an ARGUMENT — arrays (and
# None) positional, the rest (`ModelConfig` or `cfg.attention_layer(i)`'s
# frozen view, a kind, a page size) by keyword, named in `static`, and
# hashable. Two layers that differ in something static are two
# signatures and two traces: the arguments say so, nothing tests a
# model's name. Whatever else its code reads while it is traced — an
# environment lever, a kernel module's `_interpret` — is listed in
# `_switches`, which is part of every body's key.


class _ById:
    """A trace-time sink (an engine's own dict) as a static argument:
    equal only to itself, so two engines never share a trace whose
    records went into one of them."""

    __slots__ = ("ref",)

    def __init__(self, ref):
        self.ref = ref

    def __hash__(self):
        return id(self.ref)

    def __eq__(self, other):
        return isinstance(other, _ById) and other.ref is self.ref


def _switches() -> tuple:
    """Every switch a body's trace reads that is neither an argument nor
    a scope: the two A/B levers (ROUNDTABLE_INT4_MM, ROUNDTABLE_LORA_MM)
    and whether each kernel module interprets its kernels (off the chip;
    a compile test patches it). Read when the body is CALLED and part of
    its static key, so flipping one between two calls of the same shapes
    is another signature and another trace, never the first one's."""
    from ..pallas import attention, grouped, int4mm, retention
    from ..pallas import lora as plora
    return (int4mm.enabled(), plora.enabled()) + tuple(
        m._interpret()
        for m in (attention, grouped, int4mm, plora, retention))


def _announced():
    """What a body's trace reads besides its arguments: (the static part
    — the mesh, the two provenance sinks by identity, the adapter
    stack's quantization, the trace-time switches — and the arrays of
    the lora scope, which the body takes as an argument and announces
    again as its own)."""
    from ..lora import _current_scope
    stack = getattr(_MESH_CTX, "stack", None)
    mesh, sink = stack[-1] if stack else (None, None)
    scope = _current_scope()
    if scope is None:
        return (mesh, _ById(sink), None, _switches()), None
    return (mesh, _ById(sink), (_ById(scope.sink), scope.quant),
            _switches()), scope.payload


# Whether the innermost body call on this thread ran its Python: JAX's
# trace cache missed, and the body was traced (`layer_body`).
_BODY = _threading.local()


def layer_body(static: tuple[str, ...] = ()):
    """Decorator: `fn(*arrays, **static)` as a layer's body (the rule
    above). A call under a program's trace counts itself on the compile
    watch's set-up table, once: as traced if the call ran `fn`, else as
    reused (`bodies_traced` / `bodies_reused` of that program's row).
    `fn` itself stays at `__wrapped__`."""

    def wrap(fn):
        from .. import compile_watch

        def body(lora, *args, _scopes, **kw):
            _BODY.traced = True
            if _scopes[2] is None:
                return fn(*args, **kw)
            from ..lora import lora_scope
            sink, quant = _scopes[2]
            with lora_scope(lora, sink=sink.ref, quant=quant):
                return fn(*args, **kw)

        body.__name__ = body.__qualname__ = fn.__name__
        jitted = jax.jit(body, static_argnames=static + ("_scopes",))

        @functools.wraps(fn)
        def call(*args, **kw):
            scopes, lora = _announced()
            # (a body called from a body's trace: the outer's flag is
            # put back when this call is done)
            outer, _BODY.traced = getattr(_BODY, "traced", False), False
            try:
                out = jitted(lora, *args, _scopes=scopes, **kw)
                compile_watch.note_body(_BODY.traced)
            finally:
                _BODY.traced = outer
            return out

        return call

    return wrap


# Path-provenance labels for int4 einsum dispatches (ISSUE 3): the next
# hardware window's numbers must be attributable to the kernel, not a
# silent fallback, so every Int4Leaf dispatch records which path it
# compiled to — into the engine-owned sink the enclosing spmd_mesh
# carries.
PATH_KERNEL = "pallas_w4a16"
PATH_XLA = "xla_dequant"


def _record_int4(spec: str, a, leaf, path: str, reason=None) -> None:
    sink = _current_int4_sink()
    if sink is None:
        return
    entry = {"spec": spec, "a_shape": list(a.shape),
             "w_shape": list(leaf.q4.shape[:-1]) + [leaf.q4.shape[-1] * 2],
             "path": path}
    if reason:
        entry["fallback_reason"] = reason
    sink[(spec, tuple(a.shape), tuple(leaf.q4.shape))] = entry


@dataclasses.dataclass(frozen=True)
class AttnLayer:
    """The geometry of ONE attention layer where the layers of a model
    differ (`ModelConfig.attn_layers`): its query heads over the model's
    kv heads, its window (None: causal and unbounded) and its rotary
    table. The fields are ModelConfig's own, a layer's worth."""

    num_heads: int
    sliding_window: Optional[int] = None
    rope_theta: float = 10_000.0
    rotary_dim: int = 0
    rope_yarn: Optional[tuple[float, ...]] = None
    rope_attention_factor: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters + family behavior flags."""

    name: str
    vocab_size: int
    num_layers: int
    embed_dim: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    mlp_dim: int
    max_seq_len: int = 8192
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6
    # family flags
    gelu_mlp: bool = False            # Gemma: GeGLU; Llama/Mistral: SiLU
    scale_embeddings: bool = False    # Gemma: embeddings *= sqrt(embed_dim)
    rmsnorm_unit_offset: bool = False  # Gemma: weight is (1 + w)
    post_attn_norm: bool = False      # Gemma2-style extra norms
    post_mlp_norm: bool = False
    attn_logit_softcap: Optional[float] = None   # Gemma2: 50.0
    final_logit_softcap: Optional[float] = None  # Gemma2: 30.0
    sliding_window: Optional[int] = None         # Mistral: 4096
    query_pre_attn_scalar: Optional[float] = None  # Gemma: head_dim**-0.5 default
    attn_bias: bool = False           # Qwen2: bias on q/k/v projections
    tie_embeddings: bool = True       # output head = embedding table
    # MoE (Mixtral): None = dense MLP; X experts, top-k routed
    num_experts: Optional[int] = None
    num_experts_per_tok: int = 2
    # runtime implementation choice, not architecture: "dense" = XLA einsum
    # attention; "flash" = Pallas blockwise kernels (engine/pallas/) that
    # stream KV through VMEM and skip blocks beyond each row's valid length
    attn_impl: str = "dense"
    # Hybrid decoders (models/hybrid.py): one kind a layer, each layer ONE
    # mixer behind one norm and a residual — "mamba2" | "mamba1" |
    # "experts" | "attention" | "mlp" | "retention" |
    # "shortconv" | "cross" | "gmu". None = the attention-plus-MLP block
    # above, untouched. A pre-norm block whose two halves have a norm each
    # IS two such layers (attention, then mlp or experts): `axk1`.
    # "cross" and "gmu" keep NOTHING: a cross layer has a query and an
    # out-projection and reads the pages of the nearest attention layer
    # below it (under the model-level `sliding_window`, not that layer's:
    # None is causal and unbounded); a gated memory unit reads `m`, the
    # scan output of the last Mamba-1 layer below it, which rides beside
    # the residual stream (engine/paged_forward.py: the carried tuple).
    layer_kinds: Optional[tuple[str, ...]] = None
    rope: bool = True                 # False: no position embedding at all
    # Layers from this index on keep nothing (no pages, no state), so in
    # a join — a prologue chunk, a ragged step — only each row's LAST
    # token runs them: the step programs gather the carried tuple there
    # (engine/paged_forward.py, "the seam"). None: every layer runs every
    # token. Decode, one token a row, is the same either way.
    last_token_from: Optional[int] = None
    # The norm ahead of every mixer and the final one: a LayerNorm
    # (mean and variance, weight `norm` AND bias `norm_b`) in place of
    # the RMS norm.
    layer_norm: bool = False
    # Differential attention (models/diffattn.py) in every "attention"
    # and "cross" layer: query heads (2j, 2j+1) over the kv head pair
    # j // 2, two softmaxes, a subtraction and a norm a pair.
    diff_attn: bool = False
    # Mamba-2 mixer
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    conv_kernel: int = 4
    mamba_chunk: int = 128
    # Mamba-1 mixer ("mamba1", models/mamba1.py): a decay for every
    # (state index, channel) pair. Its inner width and the rank of the
    # projection its step size comes through; `ssm_state` and
    # `conv_kernel` above are shared with Mamba-2.
    mamba1_dim: int = 0
    dt_rank: int = 0
    # dl, B and C each through an RMS norm of its own (`jamba`'s
    # addition to Mamba-1); False: straight from W_x to W_dt and the scan.
    mamba1_norms: bool = True
    # Routed + shared experts, as ONE chip's share of an expert-parallel
    # group: the router scores all `routed_experts`; this chip computes
    # ids [expert_offset, expert_offset + experts_held).
    routed_experts: int = 0
    experts_held: int = 0
    expert_offset: int = 0
    moe_top_k: int = 0
    expert_dim: int = 0
    shared_expert_dim: int = 0
    routed_scaling: float = 1.0
    # "sigmoid_bias_topk" | "sigmoid_topk" (no bias) | "softmax_topk"
    # (models/hybrid.py: route says which model uses which)
    router_rule: str = "sigmoid_bias_topk"
    expert_act: str = "relu2"                # | "silu"
    expert_gated: bool = False               # (act(x W_gate) * x W_up) W_down
    # Multi-head latent attention (models/mla.py), on where kv_lora_rank
    # > 0: a page holds, a position, the normed c_kv (kv_lora_rank) and
    # the roped shared key part (qk_rope_dim) — one "head", no values.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # YaRN: (factor, original_max_position_embeddings, beta_fast,
    # beta_slow, mscale, mscale_all_dim), or None for plain rope.
    rope_yarn: Optional[tuple[float, ...]] = None
    # Grouped-query attention layers of a model with `layer_kinds`:
    # rotary embedding over the FIRST rotary_dim dimensions of a head,
    # the rest passed through (0: all of head_dim); the multiplier YaRN
    # puts on cos and sin, where the config gives one (None: 0.1
    # ln(factor) + 1); and a sigmoid gate on the attention output before
    # the out-projection, one logit a head from the layer's normed input.
    rotary_dim: int = 0
    rope_attention_factor: Optional[float] = None
    attn_gate: bool = False
    # RMS norm of every q and k head (weights `q_norm`, `k_norm`
    # [head_dim]) ahead of the rotary embedding.
    qk_norm: bool = False
    # Attention layers that differ from one another: one AttnLayer an
    # attention layer, in order (`attention_layers`), each overriding
    # num_heads, sliding_window and the rotary fields above for its
    # layer (`attention_layer`). The kv heads, head_dim and so the page
    # pools are the model's: one pool shape, one page table a sequence.
    attn_layers: Optional[tuple[AttnLayer, ...]] = None

    @property
    def kv_repeat(self) -> int:
        return self.num_heads // self.num_kv_heads

    def _layers_of(self, kind: str) -> tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_kinds or ())
                     if k == kind)

    @property
    def latent(self) -> bool:
        """Attention layers keep latent pages (models/mla.py)."""
        return self.kv_lora_rank > 0

    @property
    def lane_pack(self) -> int:
        """Heads of one token that share a 128-lane row of a page: 2
        where the heads are 64 wide and come in pairs (pallas/
        attention.py, "heads narrower than a lane row"), else 1. (A
        quantized pool keeps plain [K, D] cells, its scales a head's:
        engine/paging.py.)"""
        from ..pallas.attention import lane_pack
        if self.latent:
            return 1
        return lane_pack(self.num_kv_heads, self.head_dim)

    @property
    def page_heads(self) -> int:
        """The kv heads a page holds, as the paged kernels see them:
        rows of `lane_pack` heads each."""
        return 1 if self.latent else self.num_kv_heads // self.lane_pack

    @property
    def page_width(self) -> int:
        """The cells of one head of one position of a page: head_dim
        (times `lane_pack`: a whole lane row), or a latent entry
        (kv_lora_rank + qk_rope_dim) padded to whole lane rows — 576 is
        4.5 of them, and the kernels copy and multiply pages as they
        lie."""
        if not self.latent:
            return self.head_dim * self.lane_pack
        return -(-(self.kv_lora_rank + self.qk_rope_dim) // 128) * 128

    @property
    def page_cells(self) -> int:
        """Cells one position costs one attention layer's pools: keys
        and values of every kv head, or one latent entry."""
        if self.latent:
            return self.page_width
        return 2 * self.num_kv_heads * self.head_dim

    @property
    def recurrent(self) -> bool:
        """Some layer keeps state that is not pages."""
        return bool(self._layers_of("mamba2")
                    or self._layers_of("retention")
                    or self._layers_of("mamba1")
                    or self._layers_of("shortconv"))

    @property
    def retention_layers(self) -> tuple[int, ...]:
        return self._layers_of("retention")

    @property
    def mamba_layers(self) -> tuple[int, ...]:
        return self._layers_of("mamba2")

    @property
    def mamba1_layers(self) -> tuple[int, ...]:
        return self._layers_of("mamba1")

    @property
    def shortconv_layers(self) -> tuple[int, ...]:
        return self._layers_of("shortconv")

    @property
    def layer_runs(self) -> tuple[tuple[tuple[str, ...], int], ...]:
        """`layer_kinds` as runs: (the kinds of one block, how many such
        blocks follow one another). A run of Mamba-1 blocks — the mixer
        and the MLP behind it, where one follows — is ONE `lax.scan`
        over parameters and state stacked along a leading layer axis
        (engine/paged_forward.py); every other layer is a run of one
        and is traced where it stands."""
        return _layer_runs(self.layer_kinds or ())

    @property
    def scan_runs(self) -> tuple[int, ...]:
        """The lengths of the scanned runs, in order."""
        return tuple(n for kinds, n in self.layer_runs
                     if kinds[0] == "mamba1")

    @property
    def expert_layers(self) -> tuple[int, ...]:
        return self._layers_of("experts")

    @property
    def cross_layers(self) -> tuple[int, ...]:
        """The layers that read pages they do not own."""
        return self._layers_of("cross")

    @property
    def memory_layer(self) -> Optional[int]:
        """The Mamba-1 layer whose scan output `m` the gated memory
        units read: the last one below the first of them (None: the
        model has no such unit)."""
        units = self._layers_of("gmu")
        if not units:
            return None
        return max(i for i in self.mamba1_layers if i < units[0])

    @property
    def attention_layers(self) -> tuple[int, ...]:
        """The layers that own KV pages (every layer of a plain model)."""
        if self.layer_kinds is None:
            return tuple(range(self.num_layers))
        return self._layers_of("attention")

    def attention_layer(self, ai: int) -> "ModelConfig":
        """This config as the `ai`-th attention layer sees it: itself,
        or with that layer's own geometry in the model-level fields."""
        if self.attn_layers is None:
            return self
        return _attention_layer_view(self, ai)

    @property
    def attention_views(self) -> tuple["ModelConfig", ...]:
        """`attention_layer(ai)` of every attention layer, in order."""
        return tuple(self.attention_layer(ai)
                     for ai in range(len(self.attention_layers)))

    @property
    def attention_classes(self) -> tuple[tuple[int, Optional[int], int],
                                         ...]:
        """The distinct (num_heads, sliding_window) among the attention
        layers, each with how many layers have it: one lowering of each
        paged kernel a class, one class of page visits."""
        return _attention_classes(self)

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        return self.mamba_d_inner + 2 * self.ssm_groups * self.ssm_state


@functools.lru_cache(maxsize=None)
def _layer_runs(kinds: tuple[str, ...]) -> tuple:
    runs, i = [], 0
    while i < len(kinds):
        if kinds[i] != "mamba1":
            runs.append(((kinds[i],), 1))
            i += 1
            continue
        block = kinds[i:i + 2] if kinds[i + 1:i + 2] == ("mlp",) \
            else kinds[i:i + 1]
        n = 1
        while kinds[i + n * len(block):i + (n + 1) * len(block)] == block:
            n += 1
        runs.append((block, n))
        i += n * len(block)
    return tuple(runs)


@functools.lru_cache(maxsize=None)
def _attention_layer_view(cfg: ModelConfig, ai: int) -> ModelConfig:
    return dataclasses.replace(
        cfg, attn_layers=None, **dataclasses.asdict(cfg.attn_layers[ai]))


@functools.lru_cache(maxsize=None)
def _attention_classes(cfg: ModelConfig) -> tuple:
    counts: dict = {}
    for v in cfg.attention_views:
        key = (v.num_heads, v.sliding_window)
        counts[key] = counts.get(key, 0) + 1
    return tuple(k + (n,) for k, n in counts.items())


# --- primitives ---


def rms_norm(x: jax.Array, weight: jax.Array, eps: float,
             unit_offset: bool) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    w = (1.0 + weight.astype(jnp.float32)) if unit_offset \
        else weight.astype(jnp.float32)
    return (x * w).astype(dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         inv_freq: Optional[jax.Array] = None,
         scale: float = 1.0) -> jax.Array:
    """Rotary position embedding. x: [B, T, H, D], positions: [B, T].
    `inv_freq` [D/2], where given, replaces theta's own frequencies
    (YaRN's blend, `yarn_inv_freq`); `scale` multiplies cos and sin
    (YaRN's attention factor, as `transformers` applies it)."""
    head_dim = x.shape[-1]
    pos = positions[..., None].astype(jnp.float32)
    if inv_freq is None:
        fraction = jnp.arange(0, head_dim // 2,
                              dtype=jnp.float32) / (head_dim // 2)
        angles = pos / theta ** fraction                # [B,T,D/2]
    else:
        angles = pos * inv_freq
    angles = angles[:, :, None, :]                      # [B, T, 1, D/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if scale != 1.0:
        sin, cos = sin * scale, cos * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max: float, beta_fast: float,
                  beta_slow: float):
    """[dim/2] rotary frequencies: theta's own where a dimension turns
    more than beta_fast times over the original context, theta's / factor
    where it turns less than beta_slow times, a linear ramp between."""
    import numpy as np

    def correction_dim(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope_heads(x: jax.Array, positions: jax.Array,
               cfg: ModelConfig) -> jax.Array:
    """The rotary embedding of one grouped-query attention layer over
    q or k [B, T, H, D], by `cfg`'s rotary fields (a layer's own where
    layers differ: ModelConfig.attention_layer): the first
    `rotary_dim` dimensions turn, dimension j paired with j +
    rotary_dim / 2, the rest pass through unscaled; YaRN's blended
    frequencies and its factor on cos and sin where `rope_yarn` is
    given (the first four entries; the factor is
    `rope_attention_factor`, else 0.1 ln(factor) + 1)."""
    d = x.shape[-1]
    rot = cfg.rotary_dim or d
    if cfg.rope_yarn is None and rot == d:
        return rope(x, positions, cfg.rope_theta)
    inv_freq, scale = None, 1.0
    if cfg.rope_yarn is not None:
        factor, original_max, fast, slow = cfg.rope_yarn[:4]
        inv_freq = jnp.asarray(yarn_inv_freq(
            rot, cfg.rope_theta, factor, original_max, fast, slow))
        scale = (cfg.rope_attention_factor
                 if cfg.rope_attention_factor is not None
                 else yarn_mscale(factor, 1.0))
    turned = rope(x[..., :rot], positions, cfg.rope_theta,
                  inv_freq=inv_freq, scale=scale)
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def _softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


@dataclasses.dataclass
class Int4Leaf:
    """Packed w4a16 weight (engine/quant.py, bits=4): two SIGNED nibbles
    per int8 byte along the weight's LAST axis (even element in the low
    nibble), with per-`group` absmax scales — `s4` has q4's logical
    shape except the last axis holds n_groups. Dequantization
    (`dequant_int4`) is `lax.bitcast_convert_type(int8 → 2×int4)` —
    whose nibble pair expands minor-most, exactly matching the last-axis
    pack — followed by convert, minor-dim reshapes, and the grouped
    scale multiply: no shifts, no interleaving shuffle, so XLA/Mosaic
    fuses the chain into the consuming matmul's operand read and HBM
    streams the PACKED bytes: ~4.25 bits/param vs int8's 8 — llama.cpp's
    own default serving precision class (reference adapters go through
    4-bit GGUF). An earlier revision packed along the einsum-contracted
    axis and unpacked with a stack+reshape interleave; on real TPU that
    shuffle broke operand fusion and decode measured SLOWER than bf16
    (22.9 tok/s vs bf16's 130 — measured once before PR 1; not
    re-measured) — the last-axis/bitcast layout exists to keep the
    unpack inside the matmul fusion.

    `axis` is always q4.ndim-1 at pack time. axis/group are static
    pytree metadata (register_dataclass), so tree_map / sharding /
    param-byte accounting see only q4/s4 arrays.
    """

    q4: jax.Array
    s4: jax.Array
    axis: int
    group: int


jax.tree_util.register_dataclass(
    Int4Leaf, data_fields=("q4", "s4"), meta_fields=("axis", "group"))


def dequant_int4(q4: jax.Array, s4: jax.Array, axis: int, group: int,
                 dtype) -> jax.Array:
    """Unpack + scale a last-axis int4-packed weight back to `dtype`.

    bitcast int8 → [..., 2]·int4 puts the low nibble at [..., 0], which
    is exactly the even-low/odd-high pack order, so the unpack is a
    bitcast + convert + minor-dim merge — every reshape here touches
    only trailing dims, so the whole chain stays fusable into the
    consuming matmul operand on TPU (no cross-lane shuffle). `axis`
    must be the last axis (the only layout the packer emits). On jax
    runtimes whose int8→int4 bitcast cannot lower (0.4.x), the compat
    seam substitutes a shift/stack unpack with identical numerics
    (compat.unpack_int4_pairs)."""
    assert axis == q4.ndim - 1, "int4 pack axis must be minor-most"
    from ..compat import unpack_int4_pairs
    pairs = unpack_int4_pairs(q4)                        # [..., n/2, 2]
    shape = list(q4.shape)
    shape[-1] *= 2
    w = pairs.astype(dtype).reshape(shape)               # [..., n]
    grouped = shape[:-1] + [shape[-1] // group, group]
    w = w.reshape(grouped) * s4[..., None].astype(dtype)
    return w.reshape(shape)


def _einsum(spec: str, a: jax.Array, b, tp=None, lora=None) -> jax.Array:
    # bf16 inputs, f32 accumulation on the MXU. An int8-quantized weight
    # ({"q", "s"} dict, engine/quant.py) streams half the HBM bytes: the
    # int8→activation-dtype convert fuses into the matmul operand and the
    # per-output-channel scale applies to the OUTPUT (the scale axes are
    # the weight's non-contracted axes, which land trailing). An int4
    # leaf streams a quarter: its grouped dequant is elementwise, so it
    # rides the same operand fusion.
    #
    # `tp` is the call site's TP convention hint for the shard-aware
    # int4 kernel dispatch — "col" (column-parallel: q/k/v, gate/up,
    # lm head) or "row" (row-parallel: o_proj, down_proj), mirroring
    # sharding.param_specs (see sharding.int4_shard_axis). Ignored for
    # every non-int4 leaf and on single-device meshes.
    #
    # `lora` names this call site's LoRA target leaf (ISSUE 10):
    # when the enclosing trace announced a lora_scope (engine/lora.py,
    # the spmd_mesh pattern), the per-row/per-token adapter delta
    # `x·A_id^T·B_id` is added to the base output — grouped Pallas
    # kernel or XLA grouped BMM, every routing decision recorded into
    # the engine's lora_paths sink. Untagged call sites (lm head, MoE
    # experts, router) and traces with no active scope are untouched.
    y = _einsum_base(spec, a, b, tp)
    if lora is not None:
        from ..lora import apply_current
        y = apply_current(lora, a, y, tp=tp)
    return y


def _einsum_base(spec: str, a: jax.Array, b, tp=None) -> jax.Array:
    if isinstance(b, Int4Leaf):
        # Fused VMEM-dequant kernels — the only layout that actually
        # streams packed int4 bytes on real TPU (pallas/int4mm.py; XLA
        # materializes this dequant — measured once before PR 1; not
        # re-measured). Gate: the kernel is emitted ONLY where the
        # enclosing program explicitly announced
        # its mesh (spmd_mesh — every engine jit does). A 1-device mesh
        # dispatches the raw kernel; a multi-device mesh goes through
        # einsum_int4_spmd, which re-partitions the matmul and runs the
        # kernel per shard inside shard_map — a bare pallas_call under
        # GSPMD would be an opaque, unpartitionable custom call. Traces
        # with NO announced mesh keep the XLA path: "no context" must
        # never be mistaken for "single device". Every routing decision
        # is recorded into the engine's provenance sink.
        mesh = current_spmd_mesh()
        from ..pallas import int4mm
        if mesh is None:
            # No context ⇒ no sink either (they share the stack entry),
            # so this fallback is inherently unattributed — engines
            # always announce, so only direct forward() callers land
            # here.
            pass
        elif not int4mm.enabled():
            _record_int4(spec, a, b, PATH_XLA, "kernel-disabled")
        else:
            if mesh.size == 1:
                y, reason = int4mm.einsum_int4_or_reason(spec, a, b)
            else:
                y, reason = int4mm.einsum_int4_spmd(mesh, spec, a, b,
                                                    tp=tp)
            if y is not None:
                _record_int4(spec, a, b, PATH_KERNEL)
                return y
            _record_int4(spec, a, b, PATH_XLA, reason)
        return jnp.einsum(spec, a,
                          dequant_int4(b.q4, b.s4, b.axis, b.group,
                                       a.dtype),
                          preferred_element_type=jnp.float32)
    if isinstance(b, dict) and "q" in b:
        y = jnp.einsum(spec, a, b["q"].astype(a.dtype),
                       preferred_element_type=jnp.float32)
        s = b["s"].astype(jnp.float32)
        return y * s.reshape((1,) * (y.ndim - s.ndim) + s.shape)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def embed_tokens(emb, tokens: jax.Array) -> jax.Array:
    """Embedding lookup; quantized tables dequantize per looked-up row.
    The result's dtype follows the param dtype (s carries it)."""
    if isinstance(emb, Int4Leaf):
        # rows gather keeps the packed axis (1 → tokens.ndim after the
        # gather); dequant only the looked-up rows
        rows_q = emb.q4[tokens]
        rows_s = emb.s4[tokens]
        return dequant_int4(rows_q, rows_s, tokens.ndim, emb.group,
                            emb.s4.dtype)
    if isinstance(emb, dict) and "q" in emb:
        rows = emb["q"][tokens].astype(emb["s"].dtype)
        return rows * emb["s"][tokens][..., None]
    return emb[tokens]


def project_qkv(
    x: jax.Array,                 # [B, T, E]
    layer: Params,
    cfg: ModelConfig,
    positions: jax.Array,         # [B, T] absolute positions
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """QKV projection + rope + query scaling.

    Shared by dense attention below and the sequence-parallel cores in
    longcontext.py (which replace only the softmax(QK)V part)."""
    q = _einsum("bte,ehd->bthd", x, layer["q_proj"], tp="col",
                lora="q_proj")                                  # [B,T,H,D]
    k = _einsum("bte,ekd->btkd", x, layer["k_proj"], tp="col",
                lora="k_proj")                                  # [B,T,K,D]
    v = _einsum("bte,ekd->btkd", x, layer["v_proj"], tp="col",
                lora="v_proj")

    if cfg.attn_bias:  # Qwen2: linear bias applied BEFORE rotary (HF order)
        q = q + layer["q_bias"].astype(jnp.float32)
        k = k + layer["k_bias"].astype(jnp.float32)
        v = v + layer["v_bias"].astype(jnp.float32)

    if cfg.qk_norm:
        q = rms_norm(q, layer["q_norm"], cfg.norm_eps, False)
        k = rms_norm(k, layer["k_norm"], cfg.norm_eps, False)
    if cfg.rope:
        q = rope_heads(q.astype(x.dtype), positions, cfg)
        k = rope_heads(k.astype(x.dtype), positions, cfg)
    else:
        q, k = q.astype(x.dtype), k.astype(x.dtype)
    v = v.astype(x.dtype)

    scale = (cfg.query_pre_attn_scalar
             if cfg.query_pre_attn_scalar is not None
             else cfg.head_dim ** -0.5)
    return q * scale, k, v


def gate_heads(out: jax.Array, x: jax.Array, layer: Params,
               cfg: ModelConfig) -> jax.Array:
    """`cfg.attn_gate`: the attention result [B, T, H, D] times
    sigmoid(x W_g) — x the layer's normed input [B, T, E], W_g [E, H],
    one logit a head — before the out-projection."""
    if not cfg.attn_gate:
        return out
    g = _einsum("bte,eh->bth", x, layer["g_proj"])[..., None]
    return (out.astype(jnp.float32) * jax.nn.sigmoid(g)).astype(out.dtype)


def attention(
    x: jax.Array,                 # [B, T, E]
    layer: Params,
    cfg: ModelConfig,
    positions: jax.Array,         # [B, T] absolute positions
    kv_cache: Optional[tuple[jax.Array, jax.Array]],  # each [B, S, K, D]
    cache_offset: Optional[jax.Array],  # [B] write offset into the cache
    attn_mask: jax.Array,         # [B, T, S] boolean, True = attend
    kv_valid: Optional[jax.Array] = None,  # [B] valid entries after step
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """GQA attention with in-place cache update.

    Returns (output [B,T,E], updated (k_cache, v_cache)). When kv_cache is
    None the k/v of this call form the cache (prefill from scratch).
    """
    q, k, v = project_qkv(x, layer, cfg, positions)

    if kv_cache is not None:
        k_cache, v_cache = kv_cache
        # Scatter this step's K/V into each batch row at its own offset.
        def write_row(cache_row, new_row, off):
            return jax.lax.dynamic_update_slice(
                cache_row, new_row, (off, 0, 0))
        k_cache = jax.vmap(write_row)(k_cache, k, cache_offset)
        v_cache = jax.vmap(write_row)(v_cache, v, cache_offset)
        k_all, v_all = k_cache, v_cache
    else:
        k_all, v_all = k, v
        k_cache, v_cache = k, v

    if cfg.attn_impl == "flash" and kv_valid is not None:
        from ..pallas import attention as pattn
        t = q.shape[1]
        out = None
        mesh = current_spmd_mesh()
        if mesh is not None and mesh.size > 1:
            # multi-device: kernels under shard_map (kv heads on "model",
            # rows on "data"); None = not partitionable → dense below
            out = pattn.flash_attention_spmd(
                mesh, q, k_all, v_all, positions[:, 0], kv_valid,
                sliding_window=cfg.sliding_window,
                softcap=cfg.attn_logit_softcap)
        elif pattn.supported(t, k_all.shape[1], cfg.head_dim,
                             cfg.num_kv_heads):
            if t > 1:
                out = pattn.flash_prefill_attention(
                    q, k_all, v_all, positions[:, 0], kv_valid,
                    sliding_window=cfg.sliding_window,
                    softcap=cfg.attn_logit_softcap)
            else:
                out = pattn.ragged_decode_attention(
                    q, k_all, v_all, kv_valid,
                    sliding_window=cfg.sliding_window,
                    softcap=cfg.attn_logit_softcap)
        if out is not None:
            out = _einsum("bthd,hde->bte", gate_heads(out, x, layer, cfg),
                          layer["o_proj"],
                          tp="row", lora="o_proj").astype(x.dtype)
            return out, (k_cache, v_cache)

    # GQA: expand K/V heads to match query heads.
    if cfg.kv_repeat > 1:
        k_att = jnp.repeat(k_all, cfg.kv_repeat, axis=2)
        v_att = jnp.repeat(v_all, cfg.kv_repeat, axis=2)
    else:
        k_att, v_att = k_all, v_all

    logits = _einsum("bthd,bshd->bhts", q, k_att)        # [B,H,T,S] f32
    logits = _softcap(logits, cfg.attn_logit_softcap)
    logits = jnp.where(attn_mask[:, None, :, :], logits, MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    out = _einsum("bhts,bshd->bthd", probs, v_att).astype(x.dtype)
    out = _einsum("bthd,hde->bte", gate_heads(out, x, layer, cfg),
                  layer["o_proj"],
                  tp="row", lora="o_proj").astype(x.dtype)
    return out, (k_cache, v_cache)


def mlp(x: jax.Array, layer: Params, cfg: ModelConfig) -> jax.Array:
    if cfg.num_experts:
        return moe_mlp(x, layer, cfg)
    gate = _einsum("bte,ef->btf", x, layer["gate_proj"], tp="col",
                   lora="gate_proj")
    up = _einsum("bte,ef->btf", x, layer["up_proj"], tp="col",
                 lora="up_proj")
    act = jax.nn.gelu(gate, approximate=True) if cfg.gelu_mlp \
        else jax.nn.silu(gate)
    hidden = (act * up).astype(x.dtype)
    return _einsum("btf,fe->bte", hidden, layer["down_proj"],
                   tp="row", lora="down_proj").astype(x.dtype)


def moe_mlp(x: jax.Array, layer: Params, cfg: ModelConfig) -> jax.Array:
    """Mixtral-style sparse MoE, computed expert-dense for SPMD.

    Router picks top-k experts per token (softmax over the top-k logits,
    Mixtral semantics); the expert matmuls run batched over a leading
    expert axis and combine under the routing weights in one contraction.
    Compute-dense-combine-sparse is the EP-friendly layout: the expert
    axis shards on the mesh's "model" axis (sharding.param_specs), every
    device runs its local experts for all tokens, and the combining
    einsum's contraction over the sharded axis becomes one XLA all-reduce
    over ICI — no ragged per-expert token dispatch, fully static shapes.
    (A top-k gather path saves FLOPs at large batch; tracked as a future
    kernel.)
    """
    experts = layer["experts"]
    x_dim = cfg.num_experts
    k = cfg.num_experts_per_tok

    router_logits = _einsum("bte,ex->btx", x, layer["router"])   # f32
    top_vals, top_idx = jax.lax.top_k(router_logits, k)          # [B,T,k]
    gates = jax.nn.softmax(top_vals, axis=-1)                    # Mixtral
    # dense routing weights [B,T,X]: sum of gate * one_hot(expert)
    weights = jnp.sum(
        jax.nn.one_hot(top_idx, x_dim, dtype=jnp.float32)
        * gates[..., None], axis=-2)

    gate_h = _einsum("bte,xef->btxf", x, experts["gate_proj"])
    up_h = _einsum("bte,xef->btxf", x, experts["up_proj"])
    act = jax.nn.gelu(gate_h, approximate=True) if cfg.gelu_mlp \
        else jax.nn.silu(gate_h)
    # routing weights fold into the hidden activations elementwise, so the
    # final contraction (sharded expert axis → one all-reduce) is a plain
    # two-operand matmul
    hidden = (act * up_h * weights[..., None]).astype(x.dtype)
    out = _einsum("btxf,xfe->bte", hidden, experts["down_proj"])
    return out.astype(x.dtype)


def transformer_block(
    x: jax.Array, layer: Params, cfg: ModelConfig, positions: jax.Array,
    kv_cache, cache_offset, attn_mask, attn_fn=None, kv_valid=None,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One block. `attn_fn(h, layer) -> (out, (k, v))`, when given, replaces
    dense attention — the hook longcontext.py uses to drop in ring/Ulysses
    sequence-parallel cores while keeping the norm/residual/MLP wiring (and
    every family flag) in exactly one place."""
    h = rms_norm(x, layer["input_norm"], cfg.norm_eps, cfg.rmsnorm_unit_offset)
    if attn_fn is None:
        attn_out, new_cache = attention(h, layer, cfg, positions, kv_cache,
                                        cache_offset, attn_mask, kv_valid)
    else:
        attn_out, new_cache = attn_fn(h, layer)
    if cfg.post_attn_norm:
        attn_out = rms_norm(attn_out, layer["post_attn_norm"], cfg.norm_eps,
                            cfg.rmsnorm_unit_offset)
    x = x + attn_out
    h = rms_norm(x, layer["pre_mlp_norm"], cfg.norm_eps,
                 cfg.rmsnorm_unit_offset)
    mlp_out = mlp(h, layer, cfg)
    if cfg.post_mlp_norm:
        mlp_out = rms_norm(mlp_out, layer["post_mlp_norm"], cfg.norm_eps,
                           cfg.rmsnorm_unit_offset)
    return x + mlp_out, new_cache


@layer_body(static=("cfg",))
def _cached_block(x, layer, positions, kv_cache, cache_offset, attn_mask,
                  kv_valid, *, cfg: ModelConfig):
    """`forward`'s layer as a body (`layer_body`): one block over the
    position-aligned cache of that layer (None: none)."""
    return transformer_block(x, layer, cfg, positions, kv_cache,
                             cache_offset, attn_mask, kv_valid=kv_valid)


def make_attention_mask(positions: jax.Array, kv_len: int,
                        kv_valid_len: jax.Array,
                        sliding_window: Optional[int]) -> jax.Array:
    """Causal (+ optional sliding window) mask against a padded KV cache.

    positions: [B, T] query absolute positions; kv_valid_len: [B] number of
    valid cache entries per row. Cache layout is position-aligned (entry s
    holds position s), so causality is pos_kv <= pos_q AND s < valid.
    """
    kv_pos = jnp.arange(kv_len)[None, None, :]           # [1,1,S]
    q_pos = positions[:, :, None]                        # [B,T,1]
    mask = kv_pos <= q_pos
    mask &= kv_pos < kv_valid_len[:, None, None]
    if sliding_window is not None:
        mask &= kv_pos > q_pos - sliding_window
    return mask


def forward(
    params: Params, cfg: ModelConfig,
    tokens: jax.Array,            # [B, T]
    positions: jax.Array,         # [B, T]
    kv_caches: Optional[list[tuple[jax.Array, jax.Array]]],
    cache_offset: Optional[jax.Array],   # [B]
    kv_valid_len: jax.Array,      # [B] valid entries AFTER this step
    last_pos: Optional[jax.Array] = None,   # [B] row index into T
) -> tuple[jax.Array, list[tuple[jax.Array, jax.Array]]]:
    """Full model forward. Returns (logits [B,T,V], updated caches) —
    or (logits [B,1,V]) when `last_pos` is given: the hidden state is
    gathered at last_pos BEFORE the lm-head matmul, so prefill never
    materializes full-sequence logits. On a 256k-vocab model a batched
    [B,T,V] f32 logits temp is gigabytes (B=3, T=2048 ≈ 6.3 GB — it
    OOM'd the 3-knight discuss bench on a v5e chip; measured once
    before PR 1; not re-measured) and XLA cannot push the caller's
    post-hoc dynamic slice back through the
    einsum; callers that only need the last valid row must pass
    last_pos instead of slicing the result."""
    if cfg.layer_kinds is not None:
        if kv_caches is not None:
            raise ValueError(
                f"{cfg.name}: a model with layer_kinds serves through "
                "the paged layout (engine/paged_forward.py); forward() "
                "runs it only whole, from position 0, with no cache")
        return _forward_hybrid_whole(params, cfg, tokens, positions,
                                     kv_valid_len, last_pos)
    # Activations follow the param dtype: bf16 params (serving) keep the
    # whole network bf16; f32 params (HF logit-parity tests) stay f32.
    x = embed_tokens(params["embedding"], tokens)
    if cfg.scale_embeddings:
        x = x * jnp.sqrt(jnp.float32(cfg.embed_dim)).astype(x.dtype)

    kv_len = (kv_caches[0][0].shape[1] if kv_caches is not None
              else tokens.shape[1])
    mask = make_attention_mask(positions, kv_len, kv_valid_len,
                               cfg.sliding_window)

    new_caches = []
    for i, layer in enumerate(params["layers"]):
        cache_i = kv_caches[i] if kv_caches is not None else None
        x, new_cache = _cached_block(
            x, layer, positions, cache_i, cache_offset, mask,
            kv_valid_len, cfg=cfg)
        new_caches.append(new_cache)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps,
                 cfg.rmsnorm_unit_offset)
    if last_pos is not None:
        x = gather_rows(x, last_pos)
    head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
    logits = _einsum("bte,ve->btv", x, head, tp="col")
    logits = _softcap(logits, cfg.final_logit_softcap)
    return logits, new_caches


def _forward_hybrid_whole(params, cfg, tokens, positions, kv_valid_len,
                          last_pos):
    """A hybrid decoder over whole sequences from position 0, no cache:
    the serving path's own layer functions (chunked scan, masked expert
    loop, dense causal attention), for tests and one-shot scoring."""
    from . import hybrid
    b, t = tokens.shape
    x = embed_tokens(params["embedding"], tokens)
    zero = hybrid.zero_state(cfg, b, x.dtype)
    caches = []
    memory = None                # (what the gated memory units read)
    for i, (kind, layer) in enumerate(hybrid.layers_unrolled(cfg, params)):
        if kind == hybrid.ATTENTION:
            lcfg = cfg.attention_layer(len(caches))
            mask = make_attention_mask(positions, t, kv_valid_len,
                                       lcfg.sliding_window)
        h = hybrid.layer_norm_in(x, layer, cfg)
        if kind == hybrid.MAMBA2:
            out, _, _ = hybrid.mamba2_prefill(
                h, layer, cfg, zero["ssm"][0], zero["conv"][0],
                kv_valid_len)
        elif kind == hybrid.RETENTION:
            out = hybrid.retention.retention_prefill(
                h, layer, cfg, positions, zero["ret"][0], zero["retn"][0],
                jnp.arange(b), kv_valid_len, hybrid.RETENTION_CHUNK)[0]
        elif kind == hybrid.MAMBA1:
            # (a zero state of one layer: the run's first, at l = 0)
            out, *rest = hybrid.mamba1.mamba1_prefill(
                h, layer, cfg, zero["ssm1"][0][:, :1],
                zero["conv1"][0][:, :1], 0, jnp.arange(b), kv_valid_len,
                emit=i == cfg.memory_layer)
            if i == cfg.memory_layer:
                memory = rest[-1]
        elif kind == hybrid.SHORTCONV:
            out = hybrid.shortconv.shortconv_prefill(
                h, layer, cfg, zero["sconv"][0], kv_valid_len)[0]
        elif kind == hybrid.EXPERTS:
            out, _ = hybrid.experts_mlp(h, layer, cfg)
        elif kind == hybrid.MLP:
            out = mlp(h, layer, cfg)
        elif kind == hybrid.GMU:
            out = hybrid.gmu(h, memory, layer, x.dtype)
        elif cfg.diff_attn:
            # (a cross layer: the keys and values of the attention
            # layer below it, causal and unbounded)
            from . import diffattn
            if kind == hybrid.CROSS:
                q, kv = diffattn.queries(h, layer, cfg), caches[-1]
                mask = make_attention_mask(positions, t, kv_valid_len,
                                           cfg.sliding_window)
            else:
                q, *kv = project_qkv(h, layer, lcfg, positions)
                q = diffattn.pack_queries(q)
                caches.append(tuple(kv))
            out = diffattn.output(diffattn.dense_attention(q, *kv, mask),
                                  layer, cfg, x.dtype)
        elif cfg.latent:
            from . import mla
            out, kv = mla.expanded_attention(h, layer, cfg, positions,
                                             mask)
            caches.append(kv)
        else:
            out, kv = attention(h, layer, lcfg, positions, None, None,
                                mask, kv_valid_len)
            caches.append(kv)
        x = x + out
    x = hybrid.final_norm(x, params, cfg)
    if last_pos is not None:
        x = gather_rows(x, last_pos)
    head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
    logits = _einsum("bte,ve->btv", x, head, tp="col")
    return logits, caches


def gather_rows(x: jax.Array, pos: jax.Array) -> jax.Array:
    """Gather one T-row per batch element: [B,T,E], [B] → [B,1,E]."""
    idx = jnp.broadcast_to(pos[:, None, None],
                           (x.shape[0], 1, x.shape[2]))
    return jnp.take_along_axis(x, idx, axis=1)


# --- initialization ---


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=jnp.bfloat16) -> Params:
    """Random init with sane scales — used for tests and weight-free bench."""
    k_embed, k_layers = jax.random.split(key)
    if cfg.layer_kinds is not None:
        from . import hybrid
        keys = jax.random.split(k_layers, cfg.num_layers)
        k_head = jax.random.fold_in(k_embed, 1)
        scale = cfg.embed_dim ** -0.5
        # The embedding at unit rms: the residual stream the mixers add
        # their share to (hybrid.RESIDUAL_SHARE).
        embedding = jax.random.normal(
            k_embed, (cfg.vocab_size, cfg.embed_dim),
            jnp.float32).astype(dtype)
        if cfg.retention_layers:
            # The channel a bias-free gate reads its level from
            # (hybrid.init_layer): the same for every token.
            embedding = embedding.at[:, hybrid.GATE_CHANNEL].set(1.0)

        def one(i, kind, lk):
            # (differential attention counts the PUBLISHED layers ahead,
            # a mixer and the MLP behind it together)
            depth = (sum(k not in (hybrid.MLP, hybrid.EXPERTS)
                         for k in cfg.layer_kinds[:i]) if cfg.diff_attn
                     else cfg.layer_kinds[:i].count(hybrid.RETENTION))
            return hybrid.init_layer(
                cfg.attention_layer(cfg.attention_layers.index(i))
                if kind == hybrid.ATTENTION else cfg, kind, lk, dtype,
                depth=depth)

        layers, i = [], 0
        for kinds, n in cfg.layer_runs:
            if kinds[0] != hybrid.MAMBA1:
                layers.append(one(i, kinds[0], keys[i]))
                i += 1
                continue
            # A scanned run is BORN stacked: one leaf a parameter with
            # a leading layer axis, never a stack of per-layer leaves.
            width = len(kinds)
            run_keys = keys[i:i + n * width].reshape(n, width, -1)
            layers.append(jax.vmap(lambda ks, i=i, kinds=kinds: {
                kind: one(i, kind, ks[j])
                for j, kind in enumerate(kinds)})(run_keys))
            i += n * width
        params = {"embedding": embedding, "layers": layers,
                  "final_norm": jnp.ones((cfg.embed_dim,), dtype)}
        if cfg.layer_norm:
            params["final_norm_b"] = (jax.random.normal(
                jax.random.fold_in(k_embed, 2), (cfg.embed_dim,),
                jnp.float32) * hybrid.NORM_BIAS_STD).astype(dtype)
        if cfg.tie_embeddings:
            # The head IS the embedding: at the initialiser's range, so
            # that a token's own row does not decide its own logit
            # (hybrid.TIED_EMBED_STD).
            params["embedding"] = (
                embedding.astype(jnp.float32)
                * hybrid.TIED_EMBED_STD).astype(dtype)
        else:
            params["lm_head"] = (jax.random.normal(
                k_head, (cfg.vocab_size, cfg.embed_dim), jnp.float32)
                * scale).astype(dtype)
        return params

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    layers = []
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    e, h, k_, d, f = (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim, cfg.mlp_dim)
    for lk in layer_keys:
        ks = jax.random.split(lk, 8)
        layer = {
            "q_proj": dense(ks[0], (e, h, d), e),
            "k_proj": dense(ks[1], (e, k_, d), e),
            "v_proj": dense(ks[2], (e, k_, d), e),
            "o_proj": dense(ks[3], (h, d, e), h * d),
            "input_norm": jnp.zeros((e,), dtype) if cfg.rmsnorm_unit_offset
            else jnp.ones((e,), dtype),
            "pre_mlp_norm": jnp.zeros((e,), dtype) if cfg.rmsnorm_unit_offset
            else jnp.ones((e,), dtype),
        }
        if cfg.num_experts:
            x_ = cfg.num_experts
            layer["router"] = dense(ks[7], (e, x_), e)
            layer["experts"] = {
                "gate_proj": dense(ks[4], (x_, e, f), e),
                "up_proj": dense(ks[5], (x_, e, f), e),
                "down_proj": dense(ks[6], (x_, f, e), f),
            }
        else:
            layer.update({
                "gate_proj": dense(ks[4], (e, f), e),
                "up_proj": dense(ks[5], (e, f), e),
                "down_proj": dense(ks[6], (f, e), f),
            })
        if cfg.attn_bias:
            bks = jax.random.split(jax.random.fold_in(lk, 9), 3)
            layer["q_bias"] = (jax.random.normal(bks[0], (h, d), jnp.float32)
                               * 0.02).astype(dtype)
            layer["k_bias"] = (jax.random.normal(bks[1], (k_, d), jnp.float32)
                               * 0.02).astype(dtype)
            layer["v_bias"] = (jax.random.normal(bks[2], (k_, d), jnp.float32)
                               * 0.02).astype(dtype)
        if cfg.post_attn_norm:
            layer["post_attn_norm"] = layer["input_norm"]
        if cfg.post_mlp_norm:
            layer["post_mlp_norm"] = layer["pre_mlp_norm"]
        layers.append(layer)

    params: Params = {
        "embedding": (jax.random.normal(
            k_embed, (cfg.vocab_size, cfg.embed_dim), jnp.float32)
            * (cfg.embed_dim ** -0.5)).astype(dtype),
        "layers": layers,
        "final_norm": jnp.zeros((cfg.embed_dim,), dtype)
        if cfg.rmsnorm_unit_offset else jnp.ones((cfg.embed_dim,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(
            jax.random.fold_in(k_embed, 1),
            (cfg.vocab_size, cfg.embed_dim), cfg.embed_dim)
    return params


def param_count(params: Params) -> int:
    """Logical parameter count: an Int4Leaf's packed byte holds TWO
    parameters, so it counts 2·q4.size (+ scales, matching how int8
    counts q + s)."""
    leaves = jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, Int4Leaf))
    total = 0
    for x in leaves:
        if isinstance(x, Int4Leaf):
            total += 2 * x.q4.size + x.s4.size
        else:
            total += x.size
    return total
