"""Quantized KV pages — int8 (and grouped int4) paged-pool storage.

KV residency dominates serving HBM (LORA_r10 recorded kv_bytes at 79%
of resident memory even at toy scale) while weights already stream at
int8/int4 (PR 3) — so bf16 KV pages are the budget line that caps max
resident sessions and sets decode's streamed-bytes roofline term. This
module is the ONE definition of the page-cell quantization contract
shared by every seam that touches it (ISSUE 11):

- **Storage**: a quantized pool keeps its [P, page_size, K, Dp] layout
  with int8 payload (Dp = D for int8, D/2 packed nibbles for int4 — the
  quant.py nibble order: even element in the LOW nibble) and a parallel
  per-layer scale pool [P, page_size, K, G] float32 — one symmetric
  absmax scale per CELL (per token per kv head) per group (G = 1 for
  int8, D/group for int4). Per-cell scales are what make
  quantize-on-write LOCAL: a token's write computes its own scale from
  its own values, never re-quantizing neighbours, so repeated
  scatter/gather round trips are bit-stable (`requant_stable` below is
  the pinned property) and host spill/restore of the int8 bytes is
  exactly lossless.
- **Write seam**: `quantize_cells` runs INSIDE the jit'd serving
  programs at the K/V scatter sites (paged_forward's per-layer scatter,
  the gather-view scatter, the ring-prefill writeback) — values in,
  values out, no shape depends on occupancy, so the PR-6 recompile
  sentinel stays green.
- **Read seam**: the Pallas kernels dequantize in-kernel
  (pallas/attention._dequant_kv: the `_prefill_accumulate` /
  `_decode_accumulate` extension), so the streamed bytes on the serving
  path are the int8 payload + scales — the quantization is free where
  it matters. The XLA fallbacks (gather view, ragged dense path)
  dequantize at gather via `dequantize_cells`, numerically the same
  math.
- **Accounting**: `cell_bytes_per_token` is the closed form the memory
  ledger, fleet plan estimate and perfmodel ceiling all derive from, so
  the plan cannot drift from the real allocation.

Everything downstream (prefix cache, host offload, spec-decode verify,
LoRA mixed batches) rides page IDs and therefore shares quantized bytes
unchanged — scales travel with their pages because they are indexed by
the same page axis. Parity discipline: attach/restore byte-identity
becomes quantization-aware — pinned rms bounds against the bf16 path
plus greedy token parity (BENCH_NOTES.md records the acceptance rule);
`ROUNDTABLE_KV_QUANT=0` restores bf16 serving byte-identically.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Optional

import jax.numpy as jnp

# Default int4 group along D: matches quant.py's w4 grouping scale
# (64 there, but KV head_dim is small — 32 keeps >= 4 groups per
# 128-wide head so group error stays local).
DEFAULT_INT4_GROUP = 32


@dataclass(frozen=True)
class KVQuantSpec:
    """Static description of a quantized page pool. `bits` is 8 or 4;
    `group` is the int4 scale group along D (ignored for int8, where
    the whole D axis is one group)."""

    bits: int = 8
    group: int = DEFAULT_INT4_GROUP

    @property
    def dtype_name(self) -> str:
        return "int8" if self.bits == 8 else "int4"

    def packed_dim(self, head_dim: int) -> int:
        """Payload width Dp for a D-wide head: int8 stores D bytes,
        int4 packs two nibbles per byte."""
        return head_dim if self.bits == 8 else head_dim // 2

    def num_groups(self, head_dim: int) -> int:
        """Scale groups G per cell (the scale pool's minor dim)."""
        if self.bits == 8:
            return 1
        return head_dim // self.effective_group(head_dim)

    def effective_group(self, head_dim: int) -> int:
        """The actual int4 group: the largest even divisor of D that is
        <= `group` (the quant.py _int4_group_for rule; int8 returns D)."""
        if self.bits == 8:
            return head_dim
        g = min(self.group, head_dim)
        while g > 1 and (head_dim % g or g % 2):
            g -= 1
        return max(g, 2)

    def cell_bytes(self, head_dim: int) -> float:
        """Resident bytes per KV cell (one token, one kv head): payload
        + float32 scales."""
        return self.packed_dim(head_dim) + 4.0 * self.num_groups(head_dim)


def bf16_cell_bytes(head_dim: int, dtype_bytes: int = 2) -> float:
    return float(head_dim * dtype_bytes)


def cell_bytes_per_token(cfg: Any, spec: Optional[KVQuantSpec],
                         dtype_bytes: int = 2) -> float:
    """KV bytes one cached token costs this model under `spec` (None =
    the bf16 layout): layers x (K + V) x kv_heads x per-cell bytes —
    the ONE closed form the ledger, the fleet estimate and perfmodel's
    streamed-KV term all share."""
    per_cell = (spec.cell_bytes(cfg.head_dim) if spec is not None
                else bf16_cell_bytes(cfg.head_dim, dtype_bytes))
    layers = getattr(cfg, "attention_layers", None)
    n_layers = cfg.num_layers if layers is None else len(layers)
    if spec is None and getattr(cfg, "latent", False):
        # Latent pages (models/mla.py): one entry a position, no
        # per-head cell to quantize (the engine declines kv_quant).
        return n_layers * cfg.page_cells * dtype_bytes
    return n_layers * 2 * cfg.num_kv_heads * per_cell


def page_ratio(spec: KVQuantSpec, head_dim: int,
               dtype_bytes: int = 2) -> float:
    """How many quantized pages fit the byte budget of ONE bf16 page —
    the pool-sizing multiplier (>= 1). int8 at D=128: ~1.94x."""
    return bf16_cell_bytes(head_dim, dtype_bytes) / spec.cell_bytes(
        head_dim)


def resolve_spec(kv_quant: Any) -> tuple[Optional[KVQuantSpec],
                                         Optional[str]]:
    """(spec, decline_reason) from the `kv_quant:` config value.

    Accepts "int8" / "int4", {"bits": 8|4, "group": n}, or falsy
    (off). The ROUNDTABLE_KV_QUANT env kill-switch (=0) wins over any
    config — the machine-readable reason records which gate fired."""
    from .prefix_cache import env_flag
    if not kv_quant or kv_quant == "none":
        return None, "disabled:config"
    if not env_flag(None, "ROUNDTABLE_KV_QUANT"):
        return None, "disabled:env"
    if isinstance(kv_quant, str):
        if kv_quant not in ("int8", "int4"):
            raise ValueError(
                f"kv_quant must be none|int8|int4, got {kv_quant!r}")
        bits = 8 if kv_quant == "int8" else 4
        return KVQuantSpec(bits=bits), None
    if isinstance(kv_quant, dict):
        bits = int(kv_quant.get("bits", 8))
        if bits not in (8, 4):
            raise ValueError(
                f"kv_quant.bits must be 8 or 4, got {bits}")
        group = int(kv_quant.get("group", DEFAULT_INT4_GROUP))
        if group < 2:
            raise ValueError(
                f"kv_quant.group must be >= 2, got {group}")
        return KVQuantSpec(bits=bits, group=group), None
    raise ValueError(
        f"kv_quant must be a string or mapping, got {type(kv_quant)}")


# --- the quantize/dequantize pair (jit-safe, value in / value out) ---


def quantize_cells(x, spec: KVQuantSpec):
    """Quantize K or V values [..., D] to (payload int8 [..., Dp],
    scales f32 [..., G]) with one symmetric absmax scale per cell per
    group. Runs inside the serving programs at every scatter seam;
    shapes depend only on D and the spec, never on batch composition."""
    d = x.shape[-1]
    g = spec.effective_group(d)
    n_groups = spec.num_groups(d)
    x32 = x.astype(jnp.float32)
    xg = x32.reshape(x.shape[:-1] + (n_groups, g))
    absmax = jnp.max(jnp.abs(xg), axis=-1)
    qmax = 127.0 if spec.bits == 8 else 7.0
    s = jnp.maximum(absmax, 1e-8) / qmax
    q = jnp.clip(jnp.round(xg / s[..., None]), -qmax, qmax)
    q = q.astype(jnp.int8).reshape(x.shape[:-1] + (d,))
    if spec.bits == 4:
        q2 = q.reshape(x.shape[:-1] + (d // 2, 2))
        even, odd = q2[..., 0], q2[..., 1]
        q = (((odd.astype(jnp.int32) & 0xF) << 4)
             | (even.astype(jnp.int32) & 0xF)).astype(jnp.int8)
    return q, s


def unpack_int4(q):
    """[..., D/2] packed int8 -> [..., D] int4 values as int8 (even
    element from the LOW nibble — quantize_cells' packing order).
    Shift arithmetic only, so it lowers inside Mosaic kernels (probed
    chipless) and under plain XLA alike."""
    lo = (jnp.left_shift(q, 4) >> 4).astype(jnp.int8)
    hi = (q >> 4).astype(jnp.int8)
    return jnp.stack([lo, hi], axis=-1).reshape(q.shape[:-1]
                                                + (q.shape[-1] * 2,))


def dequantize_cells(q, s, spec: KVQuantSpec, dtype=jnp.bfloat16):
    """(payload [..., Dp], scales [..., G]) -> values [..., D] in
    `dtype` — the XLA-side read seam (gather view, ragged dense
    fallback, host-side round-trip checks). The in-kernel twin is
    pallas/attention._dequant_kv; both apply the identical scale math."""
    if spec.bits == 4:
        q = unpack_int4(q)
    d = q.shape[-1]
    n_groups = s.shape[-1]
    xg = q.astype(jnp.float32).reshape(q.shape[:-1]
                                       + (n_groups, d // n_groups))
    x = (xg * s[..., None].astype(jnp.float32)).reshape(q.shape)
    return x.astype(dtype)


# --- pool pytree helpers (combined pools + scales) ---


def split_combined(combined: list, num_layers: int):
    """The engine's jit programs carry ONE donated pytree: the per-layer
    (k, v) pools followed by the per-layer (k_scale, v_scale) pools when
    quantization is on. (pools, scales_or_None) back out."""
    if len(combined) == num_layers:
        return list(combined), None
    return list(combined[:num_layers]), list(combined[num_layers:])


def join_combined(pools: list, scales: Optional[list]) -> list:
    return list(pools) + (list(scales) if scales else [])


# --- test-visibility counters (tests/conftest.py `kv_quant` guard) ---

_lock = threading.Lock()
_kernel_dispatches = 0
_fallback_dispatches = 0


def reset_test_counters() -> None:
    global _kernel_dispatches, _fallback_dispatches
    with _lock:
        _kernel_dispatches = 0
        _fallback_dispatches = 0


def note_quant_dispatch(kernel: bool) -> None:
    """One serving dispatch consumed quantized pages — kernel-dequant
    (Pallas) or xla-dequant (gather view / ragged dense fallback)."""
    global _kernel_dispatches, _fallback_dispatches
    with _lock:
        if kernel:
            _kernel_dispatches += 1
        else:
            _fallback_dispatches += 1


def quant_dispatches() -> int:
    return _kernel_dispatches + _fallback_dispatches


def quant_kernel_dispatches() -> int:
    return _kernel_dispatches


def quant_fallback_dispatches() -> int:
    return _fallback_dispatches
