"""Micro-benchmark: which weight representation actually streams its
bytes on this chip's matmul operand path?

One gemma-2b-shaped GEMV per representation (decode is a chain of
exactly these), timed standalone so a bad int4 layout is attributable
BEFORE burning a full bench run on it. Full int4 decode measured 22.9
tok/s vs bf16's 130 (measured once before PR 1; not re-measured) — the
old interleaved stack+reshape
unpack broke XLA's operand fusion and materialized (+copied) the bf16
weight every token; the profiler showed per-token `copy` /
`shift-right-arithmetic_bitcast_fusion` ops. The fix (engine/quant.py):
pack along the LAST axis and unpack with lax.bitcast_convert_type,
whose nibble pair expands minor-most — no shuffle, fusable. This script
verifies that claim in ~a minute and prints one JSON line per variant:
effective GB/s = streamed_bytes / iter_time vs the ~819 GB/s v5e HBM
roofline.

Variants:
  bf16      plain einsum                           (2 B/param)
  int8      q int8 + per-output-channel scale      (1 B/param)
  int4      Int4Leaf bitcast dequant (shipping)    (0.5 B/param + s4)
  int4-s4   native jnp.int4 storage, convert+scale (0.5 B/param + s4)
            — candidate future layout; also exercises the S4-at-jit-
            boundary path that RecursionError'd on an earlier PJRT
            plug-in when relayout was needed (watchdogged: a crash here is a
            finding, not a wedge).
  int4-kernel / head-int4-kernel
            the fused Pallas w4a16 kernels (pallas/int4mm.py) that
            dequantize in VMEM — the path engine serving now takes on
            single-device TPU. These are the numbers that decide
            whether int4 decode finally streams packed bytes.

Usage: python bench_microquant.py          (needs the live chip)
       ROUNDTABLE_BENCH_CPU=1 ...          (CPU smoke — numbers are
                                            meaningless, plumbing runs)
Same watchdogged child-process pattern as every sibling bench: the
parent probes first and ABANDONS a hung child (no SIGKILL — a killed
JAX process can wedge the single-claim relay for the whole window).
"""

from __future__ import annotations

import json
import os
import sys
import time

E, F = 2048, 16384          # gemma-2b MLP up-projection shape
GROUP = 64
ITERS = 50
ATTEMPT_TIMEOUT_S = 300.0

# The HBM roofline each variant's effective GB/s is judged against
# comes from the ONE shared model (ISSUE 6) — the v5e 819 GB/s figure
# this docstring cites used to be a local literal.
from theroundtaible_tpu.utils import perfmodel as _perfmodel

_DEFAULT_HBM_GBPS = _perfmodel.V5E_HBM_GBPS


def _hbm_roofline_gbps(device_kind: str) -> float:
    """Detected chip's HBM bandwidth, defaulting to v5e (the CPU smoke
    path — numbers are meaningless there anyway, plumbing runs)."""
    spec = _perfmodel.chip_spec(device_kind)
    return spec.hbm_gbps if spec else _DEFAULT_HBM_GBPS


def child() -> int:
    from bench_common import install_sigterm_exit

    install_sigterm_exit()
    import jax

    if os.environ.get("ROUNDTABLE_BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    platform = dev.platform
    hbm_gbps = _hbm_roofline_gbps(getattr(dev, "device_kind", ""))
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((E, F), np.float32) * 0.02,
                    jnp.bfloat16)
    a = jnp.asarray(rng.standard_normal((1, E), np.float32),
                    jnp.bfloat16)

    from theroundtaible_tpu.engine.models.common import (Int4Leaf,
                                                         dequant_int4)
    from theroundtaible_tpu.engine.quant import (_quantize_leaf,
                                                 _quantize_leaf_int4)

    q8 = _quantize_leaf(w, (1,), jnp.bfloat16, False)
    leaf = _quantize_leaf_int4(w, (1,), jnp.bfloat16, False, GROUP)
    assert isinstance(leaf, Int4Leaf)

    @jax.jit
    def f_bf16(a, w):
        return jnp.einsum("be,ef->bf", a, w,
                          preferred_element_type=jnp.float32)

    @jax.jit
    def f_int8(a, q, s):
        y = jnp.einsum("be,ef->bf", a, q.astype(a.dtype),
                       preferred_element_type=jnp.float32)
        return y * s.astype(jnp.float32)[None, :]

    @jax.jit
    def f_int4(a, q4, s4):
        w = dequant_int4(q4, s4, leaf.axis, leaf.group, a.dtype)
        return jnp.einsum("be,ef->bf", a, w,
                          preferred_element_type=jnp.float32)

    # native S4 storage: same values, stored as jnp.int4 (XLA packs)
    @jax.jit
    def to_s4(q4):
        pairs = jax.lax.bitcast_convert_type(q4, jnp.int4)
        return pairs.reshape(E, F)

    @jax.jit
    def f_s4(a, qs4, s4):
        w = qs4.astype(a.dtype).reshape(E, F // GROUP, GROUP) \
            * s4[..., None].astype(a.dtype)
        return jnp.einsum("be,ef->bf", a, w.reshape(E, F),
                          preferred_element_type=jnp.float32)

    def timed(name, fn, args, streamed_bytes, extra=None):
        """Each iteration's activation is perturbed by (prev_out · 0) so
        every dispatch DEPENDS on the previous one: an earlier run
        measured physically impossible rates (head-bf16 "8.4 TB/s" vs
        the ~819 GB/s HBM roofline) from the independent-repeat loop —
        block_until_ready on the last of N independent dispatches does
        not reliably price the other N-1 on every transport. The full
        decode bench never had this problem because token feedback
        chains its steps; this loop now chains the same way. The
        perturbation is folded INSIDE the jitted call so each iteration
        stays ONE dispatch (eager per-iter chaining ops would add
        dispatch overhead comparable to the ~20-60us GEMVs measured)."""

        @jax.jit
        def chained(prev, *a):
            a0 = a[0] + (prev.reshape(-1)[0] * 0).astype(a[0].dtype)
            return fn(a0, *a[1:])

        try:
            out = fn(*args)
            out = chained(out, *args)   # warm the chained compile
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(ITERS):
                out = chained(out, *args)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / ITERS
            eff_gbps = streamed_bytes / dt / 1e9
            print(json.dumps({
                "variant": name, "platform": platform,
                "us_per_call": round(dt * 1e6, 1),
                "streamed_mb": round(streamed_bytes / 1e6, 2),
                "effective_gbps": round(eff_gbps, 1),
                # Shared-roofline attribution (ISSUE 6): fraction of
                # the chip's HBM bandwidth this variant achieved.
                "hbm_roofline_gbps": hbm_gbps,
                "roofline_frac": round(eff_gbps / hbm_gbps, 3),
                **(extra or {}),
            }), flush=True)
        except Exception as e:  # a variant crashing is itself the data
            print(json.dumps({"variant": name, "platform": platform,
                              "error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)

    from theroundtaible_tpu.engine.pallas import int4mm

    @jax.jit
    def f_int4_kernel(a, q4, s4):
        y = int4mm.einsum_int4(
            "be,ef->bf", a,
            Int4Leaf(q4=q4, s4=s4, axis=leaf.axis, group=leaf.group))
        assert y is not None, "kernel declined MLP shape"
        return y

    def timed_kernel(name, fn, args, streamed_bytes, spec, a_shape,
                     klf):
        """Kernel variants carry PATH PROVENANCE (ISSUE 3): a shape the
        plan declines emits an explicit fallback_reason record instead
        of crashing the whole child — the window's numbers stay
        attributable either way."""
        reason = int4mm.plan_reason(spec, a_shape, klf)
        if reason:
            print(json.dumps({"variant": name, "platform": platform,
                              "path": "xla_dequant",
                              "fallback_reason": reason}), flush=True)
            return
        timed(name, fn, args, streamed_bytes,
              extra={"path": "pallas_w4a16"})

    # Kernel variants measure FIRST (window ordering, ISSUE 3): they are
    # the least-replaceable numbers — a child killed mid-run has already
    # landed the records the window exists for.
    i4_bytes = leaf.q4.size + leaf.s4.size * 2
    timed_kernel("int4-kernel", f_int4_kernel, (a, leaf.q4, leaf.s4),
                 i4_bytes, "be,ef->bf", (1, E), leaf)
    timed("bf16", f_bf16, (a, w), w.size * 2)
    timed("int8", f_int8, (a, q8["q"], q8["s"]),
          q8["q"].size + q8["s"].size * 2)
    timed("int4", f_int4, (a, leaf.q4, leaf.s4), i4_bytes)
    try:
        qs4 = to_s4(leaf.q4)
        jax.block_until_ready(qs4)
        timed("int4-s4", f_s4, (a, qs4, leaf.s4), i4_bytes)
    except Exception as e:
        print(json.dumps({"variant": "int4-s4", "platform": platform,
                          "error": f"{type(e).__name__}: {e}"[:300]}),
              flush=True)

    # lm-head shape: [V, E] with the CONTRACTED axis (E) packed — the
    # tied-embedding head is the single biggest per-token weight read
    # (0.78 ms/tok in the int8 hardware profile), and its dequant sits
    # on the opposite side of the contraction from the MLP case above.
    V = 32768  # structural stand-in for 256k (same fusion question)
    head = jnp.asarray(rng.standard_normal((V, E), np.float32) * 0.02,
                       jnp.bfloat16)
    h8 = _quantize_leaf(head, (0,), jnp.bfloat16, False)
    hleaf = _quantize_leaf_int4(head, (0,), jnp.bfloat16, False, GROUP)
    assert isinstance(hleaf, Int4Leaf)

    @jax.jit
    def h_bf16(a, w):
        return jnp.einsum("be,ve->bv", a, w,
                          preferred_element_type=jnp.float32)

    @jax.jit
    def h_int8(a, q, s):
        y = jnp.einsum("be,ve->bv", a, q.astype(a.dtype),
                       preferred_element_type=jnp.float32)
        return y * s.astype(jnp.float32)[None, :]

    @jax.jit
    def h_int4(a, q4, s4):
        w = dequant_int4(q4, s4, hleaf.axis, hleaf.group, a.dtype)
        return jnp.einsum("be,ve->bv", a, w,
                          preferred_element_type=jnp.float32)

    @jax.jit
    def h_int4_kernel(a, q4, s4):
        y = int4mm.einsum_int4(
            "be,ve->bv", a,
            Int4Leaf(q4=q4, s4=s4, axis=hleaf.axis, group=hleaf.group))
        assert y is not None, "kernel declined head shape"
        return y

    timed_kernel("head-int4-kernel", h_int4_kernel,
                 (a, hleaf.q4, hleaf.s4),
                 hleaf.q4.size + hleaf.s4.size * 2, "be,ve->bv", (1, E),
                 hleaf)
    timed("head-bf16", h_bf16, (a, head), head.size * 2)
    timed("head-int8", h_int8, (a, h8["q"], h8["s"]),
          h8["q"].size + h8["s"].size * 2)
    timed("head-int4", h_int4, (a, hleaf.q4, hleaf.s4),
          hleaf.q4.size + hleaf.s4.size * 2)
    return 0


def main() -> int:
    from bench_common import run_watchdogged

    return run_watchdogged(os.path.abspath(__file__), [],
                           ATTEMPT_TIMEOUT_S)


if __name__ == "__main__":
    sys.exit(child() if "--child" in sys.argv else main())
