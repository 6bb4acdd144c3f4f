"""Token sampling — greedy, temperature, top-k, top-p.

Pure jit-safe functions over a logits row. `sample_token` bakes one set
of parameters into its program (Python branches); `sample_token_batch`
takes them a row, as arrays, and is what every step program calls.
Temperature 0 is greedy through a `where`, never a Python branch. The
filters' work runs under a device-side `lax.cond` on the batch's own
parameters: a batch in which no sampled row set `top_k` or `top_p` goes
from the scaled logits straight to the draw, a batch with one such row
finds its thresholds in a 128-candidate pool, and only rows that pool
cannot prove take the two vocabulary-wide sorts.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.0      # 0 = greedy
    top_k: int = 0                # 0 = disabled
    top_p: float = 1.0            # 1 = disabled
    max_new_tokens: int = 1024


def sample_token(logits: jax.Array, key: jax.Array,
                 params: SamplingParams) -> jax.Array:
    """logits: [B, V] f32 → token ids [B]."""
    greedy = jnp.argmax(logits, axis=-1)
    if params.temperature <= 0.0:
        return greedy

    scaled = logits / jnp.maximum(params.temperature, 1e-6)

    if params.top_k > 0:
        kth = jnp.sort(scaled, axis=-1)[:, -params.top_k][:, None]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)

    if params.top_p < 1.0:
        sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cumulative = jnp.cumsum(probs, axis=-1)
        # keep the smallest set whose cumulative prob >= top_p
        cutoff_idx = jnp.sum(cumulative < params.top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None],
                                     axis=-1)
        scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)

    return jax.random.categorical(key, scaled, axis=-1)


def sampling_arrays(params_list: list[SamplingParams]):
    """Per-row (temps, top_ks, top_ps) f32/i32/f32 HOST arrays for
    sample_token_batch: they reach a program inside its dispatch's one
    packed buffer (engine/dispatch_pack.py)."""
    return (np.asarray([p.temperature for p in params_list], np.float32),
            np.asarray([p.top_k for p in params_list], np.int32),
            np.asarray([p.top_p for p in params_list], np.float32))


# Candidate-pool size for the sort-free filtered path below. Covers
# every practical top_k (configs use tens); rows whose top_k or top-p
# cutoff exceeds it take the exact full-sort fallback via lax.cond.
_K_CAND = 128


def row_filtered(row) -> bool:
    """Whether a row engages the filters: it is sampled and set `top_k`
    or `top_p`. `row` is anything with the three fields (SamplingParams,
    serving_loop.RaggedSeq). The host's twin of `sample_token_batch`'s
    predicate — what the `filtered_rows` of a `segment` span counts."""
    return row.temperature > 0.0 and (row.top_k > 0 or row.top_p < 1.0)


def _exact_threshold(scaled, top_ks, top_ps):
    """The original full-sort threshold computation — two descending
    sorts over the whole vocab. Kept as the exact fallback for rows the
    candidate pool cannot prove correct. Returns one threshold a row
    [B, 1], the larger of the k-th logit and the top-p cutoff: masking
    below one and then below the other is masking below their maximum."""
    v = scaled.shape[-1]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k_idx = jnp.clip(top_ks - 1, 0, v - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    kth = jnp.where((top_ks > 0)[:, None], kth, -jnp.inf)
    scaled = jnp.where(scaled < kth, -jnp.inf, scaled)

    # re-sort after the top-k mask (-inf entries sink to the tail) so the
    # cumulative cutoff sees the same distribution sample_token does
    sorted2 = jnp.sort(scaled, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted2, axis=-1)
    cumulative = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.clip(
        jnp.sum(cumulative < top_ps[:, None], axis=-1), 0, v - 1)
    cutoff = jnp.take_along_axis(sorted2, cutoff_idx[:, None], axis=-1)
    # top_p == 1.0 means DISABLED (matching sample_token, which skips the
    # cutoff entirely): the f32 cumsum can saturate at 1.0 before the last
    # element, which would otherwise mask far-tail tokens.
    cutoff = jnp.where((top_ps < 1.0)[:, None], cutoff, -jnp.inf)
    return jnp.maximum(kth, cutoff)


def _pool_threshold(scaled, temps, top_ks, top_ps):
    """The filtered branch of `sample_token_batch`: one threshold a row
    [B, 1] below which the row's scaled logits are masked, from a
    `lax.top_k(_K_CAND)` candidate pool — and, for the rows the pool
    cannot prove, from `_exact_threshold` under a conditional of its
    own."""
    k_cand = min(_K_CAND, scaled.shape[-1])
    cand = jax.lax.top_k(scaled, k_cand)[0]          # [B, k] descending
    k_idx = jnp.clip(top_ks - 1, 0, k_cand - 1)
    kth = jnp.take_along_axis(cand, k_idx[:, None], axis=-1)
    kth = jnp.where((top_ks > 0)[:, None], kth, -jnp.inf)
    m1 = jnp.where(scaled < kth, -jnp.inf, scaled)
    cand1 = jnp.where(cand < kth, -jnp.inf, cand)    # prefix of sort(m1)

    # softmax over the kept set without sorting — the same exp(x - max)
    # / sum ops jax.nn.softmax uses (only the sum's element ORDER can
    # differ; see sample_token_batch)
    m_max = jnp.max(m1, axis=-1, keepdims=True)
    denom = jnp.sum(jnp.exp(m1 - m_max), axis=-1, keepdims=True)
    cum = jnp.cumsum(jnp.exp(cand1 - m_max) / denom, axis=-1)
    cutoff_idx = jnp.clip(
        jnp.sum(cum < top_ps[:, None], axis=-1), 0, k_cand - 1)
    cutoff = jnp.take_along_axis(cand1, cutoff_idx[:, None], axis=-1)
    cutoff = jnp.where((top_ps < 1.0)[:, None], cutoff, -jnp.inf)
    thr_fast = jnp.maximum(kth, cutoff)

    # rows the candidate pool cannot prove: kth outside the pool, or the
    # top-p cutoff beyond the pool's cumulative mass
    bad = (temps > 0.0) & ((top_ks > k_cand)
                           | ((top_ps < 1.0) & (cum[:, -1] < top_ps)))
    # Per-ROW blend, not a batch-wide switch (advisor r5): only the bad
    # rows take the exact full-sort threshold; provable rows keep the
    # pool's even when a batchmate is bad, so a row's sampled token never
    # depends on which other rows share the batch (the two cutoffs can
    # differ by one ≤~1-ulp boundary token — see sample_token_batch).
    # Cost tradeoff: when ANY row is bad the exact tail still computes
    # for the whole batch (its sorts are full-vocab either way); the
    # lax.cond keeps the all-good case sort-free.
    return jax.lax.cond(
        jnp.any(bad),
        lambda s: jnp.where(bad[:, None],
                            _exact_threshold(s, top_ks, top_ps), thr_fast),
        lambda s: thr_fast, scaled)


def sampler_mode(params_list: list[SamplingParams]) -> str:
    """Which path sample_token_batch takes for a batch of these per-row
    params — bench provenance (ISSUE 3 satellite: the sampler gets an
    ATTRIBUTABLE number): "greedy" (every row temp <= 0, single argmax —
    no sampler at all), "plain" (sampled rows, none with a filter: the
    scaled logits go straight to the draw), "sort" (some row's top_k
    exceeds the _K_CAND candidate pool, forcing the exact full-vocab
    sort fallback), or "sort-free" (the candidate pool; boundary rows
    whose top-p mass outruns the pool may still cond into the exact
    tail, but the common filtered case stays sort-free)."""
    if all(p.temperature <= 0.0 for p in params_list):
        return "greedy"
    if not any(row_filtered(p) for p in params_list):
        return "plain"
    if any(p.top_k > _K_CAND for p in params_list):
        return "sort"
    return "sort-free"


def sample_token_batch(logits: jax.Array, key: jax.Array,
                       temps: jax.Array, top_ks: jax.Array,
                       top_ps: jax.Array) -> jax.Array:
    """Per-ROW sampling parameters as dynamic arrays: heterogeneous knight
    personas (different temperatures per seat) sample correctly inside ONE
    batched program, and changing a sampling config never recompiles
    (sample_token's Python branches bake the params into the program).

    Row semantics match sample_token exactly: temperature <= 0 → greedy;
    top_k == 0 / top_p == 1.0 → disabled; top-k mask applies before the
    top-p cutoff.

    Which branch runs when — three, chosen on the device from the
    batch's own parameters, all in the one compiled program:

    - plain: no sampled row (temperature > 0) set top_k or top_p. The
      threshold is -inf on every row, nothing is masked, and the scaled
      logits go straight to the draw. No pass over the vocabulary
      beyond the argmax, the divide and the draw itself.
    - pool (`_pool_threshold`): some sampled row set a filter. The two
      thresholds the filters need — the k-th logit and the top-p cutoff
      — are found in a `lax.top_k(_K_CAND)` candidate pool instead of
      two full-vocab descending sorts. The candidate prefix IS the full
      sort's prefix, and the softmax is recomputed with the same ops
      (exp of max-shifted values over the kept-set sum — max and sum
      are plain reductions, no sort). The kth threshold is exact; the
      top-p cutoff matches the fallback's up to reduction-ORDER
      rounding of the softmax denominator (the fallback sums exps in
      sorted order, this path in vocab order — ≤ ~1 ulp), which can
      move the kept set by one boundary token only when some cumulative
      value straddles top_p within that rounding.
    - exact (`_exact_threshold`, nested in the pool branch): rows the
      pool cannot prove correct (top_k > _K_CAND, or candidate mass
      short of top_p) take the two full-vocab sorts; the other rows of
      the batch keep the pool's thresholds.

    Every branch hands back one threshold a row; the mask is applied
    once, outside, where it fuses into the draw. The draw is full-vocab
    under the SAME key whichever branch ran, so a row's token does not
    depend on its batchmates' parameters. Called outside `jit` the
    lax.cond's branches are new objects on every call, so XLA compiles
    the conditional again every time: this function is only ever called
    from inside a compiled program."""
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temps[:, None], 1e-6)

    filtered = jnp.any((temps > 0.0) & ((top_ks > 0) | (top_ps < 1.0)))
    thr = jax.lax.cond(
        filtered,
        lambda s: _pool_threshold(s, temps, top_ks, top_ps),
        lambda s: jnp.full((s.shape[0], 1), -jnp.inf, s.dtype), scaled)
    # (The barrier keeps the threshold's broadcast to [B, V] out of the
    # conditional, where XLA's code motion would otherwise put it: a
    # vocabulary-wide array written by either branch, every step.)
    thr = jax.lax.optimization_barrier(thr)
    masked = jnp.where(scaled < thr, -jnp.inf, scaled)

    sampled = jax.random.categorical(key, masked, axis=-1)
    return jnp.where(temps <= 0.0, greedy, sampled)
