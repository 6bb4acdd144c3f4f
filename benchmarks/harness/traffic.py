"""The general part of the traffic generator. A traffic mix is a data
file of parameters (`<path>/traffic/<name>.json`); its `kind` names a
module beside it (`<path>/traffic/kinds/<kind>.py`) that says what one
session of that kind sends and how its sessions are driven. Everything
a session sends is a pure function of (parameters, seed, index).
Standard library only: the load generator's child process imports this
and never JAX.

Here is what every kind shares: sizes, and text of an exact length in
byte-tokenizer tokens. Every seed gets the same multiset of sizes, in
another order: sizes are the stratified quantiles of their distribution,
shuffled by the seed. So the work of a window does not depend on the
seed, only its order does.
"""

from __future__ import annotations

import random

BOS_ID = 1
BYTE_OFFSET = 3        # ByteTokenizer: byte b is id b + 3
KNIGHT_NAMES = ("Lancelot", "Galahad", "Percival", "Gawain", "Tristan",
                "Bedivere", "Kay", "Bors")
_WORDS = ("journal fsync stream token knight round table session cache "
          "prefix page kernel ragged decode prefill latency durable "
          "crash replay commit segment batch slot pool window consensus "
          "objection proposal evidence verdict risk cost ship revert "
          "measure trace span queue admit shed drain budget deadline"
          ).split()


# --- sizes ------------------------------------------------------------

def quantile(dist: dict, u: float) -> int:
    """The u-quantile (0 < u < 1) of a size distribution, as a whole
    number of tokens."""
    kind = dist["dist"]
    if kind == "fixed":
        return int(dist["value"])
    if kind == "uniform":
        lo, hi = float(dist["lo"]), float(dist["hi"])
        return int(round(lo + u * (hi - lo)))
    if kind == "bounded_pareto":
        lo, hi, alpha = float(dist["lo"]), float(dist["hi"]), \
            float(dist["alpha"])
        tail = 1.0 - (lo / hi) ** alpha
        return int(round(lo / (1.0 - u * tail) ** (1.0 / alpha)))
    raise ValueError(f"unknown size distribution {kind!r}")


def population(dist: dict, count: int, seed: int, salt: str) -> list[int]:
    """`count` sizes: the stratified quantiles of `dist` (the same set
    for every seed), shuffled by (seed, salt)."""
    sizes = [quantile(dist, (i + 0.5) / count) for i in range(count)]
    random.Random(f"sizes:{salt}:{seed}").shuffle(sizes)
    return sizes


def text_of(n_bytes: int, rng: random.Random) -> str:
    """Exactly `n_bytes` ASCII bytes of words — `n_bytes` byte-tokenizer
    tokens."""
    parts: list[str] = []
    size = 0
    while size < n_bytes:
        w = rng.choice(_WORDS)
        parts.append(w)
        size += len(w) + 1
    return " ".join(parts)[:n_bytes].ljust(n_bytes, ".")


def byte_ids(text: str) -> list[int]:
    return [b + BYTE_OFFSET for b in text.encode("utf-8")]
