"""The shard_map API seam.

The engine is written against the API of the installed JAX (0.9.0:
`jax.shard_map` with `check_vma=`, an int8 → int4 bitcast that expands
minor-most). Every engine module imports these names from here instead
of from jax, so a version bump is a change to this module, not a
nine-module sweep. It holds no branch for a JAX that is not installed.
"""

from __future__ import annotations

import jax

shard_map = jax.shard_map


def unpack_int4_pairs(q4):
    """int8[..., n] → signed nibble pairs int4[..., n, 2], low nibble
    first (the engine/quant.py pack order): the one-op bitcast whose
    nibble pair expands minor-most — the layout XLA fuses into the
    consuming matmul operand (models/common.dequant_int4's performance
    contract)."""
    import jax.numpy as jnp
    return jax.lax.bitcast_convert_type(q4, jnp.int4)
