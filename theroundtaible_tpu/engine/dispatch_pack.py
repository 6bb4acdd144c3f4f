"""One buffer a dispatch (ISSUE 53).

Everything the host builds for one step program — page tables, token
ids, positions, the ragged maps, row and slot indices, capture points,
budgets, the done mask, the sampler's parameters, the segment's step
budget — travels as ONE array of 32-bit words: one host-to-device
transfer, issued by the jit call itself. Floats go by bit pattern,
booleans as words. The program takes the array as one argument and cuts
it apart again at its head with static slices.

A `Layout` is the description both sides read: `pack` lays a dict of
host values into the words, `unpack` cuts the words (a numpy array, or
the traced argument of the program) into the same dict. The layout is a
function of the shapes the program is already compiled for — the
constructors below take nothing else — so it rides the program as a
static argument that can take no more values than those shapes do, and
the two sides cannot drift: a field the host did not give is a KeyError
where it is packed, a field of another size a ValueError.
"""

from __future__ import annotations

import functools
from typing import Iterable, NamedTuple

import numpy as np

# Field kinds: what the 32-bit word holds.
INT, FLOAT, BOOL = "int32", "float32", "bool"


class Field(NamedTuple):
    name: str
    shape: tuple
    kind: str
    start: int
    size: int


class Layout:
    """Named fields laid end to end in one int32 vector. Hashable and
    compared by value (a static argument of the step programs); the
    constructors below cache theirs, so serving meets the same object
    again and the comparison is an identity check."""

    __slots__ = ("fields", "size", "_key", "_hash")

    def __init__(self, spec: Iterable[tuple]):
        fields, at = [], 0
        for name, shape, kind in spec:
            if kind not in (INT, FLOAT, BOOL):
                raise ValueError(f"field {name!r}: unknown kind {kind!r}")
            shape = tuple(int(d) for d in shape)
            size = int(np.prod(shape, dtype=np.int64)) if shape else 1
            fields.append(Field(name, shape, kind, at, size))
            at += size
        if len({f.name for f in fields}) != len(fields):
            raise ValueError("a layout names every field once")
        self.fields = tuple(fields)
        self.size = at
        self._key = tuple((f.name, f.shape, f.kind) for f in fields)
        self._hash = hash(self._key)

    def __hash__(self) -> int:
        return self._hash

    def __eq__(self, other) -> bool:
        return self is other or (isinstance(other, Layout)
                                 and self._key == other._key)

    def __repr__(self) -> str:
        return f"Layout({self.size} words: " + ", ".join(
            f"{f.name}{list(f.shape)}" for f in self.fields) + ")"

    def __contains__(self, name: str) -> bool:
        return any(f.name == name for f in self.fields)

    def pack(self, values: dict) -> np.ndarray:
        """The host side: `values[name]` of every field, into one
        int32 vector (a fresh one: a retried dispatch packs again)."""
        buf = np.empty((self.size,), np.int32)
        for f in self.fields:
            words = buf[f.start:f.start + f.size]
            if f.kind == FLOAT:
                words = words.view(np.float32)
            v = np.asarray(values[f.name])
            if v.size != f.size:
                raise ValueError(
                    f"field {f.name!r} holds {f.size} words "
                    f"{list(f.shape)}, got {list(v.shape)}")
            words[:] = v.reshape(-1)
        return buf

    def unpack(self, buf) -> dict:
        """The program side (and, for a numpy `buf`, the host's own
        reading of what it packed): every field again, under its name,
        shape and dtype."""
        if buf.shape != (self.size,):
            raise ValueError(f"{self!r} given a buffer of {buf.shape}")
        host = isinstance(buf, np.ndarray)
        if not host:
            import jax
            import jax.numpy as jnp
        out = {}
        for f in self.fields:
            words = buf[f.start:f.start + f.size]
            if f.kind == FLOAT:
                words = (words.view(np.float32) if host else
                         jax.lax.bitcast_convert_type(words, jnp.float32))
            elif f.kind == BOOL:
                words = words != 0
            out[f.name] = words.reshape(f.shape)
        return out


# The state a decode segment hands the next one on the device (the
# pipelined carry): on the first segment the host packs it, afterwards
# the word `carried` tells the program to take its arguments instead.
CARRY = (("last", INT), ("valid", INT), ("done", BOOL), ("budgets", INT))


@functools.lru_cache(maxsize=None)
def decode_layout(b: int, pages_per_seq: int, *, rows: bool = False,
                  lora: bool = False) -> Layout:
    """A decode segment over a rows bucket of `b`. `rows`: each row's
    state row (a model with recurrent state); `lora`: each row's
    adapter slot."""
    spec = [("tables", (b, pages_per_seq), INT)]
    spec += [(name, (b,), kind) for name, kind in CARRY]
    spec += [("temps", (b,), FLOAT), ("top_ks", (b,), INT),
             ("top_ps", (b,), FLOAT), ("budget", (), INT),
             ("carried", (), BOOL)]
    if rows:
        spec.append(("rows", (b,), INT))
    if lora:
        spec.append(("lora_ids", (b,), INT))
    return Layout(spec)


@functools.lru_cache(maxsize=None)
def prefill_layout(b: int, bucket: int, pages_per_seq: int, *,
                   hybrid: bool = False, lora: bool = False) -> Layout:
    """One prefill chunk of `b` rows by `bucket` tokens. `hybrid`: the
    rows' state rows and the snapshot each leaves."""
    spec = [("tables", (b, pages_per_seq), INT),
            ("tokens", (b, bucket), INT), ("offsets", (b,), INT),
            ("lengths", (b,), INT)]
    if hybrid:
        spec += [("rows", (b,), INT), ("cap_len", (b,), INT),
                 ("snap_idx", (b,), INT)]
    if lora:
        spec.append(("lora_ids", (b,), INT))
    return Layout(spec)


@functools.lru_cache(maxsize=None)
def ragged_layout(t: int, blocks: int, s_max: int, pages_per_seq: int, *,
                  score_width: int = 0, copy_slots: int = 0,
                  hybrid: bool = False, lora: bool = False) -> Layout:
    """A ragged dispatch over a flat buffer of `t` tokens in `blocks`
    blocks and `s_max` sequences (serving_loop.build_ragged_batch's
    shapes). `score_width` / `copy_slots`: the speculative verify's
    score rows and page-copy pairs; `hybrid`: each sequence's state
    row and snapshot; `lora`: each token's adapter slot."""
    spec = [("tables", (s_max, pages_per_seq), INT)]
    spec += [(name, (t,), INT) for name in (
        "tokens", "positions", "token_pages", "token_offs", "token_seq")]
    spec += [("seq_of_block", (blocks,), INT),
             ("block_qstart", (blocks,), INT)]
    spec += [(name, (s_max,), INT) for name in (
        "query_offsets", "kv_valid", "last_rows")]
    spec += [("temps", (s_max,), FLOAT), ("top_ks", (s_max,), INT),
             ("top_ps", (s_max,), FLOAT)]
    if score_width:
        spec.append(("sample_rows", (s_max, score_width), INT))
    if copy_slots:
        spec += [("copy_src", (copy_slots,), INT),
                 ("copy_dst", (copy_slots,), INT)]
    if hybrid:
        spec += [(name, (s_max,), INT)
                 for name in ("seq_slot", "cap_n", "snap_idx")]
    if lora:
        spec.append(("token_adapter", (t,), INT))
    return Layout(spec)


@functools.lru_cache(maxsize=None)
def sampler_layout(b: int) -> Layout:
    """The prologue's first-token sampler over `b` rows."""
    return Layout([("temps", (b,), FLOAT), ("top_ks", (b,), INT),
                   ("top_ps", (b,), FLOAT)])
