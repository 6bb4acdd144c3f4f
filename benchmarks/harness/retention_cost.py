"""What the state kernel and a decode step of a power-retention decoder
(`brumby`: models with NO attention layer, a float32 feature-map state a
kv head a layer) must at least read and do, as functions of the
configuration's published keys. Kept with the benchmark, so that no
later PR can change what `kernel.retention_roofline` and
`step.decode_roofline.retention` are measured against.

The state. At degree 2 the feature map of a head of D values has
D (D + 1) / 2 entries (`state_rows_min`: 8256 at D = 128), each holding
D values and the normaliser: `state_rows_min` x (D + 1) x 4 bytes a kv
head a layer, in the float32 the configuration's `assumed.state_dtype`
states — WHATEVER the program's layout. A layout with more rows reads a
lower share; a program that kept its state in a narrower dtype than the
file states would read over 100, and the readers raise there.

The step kernel (`retention_step`, one call a layer a decode step): for
every row it advances, the state read once and written once, and the
row's q, k, v, gate and y. Its operations — the gated update (3 a state
value) and the product with the group's phi(q) (2 a value a query head)
— are some 1.6 a byte: memory-bound by two orders.

The chunk kernel (`retention_chunk`, one call a layer for every page a
joining run touches): the state once in and once out for each 128
positions a run advances — a floor: a run that starts or ends inside a
page pays for the whole of it — and, for every token, the product of
its group's phi(q) with the state and of phi(k) with [v, 1] into it: 2
operations a state value a query head and 2 more, 100 M a token a layer.
At the bfloat16 peak the two floors are level; the program multiplies
in float32 (six passes), which the share shows.

A decode step reads, whatever its batch: every layer's projections,
gate and norms, every MLP, the final norm and the head (the embedding
gives one row a token: left out); and for every row it advances, the
row's whole state once in and once out. There are no keys and values:
the floor does not grow with the context.
"""

from __future__ import annotations

import re
from typing import Any

STATE_BYTES = 4          # float32: the file's assumed.state_dtype
KERNEL = "retention_step"
CHUNK_KERNEL = "retention_chunk"


def is_retention(config: dict[str, Any]) -> bool:
    return config.get("model_type") == "brumby"


def _sizes(config: dict[str, Any]) -> tuple[int, int, int, int, int]:
    return (int(config["hidden_size"]), int(config["num_attention_heads"]),
            int(config["num_key_value_heads"]), int(config["head_dim"]),
            int(config["num_hidden_layers"]))


def _weight_bytes(config: dict[str, Any]) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[
        config["engine"].get("dtype", "bfloat16")]


def state_rows_min(config: dict[str, Any]) -> int:
    d = int(config["head_dim"])
    return d * (d + 1) // 2


def state_values_per_head(config: dict[str, Any]) -> int:
    return state_rows_min(config) * (int(config["head_dim"]) + 1)


def state_bytes_per_layer(config: dict[str, Any]) -> int:
    """One sequence, one layer: every kv head's state."""
    return (int(config["num_key_value_heads"])
            * state_values_per_head(config) * STATE_BYTES)


def state_bytes_per_sequence(config: dict[str, Any]) -> int:
    return state_bytes_per_layer(config) * int(config["num_hidden_layers"])


def retention_params(config: dict[str, Any]) -> int:
    """One retention layer: q, k, v, o, the gate, the two head norms
    and the layer's norm."""
    e, h, k, d, _ = _sizes(config)
    return e * d * (2 * h + 2 * k) + e * k + 2 * d + e


def mlp_params(config: dict[str, Any]) -> int:
    e = int(config["hidden_size"])
    return 3 * e * int(config["intermediate_size"]) + e


def fixed_step_bytes(config: dict[str, Any]) -> int:
    """Bytes every decode step reads, whatever its rows."""
    e, _h, _k, _d, layers = _sizes(config)
    params = (layers * (retention_params(config) + mlp_params(config))
              + int(config["vocab_size"]) * e + e)
    return params * _weight_bytes(config)


def step_kernel_floor(config: dict[str, Any], row_steps: int) -> dict:
    """Least work of the step kernel over `row_steps` (row, step) pairs,
    every layer: the state once in and once out, and the row's q, k, v,
    gate and y in float32."""
    _e, h, k, d, layers = _sizes(config)
    small = (2 * h * d + 2 * k * d + k) * 4
    values = k * state_values_per_head(config)
    return {
        "bytes": float(row_steps * layers
                       * (2 * state_bytes_per_layer(config) + small)),
        "flops": float(row_steps * layers * values * (3 + 2 * h // k)),
    }


def chunk_kernel_floor(config: dict[str, Any], tokens: int) -> dict:
    """Least work of the chunk kernel over `tokens` joined positions,
    every layer: the state once in and once out a page of them, and a
    token's products with it."""
    _e, h, k, _d, layers = _sizes(config)
    page = int(config["engine"]["page_size"])
    values = k * state_values_per_head(config)
    return {
        "bytes": float(tokens / page * layers
                       * 2 * state_bytes_per_layer(config)),
        "flops": float(tokens * layers * values * (2 * h // k + 2)),
    }


def decode_floor(config: dict[str, Any], *, steps: int,
                 row_steps: int) -> dict:
    """Least work of `steps` decode steps that advanced `row_steps`
    (row, step) pairs."""
    kernel = step_kernel_floor(config, row_steps)
    dense = fixed_step_bytes(config) // _weight_bytes(config)
    return {"bytes": float(steps * fixed_step_bytes(config))
            + kernel["bytes"],
            "flops": float(2 * row_steps * dense) + kernel["flops"]}


def state_operand(config: dict[str, Any]) -> "re.Pattern[str]":
    """The slot states as the trace prints them among a kernel's
    operands: float32 [rows, kv heads, feature rows, D, D], whatever
    the rows and the layout's feature rows."""
    d = int(config["head_dim"])
    return re.compile(r"f32\[\d+,{},\d+,{},{}\]".format(
        int(config["num_key_value_heads"]), d, d))


def retention_seconds(op_seconds: dict[str, float],
                      config: dict[str, Any], named: str = "") -> float:
    """Device seconds of the retention kernels among `op_seconds` (names
    as harness/tracered.short_name makes them): Mosaic calls with the
    slot states among their operands, or under the name the program
    gives its step kernel; with `named`, of those with that name."""
    state = state_operand(config)
    return sum(s for n, s in op_seconds.items()
               if (KERNEL in n or CHUNK_KERNEL in n
                   or ("[pallas " in n and state.search(n)))
               and named in n)
