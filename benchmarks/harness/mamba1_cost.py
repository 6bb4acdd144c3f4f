"""What the selective-scan kernel and a decode step of a decoder with
Mamba-1 layers (`jamba`: a float32 state [d_inner, d_state] a layer a
sequence beside a few attention layers' pages) must at least read and
do, as functions of the configuration's published keys. Kept with the
benchmark, so that no later PR can change what
`kernel.mamba1_scan_roofline` and `step.decode_roofline.mamba1` are
measured against.

The state. d_inner x d_state values a layer a sequence (5120 x 16), in
the float32 the configuration's `assumed.state_dtype` states — WHATEVER
the program's layout. A layout that pads reads a lower share; a program
that kept its state narrower than the file states would read over 100,
and the readers raise there.

The scan kernel (`mamba1_scan`, one call a Mamba layer a join
dispatch): for every token it scans, `c`, `dt` in and `y` out of d_inner
float32 values each and `B`, `C` of d_state; and the state of a run
once in and once out for each page of positions it advances (a floor: a
run that starts or ends inside a page pays for the whole of it, and a
one-token run riding a join pays for a state a token). Its operations —
for every (state index, channel) pair a product into the exponent, the
exponential, the decayed state, the input's product and sum, the
output's product and sum: 7 a pair a token — are 0.57 M a token a layer
beside 61.6 KB: at the bfloat16 peak 3 ns against 75 ns, so on paper
the BYTES bound it by a factor of 25. In truth nothing of it runs on the
MXU: the exponentials go through the transcendental unit (one register
a cycle) and the multiply-adds through the vector units, for which
`peaks.json` has no figure, and a grid step serves eight tokens. The
share's ceiling is therefore well under 100; it is reported to be
watched, not to be closed.

A decode step reads, whatever its batch: every layer's weights (Mamba
mixers, attention projections, every MLP, the norms), the final norm and
the head (tied: the embedding once as the head; the one row a token it
gives as the embedding is left out); for every row it advances, the
row's state and conv tail once in and once out in every Mamba layer; and
the keys and values of the row's context in the attention layers.
"""

from __future__ import annotations

from typing import Any

STATE_BYTES = 4          # float32: the file's assumed.state_dtype
KERNEL = "mamba1_scan"   # the joins' scan (pallas/mamba1.py)
STEP_KERNEL = "mamba1_step"   # the same kernel at one token a block
OPS_PER_PAIR = 7


def is_mamba1(config: dict[str, Any]) -> bool:
    return config.get("model_type") == "jamba"


def _weight_bytes(config: dict[str, Any]) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[
        config["engine"].get("dtype", "bfloat16")]


def sizes(config: dict[str, Any]) -> dict[str, int]:
    e, depth = int(config["hidden_size"]), int(config["num_hidden_layers"])
    attention = sum(1 for i in range(depth)
                    if i % int(config["attn_layer_period"])
                    == int(config["attn_layer_offset"]))
    heads = int(config["num_attention_heads"])
    return {"e": e, "depth": depth, "attention": attention,
            "mamba": depth - attention,
            "d": int(config["mamba_expand"]) * e,
            "n": int(config["mamba_d_state"]),
            "k": int(config["mamba_d_conv"]),
            "r": int(config["mamba_dt_rank"]),
            "heads": heads, "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config.get("head_dim") or e // heads)}


def state_bytes_per_layer(config: dict[str, Any]) -> int:
    """One sequence, one Mamba layer: the state alone."""
    s = sizes(config)
    return s["d"] * s["n"] * STATE_BYTES


def tail_bytes_per_layer(config: dict[str, Any]) -> int:
    s = sizes(config)
    return (s["k"] - 1) * s["d"] * STATE_BYTES


def state_bytes_per_sequence(config: dict[str, Any]) -> int:
    return sizes(config)["mamba"] * (state_bytes_per_layer(config)
                                     + tail_bytes_per_layer(config))


def mamba_params(config: dict[str, Any]) -> int:
    """One Mamba-1 layer's mixer and its norm: in, conv and bias, x, the
    three small norms, dt and its bias, A_log, D, out."""
    s = sizes(config)
    e, d, n, k, r = s["e"], s["d"], s["n"], s["k"], s["r"]
    return (2 * e * d + (k + 1) * d + d * (r + 2 * n) + r + 2 * n
            + (r + 1) * d + n * d + d + d * e + e)


def attention_params(config: dict[str, Any]) -> int:
    s = sizes(config)
    return (2 * s["e"] * s["head_dim"] * (s["heads"] + s["kv_heads"])
            + s["e"])


def mlp_params(config: dict[str, Any]) -> int:
    e = int(config["hidden_size"])
    return 3 * e * int(config["intermediate_size"]) + e


def param_count(config: dict[str, Any]) -> int:
    s = sizes(config)
    return (s["mamba"] * mamba_params(config)
            + s["attention"] * attention_params(config)
            + s["depth"] * mlp_params(config)
            + int(config["vocab_size"]) * s["e"] + s["e"])


def fixed_step_bytes(config: dict[str, Any]) -> int:
    """Bytes every decode step reads, whatever its rows: the tied
    embedding counts once, as the head."""
    return param_count(config) * _weight_bytes(config)


def kv_bytes_per_position(config: dict[str, Any]) -> int:
    s = sizes(config)
    return (2 * s["kv_heads"] * s["head_dim"] * _weight_bytes(config)
            * s["attention"])


def scan_floor(config: dict[str, Any], scan_tokens: int) -> dict:
    """Least work of the scan kernel over `scan_tokens` (token, Mamba
    layer) pairs: c, dt, y and B, C a token, and the state once in and
    once out a page of them."""
    s = sizes(config)
    page = int(config["engine"]["page_size"])
    token = (3 * s["d"] + 2 * s["n"]) * 4
    return {
        "bytes": float(scan_tokens * token + scan_tokens / page
                       * 2 * state_bytes_per_layer(config)),
        "flops": float(scan_tokens * s["d"] * s["n"] * OPS_PER_PAIR),
    }


def decode_floor(config: dict[str, Any], *, steps: int, row_steps: int,
                 context_positions: int) -> dict:
    """Least work of `steps` decode steps that advanced `row_steps`
    (row, step) pairs and attended over `context_positions` cached
    positions in all."""
    s = sizes(config)
    dense = fixed_step_bytes(config) // _weight_bytes(config)
    state = 2 * state_bytes_per_sequence(config)
    return {
        "bytes": float(steps * fixed_step_bytes(config)
                       + row_steps * state
                       + context_positions * kv_bytes_per_position(config)),
        "flops": float(2 * row_steps * dense
                       + row_steps * s["mamba"] * s["d"] * s["n"]
                       * OPS_PER_PAIR
                       + 4 * context_positions * s["heads"]
                       * s["head_dim"] * s["attention"]),
    }


def kernel_seconds(op_seconds: dict[str, float], named: str = "") -> float:
    """Device seconds of the Mamba-1 kernels among `op_seconds` (names as
    harness/tracered.short_name makes them), by the names the program
    gives them: the joins' scan and the decode step's pass; with
    `named`, of that one alone."""
    names = (named,) if named else (KERNEL, STEP_KERNEL)
    return sum(s for n, s in op_seconds.items()
               if any(k in n for k in names))
