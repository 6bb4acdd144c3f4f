"""What a decode step and the decode walk of a `phi4flash` decoder (SambaY:
Mamba-1 layers and differential attention below, gated memory units and
differential cross layers above, which keep no cache of their own) must
at least read and do, as functions of the configuration's published keys
and the family's defaults the file lists under `assumed`. Kept with the
benchmark, so that no later PR can change what
`kernel.attn_roofline.diff` and `step.decode_roofline.sambay` are
measured against. The MODEL's work is counted, not the program's: the
zeros a packed query row carries are no operations here.

The layers, by the model's own depth rule over L = `num_hidden_layers`
(L % 4 == 0, `mb_per_layer` 2): L/4 + 1 Mamba-1 layers, L/4 window
layers, one full layer, L/4 - 1 gated memory units, L/4 - 1 cross
layers; every layer a SwiGLU MLP behind it.

Pages. A position of one POOLED layer (window or full) holds keys and
values of every kv head at their real width: 2 x 20 x 64 x 2 B = 5120 B.
The cross layers own none: each reads the full layer's. A decoded token
at context length C reads, in a window layer, min(C, W) positions; in
the full layer and in every cross layer, C.

Differential attention, a position a reading layer: every query head one
64-wide score (2 x 64 operations) and one weighted sum over the 128-wide
value pair (2 x 128): 384 a query head, 15 360 at 40 heads — half again
what plain attention of the same heads does, on the same bytes.

The state. d_inner x d_state float32 values and a (d_conv - 1)-row tail a
Mamba layer a sequence, read once and written once a decoded token; the
memory `m` (d_inner float32 a row a step) is made and used inside the
step and is no HBM traffic a floor could count.
"""

from __future__ import annotations

from typing import Any

STATE_BYTES = 4          # float32: the file's assumed.state_dtype
DECODE_WALK = "paged_decode_attention"
OPS_PER_PAIR = 7         # harness/mamba1_cost.py: the scan, a (n, d) pair
LANES = 128


def is_sambay(config: dict[str, Any]) -> bool:
    return config.get("model_type") == "phi4flash"


def _cell_bytes(config: dict[str, Any]) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[
        config["engine"].get("dtype", "bfloat16")]


def sizes(config: dict[str, Any]) -> dict[str, int]:
    e, depth = int(config["hidden_size"]), int(config["num_hidden_layers"])
    heads = int(config["num_attention_heads"])
    quarter = depth // 4
    return {"e": e, "depth": depth, "mamba": quarter + 1,
            "window_layers": quarter, "full": 1, "gmu": quarter - 1,
            "cross": quarter - 1,
            "window": int(config["sliding_window"]),
            "d": int(config.get("mamba_expand", 2)) * e,
            "n": int(config.get("mamba_d_state", 16)),
            "k": int(config.get("mamba_d_conv", 4)),
            "r": int(config.get("mamba_dt_rank", -(-e // 16))),
            "f": int(config["intermediate_size"]),
            "heads": heads, "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config.get("head_dim") or e // heads)}


def mamba_params(config: dict[str, Any]) -> int:
    """One Mamba-1 mixer WITHOUT inner norms and its LayerNorm: in, conv
    and bias, x, dt and its bias, A_log, D, out."""
    s = sizes(config)
    e, d, n, k, r = s["e"], s["d"], s["n"], s["k"], s["r"]
    return (2 * e * d + (k + 1) * d + d * (r + 2 * n) + (r + 1) * d
            + n * d + d + d * e + 2 * e)


def _differential_extras(s: dict[str, int]) -> int:
    # four lambda vectors, the pair norm, the derived l0 the tree stores
    return 4 * s["head_dim"] + 2 * s["head_dim"] + 1


def attention_params(config: dict[str, Any]) -> int:
    """A window or full layer: q, k, v, o with bias, and its norm."""
    s = sizes(config)
    e, h, k, d = s["e"], s["heads"], s["kv_heads"], s["head_dim"]
    return (2 * e * h * d + h * d + e + 2 * (e * k * d + k * d)
            + _differential_extras(s) + 2 * e)


def cross_params(config: dict[str, Any]) -> int:
    """A cross layer: q and o with bias, no k, no v."""
    s = sizes(config)
    e, h, d = s["e"], s["heads"], s["head_dim"]
    return 2 * e * h * d + h * d + e + _differential_extras(s) + 2 * e


def gmu_params(config: dict[str, Any]) -> int:
    s = sizes(config)
    return 2 * s["e"] * s["d"] + 2 * s["e"]


def mlp_params(config: dict[str, Any]) -> int:
    s = sizes(config)
    return 3 * s["e"] * s["f"] + 2 * s["e"]


def param_count(config: dict[str, Any]) -> int:
    """Every parameter held, the tied embedding once."""
    s = sizes(config)
    return (s["mamba"] * mamba_params(config)
            + (s["window_layers"] + s["full"]) * attention_params(config)
            + s["gmu"] * gmu_params(config)
            + s["cross"] * cross_params(config)
            + s["depth"] * mlp_params(config)
            + int(config["vocab_size"]) * s["e"] + 2 * s["e"])


def fixed_step_bytes(config: dict[str, Any]) -> int:
    """Bytes every decode step reads, whatever its rows: the tied
    embedding counts once, as the head."""
    return param_count(config) * _cell_bytes(config)


def kv_bytes_per_position_a_layer(config: dict[str, Any]) -> int:
    s = sizes(config)
    return 2 * s["kv_heads"] * s["head_dim"] * _cell_bytes(config)


def state_bytes_per_sequence(config: dict[str, Any]) -> int:
    s = sizes(config)
    return s["mamba"] * (s["n"] + s["k"] - 1) * s["d"] * STATE_BYTES


def positions_read(config: dict[str, Any], context_lengths) -> dict:
    """Positions x layers one decoded token at each of `context_lengths`
    reads: by the layers that own their pool (window layers at most the
    window), and by the cross layers from the pool they share."""
    s = sizes(config)
    own = sum(s["window_layers"] * min(c, s["window"]) + s["full"] * c
              for c in context_lengths)
    return {"own": float(own),
            "shared": float(s["cross"] * sum(context_lengths))}


def _attention_work(config: dict[str, Any], positions: float) -> dict:
    s = sizes(config)
    return {"bytes": positions * kv_bytes_per_position_a_layer(config),
            "flops": positions * s["heads"] * 6.0 * s["head_dim"]}


def pool_operand(config: dict[str, Any]) -> str:
    """The KV pool's shape as the trace prints it among the decode
    walk's operands. A kv PAIR of 64-wide heads lies in one 128-lane
    row: kv heads / 2 rows of 2 x head size a token. Where those rows
    fill whole tiles (2, 4 or a multiple of 8 of them) the walk takes
    the pool row-major, [pages, page size, rows, width]; where not — ten
    rows here — XLA stores the pool head-major and the walk takes THAT
    view, [pages, rows x page size, width] (my traced runs, PR 56:
    `bf16[640,1280,128]`)."""
    s = sizes(config)
    engine = config["engine"]
    kh, d = s["kv_heads"], s["head_dim"]
    f = LANES // d if d < LANES and LANES % d == 0 else 1
    if f > 1 and kh % f == 0:
        kh, d = kh // f, d * f
    pages, page = int(engine["num_pages"]), int(engine["page_size"])
    if kh in (2, 4) or kh % 8 == 0:
        return "[{},{},{},{}]".format(pages, page, kh, d)
    return "[{},{},{}]".format(pages, kh * page, d)


def decode_walk_seconds(op_seconds: dict[str, float],
                        config: dict[str, Any]) -> float:
    """Device seconds of the decode walk among `op_seconds` (names as
    harness/tracered.short_name makes them): the Mosaic calls the
    program names `paged_decode_attention` with this configuration's
    pool among their operands — the decode program's, and the cross
    layers' above the seam of a join (one row a sequence)."""
    pool = pool_operand(config)
    return sum(s for n, s in op_seconds.items()
               if "[pallas " in n and DECODE_WALK in n and pool in n)


def decode_walk_floor(config: dict[str, Any], context_lengths) -> dict:
    """Least work of the decode walk for one token decoded at each of
    `context_lengths`, every reading layer: the window layers, the full
    layer and the cross layers."""
    read = positions_read(config, context_lengths)
    return _attention_work(config, read["own"] + read["shared"])


def decode_floor(config: dict[str, Any], *, steps: int, row_steps: int,
                 context_lengths) -> dict:
    """Least work of `steps` decode steps that advanced `row_steps`
    (row, step) pairs whose contexts are distributed as
    `context_lengths` (a sample: scaled to `row_steps`)."""
    s = sizes(config)
    lengths = list(context_lengths)
    scale = row_steps / len(lengths) if lengths else 0.0
    read = positions_read(config, lengths)
    attention = _attention_work(config,
                                scale * (read["own"] + read["shared"]))
    dense = param_count(config)
    return {
        "bytes": float(steps * fixed_step_bytes(config)
                       + row_steps * 2 * state_bytes_per_sequence(config)
                       + attention["bytes"]),
        "flops": float(2 * row_steps * dense
                       + row_steps * s["mamba"] * s["d"] * s["n"]
                       * OPS_PER_PAIR + attention["flops"]),
    }
