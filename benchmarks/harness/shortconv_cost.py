"""What a decode step, a join and the decode walk of a decoder with
gated short-convolution layers (`lfm2_moe`: a 3-tap depthwise
convolution whose whole state is two rows, beside attention layers of
64-wide heads and routed experts) must at least read and do, as
functions of the configuration's published keys and of what the step
touched. Kept with the benchmark, so that no later PR can change what
`step.decode_roofline.shortconv` and `kernel.attn_roofline.d64` are
measured against.

A decode step reads, whatever its batch: every conv mixer's matrices
(in E x 3E, out E x E, the taps), every attention layer's projections
and its two head norms, the dense SwiGLUs of the leading layers, every
expert layer's router, bias and norm, the final norm and the head (tied:
the embedding once as the head; the one row a token it gives as the
embedding is left out). It reads the routed experts SOME row chose, and
no others: `experts_hit` counts them, summed over the expert layers and
the steps. For each row it advances it reads the keys and values of the
row's context in the attention layers AT THEIR REAL WIDTH — kv heads x
head_dim x (k, v) x 2 bytes a position a layer, 2048 B here, whatever
the rows of the pool are padded or packed to — and reads and writes the
row's conv tails (taps - 1 rows of E values a conv layer, in the dtype
the file's `assumed.state_dtype` states: bfloat16). These are floors: a
layout that pads reads a lower share; a program that read its pages
narrower than the file states would read over 100, and the readers raise
there.

A join's operations, a token: two a parameter it multiplies — the
mixers, the dense SwiGLUs, the routers, `num_experts_per_tok` experts a
sparse layer, the head for the one row a run that is scored — and four
a query head a head_dim a position it attends over.
"""

from __future__ import annotations

from typing import Any

STATE_BYTES = 2               # bfloat16: the file's assumed.state_dtype
DECODE_WALK = "paged_decode_attention"   # the walk, as the program names it
LANES = 128


def is_shortconv(config: dict[str, Any]) -> bool:
    return config.get("model_type") == "lfm2_moe"


def _cell_bytes(config: dict[str, Any]) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[
        config["engine"].get("dtype", "bfloat16")]


def sizes(config: dict[str, Any]) -> dict[str, int]:
    e, depth = int(config["hidden_size"]), int(config["num_hidden_layers"])
    types = list(config["layer_types"])
    heads = int(config["num_attention_heads"])
    dense = min(int(config["num_dense_layers"]), depth)
    return {"e": e, "depth": depth, "conv": types.count("conv"),
            "attention": types.count("full_attention"),
            "dense": dense, "sparse": depth - dense,
            "taps": int(config["conv_L_cache"]),
            "heads": heads, "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config.get("head_dim") or e // heads),
            "experts": int(config["num_experts"]),
            "top_k": int(config["num_experts_per_tok"])}


def conv_params(config: dict[str, Any]) -> int:
    """One conv mixer and its norm: in, the taps, out."""
    s = sizes(config)
    return 4 * s["e"] * s["e"] + s["taps"] * s["e"] + s["e"]


def attention_params(config: dict[str, Any]) -> int:
    s = sizes(config)
    return (2 * s["e"] * s["head_dim"] * (s["heads"] + s["kv_heads"])
            + 2 * s["head_dim"] + s["e"])


def dense_mlp_params(config: dict[str, Any]) -> int:
    e = int(config["hidden_size"])
    return 3 * e * int(config["intermediate_size"]) + e


def expert_params(config: dict[str, Any]) -> int:
    """One routed expert (gate, up, down)."""
    return 3 * int(config["hidden_size"]) \
        * int(config["moe_intermediate_size"])


def sparse_layer_fixed_params(config: dict[str, Any]) -> int:
    """What a sparse layer reads whatever was routed: the router over
    the published experts, its bias (float32: two cells), its norm."""
    s = sizes(config)
    return (s["e"] + 2) * s["experts"] + s["e"]


def fixed_params(config: dict[str, Any]) -> int:
    s = sizes(config)
    return (s["conv"] * conv_params(config)
            + s["attention"] * attention_params(config)
            + s["dense"] * dense_mlp_params(config)
            + s["sparse"] * sparse_layer_fixed_params(config)
            + int(config["vocab_size"]) * s["e"] + s["e"])


def param_count(config: dict[str, Any]) -> int:
    """Every parameter held, the tied embedding once (the router's
    float32 bias, two cells in `fixed_params`, is one parameter)."""
    s = sizes(config)
    return (fixed_params(config) - s["sparse"] * s["experts"]
            + s["sparse"] * s["experts"] * expert_params(config))


def fixed_step_bytes(config: dict[str, Any]) -> int:
    """Bytes every decode step reads, whatever its rows and routing."""
    return fixed_params(config) * _cell_bytes(config)


def kv_bytes_per_position_a_layer(config: dict[str, Any]) -> int:
    """Keys and values of one position of one attention layer at their
    REAL width: 2 x 8 x 64 x 2 = 2048 B."""
    s = sizes(config)
    return 2 * s["kv_heads"] * s["head_dim"] * _cell_bytes(config)


def kv_bytes_per_position(config: dict[str, Any]) -> int:
    return kv_bytes_per_position_a_layer(config) * sizes(config)["attention"]


def state_bytes_per_sequence(config: dict[str, Any]) -> int:
    """taps - 1 rows of E values a conv layer: 8192 B a layer."""
    s = sizes(config)
    return s["conv"] * (s["taps"] - 1) * s["e"] * STATE_BYTES


def pool_operand(config: dict[str, Any]) -> str:
    """The KV pool's shape as the trace prints it among a kernel's
    operands. A pool of heads narrower than a lane row is stored with
    the heads of one token side by side in whole lane rows: [pages,
    page size, kv heads / f, f x head size], f = 128 / head size (where
    that divides the kv heads; else the plain shape)."""
    s = sizes(config)
    engine = config["engine"]
    kh, d = s["kv_heads"], s["head_dim"]
    f = LANES // d if d < LANES and LANES % d == 0 else 1
    if f > 1 and kh % f == 0:
        kh, d = kh // f, d * f
    return "[{},{},{},{}]".format(int(engine["num_pages"]),
                                  int(engine["page_size"]), kh, d)


def decode_walk_seconds(op_seconds: dict[str, float],
                        config: dict[str, Any]) -> float:
    """Device seconds of the decode walk among `op_seconds` (names as
    harness/tracered.short_name makes them): the Mosaic calls the
    program names `paged_decode_attention` with this configuration's
    pool among their operands."""
    pool = pool_operand(config)
    return sum(s for n, s in op_seconds.items()
               if "[pallas " in n and DECODE_WALK in n and pool in n)


def decode_walk_floor(config: dict[str, Any], context_lengths) -> dict:
    """Least work of the decode walk for one token decoded at each of
    `context_lengths`: the keys and values of its context once in every
    attention layer, at their real width, and the scores and the
    weighted sum over them."""
    s = sizes(config)
    total = float(sum(context_lengths))
    return {"bytes": total * kv_bytes_per_position(config),
            "flops": total * 4.0 * s["heads"] * s["head_dim"]
            * s["attention"]}


def decode_floor(config: dict[str, Any], *, steps: int, experts_hit: int,
                 row_steps: int, context_positions: int) -> dict:
    """Least work of `steps` decode steps that hit `experts_hit`
    (expert, layer, step) triples, advanced `row_steps` (row, step)
    pairs and attended over `context_positions` cached positions in
    all."""
    s = sizes(config)
    cell = _cell_bytes(config)
    per_row_params = (fixed_params(config)
                      + s["sparse"] * s["top_k"] * expert_params(config))
    return {
        "bytes": float(steps * fixed_step_bytes(config)
                       + experts_hit * expert_params(config) * cell
                       + row_steps * 2 * state_bytes_per_sequence(config)
                       + context_positions
                       * kv_bytes_per_position(config)),
        "flops": float(2 * row_steps * per_row_params
                       + 4 * context_positions * s["heads"]
                       * s["head_dim"] * s["attention"]),
    }


def join_flops(config: dict[str, Any], *, tokens: int, runs: int,
               attended_positions: int) -> float:
    """Operations of join programs that fed `tokens` tokens in `runs`
    runs (one scored row each) and attended over `attended_positions`
    (query, key) pairs a layer in all."""
    s = sizes(config)
    e = s["e"]
    per_token = (fixed_params(config) - int(config["vocab_size"]) * e
                 + s["sparse"] * s["top_k"] * expert_params(config))
    return float(2 * tokens * per_token
                 + 2 * runs * int(config["vocab_size"]) * e
                 + 4 * attended_positions * s["heads"] * s["head_dim"]
                 * s["attention"])
