"""What the attention kernels and a decode step of a `laguna` decoder
(Laguna-XS.2: window and full attention layers with heads of their own
over one GQA page pool, a dense MLP or gated routed + shared experts)
must at least read and do, as functions of the configuration's
published keys and of what the step touched. Kept with the benchmark,
so that no later PR can change what `kernel.attn_roofline.window` and
`step.decode_roofline.window` are measured against.

The kernels. Every layer keeps, for one position, the keys and values
of `num_key_value_heads` heads of `head_dim`: 4096 bytes in bfloat16,
whatever the layer's query heads. A decoded token at context L reads,
on a FULL layer, all L of them; on a SLIDING layer only what its window
needs in whole pages from the page the window starts in: L where L <=
window, else window + (L - window) mod page — `window_span`. (The
accepted `harness/kernel_cost.py` multiplies every layer by the whole
context: here it would count 2.4 times what the layers must read at
L = 3000, and a share over 100.) Its operations: 4 x the LAYER's heads x
head_dim for each position inside the causal window (min(L, window)),
a score and a weighted sum. A prefilled token writes its keys and
values once a layer; a join's reads are left out (their offsets are not
known to the benchmark), so a share is a floor of the true one.

Which device operations are attention kernels is `kernel_cost`'s rule
unchanged: a Mosaic call with the pool `[pages, page size, kv heads,
head size]` among its operands — Mistral's shape.

A decode step reads, whatever its batch: every attention layer's
projections at ITS heads (q and out 2 x E x H_l x D, keys and values
2 x E x K x D, the gate E x H_l) and norm, the dense layers' MLP, every
sparse layer's shared expert, router and norm, the final norm and the
head (the embedding gives one row a token: left out). It reads the
routed experts SOME row chose, and no others: `experts_hit` counts
them, summed over the sparse layers and the steps. For each row it
reads the keys and values of its context, by layer class as above.
Floors: a step that copies an expert before it multiplies does more.
"""

from __future__ import annotations

from typing import Any, Optional


def is_laguna(config: dict[str, Any]) -> bool:
    return ("num_attention_heads_per_layer" in config
            and "layer_types" in config)


def _cell_bytes(config: dict[str, Any]) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[
        config["engine"].get("dtype", "bfloat16")]


def attention_layers(config: dict[str, Any]
                     ) -> list[tuple[int, Optional[int]]]:
    """(query heads, window or None) of every attention layer."""
    window = int(config["sliding_window"])
    return [(int(h), window if t == "sliding_attention" else None)
            for h, t in zip(config["num_attention_heads_per_layer"],
                            config["layer_types"])]


def class_counts(config: dict[str, Any]) -> tuple[int, int]:
    """(full layers, sliding layers)."""
    layers = attention_layers(config)
    full = sum(1 for _h, w in layers if w is None)
    return full, len(layers) - full


def sparse_layers(config: dict[str, Any]) -> int:
    return list(config["mlp_layer_types"]).count("sparse")


def kv_bytes_per_position_a_layer(config: dict[str, Any]) -> int:
    return (2 * int(config["num_key_value_heads"])
            * int(config["head_dim"]) * _cell_bytes(config))


def window_span(context: int, window: Optional[int], page: int) -> int:
    """Positions a layer must read for one token at `context`: all of
    them, or the window and what precedes it in the page it starts in."""
    if window is None or context <= window:
        return context
    return window + (context - window) % page


def decode_kernel_floor(config: dict[str, Any], context_lengths) -> dict:
    """Least work of the attention kernels for one decoded token at each
    of `context_lengths`, every layer by its own geometry."""
    page = int(config["engine"]["page_size"])
    per = kv_bytes_per_position_a_layer(config)
    d = int(config["head_dim"])
    work = {"bytes": 0.0, "flops": 0.0}
    for heads, window in attention_layers(config):
        for length in context_lengths:
            work["bytes"] += per * window_span(length, window, page)
            work["flops"] += 4.0 * heads * d * (
                length if window is None else min(length, window))
    return work


def prefill_write_bytes(config: dict[str, Any], tokens: int) -> float:
    return float(tokens * kv_bytes_per_position_a_layer(config)
                 * len(attention_layers(config)))


def unwindowed_visits(full_visits: float, config: dict[str, Any]) -> float:
    """Page visits a model of the same layers WITHOUT windows would make
    where its full layers made `full_visits`: every layer a full one."""
    full, sliding = class_counts(config)
    return full_visits * (full + sliding) / full if full else 0.0


# --- the step ----------------------------------------------------------------


def attention_params(config: dict[str, Any], heads: int) -> int:
    e, d = int(config["hidden_size"]), int(config["head_dim"])
    return (2 * e * heads * d
            + 2 * e * int(config["num_key_value_heads"]) * d
            + e * heads + e)         # the gate: one logit a head


def dense_mlp_params(config: dict[str, Any]) -> int:
    e = int(config["hidden_size"])
    return 3 * e * int(config["intermediate_size"]) + e


def expert_params(config: dict[str, Any]) -> int:
    """One routed expert (gate, up, down)."""
    return 3 * int(config["hidden_size"]) \
        * int(config["moe_intermediate_size"])


def sparse_layer_fixed_params(config: dict[str, Any]) -> int:
    """What a sparse layer reads whatever was routed: the shared expert,
    the router over the published experts, its norm."""
    e = int(config["hidden_size"])
    return (3 * e * int(config["shared_expert_intermediate_size"])
            + e * int(config["num_experts"]) + e)


def fixed_step_bytes(config: dict[str, Any]) -> int:
    """Bytes every decode step reads, whatever its rows and routing."""
    e = int(config["hidden_size"])
    sparse = sparse_layers(config)
    dense = len(config["mlp_layer_types"]) - sparse
    params = (sum(attention_params(config, h)
                  for h, _w in attention_layers(config))
              + dense * dense_mlp_params(config)
              + sparse * sparse_layer_fixed_params(config)
              + int(config["vocab_size"]) * e + e)
    return params * _cell_bytes(config)


def decode_floor(config: dict[str, Any], *, steps: int, experts_hit: int,
                 row_steps: int, context_lengths) -> dict:
    """Least work of `steps` decode steps that hit `experts_hit`
    (expert, layer, step) triples and advanced `row_steps` (row, step)
    pairs whose contexts were, on average, as `context_lengths` are."""
    cell = _cell_bytes(config)
    contexts = list(context_lengths)
    kernels = decode_kernel_floor(config, contexts)
    scale = row_steps / len(contexts) if contexts else 0.0
    per_row_params = (fixed_step_bytes(config) // cell
                      + sparse_layers(config)
                      * int(config["num_experts_per_tok"])
                      * expert_params(config))
    return {
        "bytes": float(steps * fixed_step_bytes(config)
                       + experts_hit * expert_params(config) * cell
                       + scale * kernels["bytes"]),
        "flops": float(2 * row_steps * per_row_params
                       + scale * kernels["flops"]),
    }
