"""What the latent-attention kernels and a decode step of an `axk1`
decoder (A.X-K1: multi-head latent attention beside a dense MLP or gated
routed + shared experts) must at least read and do, as functions of the
configuration's published keys and of what the step touched. Kept with
the benchmark, so that no later PR can change what `kernel.mla_roofline`
and `step.decode_roofline.mla` are measured against.

The kernels (absorbed form). A page holds, for one position of one
layer, the normed c_kv (`kv_lora_rank`) and the roped shared key part
(`qk_rope_head_dim`): 576 values, 1152 bytes in bfloat16 — the
PUBLISHED bytes, whatever the program pads a page to, so that padding
shows as lost share. A decoded token at context L reads L of them a
layer, once (keys and values are the same bytes), and does, for each of
its heads, a score over 576 columns and a weighted sum over 512:
2 x heads x (576 + 512) x L operations a layer. At 64 heads that is 121
operations a byte: between the memory and the compute roof of a v5e.

Which device operations are latent kernels is decided by what they read
(harness/kernel_cost.py's rule): a Mosaic call with the latent pool
`[pages, page size, padded width]` among its operands. The decode kernel
among them is the one the program names `mla_paged_decode`.

A decode step reads, whatever its batch: every layer's attention
weights (down- and up-projections, W_UKV, W_O), the dense layers' MLP,
every expert layer's shared expert and router, the norms, the final norm
and the head (the embedding gives one row a token: left out). It reads
the routed experts SOME row chose, and no others: `experts_hit` counts
them, summed over the expert layers and the steps. For each row it
reads the latent entries of its context. Floors: a step that copies an
expert before it multiplies does more.
"""

from __future__ import annotations

from typing import Any

LANES = 128


def _cell_bytes(config: dict[str, Any]) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[
        config["engine"].get("dtype", "bfloat16")]


def is_mla(config: dict[str, Any]) -> bool:
    return "kv_lora_rank" in config


def blocks(config: dict[str, Any]) -> int:
    """Published layers: each holds one attention layer."""
    return int(config["num_hidden_layers"])


def expert_blocks(config: dict[str, Any]) -> int:
    return blocks(config) - int(config["first_k_dense_replace"])


def entry_width(config: dict[str, Any]) -> int:
    return int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])


def latent_bytes_per_position(config: dict[str, Any]) -> int:
    """Published bytes one position holds, all layers."""
    return entry_width(config) * _cell_bytes(config) * blocks(config)


def kernel_flops_per_position(config: dict[str, Any]) -> int:
    """Operations on one cached position by one query token, all
    layers: a score over the entry and a sum over c_kv, every head."""
    return (2 * int(config["num_attention_heads"])
            * (entry_width(config) + int(config["kv_lora_rank"]))
            * blocks(config))


def decode_kernel_floor(config: dict[str, Any], context_lengths) -> dict:
    """Least work of the decode kernel for one token at each of
    `context_lengths`."""
    total = float(sum(context_lengths))
    return {"bytes": total * latent_bytes_per_position(config),
            "flops": total * kernel_flops_per_position(config)}


def pool_operand(config: dict[str, Any]) -> str:
    """The latent pool's shape as the trace prints it among a kernel's
    operands: [pages, page size, the entry in whole lane rows]."""
    engine = config["engine"]
    padded = -(-entry_width(config) // LANES) * LANES
    return "[{},{},{}]".format(int(engine["num_pages"]),
                               int(engine["page_size"]), padded)


def latent_seconds(op_seconds: dict[str, float], config: dict[str, Any],
                   named: str = "") -> float:
    """Device seconds of the latent kernels among `op_seconds` (names as
    harness/tracered.short_name makes them); with `named`, of those the
    program gave that name."""
    pool = pool_operand(config)
    return sum(s for n, s in op_seconds.items()
               if "[pallas " in n and pool in n and named in n)


# --- the step ----------------------------------------------------------------


def attention_params(config: dict[str, Any]) -> int:
    e, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    r_q, r_kv = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
    nope, rot = (int(config["qk_nope_head_dim"]),
                 int(config["qk_rope_head_dim"]))
    v = int(config["v_head_dim"])
    return (e * r_q + r_q + r_q * heads * (nope + rot)
            + e * (r_kv + rot) + r_kv + r_kv * heads * (nope + v)
            + heads * v * e + e)


def dense_mlp_params(config: dict[str, Any]) -> int:
    e = int(config["hidden_size"])
    return 3 * e * int(config["intermediate_size"]) + e


def expert_params(config: dict[str, Any]) -> int:
    """One routed expert (gate, up, down)."""
    return 3 * int(config["hidden_size"]) \
        * int(config["moe_intermediate_size"])


def expert_layer_fixed_params(config: dict[str, Any]) -> int:
    """What an expert layer reads whatever was routed: the shared
    experts, the router over the published experts, its norm."""
    e = int(config["hidden_size"])
    published = int(config["n_routed_experts"]) \
        * int(config.get("ep_size", 1))
    return (int(config["n_shared_experts"]) * expert_params(config)
            + e * published + e)


def fixed_step_bytes(config: dict[str, Any]) -> int:
    """Bytes every decode step reads, whatever its rows and routing."""
    e = int(config["hidden_size"])
    params = (blocks(config) * attention_params(config)
              + int(config["first_k_dense_replace"])
              * dense_mlp_params(config)
              + expert_blocks(config) * expert_layer_fixed_params(config)
              + int(config["vocab_size"]) * e + e)
    return params * _cell_bytes(config)


def decode_floor(config: dict[str, Any], *, steps: int, experts_hit: int,
                 row_steps: int, context_positions: int) -> dict:
    """Least work of `steps` decode steps that hit `experts_hit`
    (expert, layer, step) triples, advanced `row_steps` (row, step)
    pairs and attended over `context_positions` cached positions in
    all."""
    cell = _cell_bytes(config)
    held_share = 1.0 / int(config.get("ep_size", 1))
    per_row_params = (fixed_step_bytes(config) // cell
                      + expert_blocks(config)
                      * int(config["num_experts_per_tok"]) * held_share
                      * expert_params(config))
    return {
        "bytes": float(steps * fixed_step_bytes(config)
                       + experts_hit * expert_params(config) * cell
                       + context_positions
                       * latent_bytes_per_position(config)),
        "flops": float(2 * row_steps * per_row_params
                       + context_positions
                       * kernel_flops_per_position(config)),
    }
