"""What a decode step of a hybrid decoder (`nemotron_h`: Mamba-2, routed
and shared experts, attention) must at least read and do, as functions
of the configuration's published keys and of what the step touched.
Kept with the benchmark, so that no later PR can change what
`step.decode_roofline` is measured against.

A decode step reads, whatever its batch: every mixer's weights, every
expert layer's shared expert and router, the final norm and the head
(the embedding gives one row a token: left out). It reads the routed
experts SOME row chose, and no others: `experts_hit` counts them, summed
over the expert layers and the steps. For each row it reads the keys
and values of its context (attention layers only) and reads and writes
its recurrent state (float32). These are floors: a step that copies an
expert before it multiplies, or touches a state twice, does more.

Also here: the sizes the span readers need (held experts, expert
layers, the snapshot budget), read from the same keys.
"""

from __future__ import annotations

from typing import Any

LETTERS = {"M": "mamba2", "E": "experts", "*": "attention"}


def layer_kinds(config: dict[str, Any]) -> list[str]:
    return [LETTERS[c] for c in config["hybrid_override_pattern"]]


def count(config: dict[str, Any], kind: str) -> int:
    return layer_kinds(config).count(kind)


def _weight_bytes(config: dict[str, Any]) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[
        config["engine"].get("dtype", "bfloat16")]


def mamba2_params(config: dict[str, Any]) -> int:
    e = int(config["hidden_size"])
    heads = int(config["mamba_num_heads"])
    d_in = heads * int(config["mamba_head_dim"])
    conv = d_in + 2 * int(config["n_groups"]) * int(config["ssm_state_size"])
    return (e * (d_in + conv + heads) + d_in * e
            + (int(config["conv_kernel"]) + 1) * conv + 3 * heads + d_in + e)


def attention_params(config: dict[str, Any]) -> int:
    e = int(config["hidden_size"])
    d = int(config["head_dim"])
    return (2 * e * d * (int(config["num_attention_heads"])
                         + int(config["num_key_value_heads"])) + e)


def expert_params(config: dict[str, Any]) -> int:
    """One routed expert."""
    return 2 * int(config["hidden_size"]) \
        * int(config["moe_intermediate_size"])


def expert_layer_fixed_params(config: dict[str, Any]) -> int:
    """What an expert layer reads whatever was routed: the shared
    expert, the router over the published experts, its norm."""
    e = int(config["hidden_size"])
    published = int(config["n_routed_experts"]) \
        * int(config.get("ep_size", 1))
    return (2 * e * int(config["moe_shared_expert_intermediate_size"])
            + (e + 1) * published + e)


def state_bytes_per_sequence(config: dict[str, Any]) -> int:
    heads = int(config["mamba_num_heads"])
    p, n = int(config["mamba_head_dim"]), int(config["ssm_state_size"])
    conv = heads * p + 2 * int(config["n_groups"]) * n
    per = (heads * p * n + (int(config["conv_kernel"]) - 1) * conv) * 4
    return per * count(config, "mamba2")


def kv_bytes_per_position(config: dict[str, Any]) -> int:
    """Keys and values of one position, attention layers only."""
    return (2 * int(config["num_key_value_heads"])
            * int(config["head_dim"]) * _weight_bytes(config)
            * count(config, "attention"))


def fixed_step_bytes(config: dict[str, Any]) -> int:
    """Bytes every decode step reads, whatever its rows and routing."""
    e = int(config["hidden_size"])
    params = (count(config, "mamba2") * mamba2_params(config)
              + count(config, "attention") * attention_params(config)
              + count(config, "experts")
              * expert_layer_fixed_params(config)
              + int(config["vocab_size"]) * e + e)
    return params * _weight_bytes(config)


def decode_floor(config: dict[str, Any], *, steps: int, experts_hit: int,
                 row_steps: int, context_positions: int) -> dict:
    """Least work of `steps` decode steps that hit `experts_hit`
    (expert, layer, step) triples, advanced `row_steps` (row, step)
    pairs and attended over `context_positions` cached positions in
    all."""
    wb = _weight_bytes(config)
    top_k = int(config["num_experts_per_tok"])
    held_share = 1.0 / int(config.get("ep_size", 1))
    dense = fixed_step_bytes(config) // wb
    per_row_params = dense + count(config, "experts") * top_k \
        * held_share * expert_params(config)
    return {
        "bytes": float(steps * fixed_step_bytes(config)
                       + experts_hit * expert_params(config) * wb
                       + row_steps * 2 * state_bytes_per_sequence(config)
                       + context_positions
                       * kv_bytes_per_position(config)),
        "flops": float(2 * row_steps * per_row_params
                       + 4 * context_positions
                       * int(config["num_attention_heads"])
                       * int(config["head_dim"])
                       * count(config, "attention")),
    }
