"""What the attention kernels must at least do for the tokens a window
served: bytes and floating-point operations as functions of each row's
lengths. Kept with the benchmark, so that no later PR can change what a
roofline share is measured against.

For a decoded token at context length L, attention over one layer reads
the keys and values of L positions once (2 x L x kv_heads x head_dim
x bytes) and does 4 x heads x head_dim x L operations (scores and the
weighted sum). These are floors: a kernel that reads a page per query
block, or pads a block, does more.

Which device operations are attention kernels is decided here too, by
what they read and not by a name: the program gives its Pallas kernels
none, so the trace shows them as `%body`, `%ragged_step` after the loop
or function they sit in. An attention kernel is a Mosaic custom call
with the paged KV pool among its operands; a Pallas matmul or dequant
kernel that a later PR adds reads no pool and is not counted.
"""

from __future__ import annotations

from typing import Any


def kv_bytes_per_token(config: dict[str, Any]) -> float:
    """Bytes of keys and values one position holds, all layers."""
    heads = int(config["num_attention_heads"])
    head_dim = int(config.get("head_dim")
                   or int(config["hidden_size"]) // heads)
    cell = 1 if config["engine"].get("kv_quant") in ("int8",) else 2
    return (2.0 * int(config["num_key_value_heads"]) * head_dim * cell
            * int(config["num_hidden_layers"]))


def _flops_per_position(config: dict[str, Any]) -> float:
    heads = int(config["num_attention_heads"])
    head_dim = int(config.get("head_dim")
                   or int(config["hidden_size"]) // heads)
    return 4.0 * heads * head_dim * int(config["num_hidden_layers"])


def decode_floor(config: dict[str, Any], context_lengths) -> dict:
    """Least work for decoding one token at each of `context_lengths`."""
    total = float(sum(context_lengths))
    return {"bytes": total * kv_bytes_per_token(config),
            "flops": total * _flops_per_position(config)}


def least_seconds(work: dict, peaks: dict[str, Any]) -> dict:
    """The roofline: the larger of operations over the peak rate and
    bytes over the peak bandwidth, and which of the two it is."""
    by_flops = work["flops"] / float(peaks["bf16_flops"])
    by_bytes = work["bytes"] / float(peaks["hbm_bytes_per_s"])
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops > by_bytes else "memory"}


def pool_operand(config: dict[str, Any]) -> str:
    """The KV pool's shape as the trace prints it among a kernel's
    operands: [pages, page size, kv heads, head size]."""
    heads = int(config["num_attention_heads"])
    head_dim = int(config.get("head_dim")
                   or int(config["hidden_size"]) // heads)
    engine = config["engine"]
    return "[{},{},{},{}]".format(
        int(engine["num_pages"]), int(engine["page_size"]),
        int(config["num_key_value_heads"]), head_dim)


def attention_seconds(op_seconds: dict[str, float],
                      config: dict[str, Any]) -> float:
    """Device seconds of the attention kernels among `op_seconds`
    (names as harness/tracered.short_name makes them)."""
    pool = pool_operand(config)
    return sum(s for n, s in op_seconds.items()
               if "[pallas " in n and pool in n)


def decoded_in(rows: list[dict], start: float, end: float) -> list[int]:
    """The context length of every token the rows' flushes say was
    decoded in [start, end): a flush's tokens are spread evenly over the
    time since the row's previous flush. The first token of a row comes
    from its prefill and is not a decode."""
    out: list[int] = []
    for r in rows:
        prev_t, done = r["sent"], 0
        for t, n in r["flushes"]:
            for i in range(n):
                at = prev_t + (t - prev_t) * (i + 1) / n
                if done + i > 0 and start <= at < end:
                    out.append(r["prompt_tokens"] + done + i)
            prev_t, done = t, done + n
    return out
