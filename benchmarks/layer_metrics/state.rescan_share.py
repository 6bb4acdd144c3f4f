"""State cache: of the prompt tokens admitted in the traced slice, the
share whose pages were there and whose recurrent state was not — the
tokens an admission re-scanned although their keys and values were
cached. Read from the `admit` spans: the sum of `kv_matched_tokens` less
`state_reused_tokens` over the sum of `prompt_tokens`. Without a slice
(a rehearsal on the CPU) the same two sums over the whole run, from the
registry's `roundtable_state_*` counters. A program whose admissions
carry no such attribute (no recurrent state) gives nothing to read."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import loopspans  # noqa: E402


def read(ctx):
    spans = loopspans.slice_spans(ctx)
    if spans is not None:
        admits = [r.get("attrs", {}) for r in spans
                  if r["rung"] == "admit"]
        admits = [a for a in admits if "kv_matched_tokens" in a]
        prompt = sum(a["prompt_tokens"] for a in admits)
        if not prompt:
            return None
        return 100.0 * sum(a["kv_matched_tokens"]
                           - a["state_reused_tokens"]
                           for a in admits) / prompt
    if ctx.get("slice") is not None:
        return None
    from theroundtaible_tpu.utils import telemetry
    total = getattr(telemetry.REGISTRY, "counter_total", None)
    if total is None:
        return None
    prompt = total("roundtable_state_prompt_tokens_total")
    if not prompt:
        return None
    return 100.0 * total("roundtable_state_rescanned_tokens_total") / prompt
