"""KV manager: of the prompt tokens of requests finished in the window,
the share that was not prefilled (own slot kept from the last round,
another knight's pages shared, or the cross-session prefix cache)."""


def read(ctx):
    a, b = ctx["counters"]["start"], ctx["counters"]["end"]
    reused = b["reused_tokens"] - a["reused_tokens"]
    prefilled = b["prefill_tokens"] - a["prefill_tokens"]
    total = reused + prefilled
    return 100.0 * reused / total if total > 0 else None
