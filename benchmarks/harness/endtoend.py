"""From the load generator's row records to the end-to-end metrics.

All are taken from the client's side of the gateway socket, over
the rows whose due time fell inside the window. A row that was shed,
failed or had not finished `drain_s` after the window counts in `failed`
and stays in every denominator, at the time it had been waited for when
it was given up."""

from __future__ import annotations

from typing import Any, Optional

from . import stats


def reduce_window(rows: list[dict], start: float, end: float,
                  hard_stop: float) -> dict[str, Any]:
    """→ {"attempted", "failed", "samples", "values": {metric: value}}.
    A percentile the sample cannot support is left out of `values`."""
    measured = [r for r in rows if r["measured"]]
    failed = [r for r in measured if not r["ok"]]
    ttft_ms: list[float] = []
    tpot_ms: list[float] = []
    for r in measured:
        if r["ok"]:
            ttft_ms.append((r["first"] - r["due"]) * 1e3)
            if r["tokens"] > 1:
                tpot_ms.append((r["last"] - r["first"]) * 1e3
                               / (r["tokens"] - 1))
        else:
            waited_ms = (hard_stop - r["due"]) * 1e3
            ttft_ms.append(waited_ms)
            tpot_ms.append(waited_ms / max(r["asked_tokens"] - 1, 1))
    # Tokens completed in the window, whoever asked for them and when:
    # the rate is over all the work and all the time of the window.
    tokens = sum(n for r in rows for t, n in r["flushes"]
                 if start <= t < end)
    values: dict[str, Optional[float]] = {
        "ttft_p50_ms": stats.supported_percentile(ttft_ms, 0.50),
        "ttft_p90_ms": stats.supported_percentile(ttft_ms, 0.90),
        "ttft_p95_ms": stats.supported_percentile(ttft_ms, 0.95),
        "tpot_p50_ms": stats.supported_percentile(tpot_ms, 0.50),
        "tpot_p95_ms": stats.supported_percentile(tpot_ms, 0.95),
        "tokens_per_s": tokens / (end - start) if end > start else None,
    }
    return {"attempted": len(measured), "failed": len(failed),
            "samples": {"ttft": len(ttft_ms), "tpot": len(tpot_ms)},
            "window_tokens": tokens,
            "errors": sorted({str(r["error"]) for r in failed})[:8],
            "values": {k: v for k, v in values.items() if v is not None}}


def of_context(ctx: dict) -> dict[str, float]:
    """The window's values from a per-layer reader's context: what the
    client saw, for the readers that report it beside the device's
    view."""
    w = ctx["window"]
    return reduce_window(
        ctx["rows"], w["start"], w["end"],
        w["end"] + float(ctx["traffic"]["drain_s"]))["values"]
