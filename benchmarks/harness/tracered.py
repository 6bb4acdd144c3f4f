"""The reduction from a profiler trace to numbers.

Two steps, so that the arithmetic can be checked on a small recorded
trace by hand (tests/benchmarks/trace_small.json):

1. `load_xplane(path)` reads the profiler's `.xplane.pb` with nothing but
   JAX and keeps what the reduction needs, in plain lists:
   `{"devices": {plane: {line: [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}` — device planes are those
   named `/device:TPU:<n>`; `host` holds the program's own spans, which
   `utils/telemetry.py` mirrors into the trace as `rt:<rung>`, and the
   benchmark's own `bench:slice`, which marks the stretch the counters
   were read over.
2. `reduce(trace)` turns that into busy seconds, seconds per operation
   and per program, and the idle gaps by what the host was doing in
   them.

One clock: the profiler records from `start_trace` until `stop_trace`
returns, which is seconds more than the stretch the benchmark counts
tokens over. So every device event is clipped to the `bench:slice` span,
and the window's length is that span's, both on the trace's own clock;
the host's clock is not used. A trace without the span is reduced over
its own first-to-last device event.

Busy time is the union of the intervals in which an operation ran on the
device's operations line — not the sum, since an enclosing operation
(a `while`) spans its body. Seconds per name are *self* times for the
same reason: an operation's duration less what the operations nested in
it cover.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Iterable, Optional

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_SPAN_PREFIX = "rt:"
SLICE_SPAN = "bench:slice"
_SHAPE = re.compile(r"\b[a-z]+[0-9]+\[[0-9,]*\]")
TOP = 10
# Gaps shorter than this are the device's own turn-around between
# operations, not the host's doing.
MIN_GAP_NS = 20_000


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def short_name(name: str) -> str:
    """The profiler names a device operation by its whole HLO text.
    Keep the instruction's own name. A Mosaic (Pallas) kernel has none
    of its own (`%body.80`, `%body.81`, ... are the per-layer copies of
    one kernel, named after the loop they sit in), so it is marked
    `[pallas <operand shapes>]` and its copies folded into one name:
    what a kernel reads tells the kernels apart (harness/kernel_cost.py
    knows an attention kernel by the KV pool among its operands)."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    if 'custom_call_target="tpu_custom_call"' in rest:
        operands = rest.partition("custom-call(")[2].partition(
            "custom_call_target")[0]
        return "{} [pallas {}]".format(
            head.rsplit(".", 1)[0], " ".join(_SHAPE.findall(operands)))
    return head


def load_xplane(path: str) -> dict[str, Any]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, dict[str, list]] = {}
    host: list[list] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                lines.setdefault(line.name, []).extend(
                    [short_name(e.name), float(e.start_ns),
                     float(e.duration_ns)] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                    if e.name.startswith(HOST_SPAN_PREFIX)
                    or e.name == SLICE_SPAN)
    return {"devices": devices, "host": host}


def merged(intervals: Iterable[tuple[float, float]]
           ) -> list[tuple[float, float]]:
    """The union of [start, end) intervals, as disjoint sorted ones."""
    out: list[tuple[float, float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def self_seconds(events: list[list]) -> dict[str, float]:
    """Seconds per name on one line, each event counted for its own
    duration less that of the events nested inside it."""
    totals: dict[str, float] = {}
    stack: list[list] = []          # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _end, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + max(own, 0.0) / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return totals


# The program's span rungs, outermost first (utils/telemetry.py
# TRACE_RUNGS). Spans of several threads lie side by side in the trace —
# a request's on the gateway's thread, a turn's on the scheduler's — so
# "innermost" goes by rung first and by the later start second.
RUNGS = ("profile", "request", "resume", "discussion", "round", "turn",
         "prefill", "decode", "segment", "dispatch")


def _covering_span(host: list[list], at: float) -> str:
    """The innermost program span open at time `at`."""
    best, best_key = "none", (-2, -1.0)
    for name, start, dur in host:
        if name != SLICE_SPAN and start <= at < start + dur:
            rung = name[len(HOST_SPAN_PREFIX):]
            key = (RUNGS.index(rung) if rung in RUNGS else -1, start)
            if key > best_key:
                best, best_key = name, key
    return best


def clipped(events: list[list], lo: float, hi: float) -> list[list]:
    """The part of every event that lies inside [lo, hi)."""
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append([name, a, b - a])
    return out


def slice_span(trace: dict[str, Any]) -> Optional[tuple[float, float]]:
    """The stretch the reduction covers, on the trace's clock: the
    benchmark's `bench:slice` span, else first to last device event."""
    for name, start, dur in trace["host"]:
        if name == SLICE_SPAN:
            return start, start + dur
    events = [e for lines in trace["devices"].values()
              for e in lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []]
    if not events:
        return None
    return (min(s for _n, s, _d in events),
            max(s + d for _n, s, d in events))


def reduce(trace: dict[str, Any]) -> dict[str, Any]:
    """Busy seconds are averaged over the device planes; operations,
    programs and gaps are summed over them. Everything is clipped to
    `slice_span`, whose length is `window_s`."""
    devices = trace["devices"]
    span = slice_span(trace)
    if not devices or span is None:
        return {}
    lo, hi = span
    window_s = (hi - lo) / 1e9
    busy_total = 0.0
    ops: dict[str, float] = {}
    modules: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for lines in devices.values():
        op_events = clipped(lines.get(OPS_LINE)
                            or lines.get(MODULES_LINE) or [], lo, hi)
        union = merged((s, s + d) for _n, s, d in op_events)
        busy_total += sum(e - s for s, e in union) / 1e9
        for name, sec in self_seconds(op_events).items():
            ops[name] = ops.get(name, 0.0) + sec
        for name, _s, d in clipped(lines.get(MODULES_LINE, []), lo, hi):
            modules[name] = modules.get(name, 0.0) + d / 1e9
        # The slice's two ends count as gaps too: idle is idle.
        edges = [(lo, lo)] + union + [(hi, hi)]
        for (_s0, e0), (s1, _e1) in zip(edges, edges[1:]):
            if s1 - e0 >= MIN_GAP_NS:
                what = _covering_span(trace["host"], (e0 + s1) / 2)
                gaps[what] = gaps.get(what, 0.0) + (s1 - e0) / 1e9
    busy_s = busy_total / len(devices)

    def top(table: dict[str, float]) -> list[list]:
        return [[n, s] for n, s in sorted(
            table.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "devices": len(devices),
            "op_seconds": ops, "module_seconds": modules,
            "device_ops": top(ops), "idle_gaps": top(gaps)}
