"""The load generator: a child process of the benchmark that drives the
gateway over its socket. Standard library only — it never imports JAX,
so it neither takes the chip nor shares the server's interpreter lock.
One thread, one asyncio loop.

    python3 loadgen.py <plan.json> <out.json>

The plan names the port, the seed and one of two modes:

- `window`: the traffic mix's parameters and the file of its kind
  (`<path>/traffic/kinds/<kind>.py`), whose `drive(run)` sends the
  mix's sessions: its own warm-up first, then — once it has called
  `run.open_window(t)` — the window [t, t + seconds). The child writes
  `<out.json>.start` when the window's start is fixed. A row is
  *measured* when its due time falls inside the window. Nothing new is
  sent after the window; what is in flight gets `drain_s` seconds more
  and then counts as failed. Warm-up and window are one unbroken
  stretch of traffic, so the batch never runs empty between them.
- `check`: explicit greedy requests, whose token ids come back.

The output holds one record per row (one knight's turn): when it was
due, sent (the difference is how late a scheduled row went out), first
and last heard from, the flushes as (time, new tokens), the ids served,
and whether it finished.
"""

from __future__ import annotations

import asyncio
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from harness import client  # noqa: E402

WARMUP_DEADLINE_S = 1500.0         # survives cold compiles


def load_kind(path: str):
    """A traffic kind's module, from its file."""
    name = "traffic_kind_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Run:
    """The window of one plan, and what a traffic kind needs of it."""

    def __init__(self, plan: dict):
        self.plan = plan
        self.port = int(plan["port"])
        self.params = plan["traffic"]
        self.seed = int(plan["seed"])
        self.ramp_s = float(plan.get("ramp_s") or 0.0)
        self.rows: list[client.Row] = []
        self.kept = 0
        self.out_path = plan.get("out_path", "")
        self.start = self.end = self.hard_stop = float("inf")

    @property
    def warming(self) -> bool:
        return self.start == float("inf")

    def open_window(self, start: float) -> None:
        """Fix the window and tell the parent where it starts."""
        self.start = start
        self.end = start + float(self.plan["seconds"])
        self.hard_stop = self.end + float(self.plan["drain_s"])
        if self.out_path:
            tmp = self.out_path + ".start.tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump({"start_at": start}, f)
            os.replace(tmp, self.out_path + ".start")

    def measured(self, due: float) -> bool:
        return self.start <= due < self.end

    def keep(self, row: client.Row, prompt: list[int]) -> None:
        """Keep the prompt ids of the first few measured rows short
        enough for the reference: the check sends them again."""
        if (row.rec["measured"] and self.kept < int(
                self.plan.get("keep_prompts", 0))
                and len(prompt) <= int(self.plan["keep_prompt_max"])):
            row.rec["prompt"] = list(prompt)
            self.kept += 1

    def deadline_s(self) -> float:
        """What a request sent now tells the server it may take."""
        return WARMUP_DEADLINE_S if self.warming \
            else float(self.plan["deadline_s"])

    def timeout_s(self) -> float:
        """How long a request sent now is waited for."""
        if self.warming:
            return WARMUP_DEADLINE_S     # still warming up: it compiles
        return self.hard_stop - time.monotonic()


async def check(plan: dict) -> list[dict]:
    """Explicit greedy requests over /v1/discussions, one at a time."""
    out = []
    for i, req in enumerate(plan["requests"]):
        spec = {"session": f"check-{plan['seed']}-{i}", "index": i,
                "max_new_tokens": int(req["max_new_tokens"]),
                "temperature": 0.0}
        row = client.Row(spec, 1, req["knight"], len(req["prompt"]),
                         time.monotonic(), False)
        await client.discussion_round(
            int(plan["port"]), spec, [(req["knight"], req["prompt"])],
            [row], WARMUP_DEADLINE_S, WARMUP_DEADLINE_S)
        out.append(row.rec)
    return out


def main(argv: list[str]) -> int:
    with open(argv[1], encoding="utf-8") as f:
        plan = json.load(f)
    if plan["mode"] == "check":
        result = {"rows": asyncio.run(check(plan))}
    else:
        run = Run(dict(plan, out_path=argv[2]))
        asyncio.run(load_kind(plan["kind_file"]).drive(run))
        result = {"rows": [r.rec for r in run.rows],
                  "start": run.start, "end": run.end}
    tmp = argv[2] + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(result, f)
    os.replace(tmp, argv[2])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
