"""Traffic kind `discussions`: the roundtable's own traffic, in a closed
loop. A session is one discussion on one session name: `rounds`
requests to `POST /v1/discussions`, each asking `knights` rows for
`max_new_tokens` tokens. Every prompt is a list of byte-tokenizer ids:
the preamble shared by every session, the session's topic, the
transcript so far (each earlier round's cue and served answer, knight
by knight) and the asking knight's cue. Round r+1 is built by the client
from what round r served, so a knight's slot is reused across rounds and
the transcript grows the way `roundtable discuss` grows it.

`concurrency` clients keep one session each alive, starting the next
when the last ends. The loop warms up in itself: first one session alone
for each of `warmup.probe_prompt_tokens` (an empty batch takes the
prologue prefill program of that length's bucket), then
`warmup.sessions_per_client` whole sessions of every client; the window
opens `ramp_s` after the last of those ends.

What a session sends is a pure function of (parameters, seed, index).
Standard library only.
"""

from __future__ import annotations

import asyncio
import random
import time
from typing import Any

from harness import client, traffic

LOOP = "closed"          # the clients set the pace, not a schedule
PROBE_INDEX = 10_000     # probe sessions' indices: no client reaches them


def session_spec(params: dict, seed: int, index: int) -> dict[str, Any]:
    """Session number `index` of this mix under this seed: its name, its
    sizes and its opening transcript. Pure."""
    count = int(params["population"])
    slot = index % count
    new_tokens = traffic.population(params["max_new_tokens"], count, seed,
                                    "new")[slot]
    topic_tokens = traffic.population(params["prompt_tokens"], count, seed,
                                      "prompt")[slot]
    preamble = traffic.text_of(int(params.get("shared_preamble_tokens", 0)),
                               random.Random(f"preamble:{seed}"))
    topic = traffic.text_of(topic_tokens,
                            random.Random(f"session:{seed}:{index}"))
    return {
        "index": index, "session": f"bench-{seed}-{index}",
        "knights": list(traffic.KNIGHT_NAMES[:int(params["knights"])]),
        "rounds": int(params["rounds"]),
        "max_new_tokens": new_tokens,
        "temperature": float(params["temperature"]),
        "opening": ([traffic.BOS_ID] + traffic.byte_ids(preamble)
                    + traffic.byte_ids(topic)),
    }


def cue_ids(knight: str, round_no: int) -> list[int]:
    return traffic.byte_ids(f"\n[round {round_no}] {knight}: ")


def round_prompts(spec: dict, transcript: list[int], round_no: int
                  ) -> list[tuple[str, list[int]]]:
    """The (knight, prompt ids) rows of one discussion round."""
    return [(k, transcript + cue_ids(k, round_no))
            for k in spec["knights"]]


def grow_transcript(spec: dict, transcript: list[int], round_no: int,
                    answers: list[list[int]]) -> list[int]:
    """The transcript after a round: each knight's cue and what the
    system served for it, in seating order."""
    out = list(transcript)
    for knight, answer in zip(spec["knights"], answers):
        out += cue_ids(knight, round_no) + [int(t) for t in answer]
    return out


async def session(run, params: dict, index: int, due: float) -> None:
    """Every round of one session, the first one due at `due`."""
    spec = session_spec(params, run.seed, index)
    transcript = spec["opening"]
    for round_no in range(1, spec["rounds"] + 1):
        if round_no > 1:
            due = time.monotonic()
            if due >= run.end:
                return
        turns = round_prompts(spec, transcript, round_no)
        rows = [client.Row(spec, round_no, k, len(p), due,
                           run.measured(due)) for k, p in turns]
        for row, (_k, p) in zip(rows, turns):
            run.keep(row, p)
        run.rows.extend(rows)
        if not await client.discussion_round(
                run.port, spec, turns, rows, run.deadline_s(),
                run.timeout_s()):
            return
        transcript = grow_transcript(spec, transcript, round_no,
                                     [r.rec["ids"] for r in rows])


async def probes(run) -> None:
    """One two-token, one-round session alone for each listed prompt
    length."""
    base = dict(run.params, rounds=1, population=1,
                max_new_tokens={"dist": "fixed", "value": 2})
    for i, tokens in enumerate(
            run.params["warmup"].get("probe_prompt_tokens", [])):
        await session(run, dict(base, prompt_tokens={
            "dist": "fixed", "value": int(tokens)}),
            PROBE_INDEX + i, time.monotonic())


async def drive(run) -> None:
    params = run.params
    clients = int(params["concurrency"])
    warm_sessions = int(params["warmup"]["sessions_per_client"])
    stagger = float(params.get("stagger_s", 0.0))
    warm = {"clients": 0}

    async def one_client(number: int) -> None:
        await asyncio.sleep(number * stagger)
        index, done = number, 0
        while time.monotonic() < run.end:
            await session(run, params, index, time.monotonic())
            index += clients
            done += 1
            if done == warm_sessions:
                warm["clients"] += 1
                if warm["clients"] == clients:
                    run.open_window(time.monotonic() + run.ramp_s)

    await probes(run)
    if warm_sessions <= 0:
        run.open_window(time.monotonic() + run.ramp_s)
    await asyncio.gather(*[one_client(c) for c in range(clients)])
