"""The client's side of the gateway socket, for the load generator and
the traffic kinds: one row's record, one POST with server-sent events,
and one round of `POST /v1/discussions`. Standard library only."""

from __future__ import annotations

import asyncio
import json
import time

STREAM_LIMIT = 8 * 1024 * 1024     # one SSE line may carry a long text


class Row:
    """One row's record (one knight's turn), filled as its events
    arrive."""

    def __init__(self, spec: dict, round_no: int, knight: str,
                 prompt_tokens: int, due: float, measured: bool):
        self.rec = {
            "session": spec["session"], "index": spec["index"],
            "round": round_no, "knight": knight,
            "prompt_tokens": prompt_tokens,
            "asked_tokens": spec["max_new_tokens"],
            "due": due, "sent": None, "first": None, "last": None,
            "tokens": 0, "flushes": [], "ids": [], "ok": False,
            "error": None, "measured": measured}

    def heard(self, t: float, n_new: int, ids=None) -> None:
        if n_new <= 0:
            return
        rec = self.rec
        if rec["first"] is None:
            rec["first"] = t
        rec["last"] = t
        rec["tokens"] += n_new
        rec["flushes"].append([t, n_new])
        if ids:
            rec["ids"].extend(int(i) for i in ids)


async def post_sse(port: int, path: str, body: dict, on_event,
                   timeout_s: float) -> tuple[int, str]:
    """One POST. Calls on_event(time, sse_id, data) for every SSE event
    until it returns True or the server closes. → (status, reason)."""
    payload = json.dumps(body).encode("utf-8")

    async def run() -> tuple[int, str]:
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", port, limit=STREAM_LIMIT)
        try:
            writer.write(
                (f"POST {path} HTTP/1.1\r\nHost: bench\r\n"
                 f"Content-Length: {len(payload)}\r\n\r\n"
                 ).encode("latin-1") + payload)
            await writer.drain()
            status = int((await reader.readline()).split()[1])
            length = 0
            while True:
                line = (await reader.readline()).decode("latin-1").strip()
                if not line:
                    break
                name, _, value = line.partition(":")
                if name.lower() == "content-length":
                    length = int(value)
            if status != 200:
                raw = await reader.readexactly(length) if length else b""
                try:
                    reason = json.loads(raw).get("reason", "")
                except ValueError:
                    reason = raw[:80].decode("latin-1")
                return status, str(reason)
            sse_id = ""
            while True:
                raw = await reader.readline()
                if not raw:
                    return 200, "closed"
                if raw.startswith(b"id: "):
                    sse_id = raw[4:].strip().decode("latin-1")
                elif raw.startswith(b"data: "):
                    if on_event(time.monotonic(), sse_id,
                                raw[6:].strip().decode("utf-8")):
                        return 200, "done"
        finally:
            writer.close()

    try:
        return await asyncio.wait_for(run(), timeout=max(timeout_s, 0.05))
    except asyncio.TimeoutError:
        return 0, "timeout"
    except (OSError, asyncio.IncompleteReadError, ValueError) as e:
        return 0, f"{type(e).__name__}: {e}"


async def discussion_round(port: int, spec: dict,
                           turns: list[tuple[str, list[int]]],
                           rows: list[Row], deadline_s: float,
                           timeout_s: float) -> bool:
    """One POST /v1/discussions asking `turns` (knight, prompt ids);
    fills `rows`, one per turn. → finished cleanly."""
    state = {"retired": False, "error": None}

    def on_event(t: float, _sse_id: str, data: str) -> bool:
        ev = json.loads(data)
        kind = ev.get("type")
        if kind == "tokens":
            rows[ev["row"]].heard(t, len(ev["tokens"]), ev["tokens"])
        elif kind == "summary":
            for i, row in ev["rows"].items():
                rows[int(i)].heard(t, len(row["tokens"]), row["tokens"])
        elif kind == "retired":
            state["retired"] = True
            return True
        elif kind == "failed":
            state["error"] = f"failed:{ev.get('kind')}"
            return True
        return False

    sent = time.monotonic()
    for row in rows:
        row.rec["sent"] = sent
    status, reason = await post_sse(port, "/v1/discussions", {
        "session": spec["session"],
        "max_new_tokens": spec["max_new_tokens"],
        "temperature": spec["temperature"], "deadline_s": deadline_s,
        "turns": [{"knight": k, "prompt": p} for k, p in turns]},
        on_event, timeout_s)
    ok = status == 200 and state["retired"]
    for row in rows:
        row.rec["ok"] = ok and row.rec["tokens"] > 0
        if not row.rec["ok"]:
            row.rec["error"] = state["error"] or f"{status}:{reason}"
    return ok
