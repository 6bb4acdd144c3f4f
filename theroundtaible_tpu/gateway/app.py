"""The Gateway: asyncio HTTP/SSE front door over one SessionScheduler.

Endpoints:

| route                     | method | behavior                        |
|---------------------------|--------|---------------------------------|
| /v1/chat/completions      | POST   | OpenAI-compatible; stream=true → SSE chunks + [DONE] |
| /v1/discussions           | POST   | native multi-knight round → SSE token events |
| /v1/streams/<id>          | GET    | reconnect a stream (Last-Event-ID watermark) |
| /v1/admin/roll            | POST   | rolling restart (router fleets) |
| /healthz                  | GET    | liveness + drain state          |
| /metrics                  | GET    | Prometheus exposition snapshot  |

Every admitted stream: one fsynced intent record (gateway/resume.py),
one scheduler submit with `on_commit` bridged onto the asyncio loop,
one `roundtable_gateway_inflight_streams{request=...}` gauge removed
at completion (the PR-6 gauge-leak rule). Generation is GREEDY by
default — that is what makes post-crash re-generation byte-identical
and the resume protocol exact.

Deadline propagation: the client deadline (X-Roundtable-Deadline-S
header or body `deadline_s`, default ROUNDTABLE_GATEWAY_DEFAULT_
DEADLINE_S) becomes a `deadlines.Budget` root handed to submit_async —
an already-spent budget fails fast there with DeadlineExpired (its own
classified kind) before any prefill dispatch.
"""

from __future__ import annotations

import asyncio
import json
import os
import threading
import time
import uuid
from typing import Any, Optional

from ..engine import deadlines
from ..engine.sampling import SamplingParams
from ..engine.scheduler import DeadlineExpired, SchedulerClosed, \
    SchedulerRefused
from ..utils import telemetry, tracing
from .admission import AdmissionController, Decision, _env_float, \
    _env_int, make_budget
from .http import HttpError, Request, SseWriter, read_request, \
    send_json, send_text
from .resume import StreamIntentJournal, committed_rows
from .streams import StreamState, format_event_id, parse_event_id

_DONE_STREAM_CAP = 256   # completed streams kept for reconnects

# Failure kinds where a reconnect should FAIL OVER instead of replaying
# the failure: the stream died with its replica, not with its request —
# under a router, restore it (journal leg 2 / greedy-regen leg 3) on a
# surviving replica rather than handing the corpse back to the client.
_FAILOVER_KINDS = {"device_lost", "engine_dead", "restarting",
                   "data_loss"}


class _Shed(Exception):
    def __init__(self, decision: Decision, trace_id: str = ""):
        super().__init__(decision.reason)
        self.decision = decision
        # Echoed on the shed payload (ISSUE 20): a shed request still
        # has a trace — tail retention keeps it, and the client can
        # quote the id.
        self.trace_id = trace_id


class Gateway:
    """One gateway over one scheduler — or, with `router=`, over a
    SessionRouter's replica fleet (the scheduler argument stays the
    primary: its tokenizer and shared journal serve every replica)."""

    def __init__(self, scheduler, *, host: Optional[str] = None,
                 port: Optional[int] = None,
                 intent_dir: Optional[str] = None,
                 admission: Optional[AdmissionController] = None,
                 router=None):
        self.sched = scheduler
        self.router = router
        self.host = host or os.environ.get(
            "ROUNDTABLE_GATEWAY_HOST", "127.0.0.1")
        self.port = port if port is not None \
            else _env_int("ROUNDTABLE_GATEWAY_PORT", 8080)
        self.admission = admission or AdmissionController(
            scheduler,
            source=router.signals() if router is not None else None)
        self.default_deadline_s = _env_float(
            "ROUNDTABLE_GATEWAY_DEFAULT_DEADLINE_S", 120.0)
        self.sse_buffer = _env_int("ROUNDTABLE_GATEWAY_SSE_BUFFER", 512)
        self.keepalive_s = _env_float(
            "ROUNDTABLE_GATEWAY_KEEPALIVE_S", 15.0)
        # Abandonment linger (ISSUE 19): a stream whose LAST consumer
        # disconnected gets this long for a reconnect before its
        # scheduler round is abandoned (adapters/KV/gauges released).
        # Long enough for the Last-Event-ID resume ladder, short
        # enough that walked-away clients stop burning capacity.
        self.abandon_s = _env_float(
            "ROUNDTABLE_GATEWAY_ABANDON_S", 30.0)
        self.streams: dict[str, StreamState] = {}
        self.resumed_streams = 0
        # Stream-intent journal: rides in the session journal's
        # directory when one is attached (one durable root per pod).
        root = intent_dir
        if root is None and scheduler.journal is not None:
            root = str(scheduler.journal.root)
        self.intents = StreamIntentJournal(root) if root else None
        self._intent_cache: dict[str, dict] = (
            self.intents.load() if self.intents else {})
        # Compaction threshold for the intent journal + cache: above
        # this many records, intents whose turn already committed in
        # the session journal are compacted away (the newest half of
        # the cap stays for leg-2 reconnects). Bounds a long-lived
        # gateway's disk and memory (review fix).
        self.intent_cap = 2 * _DONE_STREAM_CAP
        self._server: Optional[asyncio.AbstractServer] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._stop_event: Optional[asyncio.Event] = None
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    @classmethod
    def boot(cls, scheduler, *, resume_dir: Optional[str] = None,
             **kw) -> "Gateway":
        """Build a gateway, optionally restoring committed sessions
        first: `resume_dir` replays the session journal through the
        library seam (engine/recovery.py) so every session's KV sits
        at its last committed turn before the first reconnect."""
        if resume_dir is not None:
            from ..engine.recovery import resume_from_journal
            resume_from_journal(resume_dir, scheduler=scheduler)
            kw.setdefault("intent_dir", resume_dir)
        return cls(scheduler, **kw)

    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        self._stop_event = asyncio.Event()
        self._server = await asyncio.start_server(
            self._handle_conn, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        telemetry.recorder().record("gateway_start", host=self.host,
                                    port=self.port)

    async def serve_until_stopped(self) -> None:
        await self.start()
        async with self._server:
            await self._stop_event.wait()

    def run(self) -> None:
        """Blocking entry (the CLI): serve until SIGINT."""
        try:
            asyncio.run(self.serve_until_stopped())
        except KeyboardInterrupt:
            pass

    def start_in_thread(self, timeout_s: float = 10.0) -> int:
        """Background entry (tests / embedding): returns the bound
        port once the socket is listening."""
        ready = threading.Event()

        async def _main():
            await self.start()
            ready.set()
            async with self._server:
                await self._stop_event.wait()

        self._thread = threading.Thread(
            target=lambda: asyncio.run(_main()),
            name="gateway", daemon=True)
        self._thread.start()
        if not ready.wait(timeout_s):
            raise RuntimeError("gateway did not start listening")
        return self.port

    def stop(self, timeout_s: float = 10.0) -> None:
        if self._loop is not None and self._stop_event is not None:
            try:
                self._loop.call_soon_threadsafe(self._stop_event.set)
            except RuntimeError:
                pass  # loop already closed
        if self._thread is not None:
            self._thread.join(timeout_s)
        # Close must not leak per-stream gauges (RT-GAUGE-LEAK): any
        # stream still marked inflight drops its series here.
        for sid, st in list(self.streams.items()):
            if not st.done:
                telemetry.REGISTRY.remove_gauge(
                    "roundtable_gateway_inflight_streams",
                    **self._stream_labels(st))
                if st.trace is not None:
                    # A leg cut off by shutdown is an anomaly worth
                    # keeping: flag → tail retention.
                    st.trace.flag("interrupted")
                    st.trace.finish("interrupted")

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------

    def describe(self) -> dict[str, Any]:
        """Keys ⊆ SURFACE_BINDINGS["gateway"] (drift-tested like the
        scheduler's describe)."""
        adm = self.admission
        out = {
            "admitted": adm.admitted,
            "shed": adm.shed,
            "queued": adm.queued,
            "expired": adm.expired,
            "inflight": self._inflight(),
            "draining": self._draining(),
            "resumed_streams": self.resumed_streams,
            "dropped_events": int(telemetry.REGISTRY.counter_total(
                "roundtable_gateway_dropped_events_total")),
            "sessions": len(self.streams),
            "host": self.host,
            "port": self.port,
            "slo": adm.slo.describe(),
            "tracing": {
                "retained": tracing.store().retained,
                "sample_rate": tracing.sample_rate(),
            },
        }
        if self.router is not None:
            out["replicas"] = self.router.describe()
        return out

    def _inflight(self) -> int:
        return sum(1 for s in self.streams.values() if not s.done)

    def _draining(self) -> bool:
        """Fleet-aware drain state: under a router, the front door only
        reports draining when NO replica is open (one rolling replica
        keeps /healthz green and admission flowing to its peers)."""
        if self.router is not None:
            return bool(self.admission.source.drain_state())
        return bool(deadlines.DRAINING
                    or self.sched.paused is not None)

    def _sched_for(self, session: str, adapters: Optional[list] = None
                   ) -> tuple[Any, Optional[str]]:
        """(scheduler, replica-name) that serves this session: the
        router's affinity/load placement, or the one scheduler with no
        replica label in the N=1 case."""
        if self.router is not None:
            rep = self.router.replica_for(session, adapters)
            return rep.scheduler, rep.name
        return self.sched, None

    def _stream_labels(self, state: StreamState) -> dict[str, str]:
        labels = {"request": state.stream_id}
        replica = getattr(state, "replica", None)
        if replica is not None:
            labels["replica"] = replica
        return labels

    # ------------------------------------------------------------------
    # connection handling
    # ------------------------------------------------------------------

    async def _handle_conn(self, reader: asyncio.StreamReader,
                           writer: asyncio.StreamWriter) -> None:
        try:
            req = await read_request(reader)
            if req is not None:
                await self._route(req, writer)
        except _Shed as s:
            d = s.decision
            payload = {
                "error": f"request shed: {d.reason}",
                "reason": d.reason,
            }
            headers = {"Retry-After": f"{max(int(d.retry_after_s), 1)}"}
            if s.trace_id:
                payload["trace"] = s.trace_id
                headers["Traceparent"] = tracing.format_traceparent(
                    s.trace_id)
            await send_json(writer, d.status, payload, headers)
        except HttpError as e:
            try:
                await self._send_error(writer, e.status, str(e),
                                       e.reason,
                                       getattr(e, "trace_id", ""))
            except (ConnectionError, RuntimeError):
                pass
        except (ConnectionError, asyncio.IncompleteReadError):
            pass  # client went away mid-write — its stream state stays
        except Exception as e:  # noqa: BLE001 — one conn must not kill the server
            try:
                await self._send_error(writer, 500, str(e)[:200],
                                       "internal")
            except Exception:  # noqa: BLE001
                pass
        finally:
            try:
                writer.close()
                await writer.wait_closed()
            except Exception:  # noqa: BLE001
                pass

    async def _send_error(self, writer: asyncio.StreamWriter,
                          status: int, error: str, kind: str,
                          trace_id: str = "") -> None:
        """Error the connection WITHOUT corrupting the protocol: once
        an SSE head has been written (the pump path failed late), a
        fresh HTTP status line would land mid-stream as malformed
        bytes — emit a terminal `failed` SSE event instead. The trace
        id (when the failure happened after one existed) rides every
        error payload so a failure always names its trace."""
        if getattr(writer, "_sse_opened", False):
            payload = {"type": "failed", "error": error, "kind": kind}
            if trace_id:
                payload["trace"] = trace_id
            await SseWriter(writer).event(payload)
        else:
            payload = {"error": error, "reason": kind}
            headers = None
            if trace_id:
                payload["trace"] = trace_id
                headers = {"Traceparent": tracing.format_traceparent(
                    trace_id)}
            await send_json(writer, status, payload, headers)

    async def _route(self, req: Request,
                     writer: asyncio.StreamWriter) -> None:
        path = req.path.rstrip("/") or "/"
        if path == "/healthz" and req.method == "GET":
            health = {
                "ok": True,
                "draining": self._draining(),
                "paused": self.sched.paused,
                "inflight": self._inflight(),
            }
            if self.router is not None:
                health["replicas"] = {
                    name: {"dead": d["dead"], "paused": d["paused"]}
                    for name, d in
                    self.router.describe()["replicas"].items()}
            await send_json(writer, 200, health)
            return
        if path == "/metrics" and req.method == "GET":
            await send_text(writer, 200,
                            telemetry.REGISTRY.prometheus_text(),
                            "text/plain; version=0.0.4")
            return
        if path == "/v1/chat/completions" and req.method == "POST":
            await self._chat_completions(req, writer)
            return
        if path == "/v1/discussions" and req.method == "POST":
            await self._discussions(req, writer)
            return
        if path.startswith("/v1/streams/") and req.method == "GET":
            await self._reconnect(req, writer,
                                  path[len("/v1/streams/"):])
            return
        if path == "/v1/admin/roll" and req.method == "POST":
            await self._admin_roll(req, writer)
            return
        raise HttpError(404, f"no route for {req.method} {req.path}",
                        "not_found")

    async def _admin_roll(self, req: Request,
                          writer: asyncio.StreamWriter) -> None:
        """Rolling restart over the fleet (or one named replica) —
        runs off the event loop; in-flight streams keep pumping and
        any stream crossing the roll reconnects through the resume
        ladder."""
        if self.router is None:
            raise HttpError(400, "no router attached: single-engine "
                            "gateway cannot roll", "no_router")
        target = None
        if req.body:
            try:
                target = req.json().get("replica")
            except (ValueError, json.JSONDecodeError) as e:
                raise HttpError(400, f"bad JSON body: {e}", "bad_json")
        loop = asyncio.get_running_loop()
        reports = await loop.run_in_executor(
            None, lambda: self.router.roll(target))
        await send_json(writer, 200, {"rolled": reports})

    # ------------------------------------------------------------------
    # admission + submit (the shared front half of both POST routes)
    # ------------------------------------------------------------------

    def _client_deadline(self, req: Request, body: dict
                         ) -> Optional[float]:
        raw = req.header("x-roundtable-deadline-s")
        if raw is None:
            raw = body.get("deadline_s")
        if raw is None:
            return self.default_deadline_s or None
        try:
            return float(raw)
        except (TypeError, ValueError):
            raise HttpError(400, f"bad deadline: {raw!r}",
                            "bad_deadline")

    def _submit_stream(self, *, session: str,
                       turns: list[tuple[str, Any]], max_new: int,
                       deadline_s: Optional[float], priority: str,
                       adapters: Optional[list], kind: str,
                       temperature: float = 0.0,
                       record_intent: bool = True,
                       traceparent: Optional[str] = None) -> StreamState:
        # One trace per client request (ISSUE 20): join the client's
        # traceparent when one parses, mint a root otherwise. The
        # RequestTrace is the critical-path clock; its span (armed
        # telemetry) is the parent everything downstream hangs off.
        tp = tracing.parse_traceparent(traceparent)
        trace = tracing.RequestTrace(
            tp[0] if tp else None,
            parent_span_id=tp[1] if tp else "",
            kind="request", session=session, endpoint=kind,
            priority=priority, rows=len(turns))
        try:
            with telemetry.attached(trace.context()):
                dec = self.admission.decide(
                    rows=len(turns), inflight=self._inflight(),
                    deadline_s=deadline_s, priority=priority,
                    adapters=adapters)
            if not dec.admit:
                raise _Shed(dec)
            trace.stage("admission")
            stream_id = uuid.uuid4().hex[:16]
            trace.stream_id = stream_id
            if trace.span is not None:
                trace.span.set_attr("stream", stream_id)
            journal = self.sched.journal
            last = journal.last_turn(session) \
                if journal is not None else None
            turn = 0 if last is None else last + 1
            state = StreamState(stream_id, session,
                                [k for k, _p in turns], turn,
                                buffer_cap=self.sse_buffer)
            state.trace = trace
            if record_intent and self.intents is not None:
                rec = self.intents.record(
                    stream_id, session=session,
                    knights=[k for k, _p in turns],
                    prompts=[p for _k, p in turns], turn=turn,
                    max_new=max_new, deadline_s=deadline_s, kind=kind,
                    adapters=adapters, temperature=temperature,
                    trace=trace.trace_id)
                if rec is not None:
                    self._intent_cache[stream_id] = rec
            self._submit_state(state, turns, max_new=max_new,
                               deadline_s=deadline_s, adapters=adapters,
                               temperature=temperature)
            trace.stage("placement")
            trace.replica = getattr(state, "replica", None)
            self.admission.note_admitted(
                queued=dec.queued,
                replica=getattr(state, "replica", None))
            return state
        except _Shed as s:
            trace.flag("shed")
            trace.finish(f"shed:{s.decision.reason}",
                         tail_stage="admission")
            s.trace_id = trace.trace_id
            raise
        except HttpError as e:
            trace.flag("failed")
            trace.finish(f"error:{e.reason}", tail_stage="admission")
            e.trace_id = trace.trace_id
            raise

    def _submit_state(self, state: StreamState,
                      turns: list[tuple[str, Any]], *, max_new: int,
                      deadline_s: Optional[float],
                      adapters: Optional[list],
                      temperature: float = 0.0) -> None:
        """The scheduler half: pick the serving replica (router) or the
        one scheduler (N=1), submit with the streaming seam bridged
        onto the asyncio loop, classify every refusal into the shed
        kinds, and publish the inflight gauge."""
        loop = self._loop
        assert loop is not None, "gateway not started"

        def on_commit(event: dict, _st=state) -> None:
            # Scheduler loop thread → asyncio loop. A closed loop means
            # the gateway is going down; the journal keeps the story.
            try:
                loop.call_soon_threadsafe(self._on_stream_event, _st,
                                          dict(event))
            except RuntimeError:
                pass

        # Placement + submit run under the request trace's context
        # (ISSUE 20): the router's placement span and the scheduler's
        # tele_ctx capture (engine/scheduler.py submit) both read the
        # thread-local stack, so the whole engine-side span tree joins
        # this trace with zero signature changes.
        ctx = state.trace.context() if state.trace is not None else None
        with telemetry.attached(ctx):
            try:
                sched, replica = self._sched_for(state.session, adapters)
            except Exception as e:  # noqa: BLE001 — NoLiveReplica et al.
                self.admission.note_shed("engine_dead")
                raise _Shed(Decision(False, "engine_dead", 503,
                                     4 * self.admission.retry_after_s)) \
                    from e
            state.replica = replica
            sampling = [SamplingParams(temperature=temperature,
                                       max_new_tokens=max_new)
                        for _ in turns]
            timeout_s = deadline_s if deadline_s else 600.0
            try:
                req = sched.submit_async(
                    state.session, turns, max_new_tokens=max_new,
                    timeout_s=timeout_s, sampling_per_turn=sampling,
                    budget=make_budget(deadline_s),
                    adapters_per_turn=adapters, on_commit=on_commit,
                    queue_when_paused=False)
            except DeadlineExpired as e:
                self.admission._count("expired", "deadline_expired")
                raise HttpError(408, str(e), "deadline_expired")
            except deadlines.DrainingError as e:
                self.admission.note_shed("draining", replica=replica)
                raise _Shed(Decision(False, "draining", 503,
                                     self.admission.retry_after_s)) \
                    from e
            except SchedulerRefused as e:
                reason = e.reason or "refused"
                self.admission.note_shed(reason, replica=replica)
                status = 503 if reason in ("fleet.drain", "quiesce") \
                    else 429
                raise _Shed(Decision(False, reason, status,
                                     self.admission.retry_after_s)) \
                    from e
            except SchedulerClosed as e:
                self.admission.note_shed("closed", replica=replica)
                raise _Shed(Decision(False, "closed", 503,
                                     self.admission.retry_after_s)) \
                    from e
            except Exception as e:  # noqa: BLE001 — classify dead engines etc.
                from ..core.errors import classify_error
                kind = classify_error(e)
                self.admission.note_shed(kind, replica=replica)
                raise _Shed(Decision(False, kind, 503,
                                     4 * self.admission.retry_after_s)) \
                    from e
        # Keep the request handle: abandonment (client disconnected,
        # nobody reconnected within abandon_s) flips req.abandoned and
        # the scheduler's health check releases the round's LoRA refs,
        # KV rows and gauges — without it a walked-away client's round
        # would burn capacity to completion.
        state.request = req
        self.streams[state.stream_id] = state
        telemetry.set_gauge("roundtable_gateway_inflight_streams", 1,
                            **self._stream_labels(state))

    def _on_stream_event(self, state: StreamState, event: dict) -> None:
        """Asyncio-loop side of the scheduler's on_commit bridge."""
        first = not any(state.history) and event.get("type") == "tokens"
        trace = state.trace
        if first and trace is not None:
            # Everything since placement was the submit→first-token
            # lump; the scheduler reports its share of that lump spent
            # queued (queue_wait_s on the event), which is carved out
            # so the waterfall separates waiting from prefill.
            trace.stage("prefill")
            trace.carve("prefill", "queue_wait",
                        event.get("queue_wait_s"))
        state.on_commit_event(event)
        if first:
            if trace is not None:
                # TTFT = the stage sum through first_flush — the SAME
                # number the trace waterfall shows, so the admission
                # SLO signal and the trace can never disagree (the old
                # code lumped time.monotonic() - state.created).
                trace.stage("first_flush")
                ttft = trace.ttft()
                slo = self.admission.p95_slo_s
                if slo and ttft > slo:
                    trace.flag("slo_violation")
                self.admission.note_ttft(ttft,
                                         trace_id=trace.trace_id)
            else:
                self.admission.note_ttft(
                    time.monotonic() - state.created)
        if state.done:
            if trace is not None:
                if state.failed is not None:
                    trace.flag("failed")
                    trace.finish(
                        f"failed:{state.failed.get('kind', 'unknown')}")
                else:
                    trace.finish("ok")
            # Stream finished (retired or failed): its per-request
            # gauge series dies NOW — a long-lived gateway must not
            # keep one series per stream ever served (RT-GAUGE-LEAK).
            telemetry.REGISTRY.remove_gauge(
                "roundtable_gateway_inflight_streams",
                **self._stream_labels(state))
            self._evict_done_streams()

    def _release_consumer(self, state: StreamState, consumer) -> None:
        """Detach a pump's consumer; when that was the LAST one on a
        live stream, start the abandonment clock — a reconnect within
        `abandon_s` cancels it, otherwise the round is abandoned and
        the scheduler releases everything it held (ISSUE 19)."""
        state.detach(consumer)
        if state.done or state.attached() or self._loop is None:
            return
        self._loop.call_later(self.abandon_s, self._reap_orphan, state)

    def _reap_orphan(self, state: StreamState) -> None:
        if state.done or state.attached():
            return  # finished or reconnected — not abandoned
        req = getattr(state, "request", None)
        if req is None:
            return
        req.abandoned = True
        telemetry.inc("roundtable_gateway_abandoned_streams_total")

    def _evict_done_streams(self) -> None:
        done = [sid for sid, st in self.streams.items() if st.done]
        while len(done) > _DONE_STREAM_CAP:
            self.streams.pop(done.pop(0), None)
        self._compact_intents()

    def _compact_intents(self) -> None:
        """Bound the intent journal + cache. A record whose turn is
        committed in the session journal is only ever needed again for
        a leg-2 reconnect, so only the newest `intent_cap // 2` of
        those are kept; uncommitted intents (a crash would need them
        for leg-3 regeneration) always survive."""
        if (self.intents is None or self.sched.journal is None
                or len(self._intent_cache) <= self.intent_cap):
            return
        committed = [
            sid for sid, rec in self._intent_cache.items()
            if committed_rows(self.sched.journal, rec["session"],
                              rec["turn"]) is not None]
        keep_committed = max(self.intent_cap // 2, 1)
        drop = set(committed[:-keep_committed])
        if not drop:
            return
        keep = {sid: rec for sid, rec in self._intent_cache.items()
                if sid not in drop}
        # Cache evicts only if the on-disk journal rewrote: the two
        # must never disagree about which streams can reconnect.
        if self.intents.compact(keep):
            self._intent_cache = keep

    # ------------------------------------------------------------------
    # POST /v1/chat/completions (OpenAI-compatible)
    # ------------------------------------------------------------------

    async def _chat_completions(self, req: Request,
                                writer: asyncio.StreamWriter) -> None:
        try:
            body = req.json()
        except (ValueError, json.JSONDecodeError) as e:
            raise HttpError(400, f"bad JSON body: {e}", "bad_json")
        messages = body.get("messages")
        if not isinstance(messages, list) or not messages:
            raise HttpError(400, "messages[] is required",
                            "bad_request")
        prompt = "\n".join(
            f"{m.get('role', 'user')}: {m.get('content', '')}"
            for m in messages) + "\nassistant:"
        knight = str(body.get("model") or "assistant")
        session = str(body.get("session")
                      or f"chat-{uuid.uuid4().hex[:8]}")
        max_new = int(body.get("max_tokens") or 128)
        temperature = float(body.get("temperature") or 0.0)
        deadline_s = self._client_deadline(req, body)
        priority = str(req.header("x-roundtable-priority")
                       or body.get("priority") or "normal")
        state = self._submit_stream(
            session=session, turns=[(knight, prompt)], max_new=max_new,
            deadline_s=deadline_s, priority=priority, adapters=None,
            kind="chat", temperature=temperature,
            traceparent=req.header("traceparent"))
        consumer = state.attach()
        if body.get("stream"):
            await self._pump_chat(writer, state, consumer)
        else:
            trace_id = state.trace.trace_id \
                if state.trace is not None else ""
            try:
                failed = await self._await_done(consumer, deadline_s)
            finally:
                self._release_consumer(state, consumer)
            if failed is not None:
                err = HttpError(500, failed.get("error", "failed"),
                                failed.get("kind", "unknown"))
                err.trace_id = trace_id
                raise err
            text = self._decode(state.history[0])
            headers = {"Traceparent": tracing.format_traceparent(
                trace_id)} if trace_id else None
            await send_json(writer, 200, {
                "id": f"chatcmpl-{state.stream_id}",
                "object": "chat.completion",
                "created": int(time.time()),
                "model": knight,
                "choices": [{"index": 0,
                             "message": {"role": "assistant",
                                         "content": text},
                             "finish_reason": "stop"}],
                "usage": {"completion_tokens": len(state.history[0])},
            }, headers)

    async def _await_done(self, consumer,
                          deadline_s: Optional[float]) -> Optional[dict]:
        """Drain a consumer without a socket (non-streaming response).
        Returns the failure payload, or None on clean retirement."""
        bound = time.monotonic() + (deadline_s or 600.0) + 60.0
        while not consumer.finished():
            if time.monotonic() > bound:
                tr = consumer.state.trace
                err = HttpError(500, "stream never finished",
                                "gateway_wedged")
                if tr is not None:
                    tr.flag("hung")
                    tr.finish("hung")
                    err.trace_id = tr.trace_id
                raise err
            for ev in await consumer.next_events(self.keepalive_s):
                if ev["type"] == "failed":
                    return {"error": ev.get("error", ""),
                            "kind": ev.get("kind", "unknown")}
        return consumer.state.failed

    # ------------------------------------------------------------------
    # POST /v1/discussions (native multi-knight)
    # ------------------------------------------------------------------

    async def _discussions(self, req: Request,
                           writer: asyncio.StreamWriter) -> None:
        try:
            body = req.json()
        except (ValueError, json.JSONDecodeError) as e:
            raise HttpError(400, f"bad JSON body: {e}", "bad_json")
        raw_turns = body.get("turns")
        if not isinstance(raw_turns, list) or not raw_turns:
            raise HttpError(400, "turns[] is required", "bad_request")
        turns: list[tuple[str, Any]] = []
        for t in raw_turns:
            if not isinstance(t, dict) or "knight" not in t \
                    or "prompt" not in t:
                raise HttpError(400, "each turn needs knight + prompt",
                                "bad_request")
            turns.append((str(t["knight"]), t["prompt"]))
        session = str(body.get("session")
                      or f"disc-{uuid.uuid4().hex[:8]}")
        max_new = int(body.get("max_new_tokens") or 64)
        adapters = body.get("adapters")
        deadline_s = self._client_deadline(req, body)
        priority = str(req.header("x-roundtable-priority")
                       or body.get("priority") or "normal")
        state = self._submit_stream(
            session=session, turns=turns, max_new=max_new,
            deadline_s=deadline_s, priority=priority,
            adapters=adapters, kind="native",
            temperature=float(body.get("temperature") or 0.0),
            traceparent=req.header("traceparent"))
        consumer = state.attach()
        await self._pump_native(writer, state, consumer)

    # ------------------------------------------------------------------
    # GET /v1/streams/<id> (reconnect)
    # ------------------------------------------------------------------

    async def _reconnect(self, req: Request,
                         writer: asyncio.StreamWriter,
                         stream_id: str) -> None:
        state = self.streams.get(stream_id)
        crossed = False
        if (state is not None and state.failed is not None
                and self.router is not None
                and state.failed.get("kind") in _FAILOVER_KINDS):
            # The stream died WITH its replica, not with its request:
            # drop the corpse and restore on a survivor — the router's
            # failover already re-established the session's KV there,
            # so leg 2/3 of the ladder resumes byte-identically and the
            # client's Last-Event-ID skips what it already saw.
            self.streams.pop(stream_id, None)
            state = None
            crossed = True
        if state is None:
            state = self._restore_stream(stream_id, crossed=crossed)
        elif state.trace is not None:
            # Live-stream rejoin (ladder leg 1): same trace, counted,
            # marked with a follow-on `resume` span so the waterfall
            # shows the reconnect without starting a new leg clock.
            state.trace.reconnects += 1
            with telemetry.span("resume", parent=state.trace.context(),
                                stream=stream_id,
                                session=state.session, live=True):
                pass
        watermark = [0] * len(state.knights)
        leid = req.header("last-event-id")
        if leid:
            parsed = parse_event_id(leid, len(state.knights))
            if parsed is not None and parsed[0] == state.turn:
                watermark = parsed[1]
        consumer = state.attach(watermark)
        self.resumed_streams += 1
        telemetry.inc("roundtable_gateway_resumed_streams_total")
        await self._pump_native(writer, state, consumer)

    def _restore_stream(self, stream_id: str,
                        crossed: bool = False) -> StreamState:
        """Post-restart reconnect: rebuild the stream from the intent
        journal — from the committed turn when the round finished
        before the crash, by greedy re-generation otherwise. The
        restore leg REJOINS the original trace (the intent record
        carries its id), so one client request stays one stitched
        trace across kill -9 and failover; `crossed` marks a leg that
        moved replicas (always tail-retained)."""
        intent = self._intent_cache.get(stream_id)
        if intent is None:
            raise HttpError(404, f"unknown stream {stream_id!r}",
                            "unknown_stream")
        session = intent["session"]
        knights = intent["knights"]
        trace = tracing.RequestTrace(
            intent.get("trace") or None, kind="resume",
            stream=stream_id, session=session,
            endpoint=str(intent.get("kind", "native")))
        if crossed:
            trace.flag("replica_crossed")
        state = StreamState(stream_id, session, knights,
                            intent["turn"], buffer_cap=self.sse_buffer)
        state.trace = trace
        rows = committed_rows(self.sched.journal, session,
                              intent["turn"])
        if rows is not None:
            # Leg 2: the round committed before the crash — serve
            # straight from the durable record, no recompute. The leg
            # is pure replay: its whole (tiny) wall is resume_replay.
            for i, row in enumerate(rows[:len(knights)]):
                state.history[i] = [int(t) for t in
                                    row.get("produced", [])]
            state.done = True
            self.streams[stream_id] = state
            trace.finish("ok", tail_stage="resume_replay")
        else:
            # Leg 3: crash mid-round — greedy re-generation over the
            # replayed KV produces the identical token stream; the
            # client's watermark skips what it already saw. A sampled
            # stream (temperature > 0) cannot regenerate identically,
            # so refuse rather than splice a different stream onto the
            # client's watermark (silent corruption).
            temperature = float(intent.get("temperature") or 0.0)
            if temperature > 0.0:
                err = HttpError(
                    409, f"stream {stream_id!r} was sampled "
                    "(temperature > 0) and its turn never committed — "
                    "post-crash regeneration cannot be byte-identical; "
                    "start a new request", "nondeterministic_stream")
                trace.flag("failed")
                trace.finish("nondeterministic_stream",
                             tail_stage="resume_replay")
                err.trace_id = trace.trace_id
                raise err
            turns = list(zip(knights, intent["prompts"]))
            # Restore bookkeeping up to here is the resume_replay
            # stage; the re-submit itself is placement, and the regen
            # prefill/decode land in the usual stages via the event
            # bridge — the resume leg gets a full waterfall.
            trace.stage("resume_replay")
            try:
                self._submit_state(state, turns,
                                   max_new=int(intent["max_new"]),
                                   deadline_s=intent.get("deadline_s"),
                                   adapters=intent.get("adapters"))
            except _Shed as s:
                trace.flag("shed")
                trace.finish(f"shed:{s.decision.reason}",
                             tail_stage="resume_replay")
                s.trace_id = trace.trace_id
                raise
            except HttpError as e:
                trace.flag("failed")
                trace.finish(f"error:{e.reason}",
                             tail_stage="resume_replay")
                e.trace_id = trace.trace_id
                raise
            trace.stage("placement")
            trace.replica = getattr(state, "replica", None)
        return state

    # ------------------------------------------------------------------
    # SSE pumps
    # ------------------------------------------------------------------

    def _decode(self, ids: list[int]) -> str:
        try:
            return self.sched.engine.tokenizer.decode(ids)
        except Exception:  # noqa: BLE001 — stream ids even if decode trips
            return ""

    async def _pump_native(self, writer: asyncio.StreamWriter,
                           state: StreamState, consumer) -> None:
        tid = state.trace.trace_id if state.trace is not None else ""
        sse = SseWriter(writer)
        await sse.open({"Traceparent": tracing.format_traceparent(tid)}
                       if tid else None)
        # Metadata first: the stream id IS the reconnect handle
        # (GET /v1/streams/<id>) — a client that only ever saw this
        # event can still resume from zero after a crash. The trace id
        # rides it (and every payload below) so any single event a
        # client holds names the trace to quote in a report.
        meta = {"type": "stream", "stream": state.stream_id,
                "session": state.session, "turn": state.turn,
                "knights": state.knights}
        if tid:
            meta["trace"] = tid
        await sse.event(
            meta,
            event_id=format_event_id(state.turn, list(consumer.sent)))
        try:
            while True:
                events = await consumer.next_events(self.keepalive_s)
                if not events:
                    if consumer.finished():
                        break
                    await sse.comment()
                    continue
                terminal = False
                for ev in events:
                    payload, ntok = self._native_payload(state, ev)
                    if tid:
                        payload["trace"] = tid
                    await sse.event(payload, event_id=ev["id"],
                                    tokens=ntok)
                    terminal = terminal or ev["type"] in ("retired",
                                                          "failed")
                if terminal:
                    break
        finally:
            self._release_consumer(state, consumer)

    def _native_payload(self, state: StreamState,
                        ev: dict) -> tuple[dict, int]:
        if ev["type"] == "tokens":
            toks = ev["tokens"]
            return ({"type": "tokens", "row": ev["row"],
                     "knight": ev["knight"], "tokens": toks,
                     "text": self._decode(toks)}, len(toks))
        if ev["type"] == "summary":
            rows = {str(i): {"tokens": d, "text": self._decode(d),
                             "knight": state.knights[i]}
                    for i, d in ev["rows"].items()}
            n = sum(len(d) for d in ev["rows"].values())
            return ({"type": "summary", "rows": rows,
                     "coalesced": True}, n)
        if ev["type"] == "failed":
            return ({"type": "failed", "error": ev.get("error", ""),
                     "kind": ev.get("kind", "unknown")}, 0)
        return ({"type": "retired", "session": state.session,
                 "turn": state.turn}, 0)

    async def _pump_chat(self, writer: asyncio.StreamWriter,
                         state: StreamState, consumer) -> None:
        tid = state.trace.trace_id if state.trace is not None else ""
        sse = SseWriter(writer)
        await sse.open({"Traceparent": tracing.format_traceparent(tid)}
                       if tid else None)
        cid = f"chatcmpl-{state.stream_id}"
        model = state.knights[0]

        def chunk(delta: dict, finish: Optional[str] = None) -> dict:
            out = {"id": cid, "object": "chat.completion.chunk",
                   "created": int(time.time()), "model": model,
                   "choices": [{"index": 0, "delta": delta,
                                "finish_reason": finish}]}
            if tid:
                out["trace"] = tid
            return out

        try:
            while True:
                events = await consumer.next_events(self.keepalive_s)
                if not events:
                    if consumer.finished():
                        break
                    await sse.comment()
                    continue
                terminal = False
                for ev in events:
                    if ev["type"] in ("tokens", "summary"):
                        toks = ev.get("tokens") or [
                            t for d in ev.get("rows", {}).values()
                            for t in d]
                        await sse.event(
                            chunk({"content": self._decode(toks)}),
                            event_id=ev["id"], tokens=len(toks))
                    elif ev["type"] == "failed":
                        await sse.event(chunk({}, finish="error"),
                                        event_id=ev["id"])
                        terminal = True
                    else:  # retired
                        await sse.event(chunk({}, finish="stop"),
                                        event_id=ev["id"])
                        await sse.event("[DONE]")
                        terminal = True
                if terminal:
                    break
        finally:
            self._release_consumer(state, consumer)
