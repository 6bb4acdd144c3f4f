"""`tpu-llm` adapter — knights served by the in-tree JAX/XLA engine.

This is the component that replaces the reference's local-llm → Ollama/
LM Studio → CUDA llama.cpp stack (reference src/adapters/local-llm.ts;
SURVEY.md §2.3). The adapter is a thin host-side shim: tokenize → dispatch to
the engine's sharded prefill+decode → detokenize. Engine construction is lazy
and cached per checkpoint so several knights (or several adapters) share one
resident model.

Fault tolerance (ISSUE 1, ARCHITECTURE.md "Fault tolerance"): this is the
adapter rung of the degradation ladder. A failed BATCHED round invalidates
the batch's KV slots and retries the knights serially (smaller programs,
per-knight isolation) before giving up; every final failure feeds the
engine's shared circuit breaker (engine.get_breaker — keyed like the engine
cache, so adapters sharing a resident engine share its health), and once the
breaker opens `is_available()` reports False with the breaker's reason so
the orchestrator's runtime-fallback path seats the knight elsewhere instead
of feeding more turns into a sick engine.
"""

from __future__ import annotations

import warnings
from typing import Any, Optional

from ..core.errors import AdapterError, classify_error
from ..engine import deadlines
from .base import BaseAdapter, DEFAULT_TIMEOUT_MS, KnightTurn

# Reserves mirror the local-llm budget contract (reference local-llm.ts:58-70),
# but get_max_source_chars answers from REAL tokenizer counts downstream.
RESPONSE_RESERVE_TOKENS = 4096
OVERHEAD_RESERVE_TOKENS = 3000
MIN_AVAILABLE_TOKENS = 2000

# Fraction of a multi-knight round's budget the BATCHED attempt may
# consume (ISSUE 2: the round budget SPLITS across batched/serial
# attempts instead of one shared ad-hoc deadline): a hung/wedged batch
# must leave the serial-retry rung real time to serve the knights.
# Config key "batch_budget_fraction" overrides. Single-turn rounds have
# no serial rung and get the whole budget.
BATCH_BUDGET_FRACTION = 0.6


class TpuLlmAdapter(BaseAdapter):
    """BaseAdapter over an EngineHandle (theroundtaible_tpu.engine)."""

    def __init__(self, name: str, engine_config: dict[str, Any],
                 timeout_ms: int = DEFAULT_TIMEOUT_MS,
                 session: Optional[str] = None):
        super().__init__(name)
        self.engine_config = dict(engine_config)
        self.default_timeout = timeout_ms
        # Session identity (ISSUE 4): namespaces this adapter's KV slot
        # names (kvcache.scoped_slot) so concurrent discussions sharing
        # one resident engine never collide — and routes rounds through
        # the attached continuous-batching scheduler when one is set.
        self.session = session
        # Persona adapter id (ISSUE 10): the LoRA adapter this knight
        # speaks through on a shared-base engine — `lora_adapter` is
        # the adapter-level default, `knight_adapters: {name: id}`
        # overrides per seat (the knight_sampling pattern). None (or a
        # lora-off engine) serves the base model.
        self.persona_adapter = engine_config.get("lora_adapter")
        self._scheduler = None
        self._engine = None
        self._engine_error: Optional[str] = None
        self._last_stats: Optional[dict] = None
        # Which degradation rung served the last round, if any
        # ("serial_retry"); chaos tests and metrics read it.
        self.last_degradation: Optional[str] = None
        # Classified kind of the failure the last round RECOVERED from
        # ("hang", "oom", ...); None when the round served clean. The
        # hang acceptance check and status surfaces read it.
        self.last_recovered_kind: Optional[str] = None

    @classmethod
    def from_config(cls, adapter_id: str, cfg: dict[str, Any],
                    timeout_ms: int = DEFAULT_TIMEOUT_MS) -> "TpuLlmAdapter":
        return cls(name=cfg.get("name", adapter_id), engine_config=cfg,
                   timeout_ms=timeout_ms)

    # --- engine lifecycle + health ---

    def breaker(self):
        """The engine-cache-shared CircuitBreaker for this config."""
        from ..engine import get_breaker
        return get_breaker(self.engine_config)

    def _get_engine(self, retry_construction: bool = False):
        if (retry_construction and self._engine is None
                and self._engine_error is not None):
            # The caller was admitted by the breaker (closed, or its
            # half-open probe), so a memoized construction failure gets a
            # fresh attempt: a checkpoint fixed after startup (or freed
            # HBM) closes the breaker in-process on the SAME admitted
            # call instead of staying memoized-dead. Passive callers
            # (is_available, get_max_source_chars) keep the memo.
            self._engine_error = None
        if self._engine is None and self._engine_error is None:
            try:
                from ..engine import get_engine
                self._engine = get_engine(self.engine_config)
            except Exception as e:  # noqa: BLE001 — surfaced via is_available
                self._engine_error = str(e)
                # A construction failure is permanent, not transient (and
                # memoized — it would only ever count once), so it OPENS
                # the breaker outright: fleet_health must report a dead
                # engine as open, not eternally 'degraded'.
                self.breaker().trip(e)
        if self._engine is None:
            raise AdapterError(
                f"TPU engine unavailable: {self._engine_error}",
                kind=classify_error(RuntimeError(self._engine_error or "")))
        return self._engine

    def attach_scheduler(self, scheduler,
                         session: Optional[str] = None) -> None:
        """Route this adapter's rounds through a shared continuous-
        batching SessionScheduler (engine/scheduler.py). Every rung of
        the degradation ladder — the batched attempt AND the per-knight
        serial retries — then goes through the scheduler's queue, so a
        degraded session keeps co-scheduling with healthy ones instead
        of seizing the engine serially.

        A scheduled adapter ALWAYS has a session id: with none given
        (and none set), a unique one is generated — the adapter NAME is
        not unique (the factory names every instance by adapter id), and
        two adapters falling back to one shared name would share an
        isolation domain, re-creating exactly the cross-session slot
        collision the namespace exists to prevent."""
        self._scheduler = scheduler
        if session is not None:
            self.session = session
        elif not self.session:
            import uuid
            self.session = f"{self.name}-{uuid.uuid4().hex[:8]}"

    def _effective_session(self) -> Optional[str]:
        """The session namespace the engine-side slots actually live
        under. _serve and _slot_name MUST agree, or serial-retry slot
        invalidation would release a name the scheduler never allocated;
        attach_scheduler guarantees a session id whenever a scheduler
        is attached."""
        return self.session

    def _serve(self, engine, turn_pairs, **kwargs):
        """The one engine-call seam: scheduled sessions submit to the
        shared batch; unscheduled calls hit the engine directly with the
        session namespace applied."""
        if self._scheduler is not None:
            return self._scheduler.submit(
                self._effective_session(), turn_pairs, **kwargs)
        return engine.generate_batch_with_stats(
            turn_pairs, session=self.session, **kwargs)

    def _slot_name(self, knight_name: str) -> str:
        """The engine-side slot name for a knight of THIS session."""
        from ..engine.kvcache import scoped_slot
        return scoped_slot(self._effective_session(), knight_name)

    def known_unhealthy(self) -> bool:
        # No construction here (contract): just the breaker verdict and
        # the memoized construction failure.
        return self.breaker().is_open or self._engine_error is not None

    def is_available(self) -> bool:
        if self.breaker().is_open:
            return False
        try:
            self._get_engine()
            return True
        except AdapterError:
            return False

    def unavailable_reason(self) -> Optional[str]:
        """Why is_available() is False (None when it isn't): the open
        breaker's reason, or the engine construction error."""
        reason = self.breaker().reason
        return reason if reason else self._engine_error

    # --- serving ---

    def get_max_source_chars(self) -> Optional[int]:
        """Budget from the engine's real max_seq_len and tokenizer
        chars-per-token ratio (replaces the 4-chars/token estimate)."""
        try:
            engine = self._get_engine()
        except AdapterError:
            return None
        ctx = engine.max_seq_len
        available = max(ctx - RESPONSE_RESERVE_TOKENS - OVERHEAD_RESERVE_TOKENS,
                        MIN_AVAILABLE_TOKENS)
        return int(available * engine.chars_per_token())

    def execute(self, prompt: str, timeout_ms: int = DEFAULT_TIMEOUT_MS) -> str:
        return self.execute_for(self.name, prompt, timeout_ms)

    def execute_for(self, knight_name: str, prompt: str,
                    timeout_ms: int = DEFAULT_TIMEOUT_MS,
                    budget=None) -> str:
        # Keyed by the KNIGHT, not the adapter: a knight degraded off the
        # batched path onto serial turns keeps its own KV slot and
        # per-knight sampling instead of colliding on the adapter's name.
        return self.execute_round(
            [KnightTurn(knight_name=knight_name, prompt=prompt)],
            timeout_ms, budget=budget)[0]

    accepts_budget = True

    def supports_batched_rounds(self) -> bool:
        return True

    def _adapter_for(self, knight_name: str) -> Optional[str]:
        """The LoRA persona adapter id for a seat: per-knight
        `knight_adapters` map first, then the adapter-level
        `lora_adapter` default."""
        overrides = self.engine_config.get("knight_adapters", {})
        return overrides.get(knight_name, self.persona_adapter)

    def _adapters_for(self, turns) -> Optional[list]:
        """Per-turn adapter ids for one round, or None when every
        seat serves the base model (the common non-persona fleet keeps
        its exact pre-LoRA call signature)."""
        ads = [self._adapter_for(t.knight_name) for t in turns]
        return ads if any(a is not None for a in ads) else None

    def _sampling_for(self, knight_name: str):
        """Per-knight SamplingParams: `knight_sampling: {name: {...}}` in
        the adapter config overrides the engine default per seat —
        heterogeneous personas (a hotter skeptic, a greedy pragmatist)
        sample correctly inside the same batched program."""
        overrides = self.engine_config.get("knight_sampling", {})
        cfg = overrides.get(knight_name)
        if not cfg:
            return None
        from ..engine.sampling import SamplingParams
        base = self._get_engine().sampling
        return SamplingParams(
            temperature=float(cfg.get("temperature", base.temperature)),
            top_k=int(cfg.get("top_k", base.top_k)),
            top_p=float(cfg.get("top_p", base.top_p)),
            # per-row decode budgets: a terse knight stops at its own
            # cap while the batch keeps decoding (engine decode_while)
            max_new_tokens=int(cfg.get("max_new_tokens",
                                       base.max_new_tokens)))

    def execute_round(self, turns: list[KnightTurn],
                      timeout_ms: int = DEFAULT_TIMEOUT_MS,
                      budget=None) -> list[str]:
        """One batched forward pass over N persistent per-knight KV slots.

        Failure handling: a failed batched dispatch degrades to serial
        per-knight retry (_serial_retry); the final outcome — success or
        AdapterError — is recorded on the engine's circuit breaker.

        Time ladder (ISSUE 2): `budget` is the round-rung Budget the
        orchestrator threads down (None builds a local root from
        timeout_ms). The round budget is SPLIT across the degradation
        attempts — the batched dispatch gets BATCH_BUDGET_FRACTION of it
        when a serial rung exists to fall back to, and each serial
        retry gets a fair share of whatever remains — so a hung batch
        can never consume the time its recovery path needs, and
        execute_round's timeout contract never multiplies into (N+1)x
        under degradation."""
        breaker = self.breaker()
        # Clear BEFORE the fail-fast below: a failed call — including one
        # that never dispatched — must not leave stale stats.
        self._last_stats = None
        self.last_degradation = None
        self.last_recovered_kind = None
        if not breaker.should_attempt():
            # Fail fast with the health verdict instead of dispatching
            # into a sick engine (should_attempt still admits periodic
            # half-open probes, so a recovered engine closes the breaker
            # again); the orchestrator's fallback path reads this as any
            # other adapter failure. The kind comes from the breaker's
            # underlying error so the operator sees the oom/timeout hint
            # that actually applies, not a generic backend-error one.
            reason = breaker.reason or ""
            raise AdapterError(f"TPU engine unavailable: {reason}",
                               kind=classify_error(RuntimeError(reason)))
        # AFTER the breaker gate: this call was admitted (closed breaker
        # or half-open probe), so a memoized construction failure gets
        # one fresh attempt — and on success the same call dispatches
        # and closes the breaker, re-seating the knights in one probe.
        engine = self._get_engine(retry_construction=True)
        per_turn = None
        if self.engine_config.get("knight_sampling"):
            per_turn = [self._sampling_for(t.knight_name)
                        or engine.sampling for t in turns]
        # ONE round budget bounds the batched attempt and every serial
        # retry (its deadline is the old shared float); the splits
        # happen inside _dispatch_round/_serial_retry.
        timeout_s = (timeout_ms or self.default_timeout) / 1000
        round_budget = (budget.child("round", timeout_s=timeout_s)
                        if budget is not None
                        else deadlines.Budget.root(timeout_s, rung="round"))
        try:
            responses, stats = self._dispatch_round(engine, turns, per_turn,
                                                    round_budget)
        except Exception as e:  # noqa: BLE001
            breaker.record_failure(e)
            # A failure after donation consumed the KV buffers must not
            # brick the engine: single-turn rounds re-raise before
            # _serial_retry's revive, so without this the breaker's
            # half-open probes would die on 'Array has been deleted'
            # for the process lifetime.
            self._revive_best_effort(engine)
            if isinstance(e, AdapterError):
                raise
            raise AdapterError(str(e), kind=classify_error(e), cause=e)
        breaker.record_success()
        # per-call snapshot, NOT engine.last_stats — adapters sharing one
        # cached engine would otherwise read each other's numbers
        self._last_stats = {
            "model": engine.cfg.name,
            "prefill_tokens": stats.prefill_tokens,
            "reused_tokens": stats.reused_tokens,
            # Of which the CROSS-SESSION prefix cache served (ISSUE 7) —
            # 0 on cache-off engines.
            "prefix_reused_tokens": stats.prefix_reused_tokens,
            "decode_tokens": stats.decode_tokens,
            "prefill_seconds": round(stats.prefill_seconds, 3),
            "decode_seconds": round(stats.decode_seconds, 3),
            "prefill_tps": round(stats.prefill_tps, 1),
            "decode_tps": round(stats.decode_tps, 1),
        }
        if stats.int4_paths is not None:
            # Path provenance (ISSUE 3): which einsum dispatches ran the
            # fused w4a16 kernels vs the XLA dequant fallback — rides the
            # per-turn engine stats into metrics.json so a window's int4
            # numbers are attributable.
            self._last_stats["int4_paths"] = stats.int4_paths
        if stats.sched is not None:
            # Scheduler provenance (ISSUE 4): queue wait + decode-batch
            # occupancy ride the per-turn stats into metrics.json, same
            # pattern as int4_paths.
            self._last_stats["sched"] = stats.sched
        if self.last_degradation:
            self._last_stats["degraded"] = self.last_degradation
        if self.last_recovered_kind:
            self._last_stats["recovered_from"] = self.last_recovered_kind
        return responses

    def _dispatch_round(self, engine, turns, per_turn, round_budget):
        # Budget split, batched rung: a multi-knight batch gets a
        # FRACTION of the round (the serial rung must still have room
        # behind it); a single-turn round has no fallback and gets all.
        if len(turns) > 1:
            frac = float(self.engine_config.get(
                "batch_budget_fraction", BATCH_BUDGET_FRACTION))
            batch_budget = round_budget.child(
                "turn", timeout_s=round_budget.remaining() * frac)
        else:
            batch_budget = round_budget.child("turn")
        kwargs: dict[str, Any] = {
            "timeout_s": max(batch_budget.remaining(), 0.0),
            "budget": batch_budget}
        ads = self._adapters_for(turns)
        if ads is not None:
            # Persona adapters ride the round into the engine /
            # scheduler (ISSUE 10); co-batched knights with DIFFERENT
            # personas decode in one mixed-adapter segment. An engine
            # without a lora store — kill-switched or config-less —
            # drops the kwarg itself and serves the base model (the
            # ROUNDTABLE_LORA=0 byte-identity contract).
            kwargs["adapters_per_turn"] = ads
        if per_turn is not None:
            kwargs["sampling_per_turn"] = per_turn
            # call-level cap = the LARGEST per-knight budget, so a
            # knight configured above the engine default isn't
            # silently clamped (row budgets bound each row below it)
            kwargs["max_new_tokens"] = max(
                p.max_new_tokens for p in per_turn)
        try:
            return self._serve(
                engine, [(t.knight_name, t.prompt) for t in turns],
                **kwargs)
        except Exception as batch_err:  # noqa: BLE001
            if len(turns) < 2:
                raise
            return self._serial_retry(engine, turns, per_turn,
                                      round_budget, batch_err)

    def _serial_retry(self, engine, turns, per_turn, round_budget,
                      batch_err):
        """Batched-round degradation rung: the fan-out failed, so the
        round becomes best-effort — invalidate the batch's KV slots (a
        mid-flight failure may have left partial scatter writes) and
        serve each knight as its own single-row program. Smaller
        programs, per-knight isolation: one knight's pathology no longer
        dooms the whole round. Every serial attempt runs inside the
        ROUND's remaining budget — a timed-out batch does not buy N
        fresh timeouts — and each knight gets a FAIR SHARE of what is
        left (remaining / knights-still-waiting, so early finishers
        donate their surplus to later knights but a single wedged
        knight can never starve the rest)."""
        if round_budget.remaining() <= 0:
            # No time left to retry anything: surface the timeout BEFORE
            # the destructive slot invalidation below, so the knights'
            # cached conversation KV survives for the next round instead
            # of being wiped for zero benefit.
            raise AdapterError(
                f"batched round failed ({batch_err}) and the round's "
                "deadline passed before serial retry could start",
                kind="timeout")
        warnings.warn(
            f"batched round failed ({batch_err}); invalidating the "
            f"batch's KV slots and retrying {len(turns)} knight(s) "
            "serially", stacklevel=3)
        # Ladder escalation ships its own postmortem (ISSUE 5): the
        # flight ring at this moment holds the failed batch's spans and
        # whatever the hang/fault machinery recorded before it.
        from ..utils import telemetry
        telemetry.inc("roundtable_degradations_total",
                      rung="serial_retry")
        telemetry.recorder().record(
            "ladder_escalation", rung="serial_retry",
            adapter=self.name, error=str(batch_err)[:200])
        telemetry.flight_dump(
            "ladder_escalation",
            extra={"rung": "serial_retry", "adapter": self.name,
                   "error": str(batch_err)[:500]})
        # A failure that surfaced AFTER donation consumed the KV cache
        # (jit programs donate the cache buffers) left the engine holding
        # deleted arrays — reallocate fresh buffers first, else every
        # serial retry dies on the secondary 'Array has been deleted'
        # error instead of re-prefilling.
        if self._revive_best_effort(engine):
            warnings.warn(
                "KV buffers were consumed by the failed dispatch; "
                "reallocated fresh pools (all cached slots lost)",
                stacklevel=3)
        if self._scheduler is None:
            # Release the SESSION-SCOPED slots (the names the engine
            # actually allocated). Scheduled sessions skip this: the
            # scheduler's _fail_request already released the failed
            # round's slots ON ITS OWN THREAD — releasing here would
            # mutate shared PagedKVCache host state from the
            # session thread while the scheduler thread iterates it
            # (dict-changed-during-iteration crashes the loop and fails
            # every other session).
            for t in turns:
                engine.kv.release(self._slot_name(t.knight_name))
        from ..engine.engine import GenStats
        total = GenStats()
        responses = []
        failures: list[tuple[str, Exception]] = []
        for i, t in enumerate(turns):
            remaining = round_budget.remaining()
            if remaining <= 0:
                raise AdapterError(
                    f"batched round failed ({batch_err}) and the round's "
                    f"deadline passed during serial retry at knight "
                    f"{t.knight_name}", kind="timeout")
            # Fair share of the remaining round budget: knights still
            # waiting split it evenly, recomputed per knight so early
            # finishers' surplus flows to later ones.
            knight_budget = round_budget.child(
                "turn", timeout_s=remaining / (len(turns) - i))
            kwargs: dict[str, Any] = {
                "timeout_s": max(knight_budget.remaining(), 0.0),
                "budget": knight_budget}
            ad = self._adapter_for(t.knight_name)
            if ad is not None:
                kwargs["adapters_per_turn"] = [ad]
            if per_turn is not None:
                kwargs["sampling_per_turn"] = [per_turn[i]]
                kwargs["max_new_tokens"] = per_turn[i].max_new_tokens
            try:
                # Through the scheduler when attached: the degraded
                # session's serial turns co-schedule with OTHER sessions'
                # healthy rows instead of seizing the engine.
                out, stats = self._serve(
                    engine, [(t.knight_name, t.prompt)], **kwargs)
            except Exception as serial_err:  # noqa: BLE001
                # Best-effort really means it: one knight's pathology
                # must not abandon the rest of the round. Keep serving
                # the remaining knights (revive first, in case THIS
                # failure consumed the buffers); the succeeded knights'
                # committed KV makes the orchestrator's per-knight
                # re-run cheap via prefix reuse.
                failures.append((t.knight_name, serial_err))
                self._revive_best_effort(engine)
                continue
            responses.append(out[0])
            total.int4_paths = stats.int4_paths
            total.sched = stats.sched
            total.prefill_tokens += stats.prefill_tokens
            total.reused_tokens += stats.reused_tokens
            total.prefix_reused_tokens += stats.prefix_reused_tokens
            total.decode_tokens += stats.decode_tokens
            total.prefill_seconds += stats.prefill_seconds
            total.decode_seconds += stats.decode_seconds
        if failures:
            names = ", ".join(n for n, _ in failures)
            first = failures[0][1]
            raise AdapterError(
                f"batched round failed ({batch_err}) and serial retry "
                f"failed for knight(s) {names}: {first}",
                kind=classify_error(first), cause=first)
        self.last_degradation = "serial_retry"
        # What the round recovered FROM — a watchdog-detected hang is
        # recorded distinctly from a crash (ISSUE 2 acceptance).
        self.last_recovered_kind = classify_error(batch_err)
        return responses, total

    def _revive_best_effort(self, engine) -> bool:
        """revive_kv_if_dead that never raises: a broken revive must not
        mask the dispatch error the operator actually needs to see.
        Scheduled sessions never revive from here — the scheduler's
        _after_engine_failure owns donation-death recovery on its own
        thread (a session-thread revive would swap the pools out from
        under a concurrently-dispatching scheduler)."""
        if self._scheduler is not None:
            return False
        try:
            return getattr(engine, "revive_kv_if_dead", lambda: False)()
        except Exception:  # noqa: BLE001 — the dispatch error wins
            return False

    def last_stats(self) -> Optional[dict]:
        return self._last_stats
