"""Benchmark — BASELINE.md measured config 2: 3-knight × 5-round discuss.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"}.

This measures the NORTH-STAR metric (BASELINE.md: "3-knight × 5-round
`discuss` wall-clock ... at wall-clock parity with Ollama on a single
A100") end to end through the REAL orchestrator: context build, prompt
assembly, one batched device program per round over 3 persistent KV slots,
consensus parsing, session/chronicle writes. Only the consensus SCORES are
scripted (random-weight models can't emit the JSON block; the reference's
compute path is identical either way) — scores run 6,6,6,6 then 9.5 so the
discussion terminates exactly at round 5.

vs_baseline anchors to Ollama gemma-2b on A100 ≈ 120 tok/s decode: a
3-knight × 5-round discussion with ~160-token turns ≈ 15 × 160 / 120 ≈ 20 s
of pure decode, plus prefill ≈ a few seconds — call it 25 s of model time.
The reference itself publishes no numbers (BASELINE.md "published: {}").

Usage: python bench_discuss.py            (real chip; gemma-2b × 3 knights)
       ROUNDTABLE_BENCH_CPU=1 ...         (tiny model smoke test)
       ROUNDTABLE_BENCH_OFFERED_LOAD=1 .. (offered-load sweep, ISSUE 4:
           K ∈ {1,2,4,8} concurrent scripted discussions through the
           continuous-batching session scheduler on ONE shared engine;
           emits one JSON line per K with aggregate decode tok/s,
           batch-occupancy %, p50/p95 turn latency, p50/p95 TTFT per
           round under concurrent admission (ISSUE 8 — served off a
           PAGED engine so ragged chunk-interleaved admission and the
           prefix cache are in play; ragged-path provenance embedded;
           ROUNDTABLE_RAGGED_ATTN=0 A/Bs the PR-4 prologue), and the
           scheduler's decision provenance embedded like int4_paths.
           ROUNDTABLE_BENCH_LOAD_KS=1,2,4 overrides the sweep.)
       ROUNDTABLE_BENCH_PREFIX_REUSE=1 .. (prefix-reuse sweep, ISSUE 7:
           the offered-load run twice on a PAGED engine — cross-session
           prefix cache ON then OFF — emitting one JSON line per mode
           with the reused-token fraction, prefill tok/s EFFECTIVE
           (total prompt tokens / prefill wall — what the user feels)
           vs COMPUTED (actually-prefilled tokens / wall — what the
           chip did), the memory ledger's shared-page split, and the
           estimated max resident sessions before refusal.)
       ROUNDTABLE_BENCH_SPEC_DECODE=1 ..  (speculation A/B, ISSUE 9: a
           scripted multi-round discussion served spec-ON then
           spec-OFF on one paged+ragged engine, in ONE record —
           accepted tok/s, acceptance rate BY ROUND (the transcript is
           the drafter's corpus, so later rounds should accept more),
           mean accepted tokens per verify dispatch, p50/p95 turn
           latency, and the greedy token-parity bit across modes.
           ROUNDTABLE_BENCH_SPEC_ROUNDS overrides the round count.)
       ROUNDTABLE_BENCH_LORA=1 ..        (multi-LoRA persona A/B,
           ISSUE 10: the same K-knight scripted load served (a) as K
           LoRA personas co-batched on ONE shared base engine vs (b)
           as a K-checkpoint fleet (one engine per distinct seed — the
           pre-LoRA diversity recipe), in ONE record — aggregate
           decode tok/s, resident HBM bytes per mode (the acceptance
           bar: shared-base K personas < 1.5x a single base vs ~Kx for
           the fleet), per-knight next-token distribution divergence
           (personas must be DIFFERENT models, measurably), the
           mixed-vs-alone token-parity bit, and the lora store/path
           provenance embedded. ROUNDTABLE_BENCH_LORA_K overrides K.)
       ROUNDTABLE_BENCH_KV_QUANT=1 ..    (quantized-KV-page A/B,
           ISSUE 11: the same pool BYTE budget served int8-KV-ON then
           bf16-OFF, in ONE record — max resident sessions before the
           allocator evicts (the acceptance bar: >= 1.8x at int8),
           scheduled decode tok/s, the ledger's resident-vs-logical
           byte split, the greedy token-parity bit across modes, the
           per-page-path dequant provenance (kernel vs XLA, with
           machine-readable fallback_reason), the quant-aware roofline
           block, and ROUNDTABLE_RECOMPILE_STRICT=1 green across the
           serve. On CPU the model is a head_dim=64 tiny-gemma variant
           (D=16's per-cell f32 scale overhead caps the page ratio at
           1.6x; serving head_dims amortize it — gemma-2b's D=256
           gives 1.97x). ROUNDTABLE_BENCH_KVQ_DTYPE=int4 A/Bs int4.)
       ROUNDTABLE_BENCH_RESTART=1 ..     (restart-under-load, ISSUE 12:
           K concurrent multi-round scripted sessions on one paged +
           host-offload engine, served fault-free then with ROLLING
           supervisor.restart() cycles fired mid-run (after rounds 1
           and 2) — ONE record with sessions recovered vs lost, the
           recovery wall per restart (quiesce → evacuate → rebuild →
           restore) and its p95, and the greedy token-parity bit vs
           the uninterrupted run: the across-restart KV restore is
           byte-identical exactly when later rounds' own-slot reuse
           produces the same tokens. ROUNDTABLE_BENCH_RESTART_N
           overrides the restart count.)
Same watchdog+retry child-process pattern as bench.py (one process per
chip: a second one hangs rather than erroring while the first holds it).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

A100_OLLAMA_DISCUSS_WALL_S = 25.0  # derivation in module docstring

ATTEMPT_TIMEOUT_S = 420.0
MAX_ATTEMPTS = 2
RETRY_DELAY_S = 20.0

TOPIC = ("Should the session store move to an append-only event log "
         "before the apply pipeline lands?")


def _registry_snapshot() -> dict:
    """Compact unified-registry snapshot for run-record embedding."""
    from theroundtaible_tpu.utils import telemetry
    return telemetry.REGISTRY.snapshot_compact()


def _perf_block() -> dict:
    """Perf-attribution block (ISSUE 6): roofline gauges, compile
    observatory summary, memory ledger, span overheads — every run
    record explains its own number."""
    from theroundtaible_tpu.utils import perfmodel
    return perfmodel.attribution_snapshot()


def offered_load_child() -> int:
    """Offered-load sweep (ISSUE 4 satellite): K concurrent 3-knight
    scripted discussions through ONE shared engine + session scheduler,
    for K in {1, 2, 4, 8}. Scores are scripted (random weights can't
    emit the consensus JSON — same stance as the main benchmark); the
    serving path is the REAL orchestrator → scheduler-routed adapter →
    continuously-batched engine."""
    from bench_common import install_sigterm_exit

    install_sigterm_exit()
    import statistics
    import tempfile
    import threading

    import jax

    if os.environ.get("ROUNDTABLE_BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")

    from theroundtaible_tpu.engine import enable_compilation_cache

    enable_compilation_cache()

    from theroundtaible_tpu.adapters.tpu_llm import TpuLlmAdapter
    from theroundtaible_tpu.core.orchestrator import run_discussion
    from theroundtaible_tpu.core.types import (ConsensusBlock, KnightConfig,
                                               RoundtableConfig, RulesConfig)
    from theroundtaible_tpu.engine.scheduler import SessionScheduler

    on_cpu = jax.devices()[0].platform == "cpu"
    model = "tiny-gemma" if on_cpu else "gemma-2b-it"
    max_seq = 1024 if on_cpu else 2048
    # Decode-representative turns (ISSUE 8): real discussion turns run
    # ~160 tokens (BASELINE.md); 32-token CPU turns made the sweep
    # prefill-dominated, which hid exactly the admission stall the
    # TTFT percentiles exist to measure.
    max_new = 96
    rounds = 2
    num_slots = 12  # up to 4 concurrent 3-knight sessions resident
    ks = [int(x) for x in os.environ.get(
        "ROUNDTABLE_BENCH_LOAD_KS", "1,2,4,8").split(",")]
    # Arrival stagger (ISSUE 8): offered load means sessions ARRIVE
    # over time — session i starts i*stagger seconds in, so later
    # sessions are LATE JOINERS admitted against a live decode batch
    # (the admission-stall shape the TTFT percentiles measure). 0
    # restores the PR-4 all-at-once burst.
    stagger_s = float(os.environ.get(
        "ROUNDTABLE_BENCH_LOAD_STAGGER_S", "1.0"))

    class Scripted(TpuLlmAdapter):
        """Real serving; scripted consensus scores terminate each
        discussion at exactly `rounds` rounds (random weights cannot
        emit the JSON block — bench_discuss's standing stance)."""

        def parse_consensus(self, response, round_num):
            score = 9.5 if round_num >= rounds else 6.0
            return ConsensusBlock(
                knight=self.name, round=round_num, consensus_score=score,
                agrees_with=[], pending_issues=[], proposal="bench",
                files_to_modify=["bench.md"] if score >= 9 else [])

    # Paged pool (ISSUE 8): the offered-load sweep measures the MODERN
    # serving shape — prefix cache + ragged chunk-interleaved admission
    # both ride the paged engines; ROUNDTABLE_RAGGED_ATTN=0 serves the
    # same sweep through the PR-4 prologue for A/B TTFT comparisons.
    engine_cfg = {"model": model, "max_seq_len": max_seq,
                  "num_slots": num_slots, "kv_layout": "paged",
                  # Contiguous-equal pool: the sweep HOLDS K sessions
                  # resident concurrently — the default half-budget
                  # pool would serve admission backpressure, not the
                  # scheduling behavior this sweep measures.
                  "num_pages": num_slots * max_seq // 128,
                  "sampling": {"temperature": 0.0,
                               "max_new_tokens": max_new}}

    def make_config():
        return RoundtableConfig(
            version="1.0", project="bench", language="en",
            knights=[KnightConfig(name=f"Knight-{c}", adapter="tpu-llm",
                                  capabilities=[], priority=i + 1)
                     for i, c in enumerate("ABC")],
            rules=RulesConfig(max_rounds=rounds, consensus_threshold=9,
                              timeout_per_turn_seconds=300,
                              escalate_to_user_after=4, auto_execute=False,
                              parallel_rounds=True),
            chronicle="chronicle.md", adapter_config={"tpu-llm": {}})

    base = Scripted("tpu-llm", engine_cfg)
    engine = base._get_engine()
    t_warm = time.monotonic()
    engine.warmup(max_prompt_tokens=max_seq - 256, batch_sizes=(1, 3))
    warmup_s = time.monotonic() - t_warm

    for k in ks:
        sched = SessionScheduler(engine, admit_hold_s=0.25)
        config = make_config()
        entries = []
        with tempfile.TemporaryDirectory() as root:
            os.makedirs(os.path.join(root, ".roundtable", "sessions"))

            session_errors = []

            def run_one(i, k=k, root=root, config=config, sched=sched):
                try:
                    time.sleep(i * stagger_s)
                    adapter = Scripted("tpu-llm", engine_cfg)
                    adapter.attach_scheduler(sched, session=f"k{k}s{i}")
                    # Disambiguator goes FIRST: slugify truncates topics
                    # at 50 chars, and same-slug concurrent sessions
                    # would share (and corrupt) one session directory.
                    topic = f"(load {k}.{i}) {TOPIC}"
                    t0 = time.monotonic()
                    result = run_discussion(topic, config,
                                            {"tpu-llm": adapter}, root,
                                            read_source_code=False)
                    entries.append((result, time.monotonic() - t0))
                except Exception as e:  # noqa: BLE001 — reported below
                    # A silently-dropped session would make the emitted
                    # throughput/occupancy line claim a K-session sweep
                    # that never happened — fail the run loud instead.
                    session_errors.append((i, e))

            t0 = time.monotonic()
            threads = [threading.Thread(target=run_one, args=(i,))
                       for i in range(k)]
            for th in threads:
                th.start()

            # Late-join probe stream (ISSUE 8): fresh single-knight
            # sessions keep ARRIVING while the K discussions hold the
            # decode batch — the "new user hits a busy server" shape.
            # Their TTFT is the admission-stall number ragged
            # chunk-interleaved admission exists to move; the prologue
            # path serializes each probe's prefill against the live
            # batch and any concurrent admissions.
            probe_ttfts = []
            probe_errors = []
            probe_stop = threading.Event()

            def probe_loop(k=k, sched=sched):
                base = ("A new petitioner arrives at the castle and "
                        "lays out the matter before the court. ")
                i = 0
                while not probe_stop.is_set():
                    # ~400 fresh tokens per probe: a cold prefill (past
                    # any prefix-cache hit) is the admission stall under
                    # measurement.
                    prompt = (base * 16
                              + f" Petition {i} of load {k}: advise.")
                    try:
                        _texts, stats = sched.submit(
                            f"probe-k{k}-{i}",
                            [("petitioner", prompt)],
                            max_new_tokens=16, timeout_s=120.0)
                        tt = (stats.sched or {}).get("ttft_s")
                        if tt is not None:
                            probe_ttfts.append(tt)
                    except Exception as e:  # noqa: BLE001 — recorded
                        # A refused/timed-out probe IS a late-join
                        # datapoint (the record must not read "instant
                        # TTFT" when admission was saturated) — count
                        # it and keep probing.
                        probe_errors.append(type(e).__name__)
                        if len(probe_errors) >= 8:
                            break
                    i += 1
                    probe_stop.wait(0.25)

            prober = threading.Thread(target=probe_loop)
            prober.start()
            for th in threads:
                th.join()
            probe_stop.set()
            prober.join(timeout=130)
            wall = time.monotonic() - t0

            turn_walls, queue_waits, ttfts = [], [], []
            decode_tokens = 0
            occupancies = []
            for result, _sess_wall in entries:
                metrics = json.loads(open(os.path.join(
                    result.session_path, "metrics.json")).read())
                for r in metrics["rounds"]:
                    for t in r["turns"]:
                        turn_walls.append(t["wall_s"])
                        if t.get("queue_wait_s") is not None:
                            queue_waits.append(t["queue_wait_s"])
                        if t.get("batch_occupancy") is not None:
                            occupancies.append(t["batch_occupancy"])
                        if t.get("engine"):
                            decode_tokens += t["engine"].get(
                                "decode_tokens", 0)
                            # TTFT (ISSUE 8): submit → every row of the
                            # round sampled its first token, straight
                            # from the scheduler's sched stats — the
                            # admission-stall number ragged admission
                            # moves.
                            tt = (t["engine"].get("sched") or {}).get(
                                "ttft_s")
                            if tt is not None:
                                ttfts.append(tt)
        provenance = sched.describe()
        sched.close()
        if session_errors:
            raise RuntimeError(
                f"offered-load K={k}: {len(session_errors)}/{k} "
                f"session(s) failed: "
                + "; ".join(f"s{i}: {e}" for i, e in session_errors))
        assert len(entries) == k, f"K={k} ran only {len(entries)} sessions"
        assert all(r.consensus for r, _ in entries), \
            "every scripted discussion must reach consensus"
        turn_walls.sort()
        ttfts.sort()
        probe_ttfts.sort()

        def _pct_of(vals, p):
            if not vals:
                return 0.0
            idx = min(int(p / 100 * len(vals)), len(vals) - 1)
            return round(vals[idx], 3)

        def pct(p):
            return _pct_of(turn_walls, p)

        result_line = {
            "metric": f"offered_load_discuss[{model}][K={k}]",
            "value": round(decode_tokens / max(wall, 1e-9), 2),
            "unit": "aggregate_decode_tok_s",
            "detail": {
                "sessions": k,
                "rounds_per_session": rounds,
                "arrival_stagger_s": stagger_s,
                "wall_s": round(wall, 2),
                "decode_tokens": decode_tokens,
                "p50_turn_s": pct(50),
                "p95_turn_s": pct(95),
                "turn_count": len(turn_walls),
                # Time-to-first-token per round under concurrent
                # admission — the headline number ragged
                # chunk-interleaved admission moves (ISSUE 8).
                "p50_ttft_s": _pct_of(ttfts, 50),
                "p95_ttft_s": _pct_of(ttfts, 95),
                "ttft_count": len(ttfts),
                # The late-join probe stream's TTFT — sessions arriving
                # at the already-busy batch (the headline this PR
                # moves; see probe_loop above). None (never 0.0) when
                # no probe completed — an empty stream must not read
                # as instant admission.
                "p50_ttft_late_join_s": (_pct_of(probe_ttfts, 50)
                                         if probe_ttfts else None),
                "p95_ttft_late_join_s": (_pct_of(probe_ttfts, 95)
                                         if probe_ttfts else None),
                "late_join_count": len(probe_ttfts),
                "late_join_errors": probe_errors,
                "queue_wait_mean_s": (
                    round(statistics.mean(queue_waits), 3)
                    if queue_waits else 0.0),
                "batch_occupancy_mean": (
                    round(statistics.mean(occupancies), 2)
                    if occupancies else 0.0),
                "batch_occupancy_pct": round(
                    100.0 * provenance["occupancy_mean"]
                    / max(num_slots, 1), 1),
                "warmup_s": round(warmup_s, 1),
                "platform": jax.devices()[0].platform,
                # Scheduler decision provenance embedded in the run
                # record, the int4_paths pattern (ISSUE 4).
                "scheduler": {kk: vv for kk, vv in provenance.items()
                              if kk != "events"},
                # Ragged-path provenance (ISSUE 8): dispatch counts and
                # fallback reasons, so the TTFT numbers are attributable
                # to the mixed-dispatch path (or its absence).
                "ragged": engine.ragged_describe(),
                "kv_layout": "paged",
                # Unified-registry snapshot (ISSUE 5): the same
                # occupancy/fallback/hang counters fleet_health reads,
                # frozen into the run record.
                "telemetry": _registry_snapshot(),
                "perf": _perf_block(),
            },
        }
        print(json.dumps(result_line), flush=True)
    return 0


def late_join_child() -> int:
    """Late-join TTFT A/B (ISSUE 8 acceptance): K fresh sessions submit
    while a resident session is DEEP IN DECODE — the admission-stall
    scenario ragged chunk-interleaved admission exists to kill — served
    twice on one paged config, ragged ON then OFF (the
    prefix_reuse_child on/off pattern), so the record carries the
    measured p50/p95 TTFT delta, not a projection. Direct scheduler
    submissions (no orchestrator): the measurement is the scheduler's
    admission path itself. Emits ONE JSON line with both modes, the
    deltas, greedy token parity across modes, and the ragged-path
    provenance (dispatch counts, fallback reasons) embedded."""
    from bench_common import install_sigterm_exit

    install_sigterm_exit()
    import threading

    import jax

    if os.environ.get("ROUNDTABLE_BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")

    from theroundtaible_tpu.engine import enable_compilation_cache

    enable_compilation_cache()

    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.models.registry import get_model_config
    from theroundtaible_tpu.engine.scheduler import SessionScheduler

    on_cpu = jax.devices()[0].platform == "cpu"
    model = "tiny-gemma" if on_cpu else "gemma-2b-it"
    max_seq = 1024 if on_cpu else 2048
    k = int(os.environ.get("ROUNDTABLE_BENCH_LATE_JOIN_K", "3"))
    bg_tokens = 256
    join_new = 48
    cfg = get_model_config(model, max_seq_len=max_seq)
    kw = {}
    if on_cpu:
        # Tests/CI expose 8 virtual devices; tiny-gemma's heads don't
        # partition an 8-way model axis, which would (correctly)
        # decline the kernel — measure the kernel path.
        kw["mesh_shape"] = {"data": 1, "model": 1}

    joiner_prompt = ("A new petitioner arrives at the castle and lays "
                     "out the matter before the court in great detail. "
                     * 16)

    def run_mode(ragged: bool) -> dict:
        eng = InferenceEngine(
            cfg, num_slots=k + 2, kv_layout="paged",
            num_pages=(k + 2) * max_seq // 128, ragged_attn=ragged,
            **kw)
        warm_s = eng.warmup(max_prompt_tokens=512, batch_sizes=(1, 2))
        sched = SessionScheduler(eng)
        results: dict = {}
        errors: list = []

        def background():
            try:
                results["bg"] = sched.submit(
                    "bg", [("scribe", "The scribe recounts the history "
                                      "of the order at great length.")],
                    max_new_tokens=bg_tokens)
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append(("bg", e))

        def joiner(i):
            try:
                while not sched._active:
                    time.sleep(0.005)
                time.sleep(0.15 * i)
                results[f"j{i}"] = sched.submit(
                    f"j{i}", [("petitioner",
                               joiner_prompt + f" Petition {i}.")],
                    max_new_tokens=join_new)
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append((f"j{i}", e))

        threads = [threading.Thread(target=background)] + [
            threading.Thread(target=joiner, args=(i,)) for i in range(k)]
        t0 = time.monotonic()
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
        wall = time.monotonic() - t0
        if errors:
            raise RuntimeError(f"late-join mode ragged={ragged}: "
                               + "; ".join(f"{s}: {e}"
                                           for s, e in errors))
        ttfts = sorted(results[f"j{i}"][1].sched["ttft_s"]
                       for i in range(k))
        provenance = sched.describe()
        sched.close()

        def pct(p):
            idx = min(int(p / 100 * len(ttfts)), len(ttfts) - 1)
            return round(ttfts[idx], 3)

        return {
            "ttfts_s": ttfts, "p50_ttft_s": pct(50),
            "p95_ttft_s": pct(95), "wall_s": round(wall, 2),
            "warmup_s": round(warm_s, 1),
            "texts": {s: results[s][0] for s in results},
            "ragged": eng.ragged_describe(),
            "scheduler": {kk: vv for kk, vv in provenance.items()
                          if kk != "events"},
        }

    on = run_mode(True)
    off = run_mode(False)
    parity = on.pop("texts") == off.pop("texts")
    result_line = {
        "metric": f"late_join_ttft[{model}][K={k}]",
        "value": on["p95_ttft_s"],
        "unit": "p95_ttft_s_ragged_on",
        "detail": {
            "late_joiners": k,
            "bg_decode_tokens": bg_tokens,
            "ragged_on": on,
            "prologue": off,
            "p95_ttft_improvement_s": round(
                off["p95_ttft_s"] - on["p95_ttft_s"], 3),
            "p50_ttft_improvement_s": round(
                off["p50_ttft_s"] - on["p50_ttft_s"], 3),
            # Greedy outputs must not depend on the admission path —
            # the kill-switch byte-identity acceptance, measured here.
            "token_parity_on_vs_off": parity,
            "platform": jax.devices()[0].platform,
            "telemetry": _registry_snapshot(),
        },
    }
    print(json.dumps(result_line), flush=True)
    return 0


def spec_decode_child() -> int:
    """Speculation A/B (ISSUE 9 acceptance): a scripted multi-round
    discussion — each round's turn prompt carries the WHOLE transcript
    so far, the roundtable shape that makes self-drafting work — served
    twice on one paged+ragged config, speculation ON then OFF (the
    late_join_child on/off pattern). Emits ONE JSON line with both
    modes, acceptance rate by round (the transcript is the drafter's
    corpus: later rounds should accept more), mean accepted tokens per
    verify dispatch, accepted tok/s, p50/p95 turn latency, the greedy
    token-parity bit across modes, and the spec/ragged provenance
    embedded. One session serves at a time, so accepted-per-dispatch is
    exact: each verify dispatch carries exactly one row."""
    from bench_common import install_sigterm_exit

    install_sigterm_exit()
    import jax

    if os.environ.get("ROUNDTABLE_BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")

    from theroundtaible_tpu.engine import enable_compilation_cache

    enable_compilation_cache()

    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.models.registry import get_model_config
    from theroundtaible_tpu.engine.scheduler import SessionScheduler

    on_cpu = jax.devices()[0].platform == "cpu"
    model = "tiny-gemma" if on_cpu else "gemma-2b-it"
    max_seq = 1024 if on_cpu else 2048
    rounds = int(os.environ.get("ROUNDTABLE_BENCH_SPEC_ROUNDS", "4"))
    knights = 2
    max_new = 48 if on_cpu else 64
    cfg = get_model_config(model, max_seq_len=max_seq)
    kw = {}
    if on_cpu:
        # Tests/CI expose 8 virtual devices; tiny-gemma's heads don't
        # partition an 8-way model axis — measure the kernel path.
        kw["mesh_shape"] = {"data": 1, "model": 1}

    def pct(xs, p):
        xs = sorted(xs)
        return round(xs[min(int(p / 100 * len(xs)), len(xs) - 1)], 3)

    def run_mode(spec: bool) -> dict:
        eng = InferenceEngine(
            cfg, num_slots=4, kv_layout="paged",
            num_pages=4 * max_seq // 128, spec_decode=spec, **kw)
        warm_s = eng.warmup(max_prompt_tokens=512, batch_sizes=(1, 2))
        sched = SessionScheduler(eng)
        transcript = ("The roundtable convenes to score the proposal. "
                      "Each knight quotes the proposal verbatim before "
                      "scoring it. ")
        by_round = []
        turn_walls: list[float] = []
        texts: list[str] = []
        dec_tok = 0
        dec_sec = 0.0
        try:
            for rnd in range(rounds):
                d0, a0 = eng._spec_drafted, eng._spec_accepted
                v0 = eng._spec_dispatches
                r_tok, r_sec = 0, 0.0
                for k in range(knights):
                    prompt = (transcript
                              + f"\nKnight {k} now speaks in turn: ")
                    t0 = time.monotonic()
                    txts, stats = sched.submit(
                        "bench", [(f"knight{k}", prompt)],
                        max_new_tokens=max_new)
                    turn_walls.append(time.monotonic() - t0)
                    texts.append(txts[0])
                    transcript += f"\nKnight {k}: {txts[0]}"
                    r_tok += stats.decode_tokens
                    r_sec += stats.decode_seconds
                dec_tok += r_tok
                dec_sec += r_sec
                dd = eng._spec_drafted - d0
                da = eng._spec_accepted - a0
                dv = eng._spec_dispatches - v0
                by_round.append({
                    "round": rnd,
                    "drafted": dd, "accepted": da,
                    "verify_dispatches": dv,
                    "acceptance_rate": (round(da / dd, 3) if dd
                                        else None),
                    "accepted_tok_s": (round(r_tok / r_sec, 1)
                                       if r_sec else None),
                })
            info = eng.spec_describe()
            sched_d = sched.describe()
        finally:
            sched.close()
        disp = info["verify_dispatches"]
        return {
            "spec": info,
            "by_round": by_round,
            # Tokens COMMITTED per verify dispatch: the guaranteed 1
            # (correction/bonus) plus every accepted draft — exact
            # here because each dispatch carries one row.
            "mean_accepted_tokens_per_verify_dispatch": (
                round(1.0 + info["accepted_tokens"] / disp, 3)
                if disp else None),
            "accepted_tok_s": (round(dec_tok / dec_sec, 1)
                               if dec_sec else None),
            "decode_tokens": dec_tok,
            "p50_turn_s": pct(turn_walls, 50),
            "p95_turn_s": pct(turn_walls, 95),
            "warmup_s": round(warm_s, 1),
            "texts": texts,
            "ragged": eng.ragged_describe(),
            "scheduler": {k: v for k, v in sched_d.items()
                          if k != "events"},
        }

    on = run_mode(True)
    off = run_mode(False)
    parity = on.pop("texts") == off.pop("texts")
    result_line = {
        "metric": f"spec_decode[{model}][rounds={rounds}]",
        "value": on["mean_accepted_tokens_per_verify_dispatch"],
        "unit": "accepted_tokens_per_verify_dispatch",
        "detail": {
            "rounds": rounds, "knights": knights,
            "max_new_tokens": max_new,
            "spec_on": on,
            "spec_off": off,
            "accepted_tok_s_speedup": (
                round(on["accepted_tok_s"] / off["accepted_tok_s"], 3)
                if on["accepted_tok_s"] and off["accepted_tok_s"]
                else None),
            # Greedy outputs must not depend on speculation — the
            # kill-switch byte-identity acceptance, measured here.
            "token_parity_on_vs_off": parity,
            "platform": jax.devices()[0].platform,
            "telemetry": _registry_snapshot(),
            "perf": _perf_block(),
        },
    }
    print(json.dumps(result_line), flush=True)
    return 0


def prefix_reuse_child() -> int:
    """Prefix-reuse sweep (ISSUE 7 satellite): the K-session scripted
    discussion load served twice on ONE paged-engine config — with the
    cross-session prefix cache on, then off — so the run record carries
    the reuse the radix tree actually delivered, not a projection.
    Recorded per mode: reused-token fraction, effective vs computed
    prefill tok/s, shared/exclusive page split, and the estimated max
    resident sessions before admission refusal (pool pages / per-session
    exclusive footprint — the capacity multiplier the tentpole claims)."""
    from bench_common import install_sigterm_exit

    install_sigterm_exit()
    import statistics
    import tempfile
    import threading

    import jax

    if os.environ.get("ROUNDTABLE_BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")

    from theroundtaible_tpu.engine import enable_compilation_cache

    enable_compilation_cache()

    from theroundtaible_tpu.adapters.tpu_llm import TpuLlmAdapter
    from theroundtaible_tpu.core.orchestrator import run_discussion
    from theroundtaible_tpu.core.types import (ConsensusBlock, KnightConfig,
                                               RoundtableConfig, RulesConfig)
    from theroundtaible_tpu.engine.scheduler import SessionScheduler

    on_cpu = jax.devices()[0].platform == "cpu"
    model = "tiny-gemma" if on_cpu else "gemma-2b-it"
    max_seq = 1024 if on_cpu else 2048
    max_new = 32 if on_cpu else 96
    rounds = 2
    num_slots = 12
    k = int(os.environ.get("ROUNDTABLE_BENCH_REUSE_K", "3"))
    # Arrival stagger between sessions: simultaneous (lockstep) arrivals
    # would admit every session before any peer COMMITS, so the index
    # would have nothing to serve — production arrivals are a process in
    # time, and the stagger is what lets session i+1 match the pages
    # session i just committed.
    stagger_s = float(os.environ.get(
        "ROUNDTABLE_BENCH_REUSE_STAGGER_S", "2.0" if on_cpu else "5.0"))

    class Scripted(TpuLlmAdapter):
        def parse_consensus(self, response, round_num):
            score = 9.5 if round_num >= rounds else 6.0
            return ConsensusBlock(
                knight=self.name, round=round_num, consensus_score=score,
                agrees_with=[], pending_issues=[], proposal="bench",
                files_to_modify=["bench.md"] if score >= 9 else [])

    def make_config():
        return RoundtableConfig(
            version="1.0", project="bench", language="en",
            knights=[KnightConfig(name=f"Knight-{c}", adapter="tpu-llm",
                                  capabilities=[], priority=i + 1)
                     for i, c in enumerate("ABC")],
            rules=RulesConfig(max_rounds=rounds, consensus_threshold=9,
                              timeout_per_turn_seconds=300,
                              escalate_to_user_after=4, auto_execute=False,
                              parallel_rounds=True),
            chronicle="chronicle.md", adapter_config={"tpu-llm": {}})

    for cache_on in (True, False):
        # Drop the previous mode's memoized engine BEFORE building this
        # one: the get_engine cache would otherwise pin BOTH full
        # engines (weights + paged pool) resident through the cache-off
        # half — ~2x HBM on a real chip, OOM risk during exactly the
        # run meant to be the fair comparison.
        from theroundtaible_tpu.engine import reset_engines
        reset_engines()
        engine_cfg = {"model": model, "max_seq_len": max_seq,
                      "num_slots": num_slots, "kv_layout": "paged",
                      "prefix_cache": cache_on, "kv_offload": cache_on,
                      "sampling": {"temperature": 0.0,
                                   "max_new_tokens": max_new}}
        base = Scripted("tpu-llm", engine_cfg)
        engine = base._get_engine()
        t_warm = time.monotonic()
        engine.warmup(max_prompt_tokens=max_seq - 256, batch_sizes=(1, 3))
        warmup_s = time.monotonic() - t_warm
        sched = SessionScheduler(engine, admit_hold_s=0.25)
        config = make_config()
        entries, session_errors = [], []
        with tempfile.TemporaryDirectory() as root:
            # One root PER SESSION: every discussion runs the IDENTICAL
            # topic (that is the whole point — the radix tree can only
            # match identical token prefixes, and serve fans one topic
            # into K sessions exactly like this), so the session-dir
            # slug dedup must come from the root, not a topic prefix
            # that would destroy the shared head.
            def run_one(i, root=root, config=config, sched=sched,
                        cache_on=cache_on):
                try:
                    sroot = os.path.join(root, f"s{i}")
                    os.makedirs(os.path.join(sroot, ".roundtable",
                                             "sessions"))
                    adapter = Scripted("tpu-llm", engine_cfg)
                    adapter.attach_scheduler(
                        sched, session=f"pr{int(cache_on)}s{i}")
                    t0 = time.monotonic()
                    result = run_discussion(TOPIC, config,
                                            {"tpu-llm": adapter}, sroot,
                                            read_source_code=False)
                    entries.append((result, time.monotonic() - t0))
                except Exception as e:  # noqa: BLE001 — reported below
                    session_errors.append((i, e))

            t0 = time.monotonic()
            threads = [threading.Thread(target=run_one, args=(i,))
                       for i in range(k)]
            for i, th in enumerate(threads):
                if i and stagger_s:
                    time.sleep(stagger_s)
                th.start()
            for th in threads:
                th.join()
            wall = time.monotonic() - t0

            prefill_tokens = reused = prefix_reused = 0
            prefill_seconds = 0.0
            for result, _w in entries:
                metrics = json.loads(open(os.path.join(
                    result.session_path, "metrics.json")).read())
                for r in metrics["rounds"]:
                    for t in r["turns"]:
                        eng_stats = t.get("engine") or {}
                        prefill_tokens += eng_stats.get(
                            "prefill_tokens", 0)
                        reused += eng_stats.get("reused_tokens", 0)
                        prefix_reused += eng_stats.get(
                            "prefix_reused_tokens", 0)
                        prefill_seconds += eng_stats.get(
                            "prefill_seconds", 0.0)
        provenance = sched.describe()
        sched.close()
        if session_errors:
            raise RuntimeError(
                f"prefix-reuse cache_on={cache_on}: "
                f"{len(session_errors)}/{k} session(s) failed: "
                + "; ".join(f"s{i}: {e}" for i, e in session_errors))
        assert len(entries) == k
        led = engine.kv.memory_ledger()
        total_prompt = prefill_tokens + reused
        # Max resident sessions before refusal: the pool's usable pages
        # over the mean EXCLUSIVE per-session footprint — sharing makes
        # the denominator shrink, which IS the capacity multiplier.
        excl_per_session = max(
            (led["exclusive_pages"]) / max(k, 1), 1e-9)
        max_resident_est = int(led["usable_pages"] // excl_per_session)
        result_line = {
            "metric": (f"prefix_reuse_discuss[{model}]"
                       f"[cache={'on' if cache_on else 'off'}]"),
            "value": round(reused / max(total_prompt, 1), 4),
            "unit": "reused_token_fraction",
            "detail": {
                "sessions": k,
                "rounds_per_session": rounds,
                "wall_s": round(wall, 2),
                "prompt_tokens_total": total_prompt,
                "prefill_tokens_computed": prefill_tokens,
                "reused_tokens": reused,
                "prefix_cache_reused_tokens": prefix_reused,
                "prefill_tok_s_effective": round(
                    total_prompt / max(prefill_seconds, 1e-9), 1),
                "prefill_tok_s_computed": round(
                    prefill_tokens / max(prefill_seconds, 1e-9), 1),
                "max_resident_sessions_est": max_resident_est,
                "memory_ledger": {kk: led[kk] for kk in (
                    "pages_in_use", "usable_pages", "shared_pages",
                    "exclusive_pages", "prefix_cache_pages")},
                "prefix_cache": (engine.prefix_cache.describe()
                                 if engine.prefix_cache is not None
                                 else None),
                "kv_offload": (engine.kv_offload.describe()
                               if engine.kv_offload is not None
                               else None),
                "warmup_s": round(warmup_s, 1),
                "platform": jax.devices()[0].platform,
                "scheduler": {kk: vv for kk, vv in provenance.items()
                              if kk != "events"},
                "telemetry": _registry_snapshot(),
                "perf": _perf_block(),
            },
        }
        print(json.dumps(result_line), flush=True)
        # Drop every strong reference to this mode's engine before the
        # next iteration's reset_engines(): loop locals outliving the
        # memo would keep both full engines resident — exactly the
        # 2x-HBM risk the reset exists to prevent.
        base = engine = sched = led = None  # noqa: F841
    return 0


def child() -> int:
    from bench_common import install_sigterm_exit

    install_sigterm_exit()
    import jax

    if os.environ.get("ROUNDTABLE_BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")

    from theroundtaible_tpu.engine import enable_compilation_cache

    enable_compilation_cache()

    from theroundtaible_tpu.adapters.tpu_llm import TpuLlmAdapter
    from theroundtaible_tpu.core.orchestrator import run_discussion
    from theroundtaible_tpu.core.types import (ConsensusBlock, KnightConfig,
                                               RoundtableConfig, RulesConfig)
    from theroundtaible_tpu.utils.metrics import aggregate_engine_stats

    on_cpu = jax.devices()[0].platform == "cpu"
    model = "tiny-gemma" if on_cpu else "gemma-2b-it"
    max_seq = 1024 if on_cpu else 2048
    max_new = 48 if on_cpu else 160
    rounds = 5

    # Sampler provenance (ISSUE 3 satellite): config 2 records WHICH
    # sampler path its decode ran — greedy (the temp=0 default), plain
    # (sampled, no filter: no candidate pool), the sort-free candidate
    # pool, or the exact full-vocab sort — so each gets an attributable
    # number in the same window. Flip the env knobs to measure the
    # sampled paths: ROUNDTABLE_BENCH_TEMPERATURE=0.7 alone runs plain;
    # with ROUNDTABLE_BENCH_TOP_P=0.95 or ROUNDTABLE_BENCH_TOP_K=40 the
    # run is sort-free; ROUNDTABLE_BENCH_TOP_K>128 forces the sort
    # fallback.
    temp = float(os.environ.get("ROUNDTABLE_BENCH_TEMPERATURE", "0.0"))
    top_p = float(os.environ.get("ROUNDTABLE_BENCH_TOP_P", "1.0"))
    top_k = int(os.environ.get("ROUNDTABLE_BENCH_TOP_K", "0"))
    from theroundtaible_tpu.engine.sampling import (SamplingParams,
                                                    sampler_mode)
    mode = sampler_mode([SamplingParams(temperature=temp, top_k=top_k,
                                        top_p=top_p)])

    real_parse = {"count": 0, "ok": 0, "seconds": 0.0}

    class ScriptedConsensusAdapter(TpuLlmAdapter):
        """Real engine serving; consensus SCORES scripted per round so the
        discussion terminates at exactly `rounds` rounds — but the real
        parse path is wall-clocked on every turn (VERDICT r2 weak #6):
        the model's raw output gets a canonical consensus JSON appended
        (the forced continuation a real checkpoint would emit) and runs
        through parse_consensus_from_response → ConsensusBlock
        validation, so extraction + repair + validation cost is INSIDE
        the measured wall. Only the resulting score is then overridden."""

        def parse_consensus(self, response, round_num):
            score = 9.5 if round_num >= rounds else 6.0
            forced = response + (
                '\n```json\n{"consensus_score": %s, "agrees_with": '
                '["Knight-A"], "pending_issues": [], "proposal": '
                '"benchmark proposal", "files_to_modify": %s}\n```\n'
                % (score, '["bench.md"]' if score >= 9 else "[]"))
            t0 = time.monotonic()
            parsed = super().parse_consensus(forced, round_num)
            real_parse["seconds"] += time.monotonic() - t0
            real_parse["count"] += 1
            if parsed is not None:
                real_parse["ok"] += 1
                # The scripted score ALWAYS wins (termination guarantee):
                # should the model's raw output ever contain its own
                # parseable consensus block, that block parses first and
                # its arbitrary score must not end the discussion early.
                parsed.consensus_score = score
                parsed.files_to_modify = (["bench.md"] if score >= 9
                                          else [])
                return parsed
            return ConsensusBlock(
                knight=self.name, round=round_num, consensus_score=score,
                agrees_with=[], pending_issues=[],
                proposal="benchmark proposal",
                files_to_modify=["bench.md"] if score >= 9 else [])

    adapter = ScriptedConsensusAdapter(
        "tpu-llm", {"model": model, "max_seq_len": max_seq, "num_slots": 4,
                    "sampling": {"temperature": temp, "top_k": top_k,
                                 "top_p": top_p,
                                 "max_new_tokens": max_new}})

    config = RoundtableConfig(
        version="1.0", project="bench", language="en",
        knights=[
            KnightConfig(name=f"Knight-{c}", adapter="tpu-llm",
                         capabilities=[], priority=i + 1)
            for i, c in enumerate("ABC")],
        rules=RulesConfig(max_rounds=rounds, consensus_threshold=9,
                          timeout_per_turn_seconds=300,
                          escalate_to_user_after=4, auto_execute=False,
                          parallel_rounds=True),
        chronicle="chronicle.md",
        adapter_config={"tpu-llm": {}},
    )

    with tempfile.TemporaryDirectory() as root:
        os.makedirs(os.path.join(root, ".roundtable", "sessions"))
        engine = adapter._get_engine()
        t_warm = time.monotonic()
        engine.warmup(max_prompt_tokens=max_seq - 256, batch_sizes=(1, 3))
        warmup_s = time.monotonic() - t_warm

        reporter = None
        if os.environ.get("ROUNDTABLE_BENCH_DEBUG"):
            from theroundtaible_tpu.commands.reporter import ConsoleReporter
            reporter = ConsoleReporter()
        t0 = time.monotonic()
        result = run_discussion(TOPIC, config, {"tpu-llm": adapter}, root,
                                read_source_code=False, reporter=reporter)
        wall = time.monotonic() - t0

        metrics_path = os.path.join(result.session_path, "metrics.json")
        metrics = json.loads(open(metrics_path).read())

    assert result.consensus, "scripted discussion must reach consensus"
    assert result.rounds == rounds

    totals = metrics["totals"]
    turns = [t for r in metrics["rounds"] for t in r["turns"]]
    agg = aggregate_engine_stats(
        type("T", (), {"engine": t["engine"]})() for t in turns)
    prefill = agg["prefill_tokens"]
    reused = agg["reused_tokens"]
    reuse_pct = 100.0 * reused / max(prefill + reused, 1)

    # The stable greedy metric key is unchanged; a sampled run (the env
    # knobs above) lands under a mode-suffixed key so the two never
    # collide in per-key dedup and each stays attributable.
    metric_key = f"discuss_wall_clock_3knight_{rounds}round[{model}]"
    if mode != "greedy":
        metric_key += f"[{mode}]"
    result_line = {
        "metric": metric_key,
        "value": round(wall, 2),
        "unit": "seconds",
        "vs_baseline": round(A100_OLLAMA_DISCUSS_WALL_S / max(wall, 1e-9),
                             3),
        "detail": {
            "rounds": result.rounds,
            "decode_tokens": agg["decode_tokens"],
            "decode_tps": agg["decode_tps"],
            "prefill_tokens": prefill,
            "reused_tokens": reused,
            "cache_reuse_pct": round(reuse_pct, 1),
            "warmup_s": round(warmup_s, 1),
            "engine_wall_s": totals.get("wall_s"),
            "platform": jax.devices()[0].platform,
            # Per-run sampler attribution: greedy / plain / sort-free / sort
            # (engine/sampling.sampler_mode) + the knobs that chose it.
            "sampler": {"mode": mode, "temperature": temp,
                        "top_k": top_k, "top_p": top_p},
            # Scores are scripted (random weights can't emit the JSON
            # block) but the full parse→validate path ran inside the
            # wall on every turn via a forced continuation:
            "consensus": {
                "scripted_scores": True,
                "real_parse_turns": real_parse["count"],
                "real_parse_ok": real_parse["ok"],
                "real_parse_s": round(real_parse["seconds"], 4),
                # Emergent (unscripted) termination is proven hermetically
                # by tests/test_emergent_consensus.py: a constructed
                # checkpoint's DECODED output carries the consensus JSON
                # and the unmodified adapter+orchestrator terminate on the
                # parsed scores. Scripting here is purely a wall-clock
                # termination guarantee for random bench weights.
                "emergent_consensus_test": "tests/test_emergent_consensus.py",
            },
            # Unified-registry snapshot (ISSUE 5, the int4_paths
            # pattern): every run record carries the window's counters.
            "telemetry": _registry_snapshot(),
            "perf": _perf_block(),
        },
    }
    # flush=True: the watchdog salvages a timeout-killed child's stdout,
    # which only works if the line left this process's buffer.
    print(json.dumps(result_line), flush=True)
    return 0




def lora_child() -> int:
    """Multi-LoRA persona A/B (ISSUE 10 acceptance): the same K-knight
    scripted multi-round load served two ways on the same base model —

    (a) SHARED BASE: one engine + K LoRA persona adapters, all K
        knights co-batched through the session scheduler (mixed-adapter
        decode segments on one resident base);
    (b) K-CHECKPOINT FLEET: K engines with distinct seeds (the
        pre-LoRA diversity recipe — each persona costs a full resident
        model), each serving its knight concurrently.

    Emits ONE JSON line with both modes: aggregate decode tok/s,
    resident HBM bytes (weights + KV + adapter stacks — the acceptance
    bar is shared-base < 1.5x a single base vs ~Kx for the fleet),
    per-knight NEXT-TOKEN DISTRIBUTION divergence (mean pairwise total
    variation on a probe prompt — personas must be measurably distinct
    models, not labels), the mixed-vs-alone token-parity bit, and the
    lora store/path provenance embedded (the int4_paths pattern)."""
    from bench_common import install_sigterm_exit

    install_sigterm_exit()
    import threading

    import jax

    if os.environ.get("ROUNDTABLE_BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")

    from theroundtaible_tpu.engine import enable_compilation_cache

    enable_compilation_cache()

    import jax.numpy as jnp
    import numpy as np

    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.models.registry import get_model_config
    from theroundtaible_tpu.engine.scheduler import SessionScheduler

    on_cpu = jax.devices()[0].platform == "cpu"
    model = "tiny-gemma" if on_cpu else "gemma-2b-it"
    max_seq = 1024 if on_cpu else 2048
    k = int(os.environ.get("ROUNDTABLE_BENCH_LORA_K", "3"))
    rounds = 3
    max_new = 32 if on_cpu else 64
    kw = {}
    if on_cpu:
        kw["mesh_shape"] = {"data": 1, "model": 1}
    personas = {f"persona{i}": {"seed": 11 + i, "init_std": 0.5}
                for i in range(k)}
    lora_scale = 4.0
    checkpoint = ""
    lora_dir = os.environ.get("ROUNDTABLE_BENCH_LORA_DIR")
    if lora_dir:
        # TRAINED personas (bench_realweights --train-lora npzs) in
        # place of the random self-contained defaults — fitted at
        # apply scale 1.0 against the REALWEIGHTS tiny-llama
        # checkpoint, so this mode serves that exact base (A/B shapes
        # are model-shaped; a different base would reject them).
        import glob
        npzs = sorted(glob.glob(os.path.join(lora_dir, "*.npz")))[:k]
        ckpt = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            ".cache", "realweights_ckpt")
        if npzs and os.path.exists(os.path.join(ckpt, "config.json")):
            personas = {os.path.splitext(os.path.basename(f_))[0]:
                        {"path": f_} for f_ in npzs}
            k = len(personas)
            lora_scale = 1.0
            model = "tiny-llama"
            max_seq = 512
            checkpoint = ckpt
    names = list(personas)
    cfg = get_model_config(model, max_seq_len=max_seq)
    lora_cfg = {"rank": 8, "max_adapters": k, "scale": lora_scale,
                "adapters": personas}
    probe = ("The roundtable convenes; the knight weighs the proposal "
             "and begins to speak:")

    def turn_prompt(i: int, rnd: int, transcript: str) -> str:
        return (f"{transcript}\nRound {rnd}, knight {i} argues the "
                "proposal on its merits: ")

    def hbm_resident(engines) -> int:
        total = 0
        for e in engines:
            total += e.perf.param_bytes + e.kv.hbm_bytes()
            if getattr(e, "lora", None) is not None:
                total += e.lora.stack_bytes()
        return total

    def probe_divergence(dists: list[np.ndarray]) -> float:
        """Mean pairwise total-variation distance between the knights'
        next-token distributions — 0 = identical models, 1 = disjoint
        support. The measurable persona-diversity claim."""
        tv = []
        for i in range(len(dists)):
            for j in range(i + 1, len(dists)):
                tv.append(0.5 * float(np.abs(dists[i]
                                             - dists[j]).sum()))
        return round(sum(tv) / max(len(tv), 1), 4)

    def lora_probe_dist(eng, adapter) -> np.ndarray:
        """Next-token distribution of the probe prompt under one
        persona (the engine's own forward with the lora scope — the
        exact serving math, eagerly)."""
        from theroundtaible_tpu.engine.lora import lora_scope
        from theroundtaible_tpu.engine.models.common import forward
        toks = jnp.asarray([eng.tokenizer.encode(probe)], jnp.int32)
        pos = jnp.arange(toks.shape[1], dtype=jnp.int32)[None]
        valid = jnp.asarray([toks.shape[1]], jnp.int32)
        last = valid - 1
        slot = 0 if adapter is None else eng.lora.slot_of(adapter)
        ids = jnp.full((1,), slot, jnp.int32)
        with lora_scope((eng.lora.stacked, ids)):
            logits, _ = forward(eng.params, eng.cfg, toks, pos, None,
                                None, valid, last_pos=last)
        p = jax.nn.softmax(logits[0, 0].astype(jnp.float32))
        return np.asarray(p)

    def base_probe_dist(eng) -> np.ndarray:
        from theroundtaible_tpu.engine.models.common import forward
        toks = jnp.asarray([eng.tokenizer.encode(probe)], jnp.int32)
        pos = jnp.arange(toks.shape[1], dtype=jnp.int32)[None]
        valid = jnp.asarray([toks.shape[1]], jnp.int32)
        logits, _ = forward(eng.params, eng.cfg, toks, pos, None, None,
                            valid, last_pos=valid - 1)
        return np.asarray(jax.nn.softmax(
            logits[0, 0].astype(jnp.float32)))

    def run_shared() -> dict:
        eng = InferenceEngine(
            cfg, checkpoint=checkpoint, num_slots=k + 1,
            kv_layout="paged", num_pages=(k + 1) * max_seq // 128,
            lora=lora_cfg, **kw)
        warm_s = eng.warmup(max_prompt_tokens=256, batch_sizes=(1,))
        sched = SessionScheduler(eng, admit_hold_s=0.25)
        results: dict = {}
        errors: list = []
        dec = {"tokens": 0}
        lock = threading.Lock()

        def knight(i):
            transcript = ""
            try:
                for rnd in range(rounds):
                    txts, stats = sched.submit(
                        f"s{i}", [(f"knight{i}",
                                   turn_prompt(i, rnd, transcript))],
                        max_new_tokens=max_new,
                        adapters_per_turn=[names[i]])
                    transcript += f"\nKnight {i}: {txts[0]}"
                    with lock:
                        dec["tokens"] += stats.decode_tokens
                results[i] = transcript
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append((i, e))

        threads = [threading.Thread(target=knight, args=(i,))
                   for i in range(k)]
        t0 = time.monotonic()
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        wall = time.monotonic() - t0
        if errors:
            raise RuntimeError(f"shared-base mode: {errors}")
        # Token parity: round-0 turn re-served ALONE per persona must
        # match what the mixed co-batched run emitted.
        parity = True
        for i in range(k):
            alone = eng.generate_batch(
                [(f"knight{i}", turn_prompt(i, 0, ""))],
                max_new_tokens=max_new, session=f"alone{i}",
                adapters_per_turn=[names[i]])[0]
            if not results[i].startswith(f"\nKnight {i}: {alone}"):
                parity = False
        dists = [lora_probe_dist(eng, names[i]) for i in range(k)]
        sched_d = sched.describe()
        out = {
            "engines": 1,
            "decode_tokens": dec["tokens"],
            "wall_s": round(wall, 2),
            "aggregate_decode_tok_s": round(dec["tokens"]
                                            / max(wall, 1e-9), 1),
            "hbm_resident_bytes": hbm_resident([eng]),
            "weights_bytes": eng.perf.param_bytes,
            "kv_bytes": eng.kv.hbm_bytes(),
            "adapter_stack_bytes": eng.lora.stack_bytes(),
            "divergence_tv": probe_divergence(dists),
            "mixed_vs_alone_parity": parity,
            "warmup_s": round(warm_s, 1),
            "max_occupancy": sched_d["max_occupancy"],
            "lora": eng.lora_describe(),
        }
        sched.close()
        return out, hbm_resident([eng]) - eng.lora.stack_bytes()

    def run_fleet() -> dict:
        engines = [InferenceEngine(
            cfg, checkpoint=checkpoint, num_slots=2, kv_layout="paged",
            num_pages=2 * max_seq // 128, seed=11 + i, **kw)
            for i in range(k)]
        warm_s = sum(e.warmup(max_prompt_tokens=256, batch_sizes=(1,))
                     for e in engines)
        errors: list = []
        dec = {"tokens": 0}
        lock = threading.Lock()

        def knight(i):
            transcript = ""
            try:
                for rnd in range(rounds):
                    txts, stats = engines[i].generate_batch_with_stats(
                        [(f"knight{i}",
                          turn_prompt(i, rnd, transcript))],
                        max_new_tokens=max_new, session=f"f{i}")
                    transcript += f"\nKnight {i}: {txts[0]}"
                    with lock:
                        dec["tokens"] += stats.decode_tokens
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append((i, e))

        threads = [threading.Thread(target=knight, args=(i,))
                   for i in range(k)]
        t0 = time.monotonic()
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        wall = time.monotonic() - t0
        if errors:
            raise RuntimeError(f"fleet mode: {errors}")
        dists = [base_probe_dist(e) for e in engines]
        return {
            "engines": k,
            "decode_tokens": dec["tokens"],
            "wall_s": round(wall, 2),
            "aggregate_decode_tok_s": round(dec["tokens"]
                                            / max(wall, 1e-9), 1),
            "hbm_resident_bytes": hbm_resident(engines),
            "weights_bytes": sum(e.perf.param_bytes for e in engines),
            "kv_bytes": sum(e.kv.hbm_bytes() for e in engines),
            # ONE fleet engine's residency — the honest "single base"
            # denominator for the headline ratios (the shared engine's
            # own bytes include a K-session KV pool, which would
            # inflate the denominator and flatter both ratios).
            "single_base_bytes": hbm_resident(engines[:1]),
            "divergence_tv": probe_divergence(dists),
            "warmup_s": round(warm_s, 1),
        }

    shared, shared_minus_stack = run_shared()
    # Trained-persona mode serves ONE real checkpoint — there is no
    # distinct-seed fleet to honestly compare against, so the A/B leg
    # runs only for the self-contained random-persona default.
    fleet = (run_fleet() if not checkpoint
             else {"skipped": "single trained checkpoint"})
    # Single-base denominator: one FLEET-shaped engine where the A/B
    # leg ran (its KV pool is single-session-sized); the shared
    # engine's own residency minus adapter stacks is the fallback —
    # conservative for the shared ratio (its pool serves K sessions).
    single_base_bytes = fleet.get("single_base_bytes",
                                  shared_minus_stack)
    result_line = {
        "metric": f"multi_lora_personas[{model}][K={k}]",
        "value": shared["aggregate_decode_tok_s"],
        "unit": "aggregate_decode_tok_s_shared_base",
        "detail": {
            "personas": k,
            "rounds": rounds,
            "shared_base_k_adapters": shared,
            "per_checkpoint_fleet": fleet,
            # The acceptance bar: K personas on one base must stay
            # under 1.5x a single base's residency; the fleet pays ~Kx.
            "single_base_bytes": single_base_bytes,
            # The persona-cost axis, KV factored out: serving K
            # personas costs (weights + adapter stacks) / weights of
            # ONE base — the model-size-independent claim (KV pools
            # scale with SESSIONS SERVED on either design, and on a
            # tiny CPU model they dwarf the weights; on a real 2B+
            # model weights dominate and the total ratio converges to
            # this one).
            "weights_ratio_shared_vs_single_base": round(
                (shared["weights_bytes"]
                 + shared["adapter_stack_bytes"])
                / max(shared["weights_bytes"], 1), 3),
            "weights_ratio_fleet_vs_single_base": float(k),
            # The ISSUE 10 acceptance bar, stated against THIS record:
            # on the persona-cost axis it holds here; the total-
            # residency form is weights-dominated only on real chips
            # (this CPU record's pools dwarf the tiny weights), so its
            # on-chip value is the window-3 measurement.
            "acceptance": {
                "criterion": "K-persona resident HBM < 1.5x "
                             "single-base (vs ~Kx per-checkpoint)",
                "weights_axis_ratio": round(
                    (shared["weights_bytes"]
                     + shared["adapter_stack_bytes"])
                    / max(shared["weights_bytes"], 1), 3),
                "meets_on_weights_axis": (
                    shared["weights_bytes"]
                    + shared["adapter_stack_bytes"])
                < 1.5 * shared["weights_bytes"],
                "total_ratio_this_platform": round(
                    shared["hbm_resident_bytes"]
                    / max(single_base_bytes, 1), 3),
                "total_ratio_note": (
                    "KV pools dominate tiny CPU models; on 2B+ "
                    "weights the total converges to the weights "
                    "axis — measured by the window-3 step"),
            },
            "single_base_def": ("one_fleet_engine"
                                if "single_base_bytes" in fleet
                                else "shared_minus_adapter_stacks"),
            "hbm_ratio_shared_vs_single_base": round(
                shared["hbm_resident_bytes"]
                / max(single_base_bytes, 1), 3),
            "hbm_ratio_fleet_vs_single_base": (round(
                fleet["hbm_resident_bytes"]
                / max(single_base_bytes, 1), 3)
                if "hbm_resident_bytes" in fleet else None),
            "hbm_saved_bytes_vs_fleet": (
                fleet["hbm_resident_bytes"]
                - shared["hbm_resident_bytes"]
                if "hbm_resident_bytes" in fleet else None),
            # CPU walls favor the fleet: K tiny engines decode with no
            # scheduler tick/hold overhead, while the shared batch pays
            # per-segment host round-trips that dwarf tiny-model
            # compute (the SPEC_r09 caveat verbatim). The on-chip claim
            # is the HBM column: K personas resident for ~1x one base
            # vs the fleet's ~Kx — the chip count it frees IS the
            # throughput multiplier at fleet scale.
            "cpu_wall_caveat": on_cpu,
            "platform": jax.devices()[0].platform,
            "telemetry": _registry_snapshot(),
        },
    }
    print(json.dumps(result_line), flush=True)
    return 0


def kv_quant_child() -> int:
    """Quantized-KV-page A/B (ISSUE 11 acceptance): the same pool byte
    budget served quant-ON (int8 pages + per-cell scales, in-kernel
    dequant) then quant-OFF (bf16 pages), in ONE record.

    Three measurements per mode, all through the REAL serving path:
    - MAX RESIDENT SESSIONS: admit fixed-shape sessions one at a time
      (offload tier off — no spill valve) until the allocator EVICTS an
      earlier session's pages; the count still fully resident is the
      honest capacity number (the pool refuses by LRU-evicting, not by
      raising). Quantized pools hold page_ratio x the pages in the same
      bytes, so the bar is >= 1.8x at int8.
    - SCHEDULED DECODE tok/s: K concurrent sessions through the
      session scheduler with ROUNDTABLE_RECOMPILE_STRICT=1 armed after
      a warm pass — the record carries the strict-green bit.
    - GREEDY TOKEN PARITY: the probe session's tokens must match
      across modes (the rms-bound acceptance rule's observable).
    """
    from bench_common import install_sigterm_exit

    install_sigterm_exit()
    import threading

    import jax

    if os.environ.get("ROUNDTABLE_BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")

    from theroundtaible_tpu.engine import enable_compilation_cache

    enable_compilation_cache()

    from theroundtaible_tpu.engine import compile_watch
    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.models.registry import get_model_config
    from theroundtaible_tpu.engine.scheduler import SessionScheduler
    from theroundtaible_tpu.utils import perfmodel

    on_cpu = jax.devices()[0].platform == "cpu"
    kvq_dtype = os.environ.get("ROUNDTABLE_BENCH_KVQ_DTYPE", "int8")
    if on_cpu:
        # head_dim=64: tiny-gemma's D=16 pays its per-cell f32 scale on
        # every 16 payload bytes (page ratio 1.6x); D=64 amortizes to
        # 1.88x so the CPU record exercises the same >= 1.8x bar the
        # chip hits at D=256.
        cfg = get_model_config("tiny-gemma", max_seq_len=512,
                               head_dim=64)
        kw = {"mesh_shape": {"data": 1, "model": 1}}
        page_size, num_slots, max_new, k_sched = 32, 32, 24, 3
    else:
        cfg = get_model_config("gemma-2b-it", max_seq_len=2048)
        kw = {}
        page_size, num_slots, max_new, k_sched = 128, 32, 48, 3
    session_prompt = (TOPIC + " The knight surveys the state of the "
                      "store, weighs the proposal on its merits, and "
                      "answers at length about the event log design. ")
    # The SAME pool byte budget on both sides, stated in pages: bf16
    # gets POOL_PAGES, the quantized pool gets page_ratio x as many —
    # byte-for-byte what the engine's default sizing does, pinned
    # explicitly so the A/B denominator can't drift with num_slots
    # (slots are sized to never bind; PAGES are the contended
    # resource, exactly the production refusal mode).
    from theroundtaible_tpu.engine import kv_quant as kvq_mod
    pool_pages = 6 * (cfg.max_seq_len // page_size)
    spec = kvq_mod.resolve_spec(kvq_dtype)[0]
    quant_pages = int(pool_pages * kvq_mod.page_ratio(
        spec, cfg.head_dim)) if spec is not None else pool_pages

    def build(quant):
        # prefix_cache off: the capacity climb must charge every
        # session its own pages — cache aliasing of the shared topic
        # preamble would make "resident sessions" unbounded and the
        # A/B vacuous. kv_offload off: no spill valve under pressure.
        return InferenceEngine(
            cfg, num_slots=num_slots, kv_layout="paged",
            page_size=page_size, kv_offload=False, prefix_cache=False,
            num_pages=(quant_pages if quant else pool_pages),
            kv_quant=(kvq_dtype if quant else None), **kw)

    def max_resident_sessions(eng) -> int:
        """Admit sessions until the allocator evicts one — the count
        still fully resident right before the first eviction."""
        admitted: list[str] = []
        for i in range(4 * num_slots):
            name = f"cap{i}"
            try:
                eng.generate(f"Distinct transcript {i}: "
                             + session_prompt, slot_name=name,
                             max_new_tokens=8)
            except RuntimeError:
                break           # hard exhaustion also ends the climb
            admitted.append(name)
            resident = set(eng.kv.slot_names())
            if any(a not in resident for a in admitted):
                return len(admitted) - 1
        return len(admitted)

    def run_mode(quant: bool) -> dict:
        eng = build(quant)
        warm_s = eng.warmup(max_prompt_tokens=256, batch_sizes=(1,))
        # Capacity climb on the bare engine (no scheduler spill valve).
        resident = max_resident_sessions(eng)
        eng.kv.revive_if_dead()
        for n in list(eng.kv.slot_names()):
            eng.kv.release(n)
        # Scheduled throughput with STRICT armed after a warm pass.
        sched = SessionScheduler(eng)
        errors: list = []
        dec = {"tokens": 0}
        lock = threading.Lock()

        def knight(i, tag):
            try:
                _, stats = sched.submit(
                    f"{tag}{i}", [(f"knight{i}",
                                   session_prompt + f"Knight {i}: ")],
                    max_new_tokens=max_new)
                with lock:
                    dec["tokens"] += stats.decode_tokens
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append((i, repr(e)))

        def round_of(tag):
            threads = [threading.Thread(target=knight, args=(i, tag))
                       for i in range(k_sched)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)

        try:
            round_of("warm")
            compile_watch.install()
            compile_watch.warmup_complete("bench_kvq")
            strict0 = compile_watch.steady_state_compiles()
            os.environ["ROUNDTABLE_RECOMPILE_STRICT"] = "1"
            dec["tokens"] = 0
            t0 = time.monotonic()
            round_of("load")
            wall = time.monotonic() - t0
        finally:
            os.environ.pop("ROUNDTABLE_RECOMPILE_STRICT", None)
            sched.close()
        strict_green = (not errors and
                        compile_watch.steady_state_compiles() == strict0)
        compile_watch.reset_steady_state()
        if errors:
            raise RuntimeError(f"kv_quant bench mode quant={quant}: "
                               f"{errors}")
        # Parity probe: one fresh greedy session, compared across modes.
        probe = eng.generate(session_prompt, slot_name="probe",
                             max_new_tokens=16)
        led = eng.kv.memory_ledger()
        spec = eng.kv_quant_spec
        kv_ctx = cfg.max_seq_len // 2
        roof = perfmodel.roofline_block(
            param_bytes=eng.perf.param_bytes,
            num_params=eng.num_params,
            n_devices=int(eng.mesh.devices.size),
            kv_stream_bytes=kv_ctx * eng.perf.kv_token_bytes,
            kv_dtype=led["kv_dtype"])
        return {
            "kv_dtype": led["kv_dtype"],
            "max_resident_sessions": resident,
            "num_pages": eng.kv.num_pages,
            "decode_tokens": dec["tokens"],
            "wall_s": round(wall, 2),
            "decode_tok_s": round(dec["tokens"] / max(wall, 1e-9), 1),
            "strict_green": strict_green,
            "warmup_s": round(warm_s, 1),
            "ledger": {k: led[k] for k in (
                "kv_dtype", "kv_quant_bits", "kv_bytes_resident",
                "kv_bytes_logical", "kv_quant_bytes_saved",
                "usable_pages", "hbm_bytes")},
            "kv_quant": eng.kv_quant_describe(),
            "kv_bytes_per_token": eng.perf.kv_token_bytes,
            "roofline": roof,
            "group": (spec.effective_group(cfg.head_dim)
                      if spec is not None else None),
            "_probe": probe,
        }

    on = run_mode(True)
    off = run_mode(False)
    parity = on.pop("_probe") == off.pop("_probe")
    ratio = round(on["max_resident_sessions"]
                  / max(off["max_resident_sessions"], 1), 3)
    result_line = {
        "metric": f"kv_quant_pages[{cfg.name}][{kvq_dtype}]",
        "value": ratio,
        "unit": "max_resident_sessions_ratio_quant_vs_bf16",
        "detail": {
            "quant_on": on,
            "quant_off": off,
            "max_resident_sessions_ratio": ratio,
            "greedy_token_parity": parity,
            "strict_green_both_modes": (on["strict_green"]
                                        and off["strict_green"]),
            "decode_ceiling_lift": round(
                on["roofline"]["decode_ceiling_tps"]
                / max(off["roofline"]["decode_ceiling_tps"], 1e-9), 3),
            "acceptance": {
                "criterion": ">= 1.8x max resident sessions at int8 "
                             "vs bf16 on the same pool byte budget, "
                             "greedy parity True, STRICT green",
                "meets": (ratio >= 1.8 and parity
                          and on["strict_green"]
                          and off["strict_green"]),
            },
            "head_dim": cfg.head_dim,
            "page_size": page_size,
            "cpu_wall_caveat": on_cpu,
            "platform": jax.devices()[0].platform,
            "telemetry": _registry_snapshot(),
            "perf": _perf_block(),
        },
    }
    print(json.dumps(result_line), flush=True)
    return 0


def restart_child() -> int:
    """Restart-under-load (ISSUE 12 acceptance): the same K-session
    multi-round scripted load served twice on a paged + host-offload
    engine — fault-free, then with rolling `supervisor.restart()`
    cycles fired mid-run — in ONE record.

    Three claims, all through the REAL serving path (scheduler submit,
    own-slot reuse across rounds, supervisor quiesce → evacuate →
    rebuild → restore):
    - ZERO LOSS: every session completes every round in the restart
      run (sessions_lost == 0, completions match the baseline).
    - RECOVERY WALL: per-restart wall (and p95 across the rolling
      cycles) as reported by the supervisor's restart report.
    - GREEDY TOKEN PARITY: later rounds extend earlier rounds'
      committed KV via own-slot reuse, so the restart run's tokens
      match the fault-free run's exactly IFF the evacuate → restore
      hop was byte-identical.
    """
    from bench_common import install_sigterm_exit

    install_sigterm_exit()
    import statistics
    import threading

    import jax

    if os.environ.get("ROUNDTABLE_BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")

    from theroundtaible_tpu.engine import enable_compilation_cache

    enable_compilation_cache()

    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.scheduler import SessionScheduler
    from theroundtaible_tpu.engine.supervisor import (EngineSupervisor,
                                                      set_supervisor,
                                                      supervisor)

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:
        config = {"model": "tiny-gemma", "max_seq_len": 512,
                  "num_slots": 12, "kv_layout": "paged", "page_size": 32,
                  "kv_offload": True,
                  "mesh": {"data": 1, "model": 1},
                  "sampling": {"temperature": 0.0}}
        max_new, rounds, k = 16, 3, 3
    else:
        config = {"model": "gemma-2b-it", "max_seq_len": 2048,
                  "num_slots": 12, "kv_layout": "paged",
                  "kv_offload": True,
                  "sampling": {"temperature": 0.0}}
        max_new, rounds, k = 48, 3, 3
    n_restarts = int(os.environ.get("ROUNDTABLE_BENCH_RESTART_N", "2"))

    def run_mode(restart: bool) -> dict:
        set_supervisor(EngineSupervisor(max_restarts=n_restarts + 2))
        eng = InferenceEngine.from_config(dict(config))
        sched = SessionScheduler(eng)
        produced: dict = {f"s{i}": [] for i in range(k)}
        errors: dict = {}
        lock = threading.Lock()

        def run_session(i: int) -> None:
            sid = f"s{i}"
            transcript = (TOPIC + f" Knight {i} weighs shard {i} of "
                          "the store against the event log proposal.")
            for _r in range(rounds):
                try:
                    texts, _stats = sched.submit(
                        sid, [(f"knight{i}", transcript)],
                        max_new_tokens=max_new, timeout_s=300.0)
                except Exception as e:  # noqa: BLE001 — counted as loss
                    with lock:
                        errors[sid] = repr(e)
                    return
                with lock:
                    produced[sid].append(texts[0])
                transcript += " " + texts[0]

        threads = [threading.Thread(target=run_session, args=(i,),
                                    daemon=True) for i in range(k)]
        t0 = time.monotonic()
        for th in threads:
            th.start()
        restart_walls: list[float] = []
        if restart:
            for cycle in range(1, n_restarts + 1):
                # Rolling restart AFTER round `cycle` has committed
                # everywhere: the next rounds must reuse KV that
                # crossed the evacuate → restore hop.
                while True:
                    with lock:
                        if errors or all(len(v) >= cycle
                                         for v in produced.values()):
                            break
                    time.sleep(0.02)
                if errors:
                    break
                rep = supervisor().restart(
                    sched.engine, reason=f"bench_rolling_{cycle}",
                    scheduler=sched)
                restart_walls.append(rep["wall_s"])
        for th in threads:
            th.join()
        wall = time.monotonic() - t0
        snap = supervisor().snapshot()
        sched.close()
        set_supervisor(None)
        return {
            "wall_s": round(wall, 2),
            "rounds_completed": {s: len(v) for s, v in produced.items()},
            "sessions_failed": errors,
            "restart_walls_s": restart_walls,
            "supervisor": {kk: snap[kk] for kk in (
                "restarts", "sessions_recovered", "sessions_lost")},
            "_tokens": {s: list(v) for s, v in produced.items()},
        }

    base = run_mode(False)
    rec = run_mode(True)
    parity = base.pop("_tokens") == rec.pop("_tokens")
    walls = rec["restart_walls_s"]
    p95 = (statistics.quantiles(walls, n=20)[-1] if len(walls) > 1
           else (walls[0] if walls else None))
    zero_loss = (not rec["sessions_failed"]
                 and rec["rounds_completed"] == base["rounds_completed"]
                 and rec["supervisor"]["sessions_lost"] == 0)
    result_line = {
        "metric": "engine_restart_under_load",
        "value": p95,
        "unit": "recovery_p95_wall_s",
        "detail": {
            "fault_free": base,
            "restart_run": rec,
            "restarts_fired": len(walls),
            "recovery_p95_wall_s": p95,
            "sessions_recovered": rec["supervisor"]["sessions_recovered"],
            "sessions_lost": rec["supervisor"]["sessions_lost"],
            "greedy_token_parity": parity,
            "acceptance": {
                "criterion": "zero sessions lost across rolling "
                             "restarts under load, greedy token parity "
                             "vs the uninterrupted run",
                "meets": bool(zero_loss and parity),
            },
            "cpu_wall_caveat": on_cpu,
            "platform": jax.devices()[0].platform,
            "telemetry": _registry_snapshot(),
            "perf": _perf_block(),
        },
    }
    print(json.dumps(result_line), flush=True)
    return 0


def main() -> int:
    from bench_common import run_watchdogged
    # The offered-load / prefix-reuse sweeps run many scripted
    # discussions in one child — wider attempt window than the single run.
    attempt_s = (2 * ATTEMPT_TIMEOUT_S
                 if os.environ.get("ROUNDTABLE_BENCH_OFFERED_LOAD")
                 or os.environ.get("ROUNDTABLE_BENCH_PREFIX_REUSE")
                 or os.environ.get("ROUNDTABLE_BENCH_SPEC_DECODE")
                 or os.environ.get("ROUNDTABLE_BENCH_LORA")
                 or os.environ.get("ROUNDTABLE_BENCH_KV_QUANT")
                 or os.environ.get("ROUNDTABLE_BENCH_RESTART")
                 else ATTEMPT_TIMEOUT_S)
    return run_watchdogged(os.path.abspath(__file__), [],
                           attempt_s, MAX_ATTEMPTS, RETRY_DELAY_S)


def _run_child() -> int:
    if os.environ.get("ROUNDTABLE_BENCH_RESTART"):
        return restart_child()
    if os.environ.get("ROUNDTABLE_BENCH_KV_QUANT"):
        return kv_quant_child()
    if os.environ.get("ROUNDTABLE_BENCH_LORA"):
        return lora_child()
    if os.environ.get("ROUNDTABLE_BENCH_SPEC_DECODE"):
        return spec_decode_child()
    if os.environ.get("ROUNDTABLE_BENCH_LATE_JOIN"):
        return late_join_child()
    if os.environ.get("ROUNDTABLE_BENCH_PREFIX_REUSE"):
        return prefix_reuse_child()
    if os.environ.get("ROUNDTABLE_BENCH_OFFERED_LOAD"):
        return offered_load_child()
    return child()


if __name__ == "__main__":
    sys.exit(_run_child() if "--child" in sys.argv else main())
