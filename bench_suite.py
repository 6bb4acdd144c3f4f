"""Benchmark suite — BASELINE.md measured configs 3, 4 and 5.

Prints ONE JSON line per requested config (bench.py covers config 1,
bench_discuss.py covers config 2):

  python bench_suite.py fleet    # 3: heterogeneous 3-model round
  python bench_suite.py summon   # 4: long-context prefill (2k-line diff)
  python bench_suite.py apply    # 5: lead-knight long decode
  python bench_suite.py all      # one JSON line each

On the real chip the models are the flagship sizes; under
ROUNDTABLE_BENCH_CPU=1 the tiny trio keeps it a smoke test. Same
child-process watchdog as bench.py (one process per chip: a second
one hangs rather than erroring while the first holds it).

The reference publishes no numbers for any of these (BASELINE.md
"published: {}"); vs_baseline anchors:
- fleet: 3 serial Ollama turns at ~120 tok/s decode, 160 tok each ≈ 4 s
  of decode per round — our 3 submeshes run the round concurrently.
- summon: llama.cpp prefill on A100 ≈ 3000 tok/s for 7B-class models.
- apply: the same 120 tok/s decode anchor as config 1.
"""

from __future__ import annotations

import json
import os
import sys
import time

ATTEMPT_TIMEOUT_S = 420.0
MAX_ATTEMPTS = 2
RETRY_DELAY_S = 20.0

FLEET_ROUND_ANCHOR_S = 4.0
SUMMON_PREFILL_ANCHOR_TPS = 3000.0
APPLY_DECODE_ANCHOR_TPS = 120.0


def _setup():
    import jax

    if os.environ.get("ROUNDTABLE_BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
    from theroundtaible_tpu.engine import enable_compilation_cache
    enable_compilation_cache()
    on_cpu = jax.devices()[0].platform == "cpu"
    return jax, on_cpu


def bench_fleet() -> dict:
    """Config 3: three different models resident at once, one round
    dispatched concurrently to all three submeshes."""
    jax, on_cpu = _setup()
    from concurrent.futures import ThreadPoolExecutor

    from theroundtaible_tpu.engine import get_engine, reset_engines
    from theroundtaible_tpu.engine.fleet import plan_fleet

    # Real-chip trio sized to FIT one v5e-1: three distinct models, all
    # int8, ~8.2 GiB estimated resident (fleet.estimate_engine_hbm_bytes)
    # vs the ~12 GiB plannable budget — plan_fleet's HBM check validates
    # this at plan time instead of OOMing mid-serve (VERDICT r2 weak #3;
    # a mistral-7b + gemma-2b + llama-1b trio at ~13 GiB estimated did
    # OOM at concurrent prefill, which set _HBM_UTILIZATION). The full
    # 3-family 7B-class trio is the v5e-8 configuration, where each
    # model gets a disjoint submesh. On one chip the submeshes share
    # device 0 (time-multiplexed residency); largest builds first while
    # the chip is emptiest (quantization peaks above resident size).
    models = (["tiny-gemma", "tiny-llama", "tiny-mistral"] if on_cpu
              else ["llama-3.2-3b-instruct", "gemma-2b-it",
                    "llama-3.2-1b-instruct"])
    max_new = 32 if on_cpu else 160
    configs = [{"model": m, "max_seq_len": 512 if on_cpu else 2048,
                "num_slots": 2,
                **({} if on_cpu else {"quant": "int8"}),
                "sampling": {"temperature": 0.0,
                             "max_new_tokens": max_new}}
               for m in models]
    reset_engines()
    plan_fleet(configs, n_devices=len(jax.devices()))
    engines = [get_engine(c) for c in configs]
    prompt = ("You are a knight at the roundtable. Topic: should the "
              "session store become an event log? Answer briefly. " * 4)

    def turn(engine_i):
        i, engine = engine_i
        return engine.generate(prompt, slot_name=f"knight-{i}",
                               max_new_tokens=max_new)

    # Warm each engine TWICE (bench.py's discipline): the first pass
    # compiles, but its donated KV buffers come back in XLA's preferred
    # layout so the next dispatch would recompile; the second pass
    # reaches the layout fixpoint. One warm pass here measured 26s for a
    # 2s round — all recompiles.
    from bench_common import timed_repeats
    with ThreadPoolExecutor(max_workers=3) as pool:
        for _ in range(2):
            for i, e in enumerate(engines):
                e.kv.release(f"knight-{i}")
            list(pool.map(turn, enumerate(engines)))

        def run_once() -> dict:
            for i, e in enumerate(engines):
                e.kv.release(f"knight-{i}")
            t0 = time.monotonic()
            outs = list(pool.map(turn, enumerate(engines)))
            assert len(outs) == 3
            return {"wall_s": time.monotonic() - t0}

        med, spread, repeats = timed_repeats(run_once)
    wall = med["wall_s"]
    decode_tokens = sum(e.last_stats.decode_tokens for e in engines)
    return {
        "metric": "fleet_round_wall_clock_3models",
        "value": round(wall, 3),
        "unit": "seconds",
        "vs_baseline": round(FLEET_ROUND_ANCHOR_S / max(wall, 1e-9), 3),
        "detail": {
            "models": models,
            "submeshes": [c.get("devices") for c in configs],
            "decode_tokens": decode_tokens,
            "repeats": repeats,
            "spread": {"wall_s": [round(spread["wall_s"][0], 3),
                                  round(spread["wall_s"][1], 3)]},
            "platform": jax.devices()[0].platform,
        },
    }


def bench_summon() -> dict:
    """Config 4: long-context prefill on a git diff sized to FILL the
    engine's context budget (the reference truncates any diff to 3000
    chars, orchestrator.ts:406; we serve the whole window)."""
    jax, on_cpu = _setup()
    from theroundtaible_tpu.engine import get_engine, reset_engines

    reset_engines()
    cfg = {"model": "tiny-gemma" if on_cpu else "gemma-2b-it",
           "max_seq_len": 4096 if on_cpu else 8192, "num_slots": 2,
           "sampling": {"temperature": 0.0, "max_new_tokens": 32}}
    engine = get_engine(cfg)
    # Build the diff to the REAL prompt budget (max_seq minus the padded
    # decode reserve) so nothing is silently head-truncated and the
    # reported tokens are the tokens actually served.
    budget_tokens = engine.max_seq_len - 64 - 1
    budget_chars = int(budget_tokens * engine.chars_per_token() * 0.95)
    lines, total = [], 0
    i = 0
    while total < budget_chars:
        line = f"+    line_{i} = compute_{i % 7}(state, {i})  # changed"
        lines.append(line)
        total += len(line) + 1
        i += 1
    prompt = ("Review this diff:\n" + "\n".join(lines))[:budget_chars]
    # Warm on the FULL prompt (compiles the exact buckets the measured
    # run hits — bench.py's minimal-warmup discipline), then measure on
    # a fresh slot.
    from bench_common import timed_repeats
    for _ in range(2):
        engine.kv.release("warm")
        engine.generate(prompt, slot_name="warm", max_new_tokens=8)

    # Without this release the resident warm slot donates its prefix
    # (share_prefixes) and the "measured" prefill is one token.
    engine.kv.release("warm")

    def run_once() -> dict:
        engine.kv.release("summon")
        t0 = time.monotonic()
        engine.generate(prompt, slot_name="summon", max_new_tokens=32)
        return {"prefill_tps": engine.last_stats.prefill_tps,
                "wall_s": time.monotonic() - t0}

    med, spread, repeats = timed_repeats(run_once)
    s = engine.last_stats
    prefill_tps = med["prefill_tps"]
    return {
        "metric": "summon_long_prefill_tokens_per_sec",
        "value": round(prefill_tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(prefill_tps / SUMMON_PREFILL_ANCHOR_TPS, 3),
        "detail": {
            "prefill_tokens": s.prefill_tokens,
            "diff_lines": len(lines),
            "wall_s": round(med["wall_s"], 2),
            "repeats": repeats,
            "spread": {"prefill_tps": [round(spread["prefill_tps"][0], 1),
                                       round(spread["prefill_tps"][1], 1)]},
            "platform": jax.devices()[0].platform,
        },
    }


def bench_apply() -> dict:
    """Config 5: lead-knight long decode (code generation)."""
    jax, on_cpu = _setup()
    from theroundtaible_tpu.engine import get_engine, reset_engines

    max_new = 128 if on_cpu else 1024
    reset_engines()
    cfg = {"model": "tiny-gemma" if on_cpu else "gemma-2b-it",
           "max_seq_len": 1024 if on_cpu else 4096, "num_slots": 2,
           "quant": "none" if on_cpu else "int8",
           "sampling": {"temperature": 0.0, "max_new_tokens": max_new}}
    engine = get_engine(cfg)
    prompt = ("Consensus decision: rewrite the session store as an "
              "append-only event log. Emit the full RTDIFF/1 patch for "
              "every file in scope. " * 4)
    from bench_common import timed_repeats
    for _ in range(2):
        engine.kv.release("warm")
        engine.generate(prompt, slot_name="warm", max_new_tokens=max_new)

    def run_once() -> dict:
        engine.kv.release("apply")
        t0 = time.monotonic()
        engine.generate(prompt, slot_name="apply", max_new_tokens=max_new)
        return {"decode_tps": engine.last_stats.decode_tps,
                "wall_s": time.monotonic() - t0}

    med, spread, repeats = timed_repeats(run_once)
    s = engine.last_stats
    decode_tps = med["decode_tps"]
    return {
        "metric": "apply_long_decode_tokens_per_sec",
        "value": round(decode_tps, 2),
        "unit": "tokens/s",
        "vs_baseline": round(decode_tps / APPLY_DECODE_ANCHOR_TPS, 3),
        "detail": {
            "decode_tokens": s.decode_tokens,
            "wall_s": round(med["wall_s"], 2),
            "repeats": repeats,
            "spread": {"decode_tps": [round(spread["decode_tps"][0], 2),
                                      round(spread["decode_tps"][1], 2)]},
            "quant": cfg["quant"],
            "platform": jax.devices()[0].platform,
        },
    }


BENCHES = {"fleet": bench_fleet, "summon": bench_summon,
           "apply": bench_apply}


def child(which: str) -> int:
    # NOT install_sigterm_exit: the fleet bench runs engine.generate on
    # ThreadPoolExecutor workers, and a SystemExit in the main thread
    # would block interpreter shutdown on joining workers stuck in JAX
    # C++ until the watchdog's grace expires into SIGKILL. Flush what
    # we have and exit promptly instead — process death closes the
    # relay socket, which is the claim-release path that matters.
    import signal

    def _term(*_):
        sys.stdout.flush()
        os._exit(1)

    signal.signal(signal.SIGTERM, _term)
    for name in (list(BENCHES) if which == "all" else [which]):
        # flush=True: the watchdog salvages a timeout-killed child's
        # stdout, which only works if the line left this buffer.
        print(json.dumps(BENCHES[name]()), flush=True)
    return 0


def main(which: str) -> int:
    """One watchdogged child PER bench (a single `all` child would stack
    5+ engine builds — two of them 7B-class — into one timeout window)."""
    from bench_common import run_watchdogged

    names = list(BENCHES) if which == "all" else [which]
    worst = 0
    for name in names:
        worst = max(worst, run_watchdogged(
            os.path.abspath(__file__), [name], ATTEMPT_TIMEOUT_S,
            MAX_ATTEMPTS, RETRY_DELAY_S))
    return worst


if __name__ == "__main__":
    which = next((a for a in sys.argv[1:] if not a.startswith("-")), "all")
    if which not in list(BENCHES) + ["all"]:
        print(f"usage: bench_suite.py [{'|'.join(BENCHES)}|all]",
              file=sys.stderr)
        sys.exit(2)
    sys.exit(child(which) if "--child" in sys.argv else main(which))
