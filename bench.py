"""Benchmark — decode throughput of the flagship model on real hardware.

Prints JSON lines: one per quant config AS EACH MEASUREMENT LANDS
(bf16 first), then the headline line LAST — every line is a complete
{"metric", "value", "unit", "vs_baseline", "detail"} record, so a child
killed mid-int8 has already emitted a usable bf16 number (round-2
lesson: a half-finished child contributed zero; VERDICT.md weak #1c).

Measures BASELINE.md config 1's engine side (gemma-2b, single chip):
chunked prefill + jit'd while_loop decode through the production
InferenceEngine (persistent KV slot, bucketed shapes). The reference
publishes no numbers (BASELINE.md "published: {}"), so vs_baseline is
computed against A100 Ollama gemma-2b decode ≈ 120 tok/s — the
wall-clock-parity target the driver defines (north star: v5e vs A100
Ollama).

Each run dict also carries a `roofline` block with `decode_ceiling_tps`,
`decode_frac` and `prefill_mfu` (VERDICT.md missing #4): decode
is weight-streaming bound at batch=1, so the ceiling is
HBM_bandwidth / streamed_param_bytes (measured from the actual param
tree, so int8 automatically gets its halved-bytes ceiling); prefill is
compute bound, ceiling = peak bf16 FLOP/s with FLOPs/token ≈ 2·params.
KV-read traffic is excluded (gemma-2b MQA at ≤2k ctx reads ~30 MB/token
vs ~5 GB of weights — <1%).

Cold-start discipline (round-1 lesson: the JSON must land well inside
the driver's capture window):
- persistent XLA compilation cache (engine.enable_compilation_cache);
- minimal warmup: ONLY the programs this bench prompt actually
  dispatches, run twice for the donated-buffer layout fixpoint;
- probe-first watchdog (bench_common): a cheap `jax.devices()` child
  must succeed before the heavy child ever starts, so the watchdog
  never kills a child that holds the chip when a probe would have
  proven the device unreachable anyway.

Driver-channel resilience (VERDICT item 9): when the probe fails (or
every attempt dies recordless), the watchdog re-emits the latest
COMMITTED builder-jsonl headline as an explicitly-marked `cached`
record with commit-hash provenance (bench_common.emit_cached_headlines)
— BENCH_r0N.json is never empty while real numbers exist in the repo,
and a cached number can never masquerade as a fresh one.
"""

from __future__ import annotations

import json
import os
import sys
import time

A100_OLLAMA_GEMMA2B_DECODE_TPS = 120.0  # external anchor, see ANCHOR_PROVENANCE

# VERDICT r4 weak #3: the anchor is an ASSERTED constant, not a
# measurement — every vs_baseline ratio inherits it, so its provenance
# rides along machine-readably in every record. It cannot be measured in
# this environment (zero egress, no A100); the bracket pins it to
# physics: A100-40GB HBM 1555 GB/s over ~2.5 GiB of int8 gemma-2b
# weights gives a ~580 tok/s weight-streaming ceiling, and llama.cpp's
# typical 20-40% of roofline on small models lands 115-230 tok/s; 120 is
# the conservative low edge. Anyone with an A100 reproduces it with the
# command below (Ollama prints "eval rate" per run).
ANCHOR_PROVENANCE = {
    "value": A100_OLLAMA_GEMMA2B_DECODE_TPS,
    "status": "asserted (reference publishes no numbers, BASELINE.md)",
    "reproduce": "ollama run gemma:2b --verbose  # eval rate, A100",
    "bracket_tps": [115, 230],
    "bracket_basis": ("A100-40GB 1555 GB/s / ~2.5 GiB int8 weights "
                      "= ~580 tok/s ceiling x llama.cpp 20-40% typical"),
}

ATTEMPT_TIMEOUT_S = 780.0  # four engines (bf16, int8, int8+paged, int4)
                           # cold; per-run lines flush as they land, so
                           # even a timeout salvages the finished configs
MAX_ATTEMPTS = 2
RETRY_DELAY_S = 20.0

# Roofline constants + ceiling math live in ONE place now (ISSUE 6):
# utils/perfmodel.py. These re-exports keep the historical bench.py
# names alive; the drift test pins them to the shared model.
from theroundtaible_tpu.utils.perfmodel import (V5E_BF16_PEAK_TFLOPS,
                                                V5E_HBM_GBPS)

PROMPT = (
    "You are taking part in a TheRoundtAIble discussion. Topic: should we "
    "refactor the session store before adding the apply pipeline? Consider "
    "the trade-offs carefully and end with a consensus JSON block. " * 8
)


def child() -> int:
    """The actual measurement (runs in a watchdogged subprocess)."""
    from bench_common import install_sigterm_exit

    install_sigterm_exit()
    import jax

    # Local smoke-testing escape hatch: select the cpu backend through
    # jax.config, as tests/conftest.py does, whatever JAX_PLATFORMS says.
    # Must run before anything initializes the backend (incl. the
    # compilation cache, which checks jax.default_backend()).
    if os.environ.get("ROUNDTABLE_BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")

    from theroundtaible_tpu.engine import enable_compilation_cache

    enable_compilation_cache()

    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.models.registry import get_model_config
    from theroundtaible_tpu.engine.sampling import SamplingParams

    devices = jax.devices()
    platform = devices[0].platform
    on_cpu = platform == "cpu"
    if on_cpu:
        cfg = get_model_config("tiny-gemma")
        decode_tokens = 64
    else:
        cfg = get_model_config("gemma-2b-it", max_seq_len=2048)
        decode_tokens = 256

    failed: list[dict] = []  # configs that errored (emit records them)
    base_key = f"decode_tokens_per_sec_per_chip[{cfg.name}]"

    def config_label(quant: str) -> str:
        return "bf16" if quant == "none" else quant

    def emit(run: dict, headline: bool) -> None:
        """Print one complete result record for `run` (flushed).

        Only the headline line carries the STABLE metric key (exactly
        one such line per successful run, so per-key summing / take-
        first / take-last parsers all agree); per-run lines get a
        config-suffixed key and exist so a child killed mid-run has
        already landed complete, unambiguous records for the finished
        configs."""
        decode_tps = run["decode_tps"]
        label = run["label"]
        detail = {
            "headline": headline,
            "runs": runs if headline else [run],
            "devices": len(devices),
            "platform": platform,
        }
        # Registry snapshot in every run record (ISSUE 5, the
        # int4_paths pattern): BENCH_r*.json carries the window's
        # occupancy/fallback/hang/breaker counters with the same commit
        # provenance as the headline number.
        from theroundtaible_tpu.utils import telemetry
        detail["telemetry"] = telemetry.REGISTRY.snapshot_compact()
        if headline:
            detail["winning_config"] = label  # winner of all runs
            detail["anchor_provenance"] = ANCHOR_PROVENANCE
            # Perf-attribution block (ISSUE 6): roofline gauges, compile
            # observatory summary (how many compiles the measured runs
            # actually paid — cache hit vs fresh), memory ledger, span
            # overheads — the window's numbers arrive with their
            # explanation attached.
            from theroundtaible_tpu.utils import perfmodel
            detail["perf"] = perfmodel.attribution_snapshot()
            if failed:
                detail["failed_configs"] = failed
        rec = {
            "metric": base_key if headline else f"{base_key}[{label}]",
            "value": decode_tps,
            "unit": "tokens/s",
            "vs_baseline": round(
                decode_tps / A100_OLLAMA_GEMMA2B_DECODE_TPS, 3),
            "detail": detail,
        }
        print(json.dumps(rec), flush=True)

    def measure(quant: str) -> dict:
        """Build + minimally warm one engine, return its measured run.

        Warmup serves the bench prompt itself on a throwaway slot: this
        compiles exactly the (batch=1, bucket) prefill programs the
        prompt's chunking hits plus the one decode-segment program; the
        second pass reaches the donated-buffer layout fixpoint (see
        InferenceEngine.warmup docstring). Slot released between passes
        so each is an honest full prefill."""
        t_build = time.monotonic()
        engine = InferenceEngine(
            cfg, num_slots=4, quant=quant,
            sampling=SamplingParams(temperature=0.0,
                                    max_new_tokens=decode_tokens))
        build_s = time.monotonic() - t_build
        param_bytes = sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(engine.params))
        t_warm = time.monotonic()
        for _ in range(2):
            engine.kv.release("__bench_warmup")
            engine.generate(PROMPT, slot_name="__bench_warmup",
                            max_new_tokens=decode_tokens)
        engine.kv.release("__bench_warmup")
        warmup_s = time.monotonic() - t_warm
        # Median-of-3 measured runs, each on a freshly released slot (no
        # prefix reuse → honest prefill every repeat). Warmup dominates
        # cold-start cost; the extra two timed runs add only seconds.
        from bench_common import timed_repeats

        def run_once() -> dict:
            engine.kv.release("bench")
            t0 = time.monotonic()
            engine.generate(PROMPT, slot_name="bench",
                            max_new_tokens=decode_tokens)
            wall = time.monotonic() - t0
            s = engine.last_stats
            return {"decode_tps": s.decode_tps,
                    "prefill_tps": s.prefill_tps, "wall_s": wall}

        med, spread, repeats = timed_repeats(run_once)
        s = engine.last_stats
        label = config_label(quant)
        # Path provenance (ISSUE 3): which einsum dispatches compiled to
        # the fused w4a16 kernels vs the XLA dequant fallback — the
        # window's int4 number must be attributable to the kernel, and
        # every decline carries an explicit fallback_reason.
        int4_paths = None
        int4_fallback_dispatches = None
        if quant == "int4":
            rep = engine.int4_path_report()
            if rep is not None:
                # Raw per-(spec, shape) dispatch count — the SAME
                # granularity as the live
                # roundtable_int4_fallback_dispatches gauge, so the
                # bench record and the registry can't disagree (the
                # int4_paths summary below dedupes for readability).
                int4_fallback_dispatches = len(rep["xla_dequant"])
                int4_paths = {
                    "pallas_w4a16": sorted(
                        {e["spec"] for e in rep["pallas_w4a16"]}),
                    "xla_dequant": sorted(
                        {(e["spec"], e.get("fallback_reason", ""))
                         for e in rep["xla_dequant"]}),
                }
        run = {
            "label": label,
            "quant": quant,
            "kv_layout": "paged",
            "decode_tps": round(med["decode_tps"], 2),
            "prefill_tps": round(med["prefill_tps"], 1),
            "prefill_tokens": s.prefill_tokens,
            "decode_tokens": s.decode_tokens,
            "wall_s": round(med["wall_s"], 2),
            "build_s": round(build_s, 1),
            "warmup_s": round(warmup_s, 1),
            "param_bytes": param_bytes,
            "repeats": repeats,
            **({"int4_paths": int4_paths} if int4_paths else {}),
            "spread": {
                "decode_tps": [round(spread["decode_tps"][0], 2),
                               round(spread["decode_tps"][1], 2)],
                "prefill_tps": [round(spread["prefill_tps"][0], 1),
                                round(spread["prefill_tps"][1], 1)],
            },
        }
        if not on_cpu:
            # The roofline block is PRODUCED by the shared perfmodel
            # (ISSUE 6): aggregate ceilings scale with the mesh size,
            # streamed bytes come from the actual quantized tree, and
            # the same math backs the live ceiling gauges — bench
            # records and serving gauges can no longer drift.
            from theroundtaible_tpu.utils import perfmodel
            run["roofline"] = perfmodel.roofline_block(
                param_bytes=param_bytes,
                num_params=engine.num_params,
                n_devices=len(devices),
                decode_tps=run["decode_tps"],
                prefill_tps=run["prefill_tps"],
                int4_fallbacks=int4_fallback_dispatches)
        return run

    # Measure bf16, int8 (the reference's llama.cpp baseline serves
    # quantized weights, so int8 is the apples-to-apples config) and int4
    # (grouped w4a16, engine/quant.py bits=4 — the llama.cpp default
    # precision CLASS, and another ~2× decode ceiling over int8 if the
    # unpack fuses into the matmul operand; its roofline block derives
    # the ceiling from the actual packed bytes either way). Each run's
    # record is printed the moment it lands; the headline (fastest) is
    # printed LAST under the same STABLE metric key (round-over-round
    # comparisons track the key). int4 measures FIRST: it is the config
    # whose number is newest (the shard-aware fused kernels are what the
    # window exists to price), and windows die mid-bench often enough
    # that the least-replaceable measurement must land before the
    # re-measures. Its record carries `int4_paths` so the number is
    # attributable to the kernel path, never a silent XLA fallback.
    runs: list[dict] = []
    for quant in ("int4", "none", "int8"):
        # One config failing (e.g. a TPU-compile surprise in a config
        # whose kernels only ever ran on CPU) must not cost the others
        # their records — and above all must not cost the HEADLINE line,
        # the stable metric key the driver tracks round over round.
        # (bench.py is the only multi-config CHILD; bench_suite already
        # isolates each sub-bench in its own watchdogged child, so this
        # loop does not belong in bench_common.)
        try:
            run = measure(quant)
        except Exception as e:  # noqa: BLE001 — recorded, not hidden
            # Full traceback to stderr: run_watchdogged surfaces its
            # tail, so a hardware-window failure stays diagnosable.
            import traceback
            traceback.print_exc(file=sys.stderr)
            label = config_label(quant)
            failed.append({"quant": quant, "label": label,
                           "error": f"{type(e).__name__}: {e}"[:300]})
            # Complete record under a DISTINCT key: [label][failed] so
            # the forwarder attempt-stamps and dedups it, while a
            # retry's SUCCESS under the clean [label] key still streams
            # through (per-key dedup would suppress it if failures
            # shared the success key).
            print(json.dumps({
                "metric": f"{base_key}[{label}][failed]",
                "value": 0.0, "unit": "tokens/s", "vs_baseline": 0.0,
                "detail": {"failed": True, **failed[-1]},
            }), flush=True)
            continue
        runs.append(run)
        emit(run, headline=False)
    if not runs:
        raise RuntimeError(f"every bench config failed: {failed}")
    emit(max(runs, key=lambda r: r["decode_tps"]), headline=True)
    # Nonzero exit on any per-config failure: the watchdog then retries
    # the whole child, the per-key dedup forwards only records no earlier
    # attempt emitted — i.e. exactly the configs that failed — so a
    # TRANSIENT device error still gets its number. (The attempt-1
    # headline is kept even if a retried config would have won: a stable
    # headline beats a lost one; the per-config records tell the story.)
    return 1 if failed else 0


def main() -> int:
    from bench_common import run_watchdogged
    return run_watchdogged(os.path.abspath(__file__), [],
                           ATTEMPT_TIMEOUT_S, MAX_ATTEMPTS, RETRY_DELAY_S)


if __name__ == "__main__":
    sys.exit(child() if "--child" in sys.argv else main())
