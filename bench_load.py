"""Capacity-frontier bench (ISSUE 19) — emits CAPACITY_r19.json.

Open-loop Poisson sweep through the loadgen harness against an
in-process gateway: >=4 offered-load points ramped to the shed point
(sessions/chip, TTFT p50/p95/p99, accepted tok/s, shed rate per
rate), the perfmodel roofline as the predicted curve with the
measured-vs-predicted gap attributed via span_overheads, one
`device_lost` chaos restart under load (zero lost sessions through
the retry/resume ladder), and the DERIVED admission thresholds that
gateway/admission.py loads via ROUNDTABLE_GATEWAY_CAPACITY_FILE.

    python bench_load.py --smoke     # tiny ~30s sweep, no artifact
    python bench_load.py             # full sweep -> CAPACITY_r19.json
"""

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

os.environ.setdefault("ROUNDTABLE_DISABLE_TPU_DETECT", "1")
os.environ.setdefault("ROUNDTABLE_PERF_CHIP", "v5e")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
_cache = os.path.join(REPO, ".pytest_xla_cache")
if not os.environ.get("JAX_COMPILATION_CACHE_DIR") and os.path.isdir(_cache):
    jax.config.update("jax_compilation_cache_dir", _cache)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny 4-point sweep, no chaos, no artifact")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "diurnal", "mmpp"])
    ap.add_argument("--duration", type=float, default=None,
                    help="seconds per sweep point")
    ap.add_argument("--rates", default=None,
                    help="comma-separated offered rates "
                         "(default: geometric ramp)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from theroundtaible_tpu.loadgen.bench import run_capacity

    t0 = time.monotonic()
    rates = ([float(r) for r in args.rates.split(",")]
             if args.rates else None)
    record = run_capacity(
        smoke=args.smoke, seed=args.seed, arrival=args.arrival,
        rates=rates, duration_s=args.duration,
        log=lambda m: print(m, file=sys.stderr))

    if not args.smoke:
        lint = subprocess.run(
            [sys.executable, "-m", "theroundtaible_tpu", "lint"],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True)
        record["detail"]["lint_exit"] = lint.returncode
        record["detail"]["acceptance"]["meets"] = (
            record["detail"]["acceptance"]["meets"]
            and lint.returncode == 0)
    record["detail"]["wall_s"] = round(time.monotonic() - t0, 1)

    meets = record["detail"]["acceptance"]["meets"]
    print(json.dumps(record, indent=1))
    if args.smoke:
        return 0 if meets else 1
    out = args.out or os.path.join(REPO, "CAPACITY_r19.json")
    with open(out, "w", encoding="utf-8") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    print(f"wrote {out}", file=sys.stderr)
    return 0 if meets else 1


if __name__ == "__main__":
    sys.exit(main())
