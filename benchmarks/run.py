#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process holds the chip: it builds the cell's configuration through
the program's normal path (scratch project → load_config → scheduler →
gateway on a loopback port), warms up on a short stretch of the cell's
own traffic, measures for `--seconds`, then checks the answers. The load
comes from a child process that never imports JAX
(`harness/loadgen.py`). Everything that belongs to one cell is found by
name: the configuration's file (`BENCHMARK.json`), the traffic mix
(`<path>/traffic/<traffic>.json`), the mix's kind
(`<path>/traffic/kinds/<kind>.py`), one reader per per-layer metric
(`<path>/layer_metrics/<metric>.py`).

Every line of standard output is one JSON object; the last one is the
contract's result. A failure is an uncaught exception or SystemExit:
nonzero exit, no result line. The platform is never forced: a cell whose
configuration does not say `"platform": "cpu"` runs on a TPU or not at
all.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Optional

T_PROCESS = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from harness import correct, endtoend, manifest as mf, server, \
    tracered  # noqa: E402

TRACE_SLICE_S = 6.0              # how much of a traced window is traced
TRACE_SLICE_AT = 0.35            # where in the window the slice starts
CHECK_REQUESTS = 4
CHECK_MAX_PROMPT = 1536          # what the reference holds beside the engine
CHECK_DECODE_TOKENS = 8


def emit(phase: str, **fields: Any) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def device_phase(config: dict, chips: int) -> dict[str, Any]:
    """The device as JAX reports it, and its row of the peaks table.
    Fails on the wrong platform, on too few chips, on an unknown kind."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    want = config.get("platform", "tpu")
    if device["platform"] != want:
        raise SystemExit(
            f"benchmark: this cell runs on {want!r}, JAX found "
            f"{device['platform']!r} — no result")
    if device["count"] < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), "
                         f"JAX reports {device['count']}")
    if want == "cpu":
        # A rehearsal cell (tests): counts only, no device metric, so
        # no peaks to hold it against.
        return {"device": device, "peaks": {}}
    peaks = load_json(os.path.join(HERE, "peaks.json"))["device_kinds"]
    if device["kind"] not in peaks:
        raise SystemExit(
            f"benchmark: device kind {device['kind']!r} is not in "
            "benchmarks/peaks.json — a device without peaks is an "
            "error, not a default")
    return {"device": device, "peaks": peaks[device["kind"]]}


LIVE_CHILDREN: list[subprocess.Popen] = []


def stop_children() -> None:
    """End every load generator still running, and wait for it: the
    benchmark leaves no process behind, however it ends."""
    while LIVE_CHILDREN:
        proc = LIVE_CHILDREN.pop()
        if proc.poll() is None:
            proc.kill()
        proc.wait()


def run_child(plan: dict, workdir: str, tag: str,
              timeout_s: float) -> dict:
    """One run of the load generator, to its end."""
    plan_path = os.path.join(workdir, f"{tag}.plan.json")
    out_path = os.path.join(workdir, f"{tag}.out.json")
    with open(plan_path, "w", encoding="utf-8") as f:
        json.dump(plan, f)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_"))}
    proc = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "harness", "loadgen.py"),
         plan_path, out_path], env=env, stdout=subprocess.DEVNULL)
    LIVE_CHILDREN.append(proc)
    try:
        rc = proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        raise RuntimeError(f"load generator ({tag}) did not end in "
                           f"{timeout_s:.0f} s")
    finally:
        stop_children()
    if rc != 0:
        raise RuntimeError(f"load generator ({tag}) exited {rc}")
    return load_json(out_path)


class PoolSampler(threading.Thread):
    """Peak pages in use over the window: the pool keeps no peak of its
    own, so it is read every 50 ms."""

    def __init__(self, kv) -> None:
        super().__init__(name="pool-sampler", daemon=True)
        self.kv, self.peak, self._halt = kv, 0, threading.Event()

    def run(self) -> None:
        while not self._halt.wait(0.05):
            self.peak = max(self.peak, self.kv.pages_in_use())

    def stop(self) -> int:
        self._halt.set()
        self.join(2.0)
        return self.peak


def sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.25))


def wait_for_boundary(sched, timeout_s: float = 3.0) -> None:
    """Return just after the scheduler finished a segment, so that what
    the counters say and what the trace holds start at the same place."""
    seen = sched.segments + sched.ragged_segments + sched.spec_segments
    bound = time.monotonic() + timeout_s
    while time.monotonic() < bound:
        now = sched.segments + sched.ragged_segments + sched.spec_segments
        if now != seen:
            return
        time.sleep(0.001)


def traced_slice(sched, gw, start: float, seconds: float,
                 trace_dir: str) -> dict[str, Any]:
    """Trace TRACE_SLICE_S seconds of the window; → the slice's own
    counters, read at the two ends of a `bench:slice` span that goes
    into the trace: the reduction clips the device's events to that
    span, so counters and device time cover the same stretch although
    the profiler records for seconds longer (`stop_trace` is slow)."""
    import jax
    from theroundtaible_tpu.utils import telemetry

    length = min(TRACE_SLICE_S, seconds / 2)
    sleep_until(start + seconds * TRACE_SLICE_AT)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    telemetry.arm()
    telemetry.set_profiling(True)
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    wait_for_boundary(sched)
    with jax.profiler.TraceAnnotation(tracered.SLICE_SPAN):
        t_a = time.monotonic()
        before = server.counters(sched, gw)
        sleep_until(t_a + length)
        wait_for_boundary(sched)
        after = server.counters(sched, gw)
        t_b = time.monotonic()
    jax.profiler.stop_trace()
    telemetry.set_profiling(False)
    telemetry.disarm()
    return {"start": t_a, "end": t_b, "seconds": t_b - t_a,
            "stop_trace_s": time.monotonic() - t_b,
            "counters_start": before, "counters_end": after}


def check_plan(rows: list[dict], port: int, seed: int) -> dict:
    """Four of the window's requests again, greedy, for their first
    token, and the first of them decoded for eight."""
    seen, picked = set(), []
    for r in sorted(rows, key=lambda r: (r["index"], r["round"],
                                         r["knight"])):
        key = (r["index"], r["round"], r["knight"])
        if (r["measured"] and r["ok"] and key not in seen
                and r.get("prompt")):
            seen.add(key)
            picked.append(r)
    step = max(len(picked) // CHECK_REQUESTS, 1)
    picked = picked[::step][:CHECK_REQUESTS]
    requests = [{"knight": r["knight"], "prompt": r["prompt"],
                 "max_new_tokens": 1} for r in picked]
    if picked:
        requests.append({"knight": picked[0]["knight"],
                         "prompt": picked[0]["prompt"],
                         "max_new_tokens": CHECK_DECODE_TOKENS})
    return {"mode": "check", "port": port, "seed": seed,
            "requests": requests}


def load_reader(path: str):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + os.path.basename(path)[:-3].replace(".", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest",
                    default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="another manifest (tests add cells by files)")
    args = ap.parse_args(argv)

    manifest = load_json(args.manifest)
    faults = mf.problems(manifest, ROOT)
    if faults:
        raise SystemExit("benchmark: BENCHMARK.json breaks the "
                         "contract: " + "; ".join(faults))
    cell = mf.cell(manifest, args.workload)
    config_file = os.path.join(ROOT, cell["config"]["file"])
    config = load_json(config_file)
    mix = load_json(mf.traffic_file(manifest, ROOT,
                                    cell["workload"]["traffic"]))

    # What the program writes goes inside the checkout (or where the
    # environment already points it): flight dumps, retained traces,
    # the compile cache (the engine's own rule: JAX_COMPILATION_CACHE_DIR
    # where set, else <checkout>/.xla_cache).
    out_dir = os.path.join(ROOT, ".bench_out")
    os.makedirs(out_dir, exist_ok=True)
    os.environ["ROUNDTABLE_TELEMETRY_DIR"] = os.path.join(out_dir,
                                                          "telemetry")
    # Request traces are read from the in-process ring, not from disk.
    os.environ["ROUNDTABLE_TRACE_SAMPLE"] = "0"
    os.environ["ROUNDTABLE_TRACE_KEEP"] = "200000"

    # Before a word goes out: without the program there is no result.
    from theroundtaible_tpu.engine import compile_watch
    from theroundtaible_tpu.utils import tracing

    found = device_phase(config, cell["workload"]["chips"])
    device, on_chip = found["device"], found["device"]["platform"] == "tpu"
    emit("device", **device, peaks=found["peaks"])

    phases: dict[str, float] = {}
    lap = time.monotonic()

    def mark(name: str) -> None:
        nonlocal lap
        now = time.monotonic()
        phases[name], lap = now - lap, now

    with tempfile.TemporaryDirectory(prefix="bench-") as work:
        sched, gw = server.build(os.path.join(work, "project"), config,
                                 args.seed)
        engine = sched.engine
        mark("build_s")
        emit("build", model=engine.cfg.name, params=engine.num_params,
             layers=engine.cfg.num_layers, quant=engine.quant,
             num_pages=engine.kv.num_pages, num_slots=engine.kv.num_slots,
             pool_bytes=engine.kv.hbm_bytes(), seconds=phases["build_s"])
        try:
            # One child for warm-up and window, an unbroken stretch of
            # the mix: its kind opens the window when its own warm-up
            # is done (the batch never runs empty in between).
            window_plan = {
                "mode": "window", "port": gw.port, "seed": args.seed,
                "traffic": mix, "seconds": args.seconds,
                "kind_file": mf.kind_file(manifest, ROOT, mix["kind"]),
                "ramp_s": float(mix.get("ramp_s", 0.0)),
                "drain_s": float(mix["drain_s"]),
                "deadline_s": args.seconds + float(mix["drain_s"]),
                "keep_prompts": 3 * CHECK_REQUESTS,
                "keep_prompt_max": CHECK_MAX_PROMPT}
            results: dict[str, Any] = {}
            failure: list[BaseException] = []

            def drive() -> None:
                try:
                    results.update(run_child(
                        window_plan, work, "window", 1600.0
                        + args.seconds + float(mix["drain_s"])))
                except BaseException as e:  # noqa: BLE001 — re-raised
                    failure.append(e)

            child = threading.Thread(target=drive, name="loadgen-wait")
            child.start()
            start_file = os.path.join(work, "window.out.json.start")
            while not os.path.exists(start_file):
                if failure or not child.is_alive():
                    raise RuntimeError(
                        f"the load generator ended before the window "
                        f"opened: {failure}")
                time.sleep(0.05)
            start = float(load_json(start_file)["start_at"])
            end = start + args.seconds
            sched.declare_warmup_complete()
            mark("warmup_s")
            emit("warmup", programs=compile_watch.compiles_seen(),
                 compile=compile_watch.summary(),
                 admitted=gw.describe()["admitted"],
                 seconds=phases["warmup_s"])
            sleep_until(start)
            setup_s = time.monotonic() - T_PROCESS
            phases["ramp_s"] = time.monotonic() - lap
            tracing.store().reset()
            sampler = PoolSampler(engine.kv)
            sampler.start()
            at_start = server.counters(sched, gw)
            sliced = None
            trace_dir = os.path.join(out_dir, "trace")
            if args.trace and on_chip:
                sliced = traced_slice(sched, gw, start, args.seconds,
                                      trace_dir)
            sleep_until(end)
            at_end = server.counters(sched, gw)
            pool_peak = sampler.stop()
            child.join()
            if failure or not results:
                raise RuntimeError(
                    f"the load generator gave no result: {failure}")
            request_traces = tracing.store().recent(200000)
            # Every row as the client saw it, for whoever asks why a
            # tail moved: the last run's, inside the checkout.
            with open(os.path.join(out_dir, "rows.json"), "w",
                      encoding="utf-8") as f:
                json.dump(results["rows"], f)
            n_new = at_end["compiles"] - at_start["compiles"]
            if n_new:
                # Which programs compiled inside the window: the
                # warm-up has to meet them next time.
                emit("compiled_in_window", entries=[
                    {k: e.get(k) for k in ("label", "dur_s", "cache_hit",
                                           "batch", "bucket", "shape")}
                    for e in compile_watch.history()[-n_new:]][:24])

            window = endtoend.reduce_window(
                results["rows"], start, end,
                end + float(mix["drain_s"]))
            emit("window", seconds=args.seconds, **{
                k: window[k] for k in ("attempted", "failed", "samples",
                                       "window_tokens", "errors")},
                 end_to_end=window["values"],
                 scheduler={k: v if k in ("queued_peak", "max_occupancy")
                            else v - at_start["scheduler"][k]
                            for k, v in at_end["scheduler"].items()},
                 spec_decode=at_end["spec_decode"],
                 gateway=at_end["gateway"],
                 pool=dict(at_end["pool"], peak_in_use=pool_peak))

            info = engine.describe()
            emit("program", events=sched.describe()["events"][-16:],
                 kv_offload=info.get("kv_offload"),
                 prefix_cache=info.get("prefix_cache"),
                 ragged=info.get("ragged"))
            # Outside the window: the answers, and the degraded paths.
            asked = check_plan(results["rows"], gw.port, args.seed)
            checked = run_child(asked, work, "check", 1600.0)
            served = [{"what": f"first-token-{i}", "prompt": q["prompt"],
                       "ids": r["ids"]} for i, (q, r) in enumerate(
                           zip(asked["requests"], checked["rows"]))]
            if served:
                served[-1]["what"] = "decode-through-cache"
            reference = correct.load_reference(config_file, config)
            verdict = correct.score(reference, engine.params, config,
                                    served) if served else {
                "correct": False, "requests": [],
                "reason": "no request short enough to check"}
            emit("right_answers", **verdict)
            degraded = server.degraded_paths(sched, on_chip)
            emit("degraded_paths", problems=degraded)
        finally:
            stop_children()
            gw.stop()
            sched.close()

    import jax
    peak_bytes = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in jax.devices()), default=0)
    emit("setup", setup_s=setup_s, **phases)

    wanted = cell["per_layer"] if args.trace else cell["end_to_end"]
    metrics: dict[str, dict] = {}
    breakdown = None
    if args.trace:
        trace = {}
        if sliced is not None:
            path = tracered.find_xplane(trace_dir)
            if path is None:
                raise RuntimeError("the profiler wrote no trace")
            trace = tracered.reduce(tracered.load_xplane(path))
            if abs(trace.get("window_s", 0.0) - sliced["seconds"]) \
                    > 0.05 * sliced["seconds"]:
                raise RuntimeError(
                    "the trace does not hold the slice's span: "
                    f"{trace.get('window_s')} s on its clock against "
                    f"{sliced['seconds']} s on the host's")
            emit("trace", slice_s=sliced["seconds"],
                 window_s=trace.get("window_s"),
                 stop_trace_s=sliced["stop_trace_s"],
                 busy_s=trace.get("busy_s"),
                 modules=trace.get("module_seconds"))
        ctx = {"cell": cell["workload"], "config": config, "traffic": mix,
               "peaks": found["peaks"], "seconds": args.seconds,
               "window": {"start": start, "end": end},
               "counters": {"start": at_start, "end": at_end},
               "slice": sliced, "trace": trace,
               "request_traces": request_traces,
               "rows": results["rows"], "pool_peak_in_use": pool_peak,
               "names": load_json(os.path.join(
                   HERE, "layer_metrics", "names.json"))}
        for m in wanted:
            value = load_reader(mf.reader_file(manifest, ROOT,
                                               m["name"]))(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if trace:
            device = dict(device, busy_s=trace["busy_s"],
                          window_s=trace["window_s"])
            breakdown = {"device_ops": trace["device_ops"],
                         "idle_gaps": trace["idle_gaps"]}
    else:
        values = dict(window["values"], setup_s=setup_s)
        for m in wanted:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        missing = [m["name"] for m in wanted if m["name"] not in metrics]
        if missing and on_chip:
            raise RuntimeError(
                f"the window supports no value for {missing}: "
                f"{window['samples']} samples — the run is too short")

    result = {"correct": bool(verdict["correct"] and not degraded),
              "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics,
              "device": dict(device, memory_peak_bytes=peak_bytes)}
    if breakdown is not None:
        result["breakdown"] = breakdown
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
