"""Subprocess entry for the gateway chaos tests (NOT a test module).

Boots a tiny-gemma engine + SessionScheduler + Gateway on an ephemeral
port, prints `PORT=<n>` once the socket listens, and serves until
killed. `--resume DIR` replays DIR's session journal through the
library seam (engine/recovery.py) before the socket opens — the
kill -9 acceptance restarts this script with it and expects every
client's Last-Event-ID reconnect to see the identical greedy stream.
"""

import argparse
import os
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("ROUNDTABLE_DISABLE_TPU_DETECT", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
_cache = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".pytest_xla_cache")
if not os.environ.get("JAX_COMPILATION_CACHE_DIR") and os.path.isdir(_cache):
    jax.config.update("jax_compilation_cache_dir", _cache)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--journal", required=True)
    ap.add_argument("--resume", default=None)
    ap.add_argument("--max-seq-len", type=int, default=512)
    ap.add_argument("--replicas", type=int, default=None,
                    help="build a router fleet (explicit --replicas 1 "
                         "serves the N=1 router path; default: plain "
                         "single-scheduler gateway)")
    args = ap.parse_args()

    from theroundtaible_tpu.engine.engine import InferenceEngine
    from theroundtaible_tpu.engine.models.registry import get_model_config
    from theroundtaible_tpu.engine.scheduler import SessionScheduler
    from theroundtaible_tpu.engine.session_journal import SessionJournal
    from theroundtaible_tpu.gateway import Gateway

    router = None
    if args.replicas is not None:
        # Multi-replica fleet (ISSUE 17): paged KV + host offload so
        # sessions can migrate between replicas; replica 0 wraps the
        # seed engine, the rest clone from its rebuild recipe.
        from theroundtaible_tpu.router import (SessionRouter,
                                               build_replicas,
                                               set_active_router)
        engine = InferenceEngine.from_config({
            "model": "tiny-gemma", "max_seq_len": args.max_seq_len,
            "num_slots": 8, "kv_layout": "paged", "page_size": 16,
            "kv_offload": True, "mesh": {"data": 1, "model": 1}})
        journal = SessionJournal(args.journal)
        reps = build_replicas(engine, args.replicas, journal=journal)
        router = SessionRouter(reps, journal=journal)
        set_active_router(router)
        sched = reps[0].scheduler
    else:
        cfg = get_model_config("tiny-gemma",
                               max_seq_len=args.max_seq_len)
        engine = InferenceEngine(cfg, num_slots=8)
        sched = SessionScheduler(engine,
                                 journal=SessionJournal(args.journal))
    if args.resume:
        from theroundtaible_tpu.engine.recovery import resume_from_journal
        r = resume_from_journal(args.resume, scheduler=sched)
        print(f"RESUMED sessions={r['sessions']} turns={r['turns']}",
              flush=True)

    gw = Gateway(sched, port=0, intent_dir=args.journal,
                 router=router)
    port = gw.start_in_thread()
    print(f"PORT={port}", flush=True)
    threading.Event().wait()  # serve until killed
    return 0


if __name__ == "__main__":
    sys.exit(main())
