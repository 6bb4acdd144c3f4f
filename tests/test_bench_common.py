"""Bench watchdog tests — the probe-first + salvage behavior VERDICT r2
demanded (weak #1a-c). These run hermetically with fake child scripts;
probe_device is exercised with ROUNDTABLE_BENCH_CPU so no test ever
touches a chip."""

import json
import os
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench_common


@pytest.fixture(autouse=True)
def _reset_probe_memo():
    bench_common._device_ok_at = None
    yield
    bench_common._device_ok_at = None


def _fake_child(tmp_path, body: str) -> str:
    path = tmp_path / "fake_bench.py"
    path.write_text(textwrap.dedent(body))
    return str(path)


def _patch_probe(monkeypatch, result=True):
    calls = []

    def fake_probe(*a, **k):
        calls.append(1)
        return result

    monkeypatch.setattr(bench_common, "probe_device", fake_probe)
    return calls


def test_watchdog_salvages_partial_output_on_timeout(
        tmp_path, monkeypatch, capsys):
    """A child that lands one measurement then hangs still scores (r2
    weak #1b: TimeoutExpired.stdout was previously discarded)."""
    _patch_probe(monkeypatch)
    script = _fake_child(tmp_path, """
        import sys, time
        print('{"metric": "m", "value": 1}', flush=True)
        time.sleep(300)
    """)
    # timeout must leave room for interpreter start under full-suite
    # load (3s flaked when the machine was saturated) while still
    # expiring long before the child's sleep
    rc = bench_common.run_watchdogged(script, [], timeout_s=15.0,
                                      attempts=1, retry_delay_s=0.0)
    out = capsys.readouterr().out.strip()
    assert rc == 0
    assert json.loads(out) == {"metric": "m", "value": 1, "attempt": 1}


def test_watchdog_skips_heavy_child_when_probe_fails(
        tmp_path, monkeypatch, capsys):
    """No probe success → the heavy child is never started (r2 weak #1a:
    killing a chip-holding child can wedge the device) — but a machine-
    readable status record still reaches stdout (r3 missing #2: three
    rounds of `parsed: null` driver artifacts)."""
    calls = _patch_probe(monkeypatch, result=False)
    marker = tmp_path / "ran"
    script = _fake_child(tmp_path, f"""
        import pathlib
        pathlib.Path({str(marker)!r}).write_text("ran")
        print('{{"metric": "m", "value": 1}}')
    """)
    rc = bench_common.run_watchdogged(script, [], timeout_s=10.0,
                                      attempts=2, retry_delay_s=0.0)
    assert rc == 1
    assert calls == [1]  # fails fast: one probe round, no retry loop
    assert not marker.exists()
    lines = [json.loads(l) for l in
             capsys.readouterr().out.strip().splitlines()]
    # The failure path may FIRST re-emit committed headline numbers —
    # every such record is explicitly cached-marked (VERDICT item 9) —
    # and ends with the machine-readable status record.
    cached, status = lines[:-1], lines[-1]
    assert all(r.get("cached") is True for r in cached)
    assert all(r["metric"].endswith("[cached]") for r in cached)
    assert status["status"] == "device_unreachable"
    assert status["metric"].startswith("bench_status[")
    assert status["value"] == 0.0
    assert status["vs_baseline"] is None
    assert status["detail"]["cached_records_emitted"] == len(cached)


def test_watchdog_happy_path_forwards_all_lines(
        tmp_path, monkeypatch, capsys):
    _patch_probe(monkeypatch)
    script = _fake_child(tmp_path, """
        print('{"metric": "bf16", "value": 1}', flush=True)
        print('{"metric": "best", "value": 2}', flush=True)
    """)
    rc = bench_common.run_watchdogged(script, [], timeout_s=30.0,
                                      attempts=2, retry_delay_s=0.0)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    assert [json.loads(l)["metric"] for l in out] == ["bf16", "best"]


def test_watchdog_retry_forwards_only_new_keys(
        tmp_path, monkeypatch, capsys):
    """Attempt 1 lands key "a" live then dies; attempt 2 re-measures "a"
    (suppressed — a driver summing per-metric lines must not
    double-count) and adds "b" (forwarded). Streaming-first: the line
    that already reached stdout wins (r3 lesson: holding lines until
    child exit lost completed measurements to external kills)."""
    _patch_probe(monkeypatch)
    marker = tmp_path / "attempt1_done"
    script = _fake_child(tmp_path, f"""
        import pathlib, sys
        marker = pathlib.Path({str(marker)!r})
        if not marker.exists():
            marker.write_text("x")
            print('{{"metric": "a", "value": 1}}', flush=True)
            sys.exit(3)
        print('{{"metric": "a", "value": 9}}', flush=True)
        print('{{"metric": "b", "value": 2}}', flush=True)
    """)
    rc = bench_common.run_watchdogged(script, [], timeout_s=30.0,
                                      attempts=2, retry_delay_s=0.0)
    out = [json.loads(l) for l in
           capsys.readouterr().out.strip().splitlines()]
    assert rc == 0
    assert out == [{"metric": "a", "value": 1, "attempt": 1},
                   {"metric": "b", "value": 2, "attempt": 2}]


def test_watchdog_all_attempts_fail_still_streams_once(
        tmp_path, monkeypatch, capsys):
    """Every attempt fails → each record still reached stdout exactly
    once (streamed live, duplicate keys suppressed across retries)."""
    _patch_probe(monkeypatch)
    script = _fake_child(tmp_path, """
        import sys
        print('{"metric": "m", "value": 1}', flush=True)
        sys.exit(3)
    """)
    rc = bench_common.run_watchdogged(script, [], timeout_s=30.0,
                                      attempts=2, retry_delay_s=0.0)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    assert [json.loads(l) for l in out] == [
        {"metric": "m", "value": 1, "attempt": 1}]


def test_watchdog_chatty_stderr_child_not_falsely_timed_out(
        tmp_path, monkeypatch, capsys):
    """A child writing >64KB to stderr must not deadlock on a full pipe
    and get killed as a fake timeout (review finding: stderr drained
    continuously, not after exit)."""
    _patch_probe(monkeypatch)
    script = _fake_child(tmp_path, """
        import sys
        for _ in range(4000):
            print("W0000 some very chatty PJRT warning line" * 2,
                  file=sys.stderr)
        print('{"metric": "m", "value": 1}', flush=True)
    """)
    rc = bench_common.run_watchdogged(script, [], timeout_s=20.0,
                                      attempts=1, retry_delay_s=0.0)
    out = capsys.readouterr().out.strip()
    assert rc == 0
    assert json.loads(out) == {"metric": "m", "value": 1, "attempt": 1}


def test_watchdog_exit0_without_records_is_failure(
        tmp_path, monkeypatch, capsys):
    """rc=0 with zero JSON records must NOT count as success (review
    finding: a silently no-op'ing child would otherwise be recorded as
    a passed bench with no metrics). Stdout ends with the
    bench_no_records status record (preceded only by cached-marked
    committed headlines, if any exist in the repo)."""
    _patch_probe(monkeypatch)
    script = _fake_child(tmp_path, """
        print("usage: oops, wrong args")
    """)
    rc = bench_common.run_watchdogged(script, [], timeout_s=20.0,
                                      attempts=2, retry_delay_s=0.0)
    assert rc == 1
    lines = [json.loads(l) for l in
             capsys.readouterr().out.strip().splitlines()]
    assert all(r.get("cached") is True for r in lines[:-1])
    status = lines[-1]
    assert status["status"] == "bench_no_records"


def test_cached_headline_fallback_is_marked_and_provenanced(
        monkeypatch, capsys):
    """VERDICT item 9: with no live measurement, the latest COMMITTED
    builder-jsonl headline is re-emitted as an explicitly `cached`
    record with commit-hash provenance — latest headline per metric key
    wins, non-headline records are never re-emitted, and a cached
    record can never masquerade as fresh (suffixed key + cached flag)."""
    content = "\n".join([
        json.dumps({"metric": "decode[x]", "value": 1.0, "unit": "t/s",
                    "vs_baseline": 0.5, "detail": {"headline": False}}),
        json.dumps({"metric": "decode", "value": 2.0, "unit": "t/s",
                    "vs_baseline": 1.0, "detail": {"headline": True}}),
        json.dumps({"metric": "decode", "value": 3.0, "unit": "t/s",
                    "vs_baseline": 1.5, "detail": {"headline": True}}),
    ])
    monkeypatch.setattr(
        bench_common, "_latest_committed_builder_jsonl",
        lambda: {"path": "BENCH_r09_builder.jsonl", "commit": "abc123",
                 "committed_at": "2026-08-01T00:00:00Z",
                 "content": content})
    n = bench_common.emit_cached_headlines("bench.py")
    assert n == 1
    rec = json.loads(capsys.readouterr().out.strip())
    assert rec["metric"] == "decode[cached]"
    assert rec["value"] == 3.0            # latest headline won
    assert rec["cached"] is True
    assert rec["detail"]["cached_from"]["commit"] == "abc123"
    assert rec["detail"]["cached_from"]["path"] == \
        "BENCH_r09_builder.jsonl"


def test_cached_headline_fallback_never_raises(monkeypatch, capsys):
    """A broken cache path must not mask the real failure record."""
    monkeypatch.setattr(
        bench_common, "_latest_committed_builder_jsonl",
        lambda: (_ for _ in ()).throw(RuntimeError("git exploded")))
    assert bench_common.emit_cached_headlines("bench.py") == 0
    assert capsys.readouterr().out == ""


def test_watchdog_metricless_json_lines_all_forwarded(
        tmp_path, monkeypatch, capsys):
    """JSON lines without a 'metric' field (metadata records) are all
    forwarded — they must not dedup against each other under key None
    (review finding)."""
    _patch_probe(monkeypatch)
    script = _fake_child(tmp_path, """
        print('{"context": "env"}', flush=True)
        print('{"context": "roofline"}', flush=True)
        print('{"metric": "m", "value": 1}', flush=True)
    """)
    rc = bench_common.run_watchdogged(script, [], timeout_s=20.0,
                                      attempts=1, retry_delay_s=0.0)
    out = [json.loads(l) for l in
           capsys.readouterr().out.strip().splitlines()]
    assert rc == 0
    assert out == [{"context": "env"}, {"context": "roofline"},
                   {"metric": "m", "value": 1, "attempt": 1}]


def test_watchdog_failed_child_reprobes_before_retry(
        tmp_path, monkeypatch, capsys):
    """Each heavy attempt is gated on its own probe (r2 weak #1: blind
    back-to-back 320s retries on an unreachable device)."""
    calls = _patch_probe(monkeypatch)
    script = _fake_child(tmp_path, """
        import sys
        sys.exit(3)
    """)
    rc = bench_common.run_watchdogged(script, [], timeout_s=30.0,
                                      attempts=2, retry_delay_s=0.0)
    assert rc == 1
    assert len(calls) == 2


def test_watchdog_success_memo_skips_next_probe(
        tmp_path, monkeypatch, capsys):
    """A heavy-child success vouches for the device, so bench_suite's
    back-to-back benches don't open 5 extra claim/release windows."""
    calls = _patch_probe(monkeypatch)
    script = _fake_child(tmp_path, """
        print('{"metric": "m", "value": 1}', flush=True)
    """)
    for _ in range(2):
        rc = bench_common.run_watchdogged(script, [], timeout_s=30.0,
                                          attempts=2, retry_delay_s=0.0)
        assert rc == 0
    assert len(calls) == 1
    capsys.readouterr()


def test_probe_hang_gives_up_after_one_attempt_without_reaping(
        monkeypatch, capsys):
    """A hung probe is abandoned (no kill) and ends probing immediately
    — repeated kills of mid-init JAX children are the r2 wedge event."""
    monkeypatch.setattr(bench_common, "_PROBE_SRC",
                        "import time; time.sleep(30)")
    t0 = __import__("time").monotonic()
    ok = bench_common.probe_device(timeout_s=1.5, attempts=3,
                                   retry_delay_s=5.0)
    elapsed = __import__("time").monotonic() - t0
    err = capsys.readouterr().err
    assert not ok
    assert elapsed < 5.0  # one attempt, no retry delays
    assert "abandoning hung child" in err


@pytest.mark.slow
def test_probe_device_real_cpu_child(monkeypatch):
    """probe_device's real child succeeds against the cpu backend."""
    monkeypatch.setenv("ROUNDTABLE_BENCH_CPU", "1")
    assert bench_common.probe_device(timeout_s=120.0, attempts=1)


@pytest.mark.slow
def test_bench_child_survives_one_config_failing(monkeypatch, capsys):
    """bench.py's per-config failure tolerance: one config raising (the
    TPU-compile-surprise case) must still land every other config's
    record AND the headline (the driver's stable metric key), emit the
    failure under a distinct [label][failed] key, and exit nonzero so
    the watchdog's retry + per-key dedup can recover the missing
    config after a transient error."""
    monkeypatch.setenv("ROUNDTABLE_BENCH_CPU", "1")
    import theroundtaible_tpu.engine.engine as engine_mod

    real = engine_mod.InferenceEngine

    class Boom(real):
        def __init__(self, *a, **kw):
            if kw.get("quant") == "int8":
                raise RuntimeError("simulated TPU compile failure")
            super().__init__(*a, **kw)

    monkeypatch.setattr(engine_mod, "InferenceEngine", Boom)
    import bench
    rc = bench.child()
    assert rc == 1  # nonzero → watchdog retry fills the missing config
    recs = [json.loads(line) for line in
            capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    by_metric = {r["metric"]: r for r in recs}
    fail_key = [k for k in by_metric if k.endswith("[failed]")]
    assert fail_key and by_metric[fail_key[0]]["detail"]["failed"]
    headline = [r for r in recs if r["detail"].get("headline")]
    assert len(headline) == 1
    d = headline[0]["detail"]
    assert {run["label"] for run in d["runs"]} == {"bf16", "int4"}
    assert d["failed_configs"][0]["label"] == "int8"
