"""The percentile arithmetic and the sample-count rule."""
import pytest

import bench_paths  # noqa: F401
from harness import endtoend, stats


@pytest.mark.parametrize("values,q,want", [
    ([5.0], 0.95, 5.0),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 0.5, 3.0),
    ([1.0, 2.0, 3.0, 4.0], 0.5, 2.5),
    (list(range(1, 102)), 0.95, 96.0),       # (n-1)*q = 95 -> 96th value
    ([10.0, 20.0], 0.25, 12.5),
    ([3.0, 1.0, 2.0], 1.0, 3.0),
])
def test_percentile_interpolates_between_order_statistics(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


@pytest.mark.parametrize("q,need", [(0.5, 20), (0.9, 100), (0.95, 200),
                                    (0.99, 1000)])
def test_a_percentile_needs_ten_samples_beyond_it(q, need):
    assert stats.samples_needed(q) == need
    assert stats.supported_percentile([1.0] * (need - 1), q) is None
    assert stats.supported_percentile([1.0] * need, q) == 1.0


@pytest.mark.parametrize("bad", [([], 0.5), ([1.0], 1.5), ([1.0], -0.1)])
def test_percentile_rejects_what_it_cannot_answer(bad):
    with pytest.raises(ValueError):
        stats.percentile(*bad)


def test_spread_is_interquartile_distance_over_median():
    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    # statistics.quantiles(n=4) -> 100.75, 102.5, 104.25
    assert stats.spread(vals) == pytest.approx(3.5 / 102.5)


def _row(due, first, last, tokens, ok=True, measured=True, flushes=None):
    return {"due": due, "sent": due, "first": first, "last": last,
            "tokens": tokens, "ok": ok, "measured": measured,
            "asked_tokens": tokens or 8, "error": None if ok else "429:x",
            "flushes": flushes if flushes is not None else (
                [[first, 1], [last, tokens - 1]] if ok else [])}


def test_window_reduction_counts_every_row_and_every_token():
    rows = [_row(10.0 + i * 0.01, 10.5 + i * 0.01, 11.5 + i * 0.01, 11)
            for i in range(200)]
    rows.append(_row(9.0, 9.5, 10.2, 5, measured=False))   # ramp row
    out = endtoend.reduce_window(rows, 10.0, 20.0, 80.0)
    assert out["attempted"] == 200 and out["failed"] == 0
    assert out["values"]["ttft_p50_ms"] == pytest.approx(500.0)
    assert out["values"]["ttft_p90_ms"] == pytest.approx(500.0)
    assert out["values"]["ttft_p95_ms"] == pytest.approx(500.0)
    assert out["values"]["tpot_p95_ms"] == pytest.approx(100.0)
    # 200 x 11 tokens, plus the ramp row's last flush (4 tokens at 10.2)
    assert out["window_tokens"] == 2204
    assert out["values"]["tokens_per_s"] == pytest.approx(220.4)


def test_a_failed_row_stays_in_every_denominator():
    rows = [_row(10.0, 10.1, 10.6, 6) for _ in range(180)]
    rows += [_row(10.0, None, None, 0, ok=False) for _ in range(20)]
    out = endtoend.reduce_window(rows, 10.0, 20.0, 80.0)
    assert out["attempted"] == 200 and out["failed"] == 20
    # 10 % failed: the 95th percentile is a row that was given up on,
    # at the 70 s it had been waited for.
    assert out["values"]["ttft_p95_ms"] == pytest.approx(70_000.0)
    assert out["values"]["ttft_p50_ms"] == pytest.approx(100.0)
    assert out["errors"] == ["429:x"]


def test_too_few_samples_leave_the_tail_out():
    rows = [_row(10.0, 10.1, 10.6, 6) for _ in range(50)]
    out = endtoend.reduce_window(rows, 10.0, 20.0, 80.0)
    assert "ttft_p95_ms" not in out["values"]
    assert "ttft_p90_ms" not in out["values"]       # needs 100
    assert "ttft_p50_ms" in out["values"]
