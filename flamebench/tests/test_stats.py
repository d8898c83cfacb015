"""Latency arithmetic of the open loop: from the due time, with a failed
or unfinished request counted as missing."""
import math

import numpy as np

from flamebench import stats
from flamebench.traffic import Request


def _rec(lat_s, due_step=0.01, fail=()):
    reqs = []
    for i, lat in enumerate(lat_s):
        due = 100.0 + i * due_step
        ok = i not in fail
        reqs.append({"due": due, "sent": due, "done": due + lat, "ok": ok,
                     "m": 10, "req": Request(np.zeros(4, np.int32),
                                             np.zeros(10, np.int32), i,
                                             False, False),
                     "queue_s": 0.001})
    t1 = 100.0 + len(lat_s) * due_step
    return {"requests": reqs, "window": (100.0, t1 + 1.0), "seconds": 2.0}


def test_latency_counts_from_the_due_time_not_the_send():
    rec = _rec([0.010] * 100)
    for r in rec["requests"]:
        r["sent"] = r["due"] + 0.5          # a generator that ran late
    assert np.allclose(stats.latencies_ms(rec), 10.0)


def test_a_stalled_request_raises_p99_and_a_failed_one_is_missing():
    base = _rec([0.010] * 200)
    assert math.isclose(stats.percentile(stats.latencies_ms(base), 99),
                        10.0)
    stalled = _rec([0.010] * 197 + [2.0, 2.0, 2.0])
    assert math.isclose(stats.percentile(stats.latencies_ms(stalled), 99),
                        2000.0)
    assert math.isclose(stats.percentile(stats.latencies_ms(stalled), 50),
                        10.0)
    failed = _rec([0.010] * 200, fail=set(range(197, 200)))
    lat = stats.latencies_ms(failed)
    assert math.isinf(max(lat))
    assert stats.percentile(lat, 99) is None     # the p99 itself is missing
    assert math.isclose(stats.percentile(lat, 50), 10.0)


def test_nearest_rank_percentile():
    assert stats.percentile([], 50) is None
    assert stats.percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert stats.percentile(list(range(1, 101)), 99) == 99
    assert stats.percentile(list(range(1, 101)), 100) == 100


def test_completed_in_window_excludes_late_and_failed():
    rec = _rec([0.010, 5.0, 0.010], fail={2})
    done = stats.completed_in_window(rec)
    assert [r["req"].user_id for r in done] == [0]


def test_knee_is_the_last_rate_before_a_failure_or_a_doubled_median():
    from flamebench import sweep

    def line(rate, p50, failed=0):
        return {"rate_per_s": rate, "p50_ms": p50, "failed": failed}
    lines = [line(24, 37.6), line(12, 26.6), line(16, 29.2), line(20, 36.2),
             line(28, 46.1), line(32, 100.3)]
    assert sweep.knee(lines) == 28          # 100.3 > 2 x 26.6
    assert sweep.knee(lines[:4] + [line(28, 40.0, failed=1)]) == 24
    assert sweep.knee([line(4, None, failed=3)]) is None
