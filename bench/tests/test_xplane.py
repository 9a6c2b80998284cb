"""The reduction from a profiler trace to busy time, busy time inside
host spans, and idle gaps by the host span they fall in."""

from __future__ import annotations

import pytest

import xplane
from conftest import BENCH

RECORDED = BENCH / "tests" / "data" / "chip_trace.xplane.pb"


def test_merge_and_cover():
    u = xplane.merge([(5, 9), (0, 3), (2, 4), (9, 10), (20, 20)])
    assert u == [(0, 4), (5, 10)]
    assert xplane.covered(u, 0, 100) == 9
    assert xplane.covered(u, 3, 6) == 2
    assert xplane.covered(u, 11, 30) == 0


def synthetic():
    ops = {"/device:TPU:0": [("fusion.1", 10, 40), ("fusion.2", 30, 50),
                             ("copy", 80, 90)],
           "/device:TPU:1": [("fusion.1", 10, 30)]}
    spans = [("bench.submit", 0, 60), ("bench.to_host", 40, 60),
             ("bench.running_score", 70, 100)]
    return xplane.Trace(ops, spans)


def test_busy_and_spans():
    t = synthetic()
    assert t.window() == (0, 100)
    assert t.busy("/device:TPU:0", 0, 100) == 50     # 10-50 and 80-90
    assert t.mean_busy(0, 100) == (50 + 20) / 2
    assert t.busy_in_spans("bench.submit") == [(40 + 20) / 2]
    assert t.busy_in_spans("bench.running_score") == [(10 + 0) / 2]


def test_idle_gaps_by_span():
    t = synthetic()
    idle = t.idle_by_span(0, 100)
    # Device 0: 0-10 submit, 50-60 to_host, 60-70 none, 70-80 and 90-100
    # running_score. Device 1: 0-10 submit, 30-40 submit, 40-60 to_host,
    # 60-70 none, 70-100 running_score. Averaged over the two.
    assert idle == pytest.approx({"bench.submit": 15.0, "bench.to_host": 15.0,
                                  "(none)": 10.0, "bench.running_score": 25.0})
    assert sum(idle.values()) == pytest.approx(100 - t.mean_busy(0, 100))


def test_top_ops():
    t = synthetic()
    assert t.top_ops(0, 100)[0] == ("fusion.1", (30 + 20) / 2)


def test_submit_idle_ms_per_submit_mean_over_devices():
    import harness

    ms = 1_000_000
    ops = {"/device:TPU:0": [("replay_chunk", 10 * ms, 30 * ms),
                             ("replay_chunk", 70 * ms, 90 * ms),
                             ("fusion", 115 * ms, 120 * ms)],
           "/device:TPU:1": [("replay_chunk", 0, 40 * ms),
                             ("replay_chunk", 60 * ms, 100 * ms)]}
    spans = [("bench.submit", 0, 50 * ms), ("bench.submit", 60 * ms, 100 * ms),
             ("bench.running_score", 110 * ms, 130 * ms)]
    calls = [harness.Call("submit", 0.0, 0.05, 256),
             harness.Call("submit", 0.06, 0.1, 256),
             harness.Call("score", 0.11, 0.13)]
    rec = harness.Run(None, 1000, 5, 2, {}, 1.0, 0.13, calls,
                      xplane.Trace(ops, spans))
    read = harness.metric_reader(BENCH, "submit_idle_ms.backfill")
    # Inside the submits device 0 idles 30 + 20 ms and device 1 10 + 0 ms;
    # the 10 ms between the spans and the score's idle are not counted.
    assert read(rec) == pytest.approx((30 + 20 + 10 + 0) / 2 / 2)
    rec.trace = None
    assert read(rec) is None
