"""The trace reduction on a small recorded trace: busy union, idle share,
top ops, per-program device time and the attribution of idle gaps to the
benchmark's host spans."""

from __future__ import annotations

import pytest

from conftest import BENCH
from harness import trace

FIXTURE = BENCH / "tests" / "fixtures" / "trace_small.pbtxt"


def _synthetic():
    # window 0..100 us; ops 10-30, 20-40 (overlap), 60-70; a module around
    # the first two; host spans: poll 0-50, submit 50-80, idle 80-100
    us = 1000.0
    return trace.to_text_proto([
        ("/device:TPU:0", [
            ("XLA Ops", [("fusion.1", 10 * us, 20 * us),
                         ("fusion.2", 20 * us, 20 * us),
                         ("copy.3", 60 * us, 10 * us)]),
            ("XLA Modules", [("jit_chunk_fn(1)", 5 * us, 40 * us),
                             ("jit_refill(2)", 55 * us, 20 * us)])]),
        ("/host:CPU", [
            ("python", [("bench.window", 0.0, 100 * us),
                        ("bench.poll", 0.0, 50 * us),
                        ("bench.submit", 50 * us, 30 * us),
                        ("bench.idle", 80 * us, 20 * us)])]),
    ])


def test_reduction_on_a_synthetic_trace():
    s = trace.summarize(trace.from_text(_synthetic()))
    assert s.window_s == pytest.approx(100e-6)
    assert s.busy_s == pytest.approx(40e-6)          # 10-40 and 60-70
    assert s.idle_pct == pytest.approx(60.0)
    assert s.top_ops[0] == ["fusion.1", pytest.approx(20e-6)]
    assert trace.module_time(s, "chunk_fn") == (pytest.approx(30e-6), 1)
    gaps = dict((k, v) for k, v in s.idle_gaps)
    # 0-10 and 40-50 in poll; 50-60, 70-80 in submit; 80-100 idle
    assert gaps["bench.poll"] == pytest.approx(20e-6)
    assert gaps["bench.submit"] == pytest.approx(20e-6)
    assert gaps["bench.idle"] == pytest.approx(20e-6)


def test_no_device_plane_reads_nothing():
    text = trace.to_text_proto([("/host:CPU", [
        ("python", [("bench.window", 0.0, 1000.0)])])])
    assert trace.summarize(trace.from_text(text)) is None


def test_recorded_chip_trace():
    if not FIXTURE.exists():
        pytest.fail(f"missing fixture {FIXTURE}")
    s = trace.summarize(trace.from_text(FIXTURE.read_text()))
    # the values the reduction gave when the fixture was recorded
    assert s.window_s == pytest.approx(0.127618242)
    assert s.busy_s == pytest.approx(0.120005855)
    assert s.idle_pct == pytest.approx(5.9649678, rel=1e-6)
    assert [n for n, _ in s.top_ops[:2]] == ["copy.70", "copy.71"]
    assert dict(s.idle_gaps)["bench.poll"] == pytest.approx(0.007612387)
    assert 0.0 < s.busy_s <= s.window_s
    assert 0.0 <= s.idle_pct < 100.0
    assert s.top_ops and all(v > 0 for _, v in s.top_ops)
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    t, runs = trace.module_time(s, "jit_chunk_fn")
    assert t == pytest.approx(0.119999421) and runs >= 1
