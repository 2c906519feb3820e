"""The trace reduction: interval arithmetic, a trace recorded here on the
CPU, and the table of peaks."""
import dataclasses
import time

import jax
import numpy as np
import pytest

from chipbench import trace as T


def _brute_union(intervals, lo, hi, step=1e-4):
    grid = np.arange(lo, hi, step) + step / 2
    cov = np.zeros_like(grid, bool)
    for s, e in intervals:
        cov |= (grid >= s) & (grid < e)
    return cov.sum() * step


def test_union_and_gaps_by_hand():
    iv = [(1.0, 3.0), (2.0, 4.0), (6.0, 7.0), (6.5, 6.7), (-1.0, 0.5),
          (9.0, 12.0)]
    assert T.merge(iv, 0.0, 10.0) == [(0.0, 0.5), (1.0, 4.0), (6.0, 7.0),
                                      (9.0, 10.0)]
    assert T.union_length(iv, 0.0, 10.0) == pytest.approx(5.5)
    assert T.gaps(iv, 0.0, 10.0) == [(0.5, 1.0), (4.0, 6.0), (7.0, 9.0)]
    assert T.union_length([], 0.0, 1.0) == 0.0
    assert T.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_union_matches_brute_force():
    rng = np.random.default_rng(0)
    s = rng.uniform(0, 1, 40)
    iv = list(zip(s, s + rng.uniform(0, 0.05, 40)))
    assert T.union_length(iv, 0.1, 0.9) == pytest.approx(
        _brute_union(iv, 0.1, 0.9), abs=2e-3)


def _recorded_trace(tmp_path):
    """A trace recorded here: a window span, two ticks, and 'op.*' spans
    standing in for device operations (two of them overlap)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("chipbench.tick"):
                with jax.profiler.TraceAnnotation("op.a"):
                    with jax.profiler.TraceAnnotation("op.b"):
                        time.sleep(0.01)
                    time.sleep(0.005)
                time.sleep(0.02)
            with jax.profiler.TraceAnnotation("chipbench.stamp"):
                time.sleep(0.03)
    jax.profiler.stop_trace()
    events = T.read_events(T.find_xplane(tmp_path))
    # the 'op.*' spans play a chip's operations
    return [dataclasses.replace(e, plane="/device:TPU:0", line=T.OP_LINE)
            if e.name.startswith("op.") else e for e in events
            if e.name.startswith(("op.", "chipbench."))]


def test_recorded_trace(tmp_path):
    events = _recorded_trace(tmp_path)
    s = T.TraceSummary(events)
    ops = [e for e in events if e.name.startswith("op.")]
    assert len(ops) == 4 and s.planes == ["/device:TPU:0"]
    assert s.window_s == pytest.approx(0.13, abs=0.03)
    busy = T.union_length([(e.start, e.end) for e in ops], s.lo, s.hi)
    assert s.busy_s() == pytest.approx(busy)
    assert s.busy_s() == pytest.approx(_brute_union(
        [(e.start, e.end) for e in ops], s.lo, s.hi, 1e-5), abs=1e-4)
    # op.b lies inside op.a: the union counts it once
    assert s.busy_s() < sum(e.dur for e in ops)
    assert 0 < s.idle_frac() < 1
    gaps = s.idle_gaps(10)
    assert sum(g[1] for g in gaps) == pytest.approx(s.window_s - s.busy_s())
    # the longest gaps are the stamp spans' 30 ms, then the ticks' 20 ms
    assert gaps[0][0] == "chipbench.stamp" and gaps[0][1] >= 0.029
    assert {g[0] for g in gaps} <= {"chipbench.stamp", "chipbench.tick",
                                    "none"}
    top = dict(s.top_ops())
    assert top["op.a"] > top["op.b"] > 0


def test_unknown_device_is_an_error():
    assert T.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(T.UnknownDevice):
        T.peaks_for("cpu")
    with pytest.raises(T.UnknownDevice):
        T.peaks_for("TPU v6 lite")
