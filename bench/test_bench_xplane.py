"""The trace reduction, on a trace written by hand."""
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import harness, xplane

DATA = harness.BENCH / "testdata"


def _hand():
    text = "\n".join(line for line in
                     (DATA / "hand.xplane.txt").read_text().splitlines()
                     if not line.startswith("#"))
    return xplane.summarize(ProfileData.from_text_proto(text),
                             window_span="bench.window")


def test_hand_written_trace():
    s = _hand()
    assert s.window_s == pytest.approx(100e-6)
    assert s.devices == 2
    # TPU:0 busy 30 + 5 + 5 (clipped) us, TPU:1 50 us: mean 45 us
    assert s.busy_s == pytest.approx(45e-6)
    assert sorted(s.gaps, key=lambda g: -g[1]) == [
        ("bench.download", pytest.approx(40e-6)),
        ("bench.round", pytest.approx(10e-6)),
        ("bench.round", pytest.approx(10e-6))]
    assert s.op_seconds == {"fusion.1": pytest.approx(25e-6),
                            "copy.2": pytest.approx(20e-6),
                            "fusion.3": pytest.approx(5e-6),
                            "fusion.9": pytest.approx(50e-6)}
    decode = s.executions_of(module="jit__decode_impl")
    assert [(e.program_id, e.seconds) for e in decode] == [
        (5, pytest.approx(30e-6))]
    assert [e.seconds for e in s.executions_of(program_ids=[7])] == [
        pytest.approx(5e-6)]
    assert [e.module for e in s.executions_of(program_ids=[9])] == [
        "jit__lambda"]
    b = s.breakdown(n=2)
    assert [k for k, _ in b["device_ops"]] == ["fusion.9", "fusion.1"]
    assert b["idle_gaps"][0][0] == "bench.download"


def _raster(intervals, lo, hi):
    """Busy nanoseconds by brute force, one cell per nanosecond."""
    grid = np.zeros(int(hi - lo), bool)
    for a, b in intervals:
        a, b = max(int(a - lo), 0), min(int(b - lo), len(grid))
        if b > a:
            grid[a:b] = True
    return int(grid.sum())


def test_union_against_brute_force():
    rng = np.random.default_rng(0)
    starts = rng.integers(0, 5000, 300)
    iv = [(float(a), float(a + d)) for a, d in
          zip(starts, rng.integers(0, 60, 300))]
    merged = xplane.union(iv)
    assert sum(b - a for a, b in merged) == _raster(iv, 0, 6000)
    assert all(b1 < a2 for (_, b1), (a2, _) in zip(merged, merged[1:]))



def test_named_window_span_must_be_there():
    text = "\n".join(line for line in
                     (DATA / "hand.xplane.txt").read_text().splitlines()
                     if not line.startswith("#"))
    with pytest.raises(ValueError, match="no host span"):
        xplane.summarize(ProfileData.from_text_proto(text),
                         window_span="bench.nowhere")
