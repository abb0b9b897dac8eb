"""The executor's ``hmpp.*`` spans read from a trace (``bench/hmpp_trace.py``):
hand-worked values on a hand-written trace, the same reduction on a trace
recorded on a TPU v5e, and proof that reading the spans leaves every
per-layer metric of the benchmark as it was."""
import pytest
from jax.profiler import ProfileData

from bench import harness, hmpp_trace, xplane

DATA = harness.BENCH / "testdata"
HAND = ["hand.xplane.txt", "hand_hmpp.xplane.txt"]


def _text(name, hmpp=True):
    lines = (DATA / name).read_text().splitlines()
    return "\n".join(line for line in lines if not line.startswith("#")
                     and (hmpp or not line.endswith("# hmpp")))


def _pd(name, hmpp=True):
    return ProfileData.from_text_proto(_text(name, hmpp))


def test_hand_worked_decomposition():
    r = hmpp_trace.read(_pd("hand_hmpp.xplane.txt"))
    assert r["executions"] == 2
    assert r["h2d_ms"] == pytest.approx(20500e-6)
    assert r["d2h_ms"] == pytest.approx(25000e-6)
    assert r["device_ms"] == pytest.approx(35500e-6)
    assert r["execute_ms"] == pytest.approx(88000e-6)
    assert r["clock_pct"] == pytest.approx(200 / 3)
    assert sorted(r["idle_gaps"]) == [
        ["hmpp.delegatestore", pytest.approx(45e-6)],
        ["hmpp.execute", pytest.approx(55e-6)],
        ["hmpp.synchronize", pytest.approx(14e-6)],
        ["idle", pytest.approx(15e-6)]]


def test_no_hmpp_spans_no_decomposition():
    r = hmpp_trace.read(_pd("hand_hmpp.xplane.txt", hmpp=False))
    assert r["executions"] == 0
    assert r["h2d_ms"] is None and r["d2h_ms"] is None
    assert r["clock_pct"] == 0.0


def _obs(summary):
    """What a traced run hands the metric readers, for both kinds of
    cell at once, with ``summary`` as its trace."""
    return {"trace": summary, "peak": harness.peaks("TPU v5 lite"),
            "chips": 1, "compiles": 0, "executions": 2,
            "exec_stats": {"fused_launches": 1, "h2d_transfers": 4,
                           "d2h_transfers": 1, "h2d_bytes": 4096,
                           "d2h_bytes": 1024},
            "work": {"flops": 1e9, "hbm_bytes": 1e8},
            "cfg": harness.load_json(harness.BENCH / "configs"
                                     / "rwkv6-3b.json"),
            "capacity": 4, "decode_tokens": 3, "prompt_tokens": 16,
            "calibration": {"prefill": [7]}}


def _readings(summary):
    out = {}
    for m in harness.benchmark_spec()["per_layer"]:
        reader = harness.load_module(harness.BENCH / "metrics"
                                     / f"{m['name']}.py")
        out[m["name"]] = reader.read(_obs(summary))
    return out


@pytest.mark.parametrize("name", HAND)
def test_hmpp_spans_change_only_gap_labels(name):
    """The benchmark's reduction of the trace without the executor's
    spans, of the trace with them, and with them collected as labels:
    the same window, busy time, executions, op times and per-layer
    readings; only the idle gaps' labels may differ."""
    without = xplane.summarize(_pd(name, hmpp=False),
                               window_span="bench.window")
    kept = xplane.summarize(_pd(name), window_span="bench.window")
    labelled = xplane.summarize(_pd(name), window_span="bench.window",
                                span_prefix=hmpp_trace.SPANS)
    base = _readings(without)
    assert any(v is not None for v in base.values())
    for s in (kept, labelled):
        assert (s.window_s, s.busy_s, s.devices) == (
            without.window_s, without.busy_s, without.devices)
        assert s.executions == without.executions
        assert s.op_seconds == without.op_seconds
        assert [d for _, d in s.gaps] == [d for _, d in without.gaps]
        assert _readings(s) == base
    assert kept.gaps == without.gaps



def test_recorded_tpu_trace():
    """A small 3mm (every size 256, three executions) traced on one TPU
    v5e chip by ``python -m bench.hmpp_trace record``.  Its device plane
    runs about 1.3 ms early against the host spans: each module starts
    before the host enqueued it (``DoEnqueueProgram``), so no module
    starts after its callsite and h2d cannot be read; the clock share
    shows it."""
    pd = ProfileData.from_file(str(DATA / "tpu_v5e_3mm_n256.xplane.pb"))
    s = xplane.summarize(pd, window_span="bench.window")
    assert s.devices == 1 and 0 < s.busy_s < s.window_s
    assert [e.module for e in s.executions] == ["jit_fused"] * 3

    spans = hmpp_trace.host_spans(pd)
    assert [n for n, _, _ in spans if n.startswith("hmpp.")] == 3 * (
        ["hmpp.execute"] + ["hmpp.advancedload"] * 4 + [
            "hmpp.callsite", "hmpp.synchronize", "hmpp.delegatestore",
            "hmpp.release"])
    enqueued = sorted(ev.start_ns for p in pd.planes
                      if p.name.startswith("/host") for line in p.lines
                      for ev in line.events if ev.name == "DoEnqueueProgram")
    early = [t - e.start_ns for t, e in zip(enqueued, s.executions)]
    assert len(early) == 3 and all(1.0e6 < d < 1.5e6 for d in early)

    r = hmpp_trace.read(pd)
    assert r["executions"] == 3 and r["clock_pct"] == 0.0
    assert r["h2d_ms"] is None
    assert r["device_ms"] == pytest.approx(0.0064, rel=0.01)
    assert r["d2h_ms"] == pytest.approx(2.4747, rel=0.001)
    assert {label for label, _ in r["idle_gaps"]} <= {
        "hmpp.execute", "hmpp.advancedload", "hmpp.synchronize",
        "hmpp.callsite", "hmpp.delegatestore", "hmpp.release", "idle"}
