"""The decode program's device time split by block kind
(``bench/scope_trace.py``): on a trace written by hand, and on a trace of
a small Nemotron-H decode recorded on a TPU v5e."""
import gzip

import pytest
from jax.profiler import ProfileData

from bench import harness, scope_trace, xplane

DATA = harness.BENCH / "testdata"
RECORDED = DATA / "tpu_v5e_nemotron_h_decode"


def test_op_scopes_reads_the_innermost_block_scope():
    hlo = "\n".join([
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f1, '
        'metadata={op_name="jit(_decode_impl)/mamba2/mul"}',
        '  ROOT %copy.2 = f32[8]{0} copy(%fusion.1), '
        'metadata={op_name="jit(_decode_impl)/moe/attn/dot_general"}',
        '  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f3, '
        'metadata={op_name="jit(_decode_impl)/head/dot_general"}',
        '  %add.4 = f32[8]{0} add(%p, %p)',
        '  %copy-start.5 = (bf16[8]{0}, bf16[8]{0:S(1)}, u32[]) '
        'copy-start(%w.1)',
        '  %copy-done.5 = bf16[8]{0:S(1)} copy-done(%copy-start.5)',
        '  %custom-call.6 = bf16[8]{0:S(1)} custom-call(%copy-done.5), '
        'custom_call_target="ConcatBitcast"',
        '  %fusion.7 = f32[8]{0} fusion(%custom-call.6, %p), kind=kOutput, '
        'calls=%f7, metadata={op_name="jit(_decode_impl)/moe/dot_general"}',
        '  %copy-start.8 = (bf16[8]{0}, bf16[8]{0:S(1)}, u32[]) '
        'copy-start(%params__blocks___3___mamba2____in_proj__.1)'])
    assert scope_trace.op_scopes(hlo) == {
        "fusion.1": "mamba2", "copy.2": "attn", "fusion.7": "moe",
        "copy-start.5": "moe", "copy-done.5": "moe",
        "copy-start.8": "mamba2"}


def test_hand_written_trace_counts_overlap_once():
    """TPU:0 runs the decode program over [10, 40] us; fusion.1 [10, 30]
    and copy.2 [20, 40] overlap by 10 us, which counts once, for
    fusion.1."""
    text = "\n".join(line for line in
                     (DATA / "hand.xplane.txt").read_text().splitlines()
                     if not line.startswith("#"))
    got = scope_trace.attribute(
        ProfileData.from_text_proto(text),
        {"fusion.1": "mamba2", "copy.2": "moe"}, window_span="bench.window")
    assert got["executions"] == 1
    assert got["device_s"] == pytest.approx(30e-6)
    assert got["seconds"] == {"mamba2": pytest.approx(20e-6),
                              "moe": pytest.approx(10e-6),
                              "attn": 0.0, "other": pytest.approx(0.0)}
    assert got["spans"] == {"mamba2": pytest.approx(20e-6),
                            "moe": pytest.approx(20e-6), "attn": 0.0}


def test_recorded_v5e_decode_splits_by_block_kind():
    """Every block kind takes device time in the recorded decode, and the
    kinds and ``other`` add up to the decode executions' device time."""
    path = xplane.find_xplane(str(RECORDED))
    with gzip.open(RECORDED / "decode.hlo.txt.gz", "rt") as f:
        scopes = scope_trace.op_scopes(f.read())
    assert set(scopes.values()) == set(scope_trace.SCOPES)
    got = scope_trace.attribute(path, scopes, window_span="bench.window")
    decode = xplane.reduce(path, window_span="bench.window").executions_of(
        module=scope_trace.DECODE_MODULE)
    assert got["executions"] == len(decode) > 0
    assert got["device_s"] == pytest.approx(sum(e.seconds for e in decode))
    sec = got["seconds"]
    assert all(sec[k] > 0 for k in scope_trace.SCOPES), sec
    assert 0 <= sec["other"] < 0.5 * got["device_s"], sec
    assert sum(sec.values()) == pytest.approx(got["device_s"])
    for k in scope_trace.SCOPES:
        assert sec[k] <= got["spans"][k] <= got["device_s"]
