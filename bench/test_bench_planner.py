"""The planner cells at tiny sizes on the CPU: every step of a run but the
chip, the control, and a broken timed path that ``correct`` must catch."""
import io
import json

import jax
import numpy as np
import pytest

from bench import harness, tiny
from bench import run as bench_run
from bench.control import readings

CELLS = [w["name"] for w in harness.benchmark_spec()["workloads"]
         if w["config"] == "polybench-xl"]


def _line(spec, name, trace=False, seed=2**31 + 11):
    out, err = io.StringIO(), io.StringIO()
    correct = bench_run.run_cell(spec, name, seed=seed, seconds=0.5,
                                 trace=trace, devices=jax.devices(),
                                 out=out, err_out=err)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] == correct
    assert list(line)[-1] == "compared"
    return line


@pytest.mark.parametrize("name", CELLS)
def test_run_and_trace(tmp_path, name):
    spec = tiny.spec(tmp_path)
    line = _line(spec, name)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    e2e = {m["name"] for m in harness.end_to_end_of(spec, name)}
    assert set(line["metrics"]) == e2e
    traced = _line(spec, name, trace=True)
    assert traced["correct"]
    # the counters are there; nothing read from a device trace on a CPU
    assert traced["metrics"]["compiles.exec"]["value"] == 0
    assert traced["metrics"]["moved_mb"]["value"] > 0
    assert "idle_pct.exec" not in traced["metrics"]
    assert "busy_s" not in traced["device"]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limit(tmp_path, name):
    spec = tiny.spec(tmp_path)
    for rec in readings(spec, name, [7, 2**32 + 9], 0.3, jax.devices()):
        assert rec["program"] <= rec["limit"] < rec["control"], rec


def _alter_where_produced(monkeypatch, program_name):
    """The last offload block's answer with one element changed."""
    program = harness.load_module(harness.BENCH / "programs"
                                  / f"{program_name}.py")
    build = program.build

    def broken(ds, inputs):
        p = build(ds, inputs)
        blk = p.offload_blocks()[-1]
        fn = blk.fn

        def altered(xp, **kw):
            out = fn(xp, **kw)
            return {k: (v.at[1, 1].add(1.0) if hasattr(v, "at")
                        else np.asarray(v) + (np.indices(v.shape).sum(0) == 2))
                    for k, v in out.items()}

        blk.fn = altered
        return p

    monkeypatch.setattr(program, "build", broken)


@pytest.mark.parametrize("name", CELLS)
def test_answer_altered_is_not_correct(tmp_path, monkeypatch, name):
    spec = tiny.spec(tmp_path)
    traffic = harness.cell(spec, name)["traffic"]
    _alter_where_produced(monkeypatch, traffic["program"])
    assert not _line(spec, name)["correct"]



class _FakeTPU:
    """A device that says it is a TPU, for a run whose trace then holds no
    TPU plane."""
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


@pytest.mark.parametrize("name", CELLS)
def test_chip_trace_unread_gives_no_result(tmp_path, name):
    spec = tiny.spec(tmp_path)
    out, err = io.StringIO(), io.StringIO()
    with pytest.raises(harness.BenchError, match="trace"):
        bench_run.run_cell(spec, name, seed=5, seconds=0.2, trace=True,
                           devices=[_FakeTPU()], out=out, err_out=err)
    assert out.getvalue() == ""
