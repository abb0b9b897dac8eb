"""The hybrid serving cells (Nemotron-H) at tiny sizes on the CPU: every
step of a run but the chip, the control, and a broken decode step that
``correct`` must catch."""
import io
import json

import jax
import jax.numpy as jnp
import pytest

from bench import harness, tiny
from bench import run as bench_run
from bench.control import readings
from bench.drivers import serve_hybrid

CELLS = [w["name"] for w in harness.benchmark_spec()["workloads"]
         if w["config"] == "nemotron3-nano-30b-a3b"]


@pytest.fixture(autouse=True)
def _own_tune_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tunecache"))


def _line(spec, name, trace=False, seed=2**31 + 23):
    out, err = io.StringIO(), io.StringIO()
    correct = bench_run.run_cell(spec, name, seed=seed, seconds=0.2,
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
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] % (2 * 8) == 0   # whole rounds of two capacities
    assert line["attempted"] >= serve_hybrid.WINDOW_ROUNDS * 2 * 8
    assert set(line["metrics"]) == {
        m["name"] for m in harness.end_to_end_of(spec, name)}
    traced = _line(spec, name, trace=True)
    assert traced["correct"]
    # on the CPU only the program's own counter reads; the device metrics
    # need the chip's trace
    assert traced["metrics"] == {"compiles.serve": {"value": 0.0,
                                                    "unit": "count"}}


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limit(tmp_path, name):
    spec = tiny.spec(tmp_path)
    for rec in readings(spec, name, [5, 2**33 + 3], 0.1, jax.devices()):
        assert rec["program"] <= rec["limit"] < rec["control"], rec


def _break_decode(monkeypatch, how):
    from repro.serve.engine import ServeRuntime
    from repro.serve.kvpool import infer_batch_axes
    step = ServeRuntime._decode_impl

    def broken(self, params, cache, tok, pos, out_buf, gen_idx):
        ntok, pos2, out2, gidx2, cache2 = step(self, params, cache, tok, pos,
                                               out_buf, gen_idx)
        C = tok.shape[0]
        if how == "state_unchanged":
            return ntok, pos2, out2, gidx2, cache
        if how == "half_batch":
            # the upper half of the rows is left out of the step, each
            # cache leaf along the batch axis the pool infers for it
            keep = jnp.arange(C) < C // 2
            ntok = jnp.where(keep, ntok, tok)
            out2 = out_buf.at[jnp.arange(C), gen_idx].set(ntok, mode="drop")
            axes = infer_batch_axes(self.model, self.max_seq)
            new, treedef = jax.tree.flatten(cache2)
            old = jax.tree.leaves(cache)
            cache2 = jax.tree.unflatten(treedef, [
                jnp.where(jnp.expand_dims(keep, [d for d in range(n.ndim)
                                                 if d != ax]), n, o)
                for n, o, ax in zip(new, old, axes)])
            return ntok, pos2, out2, gidx2, cache2
        # a token altered where it is produced
        ntok = (ntok + 1) % self.cfg.vocab
        out2 = out_buf.at[jnp.arange(C), gen_idx].set(ntok, mode="drop")
        return ntok, pos2, out2, gidx2, cache2

    monkeypatch.setattr(ServeRuntime, "_decode_impl", broken)


@pytest.mark.parametrize("how", ["state_unchanged", "half_batch",
                                 "token_altered"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_decode_is_not_correct(tmp_path, monkeypatch, name, how):
    spec = tiny.spec(tmp_path)
    _break_decode(monkeypatch, how)
    assert not _line(spec, name)["correct"]


def test_parent_without_the_config_fails_at_once(tmp_path, monkeypatch):
    """A program that does not register the configuration fails before
    any weight is made."""
    from repro.configs import base
    spec = tiny.spec(tmp_path)
    cfg = harness.cell(spec, CELLS[0])["config"]
    monkeypatch.setattr(base, "_REGISTRY", {})
    monkeypatch.setitem(cfg, "program_config", "no-such-model")
    with pytest.raises(KeyError):
        serve_hybrid.program_config(cfg)
