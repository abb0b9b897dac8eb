"""Multi-objective tuning (ISSUE 10): Pareto frontier, per-objective
winners, and the cross-program cold-start predictor.

The frontier/winner properties run both on synthetic point clouds
(hypothesis, skipped where it is not installed) and on real tuning
tables; the predictor is validated hold-one-out on 3mm/gemm/mvt against
a DETERMINISTIC synthetic truth — the measurement hook is monkeypatched
to a fixed formula with a per-stream cost term the analytic model cannot
see (the same synthesized-ground-truth style as the calibration golden),
so "learned ranking beats analytic ranking on a never-seen program" is a
reproducible fact rather than a wall-clock coincidence.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import (TuneCache, execute, pareto_front, run_host_oracle,
                        tune, winner_exec_kwargs)
from repro.core import tuner as tuner_mod
from repro.optim.offload import attention_step_program
from repro.polybench import build

# the hold-one-out trio of the acceptance criteria
_PROGS = ("table2_3mm", "gemm", "mvt")


def _build(name):
    if name == "table2_3mm":
        return build("3mm", n=16)[0]
    if name == "gemm":
        return build("gemm", n=16, iters=4)[0]
    return build(name, n=16)[0]


def _objectives(rec):
    m = rec.get("measured_s")
    return (float(m if m is not None else rec["predicted_s"]),
            float(rec["energy_j"]), float(rec["peak_bytes"]))


def _dominates(a, b):
    return all(x <= y for x, y in zip(a, b)) and any(
        x < y for x, y in zip(a, b))


def _fake_measure(pl, cfg, be, reps, placement=None):
    """Deterministic synthetic truth for one candidate: transfer bytes
    over a slow 4 GB/s link, fat per-dispatch/sync overheads, plus a
    per-stream setup cost the analytic model has NO term for — only the
    cross-program predictor (stream count is one of its features) can
    learn it."""
    c = tuner_mod.predict_cost(pl, cfg, {})
    truth = ((c["h2d_bytes"] + c["d2h_bytes"]) / 4e9
             + 8e-4 * c["dispatches"] + 2e-4 * c["syncs"]
             + 2.5e-4 * cfg.n_streams)
    return truth, 0.0


class TestParetoFrontier:
    def test_hypothesis_front_mutually_nondominated(self):
        pytest.importorskip(
            "hypothesis", reason="property tests need hypothesis "
            "(pip install -r requirements-dev.txt)")
        from hypothesis import given, settings
        from hypothesis import strategies as st

        coords = st.tuples(*([st.integers(0, 5)] * 3))

        @settings(max_examples=200, deadline=None)
        @given(st.lists(coords, min_size=1, max_size=24))
        def run(points):
            front = pareto_front(points)
            assert front, "a non-empty set has a non-dominated point"
            chosen = [points[i] for i in front]
            for a in chosen:
                assert not any(_dominates(b, a) for b in chosen)
            # everything off the frontier is dominated by something on it
            for i, p in enumerate(points):
                if i not in front:
                    assert any(_dominates(c, p) for c in chosen)
            # the lexicographic minimum of every axis order sits on it
            for axis in range(3):
                lex = min(points,
                          key=lambda p: (p[axis],) + tuple(p))
                assert any(points[i] == lex for i in front)

        run()

    def test_real_table_front_nondominated_and_contains_time_winner(self):
        pl = tune(_build("table2_3mm"), backend="numpy", measure=False,
                  cache=False, use_calibration=False)
        t = pl.meta["tuning"]
        by_label = {c["label"]: c for c in t["candidates"]}
        pts = {e["label"]: (e["time_s"], e["energy_j"], e["peak_bytes"])
               for e in t["pareto"]}
        assert pts, "frontier is never empty"
        for a in pts.values():
            assert not any(_dominates(b, a) for b in pts.values())
        # the reported points echo the candidate records exactly
        for label, pt in pts.items():
            assert pt == _objectives(by_label[label])
        # every per-objective winner is on the frontier (frontier points
        # collapse coordinate-duplicates, so compare by coordinates)
        for obj, label in t["winners"].items():
            assert _objectives(by_label[label]) in list(pts.values()), obj
        assert t["objective"] == "time"

    def test_attn_step_has_frontier_with_distinct_winners(self):
        """The acceptance benchmark: flash-attention's tile axis trades
        time (big block_q → fewer passes) against on-chip residency
        (small tiles) — ≥2 non-dominated points, and the time-optimal
        and memory-optimal winners are different plans."""
        pl = tune(attention_step_program(n_steps=1, interpret=True),
                  backend="numpy", measure=False, cache=False,
                  use_calibration=False)
        t = pl.meta["tuning"]
        assert len(t["pareto"]) >= 2
        assert t["winners"]["time"] != t["winners"]["memory"]
        # the memory winner really does hold fewer peak bytes
        by_label = {c["label"]: c for c in t["candidates"]}
        assert (by_label[t["winners"]["memory"]]["peak_bytes"]
                < by_label[t["winners"]["time"]]["peak_bytes"])

    def test_objective_selects_winner_and_weighted_mix(self):
        prog = attention_step_program(n_steps=1, interpret=True)
        for obj in ("energy", "memory"):
            pl = tune(prog, backend="numpy", measure=False, cache=False,
                      use_calibration=False, objective=obj)
            t = pl.meta["tuning"]
            assert t["objective"] == obj
            assert t["chosen"] == t["winners"][obj]
        with pytest.raises(ValueError):
            tune(prog, backend="numpy", measure=False, cache=False,
                 objective="joules")
        pl = tune(prog, backend="numpy", measure=False, cache=False,
                  use_calibration=False,
                  objective={"time": 0.5, "memory": 0.5})
        assert pl.meta["tuning"]["chosen"] in {
            c["label"] for c in pl.meta["tuning"]["candidates"]}

    @pytest.mark.parametrize("name", _PROGS)
    def test_energy_objective_executes_allclose_to_oracle(self, name):
        """An energy-selected plan is still a CORRECT plan: executing it
        through winner_exec_kwargs reproduces the host oracle."""
        if name == "table2_3mm":
            prog, inputs = build("3mm", n=16)
        elif name == "gemm":
            prog, inputs = build("gemm", n=16, iters=4)
        else:
            prog, inputs = build(name, n=16)
        pl = tune(prog, backend="numpy", measure=False, cache=False,
                  use_calibration=False, objective="energy")
        want = run_host_oracle(prog, inputs)
        got, _ = execute(pl, inputs, **winner_exec_kwargs(pl, "numpy"))
        for out in prog.outputs:
            np.testing.assert_allclose(got[out], want[out], rtol=1e-5,
                                       atol=1e-6)


class TestColdStartPredictor:
    def _tune_measured(self, name, tc, **kw):
        return tune(_build(name), backend="numpy", reps=1, cache=tc,
                    calibrate=False, use_calibration=False, **kw)

    @pytest.mark.parametrize("held_out", _PROGS)
    def test_holdout_ranking_no_worse_than_analytic(self, held_out,
                                                    tmp_path, monkeypatch):
        """Fit from the other two programs' measured rows, price the
        held-out grid, and the learned ranking must be at least as
        rank-correlated with (synthetic) truth as the uncalibrated
        analytic model — the acceptance gate, on all three rotations."""
        monkeypatch.setattr(tuner_mod, "_measure", _fake_measure)
        tc = TuneCache(tmp_path / "hold")
        for name in _PROGS:
            if name != held_out:
                self._tune_measured(name, tc)
        pl = self._tune_measured(held_out, tc)
        rec = pl.meta["tuning"]["predictor"]
        assert rec["source"] == "fit"
        assert rec["n_programs"] == 2
        assert rec["accepted"] is True
        assert (rec["rank_corr_predictor"]
                >= rec["rank_corr_analytic"])

    def test_holdout_strictly_beats_analytic_on_stream_term(self,
                                                            tmp_path,
                                                            monkeypatch):
        """mvt's grid separates stream counts into distinct execution
        classes, and the synthetic truth charges per stream — a term the
        analytic model cannot express, so the learned ranking is
        STRICTLY better there."""
        monkeypatch.setattr(tuner_mod, "_measure", _fake_measure)
        tc = TuneCache(tmp_path / "strict")
        for name in ("table2_3mm", "gemm"):
            self._tune_measured(name, tc)
        pl = self._tune_measured("mvt", tc)
        rec = pl.meta["tuning"]["predictor"]
        assert rec["accepted"] is True
        assert (rec["rank_corr_predictor"]
                > rec["rank_corr_analytic"])

    def test_cold_start_prices_unmeasured_grid(self, tmp_path,
                                               monkeypatch):
        """A program never measured at all (measure=False) still gets
        predictor prices on every candidate, and with zero measurements
        the chosen winner comes from the learned model."""
        monkeypatch.setattr(tuner_mod, "_measure", _fake_measure)
        tc = TuneCache(tmp_path / "cold")
        for name in ("table2_3mm", "gemm"):
            self._tune_measured(name, tc)
        pl = tune(_build("mvt"), backend="numpy", measure=False, cache=tc,
                  calibrate=False, use_calibration=False)
        t = pl.meta["tuning"]
        rec = t["predictor"]
        assert rec["used_for_ranking"] is True
        valid = [c for c in t["candidates"] if c["valid"]]
        assert all(c.get("predictor_s") is not None for c in valid)
        assert t["chosen"] == min(
            valid, key=lambda c: (c["predictor_s"], c["rank"]))["label"]

    def test_no_training_rows_no_predictor(self, tmp_path):
        tc = TuneCache(tmp_path / "empty")
        pl = tune(_build("gemm"), backend="numpy", measure=False, cache=tc,
                  use_calibration=False)
        rec = pl.meta["tuning"]["predictor"]
        assert rec["source"] is None
        assert rec["used_for_ranking"] is False
        assert pl.meta["tuning"]["chosen"] == next(
            c["label"] for c in pl.meta["tuning"]["candidates"]
            if c["valid"] and c["rank"] == 1)
