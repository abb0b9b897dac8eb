"""Plan-space explorer + roofline-backed cost model (ISSUE 4).

Acceptance criteria under test:

  * ``plan(program, policy="auto")`` enumerates ≥ 8 candidate plans on
    the 3mm example, every candidate passes the simulate-and-fix pass,
  * the chosen plan's measured wall time is ≤ the fixed "optimized"
    plan's on both the numpy and jax backends (within the recorded
    table — both were measured by the same procedure),
  * ``plan.meta["tuning"]`` records predicted AND measured cost for each
    candidate,
  * predicted transfer bytes match ``transfer_summary()`` directive
    counts × loop trip multipliers × dtype sizes (golden file), and the
    executed ``ExecStats`` bytes,
  * a placement policy the simulator rejects is recorded invalid and
    never ranked.
"""
import json
import pathlib

import numpy as np
import pytest

from repro.core import (PlanConfig, Program, execute, plan, predict_cost,
                        transfer_summary, tune)
from repro.core.passes import NaivePlacement, register_placement
from repro.core.passes.placement import _PLACEMENTS
from repro.optim import plan_step_program
from repro.polybench import build_3mm

GOLDEN = json.loads(
    (pathlib.Path(__file__).parent / "golden" / "cost_model.json")
    .read_text())

FIXED_OPTIMIZED = "optimized/streams2/fuse/nodonate"


def _tuned_3mm(backend):
    p, _ = build_3mm(n=32)
    return plan(p, policy="auto", backend=backend, reps=2)


def _rec_for(tuning, label):
    """The candidate record carrying ``label`` (possibly as an alias —
    identical plans are deduplicated)."""
    for c in tuning["candidates"]:
        if c["label"] == label or label in c.get("aliases", ()):
            return c
    raise KeyError(label)


class TestAcceptance:
    @pytest.mark.parametrize("backend", ["numpy", "jax"])
    def test_auto_policy_on_3mm(self, backend):
        pl = _tuned_3mm(backend)
        tuning = pl.meta["tuning"]
        valid = [c for c in tuning["candidates"] if c["valid"]]
        # ≥ 8 candidates, every one simulator-approved
        assert len(valid) >= 8
        assert all(c["error"] is None for c in valid)
        # predicted AND measured recorded for each candidate
        for c in valid:
            assert c["predicted_s"] > 0.0
            assert c["measured_s"] is not None and c["measured_s"] > 0.0
            assert c["rank"] is not None
        # dominance pruning (ISSUE 5): _measure ran exactly once per
        # distinct execution class — merged configs inherit their class
        # survivor's number instead of re-measuring it
        survivors = [c for c in valid if c["alias_of"] is None]
        assert pl.meta["tuning_cache"]["measurements"] == len(survivors)
        assert len(survivors) < len(valid)
        for c in valid:
            if c["alias_of"] is not None:
                surv = next(s for s in survivors
                            if s["label"] == c["alias_of"])
                assert c["measured_s"] == surv["measured_s"]
                assert c["predicted_s"] == surv["predicted_s"]
        # chosen is the measured argmin → ≤ the fixed optimized plan
        chosen = _rec_for(tuning, tuning["chosen"])
        fixed = _rec_for(tuning, FIXED_OPTIMIZED)
        assert chosen["measured_s"] <= fixed["measured_s"]
        # ranks follow predicted cost
        ranked = sorted(valid, key=lambda c: c["rank"])
        assert all(a["predicted_s"] <= b["predicted_s"]
                   for a, b in zip(ranked, ranked[1:]))

    def test_winner_executes_correctly(self):
        p, _ = build_3mm(n=32)
        pl = plan(p, policy="auto", backend="numpy", reps=1)
        from repro.core import run_host_oracle
        out, _ = execute(pl, backend="numpy",
                         fuse_loops=pl.meta["fuse_loops"], mode="compiled")
        oracle = run_host_oracle(p)
        np.testing.assert_allclose(out["out"], oracle["out"], rtol=2e-3,
                                   atol=1e-3)

    def test_optimized_predicted_cheaper_than_naive(self):
        """The cost model must reproduce the paper's §3 ordering on the
        worked example: fewer/hoisted transfers → lower predicted cost."""
        pl = _tuned_3mm("numpy")
        tuning = pl.meta["tuning"]
        opt = _rec_for(tuning, FIXED_OPTIMIZED)
        nv = _rec_for(tuning, "naive/streams2/fuse/nodonate")
        assert opt["predicted_s"] < nv["predicted_s"]
        assert opt["h2d_bytes"] < nv["h2d_bytes"]
        assert opt["d2h_bytes"] < nv["d2h_bytes"]

    def test_emitter_prints_tuning_verdict(self):
        from repro.core import emit
        pl = _tuned_3mm("numpy")
        text = emit(pl)
        assert "tuned, variant=" in text
        assert "predicted=" in text
        assert "measured=" in text


class TestCostModelGolden:
    """Predicted transfer schedule == golden == transfer_summary ×
    multipliers × dtype sizes == executed bytes."""

    @pytest.mark.parametrize("prog_key,builder", [
        ("3mm_n32", lambda: build_3mm(n=32)[0]),
        ("train_step_n4", lambda: plan_step_program(n_steps=4)),
    ])
    @pytest.mark.parametrize("policy", ["optimized", "naive"])
    def test_predicted_matches_golden_and_execution(self, prog_key,
                                                    builder, policy):
        p = builder()
        pl = plan(p, policy=policy)
        pred = predict_cost(pl, PlanConfig(policy=policy))
        golden = GOLDEN[prog_key][policy]
        for k, v in golden.items():
            assert pred[k] == v, f"{prog_key}/{policy}/{k}"
        _, stats = execute(pl, backend="numpy")
        assert pred["h2d_bytes"] == stats.h2d_bytes
        assert pred["d2h_bytes"] == stats.d2h_bytes
        assert pred["loads"] == stats.h2d_transfers
        assert pred["stores"] == stats.d2h_transfers
        assert pred["syncs"] == stats.syncs

    def test_loop_free_counts_equal_summary_times_sizes(self):
        """On a loop-free program every directive fires once, so the
        prediction is literally transfer_summary() × per-var nbytes."""
        p, _ = build_3mm(n=32)
        pl = plan(p)
        pred = predict_cost(pl, PlanConfig())
        s = transfer_summary(pl)
        nb = pl.meta["var_nbytes"]
        assert pred["loads"] == s["loads"]
        assert pred["stores"] == s["stores"]
        assert pred["h2d_bytes"] == s["loads"] * nb["A"]   # all n×n f32
        assert pred["d2h_bytes"] == s["stores"] * nb["G"]

    def test_fused_loop_costs_one_dispatch(self):
        """Whole-loop lowering shows up in the dispatch term: the same
        plan priced with fuse on/off differs exactly by the amortized
        per-iteration launches."""
        from repro.polybench import build
        p, _ = build("gemm", n=16, iters=4)
        pl = plan(p)
        fused = predict_cost(pl, PlanConfig(fuse_loops=True))
        unfused = predict_cost(pl, PlanConfig(fuse_loops=False))
        assert fused["kernel_launches"] == unfused["kernel_launches"] == 4
        assert fused["dispatches"] < unfused["dispatches"]
        assert fused["predicted_s"] < unfused["predicted_s"]

    def test_fused_nest_inside_impure_loop_relaunches(self):
        """A pure inner loop under an impure outer loop re-launches per
        outer iteration: the dispatch term must scale with the OUTER
        trip count, matching the compiled executor's fused_launches."""
        p = Program("half_pure")
        p.bind("A", np.ones((8, 8), np.float32))
        p.bind("C", np.ones((8, 8), np.float32))
        p.bind("h", np.ones((2,), np.float32))
        with p.loop(3):
            p.host(lambda xp, h: {"h": h * 1.5}, reads=("h",),
                   writes=("h",), name="hostwork")
            with p.loop(4):
                p.offload(lambda xp, A, C: {"C": 0.5 * (A @ C)},
                          reads=("A", "C"), writes=("C",), name="k")
        p.host(lambda xp, C, h: {"out": C[:1] + h[:1]},
               reads=("C", "h"), writes=("out",), name="consume")
        p.set_outputs("out")
        pl = plan(p)
        _, stats = execute(pl, mode="compiled", backend="numpy")
        pred = predict_cost(pl, PlanConfig(fuse_loops=True))
        # 3 fused inner-loop launches; transfers add theirs on top
        assert stats.fused_launches == 3
        assert pred["dispatches"] == 3 + pred["loads"] + pred["stores"]

    def test_pure_but_unfusable_nest_priced_per_iteration(self):
        """A pure outer loop whose body mixes a block WITH an inner loop
        never fuses whole (the compiler needs exactly one child node):
        the dispatch term must match the executor's per-outer-iteration
        launches, not price the nest as one dispatch."""
        p = Program("mixed_nest")
        p.bind("A", np.ones((8, 8), np.float32))
        p.bind("C", np.ones((8, 8), np.float32))
        with p.loop(3):
            p.offload(lambda xp, A, C: {"C": C + 0.1 * A},
                      reads=("A", "C"), writes=("C",), name="pre")
            with p.loop(4):
                p.offload(lambda xp, A, C: {"C": 0.5 * (A @ C)},
                          reads=("A", "C"), writes=("C",), name="k")
        p.host(lambda xp, C: {"out": C[:1]}, reads=("C",),
               writes=("out",), name="consume")
        p.set_outputs("out")
        pl = plan(p)
        assert set(pl.pure_device_loops()) == {0, 1}   # both pure...
        _, stats = execute(pl, mode="compiled", backend="numpy")
        assert stats.fused_launches == 6   # ...but only the inner fuses:
        # 3 × (1 segment launch + 1 inner-loop launch)
        pred = predict_cost(pl, PlanConfig(fuse_loops=True))
        assert pred["dispatches"] == 6 + pred["loads"] + pred["stores"]

    def test_flops_term_from_hlo(self):
        """The kernel term reuses the roofline HLO machinery: the 3mm
        chain of three n×n matmuls prices ≈ 3 × 2n³ FLOPs."""
        from repro.core.analysis import analyze
        from repro.core.tuner import _block_flops
        p, _ = build_3mm(n=32)
        pl = plan(p)
        flops = _block_flops(p, analyze(p).shapes)
        pred = predict_cost(pl, PlanConfig(), flops)
        assert pred["flops"] == pytest.approx(3 * 2 * 32 ** 3, rel=0.2)


class TestInvalidCandidates:
    def test_rejected_policy_never_ranked(self):
        """A placement policy whose plan the simulator rejects is
        recorded with valid=False and excluded from ranking/measuring —
        policy=auto never returns or ranks a broken plan."""
        class EagerStore(NaivePlacement):
            """Downloads a program input before anything ran on the
            device — a gap the simulator cannot fix (no valid device
            copy exists for the store) → rejected, not repaired."""
            policy = "eager-store"

            def place(self, draft):
                from repro.core import DelegateStore
                from repro.core.ir import PlanOp
                from repro.core.passes.linearize import Insertion
                ins = super().place(draft)
                first_input = sorted(draft.program.inputs)[0]
                return [Insertion(0, -1, PlanOp(
                    "directive",
                    directive=DelegateStore(var=first_input, group=0)))
                ] + ins

        register_placement("eager-store", EagerStore)
        try:
            p, _ = build_3mm(n=16)
            pl = tune(p, backend="numpy",
                      policies=("optimized", "eager-store"),
                      streams=(1, 2), reps=1)
            tuning = pl.meta["tuning"]
            bad = [c for c in tuning["candidates"]
                   if c["config"]["policy"] == "eager-store"]
            assert bad and all(not c["valid"] for c in bad)
            assert all("invalid plan" in c["error"] for c in bad)
            assert all(c["rank"] is None and c["measured_s"] is None
                       for c in bad)
            assert tuning["chosen"].startswith("optimized")
        finally:
            _PLACEMENTS.pop("eager-store", None)

    def test_all_invalid_raises(self):
        class Broken(NaivePlacement):
            policy = "broken"

            def place(self, draft):
                from repro.core import DelegateStore
                from repro.core.ir import PlanOp
                from repro.core.passes.linearize import Insertion
                first_input = sorted(draft.program.inputs)[0]
                return [Insertion(0, -1, PlanOp(
                    "directive",
                    directive=DelegateStore(var=first_input, group=0)))
                ] + super().place(draft)

        register_placement("broken", Broken)
        try:
            p, _ = build_3mm(n=8)
            with pytest.raises(RuntimeError):
                tune(p, backend="numpy", policies=("broken",), reps=1)
        finally:
            _PLACEMENTS.pop("broken", None)


class TestTunerKnobs:
    def test_top_k_limits_measurement(self):
        """top_k bounds the number of MEASURED execution classes (merged
        configs still inherit the class result)."""
        p, _ = build_3mm(n=16)
        pl = tune(p, backend="numpy", top_k=1, reps=1)
        valid = [c for c in pl.meta["tuning"]["candidates"] if c["valid"]]
        assert pl.meta["tuning_cache"]["measurements"] == 1
        measured = [c for c in valid if c["measured_s"] is not None
                    and c["alias_of"] is None]
        assert len(measured) == 1 and measured[0]["rank"] == 1
        # the other class (naive placement) was skipped entirely
        assert any(c["measured_s"] is None for c in valid)
        assert pl.meta["tuning"]["chosen"] == measured[0]["label"]

    def test_measure_off_ranks_by_prediction(self):
        p, _ = build_3mm(n=16)
        pl = tune(p, backend="numpy", measure=False)
        tuning = pl.meta["tuning"]
        valid = [c for c in tuning["candidates"] if c["valid"]]
        assert all(c["measured_s"] is None for c in valid)
        assert tuning["chosen"] == min(
            valid, key=lambda c: c["predicted_s"])["label"]

    def test_abstract_inputs_skip_measurement(self):
        import jax
        p = Program("abstract")
        p.bind("A", jax.ShapeDtypeStruct((8, 8), np.float32))
        p.offload(lambda xp, A: {"B": A * 2.0}, reads=("A",),
                  writes=("B",), name="k")
        p.host(lambda xp, B: {"o": B}, reads=("B",), writes=("o",),
               name="c")
        p.set_outputs("o")
        pl = tune(p, backend="numpy")
        assert all(c["measured_s"] is None
                   for c in pl.meta["tuning"]["candidates"])

    def test_plan_rejects_tuner_kwargs_for_fixed_policies(self):
        p, _ = build_3mm(n=8)
        with pytest.raises(TypeError):
            plan(p, top_k=2)                 # tuner knob, fixed policy
        with pytest.raises(TypeError):
            plan(p, policy="naive", reps=3)
        with pytest.raises(TypeError):
            plan(p, backend="numpy")         # backend is auto-only too

    def test_execute_follows_winner_fuse_flag(self):
        """execute() defaults fuse_loops from the plan's meta, so a
        tuned nofuse winner runs the variant the tuner measured without
        the winner_exec_kwargs side-channel."""
        from repro.polybench import build
        p, _ = build("gemm", n=16, iters=5)
        pl = plan(p)
        pl.meta["fuse_loops"] = False
        _, s = execute(pl, mode="compiled", backend="numpy")
        assert s.fused_launches == 5          # per-iteration path
        _, s2 = execute(pl, mode="compiled", backend="numpy",
                        fuse_loops=True)      # explicit arg still wins
        assert s2.fused_launches == 1

    def test_plan_auto_pins_stream_axis_from_n_streams(self):
        p, _ = build_3mm(n=8)
        pl = plan(p, policy="auto", backend="numpy", n_streams=1,
                  measure=False)
        for c in pl.meta["tuning"]["candidates"]:
            assert c["config"]["n_streams"] == 1

    def test_nodonate_candidates_never_measured_with_donation(self):
        """A donate=True backend handed to tune() must not leak donation
        into nodonate candidates (and vice versa): _measure swaps to the
        matching twin in both directions."""
        from repro.core import JaxDeviceBackend
        from repro.core.tuner import _donation_variant
        be = JaxDeviceBackend(donate=True)
        off = _donation_variant(be, False)
        assert isinstance(off, JaxDeviceBackend) and not off.donate
        assert _donation_variant(off, True).donate
        assert _donation_variant(be, True) is be
        assert _donation_variant(off, False) is off

    def test_winner_exec_kwargs_honor_variant(self):
        from repro.core import JaxDeviceBackend, winner_exec_kwargs
        p, _ = build_3mm(n=16)
        pl = plan(p)
        pl.meta.update(fuse_loops=False, donate=True)
        kw = winner_exec_kwargs(pl, "jax")
        assert kw["fuse_loops"] is False
        assert isinstance(kw["backend"], JaxDeviceBackend)
        assert kw["backend"].donate
        pl.meta["donate"] = False
        assert not winner_exec_kwargs(pl, "jax")["backend"].donate
        out, _ = execute(pl, **winner_exec_kwargs(pl, "numpy"))
        assert set(out) == set(p.outputs)

    def test_explicit_config_list(self):
        p, _ = build_3mm(n=16)
        cfgs = [PlanConfig(policy="optimized", n_streams=1),
                PlanConfig(policy="naive", n_streams=1)]
        pl = tune(p, backend="numpy", configs=cfgs, reps=1)
        assert len(pl.meta["tuning"]["candidates"]) == 2


class TestKernelAxis:
    """ISSUE 6: the tuner's kernel tile/block axis.  The flash-attention
    step program carries a kernel-tagged block, so the candidate grid
    grows a per-kernel variant choice, priced by the two-level roofline
    and re-executed through ``winner_exec_kwargs``."""

    GRID = dict(policies=("optimized",), streams=(1,), fuse=(True,),
                donate=(False,))

    def _tuned(self, **kw):
        from repro.optim import attention_step_program
        p = attention_step_program(n_steps=1, interpret=True)
        kw = dict(self.GRID, reps=1, **kw)
        return p, plan(p, policy="auto", **kw)

    def test_enumerates_at_least_three_variants(self):
        p, pl = self._tuned(measure=False)
        valid = [c for c in pl.meta["tuning"]["candidates"] if c["valid"]]
        kvs = {json.dumps(c["config"]["kernel_variants"]) for c in valid}
        assert len(kvs) >= 3
        # every candidate's label names the tile it launches
        assert all("flash_attention[" in c["label"] for c in valid)

    def test_kernel_s_differs_across_tile_candidates(self):
        """The tentpole property: kernel_s is no longer plan-invariant —
        smaller q tiles re-read K/V more, so HBM bytes (and kernel_s)
        differ across candidates of the same placement."""
        p, pl = self._tuned(measure=False)
        valid = [c for c in pl.meta["tuning"]["candidates"] if c["valid"]]
        by_bq = {}
        for c in valid:
            bq = dict(dict(c["config"]["kernel_variants"])
                      ["flash_attention"])["block_q"]
            by_bq.setdefault(bq, c)
        assert set(by_bq) == {64, 128}
        assert by_bq[64]["kernel_bytes"] > by_bq[128]["kernel_bytes"]
        assert by_bq[64]["kernel_s"] != by_bq[128]["kernel_s"]
        assert by_bq[64]["flops"] == by_bq[128]["flops"]

    def test_winner_variant_recorded_and_reexecuted(self):
        from repro.core import winner_exec_kwargs
        p, pl = self._tuned()
        t = pl.meta["tuning"]
        kv = t["kernel_variants"]
        assert set(kv) == {"flash_attention"}
        assert set(kv["flash_attention"]) == {"block_q", "block_k"}
        assert pl.meta["kernel_variants"] == kv
        assert kv["flash_attention"]["block_q"] in (64, 128)
        # the chosen label names exactly the recorded variant
        assert f"block_q={kv['flash_attention']['block_q']}" \
            in t["chosen"]
        kw = winner_exec_kwargs(pl)
        assert kw["kernel_variants"] == kv
        out, _ = execute(pl, dict(p.inputs), **kw)
        # numerics are tile-invariant: another variant agrees
        other = {"flash_attention": {"block_q": 64, "block_k": 64}}
        out2, _ = execute(pl, dict(p.inputs), mode="compiled",
                          kernel_variants=other, backend=kw["backend"])
        np.testing.assert_allclose(np.asarray(out["final_loss"]),
                                   np.asarray(out2["final_loss"]),
                                   rtol=1e-5)

    def test_dominance_pruning_keys_on_variant(self):
        """Distinct tiles are distinct execution classes (measured
        separately); identical launches merge."""
        p, pl = self._tuned()
        valid = [c for c in pl.meta["tuning"]["candidates"] if c["valid"]]
        survivors = [c for c in valid if c["alias_of"] is None]
        kvs = {json.dumps(c["config"]["kernel_variants"])
               for c in survivors}
        assert len(kvs) == len(survivors)
        assert all(c["measured_s"] is not None for c in survivors)

    def test_kernel_free_grid_and_labels_unchanged(self):
        """Programs without kernel-tagged blocks keep the plain policy
        grid (4 policies x 2 streams x 2 fuse x 2 donate since the
        pipeline policy landed): no kernel suffix in any label, empty
        variant maps."""
        p, _ = build_3mm(n=16)
        pl = plan(p, policy="auto", backend="numpy", measure=False)
        valid = [c for c in pl.meta["tuning"]["candidates"] if c["valid"]]
        assert len(valid) == 64
        assert all("[" not in c["label"] for c in valid)
        assert pl.meta["tuning"]["kernel_variants"] == {}

    def test_cache_roundtrip_restores_variant(self, tmp_path):
        from repro.core import TuneCache
        from repro.optim import attention_step_program
        tc = TuneCache(tmp_path / "kv")
        p1 = attention_step_program(n_steps=1, interpret=True)
        pl1 = tune(p1, reps=1, cache=tc, **self.GRID)
        p2 = attention_step_program(n_steps=1, interpret=True)
        pl2 = tune(p2, reps=1, cache=tc, **self.GRID)
        assert pl2.meta["tuning_cache"]["hit"] is True
        assert pl2.meta["tuning"] == pl1.meta["tuning"]
        assert pl2.meta["kernel_variants"] == pl1.meta["kernel_variants"]
        assert pl2.meta["kernel_variants"]
