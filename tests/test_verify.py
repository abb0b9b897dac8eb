"""Static plan verifier: golden diagnostics + adversarial mutation fuzz.

The contract under test (ISSUE 7 tentpole):

  * every violation class carries an op-indexed diagnostic — the golden
    tests seed one mutation per class and pin kind/var/op_index,
  * the verifier has **no false negatives** against the runtime: any
    mutant the executor-vs-host-oracle diff catches (exception or wrong
    output) is statically flagged as an error (mutation fuzzer),
  * lints never fail verification — the paper's naive-3MM redundancies
    (duplicate upload of E/F, dead store of E/F) surface as lints on a
    plan that still verifies ok,
  * ``PlanVerificationError`` is a ``PlanExecutionError``: callers
    guarding ``execute()`` see one exception family whether the failure
    is caught statically (``REPRO_VERIFY=1``) or at runtime.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (Plan, PlanExecutionError, PlanOp,
                        PlanVerificationError, execute, naive_plan, plan,
                        run_host_oracle, verify_plan)
from repro.core.ir import AdvancedLoad, DelegateStore, Release
from repro.core.verify import VIOLATION_KINDS
from repro.optim import plan_step_program
from repro.polybench import build


def clone(pl, ops=None):
    """A mutable copy sharing program/groups/io_table; drops any cached
    compiled artifact so the mutant is re-lowered from its own ops."""
    return Plan(program=pl.program,
                ops=list(pl.ops if ops is None else ops),
                groups=pl.groups, io_table=pl.io_table,
                meta={k: v for k, v in pl.meta.items()
                      if k != "_compiled"})


def _find(pl, cls, **attrs):
    """(index, directive) of the first directive of type ``cls`` whose
    attributes match ``attrs``."""
    for i, op in enumerate(pl.ops):
        if op.kind == "directive" and isinstance(op.directive, cls):
            if all(getattr(op.directive, k) == v for k, v in attrs.items()):
                return i, op.directive
    raise AssertionError(f"no {cls.__name__} matching {attrs}")


def _regroup(pl, i):
    d = pl.ops[i].directive
    m = clone(pl)
    m.ops[i] = PlanOp("directive",
                      directive=dataclasses.replace(d, group=d.group + 7))
    return m


@pytest.fixture(scope="module")
def p3mm():
    return build("3mm", n=16)[0]


class TestGoldenDiagnostics:
    """One seeded mutation per violation class, diagnostics pinned."""

    def test_async_race_regrouped_load(self, p3mm):
        """A load moved to a foreign group: the consuming callsite no
        longer completes its in-flight upload — race at the block op."""
        pl = plan(p3mm)
        i, d = _find(pl, AdvancedLoad, var="A")
        assert d.asynchronous and d.stream
        rep = verify_plan(_regroup(pl, i), collect_lints=False)
        assert not rep.ok
        v = next(v for v in rep.errors if v.kind == "async-race")
        assert v.var == "A" and v.severity == "error"
        # anchored at the consuming block op, after the load
        assert i < v.op_index < len(pl.ops)
        assert pl.ops[v.op_index].kind == "block"
        assert "in flight" in v.message

    def test_stale_host_read_deleted_store(self, p3mm):
        """Store of G deleted: the host consumer reads a host copy the
        device-dirty value never reached."""
        pl = plan(p3mm)
        m = clone(pl, [op for op in pl.ops
                       if not (op.kind == "directive"
                               and isinstance(op.directive, DelegateStore))])
        rep = verify_plan(m, collect_lints=False)
        v = next(v for v in rep.errors if v.kind == "stale-host-read")
        assert v.var == "G"
        assert m.ops[v.op_index].kind == "block"
        assert "missing delegatedstore" in v.message

    def test_use_after_release_early_release(self, p3mm):
        """A Release inserted after the first codelet frees the loaded
        inputs the later codelets still read."""
        pl = plan(p3mm)
        first_blk = next(i for i, op in enumerate(pl.ops)
                         if op.kind == "block")
        ops = list(pl.ops)
        ops.insert(first_blk + 1,
                   PlanOp("directive", directive=Release(group=0)))
        rep = verify_plan(clone(pl, ops), collect_lints=False)
        vs = [v for v in rep.errors if v.kind == "use-after-release"]
        assert vs and all(v.op_index > first_blk + 1 for v in vs)
        assert {v.var for v in vs} == {"C", "D"}

    def test_use_after_donation_gemm_inout(self):
        """gemm's C is inout: regrouping its load leaves the h2d DMA live
        when donation recycles the buffer — flagged only under donate."""
        p = build("gemm", n=16)[0]
        pl = plan(p)
        i, _ = _find(pl, AdvancedLoad, var="C")
        m = _regroup(pl, i)
        rep = verify_plan(m, donate=True, collect_lints=False)
        v = next(v for v in rep.errors if v.kind == "use-after-donation")
        assert v.var == "C" and m.ops[v.op_index].kind == "block"
        assert "donat" in v.message
        # same mutant without donation: the race remains, donation
        # hazard does not
        rep_nd = verify_plan(m, donate=False, collect_lints=False)
        assert "use-after-donation" not in rep_nd.kinds()
        assert "async-race" in rep_nd.kinds()

    def test_placement_gap_deleted_load(self, p3mm):
        pl = plan(p3mm)
        i, _ = _find(pl, AdvancedLoad, var="A")
        rep = verify_plan(clone(pl, pl.ops[:i] + pl.ops[i + 1:]),
                          collect_lints=False)
        v = next(v for v in rep.errors if v.kind == "placement-gap")
        assert v.var == "A" and "missing advancedload" in v.message

    def test_illegal_kernel_tile(self):
        from repro.optim import attention_step_program
        p = attention_step_program(n_steps=1, interpret=True)
        pl = plan(p)
        rep = verify_plan(
            pl, kernel_variants={"flash_attention":
                                 {"block_q": 77, "block_k": 64}},
            collect_lints=False)
        v = next(v for v in rep.errors if v.kind == "illegal-kernel-tile")
        assert "flash_attention" in v.message and "77" in v.message

    def test_malformed_unclosed_loop(self):
        pl = plan(plan_step_program(n_steps=2))
        m = clone(pl, [op for op in pl.ops if op.kind != "loop_end"])
        rep = verify_plan(m, collect_lints=False)
        v = next(v for v in rep.errors if v.kind == "malformed")
        assert "never closed" in v.message

    def test_redundant_directive_is_lint_not_error(self, p3mm):
        """A duplicated upload is waste, not breakage: the report stays
        ok and the finding is a lint."""
        pl = plan(p3mm)
        i, _ = _find(pl, AdvancedLoad, var="A")
        rep = verify_plan(clone(pl, pl.ops[:i] + [pl.ops[i]] + pl.ops[i:]))
        assert rep.ok and not rep.errors
        assert any(v.kind == "redundant-directive"
                   and v.severity == "lint" and v.var == "A"
                   for v in rep.lints)

    def test_naive_3mm_reproduces_paper_lints(self, p3mm):
        """The paper's 3MM insight: the naive policy uploads E and F that
        are already device-resident and downloads them for no host
        reader.  The verifier surfaces exactly those as lints."""
        rep = verify_plan(naive_plan(p3mm))
        assert rep.ok
        lint_vars = {v.var for v in rep.lints
                     if v.kind == "redundant-directive"}
        assert lint_vars == {"E", "F"}
        msgs = " ".join(v.message for v in rep.lints)
        assert "duplicate upload" in msgs and "dead store" in msgs

    def test_every_kind_is_registered(self):
        assert set(VIOLATION_KINDS) >= {
            "async-race", "stale-host-read", "use-after-release",
            "use-after-donation", "placement-gap", "illegal-kernel-tile",
            "redundant-directive", "malformed"}

    def test_violation_str_is_op_indexed(self, p3mm):
        pl = plan(p3mm)
        i, _ = _find(pl, AdvancedLoad, var="A")
        rep = verify_plan(clone(pl, pl.ops[:i] + pl.ops[i + 1:]),
                          collect_lints=False)
        s = str(rep.errors[0])
        assert "@op" in s and "placement-gap" in s


class TestExceptionContract:
    def test_verification_error_is_execution_error(self, p3mm):
        assert issubclass(PlanVerificationError, PlanExecutionError)
        pl = plan(p3mm)
        i, _ = _find(pl, AdvancedLoad, var="A")
        broken = clone(pl, pl.ops[:i] + pl.ops[i + 1:])
        with pytest.raises(PlanExecutionError) as ei:
            execute(broken, backend="numpy", verify=True)
        assert isinstance(ei.value, PlanVerificationError)
        assert ei.value.report.errors

    def test_verify_off_reaches_runtime_check(self, p3mm):
        """verify=False skips the static pass; the runtime's own
        residency check still refuses the broken plan."""
        pl = plan(p3mm)
        i, _ = _find(pl, AdvancedLoad, var="A")
        broken = clone(pl, pl.ops[:i] + pl.ops[i + 1:])
        with pytest.raises(PlanExecutionError) as ei:
            execute(broken, backend="numpy", verify=False)
        assert not isinstance(ei.value, PlanVerificationError)

    def test_planner_records_verdict(self, p3mm):
        verdict = plan(p3mm).meta["verify"]
        assert verdict["ok"] is True and verdict["n_errors"] == 0
        assert verdict["checked_ops"] > 0

    def test_emitter_annotates_verdict(self, p3mm):
        from repro.core import emit
        assert "#pragma omp2hmpp verified, ok=true" in emit(plan(p3mm))


# -- adversarial mutation fuzz ---------------------------------------------

def _mutants(pl):
    """Deterministic single-op mutations over every directive position:
    delete, duplicate, regroup (+7), restream (+1), swap-adjacent."""
    ops = pl.ops
    didx = [i for i, op in enumerate(ops) if op.kind == "directive"]
    for i in didx:
        yield f"del@{i}", ops[:i] + ops[i + 1:]
        yield f"dup@{i}", ops[:i] + [ops[i]] + ops[i:]
        d = ops[i].directive
        if hasattr(d, "group"):
            yield (f"regroup@{i}",
                   ops[:i] + [PlanOp("directive",
                                     directive=dataclasses.replace(
                                         d, group=d.group + 7))]
                   + ops[i + 1:])
        if getattr(d, "stream", None):
            yield (f"restream@{i}",
                   ops[:i] + [PlanOp("directive",
                                     directive=dataclasses.replace(
                                         d, stream=d.stream + 1))]
                   + ops[i + 1:])
    for i in didx:
        if i + 1 in didx:
            yield f"swap@{i}", ops[:i] + [ops[i + 1], ops[i]] + ops[i + 2:]


def _oracle_catches(program, mutant, oracle):
    """Ground truth: does the runtime (numpy backend, residency checks
    on, static verify OFF) reject the mutant or corrupt its outputs?"""
    try:
        out, _ = execute(mutant, backend="numpy", check=True, verify=False)
    except Exception as e:                 # noqa: BLE001 — any crash counts
        return f"{type(e).__name__}"
    for k in program.outputs:
        if not np.allclose(out[k], oracle[k], rtol=1e-5, atol=1e-6):
            return f"mismatch:{k}"
    return None


class TestMutationFuzzer:
    """No false negatives: every mutant the executor-vs-oracle diff
    catches must already be a verifier error."""

    PROGRAMS = ("3mm", "gemm", "mvt")

    def test_verifier_flags_every_oracle_caught_mutant(self):
        total, false_negatives = 0, []
        for name in self.PROGRAMS:
            p = build(name, n=16)[0]
            oracle = run_host_oracle(p)
            for planner in (plan, naive_plan):
                pl = planner(p)
                for label, mops in _mutants(pl):
                    total += 1
                    m = clone(pl, mops)
                    rep = verify_plan(m, collect_lints=False)
                    caught = _oracle_catches(p, m, oracle)
                    if caught and not rep.errors:
                        false_negatives.append(
                            f"{name}/{pl.meta['policy']}/{label}: "
                            f"runtime caught [{caught}], verifier ok")
        assert total >= 200, f"mutation corpus too small: {total}"
        assert not false_negatives, "\n".join(false_negatives)


class TestHypothesisFuzzer:
    """Randomized mutation chains (1-3 stacked single-op mutations) keep
    the no-false-negative invariant.  Skipped where hypothesis is not
    installed (it is in requirements-dev.txt, so CI always runs this)."""

    def test_stacked_mutations_keep_invariant(self):
        hyp = pytest.importorskip("hypothesis")
        st = pytest.importorskip("hypothesis.strategies")

        p = build("3mm", n=16)[0]
        oracle = run_host_oracle(p)
        base = plan(p)
        pool = list(_mutants(base))

        @hyp.given(st.lists(st.integers(0, len(pool) - 1),
                            min_size=1, max_size=3))
        @hyp.settings(max_examples=60, deadline=None)
        def run(picks):
            m = clone(base)
            for j in picks:
                # re-derive the mutation on the *current* ops when the
                # index is still a directive; else skip that pick
                label, _ = pool[j]
                kind, pos = label.split("@")
                pos = int(pos)
                ops = m.ops
                if pos >= len(ops) or ops[pos].kind != "directive":
                    continue
                for lbl, mops in _mutants(m):
                    if lbl == f"{kind}@{pos}":
                        m = clone(m, mops)
                        break
            rep = verify_plan(m, collect_lints=False)
            caught = _oracle_catches(p, m, oracle)
            assert not (caught and not rep.errors), (
                f"runtime caught [{caught}] but verifier passed "
                f"{[str(o.directive) for o in m.ops if o.kind == 'directive']}")

        run()


class TestMeshPlacement:
    """ISSUE 9: sharded-plan validation.  The mesh record is plain JSON
    (the tuner's meta["mesh"]), so these run without any device mesh."""

    def _mesh(self, specs, dropped=(), shape=(2, 4),
              axes=("data", "model")):
        return {"shape": list(shape), "axes": list(axes),
                "placement": "fsdp", "n_devices": 8,
                "specs": specs, "dropped": [list(d) for d in dropped]}

    def test_valid_sharded_plan_verifies_clean(self, p3mm):
        pl = plan(p3mm)
        mesh = self._mesh({v: ["data", None] for v in "ABCDEF"})
        rep = verify_plan(pl, mesh=mesh)
        assert rep.ok and not rep.errors

    def test_meta_mesh_is_picked_up_by_default(self, p3mm):
        pl = plan(p3mm)
        m = clone(pl)
        m.meta["mesh"] = self._mesh({"nosuchvar": ["data"]})
        rep = verify_plan(m)
        assert any(v.kind == "mesh-placement" for v in rep.errors)

    def test_unknown_var_in_spec(self, p3mm):
        pl = plan(p3mm)
        rep = verify_plan(pl, mesh=self._mesh({"zzz": ["data"]}))
        v = next(v for v in rep.errors if v.kind == "mesh-placement")
        assert v.var == "zzz"

    def test_unknown_mesh_axis(self, p3mm):
        pl = plan(p3mm)
        rep = verify_plan(pl, mesh=self._mesh({"A": ["expert", None]}))
        assert any(v.kind == "mesh-placement" and v.var == "A"
                   for v in rep.errors)

    def test_non_dividing_shard_rejected(self, p3mm):
        """3mm n=16: dim 16 over a 3-way axis does not divide — the
        divisibility guard should have dropped it upstream."""
        pl = plan(p3mm)
        mesh = self._mesh({"A": ["model", None]}, shape=(2, 3))
        rep = verify_plan(pl, mesh=mesh)
        assert any(v.kind == "mesh-placement" and v.var == "A"
                   for v in rep.errors)

    def test_drop_without_spec_is_a_gap(self, p3mm):
        """A divisibility-guard drop whose var then has NO spec at all:
        the placement has a gap (the var's distribution is undefined)."""
        pl = plan(p3mm)
        mesh = self._mesh({"A": ["data", None]},
                          dropped=[("B", "heads", 40)])
        rep = verify_plan(pl, mesh=mesh)
        assert any(v.kind == "mesh-placement" and v.var == "B"
                   for v in rep.errors)
        # an explicit replicated spec closes the gap
        mesh2 = self._mesh({"A": ["data", None], "B": []},
                           dropped=[("B", "heads", 40)])
        assert verify_plan(pl, mesh=mesh2).ok

    def test_sharded_read_is_a_sync_point(self, p3mm):
        """The async-race golden mutation (load regrouped away from its
        callsite) is NOT a race when the operand is sharded: the SPMD
        dispatch waits on every shard of the distributed upload."""
        pl = plan(p3mm)
        i, d = _find(pl, AdvancedLoad, var="A")
        assert d.asynchronous
        m = _regroup(pl, i)
        assert any(v.kind == "async-race"
                   for v in verify_plan(m, collect_lints=False).errors)
        mesh = self._mesh({"A": ["data", None]})
        rep = verify_plan(m, collect_lints=False, mesh=mesh)
        assert not any(v.kind == "async-race" for v in rep.errors)
