"""Backend/stream runtime + compiled execution path.

The contract under test (ISSUE 1 tentpole):

  * ``execute(plan, mode="compiled")`` is bitwise-identical to
    ``mode="interpreted"`` on the same backend, and matches the pure-host
    oracle to the usual float tolerance,
  * the *logical* ``ExecStats`` transfer counts are mode-invariant,
  * the compiled path actually fuses (fewer jit entries than logical
    kernel calls where segments hold several blocks),
  * every registered backend honors the residency discipline.
"""
import numpy as np
import pytest

from repro.core import (JaxDeviceBackend, NumpyHostBackend,
                        PinnedHostBackend, PlanExecutionError, Synchronize,
                        compile_plan, execute, get_backend, naive_plan,
                        plan, run_host_oracle)
from repro.core.ir import AdvancedLoad, Program
from repro.optim import plan_step_program
from repro.polybench import build_3mm


def _modes_equal(p, planner=plan, backend=None):
    pl = planner(p)
    out_i, s_i = execute(pl, mode="interpreted", backend=backend)
    out_c, s_c = execute(pl, mode="compiled", backend=backend)
    for k in p.outputs:
        np.testing.assert_array_equal(
            out_i[k], out_c[k],
            err_msg=f"compiled vs interpreted mismatch for {k!r}")
    assert s_i.transfer_counts() == s_c.transfer_counts()
    return out_c, s_i, s_c


class TestCompiledEquivalence:
    def test_train_step_program(self):
        p = plan_step_program(n_steps=4)
        out, _, _ = _modes_equal(p)
        oracle = run_host_oracle(p)
        np.testing.assert_allclose(out["w"], oracle["w"], rtol=1e-5)
        np.testing.assert_allclose(out["final_loss"], oracle["final_loss"],
                                   rtol=1e-5)

    def test_train_step_program_naive(self):
        _modes_equal(plan_step_program(n_steps=3), planner=naive_plan)

    def test_polybench_3mm(self):
        p, _ = build_3mm(n=48)
        out, _, _ = _modes_equal(p)
        oracle = run_host_oracle(p)
        np.testing.assert_allclose(out["out"], oracle["out"], rtol=2e-3,
                                   atol=1e-3)

    def test_polybench_3mm_naive(self):
        p, _ = build_3mm(n=48)
        _modes_equal(p, planner=naive_plan)

    def test_transfer_count_parity_and_fusion(self):
        """Counts are the plan's logical schedule (mode-invariant); the
        compiled path executes it in fewer jit entries."""
        p, _ = build_3mm(n=32)
        _, s_i, s_c = _modes_equal(p)
        assert s_i.fused_launches == 0
        # 3mm's three matmuls sit in one group with no host block between
        # them: one fused launch for three logical kernel calls
        assert s_c.kernel_calls == 3
        assert s_c.fused_launches == 1

    def test_loop_body_lowered_whole(self):
        """A pure-device loop body rolls into ONE fused launch for the
        whole loop (lax.fori_loop): kernel_calls still scales with trip
        count (logical parity with the interpreter) while fused_launches
        counts a single dispatch."""
        from repro.polybench import build
        p, _ = build("gemm", n=32, iters=5)
        _, s_i, s_c = _modes_equal(p)
        assert s_c.kernel_calls == 5
        assert s_c.fused_launches == 1

    def test_loop_fusion_can_be_disabled(self):
        """fuse_loops=False keeps the PR-1 per-iteration segment path:
        one fused launch per iteration, same outputs."""
        from repro.polybench import build
        p, _ = build("gemm", n=32, iters=5)
        pl = plan(p)
        out_f, s_f = execute(pl, mode="compiled")
        out_n, s_n = execute(pl, mode="compiled", fuse_loops=False)
        for k in p.outputs:
            np.testing.assert_array_equal(out_f[k], out_n[k])
        assert s_f.fused_launches == 1
        assert s_n.fused_launches == 5
        assert s_f.transfer_counts() == s_n.transfer_counts()

    def test_compiled_mode_checks_residency(self):
        """A hand-broken plan (load removed) still raises."""
        p, _ = build_3mm(n=16)
        pl = plan(p)
        drop = next(op for op in pl.ops
                    if op.kind == "directive"
                    and isinstance(op.directive, AdvancedLoad))
        pl.ops.remove(drop)
        with pytest.raises(PlanExecutionError):
            execute(pl, mode="compiled")

    def test_unknown_mode_rejected(self):
        p, _ = build_3mm(n=16)
        with pytest.raises(ValueError):
            execute(plan(p), mode="eager")


class TestBackends:
    @pytest.mark.parametrize("name", ["numpy", "jax", "pinned"])
    def test_all_backends_run_both_modes(self, name):
        be = get_backend(name)
        p = plan_step_program(n_steps=2)
        out_i, s_i = execute(plan(p), mode="interpreted", backend=be)
        out_c, s_c = execute(plan(p), mode="compiled", backend=be)
        for k in p.outputs:
            np.testing.assert_array_equal(out_i[k], out_c[k])
        assert s_i.transfer_counts() == s_c.transfer_counts()
        oracle = run_host_oracle(p)
        np.testing.assert_allclose(out_c["w"], oracle["w"], rtol=1e-5)

    def test_numpy_backend_is_exact_vs_oracle(self):
        """Pure-host backend: block bodies run under numpy, so results are
        bitwise-equal to the oracle, not just close."""
        p = plan_step_program(n_steps=3)
        out, _ = execute(plan(p), backend=NumpyHostBackend())
        oracle = run_host_oracle(p)
        np.testing.assert_array_equal(out["w"], oracle["w"])

    def test_stream_events_make_sync_a_wait_point(self):
        """Uploads enqueue events on their directive's stream; sync drains
        exactly that stream."""
        be = JaxDeviceBackend()
        h = be.upload(np.ones((8, 8), np.float32), stream=1)
        assert be._pending  # event recorded
        be.sync(1)
        assert not any(be._pending.values())
        h.block_until_ready()

    def test_planner_assigns_streams_per_group(self):
        p, _ = build_3mm(n=16)
        pl = plan(p)
        loads = pl.directives(AdvancedLoad)
        syncs = pl.directives(Synchronize)
        assert loads and all(d.stream >= 1 for d in loads)
        assert syncs and all(d.stream >= 1 for d in syncs)
        # one group -> one transfer stream shared by its transfers
        assert len({d.stream for d in loads}) == 1

    def test_pinned_backend_degrades_on_cpu(self):
        """The CPU device has a pinned_host space too: the pinned backend
        stages the round trip through it and computes on device memory."""
        be = PinnedHostBackend()
        assert be._pinned.memory_kind == "pinned_host"
        x = np.arange(16, dtype=np.float32)
        h = be.upload(x, stream=1)
        be.sync(1)
        assert h.sharding.memory_kind == "device"
        np.testing.assert_array_equal(be.download(h), x)

    def test_pinned_backend_refuses_device_without_pinned_space(
            self, monkeypatch):
        import repro.optim.offload as offload
        monkeypatch.setattr(offload, "host_memory_kind", lambda dev: None)
        with pytest.raises(RuntimeError, match="pinned_host"):
            PinnedHostBackend()

    def test_get_backend_memoizes_by_name(self):
        assert get_backend() is get_backend()
        assert get_backend() is get_backend("jax")
        assert get_backend("numpy") is get_backend("numpy")
        with pytest.raises(ValueError):
            get_backend("cuda-streams")

    def test_free_retires_pending_events(self):
        """release of an in-flight buffer must not poison a later sync
        (the default backend is shared process-wide)."""
        from repro.core import DeviceResidency
        rd = DeviceResidency()
        rd.put_host("x", np.ones((4, 4), np.float32))
        rd.prefetch("x")
        rd.release("x")
        rd.wait()    # must not raise on the deleted buffer
        p, _ = build_3mm(n=8)
        execute(plan(p))   # shared default backend still healthy

    def test_compile_plan_reused_across_executions(self):
        """execute(mode="compiled") caches the lowering on the plan, so
        repeated runs (the benchmark loop) skip re-lowering."""
        p, _ = build_3mm(n=16)
        pl = plan(p)
        execute(pl, mode="compiled")
        first, _ = pl.meta["_compiled"]["jax"]
        execute(pl, mode="compiled")
        assert pl.meta["_compiled"]["jax"][0] is first

    def test_compiled_cache_invalidated_on_plan_mutation(self):
        """Mutating plan.ops after a compiled run must re-lower, keeping
        count parity with the interpreter for the mutated plan."""
        p, _ = build_3mm(n=16)
        pl = naive_plan(p)
        _, s0 = execute(pl, mode="compiled")
        drop = next(op for op in pl.ops
                    if op.kind == "directive"
                    and isinstance(op.directive, Synchronize))
        pl.ops.remove(drop)
        _, s1 = execute(pl, mode="compiled")
        assert s1.syncs == s0.syncs - 1
        _, s_i = execute(pl, mode="interpreted")
        assert s1.transfer_counts() == s_i.transfer_counts()

    def test_emitter_shows_stream_attribute(self):
        from repro.core import emit
        p, _ = build_3mm(n=16)
        text = emit(plan(p))
        assert "stream=" in text
        assert "asynchronous" in text


class TestHazardSplit:
    def test_store_then_load_same_var_splits_segment(self):
        """An upload after an in-segment download of the same variable must
        observe the downloaded host value — the naive 3mm plan hits this
        (E stored after mm_E, loaded again at mm_G) and stays correct."""
        p, _ = build_3mm(n=24)
        pl = naive_plan(p)
        compiled = compile_plan(pl, get_backend())
        segs = [item for item in compiled.schedule if item[0] == "seg"]
        with_blocks = [s for _, s in segs if s.blocks]
        assert len(with_blocks) >= 2   # split at the store->load hazard
        _modes_equal(p, planner=naive_plan)

    def test_load_after_device_write_raises_in_both_modes(self):
        """An upload whose var a block just wrote (host copy stale) is
        rejected by the interpreter — the compiled path must split the
        segment and reject it identically, not upload stale data."""
        from repro.core import PlanOp
        p, _ = build_3mm(n=16)
        pl = plan(p)
        blk_pos = next(i for i, op in enumerate(pl.ops)
                       if op.kind == "block"
                       and p.blocks[op.block_idx].writes == ("E",))
        bad = PlanOp("directive",
                     directive=AdvancedLoad(var="E", group=0, stream=1))
        pl.ops.insert(blk_pos + 1, bad)
        with pytest.raises(PlanExecutionError):
            execute(pl, mode="interpreted")
        with pytest.raises(PlanExecutionError):
            execute(pl, mode="compiled")

    def test_host_write_inside_loop(self):
        """Host block inside the kernel loop: per-iteration upload in both
        modes, identical results."""
        p = Program()
        p.bind("A", np.ones((8,), np.float32))
        with p.loop(4):
            p.host(lambda xp, A: {"A": A + 1.0}, reads=("A",),
                   writes=("A",), name="w")
            p.offload(lambda xp, A: {"B": A * 2.0}, reads=("A",),
                      writes=("B",), name="k")
        p.host(lambda xp, B: {"o": B}, reads=("B",), writes=("o",),
               name="c")
        p.set_outputs("o")
        _, s_i, s_c = _modes_equal(p)
        assert s_c.h2d_transfers == 4
