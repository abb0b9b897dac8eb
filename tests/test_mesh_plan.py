"""Mesh-aware plan generation on forced fake devices.

Each test runs in a subprocess with ``--xla_force_host_platform_device_count``
so the main pytest process keeps its single CPU device.  Fake CPU devices
time-slice the host's cores, so these tests assert what the tuner counts
and decides (placements, predicted collective bytes, verifier verdicts,
cache hits), never a wall-clock ordering: measured times across
placements come from ``chip_smoke.py --chips 4`` on a real 4-chip host.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")


def run_py(body: str, n_devices: int = 8, timeout=560, env=None):
    code = ("import os\n"
            f"os.environ['XLA_FLAGS'] = "
            f"'--xla_force_host_platform_device_count={n_devices}'\n"
            + textwrap.dedent(body))
    # JAX_PLATFORMS=cpu: without it jax probes for a TPU backend first
    # (minutes of metadata-server retries on a non-TPU host)
    full_env = {"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin", "HOME": "/root",
                "JAX_PLATFORMS": "cpu"}
    full_env.update(env or {})
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=full_env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_mesh_tune_selects_sharded_plan_and_caches(tmp_path):
    """plan(p, policy="auto") on the mesh backend: the cost model ranks a
    sharded placement first, every sharded candidate predicts collective
    bytes and the replicated ones none, the winner's placement is recorded
    and verifies clean, it executes correctly, and the warm cache answers
    the repeat call with zero measurements."""
    out = run_py("""
        import json
        from repro.polybench import build
        from repro.core import plan, execute, run_host_oracle, verify_plan
        from repro.core.backend import get_backend
        import numpy as np

        p, _ = build("3mm", n=256)
        be = get_backend("mesh")
        assert be.n_devices == 8, be.mesh_desc
        assert be.mesh_desc[0] == (2, 4), be.mesh_desc

        tuned = plan(p, policy="auto", backend=be, reps=1)
        tuning = tuned.meta["tuning"]
        valid = [c for c in tuning["candidates"] if c["valid"]]
        placements = {c["config"]["mesh_placement"] for c in valid}
        assert placements == {"replicate", "fsdp", "tp"}, placements

        # predicted collective bytes: sharded 3mm matmuls need them,
        # replicated ones never do
        for c in valid:
            sharded = c["config"]["mesh_placement"] != "replicate"
            assert (c["coll_bytes"] > 0) == sharded, (c["label"],
                                                      c["coll_bytes"])

        # the cost model's first choice is a sharded placement
        first = next(c for c in valid if c["rank"] == 1)
        assert first["config"]["mesh_placement"] in ("fsdp", "tp"), first

        # the selected placement is the chosen candidate's, recorded with
        # specs that shard exactly when the placement does
        chosen = next(c for c in valid if c["label"] == tuning["chosen"])
        placement = chosen["config"]["mesh_placement"]
        mesh_rec = tuned.meta["mesh"]
        assert mesh_rec["placement"] == placement, mesh_rec
        shards = any(x is not None for e in mesh_rec["specs"].values()
                     for x in e)
        assert shards == (placement != "replicate"), mesh_rec

        # the winner verifies clean (collective = sync point, no gaps)
        rep = verify_plan(tuned)
        assert rep.ok, rep.summary()

        # every measured class records its kernel leg and residual
        meas = [c for c in valid if c.get("measured_s") is not None]
        assert meas and all(c.get("measured_kernel_s") is not None
                            and c.get("kernel_residual_s") is not None
                            for c in meas)

        # the winner executes correctly through the mesh backend;
        # sharded reductions reassociate the accumulation: tolerance
        # covers the collective's summation-order drift, nothing more
        out_m, _ = execute(tuned, backend=be)
        np.testing.assert_allclose(np.asarray(out_m["out"]),
                                   run_host_oracle(p)["out"], rtol=2e-3)

        # warm cache: repeat call answers with zero measurements
        p2, _ = build("3mm", n=256)
        tuned2 = plan(p2, policy="auto", backend=be, reps=1)
        ci = tuned2.meta["tuning_cache"]
        assert ci["hit"] is True and ci["measurements"] == 0, ci
        assert tuned2.meta["tuning"]["chosen"] == tuning["chosen"]
        assert tuned2.meta.get("mesh") == mesh_rec
        print("MESH_TUNE_OK", json.dumps({"chosen": tuning["chosen"]}))
    """, env={"REPRO_TUNE_CACHE": str(tmp_path / "tc")})
    assert "MESH_TUNE_OK" in out


def test_mesh_fingerprint_separates_mesh_shapes(tmp_path):
    """The same program tuned on a 2x4 and a 1x8 mesh must not alias in
    the tunecache (mesh shape is part of the backend fingerprint)."""
    out = run_py("""
        from repro.polybench import build
        from repro.core import plan
        from repro.distributed.mesh_backend import MeshBackend
        from repro.core.tunecache import backend_fingerprint

        be_a = MeshBackend(shape=(2, 4))
        be_b = MeshBackend(shape=(1, 8))
        assert backend_fingerprint(be_a) != backend_fingerprint(be_b)

        p, _ = build("gemm", n=64, iters=2)
        pl_a = plan(p, policy="auto", backend=be_a, reps=1)
        assert pl_a.meta["tuning_cache"]["hit"] is False
        p2, _ = build("gemm", n=64, iters=2)
        pl_b = plan(p2, policy="auto", backend=be_b, reps=1)
        assert pl_b.meta["tuning_cache"]["hit"] is False   # no aliasing
        p3, _ = build("gemm", n=64, iters=2)
        pl_a2 = plan(p3, policy="auto", backend=MeshBackend(shape=(2, 4)),
                     reps=1)
        assert pl_a2.meta["tuning_cache"]["hit"] is True   # same mesh hits
        print("MESH_FP_OK")
    """, env={"REPRO_TUNE_CACHE": str(tmp_path / "tc")})
    assert "MESH_FP_OK" in out


def test_16way_model_axis_specs_all_jit_valid():
    """Satellite: qwen2.5's 40 q-heads and arctic's 56-way dim on a
    16-way model axis stay unsharded with the drop recorded, and every
    PartitionSpec placement_specs produces actually jits."""
    out = run_py("""
        import jax, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        from repro.distributed.mesh_backend import placement_specs
        from repro.configs import get_config

        devs = jax.devices()
        assert len(devs) == 16
        mesh = Mesh(np.asarray(devs).reshape(1, 16), ("data", "model"))
        q = get_config("qwen2.5-14b")
        a = get_config("arctic-480b")
        assert q.n_heads == 40 and a.n_heads == 56
        shapes = {
            "w_q": jax.ShapeDtypeStruct((q.d_model, q.n_heads * q.d_head),
                                        np.float32),
            "heads40": jax.ShapeDtypeStruct((128, q.n_heads), np.float32),
            "heads56": jax.ShapeDtypeStruct((64, a.n_heads), np.float32),
            "experts128": jax.ShapeDtypeStruct((64, a.n_experts),
                                               np.float32),
            "scalar": jax.ShapeDtypeStruct((), np.float32),
        }
        for policy in ("replicate", "fsdp", "tp"):
            specs, dropped = placement_specs(shapes, mesh, policy)
            assert set(specs) == set(shapes)       # no placement gaps
            if policy == "tp":
                # 40 % 16 and 56 % 16 != 0: the dim stays unsharded
                assert specs["heads40"][-1] is None
                assert specs["heads56"][-1] is None
                assert specs["experts128"][-1] == "model"  # 128 shards
                dropped_vars = {d[0] for d in dropped}
                assert {"heads40", "heads56"} <= dropped_vars
            # every spec jit-compiles with in_shardings on this mesh
            # (one lowering per policy: all vars as one argument list)
            order = sorted(specs)
            shs = [NamedSharding(mesh, PartitionSpec(*specs[v]))
                   for v in order]
            fn = jax.jit(lambda *xs: xs, in_shardings=shs)
            fn.lower(*[shapes[v] for v in order]).compile()
        print("JIT_VALID_OK")
    """, n_devices=16)
    assert "JIT_VALID_OK" in out
