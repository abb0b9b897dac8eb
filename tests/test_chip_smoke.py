"""chip_smoke.py's phases at reduced sizes on the CPU (kernels in interpret
mode), and its refusal to report a result without a TPU."""
import dataclasses
import importlib.util
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config, reduced

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _all_ok(recs):
    assert recs and all(r["ok"] for r in recs), [r for r in recs
                                                 if not r["ok"]]


def test_kernels_phase(smoke):
    recs = smoke.phase_kernels(flash=(1, 128, 2, 2, 32), wkv=(1, 128, 2, 16),
                               rglru=(1, 256, 128), rmsnorm=(64, 256),
                               interpret=True)
    _all_ok(recs)
    assert [r["kernel"] for r in recs] == ["flash_attention", "wkv6",
                                           "rglru_scan", "rmsnorm"]


def test_planner_phase(smoke):
    recs = smoke.phase_planner(
        n_mm=64, n_jacobi=64,
        attn=dict(seq=128, kv_heads=2, group=2, head_dim=32),
        interpret=True)
    _all_ok(recs)
    runs = {(r["program"], r["policy"], r["backend"]) for r in recs
            if "policy" in r}
    for prog in ("3mm", "gemm", "jacobi2d"):
        for policy in ("optimized", "naive"):
            for backend in ("jax", "pinned"):
                assert (prog, policy, backend) in runs
    auto = next(r for r in recs if r.get("policy") == "auto")
    assert auto["n_measured"] > 0
    assert any(r.get("program") == "attention_step" for r in recs)


def test_serve_phase(smoke):
    cfg = dataclasses.replace(reduced(get_config("rwkv6-3b")), n_layers=3,
                              dtype="bfloat16")
    recs = smoke.phase_serve(cfg, n_requests=4, capacity=2, max_seq=64)
    _all_ok(recs)
    logit_recs = [r for r in recs if r.get("check")]
    assert [r["layers"] for r in logit_recs] == [3, 2]
    full, prefix = logit_recs
    # full depth: held to the host bf16 model's own drift
    assert full["bound_on"] == "l2_rel_err"
    assert full["bound"] == max(
        smoke.SERVE_DRIFT_RATIO * full["host_bf16"]["l2_rel_err"],
        smoke.SERVE_LOGIT_REL_BOUND)
    assert prefix["bound_on"] == "max_rel_err"
    assert prefix["bound"] == smoke.SERVE_LOGIT_REL_BOUND


def test_mesh_phase(smoke):
    _all_ok(smoke.phase_mesh(n=64, reps=1))


def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert smoke.main(["--chips", "4"]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_alone_without_the_repo_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120,
                       env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    for line in r.stdout.splitlines():
        assert json.loads(line).get("ok") is not True
