"""The benchmark's entry refuses to run without a TPU, and without the
program, and prints no result then."""
import os
import shutil
import subprocess
import sys

from bench import harness


def _run(cwd, workload="polybench-xl.3mm"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload,
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    for w in harness.benchmark_spec()["workloads"]:
        r = _run(harness.ROOT, w["name"])
        assert r.returncode != 0
        assert r.stdout.strip() == ""
        assert "needs a TPU" in r.stderr


def test_bare_checkout_no_result(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "polybench-xl.3mm",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_unknown_device_kind_has_no_peak():
    import pytest
    with pytest.raises(harness.BenchError):
        harness.peaks("a chip nobody measured")
    assert harness.peaks("TPU v5 lite")["bf16_flops"] == 197e12


def test_every_file_the_spec_names_exists():
    spec = harness.benchmark_spec()
    bench = harness.BENCH
    for w in spec["workloads"]:
        c = harness.cell(spec, w["name"])
        assert (bench / "drivers" / f"{c['traffic']['driver']}.py").is_file()
        assert (bench / "limits" / f"{w['name']}.json").is_file()
        assert harness.end_to_end_of(spec, w["name"])
        assert harness.per_layer_of(spec, w["name"])
    for m in spec["per_layer"]:
        assert (bench / "metrics" / f"{m['name']}.py").is_file()


def test_every_cell_brings_tiny_sizes(tmp_path, monkeypatch):
    import pytest

    from bench import tiny
    spec = tiny.spec(tmp_path)
    for c in spec["configs"]:
        assert harness.load_json(c["file"])["name"] == c["name"]
    monkeypatch.setattr(tiny, "SIZES", tmp_path / "none")
    with pytest.raises(harness.BenchError, match="no tiny sizes"):
        tiny.spec(tmp_path)


def test_per_layer_metric_must_list_its_cells():
    import pytest
    spec = {"end_to_end": [], "per_layer": [{"name": "x", "moves": "y"}]}
    with pytest.raises(harness.BenchError, match="lists no workloads"):
        harness.per_layer_of(spec, "a")
