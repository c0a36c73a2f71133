"""The command refuses what it cannot measure: no TPU, or a checkout
that holds only the benchmark's own files."""
import os
import shutil
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))                    # the repository root
from chipbench import bench

CELL = bench.benchmark()["workloads"][0]["name"]
ARGS = ["--workload", CELL, "--seed", "2147483901", "--seconds", "1",
        "--trace", "0"]


def run(cwd, env=None):
    return subprocess.run([sys.executable, "chipbench/run.py"] + ARGS,
                          cwd=cwd, capture_output=True, text=True,
                          timeout=300, env=env)


def test_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = run(bench.ROOT, env)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_a_checkout_of_the_benchmark_alone(tmp_path):
    doc = bench.benchmark()
    shutil.copy(os.path.join(bench.ROOT, "BENCHMARK.json"), tmp_path)
    for p in doc["paths"]:
        shutil.copytree(os.path.join(bench.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = run(tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
