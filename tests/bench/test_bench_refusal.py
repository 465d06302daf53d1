"""Without a TPU, or without the program beside it, the benchmark exits
non-zero and prints no result."""
import os
import shutil
import subprocess
import sys

import bench_smoke

ARGS = ["--workload", "qwen15_tree_sft", "--seed", str(2 ** 31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_the_cpu():
    p = _run(bench_smoke.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    for d in ("bench", "tests/bench"):
        shutil.copytree(os.path.join(bench_smoke.ROOT, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(bench_smoke.ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
