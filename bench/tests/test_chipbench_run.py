"""``bench/run.py`` refuses to measure without a TPU, and without the
program beside it: it exits nonzero and prints no result line."""
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
ARGS = ["--workload", "covertype-rbf.train", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
