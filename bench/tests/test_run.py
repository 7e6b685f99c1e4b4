"""The command refuses to report without its chips or without the
program beside it."""
import os
import shutil
import subprocess
import sys

from bench import harness

ARGS = ["--workload", "vit.dp.pois1024.k4", "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"),
                           *ARGS], capture_output=True, text=True, env=env,
                          cwd=root, timeout=300)


def test_no_chip_no_result():
    p = _run(harness.ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
