"""The command refuses what is not a run on the chip: on a CPU it exits
3, and in a directory that holds only the benchmark it exits 2; neither
prints a result."""

import os
import shutil
import subprocess
import sys

from chipbench import spec

ARGS = ["--workload", "internlm2-1.8b.rag", "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    r = run(spec.ROOT)
    assert r.returncode == 3, r.stderr
    assert r.stdout.strip() == ""


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(spec.BENCHMARK, tmp_path / "BENCHMARK.json")
    shutil.copytree(spec.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
