"""bench/run.py refuses to measure without a TPU, and without the
program beside it, and prints no result either way."""
import os
import shutil
import subprocess
import sys

import tiny

RUN = ["bench/run.py", "--workload", "dit-xl.teacache.poisson", "--seed",
       "3000000000", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable] + RUN, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_cpu_host_is_refused():
    p = _run(tiny.CHECKOUT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "nothing run" in p.stderr


def test_benchmark_files_alone_are_refused(tmp_path):
    shutil.copy(tiny.CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
