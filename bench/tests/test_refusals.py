"""A run that cannot measure what the cell asks for prints no result."""

import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ARGS = ["--workload", "granite-serve-chat", "--seed", "1", "--seconds", "1", "--trace", "0"]


def _run(cwd, code=None, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    cmd = [sys.executable, "bench/run.py", *ARGS] if code is None else \
        [sys.executable, "-c", code, *ARGS]
    return subprocess.run(cmd, cwd=cwd, capture_output=True, text=True, env=env, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_unknown_device_kind_exits_nonzero_without_a_result():
    code = (
        "import sys, jax\n"
        "class D:\n"
        "    platform = 'tpu'; device_kind = 'TPU v99'\n"
        "jax.devices = lambda *a: [D()]\n"
        "sys.argv = ['bench/run.py'] + sys.argv[1:]\n"
        "sys.path[:0] = ['.']\n"
        "from bench import run\n"
        "sys.exit(run.main())\n")
    p = _run(ROOT, code)
    assert p.returncode != 0
    assert "not in bench/peaks.json" in p.stderr
    assert '"correct"' not in p.stdout


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
