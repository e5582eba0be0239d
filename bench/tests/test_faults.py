"""``correct`` comes out false when the timed path is broken underneath.

Each case drives a whole run at the tiny size on the CPU (the harness's
look for a chip skipped) with one fault planted, and reads the result line.  The sound run of each cell
must come out correct, every fault not.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

CASES = [
    ("granite-serve-chat", "none", True),
    ("granite-serve-chat", "altered_token", False),
    ("granite-serve-chat", "stale_cache", False),
    ("granite-serve-code-batch", "none", True),
    ("granite-serve-code-batch", "altered_token", False),
    ("granite-serve-code-batch", "stale_cache", False),
]


def run_case(tmp_path, workload, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    p = subprocess.run([sys.executable, os.path.join(HERE, "fault_run.py"), workload, fault, "0"],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,fault,sound", CASES)
def test_fault_makes_correct_false(tmp_path, workload, fault, sound):
    res = run_case(tmp_path, workload, fault)
    assert res["correct"] is sound, res["checks"]
    assert list(res)[-1] == "checks"
