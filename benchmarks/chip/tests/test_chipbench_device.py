"""The harness refuses to run, and prints no result, without a TPU."""
import json
import os
import subprocess
import sys

from chipbench_testlib import ROOT


def test_refuses_a_cpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "mamba2_longdoc_32k", "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    for ln in p.stdout.splitlines():
        try:
            json.loads(ln)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {ln}")
