"""The data-parallel cell on two forged CPU devices: leaving out the
gradient exchange between devices comes out not correct."""

import json
import os
import subprocess
import sys

from bench.tests.benchtest import BENCHMARK, SEED

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCRIPT = f"""
import io, json, sys, time
sys.path[:0] = [{os.path.join(ROOT, 'src')!r}, {ROOT!r}]
from bench.lib.harness import run
out = {{}}
for fault in (None, "no_exchange"):
    r = run("tiny.train-dp", {SEED}, 0.2, False, time.perf_counter(), allow_cpu=True,
            fault=fault, benchmark={BENCHMARK!r}, out=io.StringIO(), err=io.StringIO())
    out[str(fault)] = r["correct"]
print(json.dumps(out))
"""


def test_dp_without_exchange_fails_the_check():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    p = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True, text=True,
                       env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) == {"None": True, "no_exchange": False}
