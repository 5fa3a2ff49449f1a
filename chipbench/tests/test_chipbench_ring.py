"""The 4-chip cell's path at test size: qwen2-72b-tp4.longgen on 4 virtual
CPU devices over the plan's TATP ring serves bf16 tokens the check
accepts, and leaving out the exchange between chips in the decode
step's attention is caught.  Each run is a process of its own
(``ring_run.py``): the device count is fixed when JAX starts."""

import json
import os
import subprocess
import sys
from pathlib import Path

from chipbench import bench

HERE = Path(__file__).resolve().parent


def ring(*args):
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(bench.ROOT), str(bench.ROOT / "src")]))
    p = subprocess.run([sys.executable, str(HERE / "ring_run.py"), *args],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_ring_serves_correctly_and_its_exchange_is_needed():
    sound = ring()
    assert sound["correct"], sound["checks"]
    assert sound["device"]["count"] == 4
    assert not ring("exchange")["correct"]
