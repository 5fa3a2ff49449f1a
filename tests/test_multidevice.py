"""Runs the multi-device validation scripts in subprocesses with 8 fake CPU
devices (XLA_FLAGS must be set before jax init, so these cannot run in the
main pytest process, which must see exactly 1 device)."""

import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)
SCRIPTS = ["check_tatp.py", "check_model.py", "check_zigzag.py",
           "check_wire_grads.py", "check_megatron.py", "check_serve.py",
           "check_graft.py"]


@pytest.mark.parametrize("script", SCRIPTS)
@pytest.mark.slow
def test_multidevice(script):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "multidevice", script)],
        capture_output=True, text=True, env=env, timeout=1500)
    assert out.returncode == 0, (
        f"{script} failed:\nSTDOUT:\n{out.stdout[-3000:]}\n"
        f"STDERR:\n{out.stderr[-3000:]}")
    assert "PASSED" in out.stdout
