"""The serving path the window drives agrees with the float32 reference,
and the comparison that decides ``correct`` fails the fp8 control and
each fault the cells can have.  Small widths on the CPU; the code path is
the chip's: plan, mesh, prefill, graft into the resident cache, decode
with per-row context lengths."""

import os
import subprocess
import sys

import pytest

import faults
from chipbench import bench
from conftest import small_cell

QUIET = dict(require_tpu=False, log=lambda *a: None)
# float32 serving against the float32 reference: roundings of 64-long dot
# products through 2 layers move a logit by about 1e-6; a wrong token
# reads 0.1 or more at these widths.
F32_LIMIT = 1e-3


def run_small(cell, seed, on_executor=None, **kw):
    seen = {}

    def keep(ex):
        seen["ex"] = ex
        if on_executor is not None:
            on_executor(ex)

    out = bench.run(cell.name, seed, 3.0, False, cell=cell, on_executor=keep,
                    **QUIET, **kw)
    return out, seen["ex"].spans.rows


@pytest.mark.parametrize("workload", [("deepseek-7b-pp2", "chat"),
                                      ("qwen2-72b-tp4", "longgen")])
def test_served_tokens_match_the_float32_reference(workload,
                                                   no_compile_cache):
    cell = small_cell(*workload, dtype="float32", limit=F32_LIMIT)
    out, rows = run_small(cell, 2 ** 35 + 17)
    assert out["correct"], out["checks"]
    assert out["checks"]["max_logit_gap"]["value"] <= F32_LIMIT
    assert out["checks"]["tokens_checked"]["value"] >= 60
    decodes = [(a, b, lens) for n, a, b, lens in rows
               if n == "executor.decode"]
    # rows at different context lengths decoded in one step
    assert any(len(set(lens)) > 1 for _, _, lens in decodes)
    # a request was grafted in while others stayed in flight through it
    prefills = [(a, b) for n, a, b, _ in rows if n == "executor.prefill"]
    assert any(
        [d for d in decodes if d[1] <= a][-1:]
        and [d for d in decodes if d[1] <= a][-1][2]
        and [d for d in decodes if d[0] >= b][:1]
        and len([d for d in decodes if d[0] >= b][0][2])
        > len([d for d in decodes if d[1] <= a][-1][2])
        for a, b in prefills)


def test_fp8_control_fails_where_bf16_serving_passes(no_compile_cache):
    cell = small_cell("deepseek-7b-pp2", "chat")
    limit = cell.params["limits"]["max_logit_gap"]
    sound, _ = run_small(cell, 77)
    assert sound["correct"], sound["checks"]
    control, _ = run_small(cell, 77, control=True)
    assert not control["correct"]
    assert control["checks"]["max_logit_gap"]["value"] > limit


def test_a_token_altered_where_it_is_produced_is_caught(no_compile_cache):
    cell = small_cell("deepseek-7b-pp2", "chat")
    out, _ = run_small(cell, 77, on_executor=faults.token_altered(
        cell.conf["vocab_size"]))
    assert not out["correct"]


def test_a_graft_that_leaves_the_cache_unchanged_is_caught(
        no_compile_cache, monkeypatch):
    monkeypatch.setattr(*faults.graft_unchanged())
    out, _ = run_small(small_cell("deepseek-7b-pp2", "chat"), 77)
    assert not out["correct"]


def test_the_harness_refuses_a_machine_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "chipbench/run.py", "--workload",
           "deepseek-7b-pp2.longgen", "--seed", "1", "--seconds", "1",
           "--trace", "0"]
    p = subprocess.run(cmd, cwd=bench.ROOT, capture_output=True, text=True,
                       env=env, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr and "'cpu'" in p.stderr
