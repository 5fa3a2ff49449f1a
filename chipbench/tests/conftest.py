"""Shared helpers for the benchmark's CPU tests: small cells cut from the
real ones, and the program on the import path."""

import dataclasses
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def small_cell(config: str, mix: str, dtype: str = "bfloat16",
               limit: float = 0.06):
    """Configuration ``config`` under traffic ``mix`` at test size: every
    width cut by the configuration's reference module (``small``), two
    layers, a small vocabulary; the same code path.  Limit 0.06 logits:
    bf16 serving at these widths reads 0.00-0.02, the fp8 control 0.1-0.2
    (CPU runs, d_model 64)."""
    import json
    from chipbench import bench, generator
    spec = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    c = bench.Cell(f"{config}.{mix}", 1,
                   bench.load_json(bench.HERE / "configs" / f"{config}.json"),
                   generator.load_mix(mix), {}, spec["per_layer"],
                   spec["end_to_end"])
    conf = dict(c.ref.small(c.conf), num_hidden_layers=2, vocab_size=512,
                torch_dtype=dtype, serving={"max_batch": 4, "max_seq": 128})
    mix = dict(c.mix, preroll_s=1, check_tokens=60)
    if mix["prompt"].get("buckets"):
        mix["prompt"] = dict(mix["prompt"], buckets=[16, 32, 64], min=8,
                             max=64, median=24)
        mix["output"] = dict(mix["output"], median=8, min=2, max=32)
    else:
        mix["prompt"] = {"dist": "fixed", "value": 16}
        mix["output"] = {"dist": "uniform", "min": 24, "max": 64}
    return dataclasses.replace(c, conf=conf, mix=mix, chips=1, params={
        "rate": 4.0, "limits": {"max_logit_gap": limit}})


@pytest.fixture
def no_compile_cache():
    """Keep CPU compiles out of the persistent cache the chip runs use."""
    import jax
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", old)
