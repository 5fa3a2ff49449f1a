"""One small run of qwen2-72b-tp4.longgen on 4 virtual CPU devices, on
the plan's TATP ring (data 1 x model 4), optionally with the exchange
between chips left out of the decode step's attention.

    python chipbench/tests/ring_run.py [exchange]

Prints the result line.  test_chipbench_faults.py runs it in a process of
its own, because the device count is fixed when JAX starts.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import json  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from conftest import small_cell  # noqa: E402


def main():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    import repro.launch.planning as planning
    import repro.models.attention as attention
    from repro.configs import get_config
    from chipbench import bench

    solve = planning.resolve_serve_plan

    def ring_plan(cfg, mb, ms, **kw):
        # the published model's plan: the small model alone would be
        # solved onto a data-parallel mesh with no ring
        return solve(get_config("qwen2-72b"), mb, ms, **kw)

    planning.resolve_serve_plan = ring_plan
    if sys.argv[1:] == ["exchange"]:
        # each chip combines only its own slice of the sharded cache
        lax = types.SimpleNamespace(**vars(attention.lax))
        lax.psum = lax.pmax = lambda x, axis: x
        attention.lax = lax
    cell = small_cell("qwen2-72b-tp4", "longgen")
    cell.chips = 4
    cell.conf["serving"] = {"max_batch": 8, "max_seq": 128}
    out = bench.run(cell.name, 31, 3.0, False, require_tpu=False, cell=cell,
                    log=lambda *a: print(*a, file=sys.stderr))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
