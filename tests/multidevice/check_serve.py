"""Continuous-batching serving on 4 devices: the engine runs on the plan's
mesh (TATP ring of 4 on ``model``), finishes every request, keeps the
resident cache in the decode layout across admissions, and its prefill
logits match the same requests served on one device.  Run with 4 fake
CPU devices; the model is deepseek-7b's family at a reduced float32 width,
so the two meshes may differ only by f32 summation order."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
from repro.configs import get_config
from repro.configs.base import reduced_config
from repro.core.plan import compile_serve_plan
from repro.launch.mesh import make_plan_mesh
from repro.launch.serve import JaxServeExecutor
from repro.serve.engine import ServeEngine, WallClock, poisson_arrivals
from repro.wafer.topology import Wafer, WaferSpec

full = get_config("deepseek-7b")
plan = compile_serve_plan(Wafer(WaferSpec()), full, 4, 288, use_cache=False)
cfg = reduced_config(full, vocab_size=512, d_model=64, d_ff=128, n_heads=4,
                     n_kv_heads=4, d_head=16)
reqs = poisson_arrivals(6, 50.0, seed=0, prompt_len=32, max_new_tokens=4)


class Recording(JaxServeExecutor):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.logits = {}
        self.layout_kept = True

    def _prefill_group(self, states):
        logits = super()._prefill_group(states)
        host = np.asarray(jax.device_get(logits), np.float32)
        for i, st in enumerate(states):
            self.logits[st.req.rid] = host[i, -1, :cfg.vocab_size]
        self.layout_kept &= all(
            a.sharding == s for a, s in zip(jax.tree.leaves(self.caches),
                                           jax.tree.leaves(self._cache_sh)))
        return logits


def run(devices):
    mesh = make_plan_mesh(plan, devices=devices)
    ex = Recording(plan, cfg, mesh=mesh)
    rep = ServeEngine(plan, ex, clock=WallClock(), cfg=cfg).run(reqs)
    return dict(mesh.shape), rep, ex


failures = []
devs = jax.devices()
m4, rep4, ex4 = run(devs[:4])
m1, rep1, ex1 = run(devs[:1])
print(f"plan degrees {plan.plan.degrees_tuple()}: mesh {m4} vs {m1}")
if m4.get("model") != 4:
    failures.append(f"4-device mesh is {m4}, not a ring of 4 on model")
for name, rep, ex in (("4 dev", rep4, ex4), ("1 dev", rep1, ex1)):
    print(f"{name}: {rep.n_finished}/{len(reqs)} finished, "
          f"{rep.generated_tokens} tokens, layout kept {ex.layout_kept}")
    if rep.n_finished != len(reqs) or \
            rep.generated_tokens != sum(r.max_new_tokens for r in reqs):
        failures.append(f"{name}: requests unfinished")
    if not ex.layout_kept:
        failures.append(f"{name}: resident cache lost the decode layout")
err = max(float(np.abs(ex4.logits[r] - ex1.logits[r]).max()
                / np.abs(ex1.logits[r]).max()) for r in ex1.logits)
print(f"prefill logits 4 dev vs 1 dev: worst rel err {err:.2e}")
if sorted(ex4.logits) != sorted(ex1.logits) or err >= 1e-4:
    failures.append(f"prefill logits differ: {err:.2e}")

if failures:
    print("FAILURES:", failures)
    sys.exit(1)
print("SERVE ENGINE 4-DEVICE CHECK PASSED")
