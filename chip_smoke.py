"""Chip smoke run: the serving main path once on a TPU, at the published
widths of deepseek-7b with random weights made from a seed.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # 4-chip TATP mesh against one chip

One chip: solve a ServePlan (``resolve_serve_plan`` into a plan cache the
script creates), build ``JaxServeExecutor`` on the plan's mesh, and run
``ServeEngine`` on a ``WallClock`` over seeded Poisson arrivals.  Every
request must finish with its ``max_new_tokens`` and every logit read must
be finite.

``--chips 4`` runs only the comparison: the same requests through the
engine on the plan's 4-chip mesh (TATP ring on ``model``), then on one
chip after the first is freed; each request's prefill logits must agree
within ``LOGIT_BOUND``.  The comparison keeps every width and cuts the
depth to ``COMPARE_LAYERS``: in float32 the two meshes agree to about
1e-6 at any depth, but in bfloat16 the roundings that differ between them
grow with depth, and over all 30 layers they alone exceed the bound.

The script exits non-zero when JAX finds no TPU, and on any failed check.
Its times are smoke figures from one short run, not a benchmark.  The last
line of stdout is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, replace
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "deepseek-7b"
N_REQUESTS, RATE, SEED = 8, 4.0, 0
MAX_BATCH, PROMPT_LEN, MAX_NEW = 4, 256, 32
# max |a - b| / max |ref| over a request's prefill logits: the bf16 weights
# are reduced in another order around the ring (as tests/multidevice)
LOGIT_BOUND = 2e-2
COMPARE_LAYERS = 4
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles: list = []  # one entry per XLA compile, once listening


def tpu_devices(n: int):
    """The first ``n`` TPU devices; exits non-zero where there are none."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"'{devs[0].platform}' ({len(devs)} device(s))")
    if len(devs) < n:
        sys.exit(f"chip_smoke: --chips {n} needs {n} TPU devices, "
                 f"JAX found {len(devs)}")
    return devs[:n]


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


@dataclass
class Run:
    setup_s: float
    compile_s: float
    serve_s: float
    tokens: int
    compiles_in_window: int
    prefill_logits: dict  # rid -> float32 [vocab]


def count_compiles() -> None:
    import jax
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: _compiles.append(ev)
        if ev == COMPILE_EVENT else None)


def serve_once(plan, cfg, devices, reqs) -> Run:
    """Build the executor on ``devices``, warm it up with one request, then
    serve ``reqs`` and check every request and every logit read."""
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_plan_mesh
    from repro.launch.serve import JaxServeExecutor, release
    from repro.serve.engine import Request, ServeEngine, WallClock

    class Recording(JaxServeExecutor):
        """Keeps each request's prefill logits and whether every decode
        step's logits were finite."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.prefill_logits = {}
            self.decode_finite = True

        def _prefill_group(self, states):
            logits = super()._prefill_group(states)
            host = np.asarray(jax.device_get(logits), np.float32)
            for i, st in enumerate(states):
                self.prefill_logits[st.req.rid] = \
                    host[i, -1, :self.cfg.vocab_size]
            return logits

        def decode(self, states):
            out = super().decode(states)
            self.decode_finite &= bool(jnp.isfinite(self.last_logits).all())
            return out

    t = time.perf_counter()
    ex = Recording(plan, cfg, mesh=make_plan_mesh(plan, devices=devices))
    jax.block_until_ready((ex.params, ex.caches))
    setup_s = time.perf_counter() - t

    # warm-up: one request through the same path compiles prefill, the
    # first-token argmax and decode
    t = time.perf_counter()
    warm = ServeEngine(plan, ex, clock=WallClock(), cfg=cfg).run(
        [Request(rid=len(reqs), arrival=0.0, prompt_len=PROMPT_LEN,
                 max_new_tokens=2)])
    check(warm.n_finished == 1, "warm-up request did not finish")
    compile_s = time.perf_counter() - t

    ex.prefill_logits.clear()
    n_compiles = len(_compiles)
    engine = ServeEngine(plan, ex, clock=WallClock(), cfg=cfg)
    t = time.perf_counter()
    rep = engine.run(reqs)
    serve_s = time.perf_counter() - t
    window_compiles = len(_compiles) - n_compiles

    check(rep.n_finished == len(reqs),
          f"{rep.n_finished} of {len(reqs)} requests finished")
    for st in engine.sched.finished:
        check(len(st.tokens) == st.req.max_new_tokens,
              f"request {st.req.rid} made {len(st.tokens)} tokens, "
              f"wanted {st.req.max_new_tokens}")
    check(sorted(ex.prefill_logits) == sorted(r.rid for r in reqs),
          "prefill logits missing for some request")
    for rid, lg in ex.prefill_logits.items():
        check(bool(np.isfinite(lg).all()),
              f"request {rid}: non-finite prefill logits")
    check(ex.decode_finite, "non-finite decode logits")
    logits = dict(ex.prefill_logits)
    release((ex.params, ex.caches, ex.last_logits))
    return Run(setup_s, compile_s, serve_s, rep.generated_tokens,
               window_compiles, logits)


def report(run: Run, label: str) -> None:
    print(f"[{label}] setup {run.setup_s:.3f} s (params + resident cache); "
          f"compile {run.compile_s:.3f} s (warm-up request: first call of "
          f"each step)")
    print(f"[{label}] smoke serving, not a benchmark: {N_REQUESTS} "
          f"requests, {run.tokens} tokens in {run.serve_s:.3f} s = "
          f"{run.tokens / run.serve_s:.1f} tok/s; compiles inside the "
          f"serving window: {run.compiles_in_window}")


def compare(a: dict, ref: dict) -> float:
    """Worst per-request max |a - ref| / max |ref| over prefill logits."""
    worst = 0.0
    for rid in sorted(ref):
        err = float(np.abs(a[rid] - ref[rid]).max()
                    / max(float(np.abs(ref[rid]).max()), 1e-6))
        print(f"  request {rid}: prefill logits rel err {err:.3e}")
        worst = max(worst, err)
    return worst


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: compare the 4-chip TATP mesh with one chip")
    args = ap.parse_args()

    devices = tpu_devices(args.chips)
    import jax
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config
    from repro.launch.mesh import init_compile_cache
    from repro.launch.planning import resolve_serve_plan
    from repro.serve.engine import poisson_arrivals

    init_compile_cache()
    count_compiles()
    t0 = time.perf_counter()
    cfg = get_config(ARCH)
    print(f"config {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads} kv_heads={cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype}")
    plan_dir = tempfile.mkdtemp(prefix=".smoke_plans_", dir=ROOT)
    try:
        plan = resolve_serve_plan(cfg, MAX_BATCH, PROMPT_LEN + MAX_NEW,
                                  cache_dir=plan_dir)
    finally:
        shutil.rmtree(plan_dir)
    print(f"plan {plan.plan_hash}: max_batch={plan.max_batch} "
          f"max_seq={plan.max_seq} degrees (dp,tp,sp,tatp)="
          f"{plan.plan.degrees_tuple()}")
    print(f"plan solve {time.perf_counter() - t0:.3f} s")
    reqs = poisson_arrivals(N_REQUESTS, RATE, seed=SEED,
                            prompt_len=PROMPT_LEN, max_new_tokens=MAX_NEW)

    if args.chips == 1:
        run = serve_once(plan, cfg, devices, reqs)
        report(run, "1 chip")
    else:
        cut = replace(cfg, n_layers=COMPARE_LAYERS)
        print(f"comparison at {cut.n_layers} of {cfg.n_layers} layers, "
              f"every width as published")
        many = serve_once(plan, cut, devices, reqs)
        report(many, f"{args.chips} chips")
        one = serve_once(plan, cut, devices[:1], reqs)
        report(one, "1 chip")
        worst = compare(many.prefill_logits, one.prefill_logits)
        print(f"{args.chips} chips vs 1 chip: worst prefill logits rel err "
              f"{worst:.3e} (bound {LOGIT_BOUND})")
        check(worst < LOGIT_BOUND,
              f"{args.chips}-chip logits differ from 1 chip by {worst:.3e}")
    for d in devices:
        print(f"device {d.id} peak_bytes_in_use "
              f"{d.memory_stats()['peak_bytes_in_use']}")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
