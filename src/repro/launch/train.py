"""End-to-end training driver with checkpoint/restart + elastic recovery.

Plan-driven launch (the solve → plan → execute pipeline)::

    PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b \
        --reduced --auto-plan --steps 50 --batch 8 --seq 128

``--auto-plan`` compiles a :class:`~repro.core.plan.WaferPlan` for the
wafer (or loads it from the on-disk plan cache — a second launch skips the
solver entirely), builds the mesh from the plan's degrees + snake device
order, and threads the plan's ParallelConfig into the step.  ``--plan
PATH`` replays an explicit plan file.  The legacy ``--mesh``/``--strategy``
flags remain for hand-driven runs.

Multi-wafer pipeline launch (one process per stage)::

    PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b \
        --reduced --wafers 2 --stage 0 --steps 5 --batch 8 --seq 128

``--wafers N`` compiles (or cache-loads) a
:class:`~repro.core.plan.MultiWaferPlan` — the upper DLWS level picks the
pipeline degree, layer split, microbatch count and GPipe/1F1B family —
and this process executes stage ``--stage``: its model slice is the
plan's layer split, its mesh is the stage's own WaferPlan.  A degraded
wafer (``--failed-dies`` + ``--fail-wafer``) misses the fault-tuple cache
and re-solves only the affected stage.  The checkpoint manifest records
the multi-wafer plan hash + stage index, so elastic restarts detect both
plan drift and stage mismatch.

Production behavior (also exercised by tests/test_train_infra.py):

* periodic atomic checkpoints (keep-k) via repro.train.checkpoint, with
  the plan hash recorded in the manifest;
* on restart, resumes from the latest checkpoint — including onto a
  *smaller* mesh (elastic recovery after node loss): the data axis shrinks
  and the same named shardings re-materialise the state; when the current
  plan's hash differs from the checkpoint's (e.g. the wafer degraded and
  the cache re-solved), the driver warns before continuing;
* simulated-failure hook (``--fail-at-step``) for fault-tolerance tests;
* straggler mitigation: step-time watchdog records slow steps and (on real
  clusters) re-solves the mapping via the wafer engine.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np


def build(cfg, mesh, par, batch: int, seq: int):
    from repro.configs.base import ShapeConfig
    from repro.core.dist import Dist
    from repro.train.data import SyntheticDataset
    from repro.train.train_loop import make_train_step

    dist = Dist(mesh)
    shape = ShapeConfig("cli", "train", seq, batch)
    bundle = make_train_step(cfg, par, dist, shape)
    data = SyntheticDataset(cfg, shape, dist)
    return dist, bundle, data


def setup(args):
    """cfg + mesh + ParallelConfig, from a plan or from the legacy flags."""
    from repro.configs import get_config, get_reduced
    from repro.configs.base import ParallelConfig
    from repro.core.dist import make_mesh

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    plan = None
    if getattr(args, "wafers", 1) > 1:
        # multi-wafer pipeline launch: this process runs ONE stage of the
        # pipeline (--stage); the MultiWaferPlan fixes the layer split and
        # every stage's mesh, so all ranks agree on the partition
        from dataclasses import replace as dc_replace

        from repro.launch.mesh import make_plan_mesh
        from repro.launch.planning import resolve_multiwafer_plan
        plan = resolve_multiwafer_plan(
            cfg, args.batch, args.seq, n_wafers=args.wafers,
            plan_path=args.plan, cache_dir=args.plan_cache,
            failed_dies=args.failed_dies, fail_wafer=args.fail_wafer,
            remat=not args.reduced)
        print(plan.summary())
        if not 0 <= args.stage < plan.pp:
            raise SystemExit(f"--stage {args.stage} out of range for "
                             f"pp={plan.pp}")
        stage_plan = plan.stages[args.stage]
        cfg = dc_replace(cfg, n_layers=plan.stage_layers[args.stage])
        mesh = make_plan_mesh(stage_plan)
        par = stage_plan.parallel_config()
        if args.reduced and par.remat:
            par = dc_replace(par, remat=False)
    elif args.plan or args.auto_plan:
        from repro.launch.mesh import make_plan_mesh
        from repro.launch.planning import resolve_plan
        plan = resolve_plan(cfg, args.batch, args.seq, plan_path=args.plan,
                            cache_dir=args.plan_cache,
                            failed_dies=args.failed_dies,
                            remat=not args.reduced)
        print(plan.summary())
        mesh = make_plan_mesh(plan)
        par = plan.parallel_config()
        if args.reduced and plan.remat:
            # reduced CPU smoke runs never need remat, whatever the plan says
            from dataclasses import replace
            par = replace(par, remat=False)
    else:
        names = ("data", "model")[: len(args.mesh)] \
            if len(args.mesh) == 2 else ("pod", "data", "model")
        mesh = make_mesh(tuple(args.mesh), names)
        par = ParallelConfig(strategy=args.strategy,
                             remat=not args.reduced)
    return cfg, mesh, par, plan


def train(args) -> dict:
    from repro.train import checkpoint as ckpt

    cfg, mesh, par, plan = setup(args)
    dist, bundle, data = build(cfg, mesh, par, args.batch, args.seq)
    ckpt_meta = {}
    if plan is not None:
        ckpt_meta["plan_hash"] = plan.plan_hash
        if hasattr(plan, "stages"):  # MultiWaferPlan: record this rank's
            ckpt_meta["stage"] = args.stage  # stage so elastic restarts
            ckpt_meta["pp"] = plan.pp  # restore the right pipeline slice
            ckpt_meta["stage_layers"] = list(plan.stage_layers)
        else:
            ckpt_meta["plan_degrees"] = list(plan.degrees_tuple())

    start_step = 0
    params = opt_state = None
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        print(f"resuming from {args.ckpt_dir}")
        prev = ckpt.read_meta(args.ckpt_dir)
        if plan and prev.get("plan_hash") \
                and prev["plan_hash"] != plan.plan_hash:
            print(f"[plan] WARNING: checkpoint was trained under plan "
                  f"{prev['plan_hash']} but this launch runs plan "
                  f"{plan.plan_hash} (wafer degraded or re-solved); "
                  f"state restores elastically onto the new mesh")
        template = jax.eval_shape(lambda: bundle.init_fn(jax.random.key(0)))
        (params, opt_state), start_step = ckpt.restore(
            args.ckpt_dir, template, dist,
            (bundle.pspecs, bundle.ospecs))
    if params is None:
        params, opt_state = bundle.init_fn(jax.random.key(args.seed))

    losses, times = [], []
    for step in range(start_step, args.steps):
        if args.fail_at_step is not None and step == args.fail_at_step \
                and start_step == 0:
            raise RuntimeError(f"simulated node failure at step {step}")
        batch = data.batch(step, bundle.bspecs)
        t0 = time.perf_counter()
        params, opt_state, metrics = bundle.step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        losses.append(loss)
        times.append(dt)
        # straggler watchdog: flag steps >3x the running median
        if len(times) > 5 and dt > 3 * float(np.median(times)):
            print(f"[watchdog] straggler step {step}: {dt:.2f}s "
                  f"(median {np.median(times):.2f}s)")
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {loss:8.4f} "
                  f"gnorm {float(metrics['grad_norm']):8.3f} {dt*1e3:7.1f}ms",
                  flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, step + 1, (params, opt_state),
                      keep=args.keep, meta=ckpt_meta)
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, args.steps, (params, opt_state),
                  keep=args.keep, meta=ckpt_meta)
    return {"first_loss": losses[0] if losses else None,
            "last_loss": losses[-1] if losses else None,
            "steps": len(losses),
            "mean_step_s": float(np.mean(times)) if times else None,
            "plan_hash": plan.plan_hash if plan else None,
            "mesh": list(np.shape(mesh.devices))}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", type=int, nargs="+", default=[1, 1])
    ap.add_argument("--strategy", default="tatp")
    ap.add_argument("--plan", default=None,
                    help="launch from an explicit WaferPlan JSON file")
    ap.add_argument("--auto-plan", action="store_true",
                    help="solve (or load the cached) WaferPlan and build "
                         "the mesh/ParallelConfig from it")
    ap.add_argument("--plan-cache", default=None,
                    help="plan cache dir (default results/plans)")
    ap.add_argument("--failed-dies", default=None,
                    help="comma-separated die ids to mark dead before "
                         "planning (degraded-wafer launches)")
    ap.add_argument("--wafers", type=int, default=1,
                    help="pipeline over N wafers (compiles/loads a "
                         "MultiWaferPlan; this process runs --stage)")
    ap.add_argument("--stage", type=int, default=0,
                    help="pipeline stage this process executes "
                         "(multi-wafer launches)")
    ap.add_argument("--fail-wafer", type=int, default=0,
                    help="wafer index --failed-dies applies to "
                         "(multi-wafer launches)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--fail-at-step", type=int, default=None)
    args = ap.parse_args()
    from repro.launch.mesh import init_compile_cache
    init_compile_cache()
    summary = train(args)
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
