"""Production mesh construction (+ TCME-informed device ordering).

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core.dist import Dist, make_mesh


def init_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at one fixed path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives in ``.jax_cache`` at
    the root of the checkout.  Entry points call this before their first
    compile.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        root = Path(__file__).resolve().parents[3]
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))


def make_production_mesh(*, multi_pod: bool = False,
                         devices: Optional[Sequence] = None) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, devices=devices)


def make_wafer_ordered_mesh(order: np.ndarray, *,
                            multi_pod: bool = False) -> Mesh:
    """Build the production mesh with an explicit device permutation.

    ``order`` is the flat device permutation produced by the TCME ring
    embedding (repro.wafer.mapping) so that every TATP ring maps onto
    physically contiguous devices (snake order on the 2D grid).
    """
    devs = np.asarray(jax.devices())[np.asarray(order)]
    return make_production_mesh(multi_pod=multi_pod, devices=devs)


def plan_device_permutation(plan, n_devices: int) -> list[int]:
    """Device permutation a plan prescribes for ``n_devices``.

    At full scale (one device per alive die) this is the plan's own
    ``device_order`` — the snake embedding TCME solved, holes skipped —
    compacted from die ids to device ranks (device k hosts the k-th alive
    die in id order).  At reduced scale (elastic restart, CPU smoke) the
    wafer order cannot apply, so the dense ``device_order_for_jax`` snake
    over the shrunken (data, model) grid is used instead.
    """
    from repro.wafer.mapping import device_order_for_jax
    if n_devices == len(plan.device_order):
        rank = {die: k for k, die in enumerate(sorted(plan.alive_dies))}
        return [rank[d] for d in plan.device_order]
    data, model = plan.mesh_shape_for(n_devices)
    return device_order_for_jax(data, model).tolist()


def make_plan_mesh(plan, devices: Optional[Sequence] = None) -> Mesh:
    """Build the (data, model) mesh a :class:`~repro.core.plan.WaferPlan`
    prescribes, with the plan's device order.

    The plan's tatp degree becomes the ``model`` axis (shrunk to divide the
    actual device count — elastic restarts and CPU smoke runs have fewer
    devices than the solved wafer); the snake permutation embeds every
    model-axis ring on physically contiguous devices.  A
    :class:`~repro.core.plan.ServePlan` is accepted directly (its decode
    mesh is the wrapped WaferPlan).
    """
    plan = getattr(plan, "plan", plan)  # ServePlan wraps its decode mesh
    devs = list(devices) if devices is not None else list(jax.devices())
    data, model = plan.mesh_shape_for(len(devs))
    devs = [devs[i] for i in plan_device_permutation(plan, len(devs))]
    return make_mesh((data, model), ("data", "model"), devices=devs)


def stage_device_partition(plan, n_devices: int) -> list[list[int]]:
    """Partition ``n_devices`` device ranks into one contiguous block per
    pipeline stage of a :class:`~repro.core.plan.MultiWaferPlan`.

    At full scale (one device per solved die) each stage gets exactly as
    many devices as its die subset; at reduced scale (CPU smoke, elastic)
    the blocks shrink proportionally, never below one device per stage.
    """
    from repro.wafer.solver import apportion
    pp = plan.pp
    if n_devices < pp:
        raise ValueError(f"{n_devices} devices cannot host a pp={pp} "
                         f"pipeline (one device per stage minimum)")
    sizes = [len(s.alive_dies) for s in plan.stages]
    cuts = sizes if n_devices == sum(sizes) \
        else apportion(n_devices, sizes)
    out, lo = [], 0
    for c in cuts:
        out.append(list(range(lo, lo + c)))
        lo += c
    return out


def make_stage_submeshes(plan, devices: Optional[Sequence] = None) \
        -> list[Mesh]:
    """One (data, model) mesh per pipeline stage, each built from the
    stage's own :class:`WaferPlan` (degrees + snake device order) over its
    block of the device partition."""
    devs = list(devices) if devices is not None else list(jax.devices())
    blocks = stage_device_partition(plan, len(devs))
    return [make_plan_mesh(stage, devices=[devs[i] for i in block])
            for stage, block in zip(plan.stages, blocks)]


def dist_for(mesh) -> Dist:
    return Dist(mesh)
