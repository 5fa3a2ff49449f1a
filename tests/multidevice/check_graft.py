"""Admission's graft on the device over sharded layouts: on the TATP ring
of 4 (``model`` shards the K/V sequence axis) and on data 2 x ring 2
(``data`` shards the batch too), the donated slot scatter must equal the
host graft (:func:`repro.models.lm.graft_cache_slots`) bit for bit, keep
every other slot, and keep the decode layout.  Rows cross sequence shards
(the prompt window lies in the first shard of the resident cache) and
batch shards.  Run with 4 fake CPU devices."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys

import jax
import numpy as np
from jax.sharding import NamedSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
from repro.configs import get_reduced
from repro.configs.base import ParallelConfig, ShapeConfig
from repro.core.dist import Dist, make_mesh
from repro.launch.serve import _graft_to_device
from repro.models.lm import graft_cache_slots
from repro.train.train_loop import cache_shapes, cache_specs

BATCH, SEQ = 4, 32


def bits(a):
    return np.ascontiguousarray(a).view(np.uint8)


def check(mesh_shape, arch, window, slots, rows):
    cfg = get_reduced(arch)
    dist = Dist(make_mesh(mesh_shape, ("data", "model"),
                          devices=jax.devices()[:4]))
    rng = np.random.default_rng(len(slots))

    def tree(seq):
        shape = ShapeConfig("serve", "decode", seq, BATCH)
        return jax.tree.map(
            lambda a: rng.standard_normal(a.shape).astype(a.dtype),
            cache_shapes(cfg, shape, dist))

    shape = ShapeConfig("serve", "decode", SEQ, BATCH)
    sharding = jax.tree.map(
        lambda s: NamedSharding(dist.mesh, s),
        cache_specs(cfg, shape, ParallelConfig(strategy="tatp"), dist))
    big, small = tree(SEQ), tree(window)
    want = graft_cache_slots(big, small, slots, rows=rows)
    got = _graft_to_device(jax.device_put(big, sharding),
                           jax.device_put(small, sharding), slots, rows,
                           sharding)
    others = [i for i in range(BATCH) if i not in slots]
    for g, w, b, sh in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                           jax.tree.leaves(big), jax.tree.leaves(sharding)):
        host = np.asarray(g)
        if g.sharding != sh or not np.array_equal(bits(host), bits(w)) \
                or not np.array_equal(bits(host[:, others]),
                                      bits(b[:, others])):
            return False
    return True


failures = []
for mesh_shape in ((1, 4), (2, 2)):
    for arch in ("qwen2-72b", "mamba2-780m", "zamba2-2.7b"):
        for window in (8, 20):
            for slots, rows in (([2], None), ([3, 0, 2], None),
                                ([2, 0, 3, 1], None), ([1, 3], [3, 0])):
                ok = check(mesh_shape, arch, window, slots, rows)
                print(f"mesh {mesh_shape} {arch} window {window} slots "
                      f"{slots} rows {rows}: {'ok' if ok else 'DIFFERS'}")
                if not ok:
                    failures.append((mesh_shape, arch, window, slots, rows))

if failures:
    print("FAILURES:", failures)
    sys.exit(1)
print("DEVICE GRAFT 4-DEVICE CHECK PASSED")
