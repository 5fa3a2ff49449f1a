"""Seeded random weights, made by the benchmark and not by the program.

Every weight is a function of (seed, leaf name, layer index) alone, so the
serving path (all layers at once, on the device, under the program's
shardings) and the reference (one layer at a time) draw identical numbers
without sharing any array.

Leaves use the program's parameter layout: projections are ``[in, out]``
(an expert stack ``[experts, in, out]``), layers are stacked on a leading
axis, and a norm's stored value is its offset from 1 (RMSNorm multiplies
by ``1 + stored``).  The leaves outside the layers are shared by every
architecture (``SHARED``); those of a layer are the configuration's
reference module's ``LEAVES``.  A leaf that neither table knows is an
error: the benchmark cannot check what it cannot describe.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# leaf -> (fixed id, standard deviation rule); a leaf's numbers never
# depend on which other leaves exist.  Reference modules give per-layer
# leaves ids of 10 and up.
SHARED = {"embed": (1, "embed"), "final_ln": (2, "norm"),
          "lm_head": (3, "fan_in")}
NORM_STD = 0.1   # norm scale = 1 + N(0, 0.1)
BIAS_STD = 0.5   # q/k/v bias, comparable to the projection's output
EMBED_STD = 1.0


def seed_key(seed: int):
    """A JAX key from any whole number, also one wider than 32 bits."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.key(int(word))


def leaf_std(rule: str, name: str, shape) -> float:
    """Standard deviation of a leaf by its rule; ``shape`` excludes the
    layer axis.  ``fan_in`` reads the input width, the second dimension
    from the end."""
    if rule == "norm":
        return NORM_STD
    if rule == "bias":
        return BIAS_STD
    if rule == "embed":
        return EMBED_STD
    if rule == "fan_in" and len(shape) >= 2:
        return 1.0 / math.sqrt(shape[-2])
    raise ValueError(f"no rule {rule!r} for weight leaf {name!r} of shape "
                     f"{shape}")


def leaf_table(name: str, leaves=None) -> tuple:
    """(id, rule) of a shared leaf or of one of ``leaves``, a reference
    module's ``LEAVES``."""
    table = SHARED if name in SHARED else (leaves or {})
    if name not in table:
        raise ValueError(f"unknown weight leaf {name!r}")
    return table[name]


def _draw(key, name: str, rule: str, shape, dtype, vocab=None):
    """Draw a leaf.  The embedding and the head are drawn over ``vocab``
    real ids and zero-padded to ``shape``, so that the reference never
    needs the program's vocabulary padding."""
    real = list(shape)
    axis = {"embed": 0, "lm_head": 1}.get(name)
    if axis is not None and vocab is not None:
        real[axis] = vocab
    std = leaf_std(rule, name, real)
    x = (jax.random.normal(key, real, jnp.float32) * std).astype(dtype)
    if tuple(real) != tuple(shape):
        pad = [(0, s - r) for s, r in zip(shape, real)]
        x = jnp.pad(x, pad)
    return x


def leaf(base, name: str, shape, dtype, layer=None, vocab=None,
         leaves=None):
    """One leaf as served (one layer's slice of a stacked leaf), from the
    key ``seed_key(seed)``; ``leaves`` is the reference module's
    ``LEAVES``.  Traceable, so the reference can jit it."""
    lid, rule = leaf_table(name, leaves)
    k = jax.random.fold_in(base, lid)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return _draw(k, name, rule, tuple(shape), dtype, vocab)


def _path_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def program_params(seed: int, like, shardings, vocab: int, leaves):
    """The program's whole parameter tree, in one jitted call on the device.

    ``like`` gives the tree's structure, shapes and dtypes; ``leaves`` is
    the reference module's ``LEAVES``.  The program stacks layers under
    ``layers`` as units ``u0``, ``u1``, ... (one per kind in its repeating
    pattern of layer kinds), each over its leading axis: entry ``i`` of
    unit ``u<k>`` is drawn as layer ``i * units + k``, the layer's place
    in the model."""
    units = len(like.get("layers", {}))

    def make(base):
        def one(path, a):
            name = _path_name(path)
            keys = [getattr(p, "key", None) for p in path]
            if "layers" not in keys:
                return leaf(base, name, a.shape, a.dtype, vocab=vocab,
                            leaves=leaves)
            k = int(keys[keys.index("layers") + 1][1:])
            return jax.vmap(lambda i: leaf(
                base, name, a.shape[1:], a.dtype, layer=i * units + k,
                leaves=leaves))(jnp.arange(a.shape[0]))
        return jax.tree_util.tree_map_with_path(one, like)

    return jax.jit(make, out_shardings=shardings)(seed_key(seed))
