"""Seeded random weights, made by the benchmark and not by the program.

Every weight is a function of (seed, leaf name, layer index) alone, so the
serving path (all layers at once, on the device, under the program's
shardings) and the reference (one layer at a time) draw identical numbers
without sharing any array.

Leaves use the program's parameter layout: projections are ``[in, out]``,
layers are stacked on a leading axis, and a norm's stored value is its
offset from 1 (RMSNorm multiplies by ``1 + stored``).  A leaf this module
does not know is an error: the benchmark cannot check what it cannot
describe.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# fixed leaf ids: a leaf's numbers never depend on which other leaves exist
LEAF_IDS = {
    "embed": 1, "final_ln": 2, "lm_head": 3,
    "wq": 10, "wk": 11, "wv": 12, "wo": 13, "ln": 14,
    "bq": 15, "bk": 16, "bv": 17,
    "mlp.w_up": 20, "mlp.w_gate": 21, "mlp.w_down": 22, "mlp.ln": 23,
}
NORM_STD = 0.1   # norm scale = 1 + N(0, 0.1)
BIAS_STD = 0.5   # q/k/v bias, comparable to the projection's output
EMBED_STD = 1.0


def seed_key(seed: int):
    """A JAX key from any whole number, also one wider than 32 bits."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.key(int(word))


def leaf_std(name: str, shape) -> float:
    """Standard deviation of a leaf; ``shape`` excludes the layer axis."""
    if name.endswith("ln"):
        return NORM_STD
    if name.startswith("b"):
        return BIAS_STD
    if name == "embed":
        return EMBED_STD
    if len(shape) == 2:
        return 1.0 / math.sqrt(shape[0])
    raise ValueError(f"no rule for weight leaf {name!r} of shape {shape}")


def _draw(key, name: str, shape, dtype, vocab=None):
    """Draw a leaf.  The embedding and the head are drawn over ``vocab``
    real ids and zero-padded to ``shape``, so that the reference never
    needs the program's vocabulary padding."""
    if name not in LEAF_IDS:
        raise ValueError(f"unknown weight leaf {name!r}")
    real = list(shape)
    axis = {"embed": 0, "lm_head": 1}.get(name)
    if axis is not None and vocab is not None:
        real[axis] = vocab
    std = leaf_std(name, real)
    x = (jax.random.normal(key, real, jnp.float32) * std).astype(dtype)
    if tuple(real) != tuple(shape):
        pad = [(0, s - r) for s, r in zip(shape, real)]
        x = jnp.pad(x, pad)
    return x


def leaf(base, name: str, shape, dtype, layer=None, vocab=None):
    """One leaf as served (one layer's slice of a stacked leaf), from the
    key ``seed_key(seed)``; traceable, so the reference can jit it."""
    k = jax.random.fold_in(base, LEAF_IDS[name])
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return _draw(k, name, tuple(shape), dtype, vocab)


def _path_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def program_params(seed: int, like, shardings, vocab: int):
    """The program's whole parameter tree, in one jitted call on the device.

    ``like`` gives the tree's structure, shapes and dtypes; leaves under
    ``layers`` are stacked over their leading axis, one draw per layer."""

    def make(base):
        def one(path, a):
            name = _path_name(path)
            if not any(getattr(p, "key", None) == "layers" for p in path):
                return leaf(base, name, a.shape, a.dtype, vocab=vocab)
            return jax.vmap(lambda i: leaf(base, name, a.shape[1:], a.dtype,
                                           layer=i))(jnp.arange(a.shape[0]))
        return jax.tree_util.tree_map_with_path(one, like)

    return jax.jit(make, out_shardings=shardings)(seed_key(seed))
