"""Capacity-factor drop path in ``models.moe.moe_ffn``: the cumsum slot
assignment, the ``keep`` mask, overflow routing to the drop slot, and
zero contribution of dropped tokens through the residual."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.models.moe import moe_ffn  # noqa: E402

E, K, D, F = 4, 1, 4, 8
T = 8  # b=1, s=8


def _params(seed: int = 0):
    """Router pins every token to expert 0 (column 0 is the only nonzero
    and the inputs are strictly positive), experts are random."""
    rng = np.random.default_rng(seed)
    router = np.zeros((D, E), np.float32)
    router[:, 0] = 1.0
    return {
        "router": jnp.asarray(router),
        "w_gate": jnp.asarray(rng.normal(size=(E, D, F)), jnp.float32),
        "w_up": jnp.asarray(rng.normal(size=(E, D, F)), jnp.float32),
        "w_down": jnp.asarray(rng.normal(size=(E, F, D)), jnp.float32),
    }


def _x(seed: int = 1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.abs(rng.normal(size=(1, T, D))) + 0.1,
                       jnp.float32)


def _run(capacity_factor: float):
    out = moe_ffn(_x(), _params(), n_experts=E, top_k=K, act="swiglu",
                  axis="ep", axis_size=1,
                  capacity_factor=capacity_factor)
    return np.asarray(out.y).reshape(T, D)


def test_slot_cumsum_and_keep_mask():
    """The slot mechanism itself: per-expert running position via cumsum,
    keep = pos < cap, overflow routed to the one-past-the-end drop
    slot."""
    cap = 2
    flat_e = jnp.asarray([0, 0, 0, 1, 3, 3, 3, 0])
    one_hot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = jnp.cumsum(one_hot, axis=0)[jnp.arange(flat_e.size), flat_e] - 1
    keep = pos < cap
    slot = jnp.where(keep, flat_e * cap + pos, E * cap)
    assert pos.tolist() == [0, 1, 2, 0, 0, 1, 2, 3]
    assert keep.tolist() == [True, True, False, True,
                             True, True, False, False]
    # kept slots are unique (no token overwrites another's buffer row)
    kept_slots = slot[keep].tolist()
    assert len(set(kept_slots)) == len(kept_slots)
    assert all(s < E * cap for s in kept_slots)
    # every overflow assignment lands on the single drop slot
    assert set(slot[~keep].tolist()) == {E * cap}
    # .at[slot].set(..., mode="drop") discards exactly the overflow rows
    buf = jnp.zeros((E * cap, 1)).at[slot].set(
        jnp.ones((flat_e.size, 1)), mode="drop")
    assert float(buf.sum()) == float(keep.sum())


def test_overflow_tokens_are_dropped():
    """All 8 tokens route to expert 0; capacity_factor=0.5 gives
    cap = max(1, round(8·1/4·0.5)) = 1, so exactly one token survives
    and the other seven produce an exactly-zero FFN output."""
    y = _run(0.5)
    assert np.any(y[0] != 0.0)
    assert np.all(y[1:] == 0.0)


def test_dropped_tokens_pass_residual_unchanged():
    y = _run(0.5)
    x = np.asarray(_x()).reshape(T, D)
    resid = x + y
    # dropped tokens: the residual stream is bitwise-untouched
    assert np.array_equal(resid[1:], x[1:])
    assert not np.array_equal(resid[0], x[0])


def test_high_capacity_admits_everything():
    """capacity_factor = E lifts cap to 8: no drops, and the originally
    admitted token's output is unchanged (same expert, same buffer row)
    up to f32 rounding."""
    y_lo, y_hi = _run(0.5), _run(float(E))
    assert np.all(np.any(y_hi != 0.0, axis=1))  # every token got output
    # bitwise equality across buffer sizes is not promised: XLA may tile
    # the expert einsums differently for an [E, 1, D] buffer and an
    # [E, 8, D] one, so the f32 sums over D and F round in another order;
    # bound the difference by (D + F) roundings of the largest output
    atol = (D + F) * np.finfo(np.float32).eps * np.abs(y_lo[0]).max()
    np.testing.assert_allclose(y_hi[0], y_lo[0], rtol=0, atol=atol)
    # and capacity is the only difference: admitted rows all run through
    # the same single expert, so equal inputs give equal outputs
    x = np.asarray(_x()).reshape(T, D)
    dup = np.isclose(x[1:], x[0]).all(axis=1)
    assert not dup.any()  # sanity: distinct tokens, distinct outputs


def test_capacity_law_matches_router_sim():
    """moe_ffn and the serving-side ExpertRouterSim must share one
    capacity law, or the engine's drop accounting diverges from the
    kernel's."""
    from repro.serve.engine import ExpertRouterSim

    class _Cfg:
        n_experts, top_k, capacity_factor = E, K, 0.5
        n_expert_groups = top_k_groups = 0

    r = ExpertRouterSim(_Cfg(), ep=1, seed=0)
    r.observe(T)
    kernel_cap = int(max(1, round(T * K / E * 0.5)))
    # with cap=1 per expert the sim can admit at most E assignments
    assert sum(r.load) <= E * kernel_cap
    assert r.routed == T * K
    assert r.dropped == r.routed - sum(r.load)
