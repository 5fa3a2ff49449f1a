"""Single-device (degenerate-ring) TATP numerics + hypothesis sweeps.
The full multi-device parity checks live in tests/multidevice/ and run via
test_multidevice.py subprocesses."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import tatp


def test_r1_matches_dense():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(6, 8), jnp.float32)
    w = jnp.asarray(rng.randn(8, 10), jnp.float32)
    y = tatp.ag_matmul_stream_w(x, w, "model", 1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), rtol=1e-5)
    dx = tatp.dgrad_stream_w(y, w, "model", 1)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(y @ w.T),
                               rtol=1e-5)
    dw = tatp.wgrad_rs(x, y, "model", 1)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(x.T @ y),
                               rtol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 12), st.integers(2, 12), st.integers(2, 12))
def test_r1_custom_vjp_grads(m, n, k):
    rng = np.random.RandomState(m * 100 + n * 10 + k)
    x = jnp.asarray(rng.randn(m, n), jnp.float32)
    w = jnp.asarray(rng.randn(n, k), jnp.float32)

    def f(x, w):
        return jnp.sum(jnp.tanh(tatp.tatp_matmul(x, w, "model", 1, True)))

    def f_ref(x, w):
        return jnp.sum(jnp.tanh(x @ w))

    gx, gw = jax.grad(f, argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(f_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_r), rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_r), rtol=2e-4,
                               atol=2e-5)


def test_choose_stream_policy():
    # paper §V: stream whichever sub-tensor is smaller
    assert tatp.choose_stream(m_loc=4096, n=4096, kb=256) == "weights"
    assert tatp.choose_stream(m_loc=8, n=4096, kb=256) == "inputs"
    assert tatp.choose_stream(1, 1, 1, requested="weights") == "weights"
