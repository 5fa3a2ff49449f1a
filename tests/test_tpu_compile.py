"""Compile the main path for a TPU v5e that is described, not attached.

The Pallas kernels at real widths, and deepseek-7b's full-width serving
steps (``make_serve_fns``' prefill and decode, and admission's graft into
the resident cache) on one described chip and on meshes of the described
2x2 host.  The TPU compiler refuses here what it
would refuse on the chip: a block shape off the tiling, a kernel that
cannot be partitioned, a program over the device's memory.  Nothing runs.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one given this file loads
the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

HBM_BYTES = 16 * 2**30  # one v5e chip
MAX_BATCH, PROMPT_LEN, MAX_SEQ = 4, 256, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the solver's jitted tiers turn x64 on for the whole process; the
    # chip path runs without it, and Mosaic refuses 64-bit grid indices
    was_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield t
    jax.config.update("jax_enable_x64", was_x64)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


# ---------------------------------------------------------------------------
# Pallas kernels at the widths of the models they serve
# ---------------------------------------------------------------------------


def _matmul(sds):
    from repro.kernels.tatp_matmul.kernel import matmul
    # deepseek-7b up-projection of a 2048-token block
    return jax.jit(matmul).lower(sds((2048, 4096), jnp.bfloat16),
                                 sds((4096, 11008), jnp.bfloat16))


def _flash(sds):
    from repro.kernels.flash_attention.kernel import flash_attention
    # deepseek-7b: 32 heads of 128, 2048 long
    qkv = sds((1, 32, 2048, 128), jnp.bfloat16)
    return jax.jit(flash_attention).lower(qkv, qkv, qkv)


def _ssd(sds):
    from repro.kernels.ssd.kernel import ssd_intra_chunk
    # mamba2-780m: 48 heads of 64, state 128, chunk 256
    b, q, h, p, n = 8, 256, 48, 64, 128
    return jax.jit(ssd_intra_chunk).lower(
        sds((b, q, h, p), jnp.float32), sds((b, q, h), jnp.float32),
        sds((h,), jnp.float32), sds((b, q, n), jnp.float32),
        sds((b, q, n), jnp.float32))


@pytest.mark.parametrize("lower", [_matmul, _flash, _ssd],
                         ids=["tatp_matmul", "flash_attention", "ssd"])
def test_kernel_compiles_for_v5e(one_chip, lower):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = lower(sds).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


# ---------------------------------------------------------------------------
# deepseek-7b serving steps at published widths
# ---------------------------------------------------------------------------


def _serve_lowerings(devices, mesh_shape):
    from repro.configs import get_config
    from repro.configs.base import ParallelConfig, ShapeConfig
    from repro.core.dist import Dist, make_mesh
    from repro.models.transformer import init_params
    from repro.train.train_loop import cache_shapes, make_serve_fns

    cfg = get_config("deepseek-7b")
    mesh = make_mesh(mesh_shape, ("data", "model"), devices=devices)
    dist = Dist(mesh)
    shape = ShapeConfig("serve", "decode", MAX_SEQ, MAX_BATCH)
    sb = make_serve_fns(cfg, ParallelConfig(strategy="tatp", remat=False),
                        dist, shape)

    def sds(tree, specs):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            tree, specs)

    params = sds(jax.eval_shape(lambda: init_params(jax.random.key(0), cfg)),
                 sb.pspecs)
    tokens = {"tokens": jax.ShapeDtypeStruct((MAX_BATCH, PROMPT_LEN),
                                             jnp.int32)}
    prefill = sb.prefill_fn.lower(params,
                                  sds(tokens, sb.bspecs["prefill"]))
    tok_spec = sb.bspecs["decode"]["tokens"]
    decode = sb.decode_fn.lower(
        params,
        sds(jax.ShapeDtypeStruct((MAX_BATCH, 1), jnp.int32), tok_spec),
        sds(cache_shapes(cfg, shape, dist), sb.cspecs),
        sds(jax.ShapeDtypeStruct((MAX_BATCH,), jnp.int32), P(tok_spec[0])))
    return {"prefill": prefill, "decode": decode}


@pytest.mark.parametrize("mesh_shape", [(1, 1), (1, 4), (2, 2)],
                         ids=["1chip", "ring4", "data2xring2"])
def test_deepseek_7b_serve_steps_fit_v5e(topo, mesh_shape):
    n = mesh_shape[0] * mesh_shape[1]
    steps = _serve_lowerings(topo.devices[:n], mesh_shape)
    for name, lowered in steps.items():
        compiled = lowered.compile()
        used = _device_bytes(compiled)
        assert used < HBM_BYTES, f"{name}: {used} B per chip"
        if name == "decode":
            # the donated resident cache must alias, not be copied
            assert compiled.memory_analysis().alias_size_in_bytes > 0


@pytest.mark.parametrize("mesh_shape", [(1, 1), (1, 4), (2, 2)],
                         ids=["1chip", "ring4", "data2xring2"])
def test_deepseek_7b_graft_scatters_in_place_on_v5e(topo, mesh_shape):
    """Admission's graft at deepseek-7b-pp2's serving widths (15 layers,
    8 slots of 1024, a 128-token prompt): the donated resident cache
    aliases the output, temporaries stay a sliver of it, and no cache
    leaf is gathered or exchanged all-to-all."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import ParallelConfig, ShapeConfig
    from repro.core.dist import Dist, make_mesh
    from repro.launch.serve import _slot_scatter
    from repro.train.train_loop import cache_shapes, cache_specs

    cfg = dataclasses.replace(get_config("deepseek-7b"), n_layers=15)
    n = mesh_shape[0] * mesh_shape[1]
    dist = Dist(make_mesh(mesh_shape, ("data", "model"),
                          devices=topo.devices[:n]))
    slots = 8

    def tree(seq):
        shape = ShapeConfig("serve", "decode", seq, slots)
        specs = cache_specs(cfg, shape, ParallelConfig(strategy="tatp"),
                            dist)
        sh = jax.tree.map(lambda s: NamedSharding(dist.mesh, s), specs)
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            cache_shapes(cfg, shape, dist), sh), sh

    big, sharding = tree(1024)
    small, _ = tree(128)
    index = jax.ShapeDtypeStruct((slots,), jnp.int32)
    compiled = _slot_scatter(sharding).lower(big, small, index,
                                             index).compile()
    resident = sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(big)) // n  # per chip
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= resident
    assert mem.temp_size_in_bytes < resident // 10
    hlo = compiled.as_text()
    assert "all-gather" not in hlo and "all-to-all" not in hlo
