"""A configuration brings its architecture as a reference module, found by
name in ``chipbench/references/``: the dense module draws the same
weights and computes the same logits as before it moved there, a module
the harness has never seen runs a cell end to end, and what no module
describes is refused."""

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import bench, weights
from conftest import small_cell

GOLDEN = json.loads((bench.HERE / "tests" / "data" /
                     "dense_gqa_golden.json").read_text())


def sha(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def on_cpu(like):
    dev = jax.devices()[0]
    return jax.tree.map(lambda _: jax.sharding.SingleDeviceSharding(dev),
                        like)


@pytest.mark.parametrize("config", ["deepseek-7b-pp2", "qwen2-72b-tp4"])
def test_dense_module_is_bit_identical_to_the_reference_it_replaced(
        config, no_compile_cache):
    from repro.models.transformer import param_shapes
    want = GOLDEN[config]
    conf = small_cell(config, "longgen").conf
    ref, seed = bench.reference(conf), want["seed"]
    like = param_shapes(bench.model_config(conf))
    params = weights.program_params(seed, like, on_cpu(like),
                                    conf["vocab_size"], ref.LEAVES)
    got = {jax.tree_util.keystr(p): sha(np.asarray(x).view(np.uint16))
           for p, x in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == want["params"]
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, conf["vocab_size"], (2, 40)).astype(np.int32)
    picks = rng.integers(0, conf["vocab_size"], (1, 2, 40))
    picks[0, :, :5] = -1
    assert sha(*ref.score(conf, seed, tokens, picks)) == want["score"]
    _, first, gaps = ref.score(conf, seed, tokens, picks, quant="fp8")
    assert sha(first, gaps) == want["score_fp8"]


def test_a_module_the_harness_never_saw_runs_a_cell(
        tmp_path, monkeypatch, no_compile_cache):
    # the dense module under another name, passing one published key of
    # its own to the program
    cell = small_cell("deepseek-7b-pp2", "chat")
    cell.conf.update(reference="gqa_head_dim", head_dim=16)
    text = (bench.REFERENCES / "dense_gqa.py").read_text()
    (tmp_path / "gqa_head_dim.py").write_text(
        text + '\nKEYS = dict(KEYS, head_dim="d_head")\n')
    monkeypatch.setattr(bench, "REFERENCES", tmp_path)
    seen = {}
    out = bench.run(cell.name, 2 ** 34 + 9, 3.0, False, cell=cell,
                    on_executor=lambda ex: seen.setdefault("ex", ex),
                    require_tpu=False, log=lambda *a: None)
    assert cell.ref.__file__ == str(tmp_path / "gqa_head_dim.py")
    assert seen["ex"].cfg.d_head == 16
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_checked"]["value"] >= 60


def test_a_key_the_module_names_must_reach_the_program(tmp_path,
                                                        monkeypatch):
    conf = dict(small_cell("deepseek-7b-pp2", "chat").conf,
                reference="gqa_head_dim")
    text = (bench.REFERENCES / "dense_gqa.py").read_text()
    (tmp_path / "gqa_head_dim.py").write_text(
        text + '\nKEYS = dict(KEYS, head_dim="d_head")\n')
    monkeypatch.setattr(bench, "REFERENCES", tmp_path)
    with pytest.raises(bench.BenchError, match="no head_dim"):
        bench.model_config(conf)


def test_a_config_that_names_a_missing_module_is_refused():
    conf = dict(small_cell("deepseek-7b-pp2", "longgen").conf,
                reference="no_such_architecture")
    with pytest.raises(bench.BenchError, match="no reference module"):
        bench.model_config(conf)
    del conf["reference"]
    with pytest.raises(bench.BenchError, match="no reference module"):
        bench.reference(conf)


def program_config(arch, **change):
    from repro.configs import get_config
    return dataclasses.replace(get_config(arch), **change)


@pytest.mark.parametrize("what,cfg", [
    ("windowed", lambda: program_config(
        "deepseek-7b", layer_pattern="LG", sliding_window=1024)),
    ("moe", lambda: program_config("olmoe-1b-7b")),
    ("softcap_geglu_tied", lambda: program_config("gemma2-9b")),
    ("head_width", lambda: program_config("qwen2-72b", d_head=64)),
])
def test_dense_module_refuses_what_it_does_not_compute(what, cfg):
    ref = bench.reference({"name": "x", "reference": "dense_gqa"})
    with pytest.raises(ValueError, match="dense SwiGLU decoders only"):
        ref.accepts(cfg())


@pytest.mark.parametrize("arch", ["deepseek-7b", "qwen2-72b"])
def test_dense_module_accepts_the_dense_programs(arch):
    ref = bench.reference({"name": "x", "reference": "dense_gqa"})
    ref.accepts(program_config(arch))


def test_a_refused_mechanism_ends_the_run_before_the_program_runs(
        tmp_path, monkeypatch):
    # a module that takes the window from the file while the dense
    # forward ignores it: the refusal names the configuration
    conf = dict(small_cell("deepseek-7b-pp2", "longgen").conf,
                reference="gqa_window", sliding_window=32)
    text = (bench.REFERENCES / "dense_gqa.py").read_text()
    (tmp_path / "gqa_window.py").write_text(
        text + '\nKEYS = dict(KEYS, sliding_window="sliding_window")\n')
    monkeypatch.setattr(bench, "REFERENCES", tmp_path)
    with pytest.raises(bench.BenchError,
                       match="deepseek-7b-pp2: the reference covers dense "
                             "SwiGLU decoders only"):
        bench.model_config(conf)


def test_weights_refuse_a_leaf_no_table_knows():
    ref = bench.reference({"name": "x", "reference": "dense_gqa"})
    base = weights.seed_key(5)
    with pytest.raises(ValueError, match="unknown weight leaf 'mlp.router'"):
        weights.leaf(base, "mlp.router", (8, 4), jnp.float32, layer=0,
                     leaves=ref.LEAVES)
    # a layer's leaf is known only through a module's table
    with pytest.raises(ValueError, match="unknown weight leaf 'wq'"):
        weights.leaf(base, "wq", (8, 8), jnp.float32, layer=0)
    like = {"embed": jax.ShapeDtypeStruct((16, 8), jnp.float32),
            "layers": {"u0": {"mlp.router": jax.ShapeDtypeStruct(
                (2, 8, 4), jnp.float32)}}}
    with pytest.raises(ValueError, match="unknown weight leaf"):
        weights.program_params(5, like, on_cpu(like), 16, ref.LEAVES)


def test_each_stacked_layer_is_drawn_by_its_place_in_the_model():
    # a repeating pattern of two layer kinds, stacked as units u0 and u1
    # of two entries each: layers 0, 2 in u0 and 1, 3 in u1
    leaves = {"wq": (10, "fan_in")}
    sds = jax.ShapeDtypeStruct((2, 8, 8), jnp.float32)
    like = {"layers": {"u0": {"wq": sds}, "u1": {"wq": sds}}}
    got = weights.program_params(7, like, on_cpu(like), 16, leaves)
    base = weights.seed_key(7)
    for unit in (0, 1):
        for i in (0, 1):
            want = weights.leaf(base, "wq", (8, 8), jnp.float32,
                                layer=2 * i + unit, leaves=leaves)
            # the same draw; XLA may fold the scale in another order
            np.testing.assert_allclose(got["layers"][f"u{unit}"]["wq"][i],
                                       want, rtol=1e-6, atol=0)
    assert np.abs(got["layers"]["u0"]["wq"][0]
                  - got["layers"]["u1"]["wq"][0]).max() > 0.1
