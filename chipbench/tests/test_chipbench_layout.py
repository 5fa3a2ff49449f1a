"""BENCHMARK.json and the files it names: every configuration, reference
module, mix, cell and per-layer metric is found by name, and every name
and unit keeps to the benchmark's character rules."""

import json
import re

import pytest

from chipbench import bench, generator, weights

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.loads((bench.ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units():
    names = [c["name"] for c in BENCH["configs"]] + \
        [w["name"] for w in BENCH["workloads"]] + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_loads_by_name(w):
    cell = bench.load_cell(w["name"])
    assert cell.params["rate"] > 0
    assert cell.params["limits"]["max_logit_gap"] > 0
    assert cell.chips in (1, 4)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.metrics, "every cell reports a per-layer metric"
    for m in cell.metrics:
        assert m["moves"] in reported, (m["name"], m["moves"])
    lens = generator.buckets(cell.mix)
    out = cell.mix["output"]
    assert max(lens) + out.get("max", out.get("value", 0)) <= cell.max_seq


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_matches_its_file(c):
    conf = json.loads((bench.ROOT / c["file"]).read_text())
    assert conf["name"] == c["name"] and conf["source"] == c["source"]
    assert set(conf["reduced"]) == set(c["reduced"])


@pytest.mark.parametrize("path", sorted((bench.HERE / "configs").glob(
    "*.json")), ids=lambda p: p.stem)
def test_config_file(path):
    conf = json.loads(path.read_text())
    assert conf["name"] == path.stem
    for key, (published, run) in conf["reduced"].items():
        assert conf[key] == run != published
    for key in ("assumed", "deployment", "serving"):
        assert conf[key], key
    for key in bench.config_keys(conf):
        assert key in conf, key


@pytest.mark.parametrize("path", sorted((bench.HERE / "configs").glob(
    "*.json")), ids=lambda p: p.stem)
def test_config_names_a_reference_module_that_loads(path):
    conf = json.loads(path.read_text())
    ref = bench.reference(conf)
    assert (bench.REFERENCES / f"{conf['reference']}.py").exists()
    for name in ("KEYS", "LEAVES", "accepts", "score", "decode_flops",
                 "decode_bytes", "prefill_flops", "small"):
        assert hasattr(ref, name), name
    # layer leaf ids: unique, and apart from the leaves every model shares
    ids = [lid for lid, _ in ref.LEAVES.values()]
    assert len(ids) == len(set(ids))
    assert not set(ids) & {lid for lid, _ in weights.SHARED.values()}
    assert not set(ref.LEAVES) & set(weights.SHARED)
    assert not set(ref.KEYS) & set(bench.SHARED_KEYS)
    # the FLOP and byte functions read the configuration
    bigger = dict(conf, num_hidden_layers=2 * conf["num_hidden_layers"])
    for f, args in ((ref.decode_flops, ([1],)), (ref.decode_bytes, ([1],)),
                    (ref.prefill_flops, (128,))):
        assert 0 < f(conf, *args) < f(bigger, *args)


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_reader_by_name(m):
    assert callable(bench.load_metric(m["name"]))
    assert m["layer"] and "\n" not in m["layer"]


def test_mixes_by_name():
    for w in BENCH["workloads"]:
        mix = generator.load_mix(w["traffic"])
        assert mix["arrivals"] == "poisson"


def test_peaks():
    p = bench.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(SystemExit):
        bench.peaks_for("TPU v9 imaginary")
