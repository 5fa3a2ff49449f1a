"""The trace reduction against a small trace recorded on one TPU v5e
(``record_trace.py``: deepseek-7b-pp2 cut to 2 layers under the chat
mix, a 3-second traced window), and on hand-made intervals."""

from pathlib import Path

import pytest

from chipbench import bench, trace

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded():
    tr = trace.load(str(DATA / "deepseek_2layer_chat.xplane.pb"),
                    bench.SPAN_NAMES)
    return tr, trace.reduce(tr, bench.DECODE_PROGRAM)


def coverage(intervals, t0, t1):
    """Busy time by counting open intervals at each edge (a second way
    to take the union)."""
    edges = []
    for _, a, b in intervals:
        a, b = max(a, t0), min(b, t1)
        if b > a:
            edges += [(a, 1), (b, -1)]
    busy, depth, last = 0.0, 0, None
    for t, d in sorted(edges, key=lambda e: (e[0], -e[1])):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_one_chip_and_the_window(recorded):
    tr, out = recorded
    assert list(tr.devices) == [0]
    t0, t1 = trace.window(tr)
    assert out["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert 2.9 < out["window_s"] < 3.2


def test_busy_is_the_union_of_device_operations(recorded):
    tr, out = recorded
    t0, t1 = trace.window(tr)
    busy = coverage(tr.devices[0].ops, t0, t1) / 1e9
    assert out["busy_s"] == pytest.approx(busy, abs=1e-9)
    assert 0 < out["busy_s"] < out["window_s"]


def test_decode_program_time_and_count(recorded):
    tr, out = recorded
    t0, t1 = trace.window(tr)
    runs = [(a, b) for name, a, b in tr.devices[0].modules
            if name.startswith("jit__decode(") and t0 <= a < t1]
    calls = [s for s in tr.spans if s[0] == "executor.decode"
             and t0 <= s[1] < t1]
    # one execution of the decode program per call of the executor
    assert out["program_execs"] == len(runs) == len(calls) > 0
    assert out["program_s"] == pytest.approx(
        sum(min(b, t1) - a for a, b in runs) / 1e9)
    assert out["collective_s"] == 0.0  # one chip: no exchange


def test_idle_gaps_are_named_by_host_spans(recorded):
    _, out = recorded
    idle = out["window_s"] - out["busy_s"]
    assert sum(out["idle_by_span"].values()) == pytest.approx(idle)
    # between arrivals the host waits for the next request: the longest gap
    names = [n for n, _ in out["idle_gaps"]]
    assert out["idle_gaps"][0][0] == "engine.wait"
    assert set(names) <= set(bench.SPAN_NAMES) | {trace.OTHER}
    assert [s for _, s in out["idle_gaps"]] == sorted(
        (s for _, s in out["idle_gaps"]), reverse=True)


def test_top_operations_leave_out_loops(recorded):
    _, out = recorded
    assert len(out["device_ops"]) == 10
    for name, secs in out["device_ops"]:
        prog, op = name.split("/", 1)
        assert prog.startswith("jit_") and not op.startswith("%while")
        assert secs > 0


def test_innermost_host_span_names_a_gap():
    spans = [("executor.prefill", 0, 100), ("engine.admit", 10, 20),
             ("executor.decode", 120, 130)]
    segs = trace.innermost(spans)
    assert segs == [("executor.prefill", 0, 10), ("engine.admit", 10, 20),
                    ("executor.prefill", 20, 100),
                    ("executor.decode", 120, 130)]
    gaps = [(5, 25), (30, 90), (95, 125), (140, 150)]
    assert trace.name_gaps(gaps, segs) == [
        "executor.prefill", "executor.prefill", trace.OTHER, trace.OTHER]


def test_union_and_idle():
    busy = trace.union([("a", 0, 3), ("b", 2, 5), ("c", 7, 8)])
    assert busy == [(0, 5), (7, 8)]
    assert trace.idle_gaps(busy, 0, 10) == [(5, 7), (8, 10)]
