"""Spans of the serve path (:mod:`repro.serve.spans`): what the engine and
the jax executor record when a recorder is active, that recording changes
nothing served, and that the cost-model executor records nothing."""

import time
from contextlib import contextmanager

import pytest

from repro.configs.paper_models import TABLE_II
from repro.core.plan import compile_serve_plan
from repro.serve import spans
from repro.serve.engine import (CostModelExecutor, Request, ServeEngine,
                                VirtualClock, poisson_arrivals)
from repro.wafer.topology import Wafer, WaferSpec


class ListRecorder:
    def __init__(self):
        self.rows = []

    @contextmanager
    def span(self, name, info=None):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.rows.append((name, t0, time.perf_counter(), info))

    def named(self, name):
        return [r for r in self.rows if r[0] == name]


def inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


# three requests over two slots: the third is admitted while the second
# is still decoding, so its graft lands beside a live row
REQS = [Request(rid=0, arrival=0.0, prompt_len=6, max_new_tokens=3),
        Request(rid=1, arrival=0.0, prompt_len=6, max_new_tokens=6),
        Request(rid=2, arrival=0.0, prompt_len=6, max_new_tokens=4)]


def serve(recorder):
    """Serve REQS on a small model; returns (tokens by rid, the
    executor)."""
    from repro.configs import get_reduced
    from repro.launch.serve import JaxServeExecutor
    cfg = get_reduced("deepseek-7b")
    plan = compile_serve_plan(Wafer(WaferSpec()), cfg, 2, 16,
                              use_cache=False)
    ex = JaxServeExecutor(plan, cfg)
    if recorder is not None:
        ex.spans = recorder
    eng = ServeEngine(plan, ex, clock=VirtualClock())
    assert eng.run(REQS).n_finished == len(REQS)
    return {st.req.rid: list(st.tokens) for st in eng.sched.finished}, ex


def nbytes(tree):
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(tree))


@pytest.fixture(scope="module")
def recorded():
    rec = ListRecorder()
    tokens, ex = serve(rec)
    return rec, tokens, ex


def test_each_admission_and_decode_records_its_steps(recorded):
    import jax
    rec, _, ex = recorded
    its = rec.named("serve.iteration")
    prefills = rec.named("serve.prefill")
    grafts = rec.named("serve.graft")
    assert len(prefills) == len(grafts) == 2
    assert [p[3] for p in prefills] == [{"rows": 2, "padded_rows": 2},
                                        {"rows": 1, "padded_rows": 2}]
    # a grafted row writes the prompt window of every K/V leaf
    plen = REQS[0].prompt_len
    row = sum(a.shape[0] * plen * a.shape[3] * a.shape[4] * a.dtype.itemsize
              for a in jax.tree.leaves(ex.caches))
    for p, g, k in zip(prefills, grafts, (2, 1)):
        # the prefill, then the graft, in one iteration
        assert p[2] <= g[1]
        assert any(inside(p, it) and inside(g, it) for it in its)
        # on the device: nothing over the host link, no host parts
        assert [r for r in rec.rows if inside(r, g) and r is not g] == []
        assert g[3] == {"d2h_bytes": 0, "h2d_bytes": 0, "rows": k,
                        "device_bytes": k * row}
    # two rows live in every step: 0 and 1 for two, 1 and 2 for three;
    # each step's wait lies in an iteration of its own
    waits = rec.named("serve.decode.wait")
    assert len(waits) == 5
    assert sorted(sum(inside(w, it) for w in waits) for it in its
                  if any(inside(w, it) for w in waits)) == [1] * 5
    assert {r[0] for r in rec.rows} == {
        "serve.iteration", "serve.prefill", "serve.graft",
        "serve.decode.wait"}


def test_migration_keeps_the_host_graft_and_its_parts(recorded):
    """Migration still grafts through the host: a slot swap records the
    fetch, merge and place parts with the bytes both ways, and moves the
    rows."""
    import types

    import jax
    import numpy as np
    _, _, ex = recorded
    before = jax.device_get(ex.caches)
    rec = ListRecorder()
    with spans.recording(rec):
        ex.migrate(ex.plan, types.SimpleNamespace(
            survivors=((0, 0, 1), (1, 1, 0)), evicted=()))
    (g,) = rec.named("serve.graft")
    parts = [r for r in rec.rows if inside(r, g) and r is not g]
    assert [r[0] for r in parts] == ["serve.graft.fetch",
                                     "serve.graft.merge",
                                     "serve.graft.place"]
    # the fresh cache and the old one out, the merged one back
    assert g[3] == {"d2h_bytes": 2 * nbytes(before),
                    "h2d_bytes": nbytes(before)}
    after = jax.device_get(ex.caches)
    for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before)):
        np.testing.assert_array_equal(a[:, [1, 0]], b)


def test_recording_changes_no_served_token(recorded):
    _, tokens, _ = recorded
    plain, _ = serve(None)
    assert plain == tokens
    assert [len(tokens[r.rid]) for r in REQS] == [r.max_new_tokens
                                                  for r in REQS]


def test_cost_model_runs_record_nothing_and_keep_their_trace(tmp_path):
    cfg, _ = TABLE_II["gpt3-6.7b"]
    plan = compile_serve_plan(Wafer(WaferSpec()), cfg, 8, 256,
                              cache_dir=str(tmp_path))
    reqs = poisson_arrivals(40, 200.0, seed=3, prompt_len=64,
                            max_new_tokens=8)

    def run():
        ex = CostModelExecutor(plan, cfg, Wafer(WaferSpec()))
        return ServeEngine(plan, ex, clock=VirtualClock()).run(reqs)

    plain = run()
    outer = ListRecorder()
    with spans.recording(outer):
        watched = run()
    assert outer.rows == []
    assert watched.trace_hash == plain.trace_hash
    assert watched.to_dict() == plain.to_dict()


def test_the_null_recorder_hands_out_one_shared_context():
    assert spans.span("serve.prefill") is spans.span("serve.graft", {})
    rec = ListRecorder()
    with spans.recording(rec):
        with spans.span("serve.graft.merge"):
            pass
        with spans.recording(None):
            with spans.span("serve.graft.place"):
                pass
    with spans.span("serve.iteration"):
        pass
    assert [r[0] for r in rec.rows] == ["serve.graft.merge"]
