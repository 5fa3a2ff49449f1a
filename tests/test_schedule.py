"""Property tests (hypothesis) for the TSPP/TATP orchestration schedules."""

import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.schedule import (PipeEvent, gpipe_schedule, line_schedule,
                                 one_f_one_b_schedule,
                                 pipeline_bubble_fraction, pipeline_schedule,
                                 pipeline_step_time, ring_schedule, simulate,
                                 simulate_pipeline, tail_latency_rounds)


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=1, max_value=12).map(lambda k: 2 * k))
def test_line_schedule_invariants(n):
    """Alg. 1 on an open line: feasible, one-hop, one compute per round,
    buffer bounded by N/2 blocks."""
    rep = simulate(line_schedule(n))
    assert rep.ok, rep.errors
    assert rep.max_hop == 1
    assert rep.computes_per_die_per_round == 1
    assert rep.n_rounds == n
    assert rep.peak_buffer_blocks <= n // 2 + 1


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=1, max_value=24),
       st.booleans())
def test_ring_schedule_invariants(n, bidirectional):
    rep = simulate(ring_schedule(n, bidirectional))
    assert rep.ok, rep.errors
    assert rep.max_hop <= 1
    if bidirectional:
        # half the rounds, O(1) buffers
        assert rep.n_rounds <= n // 2 + 1
        assert rep.peak_buffer_blocks <= 2
        assert rep.computes_per_die_per_round <= 2
    else:
        assert rep.n_rounds == n
        assert rep.peak_buffer_blocks <= 1
        assert rep.computes_per_die_per_round == 1


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=2, max_value=32))
def test_tail_latency_claim(n):
    """Naive TSPP on a line pays an O(N)-hop wrap; TATP stays at one hop
    (paper Fig. 5a)."""
    assert tail_latency_rounds(n, "line", bidirectional=False) == n - 1
    assert tail_latency_rounds(n, "line", bidirectional=True) == 1
    assert tail_latency_rounds(n, "ring", bidirectional=True) == 1


def test_line_requires_even():
    import pytest
    with pytest.raises(ValueError):
        line_schedule(5)


# ---------------------------------------------------------------------------
# inter-wafer pipeline schedules (multi-wafer level)
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=1, max_value=8),
       st.integers(min_value=1, max_value=16),
       st.booleans())
def test_pipeline_schedule_invariants(pp, n_micro, use_1f1b):
    """Both families: feasible, canonical slot count 2·(n_micro+pp−1) and
    bubble (pp−1)/(n_micro+pp−1); GPipe holds n_micro microbatches in
    flight, 1F1B at most min(pp−s, n_micro) per stage."""
    fn = one_f_one_b_schedule if use_1f1b else gpipe_schedule
    sched = fn(pp, n_micro)
    rep = simulate_pipeline(sched)
    assert rep.ok, rep.errors
    assert rep.n_slots == 2 * (n_micro + pp - 1)
    assert abs(rep.bubble - pipeline_bubble_fraction(pp, n_micro)) < 1e-12
    if use_1f1b:
        for s, infl in enumerate(rep.inflight_per_stage):
            assert infl <= min(pp - s, n_micro)
    else:
        assert rep.peak_inflight == n_micro


def test_pipeline_memory_advantage_of_1f1b():
    """The reason the upper solve level offers 1F1B: same bubble, strictly
    lower peak in-flight activation memory once n_micro > pp."""
    g = simulate_pipeline(gpipe_schedule(4, 16))
    f = simulate_pipeline(one_f_one_b_schedule(4, 16))
    assert g.bubble == f.bubble
    assert f.peak_inflight < g.peak_inflight
    assert f.peak_inflight == 4  # min(pp - 0, n_micro)


def test_pipeline_step_time_matches_closed_form():
    """Uniform stages: the slot walk equals the canonical
    (n_micro+pp−1)·(t_fwd+t_bwd+2·p2p) — exactly for GPipe (phases never
    mix), and for 1F1B when t_fwd == t_bwd (the solver's regime: both are
    step_time/(2·n_micro)).  With t_fwd ≠ t_bwd the synchronous-slot walk
    can only be more conservative for 1F1B (mixed fwd/bwd slots are
    charged at the max)."""
    p2p = 0.002
    for pp, nm in ((1, 4), (2, 8), (4, 8), (6, 16)):
        t = 0.05
        exp = (nm + pp - 1) * (2 * t + 2 * p2p)
        for fn in (gpipe_schedule, one_f_one_b_schedule):
            got = pipeline_step_time(fn(pp, nm), t, t, p2p)
            assert abs(got - exp) < 1e-12, (pp, nm, fn.__name__)
        t_f, t_b = 0.04, 0.06
        exp = (nm + pp - 1) * (t_f + t_b + 2 * p2p)
        got = pipeline_step_time(gpipe_schedule(pp, nm), t_f, t_b, p2p)
        assert abs(got - exp) < 1e-12, (pp, nm, "gpipe asymmetric")
        got = pipeline_step_time(one_f_one_b_schedule(pp, nm), t_f, t_b,
                                 p2p)
        assert got >= exp - 1e-12, (pp, nm, "1f1b asymmetric")


def test_pipeline_step_time_per_boundary():
    """Sequence-form p2p: boundary b is paid by stage b's forwards and
    stage b+1's backwards only — edge ops (stage 0 bwd, last stage fwd)
    send nothing, and a single hot boundary must cost less than charging
    every op the uniform worst case."""
    sched = gpipe_schedule(3, 4)
    t = 0.05
    uniform = pipeline_step_time(sched, t, t, 0.01)
    per_boundary = pipeline_step_time(sched, t, t, [0.01, 0.01])
    assert per_boundary <= uniform  # edge ops stop paying
    hot = pipeline_step_time(sched, t, t, [0.01, 0.0])
    assert hot <= per_boundary
    # zero boundaries == zero scalar exactly
    assert pipeline_step_time(sched, t, t, [0.0, 0.0]) \
        == pipeline_step_time(sched, t, t, 0.0)
    with pytest.raises(ValueError):
        pipeline_step_time(sched, t, t, [0.01])  # needs pp-1 entries


def test_schedule_and_report_memoized():
    from repro.core.schedule import schedule_and_report
    s1, r1 = schedule_and_report("1f1b", 4, 8)
    s2, r2 = schedule_and_report("1f1b", 4, 8)
    assert s1 is s2 and r1 is r2  # one executor run per shape
    assert r1.ok


def test_pipeline_step_time_gated_by_slowest_stage():
    """Synchronous slots: one degraded (2× slower) stage gates the whole
    pipeline, exactly what the multi-wafer solver scores."""
    sched = gpipe_schedule(4, 8)
    base = pipeline_step_time(sched, [0.1] * 4, [0.1] * 4, 0.0)
    slow = pipeline_step_time(sched, [0.1, 0.2, 0.1, 0.1],
                              [0.1, 0.2, 0.1, 0.1], 0.0)
    assert slow > base
    # every slot stage 1 occupies is stretched to 0.2
    assert slow == sum(
        max(0.2 if e.stage == 1 else 0.1
            for e in sched.events if e.t == t)
        for t in range(sched.n_slots))


def test_simulate_pipeline_catches_dependency_violation():
    sched = gpipe_schedule(2, 2)
    # corrupt: run stage 1's first forward before stage 0 produced it
    bad = [PipeEvent(0, 1, "fwd", 0) if (e.stage, e.kind, e.micro)
           == (1, "fwd", 0) else e for e in sched.events]
    sched.events = bad
    rep = simulate_pipeline(sched)
    assert not rep.ok
    assert any("before upstream" in e for e in rep.errors)


def test_pipeline_family_dispatch():
    import pytest
    assert pipeline_schedule("gpipe", 2, 4).family == "gpipe"
    assert pipeline_schedule("1f1b", 2, 4).family == "1f1b"
    with pytest.raises(ValueError):
        pipeline_schedule("dualpipe", 2, 4)
