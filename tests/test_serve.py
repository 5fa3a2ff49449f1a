"""Serving subsystem: decode-objective solve, ServePlan IR (JSON
round-trip, plan-hash stability, splan cache), continuous-batching
scheduler invariants (FCFS admission, KV budget, prefill/decode
interleaving, SLO accounting, determinism), and the per-row ``cache_len``
decode path (batched vector == per-row scalar runs)."""

import json
import math
import os

import numpy as np
import pytest

from repro.configs.paper_models import TABLE_II
from repro.core.plan import (PLAN_STATS, ServePlan, compile_serve_plan,
                             reset_plan_stats)
from repro.serve.engine import (ContinuousBatchingScheduler,
                                CostModelExecutor, Request, ServeEngine,
                                VirtualClock, poisson_arrivals)
from repro.wafer.simulator import (ParallelDegrees, StepCostContext,
                                   decode_memory_components,
                                   simulate_decode_batch)
from repro.wafer.solver import dlws_solve
from repro.wafer.topology import Wafer, WaferSpec

CFG, _ = TABLE_II["gpt3-6.7b"]
MAX_BATCH, MAX_SEQ = 8, 256


@pytest.fixture(autouse=True)
def _fresh_stats():
    reset_plan_stats()
    yield
    reset_plan_stats()


@pytest.fixture()
def plan(tmp_path):
    return compile_serve_plan(Wafer(WaferSpec()), CFG, MAX_BATCH, MAX_SEQ,
                              cache_dir=str(tmp_path))


# ---------------------------------------------------------------------------
# decode objective
# ---------------------------------------------------------------------------


def test_decode_solve_ok_and_distinct_scoring():
    w = Wafer(WaferSpec())
    sol = dlws_solve(w, CFG, 64, 8192, objective="decode")
    assert sol.best.ok and sol.method == "dlws-decode"
    # per-token latency and tokens/s are consistent
    assert sol.best.throughput == pytest.approx(64 / sol.best.step_time)
    # decode memory = weights + cache + workspace (no grads/optimizer)
    ctx = StepCostContext(w, CFG, 64, 8192, objective="decode")
    wb, cache, ws = decode_memory_components(ctx, sol.config)
    assert sol.best.mem_per_die == pytest.approx(wb + cache + ws)
    assert cache > 0


def test_decode_tp_cannot_exceed_heads():
    w = Wafer(WaferSpec())
    ctx = StepCostContext(w, CFG, 8, 1024, objective="decode")
    deg = ParallelDegrees(1, CFG.n_heads * 2, 1, 1)
    res = simulate_decode_batch(ctx, [deg])[0]
    assert res.oom and not res.ok
    assert "heads" in res.breakdown["reason"]


def test_decode_dp_bounded_by_inflight_batch():
    """dp > batch (or not dividing it) is unexecutable — each replica
    serves whole sequences — and must never leave the solver."""
    w = Wafer(WaferSpec())
    ctx = StepCostContext(w, CFG, 4, 256, objective="decode")
    res = simulate_decode_batch(ctx, [ParallelDegrees(32, 1, 1, 1)])[0]
    assert not res.ok and "batch" in res.breakdown["reason"]
    res3 = simulate_decode_batch(ctx, [ParallelDegrees(3, 1, 1, 1)])[0]
    assert not res3.ok  # 3 does not divide 4
    sol = dlws_solve(w, CFG, 4, 256, objective="decode")
    assert sol.best.ok and sol.config.dp <= 4 and 4 % sol.config.dp == 0


def test_decode_kv_scan_scales_with_context():
    """Twice the KV budget must cost more per token (the HBM scan term)."""
    w = Wafer(WaferSpec())
    deg = ParallelDegrees(1, 8, 1, 4)
    short = simulate_decode_batch(
        StepCostContext(w, CFG, 32, 2048, objective="decode"), [deg])[0]
    long = simulate_decode_batch(
        StepCostContext(w, CFG, 32, 8192, objective="decode"), [deg])[0]
    assert long.step_time > short.step_time
    assert long.mem_per_die > short.mem_per_die


def test_train_objective_untouched_by_decode_plumb():
    """The train path must not see the decode evaluator (bitwise pins)."""
    w = Wafer(WaferSpec())
    a = dlws_solve(w, CFG, 32, 2048)
    b = dlws_solve(w, CFG, 32, 2048, evaluator="reference")
    assert a.config == b.config
    assert a.best.throughput == b.best.throughput


# ---------------------------------------------------------------------------
# ServePlan IR
# ---------------------------------------------------------------------------


def test_serveplan_json_roundtrip_and_hash(plan, tmp_path):
    again = ServePlan.loads(plan.dumps())
    assert again == plan
    assert again.plan_hash == plan.plan_hash
    p = os.path.join(str(tmp_path), "sp.json")
    plan.dump(p)
    assert ServePlan.load(p) == plan


def test_serveplan_hash_ignores_telemetry_tracks_contract(plan):
    d = plan.to_dict()
    d["predicted"] = {}
    d["solver"] = {"evaluated": 1}
    assert ServePlan.from_dict(d).plan_hash == plan.plan_hash
    d["max_batch"] = plan.max_batch * 2
    assert ServePlan.from_dict(d).plan_hash != plan.plan_hash
    d2 = plan.to_dict()
    d2["stream_dtype"] = "fp8"
    assert ServePlan.from_dict(d2).plan_hash != plan.plan_hash


def test_serveplan_cache_hit_skips_solver(tmp_path):
    w = Wafer(WaferSpec())
    p1 = compile_serve_plan(w, CFG, MAX_BATCH, MAX_SEQ,
                            cache_dir=str(tmp_path))
    assert PLAN_STATS["solver_calls"] == 1
    p2 = compile_serve_plan(w, CFG, MAX_BATCH, MAX_SEQ,
                            cache_dir=str(tmp_path))
    assert PLAN_STATS["solver_calls"] == 1
    assert PLAN_STATS["cache_hits"] == 1
    assert p2 == p1
    # a degraded wafer misses and re-solves
    compile_serve_plan(w.with_faults(dies=[3]), CFG, MAX_BATCH, MAX_SEQ,
                       cache_dir=str(tmp_path))
    assert PLAN_STATS["solver_calls"] == 2


def test_serveplan_version_rejected(plan):
    d = plan.to_dict()
    d["version"] = 999
    with pytest.raises(ValueError):
        ServePlan.from_dict(d)
    bad = json.loads(plan.dumps())
    bad["plan"]["version"] = 999
    with pytest.raises(ValueError):
        ServePlan.from_dict(bad)


def test_serveplan_kv_budget_matches_cost_model(plan):
    """The plan's KV bytes must equal the cost model's cache term — the
    admission budget and the solver's memory feasibility are one number."""
    w = Wafer(WaferSpec())
    ctx = StepCostContext(w, CFG, plan.max_batch, plan.max_seq,
                          objective="decode")
    deg = ParallelDegrees(*plan.plan.degrees_tuple(),
                          seq_par=plan.plan.seq_par)
    _, cache, _ = decode_memory_components(ctx, deg)
    assert plan.kv_bytes_per_die == pytest.approx(cache)


# ---------------------------------------------------------------------------
# continuous-batching scheduler
# ---------------------------------------------------------------------------


class FixedLatencyExecutor:
    """Deterministic executor with hand-set step costs (pure scheduler
    tests: no cost model in the loop)."""

    def __init__(self, prefill_per_tok=1e-3, decode_iter=1e-2):
        self.prefill_per_tok = prefill_per_tok
        self.decode_iter = decode_iter

    def prefill(self, states):
        return sum(self.prefill_per_tok * st.req.prompt_len
                   for st in states)

    def decode(self, states):
        for st in states:
            st.tokens.append(0)
        return self.decode_iter


def _requests(n, *, arrival_gap=0.0, prompt=16, gen=4, **kw):
    return [Request(rid=i, arrival=i * arrival_gap, prompt_len=prompt,
                    max_new_tokens=gen, **kw) for i in range(n)]


def test_admission_is_fcfs_and_complete(plan):
    engine = ServeEngine(plan, FixedLatencyExecutor())
    rep = engine.run(_requests(30, arrival_gap=0.001))
    assert rep.n_finished == 30
    rids = [rid for _, rid in engine.sched.admission_trace]
    assert rids == sorted(rids)  # no bypass, ever
    assert rep.generated_tokens == 30 * 4


def test_capacity_and_kv_budget_never_exceeded(plan):
    seen = []

    def probe(engine):
        s = engine.sched
        seen.append((len(s.active), s.kv_reserved))
        assert len(s.active) <= plan.max_batch
        assert s.kv_reserved <= plan.kv_budget_tokens

    engine = ServeEngine(plan, FixedLatencyExecutor(),
                         on_iteration=probe)
    engine.run(_requests(40, prompt=64, gen=32))
    assert max(n for n, _ in seen) == plan.max_batch  # saturates
    assert max(k for _, k in seen) <= plan.kv_budget_tokens


def test_prefill_decode_interleaving_invariants(plan):
    engine = ServeEngine(plan, FixedLatencyExecutor())
    rep = engine.run(_requests(20, arrival_gap=0.005, gen=5))
    assert rep.n_finished == 20
    for st in engine.sched.finished:
        # prefill yields the first token; decode the rest, one per iter
        assert st.tokens_done == st.req.max_new_tokens
        assert len(st.token_times) == st.tokens_done - 1
        assert not math.isnan(st.first_token_at)
        if st.token_times:
            assert st.first_token_at <= st.token_times[0]
            assert all(a < b for a, b in zip(st.token_times,
                                             st.token_times[1:]))
        assert st.finished_at >= st.admitted_at >= st.req.arrival


def test_oversized_request_rejected_not_crashed(plan):
    # a request that can never fit is rejected with a recorded reason and
    # the queue behind it keeps being served (no head-of-line deadlock)
    reqs = [Request(rid=0, arrival=0.0,
                    prompt_len=plan.kv_budget_tokens + 1,
                    max_new_tokens=plan.max_seq * plan.max_batch + 1),
            Request(rid=1, arrival=0.0, prompt_len=16, max_new_tokens=4),
            Request(rid=2, arrival=0.0, prompt_len=16, max_new_tokens=4)]
    rep = ServeEngine(plan, FixedLatencyExecutor()).run(reqs)
    assert rep.n_rejected == 1
    assert rep.n_requests == 3
    assert rep.n_finished == 2
    (rid, reason), = rep.rejected
    assert rid == 0 and "can never fit" in reason


def test_submit_validates_request_fields(plan):
    sched = ContinuousBatchingScheduler(plan)
    with pytest.raises(ValueError, match="max_new_tokens must be positive"):
        sched.submit(Request(rid=0, arrival=0.0, prompt_len=8,
                             max_new_tokens=0))
    with pytest.raises(ValueError, match="prompt_len must be non-negative"):
        sched.submit(Request(rid=1, arrival=0.0, prompt_len=-1,
                             max_new_tokens=4))
    # engine.run goes through submit, so a bad request in a stream fails
    # fast with the same message instead of tripping scheduler asserts
    with pytest.raises(ValueError, match="max_new_tokens"):
        ServeEngine(plan, FixedLatencyExecutor()).run(
            [Request(rid=2, arrival=0.0, prompt_len=8, max_new_tokens=-3)])


def test_slo_accounting(plan):
    # generous SLOs: all met
    ok = ServeEngine(plan, FixedLatencyExecutor()).run(
        _requests(10, slo_ttft=1e9, slo_tpot=1e9))
    assert ok.slo_attainment == 1.0
    # impossible TPOT: none met
    bad = ServeEngine(plan, FixedLatencyExecutor()).run(
        _requests(10, gen=4, slo_ttft=1e9, slo_tpot=1e-9))
    assert bad.slo_attainment == 0.0


def test_engine_deterministic_with_cost_model_executor(plan):
    w = Wafer(WaferSpec())
    reqs = poisson_arrivals(60, 200.0, seed=3, prompt_len=64,
                            max_new_tokens=8)
    r1 = ServeEngine(plan, CostModelExecutor(plan, CFG, w),
                     clock=VirtualClock()).run(reqs)
    r2 = ServeEngine(plan, CostModelExecutor(plan, CFG, w),
                     clock=VirtualClock()).run(reqs)
    assert r1.to_dict() == r2.to_dict()
    assert r1.n_finished == 60
    # queueing under load: decode latency grows with occupancy, so the
    # p99 inter-token latency cannot beat an idle iteration
    ex = CostModelExecutor(plan, CFG, w)
    assert r1.tpot_p50 >= ex.decode_latency(1, 1) * 0.99


def test_scheduler_rejects_out_of_order_submission(plan):
    sched = ContinuousBatchingScheduler(plan)
    sched.submit(Request(rid=0, arrival=1.0, prompt_len=4,
                         max_new_tokens=1))
    with pytest.raises(ValueError):
        sched.submit(Request(rid=1, arrival=0.5, prompt_len=4,
                             max_new_tokens=1))


# ---------------------------------------------------------------------------
# per-row cache_len decode (the runtime enabler for continuous batching)
# ---------------------------------------------------------------------------


def _tiny_model():
    import jax
    from repro.configs import get_reduced
    from repro.configs.base import ParallelConfig
    from repro.core.dist import Dist, make_mesh
    from repro.models.transformer import RunCtx, init_params
    cfg = get_reduced("deepseek-7b")
    mesh = make_mesh((1,), ("model",))
    ctx = RunCtx(cfg, ParallelConfig(strategy="tatp", remat=False),
                 Dist(mesh), phase="decode")
    params = init_params(jax.random.key(0), cfg)
    return cfg, ctx, params


def _prefilled(cfg, ctx, params, b, s, max_seq, seed=0):
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    rng = np.random.RandomState(seed)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (b, s)))}
    caches, logits = jax.jit(
        lambda p, bt: lm.prefill(ctx, p, bt))(params, batch)
    big = lm.init_cache(ctx, b, max_seq)
    merged = lm.graft_cache_slots(jax.device_get(big),
                                  jax.device_get(caches),
                                  slots=range(b))
    return jax.tree.map(jnp.asarray, merged), logits


def test_vector_cache_len_matches_scalar():
    """A uniform [B] cache_len vector must reproduce the scalar path."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    cfg, ctx, params = _tiny_model()
    b, s, max_seq = 2, 8, 16
    caches, logits = _prefilled(cfg, ctx, params, b, s, max_seq)
    tok = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32) \
        % cfg.vocab_size
    step = jax.jit(lambda p, t, c, n: lm.decode_step(ctx, p, t, c, n))
    n_sc, l_sc, c_sc = step(params, tok, caches, jnp.int32(s + 1))
    n_vec, l_vec, c_vec = step(params, tok, caches,
                               jnp.full((b,), s + 1, jnp.int32))
    assert np.array_equal(np.asarray(n_sc), np.asarray(n_vec))
    np.testing.assert_allclose(np.asarray(l_sc, np.float32),
                               np.asarray(l_vec, np.float32), rtol=1e-5)
    for a, c in zip(jax.tree.leaves(c_sc), jax.tree.leaves(c_vec)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(c, np.float32), rtol=1e-5)


def test_mixed_cache_len_rows_match_isolated_decodes():
    """Rows decoding at different context lengths in one batched step must
    equal each row decoded alone — the continuous-batching correctness
    property (per-row masks, rope positions and KV writes)."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    cfg, ctx, params = _tiny_model()
    max_seq = 16
    s0, s1 = 6, 10  # two requests at different context lengths
    caches0, logits0 = _prefilled(cfg, ctx, params, 1, s0, max_seq, seed=0)
    caches1, logits1 = _prefilled(cfg, ctx, params, 1, s1, max_seq, seed=1)
    # batched cache: row 0 at context s0, row 1 at context s1
    big = lm.init_cache(ctx, 2, max_seq)
    big = lm.graft_cache_slots(jax.device_get(big),
                               jax.device_get(caches0), slots=[0])
    big = jax.tree.map(jnp.asarray, lm.graft_cache_slots(
        big, jax.device_get(caches1), slots=[1]))
    t0 = jnp.argmax(logits0[:, -1:, :], axis=-1).astype(jnp.int32) \
        % cfg.vocab_size
    t1 = jnp.argmax(logits1[:, -1:, :], axis=-1).astype(jnp.int32) \
        % cfg.vocab_size
    toks = jnp.concatenate([t0, t1], axis=0)
    clen = jnp.asarray([s0 + 1, s1 + 1], jnp.int32)
    step = jax.jit(lambda p, t, c, n: lm.decode_step(ctx, p, t, c, n))
    n_b, l_b, _ = step(params, toks, big, clen)
    # isolated references (scalar cache_len per single-row batch)
    n0, l0, _ = step(params, t0, caches0, jnp.int32(s0 + 1))
    n1, l1, _ = step(params, t1, caches1, jnp.int32(s1 + 1))
    assert int(n_b[0, 0]) == int(n0[0, 0])
    assert int(n_b[1, 0]) == int(n1[0, 0])
    # the batched and the isolated step sum the same f32 products over
    # d_model in another order (different batch shapes, different XLA
    # tiling), so logits near zero can differ by d_model roundings of the
    # largest logit: the relative bound alone cannot hold there
    for got, ref in ((l_b[0], l0[0]), (l_b[1], l1[0])):
        ref = np.asarray(ref, np.float32)
        atol = cfg.d_model * np.finfo(np.float32).eps * np.abs(ref).max()
        np.testing.assert_allclose(np.asarray(got, np.float32), ref,
                                   rtol=2e-4, atol=atol)


@pytest.mark.slow
def test_jax_executor_mixed_prompt_lengths():
    """The real-model executor must serve requests with different prompt
    lengths admitted in one iteration (prefill groups by length)."""
    from repro.configs import get_reduced
    from repro.launch.serve import JaxServeExecutor
    from repro.serve.engine import ServeEngine, WallClock
    cfg = get_reduced("deepseek-7b")
    plan = compile_serve_plan(Wafer(WaferSpec()), cfg, 2, 16,
                              use_cache=False)
    ex = JaxServeExecutor(plan, cfg)
    reqs = [Request(rid=0, arrival=0.0, prompt_len=6, max_new_tokens=3),
            Request(rid=1, arrival=0.0, prompt_len=10, max_new_tokens=3)]
    rep = ServeEngine(plan, ex, clock=WallClock()).run(reqs)
    assert rep.n_finished == 2
    assert rep.generated_tokens == 6


def test_graft_cache_slots_touches_only_target_slots():
    rng = np.random.RandomState(0)
    big = {"k": rng.randn(1, 4, 8, 2, 3), "state": rng.randn(1, 4, 5)}
    small = {"k": rng.randn(1, 2, 4, 2, 3), "state": rng.randn(1, 2, 5)}
    from repro.models.lm import graft_cache_slots
    out = graft_cache_slots(big, small, slots=[1, 3])
    np.testing.assert_array_equal(out["k"][:, [0, 2]], big["k"][:, [0, 2]])
    np.testing.assert_array_equal(out["k"][:, 1, :4], small["k"][:, 0])
    np.testing.assert_array_equal(out["k"][:, 1, 4:], big["k"][:, 1, 4:])
    np.testing.assert_array_equal(out["state"][:, 3], small["state"][:, 1])


# ---------------------------------------------------------------------------
# admission's graft on the device (the executor's donated slot scatter)
# ---------------------------------------------------------------------------

GRAFT_BATCH, GRAFT_SEQ = 4, 32


def _graft_trees(arch, window, seed=0):
    """Seeded host trees of ``arch``'s decode cache at reduced widths: the
    resident one (GRAFT_SEQ positions) and a prefill one (``window``),
    with the one-device decode layout."""
    import jax
    from jax.sharding import NamedSharding
    from repro.configs import get_reduced
    from repro.configs.base import ParallelConfig, ShapeConfig
    from repro.core.dist import Dist, make_mesh
    from repro.train.train_loop import cache_shapes, cache_specs
    cfg = get_reduced(arch)
    dist = Dist(make_mesh((1, 1), ("data", "model")))
    rng = np.random.default_rng(seed)

    def tree(seq):
        shape = ShapeConfig("serve", "decode", seq, GRAFT_BATCH)
        return jax.tree.map(
            lambda a: rng.standard_normal(a.shape).astype(a.dtype),
            cache_shapes(cfg, shape, dist))

    shape = ShapeConfig("serve", "decode", GRAFT_SEQ, GRAFT_BATCH)
    sharding = jax.tree.map(
        lambda s: NamedSharding(dist.mesh, s),
        cache_specs(cfg, shape, ParallelConfig(strategy="tatp"), dist))
    return tree(GRAFT_SEQ), tree(window), sharding


def _bits(a):
    return np.ascontiguousarray(a).view(np.uint8)


@pytest.mark.parametrize("window", [8, 20])
@pytest.mark.parametrize("slots", [[2], [3, 0, 2], [2, 0, 3, 1]],
                         ids=["k1", "k3", "kmax"])
@pytest.mark.parametrize("arch", ["qwen2-72b", "mamba2-780m",
                                  "zamba2-2.7b"])
def test_device_graft_matches_the_host_graft(arch, slots, window):
    """Bit for bit what ``graft_cache_slots`` makes on the host, on a GQA,
    an SSM and a hybrid tree: the admitted rows' prompt window lands at
    the head of their slots, every other slot and position is kept, the
    layout too; the resident cache is donated and the prefill cache
    freed."""
    import jax
    from repro.launch.serve import _graft_to_device
    from repro.models.lm import graft_cache_slots
    big, small, sharding = _graft_trees(arch, window)
    want = graft_cache_slots(big, small, slots)
    dev_big = jax.device_put(big, sharding)
    dev_small = jax.device_put(small, sharding)
    got = _graft_to_device(dev_big, dev_small, slots, None, sharding)
    others = [i for i in range(GRAFT_BATCH) if i not in slots]
    for g, w, b, sh in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                           jax.tree.leaves(big), jax.tree.leaves(sharding)):
        assert g.sharding == sh
        host = np.asarray(g)
        assert np.array_equal(_bits(host), _bits(w))
        assert np.array_equal(_bits(host[:, others]), _bits(b[:, others]))
    assert all(x.is_deleted() for x in jax.tree.leaves((dev_big, dev_small)))


def test_device_graft_compiles_once_for_every_admitted_count():
    """The slot and row vectors are padded to max_batch: admitting 1 to
    max_batch rows (in any slots, from any rows) runs one program."""
    import jax
    from repro.launch.serve import _graft_to_device, _slot_scatter
    from repro.models.lm import graft_cache_slots
    big, small, sharding = _graft_trees("qwen2-72b", 12)
    caches = jax.device_put(big, sharding)
    want = big
    sizes = []
    for k in range(1, GRAFT_BATCH + 1):
        slots = list(range(GRAFT_BATCH))[::-1][:k]
        rows = list(range(GRAFT_BATCH))[-k:]
        caches = _graft_to_device(caches, jax.device_put(small, sharding),
                                  slots, rows, sharding)
        want = graft_cache_slots(want, small, slots, rows=rows)
        sizes.append(_slot_scatter(sharding)._cache_size())
    assert sizes == sizes[:1] * GRAFT_BATCH
    for g, w in zip(jax.tree.leaves(caches), jax.tree.leaves(want)):
        assert np.array_equal(_bits(np.asarray(g)), _bits(w))
