"""Serving driver: plan-driven continuous-batching decode.

Two modes share the solve → plan → execute pipeline:

* **Engine mode** (``--serve``): compile (or load) a
  :class:`repro.core.plan.ServePlan` — ``dlws_solve(objective="decode")``
  picks the decode mesh and proves the KV budget — then run the
  continuous-batching engine (:mod:`repro.serve.engine`) over a synthetic
  open-loop request stream against the real jitted model::

      PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b \\
          --reduced --serve --auto-plan --requests 8 --rate 4 \\
          --max-batch 4 --prompt-len 16 --max-new 8

  ``--sim`` swaps the jax executor for the cost-model executor (no
  weights, simulation speed — same scheduler, deterministic clock).

  Chaos-grade serving rides the same mode: ``--fault-trace
  flap:SEED | cascade:SEED | FILE.json`` streams a fault/repair
  timeline at the engine (including the real ``JaxServeExecutor`` —
  ``migrate`` rebuilds the mesh per adopted plan), ``--governor`` (with
  ``--coalesce-s/--hysteresis/--backoff-base/--backoff-max/``
  ``--replan-budget/--governor-window``) routes it through the replan
  governor, and ``--prefill-chunk-tokens N`` arms intra-step prefill
  preemption.

* **One-shot mode** (default, the original driver): prefill a batch of
  prompts, then decode a fixed number of tokens::

      PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b \\
          --reduced --batch 4 --prompt-len 32 --gen 16

``--auto-plan`` / ``--plan PATH`` work in both modes; plans come from the
same on-disk cache as training (keyed on arch/shape/wafer incl. faults).
"""

from __future__ import annotations

import argparse
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.spans import NULL as NULL_RECORDER, span


def _build_bundle(cfg, mesh, par, max_batch: int, max_seq: int):
    """Step functions, sharded random params, and the zeroed resident
    decode cache at its global shape, laid out as ``decode_fn`` takes it
    (``cache_specs``), plus that layout for re-placing grafted caches."""
    from repro.configs.base import ShapeConfig
    from repro.core.dist import Dist
    from repro.models.transformer import init_params
    from repro.train.train_loop import cache_shapes, make_serve_fns
    from jax.sharding import NamedSharding

    dist = Dist(mesh)
    shape = ShapeConfig("serve", "decode", max_seq, max_batch)
    sb = make_serve_fns(cfg, par, dist, shape)
    params = jax.jit(lambda k: init_params(k, cfg), out_shardings=jax.tree.map(
        lambda s: NamedSharding(mesh, s), sb.pspecs))(jax.random.key(0))
    cache_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), sb.cspecs)
    shapes = cache_shapes(cfg, shape, dist)
    caches = jax.jit(lambda: jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype), shapes),
        out_shardings=cache_sh)()
    return sb, params, caches, cache_sh


def release(tree):
    """Free a device pytree now, not when the last reference dies: at
    serving widths the resident cache and the params are most of HBM, and
    a second copy must not coexist with the first."""
    for x in jax.tree.leaves(tree):
        if isinstance(x, jax.Array):
            x.delete()


def _nbytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def _graft_via_host(big, small, slots, rows, sharding):
    """Graft ``small``'s rows into ``big``'s slots on the host
    (:func:`repro.models.lm.graft_cache_slots`) and place the result
    under the decode layout.  Both device trees are freed before the
    merged one is placed, so the resident cache never exists twice.

    Migration's graft: its source cache lives on a mesh that is being
    torn down, and may already be on the host.  The ``serve.graft`` span
    records the bytes over the host link (``d2h_bytes``, ``h2d_bytes``:
    every leaf's global size).  Its ``fetch`` part also waits for the
    computation that made ``small``; its ``place`` part ends when
    ``device_put`` returns, so the rest of the copy is waited for by the
    next decode step."""
    from repro.models import lm
    info: dict = {}
    with span("serve.graft", info):
        with span("serve.graft.fetch"):
            host_big, host_small = jax.device_get((big, small))
        info["d2h_bytes"] = _nbytes((host_big, host_small))
        release(big)
        release(small)
        with span("serve.graft.merge"):
            merged = lm.graft_cache_slots(host_big, host_small, slots,
                                          rows=rows)
        info["h2d_bytes"] = _nbytes(merged)
        with span("serve.graft.place"):
            placed = jax.device_put(merged, sharding)
    return placed


def _axis_names(entry) -> tuple:
    """The mesh axes one PartitionSpec entry shards over."""
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, tuple) else (entry,)


def _head_pieces(d_len: int, s_len: int, nq: int) -> list:
    """How the common head ``[0, w)`` of axis 2 moves from shards of
    ``s_len`` positions to shards of ``d_len`` positions (``nq`` shards
    each): ``(source shard, source offset, destination shard, destination
    offset, length)`` per piece that lies inside one shard of each.  Equal
    lengths are equal global sizes: every shard copies its own block
    (shard None)."""
    if d_len == s_len:
        return [(None, 0, None, 0, d_len)]
    w = min(d_len, s_len) * nq
    cuts = sorted({0, w} | set(range(s_len, w, s_len))
                  | set(range(d_len, w, d_len)))
    return [(a // s_len, a % s_len, a // d_len, a % d_len, b - a)
            for a, b in zip(cuts, cuts[1:])]


def _slab_at(x, i, off: int, n: int):
    """Start and size of batch row ``i``'s ``n`` positions of axis 2 from
    ``off`` (every cache leaf is [reps, batch, axis 2, ...]).  The start
    is int32 throughout, as ``i`` is, also where x64 is on."""
    start = [0, i, off] + [0] * (x.ndim - 3)
    return (tuple(jnp.asarray(v, jnp.int32) for v in start),
            (x.shape[0], 1, n) + x.shape[3:])


def _build_slot_scatter(sharding):
    """The jitted graft of :func:`_graft_to_device` for one decode layout.

    Per device (``shard_map``), for each of the ``max_batch`` entries of
    ``slots``/``rows``: the row's piece moves to the batch shard that
    holds the slot (``ppermute`` over the batch axes, one shift per
    shard) and, where axis 2's shards differ in length, to the sequence
    shard that holds its positions (``ppermute`` over those axes, from
    the static shapes).  The shard that holds the slot writes it in place;
    a slot of ``max_batch`` or more is padding and writes nothing."""
    from jax import lax
    from jax.sharding import PartitionSpec as P
    mesh = jax.tree.leaves(sharding)[0].mesh
    specs = jax.tree.map(lambda s: s.spec, sharding)
    sizes = dict(mesh.shape)

    def size(names):
        return math.prod(sizes[n] for n in names)

    def index(names):
        return lax.axis_index(names) if size(names) > 1 else 0

    def leaf(d, s, spec, slot, row):
        spec = tuple(spec) + (None,) * (d.ndim - len(spec))
        b_names = _axis_names(spec[1])
        nb, bd, bs = size(b_names), d.shape[1], s.shape[1]
        my_b = index(b_names)
        own_slot = (slot < nb * bd) & (my_b == slot // bd)
        q_names = _axis_names(spec[2])
        nq = size(q_names)
        for src, soff, dst, doff, n in _head_pieces(d.shape[2], s.shape[2],
                                                    nq):
            start, shape = _slab_at(s, row % bs, soff, n)
            piece = lax.dynamic_slice(s, start, shape)
            if nb > 1:
                got = piece
                for o in range(1, nb):
                    sent = lax.ppermute(piece, b_names,
                                        [(i, (i + o) % nb)
                                         for i in range(nb)])
                    got = jnp.where((my_b - o) % nb == row // bs, sent, got)
                piece = got
            own = own_slot
            if src is not None and nq > 1:
                if src != dst:
                    piece = lax.ppermute(piece, q_names, [(src, dst)])
                own = own & (index(q_names) == dst)
            start, _ = _slab_at(d, slot % bd, doff, n)
            old = lax.dynamic_slice(d, start, piece.shape)
            d = lax.dynamic_update_slice(
                d, jnp.where(own, piece.astype(d.dtype), old), start)
        return d

    def local(big, small, slots, rows):
        flat, tree = jax.tree.flatten(big)
        flat_s = tree.flatten_up_to(small)
        flat_p = tree.flatten_up_to(specs)

        def one(j, flat):
            return [leaf(d, s, p, slots[j], rows[j])
                    for d, s, p in zip(flat, flat_s, flat_p)]

        return tree.unflatten(lax.fori_loop(0, slots.shape[0], one, flat))

    return jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=(specs, specs, P(), P()),
                                 out_specs=specs, check_vma=False),
                   out_shardings=sharding, donate_argnums=0)


_SCATTERS: dict = {}


def _slot_scatter(sharding):
    """The jitted graft for the decode layout ``sharding`` (one per layout;
    it compiles once per prompt length)."""
    leaves, tree = jax.tree.flatten(sharding)
    key = (tree, tuple(leaves))
    if key not in _SCATTERS:
        _SCATTERS[key] = _build_slot_scatter(sharding)
    return _SCATTERS[key]


def _graft_bytes(big, small) -> int:
    """Bytes one grafted row writes into ``big``: the common head of axis 2
    where the two differ there, else the whole row."""
    def row(d, s):
        w = min(d.shape[2], s.shape[2])
        return d.shape[0] * w * math.prod(d.shape[3:]) * d.dtype.itemsize
    return sum(jax.tree.leaves(jax.tree.map(row, big, small)))


def _graft_to_device(big, small, slots, rows, sharding):
    """Graft ``small``'s batch ``rows`` (default: ``0..len(slots)-1``) into
    ``big``'s batch ``slots`` on the device, with the semantics of
    :func:`repro.models.lm.graft_cache_slots`, and keep the decode layout
    ``sharding``.  ``big`` is donated and written in place; ``small`` is
    freed.  Nothing crosses the host link but the two index vectors,
    padded to ``max_batch`` so that every admitted count runs the one
    program compiled for the prompt length.

    The ``serve.graft`` span ends when the call returns (the next host
    read of a device result waits for it); its info holds the bytes over
    the host link (``d2h_bytes``, ``h2d_bytes``: 0), the admitted
    ``rows`` and the ``device_bytes`` the scatter writes."""
    slots = [int(t) for t in slots]
    rows = list(range(len(slots))) if rows is None else [int(r) for r in rows]
    n = jax.tree.leaves(big)[0].shape[1]
    pad = n - len(slots)
    info = {"d2h_bytes": 0, "h2d_bytes": 0, "rows": len(slots),
            "device_bytes": _graft_bytes(big, small) * len(slots)}
    with span("serve.graft", info):
        placed = _slot_scatter(sharding)(
            big, small, jnp.asarray(np.array(slots + [n] * pad, np.int32)),
            jnp.asarray(np.array(rows + [0] * pad, np.int32)))
    release(small)
    return placed


# ---------------------------------------------------------------------------
# engine mode: real-model executor for the continuous-batching engine
# ---------------------------------------------------------------------------


class JaxServeExecutor:
    """ServeEngine executor running the real jitted model off a ServePlan.

    Slot-structured: the decode step always runs the plan's full
    ``max_batch`` shape (idle slots carry dummy tokens at ``cache_len=1``
    and are ignored); admission prefills the newly admitted prompts in
    one padded batch and grafts their prompt-window caches into the
    resident max-seq cache at their slots on the device
    (:func:`_graft_to_device`), leaving every other in-flight request's
    state untouched.  Per-slot context positions go into the decode step
    as the ``cache_len`` vector.

    ``spans`` is the recorder (:mod:`repro.serve.spans`) that
    :meth:`ServeEngine.run` makes active for a run: the no-op one unless a
    caller sets its own.
    """

    spans = NULL_RECORDER

    def __init__(self, plan, cfg, *, mesh=None):
        from repro.launch.mesh import make_plan_mesh

        self.plan = plan
        self.cfg = cfg
        mesh = mesh if mesh is not None else make_plan_mesh(plan.plan)
        self.sb, self.params, self.caches, self._cache_sh = _build_bundle(
            cfg, mesh, plan.parallel_config(), plan.max_batch, plan.max_seq)
        self.last_tok = np.zeros(plan.max_batch, np.int32)
        self.last_logits = None  # decode logits of the latest step
        self._rng = np.random.RandomState(0)

    def _prompt(self, req):
        rng = np.random.RandomState(1000 + req.rid)
        return rng.randint(0, self.cfg.vocab_size, (req.prompt_len,))

    def prefill(self, states):
        # prefill_fn returns only the final position's logits, so one
        # batched call cannot serve mixed prompt lengths: group by length
        # (jit re-traces once per distinct length; synthetic workloads are
        # uniform, so this is one group — and one compile — in practice)
        by_len: dict = {}
        for st in states:
            by_len.setdefault(st.req.prompt_len, []).append(st)
        for group in by_len.values():
            self._prefill_group(group)
        return None  # wall clock: real elapsed time stands

    def _prefill_group(self, states):
        """Prefill one same-length group, graft it into the resident
        cache and take each row's first token.  Returns the group's
        last-position logits ([max_batch, 1, padded vocab]; rows past
        ``len(states)`` are padding)."""
        cfg, plan = self.cfg, self.plan
        plen = states[0].req.prompt_len
        toks = np.zeros((plan.max_batch, plen), np.int64)
        for i, st in enumerate(states):
            toks[i] = self._prompt(st.req)
        pre = {"tokens": jnp.asarray(toks)}
        if cfg.frontend and cfg.family != "encdec":
            pre["prefix_embeds"] = jnp.asarray(
                self._rng.randn(plan.max_batch, cfg.frontend_tokens,
                                cfg.d_model).astype(cfg.dtype) * 0.02)
        if cfg.n_enc_layers:
            pre["enc_embeds"] = jnp.asarray(
                self._rng.randn(plan.max_batch, cfg.frontend_tokens,
                                cfg.d_model).astype(cfg.dtype) * 0.02)
        with span("serve.prefill", {"rows": len(states),
                                    "padded_rows": plan.max_batch}):
            small, logits = self.sb.prefill_fn(self.params, pre)
        self.caches = _graft_to_device(self.caches, small,
                                       [st.slot for st in states],
                                       range(len(states)), self._cache_sh)
        first = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1)) \
            % cfg.vocab_size
        for i, st in enumerate(states):
            st.tokens.append(int(first[i]))
            self.last_tok[st.slot] = first[i]
        return logits

    def decode(self, states):
        toks = np.zeros((self.plan.max_batch, 1), np.int32)
        clen = np.ones(self.plan.max_batch, np.int32)
        for st in states:
            toks[st.slot, 0] = self.last_tok[st.slot]
            clen[st.slot] = st.context_len  # prompt + generated so far
        nxt, self.last_logits, self.caches = self.sb.decode_fn(
            self.params, jnp.asarray(toks), self.caches,
            jnp.asarray(clen))
        with span("serve.decode.wait"):
            nxt = np.asarray(nxt)[:, 0]
        for st in states:
            st.tokens.append(int(nxt[st.slot]))
            self.last_tok[st.slot] = nxt[st.slot]
        return None

    def migrate(self, new_plan, mig, wafer=None):
        """Adopt a post-fault plan: rebuild the mesh/step functions for
        the new contract and graft the survivors' resident KV rows from
        the old cache into their new slots on the host
        (:func:`_graft_via_host`: the old cache leaves the device before
        the new mesh is built, so admission's device graft cannot serve).

        Single-process scope: the degraded mesh is rebuilt over the same
        local device set (``make_plan_mesh`` folds the plan's ring degree
        onto however many devices exist), so "migration" moves cache rows
        between batch slots, not across hosts.  ``max_seq`` is contract-
        stable across replans, so K/V windows copy row-for-row.  Returns
        None: under a WallClock the real rebuild+graft time stands.
        """
        from repro.launch.mesh import make_plan_mesh

        # the old params and cache leave the device before the new
        # bundle is built: two copies of either do not fit at serving
        # widths (params are re-made from the same seed)
        old_caches = jax.device_get(self.caches)
        release((self.params, self.caches))
        self.params = self.caches = None
        old_last = self.last_tok
        self.plan = new_plan
        self.sb, self.params, fresh, self._cache_sh = _build_bundle(
            self.cfg, make_plan_mesh(new_plan.plan),
            new_plan.parallel_config(), new_plan.max_batch,
            new_plan.max_seq)
        if mig.survivors:
            slots = [new_slot for _, _, new_slot in mig.survivors]
            rows = [old_slot for _, old_slot, _ in mig.survivors]
            self.caches = _graft_via_host(fresh, old_caches, slots, rows,
                                          self._cache_sh)
        else:
            self.caches = fresh
        self.last_tok = np.zeros(new_plan.max_batch, np.int32)
        for _, old_slot, new_slot in mig.survivors:
            self.last_tok[new_slot] = old_last[old_slot]
        return None


def serve_engine(args) -> dict:
    """Engine mode: solve → ServePlan → continuous-batching run."""
    from repro.configs import get_config, get_reduced
    from repro.launch.planning import resolve_serve_plan
    from repro.serve.engine import (CostModelExecutor, ServeEngine,
                                    VirtualClock, WallClock,
                                    poisson_arrivals)
    from repro.wafer.topology import Wafer, WaferSpec

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    plan = resolve_serve_plan(cfg, args.max_batch,
                              args.prompt_len + args.max_new,
                              plan_path=args.plan,
                              cache_dir=args.plan_cache,
                              failed_dies=args.failed_dies,
                              allow_ep=not args.no_ep)
    print(plan.summary())
    reqs = poisson_arrivals(
        args.requests, args.rate, seed=args.seed,
        prompt_len=args.prompt_len, max_new_tokens=args.max_new,
        slo_ttft=args.slo_ttft or math.inf,
        slo_tpot=args.slo_tpot or math.inf)
    wafer = Wafer(WaferSpec(rows=plan.plan.wafer_rows,
                            cols=plan.plan.wafer_cols),
                  frozenset(plan.plan.failed_dies))
    faults = ()
    if args.fault_trace is not None:
        from repro.wafer.fault import parse_fault_trace
        trace = parse_fault_trace(args.fault_trace, wafer)
        faults = trace.events
        print(f"fault trace '{args.fault_trace}': {len(faults)} event(s), "
              f"kind={trace.kind}")
    elif args.fault_at is not None:
        from repro.wafer.fault import sample_die_faults
        rep_f = sample_die_faults(wafer, args.fault_frac, seed=args.seed)
        faults = (rep_f.as_event(args.fault_at),)
        print(f"fault scheduled at t={args.fault_at}s: "
              f"dies {rep_f.failed_dies}")
    governor = None
    if args.governor:
        from repro.serve.governor import GovernorConfig
        governor = GovernorConfig(
            coalesce_s=args.coalesce_s, hysteresis=args.hysteresis,
            backoff_base_s=args.backoff_base,
            backoff_max_s=args.backoff_max,
            replan_budget=args.replan_budget,
            window_s=args.governor_window)
    if args.sim:
        ex = CostModelExecutor(plan, cfg, wafer)
        clock = VirtualClock()
    else:
        ex = JaxServeExecutor(plan, cfg)
        clock = WallClock()
    engine = ServeEngine(plan, ex, clock=clock, cfg=cfg, wafer=wafer,
                         faults=faults, readmission=args.readmission,
                         governor=governor,
                         prefill_chunk_tokens=args.prefill_chunk_tokens,
                         plan_cache_dir=args.plan_cache)
    rep = engine.run(reqs)
    out = rep.to_dict()
    out["plan_hash"] = plan.plan_hash
    out["mode"] = "sim" if args.sim else "jax"
    return out


# ---------------------------------------------------------------------------
# one-shot mode (the original driver)
# ---------------------------------------------------------------------------


def serve(args) -> dict:
    from dataclasses import replace
    from repro.configs import get_config, get_reduced
    from repro.configs.base import ParallelConfig
    from repro.core.dist import make_mesh

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    max_seq = args.prompt_len + args.gen
    if args.plan or args.auto_plan:
        from repro.launch.mesh import make_plan_mesh
        from repro.launch.planning import resolve_plan
        plan = resolve_plan(cfg, args.batch, max_seq, plan_path=args.plan,
                            cache_dir=args.plan_cache, remat=False)
        print(plan.summary())
        mesh = make_plan_mesh(plan)
        par = replace(plan.parallel_config(), remat=False)
    else:
        names = ("data", "model")[: len(args.mesh)]
        mesh = make_mesh(tuple(args.mesh), names)
        par = ParallelConfig(strategy="tatp", remat=False)
    sb, params, big, cache_sh = _build_bundle(cfg, mesh, par, args.batch,
                                              max_seq)

    rng = np.random.RandomState(0)
    prompts = rng.randint(0, cfg.vocab_size, (args.batch, args.prompt_len))
    # prefill into a max_seq cache: pad the prompt window
    # build full-size caches and write prompt K/V via a padded prefill
    pre_batch = {"tokens": jnp.asarray(prompts)}
    if cfg.frontend and cfg.family != "encdec":
        pre_batch["prefix_embeds"] = jnp.asarray(
            rng.randn(args.batch, cfg.frontend_tokens, cfg.d_model)
            .astype(cfg.dtype) * 0.02)
    if cfg.n_enc_layers:
        pre_batch["enc_embeds"] = jnp.asarray(
            rng.randn(args.batch, cfg.frontend_tokens, cfg.d_model)
            .astype(cfg.dtype) * 0.02)

    # prefill produces prompt-length caches; graft them into the max_seq
    # layout (the continuous-batching graft, applied to every slot at once)
    small, logits = sb.prefill_fn(params, pre_batch)
    caches = _graft_to_device(big, small, range(args.batch), None, cache_sh)

    toks = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32) \
        % cfg.vocab_size
    out_tokens = [np.asarray(toks)]
    t0 = time.perf_counter()
    for i in range(args.gen):
        cache_len = jnp.full((args.batch,), args.prompt_len + i + 1,
                             jnp.int32)
        toks, logits, caches = sb.decode_fn(params, toks, caches, cache_len)
        out_tokens.append(np.asarray(toks))
    dt = time.perf_counter() - t0
    gen = np.concatenate(out_tokens, axis=1)
    return {
        "generated_shape": list(gen.shape),
        "tokens_per_s": args.batch * args.gen / dt,
        "ms_per_token": dt / args.gen * 1e3,
        "sample": gen[0][:8].tolist(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", type=int, nargs="+", default=[1, 1])
    ap.add_argument("--plan", default=None,
                    help="launch from an explicit plan JSON file "
                         "(a ServePlan in --serve mode)")
    ap.add_argument("--auto-plan", action="store_true",
                    help="solve (or load the cached) plan and build the "
                         "mesh/ParallelConfig from it")
    ap.add_argument("--plan-cache", default=None,
                    help="plan cache dir (default results/plans)")
    ap.add_argument("--failed-dies", default=None,
                    help="comma-separated dead dies (degraded launch)")
    # engine mode
    ap.add_argument("--serve", action="store_true",
                    help="continuous-batching engine mode (needs "
                         "--auto-plan or a ServePlan --plan)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=4.0,
                    help="open-loop arrival rate (req/s)")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="decode slots (max in-flight sequences)")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slo-ttft", type=float, default=None)
    ap.add_argument("--slo-tpot", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-ep", action="store_true",
                    help="pin the decode solve to ep=1 (disable "
                         "expert parallelism; A/B against the EP plan)")
    ap.add_argument("--sim", action="store_true",
                    help="cost-model executor (no jax; virtual clock)")
    # elastic serving: mid-run fault injection
    ap.add_argument("--fault-at", type=float, default=None,
                    help="inject a die-kill fault at this engine time (s): "
                         "live replan + KV migration")
    ap.add_argument("--fault-frac", type=float, default=0.125,
                    help="fraction of alive dies the fault kills "
                         "(exact, seeded)")
    ap.add_argument("--readmission", choices=("live", "drain"),
                    default="live",
                    help="evicted-sequence policy after a migration")
    # fault/repair timelines + replan governor (chaos-grade serving)
    ap.add_argument("--fault-trace", default=None,
                    help="fault/repair timeline: 'flap:SEED' (seeded "
                         "flapping link), 'cascade:SEED' (correlated die "
                         "cascade), or a FaultTrace JSON file "
                         "(schema-validated at load); takes precedence "
                         "over --fault-at")
    ap.add_argument("--governor", action="store_true",
                    help="route fault events through the replan governor "
                         "(debounce + hysteresis + backoff) instead of "
                         "one replan per event")
    ap.add_argument("--coalesce-s", type=float, default=0.25,
                    help="governor debounce window (s)")
    ap.add_argument("--hysteresis", type=float, default=0.05,
                    help="min predicted capacity delta to justify an "
                         "elective replan")
    ap.add_argument("--backoff-base", type=float, default=1.0,
                    help="first replan cool-down (s); doubles per "
                         "consecutive replan")
    ap.add_argument("--backoff-max", type=float, default=60.0,
                    help="cool-down ceiling (s)")
    ap.add_argument("--replan-budget", type=int, default=3,
                    help="max elective replans per governor window")
    ap.add_argument("--governor-window", type=float, default=60.0,
                    help="replan-budget accounting window (s)")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=None,
                    help="chunked prefill with fault-clock checks at "
                         "chunk boundaries (intra-step preemption); "
                         "default: single-pass prefill")
    args = ap.parse_args()
    from repro.launch.mesh import init_compile_cache
    init_compile_cache()
    if args.serve:
        print(json.dumps(serve_engine(args)))
    else:
        print(json.dumps(serve(args)))


if __name__ == "__main__":
    main()
