"""One run of one cell: set-up, pre-roll, the measured window, the check.

Everything that belongs to a cell is found by name: the ``workloads``
entry of ``BENCHMARK.json``, the configuration in
``chipbench/configs/<config>.json``, the module its ``"reference"``
names in the ``references`` directory beside this file (the
architecture's plain forward, weight leaves and operation counts), the
traffic mix in
``chipbench/traffic/<mix>.json``, the cell's rate and limits in
``chipbench/cells/<workload>.json``, each per-layer metric in
``chipbench/metrics/<metric>.py`` and the device's peaks in
``chipbench/peaks.json``.

The window drives the program's ``ServeEngine.run`` on a wall clock over
:class:`chipbench.executor.BenchExecutor`: resolve_serve_plan ->
make_plan_mesh -> prefill -> graft into the resident cache -> decode.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_DIR = ROOT / ".chipbench_trace"
DRAIN_S = 60.0  # at most this long finishing in-flight requests
SENTINEL = 10 ** 9 + 1  # request id of the never-sent keep-alive request
DECODE_PROGRAM = r"^jit__decode$"  # the program's jitted decode step
SPAN_NAMES = ("engine.admit", "engine.wait", "executor.prefill",
              "executor.decode")
REFERENCES = HERE / "references"
# published key every architecture has -> the program's ModelConfig field;
# a reference module's KEYS add the keys of its architecture
SHARED_KEYS = {
    "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "vocab_size": "vocab_size", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}


class BenchError(SystemExit):
    """Ends the run with a non-zero exit and no result line."""

    def __init__(self, msg: str):
        super().__init__(f"chipbench: {msg}")


class WindowClosed(Exception):
    pass


def load_json(path: Path) -> dict:
    if not path.exists():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


@dataclass
class Cell:
    name: str
    chips: int
    conf: dict      # configuration file
    mix: dict       # traffic mix file
    params: dict    # cell file: rate and limits
    metrics: list   # per-layer metric entries of BENCHMARK.json
    end_to_end: list

    @property
    def max_batch(self) -> int:
        return self.conf["serving"]["max_batch"]

    @property
    def max_seq(self) -> int:
        return self.conf["serving"]["max_seq"]

    @property
    def ref(self):
        """The configuration's reference module."""
        return reference(self.conf)


def load_cell(name: str) -> Cell:
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]

    def mine(m):
        return name in m.get("workloads", [name])

    return Cell(name=name, chips=int(w["chips"]),
                conf=load_json(HERE / "configs" / f"{w['config']}.json"),
                mix=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
                params=load_json(HERE / "cells" / f"{name}.json"),
                metrics=[m for m in bench["per_layer"] if mine(m)],
                end_to_end=[m for m in bench["end_to_end"] if mine(m)])


def load_metric(name: str):
    """The reader ``read(ctx)`` of ``chipbench/metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        raise BenchError(f"no reader for metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@functools.cache
def _load_reference(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_ref_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # a dataclass looks up its module
    spec.loader.exec_module(mod)
    return mod


def reference(conf: dict):
    """The module of the ``references`` directory that the configuration
    names with ``"reference"``, loaded once per process."""
    name = conf.get("reference")
    path = REFERENCES / f"{name}.py"
    if not name or not path.exists():
        raise BenchError(f"{conf['name']}: no reference module {name!r} "
                         f"({path})")
    return _load_reference(path)


def peaks_for(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"chipbench/peaks.json")
    return table[kind]


def chips(n: int, require_tpu: bool = True):
    """The first ``n`` accelerator devices; ends the run where JAX finds
    no TPU or too few."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU, but JAX found platform "
                         f"'{devs[0].platform}' ({len(devs)} device(s))")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def config_keys(conf: dict) -> dict:
    """Published key -> ModelConfig field: the shared keys and those of
    the configuration's reference module."""
    return {**SHARED_KEYS, **reference(conf).KEYS}


def model_config(conf: dict):
    """The program's ModelConfig for ``conf``; every published key the
    shared table and the reference module name must be in the file and
    reach the program unchanged, and the reference must compute every
    mechanism the program's configuration has."""
    from repro.configs import get_config
    keys = config_keys(conf)
    missing = [key for key in keys if key not in conf]
    if missing:
        raise BenchError(f"{conf['name']}: no {', '.join(missing)}")
    base = get_config(conf["arch"])
    cfg = dataclasses.replace(base, **{
        field_: conf[key] for key, field_ in keys.items()})
    for key, field_ in keys.items():
        if getattr(cfg, field_) != conf[key]:
            raise BenchError(f"{conf['name']}: {key}={conf[key]} did not "
                             f"reach the program ({field_}="
                             f"{getattr(cfg, field_)})")
    try:
        reference(conf).accepts(cfg)
    except ValueError as e:
        raise BenchError(f"{conf['name']}: {e}") from None
    return cfg


class Compiles:
    """Counts XLA compiles (and loads from the persistent cache)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == COMPILE_EVENT:
            self.n += 1


def wall_clock(spans):
    from repro.serve.engine import WallClock

    class SpanClock(WallClock):
        def wait_until(self, t):
            with spans.span("engine.wait"):
                return super().wait_until(t)

    return SpanClock()


@dataclass
class Window:
    """What the engine hook saw."""
    preroll_s: float
    seconds: float
    nfill: int
    t_engine: float = math.nan   # engine start (requests' time origin)
    t_open: float = math.nan
    t_mid: float = math.nan
    t_stop: float = math.nan
    queue_mid: int = -1
    queue_end: int = -1
    counters_open: tuple = (0, 0)
    counters_stop: tuple = (0, 0)
    compiles_open: int = 0
    compiles_stop: int = 0
    late: list = field(default_factory=list)  # submit - due, seconds

    @property
    def t_close(self) -> float:
        return self.t_open + self.seconds


def serve_window(engine, reqs, win: Window, compiles, tracer=None):
    """Run the engine until the window closes (the first iteration
    boundary after it), opening it once the pre-roll is over and every
    fill request has its first token."""
    sched = engine.sched
    due = {r.rid: r.arrival for r in reqs}
    submit, admit = sched.submit, sched.admit
    spans = engine.executor.spans

    def submit_timed(req):
        # the engine shifts arrivals by its start time: recover it
        win.t_engine = req.arrival - due[req.rid]
        win.late.append(time.perf_counter() - req.arrival)
        return submit(req)

    def admit_spanned(now):
        with spans.span("engine.admit"):
            return admit(now)

    sched.submit, sched.admit = submit_timed, admit_spanned
    fill = set(range(win.nfill))

    def counters():
        return (sched.occupancy_sum, sched.iterations)

    def hook(eng):
        now = time.perf_counter()
        if math.isnan(win.t_open):
            done = {st.req.rid for st in sched.finished} | {
                st.req.rid for st in sched.active.values()
                if st.tokens_done > 0}
            if now - win.t_engine >= win.preroll_s and fill <= done:
                win.t_open, win.counters_open = now, counters()
                win.compiles_open = compiles.n
                if tracer is not None:
                    tracer.start()
            return
        if math.isnan(win.t_mid) and now >= win.t_open + win.seconds / 2:
            win.t_mid, win.queue_mid = now, len(sched.waiting)
        if now >= win.t_close:
            win.t_stop, win.queue_end = now, len(sched.waiting)
            win.counters_stop, win.compiles_stop = counters(), compiles.n
            if tracer is not None:
                tracer.stop()
            raise WindowClosed

    engine.on_iteration = hook
    win.t_engine = time.perf_counter()  # until the first submission
    # a request due long after the close keeps an idle engine waiting
    # (and calling the hook) instead of returning early
    last = max(r.arrival for r in reqs)
    keep = dataclasses.replace(reqs[-1], rid=SENTINEL,
                               arrival=last + win.preroll_s
                               + win.seconds + 3600.0)
    try:
        engine.run(list(reqs) + [keep])
    except WindowClosed:
        pass
    else:
        raise BenchError("the engine drained before the window closed")
    for rid, t in due.items():
        due[rid] = win.t_engine + t
    return due


class Tracer:
    """The JAX profiler over the window, with the window as a span.
    ``keep``: where to copy the raw trace before it is deleted."""

    def __init__(self, path: Path, keep: Path | None = None):
        self.path = path
        self.keep = keep
        self.ann = None

    def start(self):
        import jax
        shutil.rmtree(self.path, ignore_errors=True)
        jax.profiler.start_trace(str(self.path))
        self.ann = jax.profiler.TraceAnnotation("chipbench.window")
        self.ann.__enter__()

    def stop(self):
        import jax
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self) -> dict:
        from chipbench import trace
        files = sorted(self.path.glob("**/*.xplane.pb"))
        if not files:
            raise BenchError("the profiler wrote no trace")
        if self.keep is not None:
            shutil.copyfile(files[-1], self.keep)
        out = trace.reduce(trace.load(str(files[-1]), SPAN_NAMES),
                           DECODE_PROGRAM)
        shutil.rmtree(self.path, ignore_errors=True)
        return out


def drain(engine, want_tokens: int, want_requests: int,
          limit_s: float) -> float:
    """After the close: drop what waits and let the requests in flight
    finish (no more admissions) until at least ``want_requests`` have
    finished, holding ``want_tokens`` served tokens, for at most
    ``limit_s``.  Outside the window; it gives the check finished
    requests that lived through admissions."""
    sched = engine.sched
    sched.waiting.clear()
    t0 = time.perf_counter()

    def enough():
        return (len(sched.finished) >= want_requests and
                sum(len(st.tokens) for st in sched.finished) >= want_tokens)

    def hook(eng):
        if enough() or time.perf_counter() - t0 > limit_s:
            raise WindowClosed

    if enough():
        return 0.0

    engine.on_iteration = hook
    try:
        engine.run([])
    except WindowClosed:
        pass
    return time.perf_counter() - t0


def records(engine, due: dict):
    """An :class:`chipbench.e2e.Rec` for every request sent."""
    from chipbench.e2e import Rec
    sched = engine.sched
    states = {st.req.rid: st for st in
              list(sched.finished) + list(sched.active.values())}
    out = []
    for rid, t in sorted(due.items()):
        r = Rec(rid, t)
        st = states.get(rid)
        if st is not None:
            r.admitted = st.admitted_at
            if not math.isnan(st.first_token_at):
                r.token_times = [st.first_token_at] + list(st.token_times)
        out.append(r)
    return out


def sample(engine, seed: int, want_tokens: int, want_requests: int):
    """Finished requests to check, drawn from the seed: the one with the
    most served tokens, then others, each from a slot not yet covered
    while there is one, until ``want_requests`` requests holding
    ``want_tokens`` served tokens are chosen."""
    fin = sorted(engine.sched.finished, key=lambda st: st.req.rid)
    if not fin:
        return []
    longest = max(fin, key=lambda st: (len(st.tokens), -st.req.rid))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 11]))
    rest = [fin[i] for i in rng.permutation(len(fin))
            if fin[i] is not longest]
    out, n, slots = [longest], len(longest.tokens), {longest.slot}
    while rest and (n < want_tokens or len(out) < want_requests):
        st = next((st for st in rest if st.slot not in slots), rest[0])
        rest.remove(st)
        out.append(st)
        n += len(st.tokens)
        slots.add(st.slot)
    return out


@dataclass
class Ctx:
    """What a per-layer metric reader reads."""
    cell: Cell
    recs: list
    win: Window
    spans: object
    trace: dict | None
    peaks: dict
    plan_solve_s: float
    max_batch: int


@dataclass
class Setup:
    """A cell's executor, warmed up and ready for windows."""
    cell: Cell
    devices: list
    peaks: dict
    plan: object
    cfg: object
    ex: object
    spans: object
    compiles: Compiles
    plan_solve_s: float
    setup_s: float


def prepare(cell: Cell, seed: int, trace: bool, *, require_tpu=True,
            t_start=None, on_executor=None, log=print) -> Setup:
    """Everything before the pre-roll: chip check, plan, weights, cache
    and the warm-up of exactly this cell's shapes."""
    t_start = time.perf_counter() if t_start is None else t_start
    devices = chips(cell.chips, require_tpu)

    def since_start(what):
        log(f"{what}: {time.perf_counter() - t_start:.3f} s after start")

    since_start("JAX found the chips")
    kind = devices[0].device_kind
    peaks = peaks_for(kind) if require_tpu else peaks_for("TPU v5 lite")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise BenchError(f"the program is not in this checkout ({e})")
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import init_compile_cache, make_plan_mesh
    from repro.launch.planning import resolve_serve_plan
    from repro.serve.engine import Request, ServeEngine
    from chipbench import generator
    from chipbench.executor import Spans, bench_executor_class

    init_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = Compiles()
    cfg = model_config(cell.conf)
    mb, ms = cell.max_batch, cell.max_seq

    t = time.perf_counter()
    plan_dir = tempfile.mkdtemp(prefix="chipbench_plan_")
    try:
        plan = resolve_serve_plan(cfg, mb, ms, cache_dir=plan_dir)
    finally:
        shutil.rmtree(plan_dir, ignore_errors=True)
    plan_solve_s = time.perf_counter() - t
    log(f"plan {plan.plan_hash}: degrees (dp,tp,sp,tatp)="
        f"{plan.plan.degrees_tuple()} prefill_chunk={plan.prefill_chunk} "
        f"solve {plan_solve_s:.3f} s")

    spans = Spans(annotate=trace)
    mesh = make_plan_mesh(plan, devices=devices)
    ex = bench_executor_class()(plan, cfg, mesh=mesh, seed=seed, spans=spans,
                                leaves=cell.ref.LEAVES, log=since_start)
    if on_executor is not None:
        on_executor(ex)
    jax.block_until_ready((ex.params, ex.caches))
    since_start(f"mesh {dict(mesh.shape)}; weights and resident cache on "
                "device")

    # warm-up: this cell's prefill shapes, one decode step, one request,
    # with inputs built as the executor builds them (the same programs)
    for plen in generator.buckets(cell.mix):
        _, logits = ex.sb.prefill_fn(
            ex.params, {"tokens": jnp.asarray(np.zeros((mb, plen),
                                                       np.int64))})
        jax.block_until_ready(logits)
        del _, logits
    nxt, ex.last_logits, ex.caches = ex.sb.decode_fn(
        ex.params, jnp.asarray(np.zeros((mb, 1), np.int32)), ex.caches,
        jnp.asarray(np.ones(mb, np.int32)))
    jax.block_until_ready(nxt)
    warm = ServeEngine(plan, ex, clock=wall_clock(spans), cfg=cfg).run(
        [Request(rid=10 ** 9, arrival=0.0,
                 prompt_len=min(generator.buckets(cell.mix)),
                 max_new_tokens=2)])
    if warm.n_finished != 1:
        raise BenchError("the warm-up request did not finish")
    since_start("warm-up done")
    spans.rows.clear()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s; compiles or cache loads so far "
        f"{compiles.n}")
    return Setup(cell, devices, peaks, plan, cfg, ex, spans, compiles,
                 plan_solve_s, setup_s)


def window(su: Setup, rate: float, seconds: float, seed: int,
           tracer=None, log=print):
    """Pre-roll and one window at ``rate``; returns (engine, Window, due
    time of every request)."""
    from repro.serve.engine import Request, ServeEngine
    from chipbench import generator
    cell, mb = su.cell, su.cell.max_batch
    specs = generator.requests(cell.mix, rate, seconds, seed, mb)
    reqs = [Request(rid=s.rid, arrival=s.arrival, prompt_len=s.prompt_len,
                    max_new_tokens=s.max_new_tokens) for s in specs]
    win = Window(cell.mix["preroll_s"], seconds,
                 generator.fill_count(cell.mix, mb))
    engine = ServeEngine(su.plan, su.ex, clock=wall_clock(su.spans),
                         cfg=su.cfg)
    due = serve_window(engine, reqs, win, su.compiles, tracer)
    late = win.late or [0.0]
    log(f"pre-roll {win.t_open - win.t_engine:.3f} s (not in set-up); "
        f"window {win.t_stop - win.t_open:.3f} s to the first iteration "
        f"boundary after its close")
    log(f"generator lateness (submit - due): max {max(late):.6f} s, "
        f"mean {sum(late) / len(late):.6f} s over {len(win.late)} requests")
    log(f"compiles inside the window: {win.compiles_stop - win.compiles_open}")
    log(f"queue: {win.queue_mid} waiting at mid-window, {win.queue_end} at "
        f"the close")
    return engine, win, due


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        control: bool = False, require_tpu: bool = True, t_start=None,
        cell: Cell | None = None, on_executor=None, keep_trace=None,
        log=print) -> dict:
    """One run; returns the result line's object.  ``cell`` and
    ``on_executor`` let tests run a small cell or break the path;
    ``keep_trace`` keeps the raw trace."""
    from chipbench import check, e2e
    cell = cell or load_cell(workload)
    su = prepare(cell, seed, trace, require_tpu=require_tpu,
                 t_start=t_start, on_executor=on_executor, log=log)
    devices, ex, spans = su.devices, su.ex, su.spans
    tracer = Tracer(TRACE_DIR / cell.name, keep_trace) if trace else None
    engine, win, due = window(su, cell.params["rate"], seconds, seed,
                              tracer, log)
    peak = [d.memory_stats()["peak_bytes_in_use"] if d.memory_stats()
            else 0 for d in devices]
    for d, p in zip(devices, peak):
        log(f"device {d.id} peak_bytes_in_use {p}")

    recs = records(engine, due)  # before the drain: its tokens never count
    attempted = sum(1 for t in due.values() if t <= win.t_close)
    rejected = len(engine.sched.rejected)
    metrics: dict = {}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.metrics}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": max(peak)}
    out: dict = {}
    if not trace:
        values = e2e.end_to_end(recs, win.t_open, win.t_close)
        values["setup_s"] = su.setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        tr = tracer.reduce()
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        ctx = Ctx(cell, recs, win, spans, tr, su.peaks, su.plan_solve_s,
                  cell.max_batch)
        for m in cell.metrics:
            v = load_metric(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
        log(f"idle by host span (s): {json.dumps(tr['idle_by_span'])}")

    # the check: a sample of finished requests against the reference
    want = (cell.mix["check_tokens"], cell.mix["check_requests"])
    spent = drain(engine, *want, DRAIN_S)
    log(f"drain after the close: {spent:.3f} s, "
        f"{len(engine.sched.finished)} requests finished in all")
    chosen = sample(engine, seed, *want)
    short = [st.req.rid for st in chosen
             if len(st.tokens) != st.req.max_new_tokens]
    samples = [(ex._prompt(st.req), list(st.tokens)) for st in chosen]
    ex.release()
    limits = cell.params["limits"]
    checks = {"requests_checked": {"value": len(samples),
                                   "limit": want[1]}}
    if samples:
        if control:
            log("control: the fp8 reference's first choices stand in for "
                "the served tokens")
        t = time.perf_counter()
        got = check.widest_gaps(cell.ref, cell.conf, seed, samples, control)
        log(f"check: {len(samples)} requests in slots "
            f"{sorted({st.slot for st in chosen})}, "
            f"{time.perf_counter() - t:.3f} s")
        checks["max_logit_gap"] = {"value": got["max_logit_gap"],
                                   "limit": limits["max_logit_gap"]}
        checks["tokens_checked"] = {"value": got["tokens_checked"],
                                    "limit": 1}
    checks["requests_short"] = {"value": len(short), "limit": 0}
    correct = (len(samples) >= want[1] and not short
               and checks["max_logit_gap"]["value"]
               <= checks["max_logit_gap"]["limit"])
    return {"correct": correct, "attempted": attempted, "failed": rejected,
            "metrics": metrics, "device": device, **out, "checks": checks}
