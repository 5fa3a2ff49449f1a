"""Reduction of a profiler trace to device metrics.

The JAX profiler writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` into plain intervals in nanoseconds on the
trace's one clock:

* device operations: each ``/device:TPU:<n>`` plane's ``XLA Ops`` line
  (named by their HLO instruction text);
* device programs: the same plane's ``XLA Modules`` line (one event per
  execution of a compiled program, named after the jitted function);
* host spans: events of the host plane whose names the caller lists (the
  benchmark's ``TraceAnnotation`` spans).

:func:`reduce` takes the window from the span named ``window`` and gives
the device's busy time (union of operation intervals), the time of each
execution of the programs that match a pattern, the collective
operations' time inside them, the operations that took most time (loops
and calls, which contain other operations, left out) and the idle gaps,
each named by the innermost host span that covers most of it.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(r"^%?(all-gather|all-reduce|collective-permute|"
                        r"reduce-scatter|all-to-all|send|recv)")
CONTAINER = re.compile(r"^%?(while|conditional|call)\b")
WINDOW_SPAN = "chipbench.window"
OTHER = "host.other"


@dataclass
class Device:
    ops: list = field(default_factory=list)      # (name, start, end)
    modules: list = field(default_factory=list)  # (name, start, end)


@dataclass
class Trace:
    devices: dict            # device id -> Device
    spans: list              # (name, start, end): host spans


def load(path: str, span_names) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: dict = {}
    spans = []
    wanted = set(span_names) | {WINDOW_SPAN}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device())
            for line in plane.lines:
                dest = {OPS_LINE: dev.ops, MODULES_LINE: dev.modules}.get(
                    line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    dest.append((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return Trace(devices, spans)


def op_name(text: str) -> str:
    """An operation's short name: the HLO instruction text up to ' = '."""
    return text.split(" = ", 1)[0]


def program_name(text: str) -> str:
    """A program's name without its fingerprint: 'jit__decode(123)'."""
    return text.split("(", 1)[0]


def clip(intervals, t0, t1):
    return [(n, max(a, t0), min(b, t1)) for n, a, b in intervals
            if b > t0 and a < t1]


def union(intervals) -> list:
    """Merged (start, end) pairs of a list of (name, start, end)."""
    out: list = []
    for _, a, b in sorted(intervals, key=lambda x: x[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def innermost(spans) -> list:
    """Host time cut into (name, start, end) segments, each named by the
    innermost span that covers it (spans of one thread nest)."""
    segs: list = []
    stack: list = []
    t = None

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][2] <= limit:
            name, _, end = stack.pop()
            if end > t:
                segs.append((name, t, end))
            t = max(t, end)

    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        if t is not None:
            close_until(a)
            if stack and a > t:
                segs.append((stack[-1][0], t, a))
        stack.append((name, a, b))
        t = a
    if t is not None:
        close_until(float("inf"))
    return segs


def idle_gaps(busy, t0, t1) -> list:
    gaps, t = [], t0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < t1:
        gaps.append((t, t1))
    return gaps


def name_gaps(gaps, segs) -> list:
    """Name each (start, end) gap after the host segment that covers most
    of it, or ``host.other`` where none covers half.  Both lists are
    sorted and the segments do not overlap."""
    starts = [s[1] for s in segs]
    out = []
    for a, b in gaps:
        share: dict = defaultdict(float)
        k = max(bisect.bisect_right(starts, a) - 1, 0)
        while k < len(segs) and segs[k][1] < b:
            name, s0, s1 = segs[k]
            ov = min(b, s1) - max(a, s0)
            if ov > 0:
                share[name] += ov
            k += 1
        best = max(share, key=share.get) if share else OTHER
        out.append(best if share and share[best] >= (b - a) / 2 else OTHER)
    return out


def window(tr: Trace):
    spans = [s for s in tr.spans if s[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace has no '{WINDOW_SPAN}' span")
    return spans[0][1], spans[0][2]


def reduce(tr: Trace, program: str) -> dict:
    """Per-device sums over the traced window, averaged over devices.

    ``program`` is a regular expression for the step program whose
    executions are timed (the decode step).  Times are in seconds."""
    t0, t1 = window(tr)
    pat = re.compile(program)
    host = [s for s in tr.spans if s[0] != WINDOW_SPAN]
    segs = innermost(clip(host, t0, t1))
    n = len(tr.devices)
    busy_s = prog_s = coll_s = 0.0
    execs = 0
    op_time: dict = defaultdict(float)
    gaps = []
    for dev in tr.devices.values():
        ops = clip(dev.ops or dev.modules, t0, t1)
        busy = union(ops)
        busy_s += sum(b - a for a, b in busy) / 1e9
        mods = sorted((a, b, program_name(name))
                      for name, a, b in clip(dev.modules, t0, t1))
        mod_starts = [a for a, _, _ in mods]
        runs = [(a, b) for a, b, name in mods if pat.search(name)]
        execs += len(runs)
        prog_s += sum(b - a for a, b in runs) / 1e9
        for text, a, b in ops:
            name = op_name(text)
            k = bisect.bisect_right(mod_starts, a) - 1
            inside = k >= 0 and a < mods[k][1]
            prog = mods[k][2] if inside else "?"
            if not CONTAINER.search(name):
                op_time[f"{prog}/{name}"] += (b - a) / 1e9
            if inside and COLLECTIVE.search(name) and pat.search(prog):
                coll_s += (b - a) / 1e9
        idle = idle_gaps(busy, t0, t1)
        gaps += [((b - a) / 1e9, name) for (a, b), name in
                 zip(idle, name_gaps(idle, segs))]
    if n == 0:
        raise ValueError("the trace has no TPU device plane")
    top_ops = sorted(((k, v / n) for k, v in op_time.items()),
                     key=lambda kv: -kv[1])[:10]
    idle_by_span: dict = defaultdict(float)
    for secs, name in gaps:
        idle_by_span[name] += secs / n
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy_s / n,
        "program_execs": execs / n,
        "program_s": prog_s / n,
        "collective_s": coll_s / n,
        "device_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": [[name, secs] for secs, name in
                      sorted(gaps, key=lambda g: -g[0])[:10]],
        "idle_by_span": dict(idle_by_span),
    }
