"""The program's own host spans (`nf.*`), read off the profiler's trace.

The program opens its host spans through one seam
(`noahgameframe_tpu/telemetry/tracing.py`): each is a
`jax.profiler.TraceAnnotation("nf.<name>", **args)`, so with a profiler
session open it lands in the `/host:CPU` plane of the same `xplane.pb`
as the device's `XLA Ops`, on the same clock.  This module opens the
traced window's file once per run and gives, for the part of every
`nf.*` span that lies inside the `bench.window` span:

    total_s[name]     summed time of the spans of that name
    self_s[name]      the same less the time of the spans they enclose,
                      per thread (spans of one thread nest)
    idle_s[name]      the device's idle time (chip 0) charged to the
                      innermost `nf.*` span open in it on the pump's
                      thread, "(no span)" where none is
    programs[name]    the device programs (`XLA Modules` events, by
                      name) that started while that span was innermost
    wire[name]        (tick, seq) -> (start, end) of the wire spans
                      `nf.trace.emit` / `.relay` / `.recv`, from the
                      keyword arguments the events keep as stats

The pump's thread is the one that holds the most `nf.*` time.  A
program that opens no such span (the parent of the PR that added them)
gives empty tables, every reader built on them returns None, and no
note is printed.

The per-layer metrics that read this are one function each at the end
of the file, shared by the cells (`layer_metrics/*.py` name the cell's
divisor).  The first of them to run also prints the **host waterfall**
as a note line.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, List, Optional, Tuple

from . import clock, xplane

NF = "nf."
WINDOW = xplane.ANNOTATION_PREFIX + "window"
NO_SPAN = "(no span)"
WIRE = ("nf.trace.emit", "nf.trace.relay", "nf.trace.recv")
OTHER_ROLES = ("nf.role.master", "nf.role.login", "nf.role.world",
               "nf.role.proxy")

Event = Tuple[float, float, str]


@dataclasses.dataclass
class HostSpans:
    window_s: float = 0.0
    total_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    self_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    count: Dict[str, int] = dataclasses.field(default_factory=dict)
    idle_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    programs: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)
    wire: Dict[str, Dict[Tuple[int, int], Tuple[float, float]]] = \
        dataclasses.field(default_factory=dict)
    pump_thread: str = ""
    pump_self_s: float = 0.0  # self time of nf.* spans on that thread
    longest: Optional[Tuple[str, float]] = None  # one span, its seconds

    def seconds(self, *names: str) -> float:
        return sum(self.total_s.get(n, 0.0) for n in names)

    def wire_gaps_ms(self, frm: str, to: str, frm_end: bool) -> List[float]:
        """Per (tick, seq) present in both: `to`'s start minus `frm`'s
        start (or end)."""
        a, b = self.wire.get(frm, {}), self.wire.get(to, {})
        return [1e3 * (b[k][0] - a[k][1 if frm_end else 0])
                for k in a if k in b]


def _clip(events: List[Event], w0: float, w1: float) -> List[Event]:
    return [(max(s, w0), min(e, w1), n) for s, e, n in events
            if e > w0 and s < w1]


def _innermost(events: List[Event]) -> Tuple[List[float], List[str]]:
    """Change points of one thread's nested spans: from marks[i] on, the
    innermost open span is names[i] (NO_SPAN where none is).  Linear in
    the spans, which nest."""
    marks: List[float] = []
    names: List[str] = []
    stack: List[Tuple[float, str]] = []  # (end, name)

    def put(t: float) -> None:
        name = stack[-1][1] if stack else NO_SPAN
        if marks and marks[-1] == t:
            names[-1] = name
        else:
            marks.append(t)
            names.append(name)

    for s, e, n in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end = stack.pop()[0]
            put(end)
        stack.append((e, n))
        put(s)
    while stack:
        end = stack.pop()[0]
        put(end)
    return marks, names


def _at(marks: List[float], names: List[str], t: float) -> str:
    i = bisect.bisect_right(marks, t) - 1
    return names[i] if i >= 0 else NO_SPAN


def _charge(gaps, marks: List[float], names: List[str]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for s, e in gaps:
        i = bisect.bisect_right(marks, s) - 1
        t = s
        while t < e:
            nxt = marks[i + 1] if i + 1 < len(marks) else float("inf")
            name = names[i] if i >= 0 else NO_SPAN
            upto = min(e, nxt)
            out[name] = out.get(name, 0.0) + (upto - t)
            t, i = upto, i + 1
    return out


def _device(pd) -> Tuple[List[Event], List[Event]]:
    """(operations, programs) of chip 0, as `xplane.reduce_trace` takes
    them: the device plane's `XLA Ops` and `XLA Modules`, or on the CPU
    backend (a rehearsal) the thunks of the XLA pools and the outermost
    `PjitFunction(jit(f))` spans of the calling threads."""
    planes = xplane._device_planes(pd)
    ops: List[Event] = []
    programs: List[Event] = []
    if planes:
        for ln in planes[0].lines:
            if ln.name == "XLA Ops":
                ops = xplane._events(ln)
            elif ln.name == "XLA Modules":
                programs = [(s, e, xplane._MODULE_HASH.sub("", n))
                            for s, e, n in xplane._events(ln)]
        return ops, programs
    last_end: Dict[str, float] = {}
    for p in pd.planes:
        if not p.name.startswith("/host:CPU"):
            continue
        for ln in p.lines:
            pool = ln.name.startswith("tf_XLA")
            for s, e, name in sorted(xplane._events(ln)):
                if name.startswith("PjitFunction(jit("):
                    if s >= last_end.get(name, -1.0):
                        last_end[name] = e
                        programs.append((s, e, "jit_" + name[17:-2]))
                elif pool and "::" not in name \
                        and not name.startswith("end: "):
                    ops.append((s, e, name))
    return ops, programs


def reduce_spans(path: str) -> HostSpans:
    pd = xplane.load(path)
    threads: Dict[str, List[Event]] = {}
    wire: Dict[str, Dict[Tuple[int, int], Tuple[float, float]]] = {}
    window: Optional[Tuple[float, float]] = None
    for p in pd.planes:
        if not p.name.startswith("/host:CPU"):
            continue
        for i, ln in enumerate(p.lines):
            thread = f"{ln.name}#{i}"  # thread names need not be unique
            for e in ln.events:
                name = e.name
                if name == WINDOW:
                    s = e.start_ns * 1e-9
                    window = (s, s + e.duration_ns * 1e-9)
                if not name.startswith(NF):
                    continue
                s = e.start_ns * 1e-9
                end = s + e.duration_ns * 1e-9
                threads.setdefault(thread, []).append((s, end, name))
                if name in WIRE:
                    args = dict(e.stats)
                    if "tick" in args and "seq" in args:
                        wire.setdefault(name, {})[
                            (int(args["tick"]), int(args["seq"]))] = (s, end)
    out = HostSpans(wire=wire)
    if not threads:
        return out
    if window is None:
        every = [ev for evs in threads.values() for ev in evs]
        window = (min(s for s, _, _ in every), max(e for _, e, _ in every))
    w0, w1 = window
    out.window_s = w1 - w0
    per_thread_self: Dict[str, float] = {}
    for thread, events in threads.items():
        events = _clip(events, w0, w1)
        for s, e, n in events:
            out.total_s[n] = out.total_s.get(n, 0.0) + (e - s)
            out.count[n] = out.count.get(n, 0) + 1
            if out.longest is None or e - s > out.longest[1]:
                out.longest = (n, e - s)
        own = xplane.self_times(events)
        for n, sec in own.items():
            out.self_s[n] = out.self_s.get(n, 0.0) + sec
        per_thread_self[thread] = sum(own.values())
        threads[thread] = events
    out.pump_thread = max(per_thread_self, key=per_thread_self.get)
    out.pump_self_s = per_thread_self[out.pump_thread]
    marks, names = _innermost(threads[out.pump_thread])
    ops, programs = _device(pd)
    busy = xplane.union((max(s, w0), min(e, w1)) for s, e, _ in ops
                        if e > w0 and s < w1)
    gaps, edge = [], w0
    for s, e in busy:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    if w1 > edge:
        gaps.append((edge, w1))
    out.idle_s = _charge(gaps, marks, names)
    for s, _e, prog in programs:
        if w0 <= s < w1:
            under = out.programs.setdefault(_at(marks, names, s), {})
            under[prog] = under.get(prog, 0) + 1
    return out


def of(run) -> HostSpans:
    """The run's host spans, read once; the first call also prints the
    host waterfall."""
    got = getattr(run, "_host_spans", None)
    if got is None:
        got = reduce_spans(run.trace_file) if run.trace_file else HostSpans()
        run._host_spans = got
        if got.total_s:
            _note_waterfall(run, got)
    return got


def _unit(run) -> Tuple[str, float]:
    """What a per-unit number is divided by: the served cell's frames,
    else the window's ticks."""
    for key in ("frames", "ticks"):
        if run.counters.get(key):
            return key, float(run.counters[key])
    return "window", 1.0


def _note_waterfall(run, hs: HostSpans) -> None:
    unit, n = _unit(run)
    idle = sum(hs.idle_s.values())
    run.note(
        "host_waterfall", per=unit, units=n, window_s=hs.window_s,
        pump_thread=hs.pump_thread,
        pump_thread_nf_self_share=hs.pump_self_s / max(hs.window_s, 1e-12),
        self_ms={k: 1e3 * v / n for k, v in sorted(hs.self_s.items())},
        spans={k: v / n for k, v in sorted(hs.count.items())},
        device_idle_ms={k: 1e3 * v / n for k, v in sorted(
            hs.idle_s.items(), key=lambda kv: -kv[1])},
        device_idle_no_span_share=hs.idle_s.get(NO_SPAN, 0.0)
        / max(idle, 1e-12),
        device_programs={k: {p: c / n for p, c in sorted(v.items())}
                         for k, v in sorted(hs.programs.items())},
        longest_span={"name": hs.longest[0], "ms": 1e3 * hs.longest[1]})


# ------------------------------------------------- the per-layer readers
def per_unit_ms(run, names, counter: str) -> Optional[float]:
    """Summed time of the named spans over `run.counters[counter]`."""
    n = run.counters.get(counter)
    sec = of(run).seconds(*names)
    if not n or sec <= 0:
        return None
    return 1e3 * sec / n


def roles_pump_ms(run, trace) -> Optional[float]:
    """The four other roles' pump passes, per pass of the benchmark's
    pump (the count of its `bench.pump` spans)."""
    passes = len(trace.annotations.get(
        xplane.ANNOTATION_PREFIX + "pump", ()))
    sec = of(run).seconds(*OTHER_ROLES)
    if not passes or sec <= 0:
        return None
    return 1e3 * sec / passes


def wire_wait_ms(run, frm: str, to: str, frm_end: bool) -> Optional[float]:
    """Median over the (tick, seq) pairs of the traced window."""
    xs = of(run).wire_gaps_ms(frm, to, frm_end)
    return clock.percentile(xs, 50.0) if xs else None


def scope_device_ms(run, trace, scope: str) -> Optional[float]:
    """Device self time per tick of the instructions under one named
    scope of the tick program, as `aoe_device_ms` takes the whole
    phase."""
    ticks = run.counters.get("ticks")
    if not ticks or not run.hlo_scopes:
        return None
    sec = trace.scope_seconds(run.hlo_scopes, scope)
    return 1e3 * sec / ticks if sec > 0 else None
