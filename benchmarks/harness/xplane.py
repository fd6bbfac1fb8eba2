"""Reduction of a jax profiler trace (`*.xplane.pb`) to what the per-layer
metrics read: device busy time, time per compiled module, time per HLO
instruction (self time: an instruction that encloses others is charged
its own part), and the idle gaps charged to the host span open in them.

What a TPU trace looks like (read by hand off PR 23's first traced run):

    plane '/device:TPU:<n>'
        line 'XLA Modules'   one event per executed program, named
                             'jit__trace_step(<fingerprint>)'
        line 'XLA Ops'       one event per executed HLO instruction; the
                             event's name is the instruction's text,
                             '%fusion.12 = f32[...] fusion(...), ...'
        line 'Async XLA Ops' copies and slices in flight (overlap the ops)
    plane '/host:CPU'        one line per host thread; the benchmark's
                             own `TraceAnnotation`s land here by name

Device and host events share one clock (ns since the trace began).
The events carry no `op_name`, so a named scope (`nf.phase.aoe`) is found
by joining instruction names with the metadata of the compiled program's
text (`scopes_from_hlo_text`).

On the CPU backend there is no device plane: the XLA modules run on host
threads and are taken from there, which is enough to rehearse the
reduction and says nothing about a chip.
"""

from __future__ import annotations

import bisect
import dataclasses
import gzip
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

ANNOTATION_PREFIX = "bench."
_INSTR = re.compile(r"^%?([\w.\-]+) = ")
_HLO_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"")
_MODULE_HASH = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


def instruction_name(event_name: str) -> str:
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def scopes_from_hlo_text(text: str) -> Dict[str, str]:
    """instruction name -> its `op_name` metadata, from
    `compiled.as_text()`.  A fusion carries the op_name of its root."""
    out: Dict[str, str] = {}
    for line in text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out.setdefault(m.group(1), m.group(2))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def self_times(events: Sequence[Tuple[float, float, str]]
               ) -> Dict[str, float]:
    """name -> summed self time of (start, end, name) events on one
    line: an event's time less the time of the events it encloses."""
    total: Dict[str, float] = {}
    stack: List[List] = []  # [end, name, self]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            _end, name, own = stack.pop()
            total[name] = total.get(name, 0.0) + max(own, 0.0)

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        close(s)
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return total


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # union of device-op intervals, mean over the chips
    chips: int
    modules: Dict[str, List[float]]  # program -> its runs' seconds (chip 0)
    op_self_s: Dict[str, float]  # instruction -> self seconds (chip 0)
    device_ops: List[List]  # top 10 [instruction text, seconds]
    idle_gaps: List[List]  # top 10 [host span, idle seconds in it]
    annotations: Dict[str, List[float]]  # host span -> its seconds

    def module_seconds(self, needle: str) -> float:
        return sum(sum(v) for k, v in self.modules.items() if needle in k)

    def module_runs(self, needle: str) -> int:
        return sum(len(v) for k, v in self.modules.items() if needle in k)

    def scope_seconds(self, scopes: Dict[str, str], needle: str) -> float:
        """Self time of the instructions whose op_name holds `needle`."""
        return sum(s for op, s in self.op_self_s.items()
                   if needle in scopes.get(op, ""))

    def unclaimed_seconds(self, scopes: Dict[str, str],
                          prefix: str = "nf.") -> float:
        return sum(s for op, s in self.op_self_s.items()
                   if prefix not in scopes.get(op, ""))


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _events(line) -> List[Tuple[float, float, str]]:
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
            for e in line.events]


def _device_planes(pd) -> List:
    return sorted((p for p in pd.planes if p.name.startswith("/device:")
                   and any(ln.name == "XLA Ops" for ln in p.lines)),
                  key=lambda p: p.name)


def _host_spans(pd) -> List[Tuple[float, float, str]]:
    spans = []
    for p in pd.planes:
        if not p.name.startswith("/host:CPU"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name.startswith(ANNOTATION_PREFIX):
                    s = e.start_ns * 1e-9
                    spans.append((s, s + e.duration_ns * 1e-9, e.name))
    return spans


def _innermost_timeline(spans) -> Tuple[List[float], List[str]]:
    """Change points (time, innermost open span from then on)."""
    marks = sorted({t for s, e, _ in spans for t in (s, e)})
    names = []
    for t in marks:
        open_ = [(e - s, n) for s, e, n in spans if s <= t < e]
        names.append(min(open_)[1] if open_ else "(no span)")
    return marks, names


def _charge_gaps(gaps: List[Interval], spans) -> Dict[str, float]:
    out: Dict[str, float] = {}
    if not gaps:
        return out
    # only the spans that can touch a gap matter
    lo, hi = gaps[0][0], gaps[-1][1]
    spans = [sp for sp in spans if sp[1] > lo and sp[0] < hi]
    if len(spans) > 4000:  # keep the walk bounded: drop the shortest
        spans = sorted(spans, key=lambda sp: sp[0] - sp[1])[:4000]
    marks, names = _innermost_timeline(spans)
    for s, e in gaps:
        i = bisect.bisect_right(marks, s) - 1
        t = s
        while t < e:
            nxt = marks[i + 1] if i + 1 < len(marks) else float("inf")
            name = names[i] if i >= 0 else "(no span)"
            upto = min(e, nxt)
            out[name] = out.get(name, 0.0) + (upto - t)
            t = upto
            i += 1
    return out


def reduce_trace(path: str, window: Optional[Interval] = None) -> Reduced:
    """Reduce one trace.  `window` (seconds on the trace's clock) bounds
    what counts; by default the whole span of the benchmark's own
    annotations, or of the device events if there are none."""
    pd = load(path)
    spans = _host_spans(pd)
    planes = _device_planes(pd)
    per_chip_ops: List[List[Tuple[float, float, str]]] = []
    modules: Dict[str, List[float]] = {}
    if planes:
        for i, p in enumerate(planes):
            ops = []
            for ln in p.lines:
                if ln.name == "XLA Ops":
                    ops = _events(ln)
                elif ln.name == "XLA Modules" and i == 0:
                    for s, e, name in _events(ln):
                        modules.setdefault(
                            _MODULE_HASH.sub("", name), []).append(e - s)
            per_chip_ops.append(ops)
    else:
        # CPU backend (rehearsal): programs are `PjitFunction(jit(f))`
        # spans on the calling thread, thunks run on the XLA pools
        ops, last_end = [], {}
        for p in pd.planes:
            if not p.name.startswith("/host:CPU"):
                continue
            for ln in p.lines:
                pool = ln.name.startswith("tf_XLA")
                for s, e, name in sorted(_events(ln)):
                    if name.startswith("PjitFunction(jit("):
                        if s >= last_end.get(name, -1.0):  # outermost only
                            last_end[name] = e
                            modules.setdefault(
                                "jit_" + name[17:-2], []).append(e - s)
                    elif pool and "::" not in name \
                            and not name.startswith("end: "):
                        ops.append((s, e, name))
        per_chip_ops.append(ops)
    if window is None:
        src = spans or [ev for ops in per_chip_ops for ev in ops]
        if not src:
            raise ValueError(f"{path}: no device events and no annotations")
        window = (min(s for s, _, _ in src), max(e for _, e, _ in src))
    w0, w1 = window
    busy = []
    for ops in per_chip_ops:
        clipped = [(max(s, w0), min(e, w1)) for s, e, _ in ops
                   if e > w0 and s < w1]
        busy.append(union(clipped))
    busy_s = sum(sum(e - s for s, e in b) for b in busy) / max(1, len(busy))
    ops0 = [ev for ev in per_chip_ops[0] if ev[1] > w0 and ev[0] < w1]
    by_text = self_times(ops0)
    op_self: Dict[str, float] = {}
    for text, sec in by_text.items():
        k = instruction_name(text)
        op_self[k] = op_self.get(k, 0.0) + sec
    top = sorted(by_text.items(), key=lambda kv: -kv[1])[:10]
    gaps, edge = [], w0
    for s, e in busy[0]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    if w1 > edge:
        gaps.append((edge, w1))
    charged = _charge_gaps(gaps, spans)
    ann: Dict[str, List[float]] = {}
    for s, e, n in spans:
        if e > w0 and s < w1:
            ann.setdefault(n, []).append(e - s)
    return Reduced(
        window_s=w1 - w0, busy_s=busy_s, chips=len(per_chip_ops),
        modules=modules, op_self_s=op_self,
        device_ops=[[t[:160], s] for t, s in top],
        idle_gaps=[[n, s] for n, s in
                   sorted(charged.items(), key=lambda kv: -kv[1])[:10]],
        annotations=ann)
