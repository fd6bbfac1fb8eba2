"""Device time of the game role's interest programs, by their own
scopes.

The per-session serve engine runs two device programs a class and lane:
`interest.build/<class>` (module `jit_interest_build`: quantise, bin the
class into the interest table, `nf.interest.bin`) and
`interest.scan/<class>` (module `jit_interest_scan`: read every
observer's nine cells, `nf.interest.scan`, and the second level's rows
of those that are over-full, `nf.interest.spill`).  Together they are
the interest step.

`harness/xplane.Reduced.op_self_s` sums self time by instruction NAME
over every program of the trace, and the served cells run several (the
tick and these) whose instructions share names (`fusion.12` is in
each).  So this file reads the trace again, keeps for each module only
the operations that ran inside that module's own runs, and joins their
names with that program's own compiled text, which the driver leaves in
`run.interest_scopes` (module -> instruction -> op_name; the NPC
class's programs: the players' table of 64 rows runs the same modules
for microseconds).  A tree whose role runs no such module (the parent
of the PR that brought this) reads nothing, and no error.
"""

from __future__ import annotations

import bisect
from typing import Dict, Optional

from . import xplane

MODULES = ("interest_build", "interest_scan")


def module_ops(run) -> Optional[Dict[str, Dict[str, float]]]:
    """module -> instruction -> self seconds of the operations inside
    that module's runs, first chip; None where the trace has no device
    plane (a CPU rehearsal).  Read once a run."""
    if hasattr(run, "_interest_module_ops"):
        return run._interest_module_ops
    out: Optional[Dict[str, Dict[str, float]]] = None
    planes = xplane._device_planes(xplane.load(run.trace_file)) \
        if run.trace_file else []
    if planes:
        ops, modules = [], []
        for ln in planes[0].lines:
            if ln.name == "XLA Ops":
                ops = xplane._events(ln)
            elif ln.name == "XLA Modules":
                modules = xplane._events(ln)
        out = {}
        for needle in MODULES:
            runs = sorted((s, e) for s, e, name in modules if needle in name)
            starts = [s for s, _ in runs]

            def inside(s: float) -> bool:
                i = bisect.bisect_right(starts, s) - 1
                return i >= 0 and s < runs[i][1]

            mine = out[needle] = {}
            for text, sec in xplane.self_times(
                    [ev for ev in ops if inside(ev[0])]).items():
                k = xplane.instruction_name(text)
                mine[k] = mine.get(k, 0.0) + sec
    run._interest_module_ops = out
    return out


def scope_ms_per_frame(run, trace, scope: str) -> Optional[float]:
    """Device self time a frame of the interest programs' instructions
    whose `op_name` lies under `scope`."""
    scopes = getattr(run, "interest_scopes", None)
    frames = run.counters.get("frames")
    if not scopes or not frames:
        return None
    within = module_ops(run)
    sec = 0.0
    for needle, names in scopes.items():
        if within is None:  # no device plane: names alone, as the tick's
            sec += trace.scope_seconds(names, scope)
        else:
            sec += sum(s for op, s in within.get(needle, {}).items()
                       if scope in names.get(op, ""))
    return 1e3 * sec / frames if sec > 0 else None


def module_ms_per_frame(run, trace) -> Optional[float]:
    """Device time a frame of the interest programs' runs, whole."""
    frames = run.counters.get("frames")
    runs = sum(trace.module_runs(m) for m in MODULES)
    if not frames or not runs:
        return None
    return 1e3 * sum(trace.module_seconds(m) for m in MODULES) / frames
