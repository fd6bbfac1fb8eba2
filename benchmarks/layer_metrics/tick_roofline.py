"""tick_roofline: the tick program's share of the chip's roofline, in %.

Source: device trace.  The least time the chip could take for the work
of one frame (harness/work.py: every live row's properties, record
cells and heartbeats read once, the mutable ones written once, counted
from the schema's logical widths) over the tick program's measured
device time.  Which of bytes and operations bounds it is printed on an
earlier line.  Never returns 0: no device time, no reading."""

from benchmarks.harness import peaks, work

STEP_MODULE = "_trace_step"


def read(run, trace):
    runs = trace.module_runs(STEP_MODULE)
    live = run.counters.get("live_rows")
    if not runs or not live:
        return None
    device_s = trace.module_seconds(STEP_MODULE) / runs
    try:
        pk = peaks.peaks_for(run.devices[0].device_kind)
    except KeyError:
        if run.rehearse:
            return None  # a CPU has no row in the table, and gets none
        raise
    w = work.tick_work(run.config, int(live))
    least_s, bound_by = work.roofline_seconds(w, pk)
    run.note("tick_roofline", bound_by=bound_by, bytes=w["bytes"],
             flops=w["flops"], least_ms=1e3 * least_s,
             device_ms=1e3 * device_s)
    return 100.0 * least_s / device_s
