"""rooms_roofline: the fleet tick program's share of the chip's
roofline, in %.

Source: device trace.  The least time the chip could take for the work
of one fleet frame (harness/work.py, the count `tick_roofline` divides
into: every LIVE row of an OCCUPIED room read once, its mutable part
written once, from the schema's logical widths; empty slots and free
rows are no work) over `rooms.step`'s measured device time.  So it
reads the same work whatever implements the tick.  Never returns 0: no
device time, no reading.  A device that is not in the table of peaks
is an error outside a rehearsal."""

from benchmarks.harness import peaks, work

STEP_MODULE = "rooms_step"


def read(run, trace):
    runs = trace.module_runs(STEP_MODULE)
    live = run.counters.get("live_rows")
    if not runs or not live:
        return None
    device_s = trace.module_seconds(STEP_MODULE) / runs
    kind = run.devices[0].device_kind
    if run.rehearse and kind not in peaks.PEAKS:
        # a rehearsal runs the same arithmetic against the v5e's row so
        # that the line has the metric's shape; the number is named
        # rehearsal_ and is no share of anything
        kind = "TPU v5 lite"
    pk = peaks.peaks_for(kind)
    w = work.tick_work(run.config, int(live))
    least_s, bound_by = work.roofline_seconds(w, pk)
    run.note("rooms_roofline", bound_by=bound_by, bytes=w["bytes"],
             flops=w["flops"], least_ms=1e3 * least_s,
             device_ms=1e3 * device_s, live_rows=int(live))
    return 100.0 * least_s / device_s
