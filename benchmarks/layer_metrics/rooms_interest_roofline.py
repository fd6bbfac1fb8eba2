"""rooms_interest_roofline: the served rooms' program's share of the
chip's roofline, in %.

Source: device trace.  The least time the chip could take for one
frame's visibility answer of the seated clients
(harness/work_rooms_served.py: every alive row of every served room read
once, every (client, visible row) written once; counted by the driver
from the banks it kept) over the device time of one run of
`rooms.interest` (one a served frame).  Never returns 0: no device time or no
work counted, no reading.  A device that is not in the table of peaks
is an error outside a rehearsal; a rehearsal runs the same arithmetic
against the v5e's row, as `interest_roofline` does and for its reason:
the number is named rehearsal_ and is no share of anything."""

from benchmarks.harness import peaks, work

MODULE = "rooms_interest"


def read(run, trace):
    nbytes = run.counters.get("rooms_interest_work_bytes")
    runs = trace.module_runs(MODULE)
    if not nbytes or not runs:
        return None
    device_s = trace.module_seconds(MODULE) / runs
    if device_s <= 0:
        return None
    kind = run.devices[0].device_kind
    if run.rehearse and kind not in peaks.PEAKS:
        kind = "TPU v5 lite"
    pk = peaks.peaks_for(kind)
    least_s, bound_by = work.roofline_seconds(
        {"bytes": float(nbytes), "flops": 0.0}, pk)
    run.note("rooms_interest_roofline", bound_by=bound_by, bytes=nbytes,
             least_ms=1e3 * least_s, device_ms=1e3 * device_s)
    return 100.0 * least_s / device_s
