"""interest_roofline: the interest step's share of the chip's roofline,
in %.

Source: device trace.  The least time the chip could take for one
frame's visibility answer (harness/work_interest.py: every alive row
inside the extent read once, every (session, visible row) written once;
counted by the driver from the banks it kept, nothing the program
states enters) over the device time of the interest step's programs a
frame (`interest_device_ms`).  Never returns 0: no device time or no
work counted, no reading.  A device that is not in the table of peaks
is an error outside a rehearsal; a rehearsal runs the same arithmetic
against the v5e's row, as `aoe_spill_roofline` does and for its reason
(the harness's own test wants every listed metric but `tick_roofline`
on a rehearsal's line): the number is named rehearsal_ and is no share
of anything."""

from benchmarks.harness import interest_trace, peaks, work


def read(run, trace):
    nbytes = run.counters.get("interest_work_bytes")
    device_ms = interest_trace.module_ms_per_frame(run, trace)
    if not nbytes or not device_ms:
        return None
    kind = run.devices[0].device_kind
    if run.rehearse and kind not in peaks.PEAKS:
        kind = "TPU v5 lite"
    pk = peaks.peaks_for(kind)
    least_s, bound_by = work.roofline_seconds(
        {"bytes": float(nbytes), "flops": 0.0}, pk)
    run.note("interest_roofline", bound_by=bound_by, bytes=nbytes,
             least_ms=1e3 * least_s, device_ms=device_ms)
    return 100.0 * least_s / (device_ms / 1e3)
