"""aoe_spill_roofline: the second level's share of the chip's roofline,
in %.

Source: device trace.  The least time the chip could take for the work
of the hot cells (harness/work_siege.py: 8 flops for every in-radius
enemy pair with an end in a cell holding more than the configuration
file's `hot_cell_rows`, those rows' features read and results written
once; counted by the driver from the positions it kept, nothing the
program states enters) over the device time under `nf.aoe.spill`.
Never returns 0: no device time or no work counted, no reading.  A
device that is not in the table of peaks is an error outside a
rehearsal; a rehearsal runs the same arithmetic against the v5e's row,
as `rooms_roofline` does and for its reason (the harness's own test
wants every listed metric but `tick_roofline` on a rehearsal's line):
the number is named rehearsal_ and is no share of anything."""

from benchmarks.harness import hostspans, peaks, work


def read(run, trace):
    flops = run.counters.get("spill_work_flops")
    nbytes = run.counters.get("spill_work_bytes")
    if not nbytes:
        return None
    device_ms = hostspans.scope_device_ms(run, trace, "nf.aoe.spill")
    if not device_ms:
        return None
    kind = run.devices[0].device_kind
    if run.rehearse and kind not in peaks.PEAKS:
        kind = "TPU v5 lite"
    pk = peaks.peaks_for(kind)
    least_s, bound_by = work.roofline_seconds(
        {"bytes": float(nbytes), "flops": float(flops or 0.0)}, pk)
    run.note("aoe_spill_roofline", bound_by=bound_by, bytes=nbytes,
             flops=flops, least_ms=1e3 * least_s, device_ms=device_ms)
    return 100.0 * least_s / (device_ms / 1e3)
