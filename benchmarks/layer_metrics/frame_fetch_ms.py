"""frame_fetch_ms: the host waiting for the device, per served frame.

Source: program span.  As `tick_fetch_ms`: summed time of the
`nf.kernel.fetch` spans inside the traced window / frames begun in it."""

from benchmarks.harness import hostspans


def read(run, trace):
    return hostspans.per_unit_ms(run, ("nf.kernel.fetch",), "frames")
