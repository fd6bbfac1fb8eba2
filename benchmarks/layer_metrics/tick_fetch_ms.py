"""tick_fetch_ms: the host waiting for the device, per tick.

Source: program span.  Summed time of the `nf.kernel.fetch` spans (the
summary fetch of `Kernel.tick_finish`: the one blocking read of a tick)
inside the traced window / ticks in it."""

from benchmarks.harness import hostspans


def read(run, trace):
    return hostspans.per_unit_ms(run, ("nf.kernel.fetch",), "ticks")
