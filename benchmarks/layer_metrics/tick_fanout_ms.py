"""tick_fanout_ms: the host's post-tick fan-out, per tick.

Source: program span.  Summed time of the `nf.kernel.fanout` spans
(`Kernel._post_tick`: events, deaths, property and record subscribers)
inside the traced window / ticks in it."""

from benchmarks.harness import hostspans


def read(run, trace):
    return hostspans.per_unit_ms(run, ("nf.kernel.fanout",), "ticks")
