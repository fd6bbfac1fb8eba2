"""rooms_decode_ms: the host's work on a fleet tick's results, per
fleet tick.

Source: program span.  Summed time of the `nf.rooms.decode` spans
(`decode_counters`: the summary's tail cut into per-room counter
columns) inside the traced window / fleet ticks in it."""

from benchmarks.harness import hostspans


def read(run, trace):
    return hostspans.per_unit_ms(run, ("nf.rooms.decode",), "ticks")
