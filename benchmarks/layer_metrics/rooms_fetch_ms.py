"""rooms_fetch_ms: the host waiting for the fleet's tick, per fleet tick.

Source: program span.  Summed time of the `nf.rooms.fetch` spans (the
one blocking read of a fleet tick: the `[slots, L]` summary of
`RoomBatch.tick`) inside the traced window / fleet ticks in it."""

from benchmarks.harness import hostspans


def read(run, trace):
    return hostspans.per_unit_ms(run, ("nf.rooms.fetch",), "ticks")
