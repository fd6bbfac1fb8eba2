"""rooms_serve_ms: the serve edge's host time for the seated clients of
a clone fleet, per served frame.

Source: program span.  Summed time of the `nf.rooms.serve` spans
(`room_serve.RoomServe.flush`: the served rooms' program dispatched,
its one read, `nf.rooms.serve.fetch`, and each seated client's
messages, `nf.rooms.serve.views`) inside the traced window / frames
begun in it.  A role that seats no client in a room opens no such span
and reads nothing."""

from benchmarks.harness import hostspans


def read(run, trace):
    return hostspans.per_unit_ms(run, ("nf.rooms.serve",), "frames")
