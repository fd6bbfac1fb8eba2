"""delivery_ms: from the game role's encode stamp to the client.

Source: program span.  Median over every (client, frame) pair of the
window of the client's FRAME_TRACE arrival minus the `t_encode_ns` the
game role stamped into it: proxy relay, loopback TCP and the client's
pump, on one clock in one process."""

from benchmarks.harness import clock


def read(run, trace):
    xs = run.series.get("delivery_ms")
    return clock.percentile(xs, 50.0) if xs else None
