"""proxy_wait_ms: a frame's bytes waiting for the proxy's turn.

Source: program span.  Median over the (tick, seq) of the traced
window's FRAME_TRACE sidecars of the start of the proxy's
`nf.trace.relay` span minus the start of the game role's
`nf.trace.emit` span; the two are joined by the `tick` and `seq`
keyword arguments the profiler keeps with each event."""

from benchmarks.harness import hostspans


def read(run, trace):
    return hostspans.wire_wait_ms(run, "nf.trace.emit", "nf.trace.relay",
                                  frm_end=False)
