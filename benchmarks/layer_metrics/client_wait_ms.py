"""client_wait_ms: a frame's bytes waiting for the client's turn.

Source: program span.  Median over the (tick, seq) of the traced
window's FRAME_TRACE sidecars of the start of the client's
`nf.trace.recv` span minus the end of the proxy's `nf.trace.relay`
span (joined as `proxy_wait_ms` joins them)."""

from benchmarks.harness import hostspans


def read(run, trace):
    return hostspans.wire_wait_ms(run, "nf.trace.relay", "nf.trace.recv",
                                  frm_end=True)
