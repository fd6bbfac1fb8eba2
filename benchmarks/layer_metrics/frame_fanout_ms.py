"""frame_fanout_ms: the host's post-tick fan-out, per served frame.

Source: program span.  As `tick_fanout_ms`: summed time of the
`nf.kernel.fanout` spans inside the traced window / frames begun in
it.  Its parts are the `nf.fanout.*` spans of the host waterfall."""

from benchmarks.harness import hostspans


def read(run, trace):
    return hostspans.per_unit_ms(run, ("nf.kernel.fanout",), "frames")
