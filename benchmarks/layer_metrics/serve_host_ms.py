"""serve_host_ms: the serve edge's host time per frame.

Source: program span.  StageClock harvest + interest + encode + assemble
+ send, summed per frame, mean over the window's frames."""

from benchmarks.harness import clock


def read(run, trace):
    xs = run.series.get("stage_serve_ms")
    return clock.mean(xs) if xs else None
