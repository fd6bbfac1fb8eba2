"""tick_stage_ms: the served frame's `tick` stage, mean over the frames.

Source: program span.  `GameRole.stage_clock` (exclusive
`perf_counter_ns` spans): host time of the tick stage, which includes
waiting for the device."""

from benchmarks.harness import clock


def read(run, trace):
    xs = run.series.get("stage_tick_ms")
    return clock.mean(xs) if xs else None
