"""interest_device_ms: device time of the interest step's programs per
served frame.

Source: device trace.  Sum of the `XLA Modules` events of the game
role's `interest.build/<class>` and `interest.scan/<class>` programs
(`jit_interest_build`: quantise, bin the class into the interest table;
`jit_interest_scan`: read every observer's nine cells and the second
level's rows) over the traced window / frames begun in it; both synced
classes' runs and both lanes' (positions, property diffs) count.  A role
that runs no such module reads nothing."""

from benchmarks.harness import interest_trace


def read(run, trace):
    return interest_trace.module_ms_per_frame(run, trace)
