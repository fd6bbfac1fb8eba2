"""tick_device_ms: device time of the tick program per tick.

Source: device trace.  Sum of the `XLA Modules` events of the compiled
step (`jit__trace_step`) over the traced window / ticks in it."""

STEP_MODULE = "_trace_step"


def read(run, trace):
    runs = trace.module_runs(STEP_MODULE)
    if not runs:
        return None
    return 1e3 * trace.module_seconds(STEP_MODULE) / runs
