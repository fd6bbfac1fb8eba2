"""tick_host_ms: the part of a tick in which the device does nothing.

Source: device trace + host clock.  The traced window's wall time per
tick minus the device's busy time per tick: dispatch, the summary fetch,
the host's post-tick fan-out."""


def read(run, trace):
    ticks = run.counters.get("ticks")
    if not ticks:
        return None
    return 1e3 * (trace.window_s - trace.busy_s) / ticks
