"""rooms_device_ms: device time of the fleet's tick program per fleet
tick.

Source: device trace.  Sum of the `XLA Modules` events of `rooms.step`
(`jit_rooms_step`: the kernel's tick vmapped over the room axis, every
slot of the bank, occupied or not) over the traced window / fleet ticks
in it."""

STEP_MODULE = "rooms_step"


def read(run, trace):
    runs = trace.module_runs(STEP_MODULE)
    if not runs:
        return None
    return 1e3 * trace.module_seconds(STEP_MODULE) / runs
