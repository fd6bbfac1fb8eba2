"""rooms_interest_device_ms: device time of the served rooms' program
per served frame.

Source: device trace.  Sum of the `XLA Modules` events of the game
role's `rooms.interest` program (`jit_rooms_interest`, scope
`nf.rooms.interest`: the served rooms' rows gathered from the fleet's
banks, their public columns' diff planes, and each seated client's
visible rows) over the traced window / frames begun in it.  A role that
runs no such module reads nothing."""

MODULE = "rooms_interest"


def read(run, trace):
    frames = run.counters.get("frames")
    runs = trace.module_runs(MODULE)
    if not frames or not runs:
        return None
    return 1e3 * trace.module_seconds(MODULE) / frames
