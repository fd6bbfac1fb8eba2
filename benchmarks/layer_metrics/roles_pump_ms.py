"""roles_pump_ms: the four other roles, per pass of the pump.

Source: program span.  Summed time of the `nf.role.master`, `.login`,
`.world` and `.proxy` spans (`ServerRole.execute`) inside the traced
window / passes of the benchmark's pump in it (its `bench.pump`
spans)."""

from benchmarks.harness import hostspans


def read(run, trace):
    return hostspans.roles_pump_ms(run, trace)
