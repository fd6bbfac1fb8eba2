"""aoe_spill_device_ms: device time per tick of the neighbour engine's
second level (the pairs the base fold cannot see: rows an over-full
cell holds beyond the base depth).

Source: device trace.  Self time of the tick program's instructions
whose `op_name` lies under the named scope `nf.aoe.spill`, read as
`aoe_fold_device_ms` reads its scope.  A program with no such scope
reads nothing."""

from benchmarks.harness import hostspans


def read(run, trace):
    return hostspans.scope_device_ms(run, trace, "nf.aoe.spill")
