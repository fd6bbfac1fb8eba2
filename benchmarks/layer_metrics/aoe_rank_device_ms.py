"""aoe_rank_device_ms: device time per tick of the neighbour engine's
binning: cell keys, the sort or the counts, and the slot ranks.

Source: device trace.  Self time of the tick program's instructions
whose `op_name` lies under the named scope `nf.aoe.rank`, as
`aoe_device_ms` takes `nf.phase.CombatModule.aoe`."""

from benchmarks.harness import hostspans


def read(run, trace):
    return hostspans.scope_device_ms(run, trace, "nf.aoe.rank")
