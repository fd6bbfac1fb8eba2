"""aoe_table_device_ms: device time per tick of the victim and attacker
cell tables' builds (the payload scatters).

Source: device trace.  Self time of the tick program's instructions
whose `op_name` lies under the named scope `nf.aoe.table`."""

from benchmarks.harness import hostspans


def read(run, trace):
    return hostspans.scope_device_ms(run, trace, "nf.aoe.table")
