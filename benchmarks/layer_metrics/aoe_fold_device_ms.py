"""aoe_fold_device_ms: device time per tick of the nine-shift fold over
the cell tables (XLA or Pallas engine).

Source: device trace.  Self time of the tick program's instructions
whose `op_name` lies under the named scope `nf.aoe.fold`."""

from benchmarks.harness import hostspans


def read(run, trace):
    return hostspans.scope_device_ms(run, trace, "nf.aoe.fold")
