"""interest_spill_device_ms: device time per served frame of the
interest table's second level as the scan reads it (the rows an
over-full cell keeps beyond the base depth, for those of an observer's
nine cells that are over-full).

Source: device trace.  Self time of the interest programs' instructions
whose `op_name` lies under the named scope `nf.interest.spill`, taken
only from operations that ran inside those programs' own runs
(harness/interest_trace.py).  A program with no such scope reads
nothing."""

from benchmarks.harness import interest_trace


def read(run, trace):
    return interest_trace.scope_ms_per_frame(run, trace, "nf.interest.spill")
