"""aoe_device_ms: device time per tick of the neighbour engine.

Source: device trace.  Self time of the instructions of the tick program
whose `op_name` lies under the named scope `nf.phase.CombatModule.aoe`
(binning, table build, fold, pull), found by joining the trace's
instruction names with the compiled program's metadata.  The share of
the tick's device time that no `nf.*` scope claims is printed on an
earlier line: fusion can merge instructions across scopes, and that
share says how much of the attribution to trust."""

SCOPE = "nf.phase.CombatModule.aoe"


def read(run, trace):
    ticks = run.counters.get("ticks")
    if not ticks or not run.hlo_scopes:
        return None
    sec = trace.scope_seconds(run.hlo_scopes, SCOPE)
    if sec <= 0:
        return None
    total = sum(trace.op_self_s.values())
    by_scope = {}
    for op, s in trace.op_self_s.items():
        name = run.hlo_scopes.get(op, "")
        at = name.find("nf.")
        key = name[at:].split("/")[0] if at >= 0 else "(no nf scope)"
        by_scope[key] = by_scope.get(key, 0.0) + s
    run.note("device_time_by_scope_ms_per_tick",
             **{k: 1e3 * v / ticks for k, v in sorted(by_scope.items())},
             unclaimed_share=trace.unclaimed_seconds(run.hlo_scopes)
             / max(total, 1e-12))
    return 1e3 * sec / ticks
