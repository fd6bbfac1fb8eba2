"""Driver `tick`: the observed tick, closed loop.

`GameWorld.tick()` again as soon as the last one returned with its
results on the host: what a game role does every frame, without the
roles.  The recipe is `chip_smoke.py`'s `phase_tick` (proven on the chip
in PR 21): build the world, load `kernel.run` and `kernel.step`, soak on
the fused device loop until the world is in its steady state (NPCs die
and respawn; the dead pile up and the cell tables are boosted once),
absorb that sanctioned retrace, then measure.

End-to-end: `tick_ms` = window wall time / ticks completed, and
`tick_p95_ms` over every tick of the window.  Around a few ticks drawn
from the seed (one of them a tick on which the regen heartbeat fires)
the NPC banks are copied on the device; after the window those ticks
are replayed by the plain reference (harness/compare.py).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.harness import clock, compare
from benchmarks.harness.npcworld import (NPC, STAT_RECORD, build_world,
                                         combat_geometry, hold_limits,
                                         overflow_totals,
                                         reference_params, sample_ticks,
                                         step_scopes, until_settled)
from benchmarks.harness.run import Run


def run(run: Run) -> None:
    import jax

    mix, config = run.mix, run.config
    rng = np.random.default_rng(run.seed)
    t0 = time.perf_counter()
    world = build_world(config, run.seed)
    k = world.kernel
    book = k.costbook
    build_s = time.perf_counter() - t0
    n = int(config["world"]["entities"])
    cap = int(k.store.capacity(NPC))
    combat = world.combat

    def geometry() -> dict:
        return combat_geometry(world) or {}

    def sync() -> None:
        jax.block_until_ready(k.state.classes[NPC].i32)

    def observed_pass(fused: int) -> dict:
        was = geometry()
        if fused:
            k.run_device(fused)
            sync()
        t = time.perf_counter()
        world.tick()
        return {"at_tick": int(k.tick_count),
                "step_s": time.perf_counter() - t,
                "geometry_from": was, "geometry_to": geometry()}

    # the two programs of this cell, until nothing compiles
    t0 = time.perf_counter()
    compile_passes = until_settled(book, lambda: observed_pass(1))
    load_s = time.perf_counter() - t0
    # the steady state a deployment runs after its first seconds
    t0 = time.perf_counter()
    k.run_device(int(mix["soak_ticks"]))
    sync()
    soak_s = time.perf_counter() - t0
    retrace_passes = until_settled(book, lambda: observed_pass(0))
    snaps = compare.Snapshots(k, NPC, STAT_RECORD)
    snaps.warm()
    est = []
    for _ in range(2):  # the window's own call, warm, and its pace
        t = time.perf_counter()
        world.tick()
        est.append(time.perf_counter() - t)
    until_settled(book, world.tick)  # no retrace left pending
    sampled = sample_ticks(rng, int(k.tick_count),
                           int(run.seconds / max(min(est), 1e-4)), config,
                           int(mix["compare_ticks"]))
    mark, compiles0 = book.mark(), book.total_compiles
    run.setup_done()

    tick_s = []
    with run.window():
        t_start = time.perf_counter()
        while True:
            t = time.perf_counter()
            with run.annotate("tick"):
                if int(k.tick_count) in sampled:
                    snaps.around(world.tick)
                else:
                    world.tick()
            t_end = time.perf_counter()
            tick_s.append(t_end - t)
            if t_end - t_start >= run.seconds:
                break
        wall_s = t_end - t_start

    # a bucket boost inside the window is the program's own, announced
    # retrace: its stall is in the metrics, and it is noted, not refused
    window_compiles = len(book.unexplained_since(mark))
    retraces = book.total_compiles - compiles0 - window_compiles
    page_ok = snaps.page_unchanged()
    live = int(k.store.live_count(NPC))
    if run.trace:
        run.hlo_scopes.update(step_scopes(k))
    totals = overflow_totals(k)
    last = dict(k.last_counters)
    geo = geometry()

    run.attempted = len(tick_s)
    run.e2e["tick_ms"] = 1e3 * wall_s / len(tick_s)
    run.e2e["tick_p95_ms"] = 1e3 * clock.percentile(tick_s, 95.0)
    run.series["tick_s"] = tick_s
    run.counters.update(ticks=len(tick_s), wall_s=wall_s, live_rows=live)
    run.note("tick", entities=n, capacity=cap, seed=run.seed,
             ticks=len(tick_s), wall_s=wall_s, tick_p50_ms=1e3 * clock.percentile(tick_s, 50.0),
             tick_max_ms=1e3 * max(tick_s),
             entity_ticks_per_s=n * len(tick_s) / wall_s,
             setup_s=run.e2e["setup_s"], world_build_s=build_s,
             program_load_s=load_s, soak_s=soak_s,
             compile_passes=compile_passes, overflow_retrace=retrace_passes,
             geometry=geo, overflow_drops_total=totals, last_counters=last,
             compiles=book.total_compiles, sampled_ticks=list(sampled),
             sanctioned_retraces_in_window=retraces,
             fold_engine=None if combat is None else combat.engine_baked)

    # free the program's state before the reference runs
    host = snaps.to_host()
    params = reference_params(config, world)
    del world, k, book, combat, snaps
    gc.collect()

    t0 = time.perf_counter()
    got = compare.compare_ticks(host, params, population=n, geometry=geo)
    got["window_compiles"] = window_compiles
    got["page_written"] = 0 if page_ok else 1
    got["ticks_missing"] = max(0, int(mix["compare_ticks"])
                               - got.pop("ticks_compared"))
    run.failed = int(got["state_wrong_rows"] > 0)
    hold_limits(run, got, mix["limits"])
    run.note("compare", seconds=time.perf_counter() - t0, **got)
    if run.control:
        t0 = time.perf_counter()
        ctl = compare.compare_ticks(host, params, population=n, geometry=geo,
                                    control=True)
        run.note("control_bfloat16", seconds=time.perf_counter() - t0, **ctl)
