"""Driver `siege`: the observed tick, closed loop, over a world whose
NPCs stand on Zipf-sized spawn camps.

The `tick` driver's recipe (build the world, load `kernel.run` and
`kernel.step`, soak on the fused device loop, absorb the sanctioned
retraces, then `GameWorld.tick()` back to back with its results on the
host) with three differences:

- the world is built with the configuration's camps (`world.camps`,
  `world.camp_zipf`, `world.leash`): `build_benchmark_world(...,
  spawn_camps=...)`, which a tree without the placement does not have;
- the neighbour engine answers this world's first observed ticks by
  deepening its cells and then by sizing its second level, one retrace
  each, so the passes before and after the soak go on until a pass
  neither compiles nor announces a retrace;
- the crowd is counted (deepest cell, hot cells, rows in them, by the
  configuration's `hot_cell_rows`) when the window opens and when it
  has closed, and every window tick's drops are held against the
  program's own budget, from the tick's counters.

The comparison is `harness/reference_siege.py`'s: walkers with homes
made from the seed, the drop model of the two levels.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.harness import clock, compare, reference_siege, work_siege
from benchmarks.harness.npcworld import (NPC, STAT_RECORD, build_world,
                                         combat_geometry, extent_of,
                                         hold_limits, overflow_totals,
                                         reference_params, sample_ticks,
                                         step_scopes, until_settled)
from benchmarks.harness.run import Run, RunFailed
from noahgameframe_tpu.game import world as _program_world

if not hasattr(_program_world, "draw_camp_npcs"):
    raise ImportError("this tree cannot stand NPCs on spawn camps "
                      "(game/world.py has no draw_camp_npcs)")

SETTLE_TRIES = 8  # a doubling, the second level, a growth: one pass each
DROP_NAMES = ("aoi_victim_overflow_drops", "aoi_attacker_overflow_drops")


def spawn_camps_of(config: dict) -> dict:
    w = config["world"]
    return {"camps": int(w["camps"]), "zipf": float(w["camp_zipf"]),
            "leash": float(w["leash"])}


def siege_geometry(world) -> dict:
    """The sizes the program states for both levels of its engine."""
    geo = combat_geometry(world) or {}
    if geo:
        cap = int(world.kernel.store.capacity(NPC))
        cells, depth, att_depth = world.combat.resolved_spill(cap)
        geo.update(spill_cells=int(cells), spill_bucket=int(depth),
                   spill_att_bucket=int(att_depth))
    return geo


def siege_params(config: dict, world, seed: int) -> reference_siege.Params:
    """The configuration FILE's frame, and the homes its seed makes."""
    base = reference_params(config, world)
    rows = int(world.kernel.store.capacity(NPC))
    return reference_siege.Params(
        dt=base.dt, extent=base.extent, aoe_radius=base.aoe_radius,
        respawn_s=base.respawn_s, movement=base.movement,
        combat=base.combat,
        home_centres=reference_siege.home_centres(
            seed, config, extent_of(config), rows),
        leash=float(config["world"]["leash"]))


def run(run: Run) -> None:
    import jax

    mix, config = run.mix, run.config
    rng = np.random.default_rng(run.seed)
    t0 = time.perf_counter()
    world = build_world(config, run.seed,
                        spawn_camps=spawn_camps_of(config))
    k = world.kernel
    book = k.costbook
    build_s = time.perf_counter() - t0
    n = int(config["world"]["entities"])
    cap = int(k.store.capacity(NPC))
    combat = world.combat
    if combat is None or world.movement is None:
        raise RunFailed("the siege world needs movement and combat")
    hot_rows = int(config["hot_cell_rows"])
    pos_col = k.store.spec(NPC).slot("Position").col

    def geometry() -> dict:
        return siege_geometry(world)

    def sync() -> None:
        jax.block_until_ready(k.state.classes[NPC].i32)

    def crowd() -> dict:
        cs = k.state.classes[NPC]
        return work_siege.occupancy(
            np.asarray(cs.vec[:, pos_col, :2]), np.asarray(cs.alive),
            float(config["world"]["aoe_radius"]), extent_of(config),
            hot_rows)

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

    crowd_0 = crowd()
    # the programs of this cell, until nothing compiles: the first
    # observed ticks size both levels of the neighbour engine
    t0 = time.perf_counter()
    compile_passes = until_settled(book, lambda: observed_pass(1),
                                   tries=SETTLE_TRIES)
    load_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    k.run_device(int(mix["soak_ticks"]))
    sync()
    soak_s = time.perf_counter() - t0
    retrace_passes = until_settled(book, lambda: observed_pass(0),
                                   tries=SETTLE_TRIES)
    snaps = compare.Snapshots(k, NPC, STAT_RECORD)
    snaps.warm()
    est = []
    for _ in range(2):  # the window's own call, warm, and its pace
        t = time.perf_counter()
        world.tick()
        est.append(time.perf_counter() - t)
    until_settled(book, world.tick, tries=SETTLE_TRIES)  # none pending
    sampled = sample_ticks(rng, int(k.tick_count),
                           int(run.seconds / max(min(est), 1e-4)), config,
                           int(mix["compare_ticks"]))
    crowd_open = crowd()
    mark, compiles0 = book.mark(), book.total_compiles
    generation0 = book.generation
    run.setup_done()

    tick_s, drops = [], []
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
            last = k.last_counters
            drops.append(last[DROP_NAMES[0]] + last[DROP_NAMES[1]])
            if t_end - t_start >= run.seconds:
                break
        wall_s = t_end - t_start

    window_compiles = len(book.unexplained_since(mark))
    retraces = book.total_compiles - compiles0 - window_compiles
    announced = book.generation - generation0
    page_ok = snaps.page_unchanged()
    live = int(k.store.live_count(NPC))
    crowd_close = crowd()
    if run.trace:
        run.hlo_scopes.update(step_scopes(k))
    totals = overflow_totals(k)
    last = dict(k.last_counters)
    geo = geometry()
    budget = combat.overflow_budget * live

    run.attempted = len(tick_s)
    run.e2e["tick_ms"] = 1e3 * wall_s / len(tick_s)
    run.e2e["tick_p95_ms"] = 1e3 * clock.percentile(tick_s, 95.0)
    run.series["tick_s"] = tick_s
    run.counters.update(ticks=len(tick_s), wall_s=wall_s, live_rows=live)
    run.note("tick", entities=n, capacity=cap, seed=run.seed,
             ticks=len(tick_s), wall_s=wall_s,
             tick_p50_ms=1e3 * clock.percentile(tick_s, 50.0),
             tick_max_ms=1e3 * max(tick_s),
             entity_ticks_per_s=n * len(tick_s) / wall_s,
             setup_s=run.e2e["setup_s"], world_build_s=build_s,
             program_load_s=load_s, soak_s=soak_s,
             compile_passes=compile_passes, overflow_retrace=retrace_passes,
             geometry=geo, overflow_drops_total=totals, last_counters=last,
             compiles=book.total_compiles, sampled_ticks=list(sampled),
             sanctioned_retraces_in_window=retraces,
             retraces_announced_in_window=announced,
             fold_engine=combat.engine_baked,
             spill_cells=geo.get("spill_cells"),
             spill_depth=geo.get("spill_bucket"),
             spill_att_depth=geo.get("spill_att_bucket"),
             window_drops_max=max(drops), window_drops_budget=budget,
             window_ticks_over_budget=int(sum(d > budget for d in drops)))
    run.note("crowd", hot_cell_rows=hot_rows, tick_0=crowd_0,
             window_open=crowd_open, window_close=crowd_close)

    # free the program's state before the reference runs
    host = snaps.to_host()
    params = siege_params(config, world, run.seed)
    del world, k, book, combat, snaps
    gc.collect()

    t0 = time.perf_counter()
    kept: dict = {}
    got = reference_siege.compare_ticks(host, params, population=n,
                                        geometry=geo, keep=kept)
    got["window_compiles"] = window_compiles
    got["page_written"] = 0 if page_ok else 1
    got["ticks_missing"] = max(0, int(mix["compare_ticks"])
                               - got.pop("ticks_compared"))
    run.failed = int(got["state_wrong_rows"] > 0)
    hold_limits(run, got, mix["limits"])
    run.note("compare", seconds=time.perf_counter() - t0, **got)
    if kept:
        t0 = time.perf_counter()
        w = work_siege.spill_work(kept["state"], params, kept["pos"],
                                  kept["attacking"], hot_rows)
        run.counters.update(spill_work_flops=w["flops"],
                            spill_work_bytes=w["bytes"])
        run.note("spill_work", seconds=time.perf_counter() - t0, **w)
    if run.control:
        t0 = time.perf_counter()
        ctl = reference_siege.compare_ticks(host, params, population=n,
                                            geometry=geo, control=True)
        run.note("control_bfloat16", seconds=time.perf_counter() - t0, **ctl)
