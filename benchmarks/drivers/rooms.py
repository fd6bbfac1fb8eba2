"""Driver `rooms`: a fleet of private rooms, the observed fleet tick,
closed loop.

Upstream's clone scenes give every player a dungeon instance of their
own; a game server at its stated capacity holds thousands.  The program
ticks them as ONE vmapped program over a leading room axis
(`parallel/rooms.py`: `RoomDirectory` over a `RoomBatch`).  The recipe:
build the directory (one template room is built as a world, its tick
traced and vmapped), admit every room in bulk (`create_rooms`: the
seeded leaves made on the host for all rooms, one scatter per leaf),
load `rooms.run` and `rooms.step`, soak on the fused loop until NPCs
die and respawn, then measure `RoomDirectory.tick()` again as soon as
the last returned with the per-room counters on the host.

End-to-end: `tick_ms` = window wall time / fleet ticks completed, and
`tick_p95_ms` over every fleet tick of the window.  Around a few ticks
drawn from the seed (one on which the regen heartbeat fires) the fleet's
NPC banks are copied on the device; after the window the fleet is freed
and every occupied room is replayed alone by the plain reference
(harness/reference_rooms.py).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.harness import clock, compare, reference, reference_rooms
from benchmarks.harness.npcworld import (NPC, STAT_RECORD, compiled_texts,
                                         hold_limits, sample_ticks,
                                         until_settled)
from benchmarks.harness.run import Run, RunFailed

OVERFLOW = ("aoi_victim_overflow_drops", "aoi_attacker_overflow_drops")


def room_seeds(seed: int, rooms: int):
    """(room ids, their seeds): a room is seeded from the run's seed
    and its id."""
    ids = list(range(1, rooms + 1))
    return ids, [(int(seed) * 1000003 + rid) % 2 ** 32 for rid in ids]


class FleetSnapshots:
    """Device copies of the fleet's NPC banks right before and right
    after a fleet tick, by one compiled copy program (warmed in set-up).
    Every leaf keeps its slot axis: `[slots, rows, ...]`."""

    def __init__(self, directory):
        import jax
        import jax.numpy as jnp

        self.directory = directory
        self.batch = directory.batch
        self.layout = compare.layout_of(self.batch.kernel, NPC, STAT_RECORD)
        self._copy = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))
        self._page_sum = jax.jit(
            lambda page: jnp.sum(page, axis=2, dtype=jnp.int32))
        self.pre, self.post, self.counters = {}, {}, {}
        self.totals_off = 0
        self.stat_sums = None

    def _leaves(self) -> dict:
        st = self.batch.state
        cs = st.classes[NPC]
        t = cs.timers
        return {"i32": cs.i32, "f32": cs.f32, "vec": cs.vec,
                "alive": cs.alive, "next_fire": t.next_fire,
                "interval": t.interval, "remain": t.remain,
                "active": t.active, "tick": st.tick, "rng": st.rng}

    def _page(self):
        return self.batch.state.classes[NPC].records[STAT_RECORD].i32

    def warm(self) -> None:
        import jax

        jax.block_until_ready(self._copy(self._leaves()))
        self.stat_sums = jax.block_until_ready(self._page_sum(self._page()))

    def around(self, tick_fn):
        """One fleet tick with a copy of the banks on either side; the
        directory's overflow totals are held to the per-room columns
        they are summed from."""
        d = self.directory
        self.pre[int(self.batch.tick_count)] = self._copy(self._leaves())
        was = {name: d.counter_totals.get(name, 0) for name in OVERFLOW}
        cols = tick_fn()
        reached = int(self.batch.tick_count)
        self.post[reached] = self._copy(self._leaves())
        self.counters[reached] = {k: np.array(v) for k, v in cols.items()}
        used = d.packer.used
        for name in OVERFLOW:
            if name in cols:
                self.totals_off += abs(
                    d.counter_totals.get(name, 0) - was[name]
                    - int(cols[name][used].sum()))
        return cols

    def page_unchanged(self) -> bool:
        import jax.numpy as jnp

        return bool(jnp.array_equal(self._page_sum(self._page()),
                                    self.stat_sums))

    def to_host(self):
        """Fetch everything kept; after this the fleet can be freed."""
        def fetch(kept):
            return {t: {k: np.asarray(v) for k, v in leaves.items()}
                    for t, leaves in kept.items()}

        got = (fetch(self.pre), fetch(self.post), dict(self.counters),
               np.asarray(self.stat_sums))
        self.pre.clear()
        self.post.clear()
        self.stat_sums = self.directory = self.batch = None
        return got


def reference_params(config: dict, world) -> reference.Params:
    """What the configuration FILE states about a room, checked against
    the template room where the program states the same thing."""
    w, cfg = config["world"], world.config
    extent = float(config["rooms"]["extent"])
    for mine, theirs in ((extent, cfg.extent), (w["dt"], cfg.dt),
                         (w["aoe_radius"], cfg.aoe_radius),
                         (w["respawn_s"], cfg.respawn_s),
                         (w["regen_period_s"], cfg.regen_period_s)):
        if abs(float(mine) - float(theirs)) > 1e-6 * abs(float(mine)):
            raise RunFailed(f"a room runs {theirs} where the "
                            f"configuration file states {mine}")
    return reference.Params(
        dt=float(w["dt"]), extent=extent,
        aoe_radius=float(w["aoe_radius"]), respawn_s=float(w["respawn_s"]),
        movement=bool(w["movement"]), combat=bool(w["combat"]))


def run(run: Run) -> None:
    import jax

    from noahgameframe_tpu.game import BenchmarkRoomRecipe
    from noahgameframe_tpu.parallel.rooms import RoomDirectory

    mix, config = run.mix, run.config
    rc, w = config["rooms"], config["world"]
    n_rooms, per_room = int(rc["rooms"]), int(rc["npcs_per_room"])
    rng = np.random.default_rng(run.seed)
    recipe = BenchmarkRoomRecipe(
        per_room, float(rc["extent"]), combat=bool(w["combat"]),
        movement=bool(w["movement"]),
        attack_period_s=float(w["attack_period_s"]),
        player_capacity=int(rc["player_capacity"]))
    t0 = time.perf_counter()
    directory = RoomDirectory(recipe, capacity=n_rooms,
                              template_seed=run.seed % 2 ** 32)
    batch = directory.batch
    book = batch.costbook
    build_s = time.perf_counter() - t0

    def sync() -> None:
        jax.block_until_ready(batch.state.classes[NPC].i32)

    t0 = time.perf_counter()
    ids, seeds = room_seeds(run.seed, n_rooms)
    directory.create_rooms(seeds, ids)
    sync()
    admit_s = time.perf_counter() - t0
    slots = np.flatnonzero(directory.packer.used)
    geo = directory.combat_geometry() or {}

    def observed_pass(fused: int) -> dict:
        if fused:
            directory.run(fused)
            sync()
        t = time.perf_counter()
        directory.tick()
        return {"at_tick": int(batch.tick_count),
                "step_s": time.perf_counter() - t}

    # the two programs of this cell, until nothing compiles
    t0 = time.perf_counter()
    compile_passes = until_settled(book, lambda: observed_pass(1))
    load_s = time.perf_counter() - t0
    # the steady state: rooms in which NPCs have died and respawned
    t0 = time.perf_counter()
    directory.run(int(mix["soak_ticks"]))
    sync()
    soak_s = time.perf_counter() - t0
    snaps = FleetSnapshots(directory)
    snaps.warm()
    est = []
    for _ in range(2):  # the window's own call, warm, and its pace
        t = time.perf_counter()
        directory.tick()
        est.append(time.perf_counter() - t)
    until_settled(book, directory.tick)
    sampled = sample_ticks(rng, int(batch.tick_count),
                           int(run.seconds / max(min(est), 1e-4)), config,
                           int(mix["compare_ticks"]))
    mark = book.mark()
    totals0 = dict(directory.counter_totals)
    ticked0 = batch.slots_ticked
    run.setup_done()

    tick_s = []
    with run.window():
        t_start = time.perf_counter()
        while True:
            t = time.perf_counter()
            with run.annotate("tick"):
                if int(batch.tick_count) in sampled:
                    snaps.around(directory.tick)
                else:
                    directory.tick()
            t_end = time.perf_counter()
            tick_s.append(t_end - t)
            if t_end - t_start >= run.seconds:
                break
        wall_s = t_end - t_start

    window_compiles = len(book.unexplained_since(mark))
    page_ok = snaps.page_unchanged()
    live = int(np.asarray(batch.state.classes[NPC].alive)[slots].sum())
    if run.trace:
        from benchmarks.harness import xplane

        for text in compiled_texts(batch._jit_step):
            run.hlo_scopes.update(xplane.scopes_from_hlo_text(text))
    window_totals = {k: v - totals0.get(k, 0)
                     for k, v in directory.counter_totals.items()}
    ticks = len(tick_s)

    run.attempted = ticks
    run.e2e["tick_ms"] = 1e3 * wall_s / ticks
    run.e2e["tick_p95_ms"] = 1e3 * clock.percentile(tick_s, 95.0)
    run.series["tick_s"] = tick_s
    run.counters.update(
        ticks=ticks, wall_s=wall_s, live_rows=live, rooms=len(slots),
        slots=batch.capacity, slots_ticked=batch.slots_ticked - ticked0,
        admitted_rows=directory.admitted_rows,
        admit_bytes=batch.admit_bytes)
    run.note("fleet", rooms=len(slots), slots=batch.capacity,
             npcs_per_room=per_room,
             room_rows=int(batch.kernel.store.capacity(NPC)),
             seed=run.seed, ticks=ticks, wall_s=wall_s,
             tick_p50_ms=1e3 * clock.percentile(tick_s, 50.0),
             tick_max_ms=1e3 * max(tick_s),
             room_ticks_per_s=len(slots) * ticks / wall_s,
             entity_ticks_per_s=live * ticks / wall_s,
             live_rows=live, setup_s=run.e2e["setup_s"],
             directory_build_s=build_s, admit_s=admit_s,
             admitted_rows=directory.admitted_rows,
             admit_bytes=batch.admit_bytes, program_load_s=load_s,
             soak_s=soak_s, compile_passes=compile_passes, geometry=geo,
             slots_ticked_in_window=batch.slots_ticked - ticked0,
             window_counter_totals=window_totals,
             compiles=book.total_compiles, sampled_ticks=list(sampled))

    # free the fleet before the reference runs
    pre, post, counters, stat_sums = snaps.to_host()
    totals_off = snaps.totals_off
    layout = snaps.layout
    params = reference_params(config, directory.template_world)
    del directory, batch, book, snaps, recipe
    gc.collect()

    want = mix.get("compare_rooms", "all")
    if want != "all" and int(want) < len(slots):
        slots = np.sort(rng.choice(slots, int(want), replace=False))
    t0 = time.perf_counter()
    got = reference_rooms.compare_fleet(
        layout, pre, post, counters, stat_sums, params, slots,
        population=per_room, geometry=geo)
    got["dropped_off"] += totals_off
    got["window_compiles"] = window_compiles
    got["page_written"] = 0 if page_ok else 1
    got["ticks_missing"] = max(0, int(mix["compare_ticks"])
                               - got.pop("ticks_compared"))
    got["rooms_missing"] = len(slots) - got.pop("rooms_compared")
    run.failed = int(got["state_wrong_rows"] > 0)
    hold_limits(run, got, mix["limits"])
    run.note("compare", seconds=time.perf_counter() - t0,
             rooms=len(slots), **got)
    if run.control:
        t0 = time.perf_counter()
        ctl = reference_rooms.compare_fleet(
            layout, pre, post, counters, stat_sums, params, slots[:256],
            population=per_room, geometry=geo, control=True)
        run.note("control_bfloat16", seconds=time.perf_counter() - t0,
                 rooms=int(min(256, len(slots))), **ctl)
