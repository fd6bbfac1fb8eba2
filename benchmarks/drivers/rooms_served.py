"""Driver `rooms_served`: the dungeon fleet behind the five roles, each
session served a clone room of its own.

The `served` driver's recipe (a `LocalCluster`, `sessions` `GameClient`s
over loopback TCP through the whole reference handshake, all entering in
one round; a throwaway same-recipe cluster first; then the single pump,
closed loop; the same taps, so the served per-layer readers read this
cell as they read `served-100k-s32`) with these differences:

- the role's single world is a small town (the configuration's `town`),
  and the role hosts the fleet: a `RoomDirectory` of `rooms.rooms`
  slots (`drivers/rooms.py`'s recipe) attached with `attach_rooms`, so
  the role's pump ticks it once a world tick.  Of `rooms.rooms`, the
  sessions' swaps make `sessions`; the others are admitted in bulk
  before the cluster starts (`rooms.room_seeds`).  The throwaway
  cluster's directory has the same slots and no bulk rooms;
- after entering the game every client swaps into the town's CLONE
  scene (`ReqAckSwapScene`), which seats it in a room made from the
  run's seed and its account, and stands still there;
- the comparison is the fleet's: around a few fleet ticks drawn from
  the seed the fleet's NPC banks are copied (`rooms.FleetSnapshots`),
  every served room and a seeded sample of the others are replayed by
  `harness/reference_rooms.py`, and every client's mirror at those
  frames is held to `harness/reference_rooms_served.py`: its room's
  NPCs in view at their quantised positions with their HP, its own
  avatar, and nothing else.

What `served.Cluster` could not be told (its `__init__` builds the
world from the configuration's `world`, which here describes a room,
and has no seam for a fleet; its client tap keeps positions only) is
done in `FleetCluster` below: the constructor and the client tap are
copies, adapted.  The window's arithmetic is `served.run`'s, copied.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.drivers import rooms as fleet
from benchmarks.drivers import served
from benchmarks.harness import (clock, reference_rooms,
                                reference_rooms_served, work_rooms_served)
from benchmarks.harness.npcworld import (NPC, build_world, hold_limits,
                                         sample_ticks, until_settled)
from benchmarks.harness.run import Run, RunFailed
from noahgameframe_tpu.net.roles.game import GameRole

if "seed" not in GameRole.attach_rooms.__code__.co_varnames:
    raise ImportError("this tree's game role serves no clone room (no "
                      "seeded attach_rooms): it cannot serve the fleet")

# a handshake stage may hold the served rooms' first compiles beside 32
# entries, and a loaded host stretches every wait
HANDSHAKE_TIMEOUT_S = 600.0


def town_config(config: dict) -> dict:
    """The configuration as `npcworld.build_world` reads a world."""
    return {**config, "world": config["town"]}


class FleetCluster(served.Cluster):
    """`served.Cluster` over the town, with the fleet attached to its
    game role and one tap more: the clients' mirrors with their HP at
    the frames whose fleet tick was sampled."""

    def __init__(self, run: Run, seed: int, sessions: int, live: bool,
                 bulk_rooms: int):
        # served.Cluster.__init__, with the town for the world
        from noahgameframe_tpu.client import GameClient
        from noahgameframe_tpu.game import BenchmarkRoomRecipe
        from noahgameframe_tpu.game.scene_process import SCENE_TYPE_CLONE
        from noahgameframe_tpu.net.defines import MsgID
        from noahgameframe_tpu.net.roles.cluster import LocalCluster
        from noahgameframe_tpu.parallel.rooms import RoomDirectory
        from noahgameframe_tpu.telemetry.pipeline import decode_trace

        config = run.config
        self.run = run
        self.world = build_world(
            town_config(config), seed,
            player_capacity=int(config["served"]["player_capacity"]))
        self.dungeon = int(config["dungeon_scene"])
        self.world.kernel.elements.add_element(
            "Scene", str(self.dungeon), {"SceneType": SCENE_TYPE_CLONE})
        kwargs = {} if live else {"lease_suspect_seconds": 3600.0,
                                  "lease_down_seconds": 7200.0}
        self.cluster = LocalCluster(
            game_world=self.world,
            game_kwargs={"interest_radius": float(
                config["served"]["interest_radius"])},
            **kwargs)
        self.game = self.cluster.game
        self.kernel = self.game.kernel
        self.clients = [GameClient(f"bench{i}") for i in range(sessions)]
        self.frames = []
        self.arrivals = []
        self.mirrors = {}  # (client, tick) -> {key: (position, HP)}
        self.sampled = range(0)
        self.mirror_ticks = {}  # town tick -> fleet tick its frame reached
        self.bad_leases = set()
        self.watch_leases = False
        self.max_gap = {"s": 0.0, "last": None}
        self._frame_trace_id = int(MsgID.FRAME_TRACE)
        self._decode_trace = decode_trace
        self._tap_stage_clock()
        for i, c in enumerate(self.clients):
            self._tap_client(i, c)
        # the fleet, as drivers/rooms.py builds it, on the role
        rc, w = config["rooms"], config["world"]
        self.recipe = BenchmarkRoomRecipe(
            int(rc["npcs_per_room"]), float(rc["extent"]),
            combat=bool(w["combat"]), movement=bool(w["movement"]),
            attack_period_s=float(w["attack_period_s"]),
            player_capacity=int(rc["player_capacity"]))
        self.directory = RoomDirectory(
            self.recipe, capacity=int(rc["rooms"]),
            template_seed=seed % 2 ** 32,
            registry=self.game.telemetry.registry)
        if bulk_rooms:
            ids, seeds = fleet.room_seeds(seed, bulk_rooms)
            self.directory.create_rooms(seeds, ids)
        self.game.attach_rooms(self.directory, seed=seed % 2 ** 32)

    def _tap_client(self, i: int, client) -> None:
        handle = client._handlers[self._frame_trace_id]

        def on_frame_trace(base):
            recv = clock.now_ns()
            ctx = self._decode_trace(base.msg_data)
            self.arrivals.append((i, int(ctx.tick), int(ctx.t_encode_ns),
                                  recv))
            if int(ctx.tick) in self.mirror_ticks:
                # what the position stream put there, with the HP the
                # property lane put beside it
                self.mirrors[(i, int(ctx.tick))] = {
                    key: (o.position, o.properties.get("HP"))
                    for key, o in client.objects.items()
                    if "Position" in o.properties}
            return handle(base)

        client._handlers[self._frame_trace_id] = on_frame_trace

    def tap_fleet(self, snaps, sampled) -> None:
        """Copy the fleet's banks around the sampled fleet ticks, and
        remember which town frame ticked each."""
        self.sampled = sampled
        d, tick = self.directory, self.directory.tick
        batch, kernel = d.batch, self.kernel

        def sampled_tick():
            if int(batch.tick_count) in self.sampled:
                got = snaps.around(tick)
                self.mirror_ticks[int(kernel.tick_count)] = int(
                    batch.tick_count)
                return got
            return tick()

        d.tick = sampled_tick

    def wait_for(self, reached, what: str) -> None:
        ok = self.cluster.pump_until(
            lambda: all(reached(c) for c in self.clients),
            extra=self.pump_clients, timeout=HANDSHAKE_TIMEOUT_S)
        if not ok:
            stuck = [c.account for c in self.clients if not reached(c)]
            raise RunFailed(f"clients never reached {what!r}: {stuck}")

    def swap_in(self) -> None:
        """Every client into the dungeon in one round."""
        for c in self.clients:
            c.swap_scene(self.dungeon)
        self.wait_for(lambda c: c.scene_id == self.dungeon,
                      "seated in a clone room")

    def seats(self) -> list:
        """(room id, slot, avatar row) of each client, in client order,
        as the role states them."""
        by_guid = {(s.world_guid.head, s.world_guid.data): s
                   for s in self.game._room_serve.seats.values()}
        out = []
        for c in self.clients:
            g = c.player_guid
            seat = by_guid.get((g.svrid, g.index))
            if seat is None:
                raise RunFailed(f"client {c.account} sits in no room")
            out.append((seat.room_id, self.directory.slot_of(seat.room_id),
                        seat.row))
        return out

    def close(self) -> None:
        super().close()
        self.directory = None


def settle(cl: FleetCluster, frames) -> list:
    """Serve until neither the town's programs nor the fleet's compile
    or announce a retrace."""
    books = (cl.kernel.costbook, cl.directory.batch.costbook)

    def state():
        return tuple((b.total_compiles, b.generation) for b in books)

    passes = []
    for _ in range(6):
        was = state()
        frames()
        passes.append(was)
        if state() == was:
            break
    return passes


def prepare(cl: FleetCluster, mix: dict, soak: bool) -> dict:
    """Bring a cluster to the state the window opens in, the same
    passes for the throwaway and the live one (the soak for the live
    one only): the town's and the fleet's programs load before any
    lease runs, the sessions enter the town and swap into the dungeon,
    then served frames until nothing compiles."""
    k, game, d = cl.kernel, cl.game, cl.directory
    took = {}

    def one_frame():
        game.execute()
        time.sleep(cl.world.config.dt)

    t0 = time.perf_counter()
    if soak:
        k.run_device(int(mix["soak_ticks"]))
        d.run(int(mix["soak_ticks"]))
    for _ in range(2):
        until_settled(k.costbook, one_frame)
    took["load_and_soak_s"] = time.perf_counter() - t0
    cl.cluster.start(timeout=60)
    t0 = time.perf_counter()
    cl.admit()
    took["admit_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    cl.swap_in()
    took["swap_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    cl.serve(int(mix["warm_frames"]))
    took["settle_passes"] = len(settle(cl, lambda: cl.serve(2)))
    took["warm_frames_s"] = time.perf_counter() - t0
    return took


def run(run: Run) -> None:
    mix, config = run.mix, run.config
    sessions = int(mix["sessions"])
    n_rooms = int(config["rooms"]["rooms"])
    if n_rooms < sessions:
        raise RunFailed(f"{n_rooms} rooms cannot seat {sessions} sessions")
    rng = np.random.default_rng(run.seed)
    radius = float(config["served"]["interest_radius"])

    t0 = time.perf_counter()
    warm_took = None
    if mix.get("warm_cluster", True):
        warm = FleetCluster(run, run.seed, sessions, live=False,
                            bulk_rooms=0)
        try:
            warm_took = prepare(warm, mix, soak=False)
        finally:
            warm.close()
        del warm
        gc.collect()
    warm_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    live = FleetCluster(run, run.seed, sessions, live=True,
                        bulk_rooms=n_rooms - sessions)
    build_s = time.perf_counter() - t0
    frames, arrivals = live.frames, live.arrivals
    game, k, cluster, d = live.game, live.kernel, live.cluster, \
        live.directory
    batch = d.batch
    books = (k.costbook, batch.costbook)
    try:
        f0 = len(frames)
        took = prepare(live, mix, soak=True)
        snaps = fleet.FleetSnapshots(d)
        snaps.warm()
        settle(live, lambda: live.serve(2))
        recent = frames[max(f0, len(frames) - 8):]
        pace = clock.mean([b[0] - a[0] for a, b in zip(recent, recent[1:])]
                          or [1e8]) / 1e9
        # drawn from the first half of the frames the pace promises: a
        # host shared with other work runs the window slower than its
        # set-up, and a tick drawn past the window's end is never kept
        sampled = sample_ticks(rng, int(batch.tick_count) + 1,
                               int(0.5 * run.seconds / max(pace, 1e-3)),
                               config, int(mix["compare_ticks"]))
        live.tap_fleet(snaps, sampled)
        seats = live.seats()
        marks = [b.mark() for b in books]
        compiles0 = sum(b.total_compiles for b in books)
        setup_gap_s = live.max_gap["s"]
        live.watch_leases = True  # the guarantee is held over the window
        run.setup_done()

        with run.window():
            w0 = clock.now_ns()
            while clock.now_ns() - w0 < run.seconds * 1e9:
                with run.annotate("pump"):
                    cluster.execute()
                with run.annotate("clients"):
                    live.pump_clients()
            w1 = clock.now_ns()

        # frames begun in the window are owed to every client: wait for
        # the stragglers (the game role rests, so no new frame begins)
        owed = {f[2] for f in frames if w0 <= f[0] < w1}

        def delivered() -> dict:
            got = {}
            for c, tick, _enc, recv in arrivals:
                if tick in owed:
                    got.setdefault(tick, {})[c] = recv
            return got

        t_late = time.perf_counter()
        while time.perf_counter() - t_late < served.LATE_WAIT_S:
            got = delivered()
            if all(len(got.get(t, ())) == sessions for t in owed):
                break
            for role in cluster.roles:
                if role is not game:
                    role.execute()
            live.pump_clients()
        late_s = time.perf_counter() - t_late
        got = delivered()

        unexplained = [r for b, m in zip(books, marks)
                       for r in b.unexplained_since(m)]
        compiles = len(unexplained)
        retraces = sum(b.total_compiles for b in books) - compiles0 \
            - compiles
        page_ok = snaps.page_unchanged()
        stats = game.pipeline_stats()
        clients_up = sum(c.connected and c.entered for c in live.clients)
        sessions_up = sum(1 for s in game.sessions.values()
                          if s.guid is not None)
        seated = len(game._room_serve.seats)
        slots = np.flatnonzero(d.packer.used)
        live_rows = int(np.asarray(
            batch.state.classes[NPC].alive)[slots].sum())
        served_slots = sorted({s for _r, s, _row in seats})
        registry = game.telemetry.registry
        counted = {name: registry.value(name) for name in (
            "nf_rooms_clone_enters_total", "nf_rooms_served_slots_total",
            "nf_rooms_served_pad")}
        params = fleet.reference_params(config, d.template_world)
        geo = d.combat_geometry() or {}
        pad = game._room_serve.pad
    finally:
        live.close()
    mirrors, bad_leases = live.mirrors, live.bad_leases
    mirror_ticks, clients = dict(live.mirror_ticks), live.clients
    pre, post, counters, stat_sums = snaps.to_host()
    totals_off = snaps.totals_off
    layout = snaps.layout
    del live, game, k, cluster, d, batch, books, snaps
    gc.collect()

    # ---- the window's numbers: every frame, every (client, frame) pair
    # a flush with no tick due (host writes between ticks) shares its tick
    # with the frame before it: the tick's frame began with the first
    begin, ticks_of = {}, {}
    for f in frames:
        begin.setdefault(f[2], f[0])
        ticks_of[f[2]] = max(ticks_of.get(f[2], 0), f[2] - f[1])
    in_window = [f for f in frames if w0 <= f[0] < w1]
    served_ticks = sum(
        ticks_of[t] for t in owed
        if len(got.get(t, ())) == sessions
        and max(got[t].values()) <= w1)
    pair_ms, order_wrong = [], 0
    for t in owed:
        for c, recv in got.get(t, {}).items():
            pair_ms.append((recv - begin[t]) / 1e6)
    last_seen = {}
    for c, tick, _enc, _recv in arrivals:
        if tick < last_seen.get(c, -1):
            order_wrong += 1
        last_seen[c] = tick
    run.attempted = len(owed) * sessions
    run.failed = run.attempted - len(pair_ms)
    wall_s = (w1 - w0) / 1e9
    if served_ticks and pair_ms:
        run.e2e["frame_ms"] = 1e3 * wall_s / served_ticks
        run.e2e["frame_p95_ms"] = clock.percentile(pair_ms, 95.0)
    run.series["stage_tick_ms"] = [f[3].get("tick", 0) / 1e6
                                   for f in in_window]
    run.series["stage_serve_ms"] = [
        sum(f[3].get(s, 0) for s in served.SERVE_STAGES) / 1e6
        for f in in_window]
    run.series["delivery_ms"] = [
        (recv - enc) / 1e6 for c, tick, enc, recv in arrivals
        if tick in owed]
    run.counters.update(ticks=sum(ticks_of[f[2]] for f in in_window),
                        frames=len(in_window), wall_s=wall_s,
                        live_rows=live_rows)
    stage_means = {s: clock.mean([f[3].get(s, 0) / 1e6 for f in in_window])
                   for s in ("tick",) + served.SERVE_STAGES + ("other",)} \
        if in_window else {}
    run.note("served", rooms=len(slots), sessions=sessions, seated=seated,
             served_pad=pad, seed=run.seed, frames_begun=len(in_window),
             ticks_served_to_all=served_ticks, pairs=len(pair_ms),
             wall_s=wall_s,
             frame_p50_ms=clock.percentile(pair_ms, 50.0) if pair_ms else None,
             frame_max_ms=max(pair_ms) if pair_ms else None,
             stage_mean_ms=stage_means, warm_cluster_s=warm_s,
             warm_cluster=warm_took, live_build_s=build_s, live=took,
             enter_s_a_session=took["admit_s"] / sessions,
             late_wait_s=late_s, longest_pump_gap_in_setup_s=setup_gap_s,
             leases_not_up_in_setup=sorted(
                 str(b[:2]) for b in bad_leases if not b[2]),
             setup_s=run.e2e["setup_s"], transport=stats.get("transport"),
             inbound_backlog_max=stats.get("inbound_backlog_max"),
             sampled_fleet_ticks=list(sampled),
             mirror_frames=sorted(mirror_ticks), geometry=geo,
             counters=counted, sanctioned_retraces_in_window=retraces,
             unexplained_compiles=[
                 {k: r.get(k) for k in ("entry", "cause", "compile_ms")}
                 for r in unexplained])

    # ---- the comparison: the fleet's ticks, then the clients' mirrors
    t0 = time.perf_counter()
    others = np.setdiff1d(slots, served_slots)
    want = int(mix.get("compare_rooms", len(others)))
    if want < len(others):
        others = np.sort(rng.choice(others, want, replace=False))
    compared = np.union1d(served_slots, others)
    res = reference_rooms.compare_fleet(
        layout, pre, post, counters, stat_sums, params, compared,
        population=int(config["rooms"]["npcs_per_room"]), geometry=geo)
    res["dropped_off"] += totals_off
    res["rooms_missing"] = len(compared) - res.pop("rooms_compared")
    extent = float(config["rooms"]["extent"])
    spots = reference_rooms_served.avatar_spots(
        run.seed, [c.account for c in clients], extent)
    own = [(c.player_guid.svrid, c.player_guid.index) for c in clients]
    got_m = reference_rooms_served.mirror_wrong(
        layout, post, mirrors, mirror_ticks, seats, spots, extent, radius,
        own)
    res["rooms_missing"] += got_m.pop("rooms_unseen")
    res.update(got_m)
    res["window_compiles"] = compiles
    res["page_written"] = 0 if page_ok else 1
    res["ticks_missing"] = max(0, int(mix["compare_ticks"])
                               - res.pop("ticks_compared"))
    res["mirrors_missing"] = max(
        0, int(mix["compare_ticks"]) * sessions - len(mirrors))
    res["sessions_down"] = 2 * sessions - clients_up - sessions_up
    res["leases_not_up"] = len({b[:2] for b in bad_leases if b[2]})
    res["frames_out_of_order"] = order_wrong
    res["pairs_undelivered"] = run.failed
    hold_limits(run, res, mix["limits"])
    run.note("compare", seconds=time.perf_counter() - t0,
             rooms=len(compared), served_rooms=len(served_slots), **res)
    if post:
        first = min(post)
        w = work_rooms_served.interest_work(
            layout, post[first], seats, spots, extent, radius)
        run.counters.update(rooms_interest_work_bytes=w["bytes"])
        run.note("rooms_interest_work", **w)
    if run.control:
        ctl = reference_rooms.compare_fleet(
            layout, pre, post, counters, stat_sums, params, served_slots,
            population=int(config["rooms"]["npcs_per_room"]), geometry=geo,
            control=True)
        ctl.update(reference_rooms_served.mirror_wrong(
            layout, post, reference_rooms_served.control_mirrors(
                layout, post, mirrors, mirror_ticks, seats, spots, extent,
                radius),
            mirror_ticks, seats, spots, extent, radius, own))
        run.note("control_bfloat16", **ctl)
