"""Driver `served`: the five-role served path, from the client's side.

The recipe is `chip_smoke.py`'s `_serve` (proven on the chip in PR 21):
a `LocalCluster` (master, login, world, proxy, game) over the cell's
world, `sessions` `GameClient`s over loopback TCP through the whole
reference handshake, all asking to enter the game in one round, avatars
spread along the diagonal.  A throwaway same-recipe cluster runs first,
with leases no stall can expire, so that the live cluster's first frames
find every program loaded and the shipped leases hold.

The window is the single pump, closed loop: `cluster.execute()`, then
every client's `execute()`, again and again.

End-to-end, on one clock (`perf_counter_ns`) in one process:
  frame_ms      window wall time / world ticks whose traffic reached
                EVERY client inside the window
  frame_p95_ms  over every (client, frame) pair of frames begun in the
                window: from the game role's `StageClock.frame_begin`
                to the client handling the frame's FRAME_TRACE, which
                TCP orders behind the frame's sync traffic.  A pair
                delivered after the window closed counts with its wait;
                one that never arrives is `failed`.

Comparison: the game role's tick against the plain reference on a few
frames drawn from the seed (harness/compare.py), and on those same
frames every client's mirror of the NPCs around its avatar against the
world state the frame was served from (interest filter, quantisation,
encode, proxy relay, SDK decode): `mirror_wrong`.
"""

from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

from benchmarks.harness import clock, compare
from benchmarks.harness.npcworld import (NPC, STAT_RECORD, build_world,
                                         combat_geometry, hold_limits,
                                         overflow_totals,
                                         reference_params, sample_ticks,
                                         step_scopes, until_settled)
from benchmarks.harness.run import Run, RunFailed

PLAYER = "Player"
QMAX = 65535  # the interest stream's u16 quantisation
SERVE_STAGES = ("harvest", "interest", "encode", "assemble", "send")
HANDSHAKE_TIMEOUT_S = 180.0
LATE_WAIT_S = 60.0


class Cluster:
    """One five-role cluster with its clients, and the taps the
    benchmark reads: frame begins, stage waterfalls, FRAME_TRACE
    arrivals, and each client's mirror at the sampled frames."""

    def __init__(self, run: Run, seed: int, sessions: int, live: bool):
        from noahgameframe_tpu.client import GameClient
        from noahgameframe_tpu.net.defines import MsgID
        from noahgameframe_tpu.net.roles.cluster import LocalCluster
        from noahgameframe_tpu.telemetry.pipeline import decode_trace

        self.run = run
        self.world = build_world(run.config, seed, player_capacity=int(
            run.config["served"]["player_capacity"]))
        kwargs = {} if live else {"lease_suspect_seconds": 3600.0,
                                  "lease_down_seconds": 7200.0}
        self.cluster = LocalCluster(
            game_world=self.world,
            game_kwargs={"interest_radius": float(
                run.config["served"]["interest_radius"])},
            **kwargs)
        self.game = self.cluster.game
        self.kernel = self.game.kernel
        self.clients = [GameClient(f"bench{i}") for i in range(sessions)]
        self.frames = []  # (begin_ns, tick_before, tick_after, stages)
        self.arrivals = []  # (client, tick, t_encode_ns, recv_ns)
        self.mirrors = {}  # (client, tick) -> {ident key: quantised pos}
        self.sampled = range(0)
        self.bad_leases = set()  # (server id, lease) seen while watched
        self.watch_leases = False
        self.max_gap = {"s": 0.0, "last": None}  # longest pump standstill
        self._frame_trace_id = int(MsgID.FRAME_TRACE)
        self._decode_trace = decode_trace
        self._tap_stage_clock()
        for i, c in enumerate(self.clients):
            self._tap_client(i, c)

    # ------------------------------------------------------------ taps
    def _tap_stage_clock(self) -> None:
        sc, kernel, run, frames = (self.game.stage_clock, self.kernel,
                                   self.run, self.frames)
        begin, end, stage = sc.frame_begin, sc.frame_end, sc.stage
        open_ = {}

        def frame_begin(tick):
            open_["t"] = clock.now_ns()
            open_["tick"] = int(kernel.tick_count)
            return begin(tick)

        def frame_end():
            last = end()
            frames.append((open_["t"], open_["tick"],
                           int(kernel.tick_count), dict(last)))
            return last

        def traced_stage(name):
            ctx = stage(name)
            if not run._tracing:
                return ctx
            both = contextlib.ExitStack()
            both.enter_context(run.annotate("stage." + name))
            both.enter_context(ctx)
            return both

        sc.frame_begin, sc.frame_end, sc.stage = (frame_begin, frame_end,
                                                  traced_stage)

    def _tap_client(self, i: int, client) -> None:
        handle = client._handlers[self._frame_trace_id]

        def on_frame_trace(base):
            recv = clock.now_ns()
            ctx = self._decode_trace(base.msg_data)
            self.arrivals.append((i, int(ctx.tick), int(ctx.t_encode_ns),
                                  recv))
            if int(ctx.tick) - 1 in self.sampled:
                # what the position stream put there: a property message
                # alone also creates a mirror object, without a position
                self.mirrors[(i, int(ctx.tick))] = {
                    key: o.position for key, o in client.objects.items()
                    if "Position" in o.properties}
            return handle(base)

        client._handlers[self._frame_trace_id] = on_frame_trace

    def tap_ticks(self, snaps, sampled) -> None:
        """Copy the banks around the sampled ticks of the role's kernel."""
        self.sampled = sampled
        kernel, tick = self.kernel, self.kernel.tick

        def sampled_tick():
            if int(kernel.tick_count) in self.sampled:
                return snaps.around(tick)
            return tick()

        kernel.tick = sampled_tick

    # ------------------------------------------------------------ pump
    def pump_clients(self) -> None:
        from noahgameframe_tpu.net.roles.master import LEASE_UP

        now = time.perf_counter()
        if self.max_gap["last"] is not None:
            self.max_gap["s"] = max(self.max_gap["s"],
                                    now - self.max_gap["last"])
        self.max_gap["last"] = now
        for c in self.clients:
            c.execute()
        for by_id in self.cluster.master.registry.values():
            for reg in by_id.values():
                if reg.lease != LEASE_UP:
                    self.bad_leases.add((reg.report.server_id, reg.lease,
                                         self.watch_leases))

    def wait_for(self, reached, what: str) -> None:
        ok = self.cluster.pump_until(
            lambda: all(reached(c) for c in self.clients),
            extra=self.pump_clients, timeout=HANDSHAKE_TIMEOUT_S)
        if not ok:
            stuck = [c.account for c in self.clients if not reached(c)]
            raise RunFailed(f"clients never reached {what!r}: {stuck}")

    def admit(self) -> None:
        """Every client in lockstep through the reference handshake, all
        entering the game in one round; then spread the avatars."""
        login_port = self.cluster.login.config.port
        game_id = self.game.config.server_id
        for what, act, reached in (
            ("login connected",
             lambda c, i: c.connect("127.0.0.1", login_port),
             lambda c: c.connected),
            ("logged in", lambda c, i: c.login(), lambda c: c.logged_in),
            ("world list", lambda c, i: c.request_world_list(),
             lambda c: c.worlds),
            ("world grant",
             lambda c, i: c.connect_world(c.worlds[0].server_id),
             lambda c: c.world_grant is not None),
            ("proxy connected", lambda c, i: c.connect_proxy(),
             lambda c: c.connected),
            ("key verified", lambda c, i: c.verify_key(),
             lambda c: c.key_verified),
            ("game server selected", lambda c, i: c.select_server(game_id),
             lambda c: c.server_selected),
            ("role created", lambda c, i: c.create_role(f"Bench{i}"),
             lambda c: c.roles),
            ("entered game", lambda c, i: c.enter_game(f"Bench{i}"),
             lambda c: c.entered),
        ):
            for i, c in enumerate(self.clients):
                act(c, i)
            self.wait_for(reached, what)
        ext = float(self.world.config.extent)
        n = len(self.clients)
        for i, c in enumerate(self.clients):
            f = (i + 0.5) / n
            c.move_to(ext * f, ext * (1.0 - f))

    def serve(self, frames: int, timeout: float = 300.0) -> None:
        f0 = len(self.frames)
        ok = self.cluster.pump_until(
            lambda: len(self.frames) - f0 >= frames,
            extra=self.pump_clients, timeout=timeout)
        if not ok:
            raise RunFailed("the cluster served no frames in time")

    def close(self) -> None:
        for c in self.clients:
            c.close()
        self.cluster.shut()


def npc_idents(kernel) -> np.ndarray:
    """[rows, 2] (head, data) of the NPC row's guid, as the wire names it."""
    host = kernel.store._hosts[NPC]
    return np.stack([np.asarray(host.guid_head, np.int64),
                     np.asarray(host.guid_data, np.int64)], axis=1)


def session_rows(game, clients) -> list:
    """Each client's avatar row in the Player bank, in client order."""
    rows = []
    for c in clients:
        g = c.player_guid
        sess = next((s for s in game.sessions.values()
                     if s.guid is not None and (s.guid.head, s.guid.data)
                     == (g.svrid, g.index)), None)
        if sess is None:
            raise RunFailed(f"client {c.account} has no session")
        rows.append(int(game.kernel.store.row_of(sess.guid)[1]))
    return rows


def mirror_wrong(host, mirrors, idents, avatar_rows, lay, extent: float,
                 radius: float, depth: int) -> dict:
    """Hold each client's mirror at each sampled frame against the world
    the frame was served from: the NPCs in the mirror are exactly those
    within the interest radius of the client's avatar (same scene; same
    group or group 0), each at its quantised position.  An NPC within
    float32 rounding of the radius may be on either side, and so may one
    that the interest table's stated cell depth drops (cells of one
    radius, `depth` rows each, the highest rows of an over-full cell
    dropped): both are counted, per million entries checked."""
    interest_cells = {"cell_size": radius,
                      "width": max(1, int(np.ceil(extent / radius))),
                      "bucket": depth, "att_bucket": depth}
    key_of = {(int(h), int(d)): r for r, (h, d) in enumerate(idents)}
    names = lay.i32_names
    scene_c, group_c = names.index("SceneID"), names.index("GroupID")
    r2 = np.float32(radius) * np.float32(radius)
    margin = compare.D2_MARGIN_ULPS * float(np.spacing(r2))
    wrong = checked = ambiguous = 0
    for (client, tick), mirror in sorted(mirrors.items()):
        post = host.post.get(tick)
        if post is None:
            continue
        me = avatar_rows[client]
        obs = post["obs_vec"][me, 0, :2]  # Position is the first vector
        obs_scene = post["obs_i32"][me, scene_c]
        obs_group = post["obs_i32"][me, group_c]
        pos = post["vec"][:, lay.position_col, :]
        i32 = post["i32"]
        d = pos[:, :2] - obs[None, :]
        d2 = d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1]
        inside = np.all((pos[:, :2] >= 0) & (pos[:, :2] <= np.float32(extent)),
                        axis=1)
        scoped = post["alive"] & inside & (i32[:, scene_c] == obs_scene) \
            & ((i32[:, group_c] == 0) | (i32[:, group_c] == obs_group))
        near = np.abs(d2.astype(np.float64) - float(r2)) <= margin
        overfull = np.zeros(near.shape, bool)
        overfull[compare.dropped_rows(pos, post["alive"] & inside,
                                      overfull, interest_cells)[0]] = True
        near |= overfull & (d2 <= r2)
        want = set(np.flatnonzero(scoped & (d2 <= r2) & ~near).tolist())
        either = set(np.flatnonzero(scoped & near).tolist())
        q = np.clip(np.round(pos * np.float32(QMAX / extent)), 0, QMAX)
        scale = extent / QMAX
        got = {}
        for key, p in mirror.items():
            row = key_of.get(key)
            if row is not None:
                got[row] = p
        rows = set(got)
        wrong += len((want - rows)) + len((rows - want - either))
        for row in rows & (want | either):
            mine = np.round(np.asarray(got[row], np.float64) / scale)
            if not np.array_equal(mine, q[row].astype(np.float64)):
                wrong += 1
        checked += len(want)
        ambiguous += len(either)
    return {"mirror_wrong": wrong, "mirror_checked": checked,
            "mirror_ambiguous": 1e6 * ambiguous / max(1, checked)}


def control_mirrors(host, mirrors, idents, avatar_rows, lay, extent: float,
                    radius: float) -> dict:
    """The control of the mirror comparison: what each client would hold
    had the interest filter and the quantisation run on bfloat16
    positions, put in the mirrors' place."""
    from benchmarks.harness.reference import _bf16

    names = lay.i32_names
    scene_c, group_c = names.index("SceneID"), names.index("GroupID")
    out = {}
    for (client, tick) in mirrors:
        post = host.post.get(tick)
        if post is None:
            continue
        me = avatar_rows[client]
        obs = _bf16(post["obs_vec"][me, 0, :2])
        pos = _bf16(post["vec"][:, lay.position_col, :])
        i32 = post["i32"]
        d = pos[:, :2] - obs[None, :]
        seen = post["alive"] & (d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1]
                                <= np.float32(radius * radius)) \
            & (i32[:, scene_c] == post["obs_i32"][me, scene_c]) \
            & ((i32[:, group_c] == 0)
               | (i32[:, group_c] == post["obs_i32"][me, group_c]))
        q = np.clip(np.round(pos * np.float32(QMAX / extent)), 0, QMAX)
        out[(client, tick)] = {
            (int(idents[r, 0]), int(idents[r, 1])):
                tuple((q[r] * (extent / QMAX)).tolist())
            for r in np.flatnonzero(seen)}
    return out


def run(run: Run) -> None:
    mix, config = run.mix, run.config
    sessions = int(mix["sessions"])
    rng = np.random.default_rng(run.seed)

    # warm-up: the same recipe once, on a cluster whose leases no stall
    # can expire; its serve programs then sit in jax's caches
    t0 = time.perf_counter()
    if mix.get("warm_cluster", True):
        warm = Cluster(run, run.seed, sessions, live=False)
        try:
            for _ in range(4):
                warm.game.execute()
                time.sleep(warm.world.config.dt)
            warm.cluster.start(timeout=60)
            warm.admit()
            warm.serve(int(mix["warm_frames"]))
        finally:
            warm.close()
        del warm
        gc.collect()
    warm_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    live = Cluster(run, run.seed, sessions, live=True)
    build_s = time.perf_counter() - t0
    frames, arrivals = live.frames, live.arrivals
    game, k, cluster = live.game, live.kernel, live.cluster
    book = k.costbook
    n = int(config["world"]["entities"])
    try:
        # nothing is registered before start(), so no lease runs yet: the
        # soak and this role's own tick programs load off the clock
        t0 = time.perf_counter()
        k.run_device(int(mix["soak_ticks"]))

        def one_frame():
            game.execute()
            time.sleep(live.world.config.dt)

        for _ in range(2):
            until_settled(book, one_frame)
        soak_s = time.perf_counter() - t0
        cluster.start(timeout=60)
        t0 = time.perf_counter()
        live.admit()
        admit_s = time.perf_counter() - t0
        snaps = compare.Snapshots(k, NPC, STAT_RECORD, observers=PLAYER)
        snaps.warm()
        f0 = len(frames)
        live.serve(int(mix["warm_frames"]))  # every session's shapes, warm
        # a bucket boost on the last of those frames leaves a retrace
        # pending: absorb it before the window opens
        until_settled(book, lambda: live.serve(2))
        recent = frames[f0:]
        pace = clock.mean([b[0] - a[0] for a, b in zip(recent, recent[1:])]
                          or [1e8]) / 1e9
        sampled = sample_ticks(rng, int(k.tick_count) + 1,
                               int(run.seconds / max(pace, 1e-3)), config,
                               int(mix["compare_ticks"]))
        live.tap_ticks(snaps, sampled)
        idents = npc_idents(k)
        avatar_rows = session_rows(game, live.clients)
        mark, compiles0 = book.mark(), book.total_compiles
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
        while time.perf_counter() - t_late < LATE_WAIT_S:
            got = delivered()
            if all(len(got.get(t, ())) == sessions for t in owed):
                break
            for role in cluster.roles:
                if role is not game:
                    role.execute()
            live.pump_clients()
        late_s = time.perf_counter() - t_late
        got = delivered()

        # a bucket boost inside the window is the program's own,
        # announced retrace: its stall is in the metrics, and it is
        # noted, not refused
        unexplained = book.unexplained_since(mark)
        compiles = len(unexplained)
        retraces = book.total_compiles - compiles0 - compiles
        page_ok = snaps.page_unchanged()
        if run.trace:
            run.hlo_scopes.update(step_scopes(k))
        stats = game.pipeline_stats()
        clients_up = sum(c.connected and c.entered for c in live.clients)
        sessions_up = sum(1 for s in game.sessions.values()
                          if s.guid is not None)
        host = snaps.to_host()
        params = reference_params(config, live.world)
        extent = float(live.world.config.extent)
        lay = host.layout
        total_compiles = book.total_compiles
        geo = combat_geometry(live.world)
        from noahgameframe_tpu.ops.stencil import auto_bucket

        interest_depth = auto_bucket(  # as GameRole._interest_step sizes it
            int(k.store.capacity(NPC)), max(1, int(np.ceil(
                extent / float(config["served"]["interest_radius"])))))
        drops = overflow_totals(k)
    finally:
        live.close()
    mirrors, bad_leases = live.mirrors, live.bad_leases
    del live, game, k, cluster, book, snaps
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
        sum(f[3].get(s, 0) for s in SERVE_STAGES) / 1e6 for f in in_window]
    run.series["delivery_ms"] = [
        (recv - enc) / 1e6 for c, tick, enc, recv in arrivals
        if tick in owed]
    run.counters.update(ticks=sum(ticks_of[f[2]] for f in in_window),
                        frames=len(in_window), wall_s=wall_s, live_rows=n)
    stage_means = {s: clock.mean([f[3].get(s, 0) / 1e6 for f in in_window])
                   for s in ("tick",) + SERVE_STAGES + ("other",)} \
        if in_window else {}
    run.note("served", entities=n, sessions=sessions, seed=run.seed,
             frames_begun=len(in_window), ticks_served_to_all=served_ticks,
             pairs=len(pair_ms), wall_s=wall_s,
             frame_p50_ms=clock.percentile(pair_ms, 50.0) if pair_ms else None,
             frame_max_ms=max(pair_ms) if pair_ms else None,
             stage_mean_ms=stage_means, warm_cluster_s=warm_s,
             live_build_s=build_s, soak_and_load_s=soak_s, admit_s=admit_s,
             late_wait_s=late_s, longest_pump_gap_in_setup_s=setup_gap_s,
             leases_not_up_in_setup=sorted(
                 str(b[:2]) for b in bad_leases if not b[2]),
             setup_s=run.e2e["setup_s"], transport=stats.get("transport"),
             inbound_backlog_max=stats.get("inbound_backlog_max"),
             compiles=total_compiles, sampled_ticks=list(sampled),
             geometry=geo, overflow_drops_total=drops,
             sanctioned_retraces_in_window=retraces,
             unexplained_compiles=[
                 {k: r.get(k) for k in ("entry", "cause", "compile_ms")}
                 for r in unexplained],

             interest_cell_depth=interest_depth)

    # ---- the comparison
    t0 = time.perf_counter()
    res = compare.compare_ticks(host, params, population=n, geometry=geo)
    res.update(mirror_wrong(host, mirrors, idents, avatar_rows, lay, extent,
                            float(config["served"]["interest_radius"]),
                            interest_depth))
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
    run.note("compare", seconds=time.perf_counter() - t0, **res)
    if run.control:
        radius = float(config["served"]["interest_radius"])
        ctl = compare.compare_ticks(host, params, population=n, geometry=geo,
                                    control=True)
        ctl.update(mirror_wrong(
            host, control_mirrors(host, mirrors, idents, avatar_rows, lay,
                                  extent, radius),
            idents, avatar_rows, lay, extent, radius, interest_depth))
        run.note("control_bfloat16", **ctl)
