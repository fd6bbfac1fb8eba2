"""Driver `siege_served`: the five-role served path over a world whose
NPCs stand on Zipf-sized spawn camps, its sessions standing in the crowd.

The `served` driver's recipe (a `LocalCluster` over the cell's world,
`sessions` `GameClient`s over loopback TCP through the whole reference
handshake, all entering in one round; a throwaway same-recipe cluster
first; then the single pump, closed loop; the same taps in `run`, so
the nine served per-layer readers read this cell as they read
`served-100k-s32`) with these differences:

- the world is the siege's: `build_benchmark_world(..., spawn_camps=...)`
  (`drivers/siege.py`'s words for it), and its comparison is
  `harness/reference_siege.py`'s, homes made from the seed, the drop
  model of combat's two levels;
- avatar i of S is moved to where NPC row floor((i + 0.5) * entities /
  S) stood at tick 0 (`harness/reference_siege_served.py` works the same
  spots out from the seed by its own code), and stands still;
- the program sizes three things from what it observes, one announced
  retrace each: combat's cells (a doubling), combat's second level, and
  the interest table's depth and second level (`GameRole`'s breach
  policy: a tree without `GameRole.resolved_interest` cannot serve this
  world, and this module does not import there).  So the passes before
  the window go on until a pass neither compiles nor announces one, and
  the throwaway cluster walks the same passes, so that the live one
  finds every size's program in jax's caches;
- every frame's interest sizes and counters are kept, and the mirror's
  reference is `harness/reference_siege_served.py`: the two-level drop
  model from the sizes the program stated for the sampled frame, worked
  out once a frame, and the program's own `dropped` counter held to the
  count those sizes imply (`dropped_off`, beside combat's).

What `served.Cluster` could not be told (its `__init__` builds the
world with no way to pass `spawn_camps`; `admit` spreads the avatars
along the diagonal itself) is done in `CrowdCluster` below.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.drivers import served, siege
from benchmarks.harness import (clock, compare, reference_siege,
                                reference_siege_served, work_interest)
from benchmarks.harness.npcworld import (NPC, STAT_RECORD, build_world,
                                         compiled_texts, extent_of,
                                         hold_limits, overflow_totals,
                                         sample_ticks, step_scopes,
                                         until_settled)
from benchmarks.harness.run import Run, RunFailed
from noahgameframe_tpu.net.roles.game import GameRole

if not hasattr(GameRole, "resolved_interest"):
    raise ImportError("this tree's game role sizes its interest table "
                      "from the capacity alone (no resolved_interest): "
                      "it cannot serve a crowd")

PLAYER = served.PLAYER
SETTLE_TRIES = 8  # a doubling, a second level, a growth: one pass each
# a handshake stage may hold the interest programs' first compiles (a
# cold cache: ~35 s a table size at 2^20 rows, three sizes) beside 32
# entries: `served.HANDSHAKE_TIMEOUT_S` (180) is sized for 131,072 rows
HANDSHAKE_TIMEOUT_S = 900.0


class CrowdCluster(served.Cluster):
    """`served.Cluster` over the siege world, its avatars in the crowd,
    with one tap more: the interest table's sizes and counters of every
    frame."""

    def __init__(self, run: Run, seed: int, sessions: int, live: bool):
        # served.Cluster.__init__, with the world built on its camps
        from noahgameframe_tpu.client import GameClient
        from noahgameframe_tpu.net.defines import MsgID
        from noahgameframe_tpu.net.roles.cluster import LocalCluster
        from noahgameframe_tpu.telemetry.pipeline import decode_trace

        self.run = run
        self.world = build_world(
            run.config, seed,
            player_capacity=int(run.config["served"]["player_capacity"]),
            spawn_camps=siege.spawn_camps_of(run.config))
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
        self.frames = []
        self.arrivals = []
        self.mirrors = {}
        self.sampled = range(0)
        self.bad_leases = set()
        self.watch_leases = False
        self.max_gap = {"s": 0.0, "last": None}
        self._frame_trace_id = int(MsgID.FRAME_TRACE)
        self._decode_trace = decode_trace
        self._tap_stage_clock()
        for i, c in enumerate(self.clients):
            self._tap_client(i, c)
        # where the crowd stands before the first tick: the avatars' spots
        cs = self.kernel.state.classes[NPC]
        col = self.kernel.store.spec(NPC).slot("Position").col
        rows = reference_siege_served.avatar_rows(
            int(run.config["world"]["entities"]), sessions)
        self.spots = np.asarray(cs.vec[:, col, :2])[rows]
        self.interest = {}  # tick reached -> (sizes stated, counters)
        self._tap_interest()

    def _tap_interest(self) -> None:
        sc, game, kernel = self.game.stage_clock, self.game, self.kernel
        begin, end, kept = sc.frame_begin, sc.frame_end, self.interest
        open_ = {}

        def frame_begin(tick):
            open_["sizes"] = tuple(game.resolved_interest(NPC))
            open_["last"] = game.interest_last.get(NPC)
            return begin(tick)

        def frame_end():
            last = end()
            counted = game.interest_last.get(NPC)
            if counted is not None and counted is not open_["last"]:
                kept[int(kernel.tick_count)] = (open_["sizes"],
                                                dict(counted))
            return last

        sc.frame_begin, sc.frame_end = frame_begin, frame_end

    def wait_for(self, reached, what: str) -> None:
        ok = self.cluster.pump_until(
            lambda: all(reached(c) for c in self.clients),
            extra=self.pump_clients, timeout=HANDSHAKE_TIMEOUT_S)
        if not ok:
            stuck = [c.account for c in self.clients if not reached(c)]
            raise RunFailed(f"clients never reached {what!r}: {stuck}")

    def admit(self) -> None:
        """The handshake as ever; then every avatar to its spot in the
        crowd (the second move of the same pump pass is the one that
        stands)."""
        super().admit()
        for c, (x, y) in zip(self.clients, self.spots):
            c.move_to(float(x), float(y))

    def sized(self) -> dict:
        """The sizes the program states, all three policies."""
        bucket, cells, depth = self.game.resolved_interest(NPC)
        return dict(siege.siege_geometry(self.world),
                    interest_bucket=int(bucket),
                    interest_spill_cells=int(cells),
                    interest_spill_depth=int(depth))


def prepare(cl: CrowdCluster, mix: dict, timeout: float = 60.0) -> dict:
    """Bring a cluster to the state the window opens in, the same passes
    for the throwaway and the live one: the role's observed ticks size
    combat's two levels (nothing is registered before `start()`, so no
    lease runs yet), the soak on the fused loop, the sessions admitted
    into the crowd, then served frames until the interest level is sized
    and no retrace is pending.  Returns the seconds each part took."""
    k, game, book = cl.kernel, cl.game, cl.kernel.costbook
    took = {}

    def one_frame():
        game.execute()
        time.sleep(cl.world.config.dt)

    t0 = time.perf_counter()
    until_settled(book, one_frame, tries=SETTLE_TRIES)
    took["load_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    k.run_device(int(mix["soak_ticks"]))
    for _ in range(2):
        until_settled(book, one_frame, tries=SETTLE_TRIES)
    took["soak_s"] = time.perf_counter() - t0
    cl.cluster.start(timeout=timeout)
    t0 = time.perf_counter()
    cl.admit()
    took["admit_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    cl.serve(int(mix["warm_frames"]))  # every session's shapes, warm
    took["sizing_passes"] = len(until_settled(
        book, lambda: cl.serve(2) or cl.sized(), tries=SETTLE_TRIES))
    took["warm_frames_s"] = time.perf_counter() - t0
    return took


def interest_scopes(game) -> dict:
    """module -> instruction -> op_name of the role's interest programs
    over the NPC class, as `harness/interest_trace.py` reads them: the
    table's build, and the scan at the widest padded session count."""
    from benchmarks.harness import xplane

    mine = [key for key in game._interest_jit if key[1] == NPC]
    build = [key for key in mine if key[0] == "build"]
    scans = sorted((key for key in mine if key[0] == "scan"),
                   key=lambda key: key[2])
    out = {}
    for module, key in (("interest_build", build[-1:]),
                        ("interest_scan", scans[-1:])):
        names = out[module] = {}
        for k in key:
            for text in compiled_texts(game._interest_jit[k]):
                names.update(xplane.scopes_from_hlo_text(text))
    return out


def run(run: Run) -> None:
    mix, config = run.mix, run.config
    sessions = int(mix["sessions"])
    rng = np.random.default_rng(run.seed)
    radius = float(config["served"]["interest_radius"])

    t0 = time.perf_counter()
    warm_took = None
    if mix.get("warm_cluster", True):
        warm = CrowdCluster(run, run.seed, sessions, live=False)
        try:
            warm_took = dict(prepare(warm, mix), sized=warm.sized())
        finally:
            warm.close()
        del warm
        gc.collect()
    warm_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    live = CrowdCluster(run, run.seed, sessions, live=True)
    build_s = time.perf_counter() - t0
    frames, arrivals = live.frames, live.arrivals
    game, k, cluster = live.game, live.kernel, live.cluster
    book = k.costbook
    n = int(config["world"]["entities"])
    try:
        f0 = len(frames)
        took = prepare(live, mix)
        snaps = compare.Snapshots(k, NPC, STAT_RECORD, observers=PLAYER)
        snaps.warm()
        until_settled(book, lambda: live.serve(2), tries=SETTLE_TRIES)
        recent = frames[max(f0, len(frames) - 8):]
        pace = clock.mean([b[0] - a[0] for a, b in zip(recent, recent[1:])]
                          or [1e8]) / 1e9
        sampled = sample_ticks(rng, int(k.tick_count) + 1,
                               int(run.seconds / max(pace, 1e-3)), config,
                               int(mix["compare_ticks"]))
        live.tap_ticks(snaps, sampled)
        idents = served.npc_idents(k)
        avatar_rows = served.session_rows(game, live.clients)
        mark, compiles0 = book.mark(), book.total_compiles
        resizes0 = int(game.interest_resizes)
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

        unexplained = book.unexplained_since(mark)
        compiles = len(unexplained)
        retraces = book.total_compiles - compiles0 - compiles
        page_ok = snaps.page_unchanged()
        if run.trace:
            run.hlo_scopes.update(step_scopes(k))
            run.interest_scopes = interest_scopes(game)
        stats = game.pipeline_stats()
        clients_up = sum(c.connected and c.entered for c in live.clients)
        sessions_up = sum(1 for s in game.sessions.values()
                          if s.guid is not None)
        host = snaps.to_host()
        params = siege.siege_params(config, live.world, run.seed)
        extent = float(live.world.config.extent)
        lay = host.layout
        total_compiles = book.total_compiles
        sized = live.sized()
        geo = siege.siege_geometry(live.world)
        drops = overflow_totals(k)
        resizes = int(game.interest_resizes) - resizes0
        budget = game.interest_overflow_budget * int(k.store.live_count(NPC))
        registry = game.telemetry.registry
        dropped_total = registry.value("nf_interest_dropped_total", cls=NPC)
    finally:
        live.close()
    mirrors, bad_leases = live.mirrors, live.bad_leases
    interest, spots = live.interest, live.spots
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
        sum(f[3].get(s, 0) for s in served.SERVE_STAGES) / 1e6
        for f in in_window]
    run.series["delivery_ms"] = [
        (recv - enc) / 1e6 for c, tick, enc, recv in arrivals
        if tick in owed]
    run.counters.update(ticks=sum(ticks_of[f[2]] for f in in_window),
                        frames=len(in_window), wall_s=wall_s, live_rows=n)
    stage_means = {s: clock.mean([f[3].get(s, 0) / 1e6 for f in in_window])
                   for s in ("tick",) + served.SERVE_STAGES + ("other",)} \
        if in_window else {}
    window_frames = [interest[f[2]] for f in in_window if f[2] in interest]
    counted = [c for _sizes, c in window_frames]
    run.note("served", entities=n, sessions=sessions, seed=run.seed,
             frames_begun=len(in_window), ticks_served_to_all=served_ticks,
             pairs=len(pair_ms), wall_s=wall_s,
             frame_p50_ms=clock.percentile(pair_ms, 50.0) if pair_ms else None,
             frame_max_ms=max(pair_ms) if pair_ms else None,
             stage_mean_ms=stage_means, warm_cluster_s=warm_s,
             warm_cluster=warm_took, live_build_s=build_s, live=took,
             late_wait_s=late_s, longest_pump_gap_in_setup_s=setup_gap_s,
             leases_not_up_in_setup=sorted(
                 str(b[:2]) for b in bad_leases if not b[2]),
             setup_s=run.e2e["setup_s"], transport=stats.get("transport"),
             inbound_backlog_max=stats.get("inbound_backlog_max"),
             compiles=total_compiles, sampled_ticks=list(sampled),
             geometry=sized, overflow_drops_total=drops,
             sanctioned_retraces_in_window=retraces,
             unexplained_compiles=[
                 {k: r.get(k) for k in ("entry", "cause", "compile_ms")}
                 for r in unexplained])
    run.note("interest", sizes=sized, resizes_in_window=resizes,
             frames_counted=len(counted), dropped_total=dropped_total,
             window_dropped_max=max((c["dropped"] for c in counted),
                                    default=None),
             window_dropped_budget=budget,
             window_frames_over_budget=int(sum(
                 c["dropped"] > budget for c in counted)),
             hot_cells=[min((c["hot_cells"] for c in counted), default=None),
                        max((c["hot_cells"] for c in counted), default=None)],
             cell_rows_max=max((c["cell_rows_max"] for c in counted),
                               default=None),
             spill_rows_max=max((c["spill_rows"] for c in counted),
                                default=None),
             candidates_max=max((c["candidates_max"] for c in counted),
                                default=None))

    # ---- the comparison
    t0 = time.perf_counter()
    res = reference_siege.compare_ticks(host, params, population=n,
                                        geometry=geo)
    want_spots = reference_siege_served.avatar_spots(
        run.seed, config, extent_of(config), sessions)
    sizes_of = {t: interest[t][0] for t in interest}
    missing = {t for (_c, t) in mirrors if t not in sizes_of}
    for t in missing:  # a frame the tap never saw fails `dropped_off`
        sizes_of[t] = (sized["interest_bucket"],
                       sized["interest_spill_cells"],
                       sized["interest_spill_depth"])
    got_m = reference_siege_served.mirror_wrong(
        host, mirrors, idents, avatar_rows, lay, extent, radius, sizes_of,
        want_spots,
        program_dropped={t: interest[t][1]["dropped"] for t in interest})
    res["dropped_off"] += got_m.pop("interest_dropped_off")
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
    run.note("compare", seconds=time.perf_counter() - t0, **res)
    first = min(host.post) if host.post else None
    if first is not None:
        w = work_interest.interest_work(host.post[first], avatar_rows, lay,
                                        extent, radius)
        run.counters.update(interest_work_bytes=w["bytes"])
        run.note("interest_work", **w)
    if run.control:
        ctl = reference_siege.compare_ticks(host, params, population=n,
                                            geometry=geo, control=True)
        ctl.update(reference_siege_served.mirror_wrong(
            host, served.control_mirrors(
                host, mirrors, idents, avatar_rows, lay, extent, radius),
            idents, avatar_rows, lay, extent, radius, sizes_of, want_spots))
        run.note("control_bfloat16", **ctl)
