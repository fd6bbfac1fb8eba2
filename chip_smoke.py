#!/usr/bin/env python
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the main path once, in this one process, through the entry points
users have, with the defaults as shipped (no NF_* knob is set here), and
checks what comes out by the repo's own means: integers the tick itself
produces, state digests, the client SDK's object mirror.

    python chip_smoke.py              one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4    the paths across chips, and only those

One chip:
  tick     BASELINE config 4: build_benchmark_world(1M), the fused device
           loop and observed ticks, gated on population, combat hits, the
           dead/respawn ledger, digests that move, and zero unexplained
           recompiles after warm-up.
  determinism
           two same-seed 100k worlds give identical state_digest streams
           on the chip; the same seed at 4,096 entities on the chip and on
           the host CPU is compared and printed, not gated.
  engines  the fold the module chose from its grid at the 100k geometry
           (on the chip the Pallas kernel, compiled natively) against a
           world pinned to the other fold: bit for bit the same.
  served   all five roles (LocalCluster) over a 100k world, 32 GameClient
           sessions through the whole login handshake over loopback TCP,
           then >= 60 served frames.

Four chips (--chips 4):
  mesh     the entity-sharded tick with live row migration (BASELINE
           config 5) over make_mesh(4) at 1M entities against a one-device
           control: canonical_digest equal, rows migrated, none dropped.
  mesh_npc the same migration with the benchmark world's own NPC row
           (records, timers, the stat page) through GameWorld.shard(),
           1M entities, against a one-device control.
  rooms    64 rooms room-major over a 4-wide mesh, 30 ticks, every room's
           digest equal to its single-world control's.

Every phase prints one JSON object on its own line; a failed gate raises,
so the exit code is non-zero and no result line follows.  The last line
of stdout is the result the driver reads:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

`--platform cpu --tiny` is a REHEARSAL of the control flow on the CPU
backend (tiny sizes, interpret-mode kernels, four virtual devices under
`--chips 4`); its last line says `"platform": "cpu"` and it proves nothing
about the chip.  Tick times printed here are smoke readings, not a
benchmark.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from collections import Counter

# sizes of the real run and of the --tiny rehearsal
FULL = dict(tick_n=1_000_000, world_n=100_000, cpu_n=4_096, clients=32,
            frames=60, soak=200, mesh_n=1_000_000, mesh_ticks=20,
            mesh_budget=2048, mesh_npc_n=1_000_000, rooms=64, room_ticks=30, room_npcs=160)
TINY = dict(tick_n=2_048, world_n=2_048, cpu_n=2_048, clients=3,
            frames=12, soak=200, mesh_n=8_192, mesh_ticks=20,
            mesh_budget=256, mesh_npc_n=3_000, rooms=8, room_ticks=10, room_npcs=24)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def gate(ok: bool, what: str, **ctx) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke gate failed: {what} {ctx or ''}")


class CacheWatch:
    """Counts jax's persistent-compile-cache events (requests, hits, and
    misses that were written back) through the public monitoring hook."""

    _EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "written",
    }

    def __init__(self) -> None:
        import jax

        self.n = Counter()
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, **_kw) -> None:
        name = self._EVENTS.get(event)
        if name:
            self.n[name] += 1

    def since(self, before: Counter) -> dict:
        return {k: self.n[k] - before[k]
                for k in ("requests", "hits", "written")}

    def snap(self) -> Counter:
        return Counter(self.n)


def _sync(world) -> None:
    import jax

    jax.block_until_ready(world.kernel.state.classes["NPC"].i32)


def _npc_ledger(world) -> dict:
    """Integers read off the NPC banks: live rows, rows at HP<=0, rows
    registered dead (DeadTick>0), and the rows on which the two differ."""
    import numpy as np

    k = world.kernel
    spec = k.store.spec("NPC")
    cs = k.state.classes["NPC"]
    alive = np.asarray(cs.alive)
    i32 = np.asarray(cs.i32)
    hp = i32[:, spec.slot("HP").col]
    dead = i32[:, spec.slot("DeadTick").col] > 0
    down = alive & (hp <= 0)
    return {"alive": int(alive.sum()), "down": int(down.sum()),
            "registered_dead": int((alive & dead).sum()),
            "mismatch": int((down != (alive & dead)).sum())}


def _until_settled(book, one_pass, tries: int = 4) -> list:
    """Repeat one_pass() until a pass neither compiles nor bumps the
    CostBook generation (an observed tick that sees cell-table overflow
    boosts the buckets, a sanctioned bump, and the next tick retraces).
    Returns what each pass that did not settle returned."""
    unsettled = []
    for _ in range(tries):
        was = (book.total_compiles, book.generation)
        got = one_pass()
        if (book.total_compiles, book.generation) == was:
            break
        unsettled.append(got)
    return unsettled


def _observed(world, n: int) -> list:
    """n observed ticks through GameWorld.tick(); one counter dict each."""
    out = []
    for _ in range(n):
        world.tick()
        out.append(dict(world.kernel.last_counters))
    return out


# ------------------------------------------------------------------ tick
def phase_tick(sz, seed, cache) -> None:
    from noahgameframe_tpu.game import build_benchmark_world

    n = sz["tick_n"]
    t0 = time.perf_counter()
    w = build_benchmark_world(n, seed=seed)
    k = w.kernel
    k.enable_digest()
    build_s = time.perf_counter() - t0
    book = k.costbook

    cap = int(k.store.capacity("NPC"))

    def geometry() -> dict:
        return {"width": w.combat.width,
                "bucket": w.combat.resolved_bucket(cap),
                "att_bucket": w.combat.resolved_att_bucket(cap)}

    def observed_pass(fused: int) -> dict:
        """`fused` ticks of the fused loop ("kernel.run", traced trip
        count), then one observed tick ("kernel.step").  Only an observed
        tick sees cell-table overflow: a breach of the budget boosts the
        buckets (a sanctioned generation bump) and the next tick
        retraces.  Says what the tick dropped and the geometry it left."""
        was = geometry()
        t0 = time.perf_counter()
        if fused:
            k.run_device(fused)
            _sync(w)
        t1 = time.perf_counter()
        w.tick()
        c = k.last_counters
        return {"at_tick": int(k.tick_count),
                "kernel.run_s": round(t1 - t0, 1),
                "kernel.step_s": round(time.perf_counter() - t1, 1),
                "victim_drops": c["aoi_victim_overflow_drops"],
                "attacker_drops": c["aoi_attacker_overflow_drops"],
                "geometry_from": was, "geometry_to": geometry()}

    # warm-up: the two programs of this phase, until nothing compiles
    c0 = cache.snap()
    compile_passes = _until_settled(book, lambda: observed_pass(1))
    cache_use = cache.since(c0)
    mark = book.mark()

    t0 = time.perf_counter()
    k.run_device(30)
    _sync(w)
    fused_ms = 1e3 * (time.perf_counter() - t0) / 30
    # long enough for NPCs to die (HP 100) and come back (respawn 5 s);
    # the dead pile up and overflow the cell tables: absorb that
    # sanctioned retrace before the observed window, and say it happened
    k.run_device(sz["soak"])
    _sync(w)
    retrace_passes = _until_settled(book, lambda: observed_pass(0))

    before = _npc_ledger(w)
    t0 = time.perf_counter()
    obs = _observed(w, 5)
    observed_ms = 1e3 * (time.perf_counter() - t0) / 5
    after = _npc_ledger(w)
    respawns = sum(c["respawns"] for c in obs)
    # registered_dead moves by (kills - respawns): the kills implied by
    # the ledger can never be negative
    kills = after["registered_dead"] - before["registered_dead"] + respawns
    digests = [c["state_digest"] for c in obs]
    unexplained = book.unexplained_since(mark)
    hbm = book.hbm_sample()

    emit("tick", entities=n, capacity=cap,
         seed=seed, world_build_s=round(build_s, 1),
         compile_passes=compile_passes, overflow_retrace=retrace_passes,
         observed_geometry=geometry(),
         overflow_drops_total={
             kind: k.counter_totals.get(f"aoi_{kind}_overflow_drops", 0)
             for kind in ("victim", "attacker")},
         compile_cache=cache_use,
         smoke_reading_not_a_benchmark={
             "fused_tick_ms": round(fused_ms, 3),
             "observed_tick_ms": round(observed_ms, 3)},
         ticks=int(k.tick_count), fold_engine=w.combat.engine_baked,
         combat_hits=[c["combat_hits"] for c in obs],
         respawns=respawns, kills_implied=kills, ledger=after,
         overflow_drops={
             "victim": [c["aoi_victim_overflow_drops"] for c in obs],
             "attacker": [c["aoi_attacker_overflow_drops"] for c in obs]},
         state_digests=digests,
         compiles=book.total_compiles, recompiles=book.total_recompiles,
         generation_events=[e.get("cause") for e in book.gen_events],
         unexplained_recompiles=len(unexplained),
         hbm={"source": hbm["source"], "live_bytes": hbm["live_bytes"],
              "peak_bytes": hbm["peak_bytes"],
              "limit_bytes": hbm["limit_bytes"]})

    gate(after["alive"] == n and k.store.live_count("NPC") == n,
         "population conserved", ledger=after)
    gate(sum(c["deaths"] for c in obs) == 0, "no NPC row was destroyed")
    gate(all(c["combat_hits"] > 0 for c in obs), "combat_hits > 0")
    gate(before["mismatch"] == 0 and after["mismatch"] == 0,
         "every NPC at HP<=0 is registered dead, and no other",
         before=before, after=after)
    gate(after["registered_dead"] > 0 and kills >= 0,
         "deaths and respawns consistent", kills=kills, after=after)
    gate(len(set(digests)) == len(digests), "state_digest moves every tick")
    gate(not unexplained, "zero unexplained recompiles", got=unexplained)


# ---------------------------------------------- determinism and engines
def phase_determinism_and_engines(sz, seed, cache, platform) -> None:
    import jax
    import numpy as np

    from noahgameframe_tpu.game import build_benchmark_world
    from noahgameframe_tpu.ops.stencil_pallas import pallas_interpret

    n = sz["world_n"]

    def world(engine=None):
        w = build_benchmark_world(n, seed=seed)
        w.kernel.enable_digest()
        if engine is not None:
            w.combat.use_pallas = engine  # before the first trace
        return w

    def digests(w, ticks):
        return [c["state_digest"] for c in _observed(w, ticks)]

    c0 = cache.snap()
    a, b = world(), world()
    da, db = digests(a, 10), digests(b, 10)
    emit("determinism", entities=n, seed=seed, ticks=10,
         identical=da == db, digests=da, compile_cache=cache.since(c0))
    gate(da == db, "two same-seed worlds give one digest stream",
         a=da, b=db)

    # the same seed on the chip and on the host CPU, printed and not
    # gated: float paths may legitimately differ between backends
    small = sz["cpu_n"]
    chip_w = build_benchmark_world(small, seed=seed)
    chip_w.kernel.enable_digest()
    d_chip = digests(chip_w, 10)
    with jax.default_device(jax.devices("cpu")[0]):
        cpu_w = build_benchmark_world(small, seed=seed)
        cpu_w.kernel.enable_digest()
        d_cpu = digests(cpu_w, 10)
    first_diff = next(
        (i for i, (x, y) in enumerate(zip(d_chip, d_cpu)) if x != y), None)
    emit("determinism_vs_cpu", entities=small, gated=False,
         chip_platform=platform, cpu_equals_chip=d_chip == d_cpu,
         first_differing_tick=first_diff)

    # the two folds on the same state: world `a` baked the engine the
    # module chose from its grid (nothing pins it), `p` is pinned to the
    # other one
    chosen = a.combat.engine_baked
    p = world(engine=1 - chosen)
    dp = digests(p, 10)
    for w in (a, p):
        w.kernel.run_device(sz["soak"])  # deaths and respawns land
    da2, dp2 = digests(a, 5), digests(p, 5)
    banks_equal = all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a.kernel.state.classes["NPC"]),
                        jax.tree.leaves(p.kernel.state.classes["NPC"])))
    cap = p.kernel.store.capacity("NPC")
    emit("engines", entities=n, geometry={
             "width": p.combat.width,
             "bucket": p.combat.resolved_bucket(cap),
             "att_bucket": p.combat.resolved_att_bucket(cap)},
         engine_chosen=chosen,
         engine_pinned=p.combat.engine_baked,
         pallas_interpret=pallas_interpret(),
         ticks=int(p.kernel.tick_count),
         digests_equal=(da + da2) == (dp + dp2), banks_equal=banks_equal,
         respawns=p.kernel.counter_totals.get("respawns", 0))
    gate(chosen in (0, 1) and p.combat.engine_baked == 1 - chosen,
         "the unpinned world baked a fold and the pinned one the other")
    gate(platform == "tpu" or chosen == 0,
         "off the chip the module chooses the XLA fold")
    gate(pallas_interpret() == (platform == "cpu"),
         "the Pallas fold is interpreted on the CPU and nowhere else")
    gate((da + da2) == (dp + dp2) and banks_equal,
         "the Pallas fold matches the XLA fold bit for bit",
         chosen=da2, pinned=dp2)


# ---------------------------------------------------------------- served
_PROP_MSGS = (
    "ACK_OBJECT_PROPERTY_ENTRY", "ACK_PROPERTY_INT", "ACK_PROPERTY_FLOAT",
    "ACK_PROPERTY_STRING", "ACK_PROPERTY_OBJECT", "ACK_PROPERTY_VECTOR2",
    "ACK_PROPERTY_VECTOR3", "ACK_BATCH_PROPERTY")


def _serve(n, n_clients, frames, seed, **cluster_kwargs) -> dict:
    """One five-role cluster over a fresh same-seed world: n_clients
    GameClient sessions through the whole reference handshake over
    loopback TCP, then `frames` served frames.  Returns what was seen."""
    from noahgameframe_tpu.client import GameClient
    from noahgameframe_tpu.game import build_benchmark_world
    from noahgameframe_tpu.net.defines import MsgID
    from noahgameframe_tpu.net.roles.cluster import LocalCluster
    from noahgameframe_tpu.net.roles.master import LEASE_UP

    world = build_benchmark_world(n, seed=seed, player_capacity=64)
    cluster = LocalCluster(game_world=world,
                           game_kwargs={"interest_radius": 8.0},
                           **cluster_kwargs)
    game, master = cluster.game, cluster.master
    clients = [GameClient(f"smoke{i}") for i in range(n_clients)]
    prop_ids = {int(MsgID[m]) for m in _PROP_MSGS}
    interest_id = int(MsgID.ACK_INTEREST_POS)
    seen = [Counter() for _ in clients]
    for c, cnt in zip(clients, seen):
        for mid, fn in list(c._handlers.items()):
            def counted(base, _fn=fn, _mid=mid, _cnt=cnt):
                _cnt[_mid] += 1
                return _fn(base)
            c._handlers[mid] = counted
    bad_leases = set()
    # the longest time the single pump stood still, and what it was
    # waiting for then: a stall near the lease threshold is a finding
    pump = {"last": None, "max_gap_s": 0.0, "stage": "start", "during": ""}

    def pump_clients() -> None:
        now = time.perf_counter()
        if pump["last"] is not None and now - pump["last"] > pump["max_gap_s"]:
            pump["max_gap_s"] = now - pump["last"]
            pump["during"] = pump["stage"]
        pump["last"] = now
        for c in clients:
            c.execute()
        for by_id in master.registry.values():
            for reg in by_id.values():
                if reg.lease != LEASE_UP:
                    bad_leases.add((reg.report.server_id, reg.lease))

    def wait_for(who, reached, what) -> None:
        pump["stage"] = what
        ok = cluster.pump_until(lambda: all(reached(c) for c in who),
                                extra=pump_clients, timeout=180.0)
        gate(ok, f"every client reached: {what}",
             stuck=[c.account for c in who if not reached(c)])

    try:
        # nothing is registered before start(), so no lease is running
        # yet: load this role's own tick programs now, not on the clock
        # (each Kernel compiles through its own CostBook, so even a
        # cache hit is seconds of loading for a 100k tick on the chip)
        for _ in range(4):  # ... the overflow retrace of tick 1 included
            game.execute()
            time.sleep(world.config.dt)
        cluster.start(timeout=60)
        # every client in lockstep through the reference handshake
        login_port = cluster.login.config.port
        game_id = game.config.server_id
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
            ("role created", lambda c, i: c.create_role(f"Smoke{i}"),
             lambda c: c.roles),
            # every client at once: a login burst.  Enter-game is ~0.57 s
            # of eager device reads and writes per session on the chip,
            # and the game role serves the burst a frame period at a
            # time (GameRole.inbound_budget_seconds), so the pump and the
            # leases outlive it
            ("entered game", lambda c, i: c.enter_game(f"Smoke{i}"),
             lambda c: c.entered),
        ):
            for i, c in enumerate(clients):
                act(c, i)
            wait_for(clients, reached, what)

        # spread the avatars over the world, then serve
        ext = float(world.config.extent)
        for i, c in enumerate(clients):
            f = (i + 0.5) / n_clients
            c.move_to(ext * f, ext * (1.0 - f))
        book = game.kernel.costbook
        mark = book.mark()
        f0, t0 = game.stage_clock.frames, time.perf_counter()
        out0 = _out_bytes(game), _out_bytes(cluster.proxy)
        pump["stage"] = "serving"
        ok = cluster.pump_until(
            lambda: game.stage_clock.frames - f0 >= frames,
            extra=pump_clients, timeout=300.0)
        serve_s = time.perf_counter() - t0
        served = game.stage_clock.frames - f0
        gate(ok, "served the frames in time", frames=served)
        cluster.pump(extra=pump_clients, rounds=50)  # last frames land

        stats = game.pipeline_stats()
        return {
            "frames": served, "serve_s": round(serve_s, 2),
            "transports": {r.config.name: r.transport_backend
                           for r in cluster.roles},
            "transport": stats["transport"], "stages": stats["stages"],
            "inbound_backlog_max": stats["inbound_backlog_max"],
            "bytes_per_frame": {
                "game_to_proxy": (_out_bytes(game) - out0[0]) // served,
                "proxy_to_clients":
                    (_out_bytes(cluster.proxy) - out0[1]) // served},
            "per_client": [{
                "objects": len(c.objects),
                "property_msgs": sum(cnt[m] for m in prop_ids),
                "interest_msgs": cnt[interest_id],
            } for c, cnt in zip(clients, seen)],
            "clients_up": sum(c.connected and c.entered for c in clients),
            "sessions": sum(1 for s in game.sessions.values()
                            if s.guid is not None),
            "leases_not_up": sorted(bad_leases),
            "max_pump_gap": {"s": round(pump["max_gap_s"], 2),
                             "during": pump["during"]},
            "compiles": book.total_compiles,
            "recompiles": book.total_recompiles,
            "unexplained": book.unexplained_since(mark),
        }
    finally:
        for c in clients:
            c.close()
        cluster.shut()


def phase_served(sz, seed, cache) -> None:
    n, n_clients = sz["world_n"], sz["clients"]
    # Warm-up: the same recipe once on a throwaway cluster.  The first
    # tick/flush/persist/interest compiles stall the single pump for
    # seconds each (tens of seconds in all on the chip), which would
    # expire healthy leases; so the throwaway cluster, and only it, gets
    # leases no stall can expire.  Its programs are then in jax's caches
    # and the gated run below keeps the shipped lease thresholds.
    t0 = time.perf_counter()
    _serve(n, n_clients, sz["frames"], seed,
           lease_suspect_seconds=3600.0, lease_down_seconds=7200.0)
    warm_s = time.perf_counter() - t0

    c0 = cache.snap()
    got = _serve(n, n_clients, sz["frames"], seed)
    per_client = got.pop("per_client")
    unexplained = got.pop("unexplained")
    keys = ("objects", "property_msgs", "interest_msgs")
    emit("served", entities=n, clients=n_clients,
         warm_s=round(warm_s, 1), **got,
         per_client_min={k: min(p[k] for p in per_client) for k in keys},
         per_client_max={k: max(p[k] for p in per_client) for k in keys},
         unexplained_recompiles=len(unexplained),
         compile_cache=cache.since(c0))

    gate(all(p["property_msgs"] > 0 and p["interest_msgs"] > 0
             and p["objects"] > 1 for p in per_client),
         "every client's mirror got property and interest traffic",
         per_client=per_client)
    gate(not got["leases_not_up"], "no role was ever SUSPECT or DOWN",
         got=got["leases_not_up"])
    gate(got["clients_up"] == n_clients and got["sessions"] == n_clients,
         "no session dropped", got=got)
    gate(not unexplained, "zero unexplained recompiles while serving",
         got=unexplained)


def _out_bytes(role) -> int:
    """Payload bytes this role's listening side has sent so far."""
    return sum(role.server.counters.out_bytes.values())


# ------------------------------------------------------------------ mesh
def _device_bytes(tree) -> dict:
    """Bytes each device holds of `tree`, from addressable_shards."""
    import jax

    held = Counter()
    for leaf in jax.tree.leaves(tree):
        for s in leaf.addressable_shards:
            held[str(s.device)] += s.data.nbytes
    return dict(sorted(held.items()))


def _memory_stats() -> dict:
    import jax

    out = {}
    for d in jax.local_devices():
        ms = d.memory_stats() or {}
        out[str(d)] = {"bytes_in_use": ms.get("bytes_in_use"),
                       "peak_bytes_in_use": ms.get("peak_bytes_in_use")}
    return out


def phase_mesh(sz, seed) -> None:
    import numpy as np

    from noahgameframe_tpu.ops.stencil import auto_bucket
    from noahgameframe_tpu.parallel.mesh import make_mesh
    from noahgameframe_tpu.parallel.rowmigrate import canonical_digest
    from noahgameframe_tpu.parallel.spatial import (
        _GID,
        SpatialGeom,
        SpatialWorld,
    )

    n, ticks = sz["mesh_n"], sz["mesh_ticks"]
    cell = 4.0
    width = int(max(64.0, float(np.sqrt(n / 0.4))) / cell)
    width -= width % 4
    extent = width * cell
    rng = np.random.default_rng(seed)
    pos = rng.uniform(1.0, extent - 1.0, (n, 2)).astype(np.float32)
    hp = np.full(n, 10_000, np.int32)
    atk = rng.integers(5, 20, n).astype(np.int32)
    camp = (np.arange(n) % 2).astype(np.int32)

    def run(shards):
        geom = SpatialGeom(
            extent=extent, cell_size=cell, width=width, n_shards=shards,
            bucket=auto_bucket(n, width) + 8,
            att_bucket=auto_bucket(max(1, n // 30), width, lo=4, align=2) + 4,
            radius=4.0, mig_budget=sz["mesh_budget"], speed=1.0,
            attack_period=30,
        )
        # ShardedKernel + RowMigrationModule over make_mesh on whatever
        # devices jax has: the real chips here, never virtual ones
        w = SpatialWorld(geom, mesh=make_mesh(shards))
        w.place(pos, hp, atk, camp)
        t0 = time.perf_counter()
        w.step(2)
        compile_s = time.perf_counter() - t0
        mark = w.costbook.mark()
        stats = np.zeros(3, np.int64)
        t0 = time.perf_counter()
        for _ in range(ticks - 2):
            w.step(1)
            stats += w.stats_last[:, :3].sum(axis=0)
        tick_ms = 1e3 * (time.perf_counter() - t0) / (ticks - 2)
        banks = w.kernel.state.classes["spatial"]
        return {
            "shards": shards, "bank_rows": int(w.bank_size),
            "compile_plus_warm_s": round(compile_s, 1),
            "smoke_tick_ms": round(tick_ms, 3),
            "migrated_total": int(stats[0]),
            "mig_overflow_total": int(stats[1]),
            "mig_dropped_total": int(stats[2]),
            "unexplained_recompiles": len(w.costbook.unexplained_since(mark)),
            "canonical_digest": canonical_digest(
                w.kernel.state, ["spatial"], {"spatial": _GID}),
            "alive": int(np.asarray(banks.alive).sum()),
            "bank_bytes_per_device": _device_bytes(banks),
            "memory_stats": _memory_stats(),
        }

    mesh4 = run(4)
    control = run(1)
    emit("mesh", entities=n, ticks=ticks, seed=seed, mesh=mesh4,
         control=control,
         digest_equal=mesh4["canonical_digest"] == control["canonical_digest"])
    held = mesh4["bank_bytes_per_device"]
    gate(len(held) == 4 and len(set(held.values())) == 1,
         "the banks are spread evenly over four devices", held=held)
    gate(mesh4["canonical_digest"] == control["canonical_digest"],
         "sharded canonical_digest equals the one-device control's")
    gate(mesh4["alive"] == n and control["alive"] == n,
         "population conserved on both")
    gate(mesh4["migrated_total"] > 0, "rows migrated")
    gate(mesh4["mig_dropped_total"] == 0, "no migrating row was dropped")
    gate(mesh4["unexplained_recompiles"] == 0
         and control["unexplained_recompiles"] == 0,
         "zero unexplained recompiles")


def phase_mesh_npc(sz, seed) -> None:
    """The same migration with the row a served world really has: the
    benchmark world's NPC class (47 i32 + float and vector properties,
    timers, the [N, 9, 29] stat record page) through
    build_benchmark_world(placement=...) and GameWorld.shard(), against
    the plain one-device benchmark world (no migration phase: with all
    2^20 rows on one device that phase alone asks the chip's compiler
    for 10 GB of temporaries).  Movement is off because it draws each new
    target from a per-row random stream, so a row that migrated walks
    elsewhere than its control; what migrates here is the seeded world
    homing to its owners.  Everything but LastAttacker is gated equal:
    that property holds a packed (class, row) handle, which names the
    row the attacker sat on and not the attacker, so it differs as soon
    as rows move.  That is a fault of the program (a migrated entity is
    left with stale references to it), printed here and not repaired."""
    import jax.numpy as jnp
    import numpy as np

    from noahgameframe_tpu.core.store import with_class
    from noahgameframe_tpu.game import build_benchmark_world
    from noahgameframe_tpu.parallel.mesh import make_mesh
    from noahgameframe_tpu.parallel.rowmigrate import (
        SpatialPlacement,
        canonical_digest,
    )
    from noahgameframe_tpu.persist.rowblob import row_nbytes

    n, ticks = sz["mesh_npc_n"], sz["mesh_ticks"]
    extent = max(64.0, float(np.sqrt(n / 0.4)))  # the benchmark world's
    width = int(extent // 4.0)

    def run(shards):
        t0 = time.perf_counter()
        placement = SpatialPlacement(
            class_name="NPC", pos_prop="Position", extent=extent,
            cell_size=extent / width, width=width, n_shards=shards,
            mig_budget=sz["mesh_budget"]) if shards > 1 else None
        w = build_benchmark_world(n, seed=seed, movement=False,
                                  placement=placement)
        k = w.kernel
        # Which victim an over-full cell drops follows row order, so a
        # world whose rows migrated drops others than its control does
        # (found on the chip: 84 drops a tick at 1M under the shipped
        # bucket of 16, under the budget that would boost it).  The
        # comparison is exact only where nothing is dropped: double the
        # victim bucket on both sides, and gate on zero drops.
        w.combat.bucket = 2 * w.combat.resolved_bucket(
            k.store.capacity("NPC"))
        spec = k.store.spec("NPC")
        # identity for the placement-invariant digest: an inert column
        ident = spec.slot("Gold").col
        last_attacker = spec.slot("LastAttacker").col
        cs = k.state.classes["NPC"]
        k.state = with_class(k.state, "NPC", cs.replace(
            i32=cs.i32.at[:, ident].set(
                jnp.arange(cs.i32.shape[0], dtype=jnp.int32))))
        del cs  # or the unsharded banks stay on the first device
        if placement is not None:
            w.shard(mesh=make_mesh(shards))
        build_s = time.perf_counter() - t0

        stats = np.zeros(3, np.int64)
        hits = 0

        def tick():
            nonlocal stats, hits
            w.tick()
            # (a bucket boost invalidates the trace and drops aux: the
            # tick that follows registers the stats again)
            got = (k.state.aux.get(w.migration.aux_key)
                   if w.migration is not None else None)
            if got is not None:
                stats += np.asarray(got).sum(axis=0)
            hits += k.last_counters["combat_hits"]

        t0 = time.perf_counter()
        _until_settled(k.costbook, tick)
        compile_s = time.perf_counter() - t0
        mark = k.costbook.mark()
        warm = int(k.tick_count)
        t0 = time.perf_counter()
        while k.tick_count < ticks:
            tick()
        tick_ms = 1e3 * (time.perf_counter() - t0) / max(1, ticks - warm)

        def digest(state):
            return canonical_digest(state, ["NPC"], {"NPC": ident})

        banks = k.state.classes["NPC"]
        return {
            "shards": shards, "world_build_s": round(build_s, 1),
            "compile_plus_warm_s": round(compile_s, 1),
            "smoke_tick_ms": round(tick_ms, 3),
            "ticks": int(k.tick_count), "combat_hits": int(hits),
            "migrated_total": int(stats[0]),
            "mig_overflow_total": int(stats[1]),
            "mig_dropped_total": int(stats[2]),
            "victim_bucket": w.combat.bucket,
            "overflow_drops_total": {
                kind: k.counter_totals.get(f"aoi_{kind}_overflow_drops", 0)
                for kind in ("victim", "attacker")},
            "unexplained_recompiles":
                len(k.costbook.unexplained_since(mark)),
            "alive": int(np.asarray(banks.alive).sum()),
            "digest_all_columns": digest(k.state),
            "digest_but_last_attacker": digest(with_class(
                k.state, "NPC", banks.replace(
                    i32=banks.i32.at[:, last_attacker].set(0)))),
            "row_bytes": int(row_nbytes(banks)),
            "bank_bytes_per_device": _device_bytes(banks),
            "memory_stats": _memory_stats(),
        }

    mesh4 = run(4)
    control = run(1)
    equal = mesh4["digest_but_last_attacker"] == control[
        "digest_but_last_attacker"]
    emit("mesh_npc", entities=n, ticks=ticks, seed=seed, movement=False,
         mesh=mesh4, control=control, digest_equal_but_last_attacker=equal,
         last_attacker_handles_stale_after_migration=(
             mesh4["digest_all_columns"] != control["digest_all_columns"]))
    held = mesh4["bank_bytes_per_device"]
    gate(len(held) == 4 and len(set(held.values())) == 1,
         "the NPC banks are spread evenly over four devices", held=held)
    gate(not any(r["overflow_drops_total"][kind] for r in (mesh4, control)
                 for kind in ("victim", "attacker")),
         "no cell table overflowed on either side (else they may differ)")
    gate(equal, "the sharded NPC world equals its one-device control in "
                "every leaf but LastAttacker")
    gate(mesh4["alive"] == n and control["alive"] == n,
         "population conserved on both")
    gate(mesh4["combat_hits"] > 0
         and mesh4["combat_hits"] == control["combat_hits"],
         "combat ran, and hit as often as on the control")
    gate(mesh4["migrated_total"] > 0, "NPC rows migrated")
    gate(mesh4["mig_dropped_total"] == 0, "no migrating NPC row was dropped")
    gate(mesh4["unexplained_recompiles"] == 0
         and control["unexplained_recompiles"] == 0,
         "zero unexplained recompiles")


# ----------------------------------------------------------------- rooms
def phase_rooms(sz, seed) -> None:
    import numpy as np

    from noahgameframe_tpu.game import GameWorld
    from noahgameframe_tpu.game.world import WorldConfig
    from noahgameframe_tpu.parallel.mesh import ROOMS_AXIS, make_mesh
    from noahgameframe_tpu.parallel.rooms import RoomDirectory

    n_rooms, ticks, npcs = sz["rooms"], sz["room_ticks"], sz["room_npcs"]
    cap = 1 << int(np.ceil(np.log2(npcs * 1.5)))
    extent = float(np.sqrt(npcs / 0.4))  # the benchmark world's density

    def recipe(s):
        w = GameWorld(WorldConfig(
            npc_capacity=cap, player_capacity=8, extent=extent, seed=s,
            middleware=False, combat=True, movement=True, regen=True))
        w.start()
        w.scene.create_scene(1, width=extent)
        w.seed_npcs(npcs, rng=np.random.default_rng(s + 100))
        return w

    t0 = time.perf_counter()
    d = RoomDirectory(recipe, capacity=n_rooms,
                      mesh=make_mesh(4, axis=ROOMS_AXIS),
                      template_seed=seed)
    rooms = [d.create_room(seed=seed + 1 + i, control=True)
             for i in range(n_rooms)]
    build_s = time.perf_counter() - t0
    # batch and controls advance in lockstep through the directory:
    # the observed tick (rooms.step) and the fused run (rooms.run)
    t0 = time.perf_counter()
    d.tick()
    d.run(1)
    d.digest(rooms[0])
    compile_s = time.perf_counter() - t0
    mark = d.batch.costbook.mark()
    d.run(ticks - 3)
    counters = d.tick()
    pairs = [(int(d.digest(r)), int(d.control_digest(r))) for r in rooms]
    unexplained = d.batch.costbook.unexplained_since(mark)
    emit("rooms", rooms=n_rooms, ticks=ticks, npcs_per_room=npcs,
         npc_capacity=cap, build_s=round(build_s, 1),
         compile_plus_warm_s=round(compile_s, 1),
         rooms_equal_to_control=sum(a == b for a, b in pairs),
         distinct_digests=len({a for a, _ in pairs}),
         combat_hits_last_tick=int(np.asarray(counters["combat_hits"]).sum()),
         state_bytes_per_device=_device_bytes(d.batch.state),
         memory_stats=_memory_stats(),
         unexplained_recompiles=len(unexplained))
    gate(all(a == b for a, b in pairs),
         "every room's digest equals its control's",
         differing=[r for r, (a, b) in zip(rooms, pairs) if a != b])
    gate(len({a for a, _ in pairs}) == n_rooms, "rooms are independent")
    gate(int(np.asarray(counters["tick"]).min()) == ticks,
         "every room is at the same tick", want=ticks)
    held = _device_bytes(d.batch.state)
    gate(len(held) == 4 and len(set(held.values())) == 1,
         "the room batch is spread evenly over four devices", held=held)
    gate(not unexplained, "zero unexplained recompiles", got=unexplained)


# ------------------------------------------------------------------ main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=42,
                    help="every world's data is made from this")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the mesh and rooms phases and nothing else")
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="cpu: a rehearsal of the control flow, not a "
                         "check of the chip")
    ap.add_argument("--tiny", action="store_true",
                    help="tiny sizes (rehearsals)")
    args = ap.parse_args()

    from noahgameframe_tpu.utils.platform import (
        force_cpu,
        init_compile_cache,
        require_tpu,
    )

    if args.platform == "cpu":
        force_cpu(args.chips)  # that many virtual devices, no more
        print("# REHEARSAL on the CPU backend: control flow only, "
              "nothing here is a reading of the chip", flush=True)
        import jax

        devs = jax.devices()
    else:
        devs = require_tpu()
    if len(devs) < args.chips:
        raise RuntimeError(
            f"--chips {args.chips} needs {args.chips} devices, "
            f"jax has {len(devs)}")
    cache_dir = init_compile_cache()
    cache = CacheWatch()
    sz = TINY if args.tiny else FULL
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit("start", device=device, chips=args.chips, tiny=args.tiny,
         seed=args.seed, compile_cache_dir=cache_dir)

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(sz, args.seed)
        phase_mesh_npc(sz, args.seed)
        phase_rooms(sz, args.seed)
    else:
        phase_tick(sz, args.seed, cache)
        phase_determinism_and_engines(sz, args.seed, cache, devs[0].platform)
        phase_served(sz, args.seed, cache)
    emit("done", seconds=round(time.perf_counter() - t0, 1),
         compile_cache=cache.since(Counter()))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
