"""Benchmark entry point: entities ticked per second on one chip.

Runs the BASELINE config-2/4 style workload — N NPCs random-walking,
regenerating, and resolving AoE combat through the grid-AOI pipeline —
as the fully-fused device tick (`Kernel.run_device`), and prints ONE JSON
line:

    {"metric": "entities_ticked_per_sec_per_chip", "value": ..., "unit":
     "entity-ticks/s", "vs_baseline": ..., "detail": {"platform": "tpu", ...}}

`vs_baseline` is value / (1M entities * 30 Hz), i.e. 1.0 == the north-star
"1M NPCs at 30 Hz on one chip's share of a v4-8" (BASELINE.json).  The
reference itself publishes no numbers (BASELINE.md): its design point is
5000 entities/process at <=1 kHz host loop.

Device contract: a measurement comes from a TPU or not at all.
`--platform tpu` (the default) fails, with no metric line and a non-zero
exit, when `jax.devices()[0].platform` is not "tpu"; `--platform cpu` is
an explicit rehearsal for tests, and names its metric `*_cpu_rehearsal`
so a CPU rate is never read as a per-chip one.  Any exception exits
non-zero.  A chip belongs to one process: the sweep parents below spawn
their points as children and never touch jax themselves.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

NORTH_STAR_RATE = 1_000_000 * 30  # entity-ticks/sec


def _emit(payload: dict) -> None:
    print(json.dumps(payload), flush=True)


def _pin_fold(world, args) -> None:
    """--pallas: pin the fold's engine on a freshly built world, before
    its first trace (the choice is baked into the compiled tick)."""
    if args.pallas is not None and getattr(world, "combat", None):
        world.combat.use_pallas = args.pallas


def _jax_on(platform: str, n_devices=None):
    """jax on the platform a mode was asked for: "tpu" is a TPU or an
    error (never a fallback), "cpu" is an explicit rehearsal, with
    `n_devices` virtual devices where the mode runs a mesh."""
    from noahgameframe_tpu.utils.platform import force_cpu, require_tpu

    if platform == "cpu":
        return force_cpu(n_devices)
    require_tpu()
    import jax

    return jax


def _chip_metric(stem: str, dev) -> str:
    """`<stem>_per_chip` on a TPU; a CPU rehearsal's rate goes under
    `<stem>_cpu_rehearsal`, never under the device metric's name."""
    return stem + ("_per_chip" if dev.platform == "tpu"
                   else "_cpu_rehearsal")


def _overflow_gauges(world) -> tuple:
    """Run both offline overflow replays, publish them on the world's
    telemetry registry, and read the JSON values BACK from the registry —
    bench JSON and a /metrics scrape can never disagree."""
    reg = world.telemetry.registry
    g = reg.gauge(
        "nf_bench_overflow_replay",
        "offline cell-table overflow replay (max drops per tick)",
        ("side",),
    )
    g.set(_grid_overflow_max(world), side="victim")
    g.set(_att_overflow_max(world), side="attacker")
    return (
        int(reg.value("nf_bench_overflow_replay", side="victim")),
        int(reg.value("nf_bench_overflow_replay", side="attacker")),
    )


def _hist_pcts(hist) -> tuple:
    """p50/p95/p99 in ms from a registry histogram (the ONE percentile
    implementation — telemetry.registry.Histogram.percentile)."""
    return tuple(round(hist.percentile(p) * 1e3, 3) for p in (50, 95, 99))


def _costbook_detail(book, pipeline_stats=None) -> dict:
    """Compiled-cost evidence for a BENCH `detail` block: compile wall,
    recompile count+causes, HBM peak from a fresh census, per-entry
    cost — and, when the run has a StageClock waterfall, the per-stage
    achieved-vs-peak roofline fractions (CostBook x StageClock)."""
    import jax

    from noahgameframe_tpu.telemetry.costbook import roofline_fold

    hbm = book.hbm_sample()
    out = {
        "compile_ms": round(book.compile_s_total * 1e3, 1),
        "compiles": book.total_compiles,
        "recompiles": book.total_recompiles,
        "recompile_causes": {
            n: dict(e.causes)
            for n, e in sorted(book.entries.items()) if e.causes
        },
        "hbm_peak_bytes": int(hbm.get("peak_bytes", 0)),
        "hbm_live_bytes": int(hbm.get("live_bytes", 0)),
        "hbm_source": hbm.get("source"),
        "entries": {n: {"compiles": e.compiles,
                        "flops": e.last.get("flops", 0.0),
                        "bytes_accessed": e.last.get("bytes_accessed", 0.0),
                        "temp_bytes": e.last.get("temp_bytes", 0)}
                    for n, e in sorted(book.entries.items())},
    }
    if pipeline_stats is not None and jax.devices()[0].platform == "tpu":
        # fractions of peak are device metrics: a CPU rehearsal has none
        rf = roofline_fold(book, pipeline_stats)
        out["roofline"] = {
            "device_kind": rf["device_kind"],
            "peaks_source": rf["peaks"]["source"],
            "stages": {
                s: {"frac_of_peak_flops": round(v["frac_of_peak_flops"], 6),
                    "frac_of_peak_bytes": round(v["frac_of_peak_bytes"], 6),
                    "device_s_per_frame": v["device_s_per_frame"]}
                for s, v in rf["stages"].items()
            },
        }
    return out


def _combat_cost_probe(world) -> dict:
    """Attribute the combat fold's compiled cost to a per-engine
    CostBook entry (``combat.fold_p0/p1``) from the final world state,
    OUTSIDE the timed region — so ``detail.costbook.entries`` carries the
    fold's ``bytes_accessed`` in the same ledger as everything else.
    Probes the engine the run's trace baked in, one compile + one call;
    the fold math and geometry are exactly the combat phase's
    (`game/combat.py` is the source of truth)."""
    combat = getattr(world, "combat", None)
    if combat is None:
        return {}
    try:
        import jax
        import jax.numpy as jnp

        from noahgameframe_tpu.game.combat import combat_fold_xla
        from noahgameframe_tpu.ops.stencil import (
            CellTable,
            build_cell_table_pair,
        )
        from noahgameframe_tpu.ops.stencil_pallas import (
            combat_fold_pallas,
            pallas_interpret,
        )

        k = world.kernel
        cname = combat.class_name
        spec = k.store.spec(cname)
        cs = k.state.classes[cname]
        pos = cs.vec[:, spec.slot("Position").col, :2]
        alive = cs.alive
        cap = alive.shape[0]
        cell_size, width = combat.cell_size, combat.width
        bucket = combat.resolved_bucket(cap)
        att_bucket = combat.resolved_att_bucket(cap)
        engine = (combat.resolved_engine(cap) if combat.engine_baked is None
                  else combat.engine_baked)

        f32 = jnp.float32
        camp_f = cs.i32[:, spec.slot("Camp").col].astype(f32)
        scene_f = cs.i32[:, spec.slot("SceneID").col].astype(f32)
        group_f = cs.i32[:, spec.slot("GroupID").col].astype(f32)
        atk_f = cs.i32[:, spec.slot("ATK_VALUE").col].astype(f32)
        interval = max(1, k.schedule.ticks_of(combat.attack_period_s))
        attacking = alive & ((jnp.arange(cap) % interval) == 0)
        interp = pallas_interpret()
        entry = f"combat.fold_p{engine}"
        radius = combat.radius

        rows_f = jnp.arange(cap, dtype=f32)
        vic_f = jnp.stack(
            [pos[:, 0], pos[:, 1], camp_f, scene_f, group_f], -1
        )
        att_f = jnp.stack(
            [pos[:, 0], pos[:, 1], atk_f, camp_f, scene_f, group_f,
             rows_f], -1
        )
        vt, at = build_cell_table_pair(
            pos, alive, vic_f, attacking, att_f,
            cell_size, width, bucket, att_bucket,
        )

        def fold_of(vp, vs, ap, as_):
            tables = (
                CellTable(vp, vs, jnp.int32(0), width, cell_size, bucket),
                CellTable(ap, as_, jnp.int32(0), width, cell_size,
                          att_bucket),
            )
            if engine == 1:
                return combat_fold_pallas(*tables, radius, interpret=interp)
            return combat_fold_xla(*tables, radius)

        fold = k.costbook.wrap(entry, fold_of, stage="aoe")
        jax.block_until_ready(
            fold(vt.payload, vt.slot_of, at.payload, at.slot_of)
        )
        return {"engine": engine, "entry": entry}
    except Exception as e:  # noqa: BLE001 — evidence, never a bench kill
        return {"error": f"{type(e).__name__}: {e}"}


def _grid_overflow_max(world) -> int:
    """Rebuild the combat victim cell-table from the final state once
    (outside the timed region) and report entities dropped by bucket
    overflow — silent drops were a round-1 finding.  This is exactly the
    table the combat phase builds (all alive entities, auto-sized
    buckets), so it is the real per-tick drop count, not an upper bound."""
    try:
        import jax.numpy as jnp

        from noahgameframe_tpu.ops.stencil import build_cell_table

        combat = getattr(world, "combat", None)
        if combat is None:
            return -1
        cname = combat.class_name
        store = world.kernel.store
        spec = store.spec(cname)
        cs = world.kernel.state.classes[cname]
        pos = cs.vec[:, spec.slot("Position").col, :2]
        n = pos.shape[0]
        bucket = combat.resolved_bucket(n)
        table = build_cell_table(
            pos,
            cs.alive,
            jnp.zeros((n, 0), jnp.float32),
            combat.cell_size,
            combat.width,
            bucket,
        )
        return int(table.dropped)
    except Exception:  # noqa: BLE001
        return -1


def _att_overflow_max(world) -> int:
    """Worst-phase attacker-table drop count: replay each firing residue
    of the attack timer against the final positions (the attacker
    candidate table only holds one residue class per tick under staggered
    arming — a dropped attacker is an attack that doesn't land).  Exact
    for the benchmark world (timers keep their armed phase forever:
    next_fire advances by one interval per firing)."""
    try:
        import jax
        import jax.numpy as jnp

        from noahgameframe_tpu.ops.stencil import build_cell_table

        combat = getattr(world, "combat", None)
        if combat is None:
            return -1
        k = world.kernel
        cname = combat.class_name
        spec = k.store.spec(cname)
        cs = k.state.classes[cname]
        pos = cs.vec[:, spec.slot("Position").col, :2]
        n = pos.shape[0]
        att_bucket = combat.resolved_att_bucket(n)
        slot = k.schedule.slot(cname, "Attack")
        t = cs.timers
        interval = max(1, k.schedule.ticks_of(combat.attack_period_s))
        armed = t.active[:, slot] & cs.alive
        residue = t.next_fire[:, slot] % interval

        @jax.jit
        def drops_of(p):
            mask = armed & (residue == p)
            return build_cell_table(
                pos,
                mask,
                jnp.zeros((n, 0), jnp.float32),
                combat.cell_size,
                combat.width,
                att_bucket,
            ).dropped

        return max(int(drops_of(p)) for p in range(interval))
    except Exception:  # noqa: BLE001
        return -1


def run_served(args) -> dict:
    """The SERVED path: kernel.tick() with host observation + the game
    role's full per-frame sync flush (diff fetch, message serialization,
    envelope encode, broadcast fan-out to S sessions) — the cost a real
    game server pays per frame, which run_device excludes (round-1 weak
    #4: benchmark path != served path).  Transport writes are captured
    into a byte counter instead of sockets."""
    import jax

    from noahgameframe_tpu.core.datatypes import Guid  # noqa: F401
    from noahgameframe_tpu.game import build_benchmark_world
    from noahgameframe_tpu.net.roles.base import RoleConfig
    from noahgameframe_tpu.net.roles.game import GameRole, Session
    from noahgameframe_tpu.net.wire import Ident, ident_key
    from noahgameframe_tpu.utils.platform import init_compile_cache

    init_compile_cache()
    n = args.entities
    # one live Player avatar per simulated session, + headroom (the
    # driver's served probe seats 500 — round-2 weak #6 follow-up: the
    # default 64-row Player bank made the probe crash at session 65)
    from noahgameframe_tpu.core.datatypes import next_pow2

    world = build_benchmark_world(
        n,
        combat=not args.no_combat,
        seed=args.seed,
        player_capacity=next_pow2(args.sessions + 8, lo=64),
    )
    _pin_fold(world, args)
    role = GameRole(
        RoleConfig(6, 0, "BenchGame", "127.0.0.1", 0),
        backend="py",
        world=world,
        cross_server_sync=False,
        interest_radius=args.interest_radius,
        # store_true flags pass None when absent so NF_SERVE_BATCH /
        # NF_SERVE_OVERLAP env knobs still decide (A/B harness parity)
        serve_batch=args.serve_batch or None,
        serve_overlap=args.serve_overlap or None,
    )
    sent = {"msgs": 0, "bytes": 0}

    def fake_send(conn_id: int, msg_id: int, body: bytes) -> bool:
        sent["msgs"] += 1
        sent["bytes"] += len(body)
        return True

    role.server.send_raw = fake_send
    # S simulated sessions with live Player avatars in the NPC scene
    n_sessions = args.sessions
    for i in range(n_sessions):
        ident = Ident(svrid=99, index=i + 1)
        sess = Session(ident=ident, conn_id=1000 + (i % 8), account=f"bot{i}")
        g = role.kernel.create_object("Player", {"Name": f"Bot{i}"},
                                      scene=1, group=0)
        sess.guid = g
        role.sessions[ident_key(ident)] = sess
        role._guid_session[g] = ident_key(ident)

    dt = world.config.dt * 1.0001  # epsilon: defeat float >= dt jitter
    now = 1000.0
    # warm up: compile + first flush
    for _ in range(3):
        now += dt
        role.execute(now)
    jax.block_until_ready(role.kernel.state.classes["NPC"].i32)
    sent["msgs"] = sent["bytes"] = 0
    frame_ms = []
    t_all = time.perf_counter()
    for _ in range(args.ticks):
        now += dt
        t0 = time.perf_counter()
        role.execute(now)
        jax.block_until_ready(role.kernel.state.classes["NPC"].i32)
        frame_ms.append(1000 * (time.perf_counter() - t0))
    elapsed = time.perf_counter() - t_all
    # percentiles come from the role's telemetry registry — the same
    # histogram a /metrics scrape of this role would serve
    frame_hist = role.telemetry.registry.histogram(
        "nf_bench_frame_seconds", "served-path frame wall time",
        window=max(512, args.ticks),
    )
    for ms in frame_ms:
        frame_hist.observe(ms / 1e3)
    p50, p95, p99 = _hist_pcts(frame_hist)

    rate = n * args.ticks / elapsed
    dev = __import__("jax").devices()[0]
    return {
        "metric": _chip_metric("served_entity_ticks_per_sec", dev),
        "value": round(rate, 1),
        "unit": "entity-ticks/s",
        "vs_baseline": round(rate / NORTH_STAR_RATE, 4),
        "detail": {
            "entities": n,
            "ticks": args.ticks,
            "seed": args.seed,
            "sessions": n_sessions,
            "elapsed_s": round(elapsed, 4),
            "frame_ms_p50": p50,
            "frame_ms_p95": p95,
            "frame_ms_p99": p99,
            "sync_msgs": sent["msgs"],
            "sync_bytes": sent["bytes"],
            "interest_radius": args.interest_radius,
            "serve_batch": bool(role.serve_batch),
            "serve_overlap": bool(role.serve_overlap),
            "device": str(dev),
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            # per-stage frame waterfall (ISSUE 7): p50/p95/mean ms per
            # pipeline stage from the role's StageClock, plus the last
            # frame's exact breakdown and trace-sidecar counters
            "pipeline": role.pipeline_stats(),
            # compiled-cost evidence + the measured roofline: per-stage
            # achieved-vs-peak fractions from CostBook x StageClock
            "costbook": _costbook_detail(role.kernel.costbook,
                                         role.pipeline_stats()),
        },
    }


def run_sharded(args) -> dict:
    """BASELINE config-5 evidence: the SAME world and tick, sharded over
    an n-device mesh (virtual CPU devices stand in for a pod slice —
    the driver's dryrun validates compilation, this measures a full
    fused run and reports mesh geometry + throughput)."""
    from noahgameframe_tpu.utils.platform import force_cpu, init_compile_cache

    jax = force_cpu(args.sharded)
    init_compile_cache()  # pay the XLA compile once

    from noahgameframe_tpu.game import build_benchmark_world
    from noahgameframe_tpu.parallel import ShardedKernel

    n = args.entities
    world = build_benchmark_world(n, combat=not args.no_combat,
                                  seed=args.seed)
    _pin_fold(world, args)
    sk = ShardedKernel(world.kernel, n_devices=args.sharded)
    sk.place()
    k = world.kernel
    # the benchmark loop reuses ONE compiled sharded step (host-looped,
    # state device-resident) — compile cost is a single step's, not the
    # round-3 fori-fused 319 s program
    t_c0 = time.perf_counter()
    sk.run_device(1, fused=False)  # compile + first tick
    jax.block_until_ready(k.state.classes["NPC"].i32)
    compile_s = time.perf_counter() - t_c0
    t0 = time.perf_counter()
    sk.run_device(args.ticks, fused=False)
    jax.block_until_ready(k.state.classes["NPC"].i32)
    dt = time.perf_counter() - t0
    rate = n * args.ticks / dt
    grid_drop, att_drop = _overflow_gauges(world)
    return {
        "metric": "sharded_entity_ticks_per_sec",
        "value": round(rate, 1),
        "unit": "entity-ticks/s",
        "vs_baseline": round(rate / NORTH_STAR_RATE, 4),
        "detail": {
            "entities": n,
            "ticks": args.ticks,
            "seed": args.seed,
            "devices": args.sharded,
            "mesh": str(dict(sk.mesh.shape)),
            "elapsed_s": round(dt, 4),
            "compile_plus_first_tick_s": round(compile_s, 2),
            "tick_ms": round(1000 * dt / args.ticks, 3),
            "platform": jax.devices()[0].platform,
            "per_device_rate": round(rate / args.sharded, 1),
            "combat": not args.no_combat,
            "grid_overflow_max": grid_drop,
            "att_overflow_max": att_drop,
            "costbook": _costbook_detail(k.costbook),
        },
    }


def run_mesh_migrate(args) -> dict:
    """ISSUE 15 r09 evidence: the unified engine's full-row migration
    ladder.  Sweeps entity count x mesh width x migration budget through
    the ONE engine (SpatialWorld as a thin preset over Kernel +
    ShardedKernel + RowMigrationModule) on virtual CPU devices —
    config-5 shape.  Each point reports throughput, migration traffic
    (rows and analytic collective bytes = row_bytes x migrated), and a
    CostBook recompile gate: after the 2-tick warmup, the sweep loop
    must compile NOTHING new (`unexplained_recompiles == 0`)."""
    from noahgameframe_tpu.utils.platform import force_cpu, init_compile_cache

    jax = force_cpu(args.mesh_migrate)
    init_compile_cache()

    import numpy as np

    from noahgameframe_tpu.ops.stencil import auto_bucket
    from noahgameframe_tpu.parallel.spatial import SpatialGeom, SpatialWorld

    entities = [int(x) for x in
                (args.mig_entities or "100000,1000000").split(",")]
    if args.mig_widths:
        widths = [int(x) for x in args.mig_widths.split(",")]
    else:
        widths = [w for w in (2, 4, 8) if w <= args.mesh_migrate] or [1]
    budgets = [int(x) for x in (args.mig_budgets or "2048,8192").split(",")]
    ticks = args.mig_ticks

    def point(n, shards, budget):
        radius = 4.0
        cell = 4.0
        extent = max(64.0, float(np.sqrt(n / 0.4)))
        width = max(shards, int(extent / cell))
        width -= width % shards
        extent = width * cell
        bucket = auto_bucket(n, width) + 8
        att_bucket = auto_bucket(max(1, n // 30), width, lo=4, align=2) + 4
        geom = SpatialGeom(
            extent=extent, cell_size=cell, width=width, n_shards=shards,
            bucket=bucket, att_bucket=att_bucket, radius=radius,
            mig_budget=budget, speed=1.0, attack_period=30,
        )
        rng = np.random.default_rng(args.seed)
        pos = rng.uniform(1.0, extent - 1.0, (n, 2)).astype(np.float32)
        hp = np.full(n, 10_000, np.int32)
        atk = rng.integers(5, 20, n).astype(np.int32)
        camp = (np.arange(n) % 2).astype(np.int32)
        world = SpatialWorld(geom)
        world.place(pos, hp, atk, camp)
        t_c0 = time.perf_counter()
        world.step(2)  # compile + warm (stats fetch path included)
        compile_s = time.perf_counter() - t_c0
        mark = world.costbook.mark()
        migrated = overflow = dropped = 0
        t0 = time.perf_counter()
        for _ in range(ticks):
            world.step(1)
            s = world.stats_last.sum(axis=0)
            migrated += int(s[0])
            overflow += int(s[1])
            dropped += int(s[2])
        dt = time.perf_counter() - t0
        unexplained = world.costbook.unexplained_since(mark)
        row_b = world._mig.row_bytes() if world._mig is not None else 0
        return {
            "entities": n,
            "devices": shards,
            "mesh": str({"shard": shards}),
            "mig_budget": budget,
            "ticks": ticks,
            "compile_plus_warm_s": round(compile_s, 2),
            "tick_ms": round(1000 * dt / ticks, 3),
            "entity_ticks_per_sec": round(n * ticks / dt, 1),
            "migrated_total": migrated,
            "mig_overflow_total": overflow,
            "mig_dropped_total": dropped,
            "row_bytes": row_b,
            # analytic wire cost of the migration collective: every
            # migrated row moves its FULL ClassState (banks + records +
            # timers + alive) once
            "migrate_collective_bytes_per_tick": (
                row_b * migrated // max(1, ticks)
            ),
            "unexplained_recompiles": len(unexplained),
            "geometry": {"width": width, "slab_h": geom.slab_h,
                         "bucket": bucket, "att_bucket": att_bucket},
            "costbook": _costbook_detail(world.costbook),
        }

    points = []
    for n in entities:
        for shards in widths:
            for budget in budgets:
                # full product at the smallest N ranks the knobs; larger
                # Ns run the headline config only (CPU wall-clock bound)
                if n != entities[0] and (shards != widths[-1]
                                         or budget != budgets[-1]):
                    continue
                points.append(point(n, shards, budget))
    best = max(points, key=lambda p: p["entity_ticks_per_sec"])
    return {
        "metric": "mesh_migrate_entity_ticks_per_sec",
        "value": best["entity_ticks_per_sec"],
        "unit": "entity-ticks/s",
        "vs_baseline": round(best["entity_ticks_per_sec"] / NORTH_STAR_RATE,
                             4),
        "detail": {
            "devices": args.mesh_migrate,
            "seed": args.seed,
            "platform": jax.devices()[0].platform,
            "engine": "unified (full-row ClassState migration)",
            "unexplained_recompiles": sum(p["unexplained_recompiles"]
                                          for p in points),
            "points": points,
        },
    }


def run_reshard(args) -> dict:
    """ISSUE 17 r10 evidence: the elastic reshard ladder.  Each point
    builds a lean migrating world on a 2-device mesh, grows it to 4 and
    drains back to 3 under continuous motion churn, and reports the
    reshard costs the live serving path pays: rebalance/exodus ticks,
    wall time per op (retrace included), rows moved, analytic collective
    bytes (full ClassState row x rows moved), and the same CostBook gate
    as the migration ladder — after the warmup mark, every recompile
    must be generation-sanctioned (``unexplained_recompiles == 0``)."""
    # NO persistent compile cache here, deliberately: jaxlib 0.4.37's
    # CPU client segfaulted (heap corruption) deserializing a CACHE HIT
    # of the exodus-armed drain executable — cold compiles run fine,
    # the second process to hit the entry died at dispatch.  The
    # ladder's compiles are single-step and cheap, so skipping
    # init_compile_cache() costs seconds and removes the landmine.
    # on the chip the ladder runs over the first 4 real devices (grow
    # targets a 4-wide mesh)
    jax = _jax_on(args.platform, args.reshard)
    if len(jax.devices()) < 4:
        raise RuntimeError("--reshard needs >=4 devices")

    import jax.numpy as jnp
    import numpy as np

    from noahgameframe_tpu.core.schema import ClassDef, ClassRegistry, prop, record
    from noahgameframe_tpu.core.store import StoreConfig, with_class
    from noahgameframe_tpu.kernel.kernel import Kernel
    from noahgameframe_tpu.kernel.module import Module
    from noahgameframe_tpu.parallel.elastic import ElasticMesh
    from noahgameframe_tpu.parallel.mesh import make_mesh
    from noahgameframe_tpu.parallel.rowmigrate import (
        RowMigrationModule,
        SpatialPlacement,
    )
    from noahgameframe_tpu.parallel.shard import ShardedKernel

    extent = 256.0

    class _Drift(Module):
        name = "drift"

        def __init__(self):
            super().__init__()
            self.add_phase("move", self._move, order=10)

        def _move(self, state, ctx):
            cs = state.classes["Npc"]
            y = jnp.mod(cs.vec[:, 0, 1] + 1.5, extent)
            return with_class(state, "Npc",
                              cs.replace(vec=cs.vec.at[:, 0, 1].set(y)))

    # capacities must split at every width visited (2, 4 and the
    # post-drain 3) — LCM 12
    caps = [int(x) for x in (args.mig_entities or "12000,60000").split(",")]
    budgets = [int(x) for x in (args.mig_budgets or "512,2048").split(",")]

    def point(cap, budget):
        if cap % 12:
            raise ValueError(f"--reshard capacities must divide by 12 "
                             f"(widths 2/4/3 are visited), got {cap}")
        reg = ClassRegistry()
        reg.define(ClassDef(name="Npc", properties=[
            prop("Id", "int"), prop("HP", "int"), prop("Position", "vector2"),
        ], records=[
            record("Bag", 3, [("item", "int"), ("weight", "float")]),
        ]))
        k = Kernel(reg, store_config=StoreConfig(
            default_capacity=cap, capacities={"Npc": cap},
            timer_slots={"Npc": 2},
        ), seed=args.seed)
        mesh = make_mesh(2)
        mig = RowMigrationModule(SpatialPlacement(
            class_name="Npc", pos_prop="Position", extent=extent,
            cell_size=8.0, width=32, n_shards=2, mig_budget=budget,
        ), mesh=mesh, order=20)
        k.build([_Drift(), mig])
        mig.bind(k)

        live = cap // 2
        rng = np.random.default_rng(args.seed)
        i32 = np.zeros((cap, 2), np.int32)
        i32[:, 0] = np.arange(cap)
        i32[:live, 1] = 100
        vec = np.zeros((cap, 1, 3), np.float32)
        vec[:live, 0, 0] = rng.uniform(1.0, extent - 1, live)
        vec[:live, 0, 1] = rng.uniform(1.0, extent - 1, live)
        alive = np.zeros(cap, bool)
        alive[:live] = True
        cs = k.state.classes["Npc"].replace(
            i32=jnp.asarray(i32), vec=jnp.asarray(vec),
            alive=jnp.asarray(alive))
        k.state = with_class(k.state, "Npc", cs)

        sk = ShardedKernel(k, mesh=mesh)
        sk.place()
        el = ElasticMesh(sk, migration=mig, ident_cols={"Npc": 0},
                         exodus_tick_bound=512)
        sk.run_device(2, fused=False)  # compile + warm at width 2
        mark = k.costbook.mark()

        def drive(begin):
            t0 = time.perf_counter()
            begin()
            for _ in range(600):
                el.poll()
                if el.inflight is None:
                    break
                sk.run_device(1, fused=False)
            assert el.inflight is None, "reshard op never settled"
            return time.perf_counter() - t0, el.ops_done[-1]

        grow_s, grow = drive(lambda: el.begin_grow(4))
        drain_s, drain = drive(lambda: el.begin_drain(1))
        unexplained = k.costbook.unexplained_since(mark)
        row_b = mig.row_bytes()
        moved = int(el.rows_moved_total)
        return {
            "capacity": cap,
            "live": live,
            "mig_budget": budget,
            "grow_wall_s": round(grow_s, 2),
            "grow_rebalance_ticks": int(grow["rebalance_ticks"]),
            "drain_wall_s": round(drain_s, 2),
            "drain_exodus_ticks": int(drain["exodus_ticks"]),
            "drained_in_budget": bool(drain["drained_in_budget"]),
            "pop_conserved": all(
                op["pop_after"] == op["pop_before"] == live
                for op in (grow, drain)),
            "rows_moved_total": moved,
            "dropped_rows": int(el.dropped_rows),
            "row_bytes": row_b,
            # analytic wire cost: every re-homed row ships its FULL
            # ClassState (banks + records + timers + alive) once
            "reshard_collective_bytes": row_b * moved,
            "unexplained_recompiles": len(unexplained),
            "costbook": _costbook_detail(k.costbook),
        }

    points = []
    for cap in caps:
        for budget in budgets:
            # full product at the smallest capacity ranks the budget
            # knob; larger rungs run the headline config only
            if cap != caps[0] and budget != budgets[-1]:
                continue
            points.append(point(cap, budget))
    head = points[-1]
    return {
        "metric": "reshard_drain_exodus_ticks",
        "value": head["drain_exodus_ticks"],
        "unit": "ticks",
        "detail": {
            "devices": args.reshard,
            "seed": args.seed,
            "platform": jax.devices()[0].platform,
            "widths_visited": [2, 4, 3],
            "all_gates": all(
                p["pop_conserved"] and p["dropped_rows"] == 0
                and p["unexplained_recompiles"] == 0 for p in points),
            "unexplained_recompiles": sum(p["unexplained_recompiles"]
                                          for p in points),
            "points": points,
        },
    }


def run_bench(args) -> dict:
    import jax

    from noahgameframe_tpu.game import build_benchmark_world
    from noahgameframe_tpu.ops.verlet import skin_from_env
    from noahgameframe_tpu.utils.platform import init_compile_cache

    init_compile_cache()
    n = args.entities
    world = build_benchmark_world(n, combat=not args.no_combat,
                                  seed=args.seed)
    _pin_fold(world, args)
    k = world.kernel

    train_k = int(getattr(args, "train", 0) or 0)
    if train_k > 1:
        # K-tick train arm (ISSUE 20): the OBSERVED tick path — every
        # per-tick lane (digests, diffs, deaths, events) fans out on the
        # host — in ceil(ticks/K) dispatches instead of one per tick.
        # tick_ms below is amortized PER TICK, so decide_tuning compares
        # it against the fused baseline directly: NF_TICK_TRAIN only
        # promotes when full observability beats the blind fused loop.
        t_c0 = time.perf_counter()
        k.configure_train(train_k)
        k.train(train_k)
        jax.block_until_ready(k.state.classes["NPC"].i32)
        compile_s = time.perf_counter() - t_c0

        d0 = k.train_dispatches
        t0 = time.perf_counter()
        k.train(args.ticks)
        jax.block_until_ready(k.state.classes["NPC"].i32)
        dt = time.perf_counter() - t0
        train_detail = {
            "tick_train": train_k,
            "train_dispatches": k.train_dispatches - d0,
            "train_ticks_timed": args.ticks,
            "train_fetch_bytes": k.train_fetch_bytes,
        }
        # the latency passes below ride run_device; warm its compile
        # outside their timed windows
        k.run_device(1, reconcile=False)
        jax.block_until_ready(k.state.classes["NPC"].i32)
    else:
        train_detail = {}
        # compile + warm up (the trip count is a traced scalar: this ONE
        # compile serves the timed loop, the single-step pass, and every
        # latency window below)
        t_c0 = time.perf_counter()
        k.run_device(args.ticks)
        jax.block_until_ready(k.state.classes["NPC"].i32)
        compile_s = time.perf_counter() - t_c0

        t0 = time.perf_counter()
        k.run_device(args.ticks)
        jax.block_until_ready(k.state.classes["NPC"].i32)
        dt = time.perf_counter() - t0

    # per-tick latency distribution on the single-step path (the latency a
    # 30 Hz world-tick loop would see; run_device amortises dispatch, the
    # single step does not).  Reuses run_device's one compiled program
    # with a trip count of 1 — a separately-compiled _trace_step
    # program would be a SECOND minute-long 1M XLA compile.
    # percentile math + sample windows live in the telemetry registry:
    # bench JSON reads the SAME histograms a /metrics scrape would
    reg = world.telemetry.registry
    lat_hist = reg.histogram(
        "nf_bench_tick_seconds", "single-dispatch tick latency"
    )
    for _ in range(max(8, min(64, args.ticks))):
        t1 = time.perf_counter()
        k.run_device(1, reconcile=False)
        jax.block_until_ready(k.state.classes["NPC"].i32)
        lat_hist.observe(time.perf_counter() - t1)
    p50, p95, p99 = _hist_pcts(lat_hist)

    # Windowed latency: the single-step numbers above include one host
    # dispatch PER TICK, which dwarfs the compute at small N.  Here each
    # sample is a fused window of `lat_k` ticks in ONE dispatch
    # (run_device), so the per-tick dispatch share is 1/lat_k; window
    # count adapts to a fixed wall budget, floor 24, cap 256.
    tick_s_est = max(1e-5, dt / args.ticks)
    if args.lat_k:
        lat_k = max(1, args.lat_k)
    else:
        # auto: window wall ≈ 1.6 s.  Trip count is a traced scalar in
        # run_device, so any lat_k reuses the one compiled program.
        lat_k = max(4, min(256, int(round(1.6 / tick_s_est))))
    # floor 24 (p95 stays meaningful, p99 ≈ max)
    n_windows = int(max(24, min(256, args.lat_budget_s / (lat_k * tick_s_est))))
    # reconcile=False: end-of-window death reconciliation is one
    # device→host fetch per class, harness cost and not the tick's.
    # One reconciling call after the loop keeps host free-lists exact.
    k.run_device(lat_k, reconcile=False)  # warm the lat_k-sized compile
    jax.block_until_ready(k.state.classes["NPC"].i32)
    dev_hist = reg.histogram(
        "nf_bench_tick_seconds_device",
        "fused-window per-tick latency (dispatch amortised over lat_k)",
    )
    for _ in range(n_windows):
        t1 = time.perf_counter()
        k.run_device(lat_k, reconcile=False)
        jax.block_until_ready(k.state.classes["NPC"].i32)
        dev_hist.observe((time.perf_counter() - t1) / lat_k)
    # Verlet cache effectiveness (NF_VERLET_SKIN > 0): lifetime counters
    # off the carried caches in state.aux — rebuilds/tick is the
    # amortization the skin bought (1.0 == rebuilt every tick).  Read
    # BEFORE the reconciling tick: if that tick observes bucket overflow
    # the combat module invalidates, which (correctly) drops the caches.
    verlet = {}
    for key, c in (getattr(k.state, "aux", None) or {}).items():
        if not key.startswith("verlet/"):
            continue
        reb = int(jax.device_get(c.rebuilds))
        reu = int(jax.device_get(c.reuses))
        verlet[key[len("verlet/"):]] = {
            "rebuilds": reb,
            "reuses": reu,
            "rebuilds_per_tick": round(reb / max(1, reb + reu), 4),
        }
    k.tick()  # reconcile host free-lists once, outside timing; also
    # fetches the on-device counter bank for the detail block below
    dp50, dp95, dp99 = _hist_pcts(dev_hist)
    grid_drop, att_drop = _overflow_gauges(world)
    # per-engine combat-fold cost attribution (combat.fold_p{0,1} in
    # detail.costbook.entries) — outside every timed region
    pallas_probe = _combat_cost_probe(world)

    ticks_per_s = args.ticks / dt
    rate = n * ticks_per_s
    dev = jax.devices()[0]
    return {
        "metric": _chip_metric("entities_ticked_per_sec", dev),
        "value": round(rate, 1),
        "unit": "entity-ticks/s",
        "vs_baseline": round(rate / NORTH_STAR_RATE, 4),
        "detail": {
            "entities": n,
            "ticks": args.ticks,
            "seed": args.seed,
            "elapsed_s": round(dt, 4),
            "compile_and_warmup_s": round(compile_s, 2),
            "ticks_per_s": round(ticks_per_s, 2),
            "tick_ms": round(1000 * dt / args.ticks, 3),
            "tick_ms_p50": p50,
            "tick_ms_p95": p95,
            "tick_ms_p99": p99,
            # windowed (dispatch-amortised) distribution — the device
            # numbers; p50 here should track tick_ms (the fused mean)
            "tick_ms_p50_device": dp50,
            "tick_ms_p95_device": dp95,
            "tick_ms_p99_device": dp99,
            "lat_windows": n_windows,
            "lat_k": lat_k,
            "device": str(dev),
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": len(jax.devices()),
            "combat": not args.no_combat,
            **train_detail,
            "grid_overflow_max": grid_drop,
            "att_overflow_max": att_drop,
            # on-device counter bank from the reconciling tick above
            "tick_counters": dict(k.last_counters),
            # elected skin, whether or not Verlet caches engaged — a run
            # is only reproducible with the same (seed, skin) pair
            "verlet_skin": skin_from_env(),
            # which combat fold engine the tick baked in (0 XLA /
            # 1 Pallas)
            **({"pallas_engine": pallas_probe.get("engine"),
                "pallas_probe": pallas_probe} if pallas_probe else {}),
            **({"verlet": verlet} if verlet else {}),
            # compiled-cost evidence: compile wall, recompiles+causes,
            # HBM peak, per-entry FLOPs/bytes (telemetry/costbook.py)
            "costbook": _costbook_detail(k.costbook),
        },
    }


def _run_session_sweep(args) -> dict:
    """--sweep-sessions: one served measurement per session count (the
    ISSUE 13 serving-edge scaling curve), each point in a SUBPROCESS so
    an OOM or wall-clock blowout at the 100k rung can't burn the smaller
    points.  With --sweep-ab every count also runs the legacy per-session
    engine first — the before/after `detail.pipeline` waterfall pair the
    r08 artifact records."""
    counts = [int(x) for x in args.sweep_sessions.split(",") if x.strip()]
    radius = 8.0 if args.interest_radius is None else args.interest_radius

    def one(sessions: int, serve_batch: bool) -> dict:
        cmd = [
            sys.executable, "-u", __file__,
            "--served", "--platform", args.platform,
            "--entities", str(args.entities), "--ticks", str(args.ticks),
            "--sessions", str(sessions), "--seed", str(args.seed),
            "--interest-radius", str(radius),
        ]
        if args.no_combat:
            cmd.append("--no-combat")
        if serve_batch:
            cmd.append("--serve-batch")
        if args.serve_overlap:
            cmd.append("--serve-overlap")
        point = {"sessions": sessions, "serve_batch": serve_batch}
        try:
            r = subprocess.run(
                cmd, capture_output=True, text=True,
                timeout=args.sweep_timeout,
            )
        except subprocess.TimeoutExpired:
            point["error"] = f"timeout after {args.sweep_timeout:.0f}s"
            return point
        for ln in reversed((r.stdout or "").strip().splitlines()):
            if ln.startswith("{"):
                try:
                    p = json.loads(ln)
                except json.JSONDecodeError:
                    break
                if p.get("error"):
                    point["error"] = p["error"]
                point["value"] = p.get("value")
                point["detail"] = p.get("detail")
                return point
        point["error"] = f"rc={r.returncode}"
        point["tail"] = (r.stderr or "").strip().splitlines()[-3:]
        return point

    points = []
    for s in counts:
        if args.sweep_ab:
            points.append(one(s, False))
        points.append(one(s, True))
    head = next(
        (p for p in points
         if p.get("serve_batch") and p.get("value") and not p.get("error")),
        None,
    )
    if head is None:
        raise RuntimeError(f"no sweep point succeeded: {points}")
    return {
        "metric": "served_session_sweep",
        "value": head["value"],
        "unit": "entity-ticks/s",
        "vs_baseline": round(head["value"] / NORTH_STAR_RATE, 4),
        "detail": {
            "entities": args.entities,
            "ticks": args.ticks,
            "seed": args.seed,
            "interest_radius": radius,
            "sweep_sessions": counts,
            "sweep_ab": bool(args.sweep_ab),
            "baseline_artifact": "r05_served_100k_2000s_cpu.json",
            "baseline_frame_ms_p99": 726.402,
            "points": points,
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    # entities/ticks default to None: each mode fills in its own size
    ap.add_argument("--entities", type=int, default=None)
    ap.add_argument("--ticks", type=int, default=None)
    ap.add_argument("--no-combat", action="store_true")
    ap.add_argument(
        "--seed", type=int, default=42,
        help="world seed for the benchmark population; recorded in the "
             "BENCH json so any run can be reproduced (or replayed) "
             "exactly",
    )
    ap.add_argument(
        "--served", action="store_true",
        help="measure the served path (tick + diff flush + fan-out) "
             "instead of the fused device loop",
    )
    ap.add_argument("--sessions", type=int, default=50)
    ap.add_argument(
        "--interest-radius", type=float, default=None,
        help="served mode: per-session interest-filtered Position "
             "streams (quantized) instead of group-wide broadcast",
    )
    ap.add_argument(
        "--serve-batch", action="store_true",
        help="served mode: the NF_SERVE_BATCH engine (vmap-over-sessions "
             "interest deltas + batched host assembly) instead of the "
             "legacy per-session loops",
    )
    ap.add_argument(
        "--serve-overlap", action="store_true",
        help="served mode: double-buffered snapshots — frame N's serve "
             "overlaps frame N+1's device tick (implies --serve-batch; "
             "bounded <=1-tick staleness)",
    )
    ap.add_argument(
        "--sweep-sessions", default=None, metavar="N,N,...",
        help="served mode: run one measurement per session count "
             "(e.g. 2000,20000,100000), each in a subprocess, and emit "
             "one combined payload with per-point detail.pipeline "
             "waterfalls",
    )
    ap.add_argument(
        "--sweep-ab", action="store_true",
        help="with --sweep-sessions: also run the legacy engine at "
             "every count (before/after waterfall pairs)",
    )
    ap.add_argument(
        "--pallas", type=int, choices=(0, 1), default=None,
        help="pin the combat fold engine for this run: 0 XLA stencil "
             "fold, 1 Pallas fold over the same tables "
             "(CombatModule.use_pallas).  Left out, the module chooses "
             "from the grid it traces",
    )
    ap.add_argument(
        "--sweep-timeout", type=float, default=900.0,
        help="per-point subprocess timeout for --sweep-sessions",
    )
    ap.add_argument(
        "--lat-k", type=int, default=0,
        help="ticks per fused window in the device-honest latency "
             "sampler (per-tick host share = one dispatch / lat-k); "
             "0 = auto-size for ~1.6 s windows",
    )
    ap.add_argument(
        "--lat-budget-s", type=float, default=20.0,
        help="wall budget for the windowed latency pass; window count "
             "adapts to it (floor 24, cap 256)",
    )
    ap.add_argument(
        "--sharded", type=int, default=0, metavar="N",
        help="run the mesh-sharded tick over N virtual CPU devices "
             "(BASELINE config-5 evidence) instead of the single-chip loop",
    )
    ap.add_argument(
        "--mesh-migrate", type=int, default=0, metavar="N",
        help="unified-engine migration ladder over N virtual CPU "
             "devices: entity count x mesh width x migration budget "
             "through the full-row ClassState migration, with a "
             "CostBook zero-unexplained-recompile gate (r09 evidence)",
    )
    ap.add_argument(
        "--reshard", type=int, default=0, metavar="N",
        help="elastic reshard ladder over N virtual CPU devices (needs "
             ">=4; with --platform tpu, over the first 4 real chips): "
             "grow 2->4 then drain->3 under motion churn, reporting "
             "rebalance/exodus ticks, reshard collective bytes and the "
             "zero-unexplained-recompile gate (r10 evidence); capacity/"
             "budget knobs reuse --mig-entities/--mig-budgets",
    )
    ap.add_argument(
        "--train", type=int, default=0, metavar="K",
        help="K-tick observed trains (NF_TICK_TRAIN): one lax.scan "
             "dispatch covers K ticks with every per-tick lane stacked "
             "[K,...] for the host.  Device-loop mode times k.train() "
             "instead of run_device().  0/1 = off",
    )
    ap.add_argument(
        "--mig-entities", default=None, metavar="N,N,...",
        help="mesh-migrate entity ladder (default 100000,1000000; the "
             "full knob product runs at the smallest count only)",
    )
    ap.add_argument(
        "--mig-widths", default=None, metavar="S,S,...",
        help="mesh-migrate mesh widths in shards (default 2,4,8 "
             "clipped to --mesh-migrate)",
    )
    ap.add_argument(
        "--mig-budgets", default=None, metavar="B,B,...",
        help="mesh-migrate per-direction row budgets (default 2048,8192)",
    )
    ap.add_argument(
        "--mig-ticks", type=int, default=10,
        help="timed ticks per mesh-migrate point (after a 2-tick warmup)",
    )
    ap.add_argument(
        "--platform",
        choices=("tpu", "cpu"),
        default=None,
        help="tpu (the default): fail unless jax's first device is a "
             "TPU.  cpu: an explicit rehearsal on the CPU backend, for "
             "tests.  --sharded and --mesh-migrate always run on "
             "virtual CPU devices, as their own help says",
    )
    args = ap.parse_args()
    explicit_tpu = args.platform == "tpu"
    args.platform = args.platform or "tpu"

    if args.served and args.sweep_sessions:
        # the sweep parent never touches jax — every point is a child
        # that holds the device (chip or CPU) alone, one at a time
        if args.entities is None:
            args.entities = 100_000
        if args.ticks is None:
            args.ticks = 8
        _emit(_run_session_sweep(args))
        return

    if args.reshard:
        if args.platform != "tpu" and args.reshard < 4:
            ap.error("--reshard runs on N>=4 virtual CPU devices (with "
                     "--platform cpu) or on real chips: the ladder grows "
                     "to a 4-wide mesh")
        _emit(run_reshard(args))
        return

    if args.mesh_migrate:
        _emit(run_mesh_migrate(args))
        return

    if args.sharded:
        if args.served:
            ap.error("--sharded measures the fused device loop; combining "
                     "it with --served is not supported")
        if explicit_tpu:
            ap.error("--sharded runs on N virtual CPU devices; it cannot "
                     "be combined with --platform tpu (chip_smoke.py "
                     "--chips 4 drives the real mesh)")
        if args.entities is None:
            args.entities = 512_000
        if args.ticks is None:
            args.ticks = 30
        _emit(run_sharded(args))
        return

    _jax_on(args.platform)
    if args.entities is None:
        args.entities = 1_000_000
    if args.ticks is None:
        args.ticks = 90

    # apply measured A/B winners (scripts/decide_tuning.py ->
    # bench_runs/tuning.json) on the chip; explicit env vars still
    # override via setdefault.  CPU rehearsals keep defaults — the
    # tuning was measured on chip and does not transfer.
    tuning_applied = {}
    if args.platform == "tpu":
        tpath = os.path.join(os.path.dirname(__file__), "bench_runs",
                             "tuning.json")
        try:
            with open(tpath) as f:
                for k, v in (json.load(f).get("env") or {}).items():
                    if os.environ.setdefault(k, str(v)) == str(v):
                        tuning_applied[k] = str(v)
        except (OSError, json.JSONDecodeError, AttributeError):
            pass

    payload = run_served(args) if args.served else run_bench(args)
    if tuning_applied:
        payload.setdefault("detail", {})["tuning_applied"] = tuning_applied
    _emit(payload)


if __name__ == "__main__":
    main()
