"""Spatially-sharded combat core (parallel/spatial.py): slab partition,
halo exchange, budgeted cross-shard migration.

Parity oracle: `reference_step` — the same movement/duty math over the
single-device square-grid fold (game.combat.combat_fold_xla).  Within
budgets the two paths must produce bit-identical positions and HP."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from noahgameframe_tpu.parallel.spatial import (
    SpatialGeom,
    SpatialWorld,
    reference_step,
)


def _mk_world(n=1500, seed=3, **over):
    geom_kw = dict(
        extent=128.0, cell_size=4.0, width=32, n_shards=4,
        bucket=24, att_bucket=24, radius=4.0, mig_budget=512,
        speed=1.0, attack_period=3,
    )
    geom_kw.update(over)
    geom = SpatialGeom(**geom_kw)
    rng = np.random.default_rng(seed)
    pos = rng.uniform(1.0, geom.extent - 1.0, (n, 2)).astype(np.float32)
    hp = np.full(n, 1000, np.int32)
    atk = rng.integers(5, 20, n).astype(np.int32)
    camp = (np.arange(n) % 2).astype(np.int32)
    return geom, pos, hp, atk, camp


def _run_reference(geom, pos, hp, atk, camp, ticks):
    n = pos.shape[0]
    gid = jnp.arange(n, dtype=jnp.int32)
    active = jnp.ones(n, bool)
    posj = jnp.asarray(pos)
    hpj = jnp.asarray(hp)
    diedj = jnp.full(n, -1, jnp.int32)
    atkj = jnp.asarray(atk)
    campj = jnp.asarray(camp)
    step = jax.jit(
        lambda p, h, dd, t: reference_step(
            geom, p, h, atkj, campj, gid, dd, active, t
        )
    )
    for t in range(ticks):
        posj, hpj, diedj = step(posj, hpj, diedj, jnp.int32(t))
    return np.asarray(posj), np.asarray(hpj)


def test_spatial_matches_single_device():
    """20 ticks of movement + combat: every gid's position and HP match
    the single-device engine bit-for-bit, and rows really migrated."""
    geom, pos, hp, atk, camp = _mk_world()
    ticks = 20

    world = SpatialWorld(geom)
    world.place(pos, hp, atk, camp)
    migrated_total = 0
    for _ in range(ticks):
        world.step()
        migrated_total += int(world.stats_last[:, 0].sum())
        # generous budgets: nothing may overflow or drop
        assert world.stats_last[:, 1:].sum() == 0, world.stats_last

    ref_pos, ref_hp = _run_reference(geom, pos, hp, atk, camp, ticks)

    got = world.gather()
    assert len(got) == pos.shape[0]
    for gid_, (x, y, hp_) in got.items():
        assert hp_ == int(ref_hp[gid_]), f"gid {gid_} hp"
        np.testing.assert_array_equal(
            np.float32([x, y]), ref_pos[gid_], err_msg=f"gid {gid_} pos"
        )
    # the walk at speed 1.0 over 20 ticks must cross slab boundaries
    assert migrated_total > 20, migrated_total
    # and combat must actually have landed damage
    damaged = sum(1 for _, (_, _, h) in got.items() if h < 1000)
    assert damaged > len(got) * 0.5


def test_spatial_halo_crosses_slab_boundary():
    """Two enemies straddling a slab boundary within radius damage each
    other even though they live on different shards (speed 0 => no
    migration could have brought them together)."""
    geom = SpatialGeom(
        extent=64.0, cell_size=4.0, width=16, n_shards=2,
        bucket=8, att_bucket=8, radius=4.0, mig_budget=8,
        speed=0.0, attack_period=1,
    )
    # slab boundary at y = 8 cells * 4.0 = 32.0
    pos = np.float32([[10.0, 31.0], [10.0, 33.0]])
    hp = np.int32([100, 100])
    atk = np.int32([7, 9])
    camp = np.int32([0, 1])
    world = SpatialWorld(geom)
    world.place(pos, hp, atk, camp)
    # placement: one row per slab
    st = jax.tree.map(np.asarray, world.state)
    owners = {int(st.gid[r]) for r in np.flatnonzero(st.active)
              if r < world.bank_size}
    assert owners == {0}, "gid 0 should live on shard 0"
    world.step(3)
    got = world.gather()
    assert got[0][2] == 100 - 3 * 9, got  # hit by gid 1 across the halo
    assert got[1][2] == 100 - 3 * 7, got
    assert world.stats_last[:, 1:].sum() == 0


def test_spatial_migration_budget_overflow_counts():
    """A starved migration budget must not crash or corrupt the world:
    overflow rows are counted, stay home, retry — and the runtime ALERTS
    (log + counter), it doesn't just expose a bench counter."""
    geom, pos, hp, atk, camp = _mk_world(n=800, mig_budget=1, speed=2.0)
    world = SpatialWorld(geom)
    world.place(pos, hp, atk, camp)
    overflow_seen = 0
    for _ in range(10):
        world.step()
        overflow_seen += int(world.stats_last[:, 1].sum())
    got = world.gather()
    # nothing lost: every entity still exists exactly once
    assert len(got) == 800
    assert overflow_seen > 0, "budget of 1 should have overflowed"
    assert world.overflow_alerts > 0, "breach must raise the alert counter"


def _teleport_gid(world, g, xy):
    """Host-side surgery: move gid g's bank row to world position xy."""
    st = world.state
    act = np.asarray(st.active)
    gids = np.asarray(st.gid)
    r = next(int(i) for i in np.flatnonzero(act) if int(gids[i]) == g)
    newpos = np.asarray(st.pos).copy()
    newpos[r] = xy
    world.state = st._replace(pos=jax.device_put(
        jnp.asarray(newpos), st.pos.sharding
    ))


def test_spatial_bank_full_row_retries_never_destroyed():
    """Migration-loss regression: a migrant whose destination bank has no
    free slot STAYS HOME and retries next tick — the sender clamps to the
    destination's advertised free-slot count, so no row is ever cleared
    from its source bank without a slot waiting.  mig_dropped is now a
    should-never-fire assertion counter."""
    geom = SpatialGeom(
        extent=64.0, cell_size=4.0, width=16, n_shards=2,
        bucket=64, att_bucket=8, radius=4.0, mig_budget=64,
        speed=0.0, attack_period=97,
    )
    # 2 rows in slab 0 (bank 8: room to spare), 8 rows in slab 1 (bank
    # exactly FULL).  Teleporting a slab-0 row into slab 1 makes it want
    # to migrate into a full bank.
    rng = np.random.default_rng(0)
    pos = np.vstack([
        rng.uniform([1, 1], [62, 30], (2, 2)),    # slab 0
        rng.uniform([1, 33], [62, 62], (8, 2)),   # slab 1 — fills bank 1
    ]).astype(np.float32)
    hp = np.full(10, 100, np.int32)
    atk = np.full(10, 5, np.int32)
    camp = (np.arange(10) % 2).astype(np.int32)
    world = SpatialWorld(geom, bank_size=8)
    world.place(pos, hp, atk, camp)
    _teleport_gid(world, 0, [10.0, 50.0])  # wants slab 1 (full)
    world.step()
    # destination full: clamped (mig_overflow), still awaiting retry
    # (misplaced), NOT destroyed, and the assertion counter is silent
    assert world.stats_last[:, 2].sum() == 0, world.stats_last
    assert world.stats_last[:, 1].sum() == 1, world.stats_last
    assert world.stats_last[:, 3].sum() == 1, world.stats_last
    assert len(world.gather()) == 10
    # free a slot on shard 1 by moving one of its rows into slab 0; the
    # stranded row's retry then succeeds (capacity is advertised before
    # a shard's own outbound clearing, so the slot is visible one tick
    # after it frees)
    _teleport_gid(world, 2, [10.0, 10.0])
    world.step()   # gid 2 migrates down; gid 0 still blocked this tick
    assert world.stats_last[:, 0].sum() == 1, world.stats_last
    assert world.stats_last[:, 2].sum() == 0, world.stats_last
    world.step()   # retry lands: gid 0 migrates into the freed slot
    assert world.stats_last[:, 0].sum() == 1, world.stats_last
    assert world.stats_last[:, 1:4].sum() == 0, world.stats_last
    got = world.gather()
    assert len(got) == 10
    # every gid exists exactly once and gid 0 kept its position
    assert got[0][:2] == (10.0, 50.0), got[0]


def test_spatial_stranded_row_hops_home():
    """A row teleported 3 slabs from its owner reaches it by hopping one
    slab per tick (migration selects by direction of travel, not exact
    neighbor) and resumes combat — never permanently stranded."""
    geom = SpatialGeom(
        extent=64.0, cell_size=4.0, width=16, n_shards=4,
        bucket=8, att_bucket=8, radius=4.0, mig_budget=8,
        speed=0.0, attack_period=1,
    )
    # gid 0 placed in slab 0, then teleported to slab 3 next to gid 1
    # (an enemy); gid 2 keeps slab 0 non-empty
    pos = np.float32([[10.0, 2.0], [10.0, 60.0], [20.0, 2.0]])
    hp = np.int32([100, 100, 100])
    atk = np.int32([5, 5, 5])
    camp = np.int32([0, 1, 0])
    world = SpatialWorld(geom)
    world.place(pos, hp, atk, camp)
    st = world.state
    newpos = np.asarray(st.pos).copy()
    rows0 = np.flatnonzero(np.asarray(st.active)[: world.bank_size])
    g0 = next(r for r in rows0 if int(np.asarray(st.gid)[r]) == 0)
    newpos[g0] = [10.0, 58.0]  # slab 3, within radius of gid 1
    world.state = st._replace(pos=jax.device_put(
        jnp.asarray(newpos), st.pos.sharding
    ))
    hops = []
    for _ in range(4):
        world.step()
        hops.append(int(world.stats_last[:, 0].sum()))
    # 3 hops (slab 0->1->2->3), then settled
    assert hops[:3] == [1, 1, 1] and hops[3] == 0, hops
    got = world.gather()
    # all three rows still exist; gids 0 and 1 traded damage once they
    # shared slab 3 (the first post-arrival tick)
    assert len(got) == 3
    assert got[0][2] < 100 and got[1][2] < 100, got
    assert got[2][2] == 100


def test_spatial_checkpoint_resume_continues_exactly(tmp_path):
    """save -> load -> keep ticking reproduces the uncheckpointed
    trajectory bit-for-bit (movement and duty are pure functions of
    (gid, tick), so a resumed world cannot drift)."""
    geom, pos, hp, atk, camp = _mk_world(n=500)
    w1 = SpatialWorld(geom)
    w1.place(pos, hp, atk, camp)
    w1.step(7)
    ckpt = str(tmp_path / "spatial.npz")
    w1.save(ckpt)
    w1.step(8)
    expect = w1.gather()

    w2 = SpatialWorld(geom)
    w2.load(ckpt)
    assert w2.tick_count == 7
    w2.step(8)
    assert w2.gather() == expect


def test_spatial_speed_zero_is_migration_free():
    geom, pos, hp, atk, camp = _mk_world(n=300, speed=0.0)
    world = SpatialWorld(geom)
    world.place(pos, hp, atk, camp)
    for _ in range(5):
        world.step()
        assert world.stats_last[:, 0].sum() == 0


def test_spatial_life_cycle_parity():
    """With the full phase chain on (combat + regen + death + respawn),
    entities die and revive while migrating across shards — HP stays
    parity-exact with the single-device oracle."""
    geom, pos, hp, atk, camp = _mk_world(
        n=600, speed=1.0, attack_period=2,
        regen_per_tick=1, hp_max=60, respawn_ticks=5,
    )
    hp = np.full_like(hp, 60)
    ticks = 60
    world = SpatialWorld(geom)
    world.place(pos, hp, atk, camp)
    for _ in range(ticks):
        world.step()
        assert world.stats_last[:, 1:].sum() == 0, world.stats_last
    ref_pos, ref_hp = _run_reference(geom, pos, hp, atk, camp, ticks)
    got = world.gather()
    mismatch = [g for g, (_, _, h) in got.items() if h != int(ref_hp[g])]
    assert not mismatch, mismatch[:5]
    # the chain actually cycled: some rows are dead right now, some are
    # back at full health having died earlier
    dead_now = sum(1 for _, (_, _, h) in got.items() if h == 0)
    assert dead_now > 0, "nothing died - config not lethal enough"
    st = jax.tree.map(np.asarray, world.state)
    revived = ((st.died == -1) & (st.hp == 60) & st.active).sum()
    assert revived > 0


def test_spatial_soak_conserves_entities():
    """120 ticks of fast movement with a moderate budget: entities churn
    across shards continuously but the population is conserved — every
    gid exists exactly once, none duplicated, none lost — and HP stays
    parity-exact with the single-device oracle (the budget never
    overflows at this rate, so the worlds stay identical)."""
    # buckets sized for 120 ticks of density drift: ANY cell-bucket drop
    # breaks parity (the dropped SET depends on within-cell order, which
    # differs between the paths), so the guard below asserts zero drops
    # — zero spatial drops implies zero reference drops (same cell
    # populations, same bucket)
    geom, pos, hp, atk, camp = _mk_world(
        n=900, speed=1.5, mig_budget=256, bucket=48, att_bucket=48
    )
    ticks = 120
    world = SpatialWorld(geom)
    world.place(pos, hp, atk, camp)
    migrated = 0
    for _ in range(ticks):
        world.step()
        migrated += int(world.stats_last[:, 0].sum())
        assert world.stats_last[:, 1:].sum() == 0, world.stats_last
    st = jax.tree.map(np.asarray, world.state)
    gids = st.gid[st.active]
    assert len(gids) == 900
    assert len(np.unique(gids)) == 900, "duplicated or lost gid"
    assert migrated > ticks, migrated  # sustained churn
    ref_pos, ref_hp = _run_reference(geom, pos, hp, atk, camp, ticks)
    got = world.gather()
    mismatches = [g for g, (_, _, h) in got.items() if h != int(ref_hp[g])]
    assert not mismatches, mismatches[:5]


def test_spatial_single_shard_degenerate():
    """n_shards=1: self-permutes, no real neighbors, halos masked to
    zero — combat still lands and nothing migrates or overflows."""
    geom = SpatialGeom(
        extent=64.0, cell_size=4.0, width=16, n_shards=1,
        bucket=16, att_bucket=16, radius=4.0, mig_budget=8,
        speed=1.0, attack_period=2,
    )
    rng = np.random.default_rng(1)
    n = 300
    world = SpatialWorld(geom)
    world.place(
        rng.uniform(1, 63, (n, 2)).astype(np.float32),
        np.full(n, 100, np.int32), np.full(n, 7, np.int32),
        (np.arange(n) % 2).astype(np.int32),
    )
    world.step(10)
    got = world.gather()
    assert len(got) == n
    assert sum(1 for _, (_, _, h) in got.items() if h < 100) > n // 2
    assert world.stats_last.sum() == 0


def test_spatial_auto_resize_stops_bucket_drops():
    """SpatialGeom twin of CombatModule's overflow auto-resize: a pile-up
    in one cell with bucket 1 breaches the budget, both buckets double
    (bounded) with a retrace, and the drops actually STOP."""
    geom = SpatialGeom(
        extent=128.0, cell_size=16.0, width=8, n_shards=2,
        bucket=1, att_bucket=1, radius=4.0, mig_budget=64,
        speed=0.0, attack_period=1,
    )
    n = 64
    rng = np.random.default_rng(21)
    # everyone inside ONE cell (same slab), zero speed: pure pile-up
    pos = rng.uniform(33.0, 40.0, (n, 2)).astype(np.float32)
    hp = np.full(n, 100000, np.int32)
    atk = np.ones(n, np.int32)
    camp = (np.arange(n) % 2).astype(np.int32)
    world = SpatialWorld(geom)
    world.max_bucket_boost = 256
    world.place(pos, hp, atk, camp)
    for _ in range(20):
        world.step()
        if world.geom.bucket >= n:
            break
    assert world._bucket_boost > 1, "budget breach never resized"
    assert world.geom.bucket >= n and world.geom.att_bucket >= n
    assert world.overflow_alerts >= 1
    world.step()
    world.step()
    assert world.stats_last[:, 4:].sum() == 0, world.stats_last


def test_spatial_auto_resize_disabled_keeps_geometry():
    geom = SpatialGeom(
        extent=128.0, cell_size=16.0, width=8, n_shards=2,
        bucket=1, att_bucket=1, radius=4.0, mig_budget=64,
        speed=0.0, attack_period=1,
    )
    n = 32
    pos = np.random.default_rng(22).uniform(
        33.0, 40.0, (n, 2)).astype(np.float32)
    world = SpatialWorld(geom)
    world.auto_resize = False
    world.place(pos, np.full(n, 10000, np.int32),
                np.ones(n, np.int32), (np.arange(n) % 2).astype(np.int32))
    for _ in range(4):
        world.step()
    assert world.geom.bucket == 1 and world._bucket_boost == 1
    assert world.stats_last[:, 4:].sum() > 0  # drops persist, by choice


def test_spatial_snapshot_cross_engine_drops_verlet_cache(tmp_path):
    """A snapshot that says another build wrote it (`binning` present
    and not "sort": a file from before that build was deleted, whose
    vc_skey holds per-row keys) loads with its Verlet-cache leaves
    zeroed, forcing a first-tick rebuild — and the resumed trajectory
    stays bit-identical to an unbroken run."""
    geom, pos, hp, atk, camp = _mk_world(n=400, seed=12, n_shards=2,
                                         cell_size=8.0, width=16,
                                         radius=4.0, speed=0.1, skin=4.0)
    world = SpatialWorld(geom)
    world.place(pos, hp, atk, camp)
    world.step(6)
    p = str(tmp_path / "snap.npz")
    world.save(p)
    # unbroken oracle
    world.step(6)
    ref = world.gather()

    with np.load(p) as z:
        assert "binning" not in z.files  # one build: nothing to record
        foreign = {f: z[f] for f in z.files}
    foreign["binning"] = "count"
    p_count = str(tmp_path / "snap_count.npz")
    np.savez_compressed(p_count, **foreign)
    w2 = SpatialWorld(geom)
    w2.load(p_count)
    # the anchor must be fully invalidated
    assert not np.asarray(w2.state.vc_active).any()
    w2.step(6)
    got = w2.gather()
    assert ref.keys() == got.keys()
    for g, (x, y, hp_) in ref.items():
        cx, cy, chp = got[g]
        assert hp_ == chp, f"gid {g} hp"
        np.testing.assert_array_equal(np.float32([x, y]),
                                      np.float32([cx, cy]))

    # this build's own file (and an older one that says "sort") keeps
    # the cache: the cheap path stays cheap
    foreign["binning"] = "sort"
    p_sort = str(tmp_path / "snap_sort.npz")
    np.savez_compressed(p_sort, **foreign)
    for path in (p, p_sort):
        w3 = SpatialWorld(geom)
        w3.load(path)
        assert np.asarray(w3.state.vc_active).any()
