"""Sharded-tick tests on the virtual 8-device CPU mesh (conftest sets
XLA_FLAGS=--xla_force_host_platform_device_count=8)."""

import jax
import numpy as np
import pytest

from noahgameframe_tpu.game import GameEvent, GameWorld, WorldConfig
from noahgameframe_tpu.parallel import (
    ShardedKernel,
    make_mesh,
    shard_rows_by_cell,
    world_shardings,
)

N_DEV = 8


@pytest.fixture()
def world():
    w = GameWorld(
        WorldConfig(
            npc_capacity=256,
            player_capacity=64,
            extent=64.0,
            attack_period_s=1.0 / 30.0,
        )
    )
    w.start()
    w.scene.create_scene(1, width=64.0)
    w.seed_npcs(200, camps=2)
    return w


def test_make_mesh():
    mesh = make_mesh(N_DEV)
    assert mesh.devices.size == N_DEV


def test_world_shardings_structure(world):
    mesh = make_mesh(N_DEV)
    sh = world_shardings(world.kernel.state, mesh)
    npc = sh.classes["NPC"]
    assert npc.i32.spec == jax.sharding.PartitionSpec("shard")
    assert sh.tick.spec == jax.sharding.PartitionSpec()


def test_sharded_tick_matches_single_device(world):
    """Golden test: the sharded world tick must be bit-identical to the
    single-device tick (same seed, same phases)."""
    # single-device run
    ref = GameWorld(
        WorldConfig(
            npc_capacity=256,
            player_capacity=64,
            extent=64.0,
            attack_period_s=1.0 / 30.0,
        )
    )
    ref.start()
    ref.scene.create_scene(1, width=64.0)
    ref.seed_npcs(200, camps=2)
    for _ in range(40):
        ref.tick()

    sk = ShardedKernel(world.kernel, n_devices=N_DEV)
    sk.place()
    for _ in range(40):
        sk.tick()

    a = world.kernel.state.classes["NPC"]
    b = ref.kernel.state.classes["NPC"]
    np.testing.assert_array_equal(np.asarray(a.i32), np.asarray(b.i32))
    np.testing.assert_allclose(np.asarray(a.vec), np.asarray(b.vec), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(a.alive), np.asarray(b.alive))


def test_sharded_run_device(world):
    sk = ShardedKernel(world.kernel, n_devices=N_DEV)
    sk.place()
    sk.run_device(35)
    hp = np.asarray(world.kernel.store.column(world.kernel.state, "NPC", "HP"))
    alive = np.asarray(world.kernel.state.classes["NPC"].alive)
    assert alive.sum() == 200
    assert (hp[alive] < 100).any()  # combat happened across shards


def test_sharded_events_still_fire(world):
    sk = ShardedKernel(world.kernel, n_devices=N_DEV)
    sk.place()
    killed = []
    world.kernel.events.subscribe_batch(
        int(GameEvent.ON_OBJECT_BE_KILLED), lambda c, m, p: killed.append(int(m.sum()))
    )
    for _ in range(40):
        sk.tick()
    assert sum(killed) > 0


def test_capacity_divisibility_check():
    # a LARGE non-divisible class still errors (silent replication of a
    # real entity bank would be a perf surprise)...
    w = GameWorld(WorldConfig(npc_capacity=8191))
    w.start()
    with pytest.raises(ValueError):
        ShardedKernel(w.kernel, n_devices=8)
    # ...but small control-plane classes replicate (with a warning)
    # instead of blocking the mesh — a 16-device dryrun must not fail on
    # IObject capacity 8 — and the mixed replicated+sharded world must
    # actually TICK, not just construct
    w2 = GameWorld(WorldConfig(npc_capacity=96, player_capacity=64))
    w2.start()
    w2.scene.create_scene(1, width=64.0)
    w2.seed_npcs(48)
    with pytest.warns(UserWarning, match="REPLICATED"):
        sk = ShardedKernel(w2.kernel, n_devices=3)
    assert "IObject" in sk.replicated_classes
    assert "Player" in sk.replicated_classes  # 64 % 3 != 0, small
    assert "NPC" not in sk.replicated_classes  # 96 % 3 == 0, sharded
    sk.place()
    sk.run_device(3)
    alive = np.asarray(w2.kernel.state.classes["NPC"].alive)
    assert alive.sum() == 48


def test_shard_rows_by_cell():
    cell = np.asarray([3, 1, 3, 0, 1, 2])
    order = shard_rows_by_cell(6, 2, cell)
    assert (np.sort(cell[order]) == cell[order]).all()


def test_sharded_large_world_uneven_aliveness():
    """Round-2 verdict item 9: a >=64k-capacity sharded world with
    aliveness concentrated on a few shards (non-uniform row occupancy)
    must tick correctly and preserve combat/diff semantics."""
    w = GameWorld(
        WorldConfig(
            npc_capacity=65536,
            player_capacity=64,
            extent=256.0,
            attack_period_s=1.0 / 30.0,
            middleware=False,
        )
    )
    w.start()
    w.scene.create_scene(1, width=256.0)
    # 12k alive entities: rows are allocated densely from 0, so with
    # capacity 64k over 8 shards only the first ~1.5 shards hold live
    # rows — the worst-case imbalance for per-shard work
    w.seed_npcs(12_000, camps=2)
    sk = ShardedKernel(w.kernel, n_devices=N_DEV)
    sk.place()
    sk.run_device(35)
    hp = np.asarray(w.kernel.store.column(w.kernel.state, "NPC", "HP"))
    alive = np.asarray(w.kernel.state.classes["NPC"].alive)
    assert alive.sum() == 12_000
    assert (hp[alive] < 100).any()  # combat still lands
    # dead region stayed dead
    assert not alive[12_000:].any()


@pytest.mark.parametrize("movement", [False, True])
def test_sharded_combat_parity_across_shards(movement):
    """Cross-shard combat parity: entities intermingled at the same
    coordinates but placed on DIFFERENT shards must resolve identical
    damage to the single-device run (the collective path carries the
    cell-table across shard boundaries).  The movement=True variant has
    entities crossing cell (and shard-locality) boundaries every tick —
    the sharded global sort/scatter must stay bit-identical under
    churn, not just for a static layout."""

    def build():
        w = GameWorld(
            WorldConfig(
                npc_capacity=512,
                player_capacity=64,
                extent=64.0,
                attack_period_s=1.0 / 30.0,
                movement=movement,
                regen=False,
                middleware=False,
            )
        )
        w.start()
        w.scene.create_scene(1, width=64.0)
        # interleaved camps at close quarters; row i and row i+1 land on
        # different shards once the 512 rows split 64-per-shard
        rng = np.random.RandomState(5)
        pos = rng.uniform(0, 64.0, (400, 2)).astype(np.float32)
        k = w.kernel
        values = {
            "SceneID": [1] * 400,
            "GroupID": [0] * 400,
            "Position": [(float(x), float(y), 0.0) for x, y in pos],
            "HP": [300] * 400,
            "Camp": [i % 2 for i in range(400)],
        }
        k.state, guids, rows = k.store.create_many(k.state, "NPC", 400, values=values)
        from noahgameframe_tpu.game.defines import COMM_PROPERTY_RECORD, PropertyGroup

        k.state = k.store.record_write_rows(
            k.state, "NPC", rows, COMM_PROPERTY_RECORD,
            int(PropertyGroup.EFFECTVALUE),
            {"MAXHP": [300] * 400, "ATK_VALUE": [9] * 400, "DEF_VALUE": [2] * 400},
        )
        w.combat.arm_all()
        return w

    ref = build()
    for _ in range(8):
        ref.tick()

    w = build()
    sk = ShardedKernel(w.kernel, n_devices=N_DEV)
    sk.place()
    for _ in range(8):
        sk.tick()

    a = np.asarray(w.kernel.store.column(w.kernel.state, "NPC", "HP"))
    b = np.asarray(ref.kernel.store.column(ref.kernel.state, "NPC", "HP"))
    np.testing.assert_array_equal(a, b)
    la = np.asarray(w.kernel.store.column(w.kernel.state, "NPC", "LastAttacker"))
    lb = np.asarray(ref.kernel.store.column(ref.kernel.state, "NPC", "LastAttacker"))
    np.testing.assert_array_equal(la, lb)
    if movement:
        pa = np.asarray(w.kernel.state.classes["NPC"].vec)
        pb = np.asarray(ref.kernel.state.classes["NPC"].vec)
        np.testing.assert_array_equal(pa, pb)


def test_sharded_world_checkpoint_roundtrip(tmp_path):
    """Config-5 operations: a mesh-sharded world checkpoints and resumes
    bit-identically (save gathers the sharded banks; the resumed world
    re-places onto a mesh and keeps ticking)."""
    import numpy as np

    from noahgameframe_tpu.game import build_benchmark_world
    from noahgameframe_tpu.parallel import ShardedKernel
    from noahgameframe_tpu.persist.checkpoint import load_world, save_world

    w = build_benchmark_world(2000, seed=3)
    sk = ShardedKernel(w.kernel, n_devices=8)
    sk.place()
    sk.run_device(10)
    save_world(w.kernel, tmp_path, modules=w.all_modules)
    ref = np.asarray(w.kernel.state.classes["NPC"].i32)

    w2 = build_benchmark_world(2000, seed=99)
    load_world(w2.kernel, tmp_path, modules=w2.all_modules)
    np.testing.assert_array_equal(
        np.asarray(w2.kernel.state.classes["NPC"].i32), ref
    )
    sk2 = ShardedKernel(w2.kernel, n_devices=8)
    sk2.place()
    sk2.run_device(5)  # resumed world re-shards and keeps ticking


def test_sharded_kernel_drops_traces_on_invalidate(world):
    """Trace-generation sync: kernel.invalidate() (bucket resize, phase
    swap) must flush the ShardedKernel's jit caches too, else the mesh
    keeps ticking the STALE program — CombatModule's overflow auto-resize
    would silently never take effect under a mesh."""
    sk = ShardedKernel(world.kernel, n_devices=N_DEV)
    sk.place()
    sk.tick()
    f_step = sk._jit_step
    assert f_step is not None
    world.kernel.invalidate()
    sk.tick()
    assert sk._jit_step is not None and sk._jit_step is not f_step
    # run_device syncs the same way
    sk.run_device(2)
    f_run = sk._jit_run
    world.kernel.set_phases(world.kernel.phases)
    sk.run_device(2)
    assert sk._jit_run is not f_run


def test_sharded_combat_overflow_resize_takes_effect():
    """End to end under the mesh: everyone piled into one cell with a
    bucket of 1 overflows; CombatModule doubles the bucket + invalidates
    (the doubling alone: the second level is held off here),
    the generation sync retraces the SHARDED tick, and the drops stop —
    the r05 capture showed grid_overflow_max=374 silently dropped because
    the old mesh kept its stale trace."""
    w = GameWorld(WorldConfig(
        combat=True, movement=False, regen=False, middleware=False,
        npc_capacity=64, player_capacity=8, extent=64.0,
        aoe_radius=8.0, aoi_bucket=1,
        attack_period_s=1.0 / 30.0, respawn_s=1e6,
    )).start()
    w.scene.create_scene(1)
    w.seed_npcs(32)
    k = w.kernel
    host = k.store._hosts["NPC"]
    for row in np.flatnonzero(host.alloc_mask):
        k.set_property(host.row_guid[int(row)], "Position",
                       (10.0, 10.0, 0.0))
    c = w.combat
    assert c.auto_resize
    c.max_bucket_boost = 64  # headroom for 32 piled into bucket 1
    c.SPILL_MIN_OVERDEPTH = 1 << 20
    sk = ShardedKernel(k, n_devices=N_DEV)
    sk.place()
    for _ in range(20):
        sk.tick()
        if c._bucket_boost >= 32:
            break
    assert c._bucket_boost >= 32, "mesh never picked up the resize"
    assert c.overflow_alerts >= 1
    # the grown bucket holds all 32 entities: the overflow event stops
    # firing, so the running total freezes (overflow_last is reset by the
    # GameWorld.tick module-execute loop, which sk.tick() bypasses)
    sk.tick()
    before = c.overflow_total
    sk.tick()
    sk.tick()
    assert c.overflow_total == before
