"""One engine (ISSUE 15): full-row ClassState migration parity gates.

Three contracts of the unified mesh engine:

1. the six-column combat workload through the unified ``SpatialWorld``
   is bit-identical to the single-device parity oracle on a 1-shard
   AND an in-process 8-device mesh (120-tick soak marked slow; a short
   tier-1 slice always runs),
2. a FULL-store workload — property banks, a record page, the TimerState
   triple — survives forced cross-shard migration with per-tick
   placement-invariant digest parity against a single-shard control
   that never migrates,
3. a pre-unification slab snapshot (no ``layout`` marker) loads into
   the unified engine: caches dropped, banks intact, trajectory
   unchanged.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from noahgameframe_tpu.core.schema import ClassDef, ClassRegistry, prop, record
from noahgameframe_tpu.core.store import StoreConfig, with_class
from noahgameframe_tpu.kernel.kernel import Kernel
from noahgameframe_tpu.kernel.module import Module
from noahgameframe_tpu.parallel.mesh import make_mesh
from noahgameframe_tpu.parallel.rowmigrate import (
    RowMigrationModule,
    SpatialPlacement,
    canonical_digest,
)
from noahgameframe_tpu.parallel.shard import ShardedKernel
from noahgameframe_tpu.parallel.spatial import (
    SpatialGeom,
    SpatialWorld,
    reference_step,
)

EXTENT = 64.0
CAP = 64
N_LIVE = 32


class _Drift(Module):
    """Deterministic full-store churn: every live row drifts +3 cells/tick
    in y (wrapping, so rows stream across every slab boundary) and stamps
    id-derived values into its record page and timer banks — content that
    MUST ride migration bit-exactly for the digests to agree."""

    name = "drift"

    def __init__(self):
        super().__init__()
        self.add_phase("move", self._move, order=10)
        self.add_phase("mark", self._mark, order=15)

    def _move(self, state, ctx):
        cs = state.classes["Npc"]
        y = jnp.mod(cs.vec[:, 0, 1] + 3.0, EXTENT)
        return with_class(state, "Npc",
                          cs.replace(vec=cs.vec.at[:, 0, 1].set(y)))

    def _mark(self, state, ctx):
        cs = state.classes["Npc"]
        ident = cs.i32[:, 0]
        live = cs.alive
        add = jnp.where(live, ident, 0)
        bag = cs.records["Bag"]
        bag = bag.replace(
            i32=bag.i32 + add[:, None, None],
            f32=bag.f32 + add[:, None, None].astype(jnp.float32) * 0.5,
            used=bag.used | live[:, None],
        )
        tm = cs.timers
        tm = tm.replace(
            next_fire=tm.next_fire + jnp.where(live, 1, 0)[:, None],
            remain=tm.remain + add[:, None],
        )
        return with_class(
            state, "Npc",
            cs.replace(records={**cs.records, "Bag": bag}, timers=tm),
        )


def _mk_world(n_shards: int):
    reg = ClassRegistry()
    reg.define(ClassDef(name="Npc", properties=[
        prop("Id", "int"), prop("HP", "int"), prop("Position", "vector2"),
    ], records=[
        record("Bag", 3, [("item", "int"), ("weight", "float")]),
    ]))
    k = Kernel(reg, store_config=StoreConfig(
        default_capacity=CAP, capacities={"Npc": CAP},
        timer_slots={"Npc": 2},
    ), seed=0)
    mesh = make_mesh(n_shards)
    mig = RowMigrationModule(SpatialPlacement(
        class_name="Npc", pos_prop="Position", extent=EXTENT,
        cell_size=8.0, width=8, n_shards=n_shards, mig_budget=4,
    ), mesh=mesh, order=20)
    k.build([_Drift(), mig])
    mig.bind(k)

    # identical initial banks on every placement: 32 live rows in the
    # lower half of the bank space, unique ids, scattered positions
    rng = np.random.default_rng(7)
    i32 = np.zeros((CAP, 2), np.int32)
    i32[:, 0] = np.arange(CAP)
    i32[:N_LIVE, 1] = 100
    vec = np.zeros((CAP, 1, 3), np.float32)
    vec[:N_LIVE, 0, 0] = rng.uniform(1.0, EXTENT - 1, N_LIVE)
    vec[:N_LIVE, 0, 1] = rng.uniform(1.0, EXTENT - 1, N_LIVE)
    alive = np.zeros(CAP, bool)
    alive[:N_LIVE] = True
    cs = k.state.classes["Npc"].replace(
        i32=jnp.asarray(i32), vec=jnp.asarray(vec), alive=jnp.asarray(alive))
    k.state = with_class(k.state, "Npc", cs)

    sk = ShardedKernel(k, mesh=mesh)
    sk.place()
    return k, sk, mig


def test_full_store_migration_digest_parity():
    """Records + timers + banks cross shards bit-identically: per-tick
    canonical digest of the 8-device mesh run equals the single-shard
    control that never migrates a row."""
    km, skm, migm = _mk_world(8)
    kc, skc, _ = _mk_world(1)
    moved_total = 0
    for t in range(24):
        skm.run_device(1, fused=False)
        skc.run_device(1, fused=False)
        stats = np.asarray(km.state.aux[migm.aux_key])
        moved_total += int(stats[:, 0].sum())
        assert int(stats[:, 2].sum()) == 0, "protocol dropped a row"
        dm = canonical_digest(km.state, ["Npc"], {"Npc": 0})
        dc = canonical_digest(kc.state, ["Npc"], {"Npc": 0})
        assert dm == dc, f"digest divergence at tick {t}"
    assert moved_total > 0, "workload never migrated - gate proves nothing"
    # live population conserved: budget overflow strands, never destroys
    assert int(np.asarray(km.state.classes["Npc"].alive).sum()) == N_LIVE


def test_migration_preserves_record_content_per_id():
    """Spot-check beyond the digest: after churn, each live row's record
    page on the mesh matches the control's row with the same Id."""
    km, skm, _ = _mk_world(8)
    kc, skc, _ = _mk_world(1)
    for _ in range(12):
        skm.run_device(1, fused=False)
        skc.run_device(1, fused=False)

    def by_id(k):
        cs = jax.tree.map(np.asarray, k.state.classes["Npc"])
        out = {}
        for r in np.flatnonzero(cs.alive):
            out[int(cs.i32[r, 0])] = (
                cs.records["Bag"].i32[r], cs.records["Bag"].f32[r],
                cs.timers.next_fire[r], cs.timers.remain[r], cs.vec[r],
            )
        return out

    mesh_rows, ctrl_rows = by_id(km), by_id(kc)
    assert set(mesh_rows) == set(ctrl_rows)
    for ident, banks in ctrl_rows.items():
        for a, b in zip(mesh_rows[ident], banks):
            np.testing.assert_array_equal(a, b, err_msg=f"id {ident}")


def _combat_parity(n_shards: int, ticks: int):
    geom = SpatialGeom(
        extent=128.0, cell_size=4.0, width=32, n_shards=n_shards,
        bucket=24, att_bucket=24, radius=4.0, mig_budget=256,
        speed=1.0, attack_period=3,
    )
    rng = np.random.default_rng(11)
    n = 300
    pos = rng.uniform(1.0, 127.0, (n, 2)).astype(np.float32)
    hp = np.full(n, 3000, np.int32)
    atk = rng.integers(5, 20, n).astype(np.int32)
    camp = (np.arange(n) % 2).astype(np.int32)

    world = SpatialWorld(geom)
    world.place(pos, hp, atk, camp)
    world.step(ticks)
    assert world.stats_last[:, 2].sum() == 0

    gid = jnp.arange(n, dtype=jnp.int32)
    active = jnp.ones(n, bool)
    posj, hpj = jnp.asarray(pos), jnp.asarray(hp)
    diedj = jnp.full(n, -1, jnp.int32)
    step = jax.jit(lambda p, h, dd, t: reference_step(
        geom, p, h, jnp.asarray(atk), jnp.asarray(camp), gid, dd, active, t
    ))
    for t in range(ticks):
        posj, hpj, diedj = step(posj, hpj, diedj, jnp.int32(t))
    ref_pos, ref_hp = np.asarray(posj), np.asarray(hpj)
    got = world.gather()
    assert len(got) == n
    for g, (x, y, hp_) in got.items():
        assert hp_ == int(ref_hp[g]), f"gid {g} hp"
        np.testing.assert_array_equal(np.float32([x, y]), ref_pos[g])


def test_unified_combat_short_parity_mesh():
    """Tier-1 slice of the 120-tick gate: the 4-shard unified run
    reproduces the oracle bit-exactly (the 1-shard case is covered by
    the digest-parity control above and by the slow 120-tick gate)."""
    _combat_parity(4, 16)


@pytest.mark.slow
def test_unified_combat_120_tick_gate():
    """The full 120-tick six-column digest-parity gate, single-device
    and in-process 8-device mesh."""
    _combat_parity(1, 120)
    _combat_parity(8, 120)


def test_gameworld_selects_placement_by_config():
    """Tentpole wiring: WorldConfig.placement attaches the full-row
    migration phase to the standard stack; stats ride kernel aux."""
    from noahgameframe_tpu.game.world import GameWorld, WorldConfig

    pl = SpatialPlacement(
        class_name="NPC", pos_prop="Position", extent=64.0,
        cell_size=8.0, width=8, n_shards=2, mig_budget=8,
    )
    w = GameWorld(WorldConfig(
        npc_capacity=64, extent=64.0, combat=False, movement=False,
        regen=False, middleware=False, placement=pl,
    ))
    w.start()
    w.scene.create_scene(1, width=64.0)
    w.seed_npcs(8)
    w.tick()
    w.tick()
    assert w.migration is not None
    assert w.migration.aux_key in w.kernel.state.aux
    assert np.asarray(w.kernel.state.aux[w.migration.aux_key]).shape == (2, 3)
    # off-config worlds carry no migration phase at all
    w0 = GameWorld(WorldConfig(
        npc_capacity=64, combat=False, movement=False, regen=False,
        middleware=False,
    ))
    assert w0.migration is None


def test_slab_snapshot_loads_into_unified_engine(tmp_path):
    """Satellite: a pre-unification slab snapshot (binning recorded but
    no full-row `layout` marker) loads into the unified engine — Verlet
    caches dropped, banks intact, trajectory unchanged."""
    geom = SpatialGeom(
        extent=128.0, cell_size=8.0, width=16, n_shards=2,
        bucket=48, att_bucket=48, radius=4.0, mig_budget=64,
        speed=0.1, attack_period=3, skin=4.0,
    )
    rng = np.random.default_rng(5)
    n = 120
    pos = rng.uniform(1.0, 127.0, (n, 2)).astype(np.float32)
    hp = np.full(n, 900, np.int32)
    atk = rng.integers(5, 15, n).astype(np.int32)
    camp = (np.arange(n) % 2).astype(np.int32)

    w1 = SpatialWorld(geom)
    w1.place(pos, hp, atk, camp)
    w1.step(6)
    assert np.asarray(w1.state.vc_active).any(), "skin run must carry cache"
    p_new = tmp_path / "unified.npz"
    w1.save(p_new)

    # rewrite the snapshot as the OLD slab engine wrote it: same bank
    # columns, binning marker, but no `layout` key
    with np.load(p_new) as z:
        legacy = {f: z[f] for f in z.files if f != "layout"}
    legacy["binning"] = "sort"
    p_old = tmp_path / "slab.npz"
    np.savez_compressed(p_old, **legacy)

    w2 = SpatialWorld(geom)
    w2.load(p_old)
    assert w2.tick_count == 6
    # cross-engine load drops the cache (geometry/layout re-derived)...
    assert not np.asarray(w2.state.vc_active).any()
    # ...but the row banks are intact
    st1 = jax.tree.map(np.asarray, w1.state)
    st2 = jax.tree.map(np.asarray, w2.state)
    np.testing.assert_array_equal(st1.pos, st2.pos)
    np.testing.assert_array_equal(st1.hp, st2.hp)
    np.testing.assert_array_equal(st1.gid, st2.gid)
    np.testing.assert_array_equal(st1.active, st2.active)

    # and the resumed trajectory is bit-identical to the uninterrupted one
    w1.step(6)
    w2.step(6)
    g1, g2 = w1.gather(), w2.gather()
    assert g1.keys() == g2.keys()
    for g in g1:
        np.testing.assert_array_equal(
            np.float32(g1[g]), np.float32(g2[g]), err_msg=f"gid {g}")


def test_snapshot_loads_across_mesh_widths(tmp_path):
    """Satellite (ISSUE 17): a snapshot taken on a 4-device mesh loads
    into a 2-device world (and the canonical digest stays pinned while
    both continue) — GameWorld.load re-places the restored banks through
    ``world_shardings`` on the CURRENT mesh with every trace dropped."""
    from noahgameframe_tpu.game.world import GameWorld, WorldConfig

    def mk(n_shards):
        pl = SpatialPlacement(
            class_name="NPC", pos_prop="Position", extent=64.0,
            cell_size=8.0, width=8, n_shards=n_shards, mig_budget=8,
        )
        w = GameWorld(WorldConfig(
            npc_capacity=64, extent=64.0, combat=False, movement=False,
            regen=False, middleware=False, placement=pl,
        ))
        w.start()
        w.scene.create_scene(1, width=64.0)
        w.seed_npcs(24, rng=np.random.default_rng(3))
        # unique identity in an inert saved column (Gold) so the
        # placement-invariant digest can pair rows across widths
        slot = w.kernel.store.spec("NPC").slot("Gold")
        cs = w.kernel.state.classes["NPC"]
        k = w.kernel
        k.state = with_class(k.state, "NPC", cs.replace(
            i32=cs.i32.at[:, slot.col].set(jnp.arange(64))))
        w.shard(n_shards)
        return w, slot.col

    def dig(w, col):
        return canonical_digest(w.kernel.state, ["NPC"], {"NPC": col})

    w4, col = mk(4)
    for _ in range(5):
        w4.tick()
    snap = tmp_path / "wide.ckpt"
    w4.save(snap)
    snap_digest = dig(w4, col)

    w2, _ = mk(2)
    w2.load(snap)
    assert w2.kernel.tick_count == w4.kernel.tick_count
    assert dig(w2, col) == dig(w4, col), "restore must be content-exact"
    # the restored world ticks on ITS mesh; parity holds as both advance
    for _ in range(5):
        w4.tick()
        w2.tick()
        assert dig(w2, col) == dig(w4, col)

    # and the narrow→wide direction: the same snapshot was written by a
    # 4-device world; an 8-device world swallows it too
    w8, _ = mk(8)
    w8.load(snap)
    assert dig(w8, col) == snap_digest
    w8.tick()
    assert int(np.asarray(
        w8.kernel.state.classes["NPC"].alive).sum()) == 24
