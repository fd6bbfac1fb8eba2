"""Spatially-sharded combat preset over the unified mesh engine.

Historically this module owned a bespoke six-column mini-world
(pos/hp/atk/camp/gid in its own NamedTuple banks) that bypassed the
Kernel's property banks, records and timers entirely.  It is now a THIN
PRESET over the one mesh engine: entities live in a real ``ClassState``
("spatial" class: five int properties + a vector2 position), the tick is
``Kernel._trace_step`` compiled by ``ShardedKernel``, and cross-shard
migration is the generic full-row protocol in ``parallel/rowmigrate.py``
(free-slot capacity vote → pack → ppermute → scatter-insert, lifted from
the slab engine and generalized to every store leaf).

Phase chain (one jit-compiled sharded tick):

- ``spatial.walk`` (order 10): deterministic per-gid random walk, pure
  elementwise — identical math on any placement.
- ``rowmigrate.migrate`` (order 20): budgeted ppermute migration of FULL
  ClassState rows toward the shard owning their cell row.  Up to
  `mig_budget` rows per direction per tick; overflow rows stay home,
  miss combat that tick (counted) and retry.
- ``spatial.combat`` (order 30): per-slab cell tables, dense halo planes
  to both neighbors, the shared combat fold, damage/regen/respawn.

Damage semantics are bit-identical to the single-device engine: the
fold body is game.combat.combat_fold_closure (shared, not copied), the
attacker `row` payload column carries the GLOBAL entity gid, damage
sums are exact int32 in f32 (< 2^24), and tie-breaks reduce over gid —
so within migration/bucket budgets, spatial and single-device worlds
produce identical HP trajectories (tests/test_spatial.py pins this).

Verlet/binning caches ride ``WorldState.aux`` (never ClassState): they
are rebuilt, not migrated, and stay excluded from ``state_digest`` — the
cache-rebuild contract documented in docs/ARCHITECTURE.md.

Reference contrast: NFCWorldNet_ServerModule.cpp:600-830 re-homes
players between game servers through the World relay (serialize,
destroy, recreate); here migration is fixed-size collectives inside
the jitted tick and visibility across the boundary is a dense halo, not
a relay hop.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core.schema import ClassDef, ClassRegistry, prop
from ..core.store import StoreConfig, with_class
from ..game.combat import combat_fold_closure
from ..kernel.kernel import Kernel
from ..kernel.module import Module
from ..ops.stencil import build_cell_table_pair, pull
from ..ops.verlet import VerletCache, full_table, refresh, sub_table
from .mesh import SHARD_AXIS, make_mesh
from .rowmigrate import (
    _pack_rows,  # noqa: F401  (re-export: the slab protocol's packer moved)
    RowMigrationModule,
    SpatialPlacement,
)
from .shard import ShardedKernel

# i32 property columns of the "spatial" class, in definition order
_HP, _ATK, _CAMP, _GID, _DIED = range(5)
_POS = 0  # vec column


class SpatialGeom(NamedTuple):
    """Static geometry of the spatially-sharded world."""

    extent: float          # world is [0, extent)^2
    cell_size: float
    width: int             # cells per axis; grid [width, width]
    n_shards: int          # horizontal slabs; width % n_shards == 0
    bucket: int            # victim slots per cell
    att_bucket: int        # attacker slots per cell
    radius: float          # AoE radius (<= cell_size)
    mig_budget: int        # migrant rows per direction per shard per tick
    speed: float = 0.5     # random-walk step per tick (< cell_size)
    attack_period: int = 30  # a gid attacks every `attack_period` ticks
    # the rest of the benchmark phase chain (0 disables either):
    regen_per_tick: int = 0   # hp regained per tick while alive
    hp_max: int = 0           # regen/respawn ceiling (0 = no ceiling)
    respawn_ticks: int = 0    # dead rows revive at hp_max after this many
    # Verlet skin (ops/verlet.py): > 0 gates the per-slab sort+build on
    # accumulated displacement.  Requires cell_size >= radius + skin.
    # Any tick that migrates a row (or strands one mid-hop) changes the
    # in-slab mask and forces a rebuild, so the win concentrates in ticks
    # where no entity crosses a slab boundary.
    skin: float = 0.0

    @property
    def slab_h(self) -> int:
        return self.width // self.n_shards

    def placement(self, class_name: str = "spatial",
                  pos_prop: str = "pos") -> SpatialPlacement:
        """The rowmigrate config this geometry implies."""
        return SpatialPlacement(
            class_name=class_name, pos_prop=pos_prop, extent=self.extent,
            cell_size=self.cell_size, width=self.width,
            n_shards=self.n_shards, mig_budget=self.mig_budget,
        )


class SpatialState(NamedTuple):
    """Host-facing VIEW of the unified engine's state, kept for API and
    snapshot compatibility: column slices of the "spatial" ClassState
    banks plus the aux-carried Verlet cache.  Leading axis =
    n_shards * bank_size, sharded row-wise so shard i holds rows
    [i*bank : (i+1)*bank]."""

    pos: jnp.ndarray     # [cap, 2] f32
    hp: jnp.ndarray      # [cap] i32
    atk: jnp.ndarray     # [cap] i32
    camp: jnp.ndarray    # [cap] i32
    gid: jnp.ndarray     # [cap] i32 — stable global id, rides migration
    died: jnp.ndarray    # [cap] i32 — tick of death, -1 while alive
    active: jnp.ndarray  # [cap] bool
    # Verlet cache leaves (geom.skin > 0; carried zeros otherwise).
    # cstat: [n_shards, 3] = rebuilds/reuses/age, one [1, 3] row per shard.
    vc_pos: jnp.ndarray      # [cap, 2] f32 — anchor positions
    vc_active: jnp.ndarray   # [cap] bool  — anchor in-slab mask
    vc_order: jnp.ndarray    # [cap] i32
    vc_skey: jnp.ndarray     # [cap] i32
    vc_slot: jnp.ndarray     # [cap] i32
    cstat: jnp.ndarray       # [n_shards, 3] i32


def _walk(pos, gid, tick, geom: SpatialGeom):
    """Deterministic per-gid random walk — a pure function of (gid,
    tick), so every shard placement computes the identical trajectory
    (the parity tests rely on this).  The murmur3-style finalizer
    matters: a LINEAR hash of (gid, tick) rotates each heading by a
    constant ~0.9 deg/tick, producing near-straight paths that stick to
    the clipped world walls and pile entire populations into corner
    cells within ~100 ticks."""
    h = (gid.astype(jnp.uint32) * jnp.uint32(2654435761)
         + jnp.uint32(tick) * jnp.uint32(40503))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    ang = (h >> 8).astype(jnp.float32) * (2.0 * np.pi / float(1 << 24))
    step = jnp.stack([jnp.cos(ang), jnp.sin(ang)], -1) * geom.speed
    eps = 1e-3
    return jnp.clip(pos + step, eps, geom.extent - eps)


def _life_phases(geom: SpatialGeom, hp, died, incoming, tick):
    """Damage -> death mark -> regen -> respawn, shared verbatim by the
    spatial tick and the single-device parity oracle (pure elementwise,
    placement-invariant)."""
    hp_after = jnp.maximum(hp - incoming, 0)
    died = jnp.where((hp > 0) & (hp_after == 0), tick, died)
    if geom.regen_per_tick > 0:
        regen = jnp.where(hp_after > 0, hp_after + geom.regen_per_tick,
                          hp_after)
        if geom.hp_max > 0:
            regen = jnp.minimum(regen, geom.hp_max)
        hp_after = regen
    if geom.respawn_ticks > 0:
        revive = (
            (hp_after == 0) & (died >= 0)
            & (tick - died >= geom.respawn_ticks)
        )
        hp_after = jnp.where(revive, geom.hp_max, hp_after)
        died = jnp.where(revive, -1, died)
    return hp_after, died


def _combat_body(geom: SpatialGeom, axis, pos, hp, atk, camp, gid, died,
                 active, vc_pos, vc_active, vc_order, vc_skey, vc_slot,
                 cstat, tick):
    """Combat on one shard (runs under shard_map; arrays are the
    shard-local banks).  Movement and migration already happened in
    earlier phases; cells are re-derived from the post-migration
    positions exactly as the old fused body did."""
    n = geom.n_shards
    hs = geom.slab_h
    w = geom.width
    me = jax.lax.axis_index(axis)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]

    cx = jnp.clip((pos[:, 0] / geom.cell_size).astype(jnp.int32), 0, w - 1)
    cy = jnp.clip((pos[:, 1] / geom.cell_size).astype(jnp.int32), 0, w - 1)
    owner = cy // hs

    # -- local cell tables over the slab ---------------------------------
    in_slab = active & (owner == me)
    misplaced = jnp.sum(active & (owner != me), dtype=jnp.int32)
    cell_local = (cy - me * hs) * w + cx
    f32 = jnp.float32
    camp_f = camp.astype(f32)
    zeros_f = jnp.zeros_like(camp_f)
    vic_feats = jnp.stack(
        [pos[:, 0], pos[:, 1], camp_f, zeros_f, zeros_f], -1
    )
    attacking = (
        in_slab
        & (hp > 0)
        & ((gid + tick) % geom.attack_period == 0)
    )
    eff_atk = jnp.where(attacking, atk, 0).astype(f32)
    att_feats = jnp.stack(
        [pos[:, 0], pos[:, 1], eff_atk, camp_f, zeros_f, zeros_f,
         gid.astype(f32)],
        -1,
    )
    if geom.skin > 0.0:
        # displacement-gated build (ops/verlet.py): the anchor mask is the
        # in-slab set, so any migration/straggler flip forces a rebuild —
        # and the vote is pmax'd over the mesh so every shard's carried
        # cache takes the same branch.  cell_local is derived from the
        # same positions passed to refresh, as its contract requires.
        cache = VerletCache(
            anchor_pos=vc_pos, anchor_active=vc_active, order=vc_order,
            skey=vc_skey, slot_of=vc_slot,
            rebuilds=cstat[0, 0], reuses=cstat[0, 1], age=cstat[0, 2],
        )
        cache, _rebuilt = refresh(
            cache, pos, in_slab, geom.cell_size, w, geom.bucket, geom.skin,
            cell=cell_local, n_cells=hs * w, height=hs, axis_name=axis,
        )
        vic_t = full_table(
            cache, vic_feats, in_slab, hs * w, geom.cell_size, w,
            geom.bucket, height=hs,
        )
        att_t = sub_table(
            cache, attacking, att_feats, hs * w, geom.cell_size, w,
            geom.att_bucket, height=hs,
        )
        vc_pos, vc_active = cache.anchor_pos, cache.anchor_active
        vc_order, vc_skey, vc_slot = cache.order, cache.skey, cache.slot_of
        cstat = jnp.stack([cache.rebuilds, cache.reuses, cache.age])[None, :]
    else:
        vic_t, att_t = build_cell_table_pair(
            pos, in_slab, vic_feats, attacking, att_feats,
            geom.cell_size, w, geom.bucket, geom.att_bucket,
            cell=cell_local, height=hs,
        )

    # -- halo exchange: one dense attacker plane per edge ----------------
    ag = att_t.grid_view()  # [hs, w, K_att, F+1]
    halo_top = jax.lax.ppermute(ag[hs - 1:hs], axis, fwd)   # prev's bottom
    halo_bot = jax.lax.ppermute(ag[0:1], axis, bwd)          # next's top
    halo_top = jnp.where(me > 0, halo_top, jnp.zeros_like(halo_top))
    halo_bot = jnp.where(me < n - 1, halo_bot, jnp.zeros_like(halo_bot))
    ag_h = jnp.concatenate([halo_top, ag, halo_bot], axis=0)  # [hs+2, ...]

    # -- fold: same body as the single-chip engine, halo-aware walk ------
    fold, init = combat_fold_closure(vic_t.grid_view(), geom.radius)
    agp = jnp.pad(ag_h, ((0, 0), (1, 1), (0, 0), (0, 0)))
    acc = init
    for dy in (0, 1, 2):  # (dy, dx) ascending == ops.stencil.STENCIL order
        for dx in (0, 1, 2):
            cand = jax.lax.slice(
                agp, (dy, dx, 0, 0),
                (dy + hs, dx + w, agp.shape[2], agp.shape[3]),
            )
            acc = fold(acc, cand)
    inc, _besta, _bestr = acc

    # -- damage -----------------------------------------------------------
    pulled = pull(vic_t, inc, fill=0)
    incoming = jnp.where(in_slab & (hp > 0), pulled, 0)
    hp, died = _life_phases(geom, hp, died, incoming, tick)

    # columns: misplaced (awaiting migration retry), vic/att cell-bucket
    # drops — the migration counters ride rowmigrate's own stats aux
    stats = jnp.stack(
        [misplaced, vic_t.dropped, att_t.dropped]
    )[None, :]  # [1, 3] per shard -> [n_shards, 3] outside
    return (hp, died, vc_pos, vc_active, vc_order, vc_skey, vc_slot,
            cstat, stats)


VC_AUX = "spatial.vc"
COMBAT_STATS_AUX = "spatial.stats"


class _SpatialModule(Module):
    """Walk + combat phases of the spatial preset (migration is the
    generic RowMigrationModule between them)."""

    name = "spatial"

    def __init__(self, world: "SpatialWorld"):
        super().__init__()
        self.world = world
        self.add_phase("walk", self._walk_phase, order=10)
        self.add_phase("combat", self._combat_phase, order=30)

    def _walk_phase(self, state, ctx):
        g = self.world.geom
        cs = state.classes["spatial"]
        new = _walk(cs.vec[:, _POS, :2], cs.i32[:, _GID], ctx.tick, g)
        vec = cs.vec.at[:, _POS, 0].set(new[:, 0]).at[:, _POS, 1].set(
            new[:, 1])
        return with_class(state, "spatial", cs.replace(vec=vec))

    def _combat_phase(self, state, ctx):
        w = self.world
        g = w.geom  # read at trace time: invalidate() picks up resizes
        cs = state.classes["spatial"]
        vc = state.aux[VC_AUX]
        row, rep = P(w.axis), P()
        smapped = jax.shard_map(
            partial(_combat_body, g, w.axis),
            mesh=w.mesh,
            in_specs=(row,) * 13 + (rep,),
            out_specs=(row,) * 9,
            check_vma=False,
        )
        (hp, died, vc_pos, vc_active, vc_order, vc_skey, vc_slot, cstat,
         stats) = smapped(
            cs.vec[:, _POS, :2], cs.i32[:, _HP], cs.i32[:, _ATK],
            cs.i32[:, _CAMP], cs.i32[:, _GID], cs.i32[:, _DIED],
            cs.alive, vc["pos"], vc["active"], vc["order"], vc["skey"],
            vc["slot"], vc["cstat"], ctx.tick,
        )
        i32 = cs.i32.at[:, _HP].set(hp).at[:, _DIED].set(died)
        state = with_class(state, "spatial", cs.replace(i32=i32))
        ctx.count("misplaced", jnp.sum(stats[:, 0]))
        ctx.count("grid_drops", jnp.sum(stats[:, 1:]))
        return state.replace(aux={
            **state.aux,
            VC_AUX: {"pos": vc_pos, "active": vc_active, "order": vc_order,
                     "skey": vc_skey, "slot": vc_slot, "cstat": cstat},
            COMBAT_STATS_AUX: stats,
        })


class SpatialWorld:
    """Thin spatial preset over the unified Kernel/ShardedKernel engine.

    Usage:
        geom = SpatialGeom(...)
        world = SpatialWorld(geom)            # makes its own mesh
        world.place(pos, hp, atk, camp)       # numpy rows, any order
        world.step()                          # one jitted sharded tick
        world.gather()                        # {gid -> (pos, hp)} to host
    """

    def __init__(self, geom: SpatialGeom, mesh: Optional[Mesh] = None,
                 bank_size: Optional[int] = None):
        if geom.width % geom.n_shards:
            raise ValueError("width must divide into n_shards slabs")
        if geom.skin > 0.0 and geom.cell_size < geom.radius + geom.skin:
            raise ValueError(
                f"Verlet skin {geom.skin} needs cell_size >= radius + skin "
                f"({geom.radius + geom.skin}), got {geom.cell_size}"
            )
        self.geom = geom
        self.mesh = mesh if mesh is not None else make_mesh(geom.n_shards)
        self.axis = SHARD_AXIS
        self.bank_size = bank_size
        self.stats_last = np.zeros((geom.n_shards, 6), np.int32)
        self.overflow_budget = 1e-4  # alert threshold, as CombatModule
        self.overflow_alerts = 0
        # crowding response, ported from CombatModule._on_overflow: when
        # cell-bucket drops breach the budget, double both buckets
        # (bounded) and retrace — silent drops stop instead of repeating
        # every tick (r05_sharded_4m saw grid_overflow_max=374/tick).
        self.auto_resize = True
        self.max_bucket_boost = 8
        self._bucket_boost = 1
        self._kernel: Optional[Kernel] = None
        self._sharded: Optional[ShardedKernel] = None
        self._mig: Optional[RowMigrationModule] = None
        self._tick0 = 0
        # one cost ledger across rebuilds; the kernel adopts it at build
        # so benches and tests keep reading world.costbook
        from ..telemetry.costbook import CostBook

        self.costbook = CostBook()

    # -- engine assembly ---------------------------------------------------
    def _build_kernel(self, cap: int) -> None:
        g = self.geom
        reg = ClassRegistry()
        reg.define(ClassDef(name="spatial", properties=[
            prop("hp", "int"), prop("atk", "int"), prop("camp", "int"),
            prop("gid", "int"), prop("died", "int"),
            prop("pos", "vector2"),
        ]))
        k = Kernel(
            reg,
            store_config=StoreConfig(default_capacity=cap,
                                     capacities={"spatial": cap}),
            seed=0,
        )
        k.costbook = self.costbook
        self._mig = RowMigrationModule(
            g.placement(), mesh=self.mesh, order=20)
        k.build([_SpatialModule(self), self._mig])
        self._mig.bind(k)
        n_sh, bank = g.n_shards, cap // g.n_shards
        k.register_aux(VC_AUX, lambda: {
            "pos": jnp.zeros((cap, 2), jnp.float32),
            "active": jnp.zeros((cap,), bool),
            "order": jnp.zeros((cap,), jnp.int32),
            "skey": jnp.zeros((cap,), jnp.int32),
            "slot": jnp.zeros((cap,), jnp.int32),
            "cstat": jnp.zeros((n_sh, 3), jnp.int32),
        })
        k.register_aux(
            COMBAT_STATS_AUX, lambda: jnp.zeros((n_sh, 3), jnp.int32))
        self._kernel = k
        self._sharded = ShardedKernel(k, mesh=self.mesh)

    @property
    def kernel(self) -> Optional[Kernel]:
        """The unified engine underneath (None before place()/load())."""
        return self._kernel

    @property
    def tick_count(self) -> int:
        return self._kernel.tick_count if self._kernel else self._tick0

    @tick_count.setter
    def tick_count(self, v: int) -> None:
        v = int(v)
        if self._kernel is None:
            self._tick0 = v
            return
        self._kernel.tick_count = v
        self._kernel.state = self._kernel.state.replace(
            tick=jnp.asarray(v, jnp.int32))

    # -- state view (API/snapshot compatibility) ---------------------------
    @property
    def state(self) -> Optional[SpatialState]:
        if self._kernel is None:
            return None
        self._kernel._ensure_aux()
        cs = self._kernel.state.classes["spatial"]
        vc = self._kernel.state.aux[VC_AUX]
        return SpatialState(
            pos=cs.vec[:, _POS, :2], hp=cs.i32[:, _HP],
            atk=cs.i32[:, _ATK], camp=cs.i32[:, _CAMP],
            gid=cs.i32[:, _GID], died=cs.i32[:, _DIED], active=cs.alive,
            vc_pos=vc["pos"], vc_active=vc["active"], vc_order=vc["order"],
            vc_skey=vc["skey"], vc_slot=vc["slot"], cstat=vc["cstat"],
        )

    @state.setter
    def state(self, st: Optional[SpatialState]) -> None:
        if st is None:
            return
        k = self._kernel
        if k is None:
            raise RuntimeError("place() or load() before assigning state")
        k._ensure_aux()
        cs = k.state.classes["spatial"]
        i32 = jnp.stack(
            [jnp.asarray(st.hp), jnp.asarray(st.atk), jnp.asarray(st.camp),
             jnp.asarray(st.gid), jnp.asarray(st.died)], axis=1,
        ).astype(jnp.int32)
        pos = jnp.asarray(st.pos)
        vec = cs.vec.at[:, _POS, 0].set(pos[:, 0]).at[:, _POS, 1].set(
            pos[:, 1])
        cs = cs.replace(i32=i32, vec=vec, alive=jnp.asarray(st.active))
        new_state = with_class(k.state, "spatial", cs)
        k.state = new_state.replace(aux={
            **new_state.aux,
            VC_AUX: {
                "pos": jnp.asarray(st.vc_pos),
                "active": jnp.asarray(st.vc_active),
                "order": jnp.asarray(st.vc_order),
                "skey": jnp.asarray(st.vc_skey),
                "slot": jnp.asarray(st.vc_slot),
                "cstat": jnp.asarray(st.cstat),
            },
        })
        self._sharded.place()

    # -- placement --------------------------------------------------------
    def place(self, pos: np.ndarray, hp: np.ndarray, atk: np.ndarray,
              camp: np.ndarray) -> None:
        """Distribute entities into per-shard bank rows by their slab.

        Vectorized: one stable argsort by owning shard, per-shard base
        offsets, and a single fancy-index write per bank.  Rows seed the
        ClassState banks DIRECTLY (device-only population): per-guid
        host allocation would be O(n) interpreter work at placement, and
        these rows never need host identity — the host alloc_mask stays
        all-False, so migration-vacated slots never reconcile as deaths.
        """
        g = self.geom
        n = pos.shape[0]
        cy = np.clip((pos[:, 1] / g.cell_size).astype(np.int32), 0,
                     g.width - 1)
        owner = cy // g.slab_h
        counts = np.bincount(owner, minlength=g.n_shards)
        bank = self.bank_size or int(1 << int(np.ceil(np.log2(
            max(counts.max() * 2, 64)))))
        over = np.flatnonzero(counts > bank)
        if over.size:
            raise ValueError(f"bank {int(over[0])} overflow at placement")
        cap = bank * g.n_shards
        self.bank_size = bank
        self._build_kernel(cap)
        i32 = np.zeros((cap, 5), np.int32)
        i32[:, _GID] = -1
        i32[:, _DIED] = -1
        vec = np.zeros((cap, 1, 3), np.float32)
        alive = np.zeros((cap,), bool)
        if n:
            order = np.argsort(owner, kind="stable")
            so = owner[order]
            starts = np.zeros(g.n_shards, np.int64)
            starts[1:] = np.cumsum(counts)[:-1]
            r = so.astype(np.int64) * bank + (np.arange(n) - starts[so])
            vec[r, 0, 0] = pos[order, 0]
            vec[r, 0, 1] = pos[order, 1]
            i32[r, _HP] = hp[order]
            i32[r, _ATK] = atk[order]
            i32[r, _CAMP] = camp[order]
            i32[r, _GID] = order
            alive[r] = True
        k = self._kernel
        cs = k.state.classes["spatial"].replace(
            i32=jnp.asarray(i32), vec=jnp.asarray(vec),
            alive=jnp.asarray(alive),
        )
        k.state = with_class(k.state, "spatial", cs)
        self._sharded.place()

    # -- compiled step ----------------------------------------------------
    def step(self, n: int = 1) -> None:
        sk = self._sharded
        for _ in range(n):
            sk.run_device(1, fused=False)
        aux = self._kernel.state.aux
        mig = np.asarray(aux[self._mig.aux_key])
        cmb = np.asarray(aux[COMBAT_STATS_AUX])
        self.stats_last = np.concatenate([mig, cmb], axis=1)
        # runtime alerting, same contract as CombatModule's overflow
        # budget (the counters alone are bench-only visibility):
        # - mig_dropped rows left their source bank and found no free
        #   slot at the destination — permanently LOST, always alert
        #   (should never fire now that senders clamp to advertised
        #   destination capacity)
        # - rows that missed migration (budget or capacity clamp) are a
        #   SUBSET of `misplaced` — every unmigrated row is still active
        #   with owner != me when misplaced is counted — so `missed`
        #   counts misplaced + bucket drops and each affected row once
        #   (adding mig_overflow on top would double-count)
        lost_forever = int(self.stats_last[:, 2].sum())
        missed = int(self.stats_last[:, 3].sum()) + int(
            self.stats_last[:, 4:].sum()
        )
        if lost_forever or missed:
            alive = self._kernel.state.classes["spatial"].alive
            pop = max(1, int(np.asarray(alive).sum()))
            if lost_forever or missed / pop > self.overflow_budget:
                self.overflow_alerts += 1
                import logging

                logging.getLogger("nf.spatial").warning(
                    "spatial overflow: %d rows lost (bank full), %d "
                    "missed combat/migration this tick (%.4f%% of %d, "
                    "budget %.4f%%) - stats %s",
                    lost_forever, missed, 100 * missed / pop, pop,
                    100 * self.overflow_budget,
                    self.stats_last.sum(axis=0).tolist(),
                )
            # cell-bucket drops specifically (columns 4:6) respond to a
            # bucket resize; migration misses do not
            drops = int(self.stats_last[:, 4:].sum())
            if (
                self.auto_resize
                and drops / pop > self.overflow_budget
                and self._bucket_boost < self.max_bucket_boost
            ):
                self._resize_buckets(drops, pop)

    def _resize_buckets(self, drops: int, pop: int) -> None:
        """Double both cell buckets and retrace — the SpatialGeom twin of
        CombatModule._on_overflow.  Kernel.invalidate() drops the traces
        AND the registered aux (the carried Verlet cache bakes the old
        bucket into its slot assignment); the lifetime counters in cstat
        survive by being written back into the re-primed cache."""
        self._bucket_boost *= 2
        g = self.geom
        self.geom = g._replace(bucket=g.bucket * 2, att_bucket=g.att_bucket * 2)
        k = self._kernel
        old_cstat = k.state.aux[VC_AUX]["cstat"]
        # sanctioned retrace: the doubled buckets bake into the next trace
        k.invalidate()
        k._ensure_aux()
        vc = dict(k.state.aux[VC_AUX])
        vc["cstat"] = old_cstat
        k.state = k.state.replace(aux={**k.state.aux, VC_AUX: vc})
        import logging

        logging.getLogger("nf.spatial").warning(
            "cell-bucket overflow: %d drops over %d rows breached budget "
            "%.4f%%; buckets doubled to %d/%d (boost x%d of max x%d), "
            "step retraced",
            drops, pop, 100 * self.overflow_budget,
            self.geom.bucket, self.geom.att_bucket,
            self._bucket_boost, self.max_bucket_boost,
        )

    # -- Verlet cache visibility ------------------------------------------
    @property
    def rebuilds_total(self) -> int:
        """Max over shards (the pmax vote makes every shard rebuild
        together, so any shard's counter is the grid's)."""
        if self._kernel is None:
            return 0
        return int(np.asarray(self.state.cstat)[:, 0].max())

    @property
    def reuses_total(self) -> int:
        if self._kernel is None:
            return 0
        return int(np.asarray(self.state.cstat)[:, 1].max())

    # -- host observation -------------------------------------------------
    def gather(self):
        """{gid: (x, y, hp)} for live rows — host-side verification."""
        st = jax.tree.map(np.asarray, self.state)
        out = {}
        for r in np.flatnonzero(st.active):
            out[int(st.gid[r])] = (
                float(st.pos[r, 0]), float(st.pos[r, 1]), int(st.hp[r])
            )
        return out

    # -- checkpoint / resume ----------------------------------------------
    def save(self, path: str) -> None:
        """Snapshot banks + tick counter; resuming continues the exact
        trajectory (the walk/duty are pure functions of (gid, tick)).
        The npz keys are the historical slab-engine layout, so old
        snapshots load into the unified engine and vice versa; `layout`
        marks full-row snapshots (absent = pre-unification slab file)."""
        st = jax.tree.map(np.asarray, self.state)
        np.savez_compressed(
            path, tick=self.tick_count, bank=self.bank_size,
            layout="classrow", **st._asdict(),
        )

    def load(self, path: str) -> None:
        with np.load(path) as z:
            tick = int(z["tick"])
            self.bank_size = int(z["bank"])
            cap = z["pos"].shape[0]
            # snapshots from before the Verlet cache carry zero caches:
            # the all-False anchor mask forces a rebuild on the first
            # tick, so resume trajectories are unchanged
            fresh = {
                "vc_pos": np.zeros((cap, 2), np.float32),
                "vc_active": np.zeros((cap,), bool),
                "vc_order": np.zeros((cap,), np.int32),
                "vc_skey": np.zeros((cap,), np.int32),
                "vc_slot": np.zeros((cap,), np.int32),
                "cstat": np.zeros((self.geom.n_shards, 3), np.int32),
            }
            # vc_order/vc_skey are the sorted order and sorted keys
            # (VerletCache docstring).  An older file may say it was
            # written by another build (`binning` present and not
            # "sort": per-row keys in vc_skey), and a pre-unification
            # slab snapshot (no `layout` key) does not carry the full-row
            # layout this engine does: in either case the cache is
            # dropped (all-False anchors => first tick rebuilds;
            # trajectories are unchanged) and only the row banks load.
            # Geometry is re-derived from this world's SpatialGeom + the
            # stored bank size.
            stored = str(z["binning"]) if "binning" in z.files else "sort"
            layout = str(z["layout"]) if "layout" in z.files else "slab"
            drop_cache = stored != "sort" or layout != "classrow"

            def pick(f):
                if f in z.files and not (drop_cache and f.startswith("vc_")):
                    return z[f]
                return fresh[f]

            self._build_kernel(cap)
            self.state = SpatialState(
                *[pick(f) for f in SpatialState._fields]
            )
            self.tick_count = tick


def reference_step(geom: SpatialGeom, pos, hp, atk, camp, gid, died, active,
                   tick):
    """Single-device twin of the spatial tick (same movement, same
    attacker duty, the square-grid combat_fold_xla, the same
    _life_phases chain) — the parity oracle for tests and the
    global-sort side of the A/B."""
    from ..game.combat import combat_fold_xla

    pos = _walk(pos, gid, tick, geom)
    f32 = jnp.float32
    camp_f = camp.astype(f32)
    zeros_f = jnp.zeros_like(camp_f)
    vic_feats = jnp.stack([pos[:, 0], pos[:, 1], camp_f, zeros_f, zeros_f], -1)
    attacking = active & (hp > 0) & ((gid + tick) % geom.attack_period == 0)
    eff_atk = jnp.where(attacking, atk, 0).astype(f32)
    att_feats = jnp.stack(
        [pos[:, 0], pos[:, 1], eff_atk, camp_f, zeros_f, zeros_f,
         gid.astype(f32)],
        -1,
    )
    vic_t, att_t = build_cell_table_pair(
        pos, active, vic_feats, attacking, att_feats,
        geom.cell_size, geom.width, geom.bucket, geom.att_bucket,
    )
    inc, _bestr = combat_fold_xla(vic_t, att_t, geom.radius)
    pulled = pull(vic_t, inc, fill=0)
    incoming = jnp.where(active & (hp > 0), pulled, 0)
    hp, died = _life_phases(geom, hp, died, incoming, tick)
    return pos, hp, died
