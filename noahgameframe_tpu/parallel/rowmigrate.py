"""Full-row cross-shard migration: the slab protocol generalized to ClassState.

parallel/spatial.py pioneered budgeted ppermute migration for its private
six-column mini-world (free-slot capacity vote → pack → ppermute →
scatter-insert).  This module lifts that protocol to the real entity
store: a migrating entity moves its ENTIRE ``ClassState`` row — every
property bank, every record page, the TimerState triple, and the alive
bit — as one pytree-structured pack/scatter compiled into the sharded
tick.  The pack list is derived generically from the store pytree by
``persist.rowblob.class_row_leaf_items`` (the same leaf walk
``shard.py:world_shardings`` does for placement), so a newly added bank
can never be silently left behind; the ``migrate-covers-store`` nf-lint
rule pins that statically and the walk asserts it at trace time.

Verlet/binning caches are NOT migrated: they live in ``WorldState.aux``
(never in ClassState), are excluded from ``state_digest``, and are
dropped-and-rebuilt on arrival — the cache-rebuild contract documented in
docs/ARCHITECTURE.md.

Reference contrast: NFCWorldNet_ServerModule.cpp:600-830 re-homes an
entity between game servers by serialize → destroy → recreate through the
World relay; here the same "whole entity moves" semantics is two
fixed-size collectives inside the jitted tick.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core.store import ClassState, WorldState, with_class
from ..kernel.module import Module
from ..persist.rowblob import class_row_leaf_items, rebuild_class_state, row_nbytes
from .mesh import SHARD_AXIS, make_mesh

def _pack_rows(sel, rank, budget, *arrays):
    """Gather up to `budget` selected rows into fixed [budget] buffers.
    sel: [n] bool, rank: [n] exclusive rank among selected.  Returns
    (valid [budget] bool, packed arrays).  Generic over trailing dims —
    property banks [n, k], record pages [n, R, k] and [n, R, k, 3] all
    pack with the same leading-axis scatter."""
    idx = jnp.where(sel & (rank < budget), rank, budget)
    valid = jnp.zeros((budget + 1,), bool).at[idx].set(sel)[:budget]
    out = []
    for a in arrays:
        buf_shape = (budget + 1,) + a.shape[1:]
        out.append(jnp.zeros(buf_shape, a.dtype).at[idx].set(a)[:budget])
    return valid, out


def migrate_rows(leaves, alive, owner_fn, axis, n_shards, budget):
    """One budgeted ppermute migration round over arbitrary row leaves.

    Runs INSIDE shard_map: ``leaves`` are the shard-local banks (leading
    axis = local bank rows), ``alive`` the local occupancy mask.
    ``owner_fn(leaves, alive) -> [rows] i32`` returns each row's owning
    shard index; it is re-evaluated after each direction so freshly
    arrived rows are never double-hopped.  Protocol (verbatim from the
    slab engine, now generic over the leaf list):

    1. each shard advertises its free-slot count BEFORE clearing its own
       outbound rows (the advertised number only understates reality);
       the sender clamps to min(budget, advertised) so a row that would
       find no destination slot stays home and retries,
    2. selected rows pack into fixed [budget] buffers, one ppermute per
       leaf per direction,
    3. arrivals scatter into free-slot ranks; a drop here is a protocol
       bug (counted, should never fire), not expected overflow.

    Returns (leaves, alive, (migrated, overflow, dropped)) — the three
    stats as i32 scalars for this shard.
    """
    n = n_shards
    me = jax.lax.axis_index(axis)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    cap_rows = alive.shape[0]
    migrated = jnp.int32(0)
    overflow = jnp.int32(0)
    dropped = jnp.int32(0)
    leaves = list(leaves)
    owner = owner_fn(leaves, alive)
    for d, perm in ((1, fwd), (-1, bwd)):
        # direction of travel, not exact neighbor: a row stranded 2+
        # shards from home hops one shard toward its owner per tick
        m = alive & ((owner > me) if d == 1 else (owner < me))
        free_cnt = jnp.sum(~alive, dtype=jnp.int32)
        remote_free = jax.lax.ppermute(free_cnt, axis, bwd if d == 1 else fwd)
        cap_d = jnp.minimum(jnp.int32(budget), remote_free)
        csum = jnp.cumsum(m.astype(jnp.int32))
        sel = m & (csum <= cap_d)
        migrated = migrated + jnp.sum(sel, dtype=jnp.int32)
        overflow = overflow + jnp.sum(m, dtype=jnp.int32) - jnp.sum(
            sel, dtype=jnp.int32
        )
        valid, packed = _pack_rows(sel, csum - 1, budget, *leaves)
        rvalid = jax.lax.ppermute(valid, axis, perm)
        rpacked = [jax.lax.ppermute(b, axis, perm) for b in packed]
        # wrap-around sends are impossible (owner is clipped into range),
        # but mask the circular receive anyway for edge shards
        sender_ok = (me - d >= 0) & (me - d < n)
        rvalid = rvalid & sender_ok
        alive = alive & ~sel
        # insert into free slots: dest[j] = row index of the j-th free slot
        free = ~alive
        frank = jnp.cumsum(free.astype(jnp.int32)) - free.astype(jnp.int32)
        slots = jnp.where(free & (frank < budget), frank, budget)
        dest = (
            jnp.full((budget + 1,), cap_rows, jnp.int32)
            .at[slots]
            .set(jnp.arange(cap_rows, dtype=jnp.int32))[:budget]
        )
        dest_j = jnp.where(rvalid, dest, cap_rows)
        dropped = dropped + jnp.sum(
            rvalid & (dest_j >= cap_rows), dtype=jnp.int32
        )
        leaves = [
            cur.at[dest_j].set(rb, mode="drop")
            for cur, rb in zip(leaves, rpacked)
        ]
        alive = alive.at[dest_j].set(True, mode="drop")
        owner = owner_fn(leaves, alive)
    return leaves, alive, (migrated, overflow, dropped)


def mesh_migrate_class(
    cs: ClassState,
    mesh: Mesh,
    owner_fn: Callable,
    budget: int,
    axis: str = SHARD_AXIS,
    extra_leaves: Optional[Sequence[jnp.ndarray]] = None,
):
    """Migrate full ClassState rows toward their owning shard.

    ``owner_fn({path: local_leaf}) -> [rows] i32`` maps the shard-local
    leaf dict (paths as in ``persist.rowblob.ROW_LEAF_SPEC``, plus
    ``alive``) to owning shard indices.  The alive bit is the protocol's
    own occupancy bookkeeping; every other leaf rides the generic
    pack/scatter.  Returns (new ClassState, [n_shards, 3] i32 stats:
    migrated / budget-overflow / dropped per shard).

    ``extra_leaves`` are additional per-row arrays (leading axis = class
    capacity, row-sharded like the banks) that migrate WITH the row but
    live outside ClassState — e.g. the tick's in-flight fired mask, which
    the schedule computed before this phase and later phases still read.
    They ride the same pack/ppermute/scatter and are returned as a third
    element, permuted consistently with the class state.
    """
    n = mesh.devices.size
    items = class_row_leaf_items(cs)
    paths = [p for p, _ in items]
    arrs = [a for _, a in items]
    ai = paths.index("alive")
    extras = list(extra_leaves) if extra_leaves else []
    n_row = len(arrs)
    row = P(axis)

    def body(*local):
        local = list(local)
        row_local, extras_local = local[:n_row], local[n_row:]
        alive = row_local[ai]
        others = row_local[:ai] + row_local[ai + 1:] + extras_local

        def owner_of(ls, alv):
            # extras sit past the named paths; owner_fn never sees them
            full: Dict[str, jnp.ndarray] = {}
            j = 0
            for p in paths:
                if p == "alive":
                    full[p] = alv
                else:
                    full[p] = ls[j]
                    j += 1
            return owner_fn(full)

        new_others, new_alive, (mig, ovf, drp) = migrate_rows(
            others, alive, owner_of, axis, n, budget
        )
        merged = []
        j = 0
        for p in paths:
            if p == "alive":
                merged.append(new_alive)
            else:
                merged.append(new_others[j])
                j += 1
        stats = jnp.stack([mig, ovf, drp])[None, :]  # [1, 3] per shard
        return tuple(merged) + tuple(new_others[n_row - 1:]) + (stats,)

    smapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(row,) * (n_row + len(extras)),
        out_specs=(row,) * (n_row + len(extras) + 1),
        check_vma=False,
    )
    out = smapped(*(arrs + extras))
    new_leaves = list(out[:n_row])
    new_extras = list(out[n_row:-1])
    stats = out[-1]
    new_cs = rebuild_class_state(cs, new_leaves)
    if extra_leaves is None:
        return new_cs, stats
    return new_cs, stats, new_extras


# -- GameWorld-facing placement config ------------------------------------


@dataclasses.dataclass(frozen=True)
class SpatialPlacement:
    """Config-selected spatial placement: grid geometry + migration budget
    as a kernel phase.  Attach via ``RowMigrationModule`` (GameWorld does
    this when ``WorldConfig.placement`` is set)."""

    class_name: str          # entity class whose rows migrate
    pos_prop: str            # vector property giving world position
    extent: float            # world is [0, extent)^2
    cell_size: float
    width: int               # cells per axis
    n_shards: int            # horizontal slabs
    mig_budget: int          # migrant rows per direction per shard per tick

    @property
    def slab_h(self) -> int:
        # ceil division: when width % n_shards != 0 (an elastic drain to
        # an odd survivor count) the LAST shard owns a narrower slab but
        # owner_of_pos stays in [0, n_shards) for every cell
        return -(-self.width // self.n_shards)

    def owner_of_pos(self, pos_xy: jnp.ndarray) -> jnp.ndarray:
        """[rows, 2+] positions -> [rows] i32 owning shard index."""
        cy = jnp.clip(
            (pos_xy[:, 1] / self.cell_size).astype(jnp.int32), 0,
            self.width - 1,
        )
        return cy // self.slab_h


class RowMigrationModule(Module):
    """Kernel module registering the ``migrate`` phase: full-row
    cross-shard migration for one class, keyed on its position property.

    Stats ride ``state.aux["rowmigrate.<class>.stats"]`` ([n_shards, 3]
    i32: migrated / budget-overflow / dropped) so the headless sharded
    loop keeps them device-resident, and ``ctx.count`` mirrors the
    migrated total into the tick summary for the observed path.
    """

    name = "rowmigrate"

    def __init__(self, placement: SpatialPlacement,
                 mesh: Optional[Mesh] = None, order: int = 20):
        super().__init__()
        self.placement = placement
        self.mesh = mesh if mesh is not None else make_mesh(placement.n_shards)
        self.aux_key = f"rowmigrate.{placement.class_name}.stats"
        # exodus overlay (parallel/elastic.py drain protocol): when set,
        # owners are remapped through a host table so rows vacate a
        # draining shard; both are trace-time constants, so arming or
        # clearing REQUIRES kernel.invalidate() (set_exodus does it)
        self._exodus_map: Optional[jnp.ndarray] = None
        self.add_phase("migrate", self._migrate, order=order)

    def bind(self, kernel) -> None:
        """Register carried aux BEFORE the first trace (stats must exist
        in the state pytree so sharded in/out shardings stay stable)."""
        self.kernel = kernel
        n = self.placement.n_shards
        kernel.register_aux(
            self.aux_key, lambda: jnp.zeros((n, 3), jnp.int32)
        )

    def retarget(self, placement: Optional[SpatialPlacement] = None,
                 mesh: Optional[Mesh] = None) -> None:
        """Re-aim the migrate phase at a new placement and/or mesh — the
        elastic reshard path.  The stats aux re-registers at the new
        shard count; the caller must invalidate + re-place (ElasticMesh
        does both via ShardedKernel.reshard, which drops the old aux and
        primes the new shape before the next trace)."""
        if placement is not None:
            if placement.class_name != self.placement.class_name:
                raise ValueError("retarget cannot change the migrating "
                                 "class (aux key is class-keyed)")
            self.placement = placement
        if mesh is not None:
            self.mesh = mesh
        if self.kernel is not None:
            self.bind(self.kernel)

    def set_exodus(self, index_map) -> None:
        """Arm the drain overlay: spatial owner ``o`` is remapped to
        ``index_map[o]`` so every row owned by a draining shard re-homes
        to a surviving one.  Bumps the kernel trace generation — the
        remap is a traced constant."""
        self._exodus_map = jnp.asarray(index_map, jnp.int32)
        if self.kernel is not None:
            self.kernel.invalidate()

    def clear_exodus(self) -> None:
        if self._exodus_map is None:
            return
        self._exodus_map = None
        if self.kernel is not None:
            self.kernel.invalidate()

    def after_init(self) -> None:
        if self.kernel is not None and self.aux_key not in getattr(
                self.kernel, "_aux_init", {}):
            self.bind(self.kernel)

    def row_bytes(self) -> int:
        """Per-row wire bytes of the migrating class (bench accounting)."""
        if self.kernel is None or self.kernel.state is None:
            return 0
        cs = self.kernel.state.classes[self.placement.class_name]
        return row_nbytes(cs)

    def _migrate(self, state: WorldState, ctx) -> WorldState:
        pl = self.placement
        exodus = self._exodus_map
        cs = state.classes[pl.class_name]
        slot = ctx.store.spec(pl.class_name).slot(pl.pos_prop)

        def owner_fn(leaves: Dict[str, jnp.ndarray]) -> jnp.ndarray:
            pos = leaves["vec"][:, slot.col, :]
            owner = pl.owner_of_pos(pos)
            if exodus is not None:
                # runs inside mesh_migrate_class's shard_map, so the
                # local shard index is addressable.  While the drain is
                # armed, ALL migration freezes except evacuation: rows
                # standing on the draining shard route to their remapped
                # owner (never the draining shard itself — the remap has
                # no fixed point there), everyone else re-homes to where
                # they already stand.  Routing by spatial owner instead
                # would keep a trickle of through-traffic hopping ACROSS
                # the draining bank (ring transit is one shard per
                # tick), and under continuous motion churn the bank then
                # never empties — the drain blows its tick bound.
                # Spatial rebalance pauses for the few evacuation ticks
                # and resumes when clear_exodus() re-arms normal routing.
                mapped = jnp.take(exodus, owner)
                me = jax.lax.axis_index(SHARD_AXIS)
                draining_here = jnp.take(exodus, me) != me
                owner = jnp.where(draining_here, mapped, me)
            return owner

        # the tick's fired mask was computed pre-migration; it must move
        # WITH the row or a migrant's timer fire lands on its vacated
        # (dead) slot and every later handler silently skips it
        fired = ctx._fired.get(pl.class_name)
        extras = [fired] if fired is not None and fired.shape[1] else None

        # the module's mesh is generation-safe by contract: every elastic
        # reshard retarget()s it and invalidates before the re-trace
        out = mesh_migrate_class(
            cs, self.mesh, owner_fn, pl.mig_budget,  # nf-lint: disable=mesh-not-captured -- retarget()+invalidate() re-aim it pre-retrace
            extra_leaves=extras,
        )
        if extras is None:
            cs2, stats = out
        else:
            cs2, stats, (new_fired,) = out
            # vacated source slots keep stale mask bytes; dead rows never
            # fire, so pin the invariant here rather than trust consumers
            ctx.remap_fired(pl.class_name, new_fired & cs2.alive[:, None])
        ctx.count("migrated", jnp.sum(stats[:, 0]))
        ctx.count("mig_overflow", jnp.sum(stats[:, 1]))
        state = with_class(state, pl.class_name, cs2)
        return state.replace(aux={**state.aux, self.aux_key: stats})


# -- placement-invariant digest (parity oracle) ----------------------------


def canonical_digest(state: WorldState, class_order: Sequence[str],
                     ident_cols: Dict[str, int]) -> int:
    """Host-side uint32 digest that is invariant to row PLACEMENT.

    ``kernel.state_digest`` is position-weighted, so the same logical
    world hashed on an 8-shard mesh (rows scattered by migration) and on
    a single-shard control (rows never move) produces different values.
    This twin canonicalizes first: per class, live rows are ordered by a
    stable identity column (``ident_cols[cname]``: i32 column index; the
    class's rows must carry unique ids there), dead rows are dropped
    entirely (a vacated slot keeps stale bank bytes by design), and the
    same fold math as state_digest runs over the canonical view.  Two
    runs agree iff every live row's full ClassState content agrees.
    """
    mult = np.uint64(1000003)
    mask = np.uint64(0xFFFFFFFF)

    def fold(acc: np.uint64, arr: np.ndarray) -> np.uint64:
        a = np.ascontiguousarray(arr)
        if a.dtype == np.bool_:
            u = a.astype(np.uint32)
        elif a.dtype.itemsize == 4:
            u = a.view(np.uint32)
        else:
            u = a.astype(np.uint32)
        u = u.ravel().astype(np.uint64)
        w = np.arange(u.size, dtype=np.uint64) * 2 + 1
        s = np.uint64(int((u * w).sum(dtype=np.uint64)) & 0xFFFFFFFF)
        return (acc * mult + s) & mask

    acc = np.uint64(0x9E3779B9)
    acc = fold(acc, np.asarray(state.tick))
    for cname in class_order:
        cs = state.classes[cname]
        alive = np.asarray(cs.alive)
        ident = np.asarray(cs.i32)[:, ident_cols[cname]]
        live = np.flatnonzero(alive)
        order = live[np.argsort(ident[live], kind="stable")]
        acc = fold(acc, np.uint32(live.size))
        for _path, arr in class_row_leaf_items(cs):
            a = np.asarray(arr)
            if _path == "alive":
                continue  # canonical view is all-live by construction
            acc = fold(acc, a[order])
    return int(acc)
