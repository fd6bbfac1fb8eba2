"""Verlet-cached cell tables: displacement-gated rebuild of the binning.

The per-tick cell-table build (ops/stencil.py) re-sorts every entity every
tick, and the profile names that one stable argsort as the dominant
irregular-memory cost of the device tick.  Molecular-dynamics engines
solved this shape decades ago: Verlet neighbor lists (Verlet, Phys. Rev.
159, 1967) bin with an INFLATED radius `r + skin` and rebuild only when
accumulated displacement threatens recall — GPU MD codes (HOOMD-blue,
Anderson et al. 2008) amortize the O(N log N) structure build across many
cheap reuse steps the same way.

Applied to the cell-table engine:

- The grid is laid out with `cell_size >= r + skin` (the caller inflates
  its geometry once, at module init).  A build anchors every entity at its
  CURRENT position; the cache keeps that anchor plus the sorted order /
  sorted keys / slot assignment the argsort produced.
- While every entity has moved less than `skin / 2` from its anchor
  (`2 * max_displacement < skin`), any pair within true radius `r` of each
  other TODAY was within `r + skin` of each other at anchor time, so the
  anchor binning still covers the 3x3 stencil query — the sort can be
  skipped and only the cheap payload scatter replayed with fresh features.
- Queries always mask by true distance on CURRENT positions, so results
  are bit-identical to an always-rebuild baseline on the same (inflated)
  geometry: the same candidate pairs pass the mask either way, damage
  sums are order-insensitive exact int-in-f32, and the combat tie-break
  is placement-invariant (global min row, game/combat.py).  The one
  caveat is bucket overflow: anchor and current binnings can drop
  DIFFERENT rows when a cell exceeds its K slots, so bit-parity claims
  assume zero drops (auto_bucket's contract).

The rebuild decision is a single on-device scalar, so the whole build
wraps in one `lax.cond`: the expensive branch re-sorts and re-anchors,
the cheap branch bumps the age.  Under shard_map the predicate is
`lax.pmax`-combined so every shard votes coherently (a one-sided rebuild
would desynchronize the carried caches).  Under jit+GSPMD the global
`jnp.max` reduction achieves the same automatically.

Any change to the ACTIVE set (spawn, destroy, shard migration) forces a
rebuild: a departed row's stale slot would keep it visible, an arrival
would be invisible.  The trigger therefore compares the full active mask
against the anchor mask, which also guarantees every subset table built
through the cached order (attackers, moved-entity interest lists) only
ever draws from anchored rows.
"""

from __future__ import annotations

import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .stencil import (
    CellTable,
    _cell_keys,
    _key_segments,
    _slots_from_ranks,
    table_from_slots,
)

ENV_SKIN = "NF_VERLET_SKIN"


class VerletCache(NamedTuple):
    """Carried tick state for one grid (pure arrays: rides WorldState /
    shard_map carries, donates, checkpoints and tree_maps like any leaf).

    anchor_pos:    [N, 2] f32 — positions at the last rebuild.
    anchor_active: [N] bool   — active mask at the last rebuild.
    order:         [N] i32    — the stable sort by anchor cell id.
    skey:          [N] i32    — the SORTED anchor cell keys (n_cells for
                                inactive): with `order`, what sub_table()
                                needs to re-rank a fresh subset on a
                                reuse tick.
    slot_of:       [N] i32    — full-table slot per row for the bucket the
                                cache was built with (geometry-baked: any
                                bucket/width change must drop the cache).
    rebuilds/reuses: i32 scalars — lifetime counters (telemetry).
    age:           i32 scalar — ticks since the last rebuild (staleness).
    """

    anchor_pos: jnp.ndarray
    anchor_active: jnp.ndarray
    order: jnp.ndarray
    skey: jnp.ndarray
    slot_of: jnp.ndarray
    rebuilds: jnp.ndarray
    reuses: jnp.ndarray
    age: jnp.ndarray


def skin_from_env(default: float = 0.0) -> float:
    """The NF_VERLET_SKIN tuning knob; <= 0 (or unset/garbage) means off —
    exactly today's rebuild-every-tick behavior, zero structural change."""
    raw = os.environ.get(ENV_SKIN, "").strip()
    if not raw:
        return float(default)
    try:
        return float(raw)
    except ValueError:
        return float(default)


def init_cache(n: int) -> VerletCache:
    """A never-built cache: the all-False anchor mask disagrees with any
    live world, so the first refresh() always takes the rebuild branch
    (and table_from_slots stays harmless even if queried raw)."""
    # each leaf gets its OWN buffer — run_device donates the whole state
    # pytree, and XLA rejects the same buffer donated twice
    return VerletCache(
        anchor_pos=jnp.zeros((n, 2), jnp.float32),
        anchor_active=jnp.zeros((n,), bool),
        order=jnp.zeros((n,), jnp.int32),
        skey=jnp.zeros((n,), jnp.int32),
        slot_of=jnp.zeros((n,), jnp.int32),
        rebuilds=jnp.int32(0),
        reuses=jnp.int32(0),
        age=jnp.int32(0),
    )


def need_rebuild(
    cache: VerletCache,
    pos: jnp.ndarray,
    active: jnp.ndarray,
    skin: float,
    axis_name: Optional[str] = None,
) -> jnp.ndarray:
    """Scalar bool: must the binning be rebuilt this tick?

    Triggers on ARRIVALS — rows active now that the anchor never binned
    (spawn, respawn, migration-in): a stale table would hide them.  Rows
    that merely LEFT (death, migration-out) do not trigger — the payload
    replay (table_from_slots) forces every now-inactive row to the dump
    slot, which is exactly what a fresh build of the shrunken set would
    produce; this also keeps every sub_mask a subset of the anchor, since
    callers only pass sub_mask & active.  Also triggers when
    `2 * max_displacement >= skin` over rows live in BOTH the anchor and
    the present (the boundary itself rebuilds: reuse is only proven for
    strictly-less-than).  Displacement uses the first two position
    components, matching the grid's 2D cells.

    axis_name: shard_map axis to pmax the vote over (sharded worlds must
    rebuild together or their carried caches desynchronize); jit+GSPMD
    callers omit it — the global reductions already see the whole array.
    """
    d = pos[:, :2] - cache.anchor_pos
    both = active & cache.anchor_active
    d2 = jnp.where(both, jnp.sum(d * d, axis=-1), 0.0)
    s = jnp.float32(float(skin))
    trig = jnp.any(active & ~cache.anchor_active) | (
        4.0 * jnp.max(d2, initial=0.0) >= s * s
    )
    if axis_name is not None:
        trig = jax.lax.pmax(trig.astype(jnp.int32), axis_name) > 0
    return trig


def refresh(
    cache: VerletCache,
    pos: jnp.ndarray,
    active: jnp.ndarray,
    cell_size: float,
    width: int,
    bucket: int,
    skin: float,
    *,
    cell: Optional[jnp.ndarray] = None,
    n_cells: Optional[int] = None,
    height: int = -1,
    axis_name: Optional[str] = None,
) -> Tuple[VerletCache, jnp.ndarray]:
    """The lax.cond-gated build step: returns (valid cache, rebuilt i32).

    Rebuild branch = the key pass, the sort and the slot assignment
    (everything build_cell_table derives before the payload scatter),
    re-anchored at today's positions.  Reuse branch = the cached arrays
    untouched, age bumped.  Either way the returned cache is valid for
    full_table()/sub_table() THIS tick, which replay only the sort-free
    payload scatters against fresh features.

    cell/n_cells/height: precomputed (rectangular) cell ids, same contract
    as build_cell_table_pair — the spatial slab shards pass local ids.
    Note `cell` must be derived from the SAME positions passed here; the
    rebuild branch anchors both together.
    """
    if n_cells is None:
        if cell is not None:
            raise ValueError("precomputed cell ids need n_cells")
        n_cells = width * width
    trig = need_rebuild(cache, pos, active, skin, axis_name=axis_name)
    n = pos.shape[0]

    def rebuild(_):
        _nc, key = _cell_keys(
            pos, active, cell_size, width, cell=cell, n_cells=n_cells
        )
        order, skey, rank = _key_segments(key)
        slot_of = _slots_from_ranks(n, n_cells, order, skey, rank, bucket)
        return VerletCache(
            anchor_pos=pos[:, :2].astype(jnp.float32),
            anchor_active=active,
            order=order.astype(jnp.int32),
            skey=skey.astype(jnp.int32),
            slot_of=slot_of,
            rebuilds=cache.rebuilds + 1,
            reuses=cache.reuses,
            age=jnp.int32(0),
        )

    def reuse(_):
        return cache._replace(reuses=cache.reuses + 1, age=cache.age + 1)

    new_cache = jax.lax.cond(trig, rebuild, reuse, None)
    return new_cache, trig.astype(jnp.int32)


def full_table(
    cache: VerletCache,
    features: jnp.ndarray,
    active: jnp.ndarray,
    n_cells: int,
    cell_size: float,
    width: int,
    bucket: int,
    height: int = -1,
) -> CellTable:
    """The full-population table through the cached slot assignment: one
    payload scatter, no sort.  Bit-identical to build_cell_table when the
    cache is fresh (refresh() guarantees it is)."""
    return table_from_slots(
        features, active, cache.slot_of, n_cells, cell_size, width, bucket,
        height,
    )


def sub_table(
    cache: VerletCache,
    sub_mask: jnp.ndarray,
    sub_features: jnp.ndarray,
    n_cells: int,
    cell_size: float,
    width: int,
    sub_bucket: int,
    height: int = -1,
) -> CellTable:
    """A subset table (this tick's attackers / moved entities) through the
    cached order: the subset CHANGES every tick, so its per-cell ranks are
    recomputed, by a segmented exclusive cumsum over the cached sorted
    order instead of a second sort.  Bit-identical to the pair builder's
    sub table for any sub_mask subset of the anchor active set."""
    order, skey = cache.order, cache.skey
    n = order.shape[0]
    seg_start = jnp.concatenate(
        [jnp.ones((1,), bool), skey[1:] != skey[:-1]]
    )
    sub_sorted = sub_mask[order]
    ex = jnp.cumsum(sub_sorted.astype(jnp.int32)) - sub_sorted.astype(jnp.int32)
    head_ex = jax.lax.cummax(jnp.where(seg_start, ex, -1))
    sub_rank = jnp.where(sub_sorted, ex - head_ex, n_cells * sub_bucket + 1)
    return table_from_slots(
        sub_features, sub_mask,
        _slots_from_ranks(n, n_cells, order, skey, sub_rank, sub_bucket),
        n_cells, cell_size, width, sub_bucket, height,
    )
