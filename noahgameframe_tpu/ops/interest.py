"""Per-observer interest queries + quantized delta filtering, on device.

The group-granular broadcast of the reference (NFCSceneAOIModule: every
player in the (scene, group) sees every change there,
NFCSceneAOIModule.cpp:531-593) collapses at TPU-scale worlds — one busy
group means full-world fan-out per client (round-3: 24.5 MB/frame of
position sync at 100k entities / 500 sessions).  This module computes
*per-session* visible sets the TPU-first way:

1. `quantize` — u16-quantize positions over the scene extent and mask
   out-of-extent rows.  One fused elementwise op.  Per-session change
   suppression (send only what THIS observer hasn't seen at this
   quantum) happens on the host against each session's seen-state
   (net/roles/game.py `_send_interest_pos`) — a global delta gate can't
   express enter-view resends.
2. `visible_candidates` — bin the alive entities into the stencil
   engine's cell table (ops/stencil.build_cell_table, one argsort) and,
   for every observer position, read the 3x3 neighborhood's K slots and
   distance-mask them: [S, 9K] candidate rows in ONE dispatch, no host
   loops.  Where the table has a SECOND LEVEL (a crowd: cells far over
   K, sized by the game role's breach policy and not by an option), the
   rows an over-full cell keeps beyond K are read too, for those of the
   nine cells that are over-full: [S, 9K + 9D] candidates, D the
   level's depth.

Both are static-shaped and jit-compiled by the caller (the game role
caches per-shape jits).  The host then slices each session's visible
rows and packs one compact message per session (net/roles/game.py).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .stencil import STENCIL, CellTable, build_cell_table
from .verlet import VerletCache, refresh, sub_table

QMAX = 65535  # u16 quantization range


# what a build of the interest table reports, in `InterestResult.stats`
STAT_NAMES = ("dropped", "hot_cells", "cell_rows_max", "spill_rows")


class InterestResult(NamedTuple):
    rows: jnp.ndarray  # [S, 9K + 9D] int32 row ids (garbage where ~ok)
    ok: jnp.ndarray  # [S, 9K + 9D] bool — occupied slot AND within radius
    # [4] int32, `STAT_NAMES`: rows that fit neither level of the table
    # this answer was read from, its over-full cells, its fullest cell,
    # rows its second level placed (None: a cached table counts none)
    stats: Optional[jnp.ndarray] = None


def quantize(
    pos: jnp.ndarray,  # [C, >=2] float32 world positions
    alive: jnp.ndarray,  # [C] bool
    extent: float,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(q [C,3] i32, in_extent [C] bool).

    World-coordinate contract: the stream covers [0, extent] per axis.
    Rows outside it are NOT clamped onto the boundary (a client would
    render them pinned at the edge) — they are excluded via the returned
    mask and simply don't ride the wire until they re-enter the extent.
    """
    p3 = pos[:, :3] if pos.shape[1] >= 3 else jnp.pad(
        pos, ((0, 0), (0, 3 - pos.shape[1]))
    )
    # X/Y only: visibility distance is 2D, and Z is client-supplied
    # (jump/flight jitter) — gating on it would let an entity go
    # invisible by sending z=-0.5 while staying fully active
    in_extent = (
        jnp.all((p3[:, :2] >= 0.0) & (p3[:, :2] <= extent), axis=-1) & alive
    )
    q = jnp.clip(jnp.round(p3 * (QMAX / extent)), 0, QMAX).astype(jnp.int32)
    return q, in_extent


def visible_candidates(
    pos: jnp.ndarray,  # [C, >=2] float32 entity positions
    moved: jnp.ndarray,  # [C] bool — which entities changed this frame
    scene: jnp.ndarray,  # [C] float32 scene id
    group: jnp.ndarray,  # [C] float32 group id (0 = scene-wide)
    obs_pos: jnp.ndarray,  # [S, >=2] float32 observer positions
    obs_scene: jnp.ndarray,  # [S] float32
    obs_group: jnp.ndarray,  # [S] float32
    radius: float,
    cell_size: float,
    width: int,
    bucket: int,
    spill: Tuple[int, int] = (0, 0),
) -> InterestResult:
    """For each observer, the `moved` entities within `radius` AND visible
    under the reference's broadcast scoping (NFCSceneAOIModule): same
    scene, and either the same group or the entity carries GroupID 0
    (scene-wide).  Scenes share one coordinate space, so proximity alone
    would leak entities across scene/clone-group boundaries.

    cell_size must be >= radius so the 3x3 stencil covers the disc.
    `spill = (cells, depth)` is the table's second level
    (ops/stencil.build_cell_table): the first `cells` over-full cells in
    cell order keep `depth` rows beyond `bucket`.  A row that fits
    neither level (the highest rows of its cell) is in no observer's
    answer for as long as it stands that deep in that cell: it is
    counted in `stats` (`dropped`), which the game role publishes as
    `nf_interest_dropped_total` and holds against its budget
    (net/roles/game.py `_answer_interest_breach` deepens the table)."""
    table = interest_table(
        pos, moved, scene, group, cell_size, width, bucket, spill)
    return _scan_observers(
        table, obs_pos, obs_scene, obs_group, radius, cell_size
    )._replace(stats=table_stats(table))


def interest_table(
    pos, active, scene, group, cell_size: float, width: int, bucket: int,
    spill: Tuple[int, int] = (0, 0),
) -> CellTable:
    """The interest programs' one table build (device scope
    `nf.interest.bin`): `active` rows binned with `_interest_feats`."""
    with jax.named_scope("nf.interest.bin"):
        return build_cell_table(
            pos, active, _interest_feats(pos, scene, group), cell_size,
            width, bucket, spill_cells=spill[0], spill_bucket=spill[1])


def table_stats(table: CellTable) -> jnp.ndarray:
    """`InterestResult.stats` of a table built here (zeros from a table
    that counts none: the Verlet-cached sub-table)."""
    st = table.stats
    if st is None:
        return jnp.zeros((len(STAT_NAMES),), jnp.int32)
    return jnp.stack([table.dropped, st.hot_cells, st.rows_max,
                      st.spill_rows]).astype(jnp.int32)


def table_seam(table: CellTable):
    """What of a built table crosses from the program that builds it to
    the program that scans it (the game role keeps them apart: the build
    knows no session, the scan no row): (payload, hot_of, stats), the
    level's index one word where there is no level."""
    hot_of = (jnp.zeros((1,), jnp.int32) if table.hot_of is None
              else table.hot_of)
    return table.payload, hot_of, table_stats(table)


def seam_table(payload, hot_of, cell_size: float, width: int, bucket: int,
               spill: Tuple[int, int] = (0, 0)) -> CellTable:
    """`table_seam`'s other side: the table `_scan_observers` reads,
    from the payload and the level's index (geometry static)."""
    return CellTable(
        payload, jnp.zeros((1,), jnp.int32), jnp.zeros((), jnp.int32),
        width, cell_size, bucket, spill_cells=spill[0],
        spill_bucket=spill[1], hot_of=hot_of if spill[0] else None)


def scope_mask(cand_scene, cand_group, obs_scene, obs_group) -> jnp.ndarray:
    """The reference's broadcast visibility scope (NFCSceneAOIModule):
    same scene, and either the same group or the candidate carries
    GroupID 0 (scene-wide wildcard).  All args are broadcastable f32
    planes.  Shared by the per-observer scan below AND the fused Pallas
    neighborhood kernel's AOI occupancy fold (ops/stencil_pallas.py) so
    scope semantics cannot drift between the serving and combat paths."""
    return (cand_scene == obs_scene) & (
        (cand_group == 0) | (cand_group == obs_group)
    )


def _interest_feats(pos, scene, group) -> jnp.ndarray:
    """The candidate feature layout both builders share: row id, x, y,
    scene, group (occupancy appended by the table builder)."""
    n = pos.shape[0]
    return jnp.concatenate(
        [
            jnp.arange(n, dtype=jnp.float32)[:, None],  # row id
            pos[:, :2].astype(jnp.float32),
            scene.astype(jnp.float32)[:, None],
            group.astype(jnp.float32)[:, None],
        ],
        axis=1,
    )


def _scan_observers(
    table: CellTable,
    obs_pos: jnp.ndarray,
    obs_scene: jnp.ndarray,
    obs_group: jnp.ndarray,
    radius: float,
    cell_size: float,
) -> InterestResult:
    """The per-observer 3x3 read shared by the fresh and Verlet-cached
    builders: observers index by their CURRENT cell, candidates mask by
    TRUE radius on the current positions carried in the payload — which
    is what keeps cached (anchor-binned) tables bit-identical, provided
    cell_size >= radius + skin/2 covers the staleness."""
    h = table.height if table.height > 0 else table.width
    w = table.width
    inv = 1.0 / cell_size
    ox = jnp.floor(obs_pos[:, 0] * inv).astype(jnp.int32)
    oy = jnp.floor(obs_pos[:, 1] * inv).astype(jnp.int32)
    r2 = radius * radius

    def read(cells, present):
        """One neighbour cell's slots for every observer: cells
        [S, depth, F+1], occupancy in the last column."""
        occ = (cells[..., -1] > 0) & present[:, None]
        dxv = cells[..., 1] - obs_pos[:, None, 0]
        dyv = cells[..., 2] - obs_pos[:, None, 1]
        within = (dxv * dxv + dyv * dyv) <= r2
        scoped = scope_mask(
            cells[..., 3], cells[..., 4],
            obs_scene[:, None], obs_group[:, None],
        )
        return cells[..., 0].astype(jnp.int32), occ & within & scoped

    def nine():
        for dy, dx in STENCIL:
            yy, xx = oy + dy, ox + dx
            in_grid = (yy >= 0) & (yy < h) & (xx >= 0) & (xx < w)
            yield jnp.clip(yy, 0, h - 1), jnp.clip(xx, 0, w - 1), in_grid

    with jax.named_scope("nf.interest.scan"):
        grid = table.grid_view()  # [H, W, K, F+1]
        got = [read(grid[yy, xx], in_grid) for yy, xx, in_grid in nine()]
    if table.spill_cells > 0:
        # the rows an over-full cell keeps beyond its K slots, for those
        # of the nine that are over-full: the same tests, `depth` wide
        with jax.named_scope("nf.interest.spill"):
            level = table.spill_view()  # [cells, depth, F+1]
            hot_of = table.hot_of.reshape(h, w)
            for yy, xx, in_grid in nine():
                hot = hot_of[yy, xx]
                got.append(read(level[jnp.maximum(hot, 0)],
                                in_grid & (hot >= 0)))
    return InterestResult(
        rows=jnp.concatenate([rows for rows, _ in got], axis=1),
        ok=jnp.concatenate([ok for _, ok in got], axis=1),
    )


def visible_candidates_cached(
    cache: VerletCache,
    pos: jnp.ndarray,
    moved: jnp.ndarray,  # [C] bool — this frame's candidate subset
    alive: jnp.ndarray,  # [C] bool — the cache anchors over ALL alive rows
    scene: jnp.ndarray,
    group: jnp.ndarray,
    obs_pos: jnp.ndarray,
    obs_scene: jnp.ndarray,
    obs_group: jnp.ndarray,
    radius: float,
    cell_size: float,
    width: int,
    bucket: int,
    skin: float,
) -> Tuple[InterestResult, VerletCache, jnp.ndarray]:
    """`visible_candidates` with a Verlet-cached binning (ops/verlet.py):
    the cache anchors the FULL alive population, and each frame's `moved`
    subset rides a sub-table through the cached sorted order (a streaming
    cumsum instead of an argsort — the moved set changes every frame, so
    its table always refreshes; only the sort is amortized).

    cell_size must be >= radius + skin (caller inflates its geometry);
    the distance mask uses the true radius on current positions, so
    results are bit-identical to the fresh builder on the same inflated
    grid (modulo bucket-overflow drops — size generously).

    Returns (result, new_cache, rebuilt i32) — thread the cache back in
    next frame."""
    # anchor over the STABLE alive set — anchoring on `moved` would flip
    # the active mask (and force a rebuild) every frame.  moved & alive
    # is then a subset of the anchor by construction, which is all
    # sub_table needs.
    cache, rebuilt = refresh(
        cache, pos, alive, cell_size, width, bucket, skin
    )
    feats = _interest_feats(pos, scene, group)
    table = sub_table(
        cache, moved & alive, feats, width * width, cell_size, width, bucket
    )
    result = _scan_observers(
        table, obs_pos, obs_scene, obs_group, radius, cell_size
    )
    return result, cache, rebuilt
