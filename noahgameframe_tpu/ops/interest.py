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
2. `visible_candidates` — sort the alive entities by cell with the
   stencil engine's key pass and one stable sort (`interest_sorted`:
   ops/stencil.py's ranks, no table) and, for every observer position,
   read the 3x3 neighborhood's members straight from the sorted list
   (`scan_sorted`): after the sort a cell's members are one run of it,
   in row order, so the `bucket` entries at the cell's start are what a
   K-slot cell table would hold.  Where a cell is over-full (a crowd,
   sized by the game role's breach policy and not by an option) the
   first `cells` over-full cells keep a SECOND LEVEL of `depth` entries
   more, read behind the first K for those of the nine cells that own
   one: [S, 9K + 9D] candidates, distance- and scope-masked, in ONE
   dispatch, no host loops.  A frame sorts the whole class and reads
   only the windows its observers stand in.

Both are static-shaped and jit-compiled by the caller (the game role
caches per-shape jits).  The host then slices each session's visible
rows and packs one compact message per session (net/roles/game.py).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .stencil import STENCIL, CellTable, _cell_keys, _rank_full, hot_index
from .verlet import VerletCache, refresh, sub_table

QMAX = 65535  # u16 quantization range


# what a build of the interest answer counts, in `InterestResult.stats`
STAT_NAMES = ("dropped", "hot_cells", "cell_rows_max", "spill_rows")


class InterestResult(NamedTuple):
    rows: jnp.ndarray  # [S, 9K + 9D] int32 row ids (0 in an empty slot)
    ok: jnp.ndarray  # [S, 9K + 9D] bool — live candidate AND within radius
    # [4] int32, `STAT_NAMES`: rows that fit neither level of the slot
    # rule this answer was read under, the over-full cells, the fullest
    # cell, rows the second level placed (zeros from a cached table)
    stats: Optional[jnp.ndarray] = None


class InterestSeam(NamedTuple):
    """What crosses from the program that sorts a class to the program
    that scans it (the game role keeps them apart: the build knows no
    session, the scan no row).  Cell `c`'s candidates are entries
    `first[c] + j` of `feats`: the base level while `j < min(count[c],
    K)`, and where `hot_of[c] >= 0` the second level, `j = K + i` while
    `i < min(count[c] - K, D)`.

    feats:  [n + K + D, 5] f32 — `_interest_feats` in sorted order, then
            zero rows, so that no window reaches past the end
    first:  [n_cells] int32 — where a cell's run starts
    count:  [n_cells] int32 — the run's length
    hot_of: [n_cells] int32 — a cell's row of the second level, -1 for a
            cell that has none ([1] where there is no level)
    stats:  [4] int32, `STAT_NAMES`
    """

    feats: jnp.ndarray
    first: jnp.ndarray
    count: jnp.ndarray
    hot_of: jnp.ndarray
    stats: jnp.ndarray


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
    `spill = (cells, depth)` is the second level (ops/stencil.py
    `_spill_slots`): the first `cells` over-full cells in cell order
    keep `depth` rows beyond `bucket`.  A row that fits neither level
    (the highest rows of its cell) is in no observer's answer for as
    long as it stands that deep in that cell: it is counted in `stats`
    (`dropped`), which the game role publishes as
    `nf_interest_dropped_total` and holds against its budget
    (net/roles/game.py `_answer_interest_breach` deepens the level)."""
    seam = interest_sorted(
        pos, moved, scene, group, cell_size, width, bucket, spill)
    return scan_sorted(
        seam, obs_pos, obs_scene, obs_group, radius, cell_size, width,
        bucket, spill,
    )._replace(stats=seam.stats)


def interest_sorted(
    pos, active, scene, group, cell_size: float, width: int, bucket: int,
    spill: Tuple[int, int] = (0, 0),
) -> InterestSeam:
    """The interest programs' one build (device scope `nf.interest.bin`):
    `active` rows keyed by cell and sorted once, stably, as
    `ops/stencil.build_cell_table` sorts them, with everything that
    build counts (`_rank_full`: the two-level slot rule's `dropped` and
    `CellStats`, the cells' starts) and `hot_of` (`hot_index`), but no
    table: the scan reads a cell's slots where they already lie, in the
    sorted list.  Its `slot_of` un-sort is read by nobody here and
    dropped by the compiler."""
    cells, depth = spill
    if cells and depth <= 0:
        raise ValueError(f"second level ({cells}, {depth}): a depth of 0")
    if cells <= 0:
        cells = depth = 0
    i32 = jnp.int32
    with jax.named_scope("nf.interest.bin"):
        n_cells, key = _cell_keys(pos, active, cell_size, width)
        order, _slot_of, dropped, st, start = _rank_full(
            n_cells, key, active, bucket, cells, depth)
        feats = jnp.pad(_interest_feats(pos, scene, group)[order],
                        ((0, bucket + depth), (0, 0)))
        hot_of = (hot_index(start, bucket, cells) if cells
                  else jnp.full((1,), -1, i32))
        stats = jnp.stack([dropped, st.hot_cells, st.rows_max,
                           st.spill_rows]).astype(i32)
    return InterestSeam(feats, start[:-1], start[1:] - start[:-1], hot_of,
                        stats)


def table_seam(table: CellTable) -> InterestSeam:
    """A one-level table read as the scan reads the sorted list (the
    Verlet-cached sub-table, whose cells' members do not lie in one run
    of the cached order): cell `c`'s K slots are entries `c * K` on and
    its members their occupied prefix (ranks fill a cell's slots from
    0).  No level, and no counts: a cached table counts none."""
    i32 = jnp.int32
    k, n_cells = table.bucket, table.n_cells
    occ = table.payload[: n_cells * k, -1].reshape(n_cells, k) > 0
    return InterestSeam(
        table.payload[:, :-1], jnp.arange(n_cells, dtype=i32) * k,
        jnp.sum(occ, axis=1, dtype=i32), jnp.full((1,), -1, i32),
        jnp.zeros((len(STAT_NAMES),), i32))


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
    scene, group."""
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


def _windows(feats: jnp.ndarray, heads: jnp.ndarray, size: int):
    """`feats[h : h + size]` for every `h` in `heads`: [..., size, F], one
    gathered slice a head.  A gather moves a start that would run past
    the end back inside, which would shift a cell's rows: the seam's
    padding keeps every window inside, so no start is ever moved."""
    f = feats.shape[1]
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(1, 2), collapsed_slice_dims=(), start_index_map=(0,))
    got = jax.lax.gather(
        feats, heads.reshape(-1, 1), dnums, (size, f),
        mode=jax.lax.GatherScatterMode.CLIP)
    return got.reshape(heads.shape + (size, f))


def scan_sorted(
    seam: InterestSeam,
    obs_pos: jnp.ndarray,
    obs_scene: jnp.ndarray,
    obs_group: jnp.ndarray,
    radius: float,
    cell_size: float,
    width: int,
    bucket: int,
    spill: Tuple[int, int] = (0, 0),
) -> InterestResult:
    """The per-observer 3x3 read shared by the fresh and Verlet-cached
    builders: for every observer and each of its nine cells, a window of
    `bucket` entries at the cell's start (`nf.interest.scan`) and, for a
    cell that owns a row of the second level, the `depth` entries behind
    them (`nf.interest.spill`): the slots a two-level table would hold,
    in their order, so the layout is `[S, 9K]` then `[S, 9D]`.

    Observers index by their CURRENT cell, candidates mask by TRUE
    radius on the current positions the list carries — which is what
    keeps cached (anchor-binned) lists bit-identical, provided
    cell_size >= radius + skin/2 covers the staleness."""
    i32 = jnp.int32
    cells, depth = spill
    if cells <= 0:
        depth = 0
    w = width
    inv = 1.0 / cell_size
    ox = jnp.floor(obs_pos[:, 0] * inv).astype(i32)
    oy = jnp.floor(obs_pos[:, 1] * inv).astype(i32)
    r2 = radius * radius
    s = obs_pos.shape[0]

    def read(heads, size, member, present):
        """`size` entries from each of `heads` ([S, 9]) and the tests:
        rows (0 past the cell's members, as a table's empty slot) and
        ok, [S, 9 * size]."""
        got = _windows(seam.feats, heads, size)  # [S, 9, size, 5]
        dxv = got[..., 1] - obs_pos[:, None, None, 0]
        dyv = got[..., 2] - obs_pos[:, None, None, 1]
        within = (dxv * dxv + dyv * dyv) <= r2
        scoped = scope_mask(
            got[..., 3], got[..., 4],
            obs_scene[:, None, None], obs_group[:, None, None],
        )
        ok = member & present[..., None] & within & scoped
        rows = jnp.where(member, got[..., 0].astype(i32), 0)
        return rows.reshape(s, 9 * size), ok.reshape(s, 9 * size)

    with jax.named_scope("nf.interest.scan"):
        dy = jnp.asarray([d for d, _ in STENCIL], i32)
        dx = jnp.asarray([d for _, d in STENCIL], i32)
        yy, xx = oy[:, None] + dy[None, :], ox[:, None] + dx[None, :]
        in_grid = (yy >= 0) & (yy < w) & (xx >= 0) & (xx < w)
        cell = jnp.clip(yy, 0, w - 1) * w + jnp.clip(xx, 0, w - 1)
        first, count = seam.first[cell], seam.count[cell]  # [S, 9]
        j = jnp.arange(bucket, dtype=i32)
        rows, ok = read(first, bucket, j < count[..., None], in_grid)
    if depth > 0:
        # the rows an over-full cell keeps beyond its K, for those of
        # the nine that own a row of the level: the same tests
        with jax.named_scope("nf.interest.spill"):
            hot = seam.hot_of[cell] >= 0
            j = jnp.arange(depth, dtype=i32)
            rows2, ok2 = read(
                first + bucket, depth,
                hot[..., None] & (j < (count - bucket)[..., None]), in_grid)
        rows = jnp.concatenate([rows, rows2], axis=1)
        ok = jnp.concatenate([ok, ok2], axis=1)
    return InterestResult(rows=rows, ok=ok)


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
    its table always refreshes; only the sort is amortized), read by the
    same scan through `table_seam`.

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
    result = scan_sorted(
        table_seam(table), obs_pos, obs_scene, obs_group, radius,
        cell_size, width, bucket,
    )
    return result, cache, rebuilt
