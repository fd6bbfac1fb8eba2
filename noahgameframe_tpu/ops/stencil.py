"""Cell-table stencil engine: dense neighborhood queries without gathers.

This is the TPU-first replacement for the bucketed-grid + candidate-gather
pipeline in ops/aoi.py.  Measured on a real v5e, the old pipeline's
per-candidate irregular gathers (`pos[cand]`, `atk[cand]`, ... over
[N, 9K] index arrays) run at ~1% of HBM bandwidth and dominated the whole
world tick (~250 ms of a 264 ms tick at 131k entities).  Sorting, by
contrast, is nearly free (argsort of 131k int32 keys: 0.11 ms), and dense
shifted-window arithmetic rides the VPU at full throughput.

So the engine inverts the layout ONCE per query instead of gathering per
candidate:

1. `build_cell_table` sorts `(cell id, row)` pairs (one cheap sort that
   hands back the sorted keys with the order, so nothing is gathered to
   rank them), packs caller-chosen per-entity features into a dense
   `[n_cells*K + 1, F+1]` payload table and remembers each row's slot
   (`slot_of`, ONE un-sort scatter).  Entities beyond a cell's K slots
   land in the dump slot and are counted in `dropped` — size K from
   `auto_bucket` to keep that ~zero.  The build SENDS NO ROW to the
   table: a scatter on a v5e costs 85 ns for every row sent (2^20 of
   them a tick, 89 ms), a gather ~5 ns for every index followed.  After
   the sort a cell's members are one run of the sorted list, in the
   order its slots hold them, so the payload is GATHERED slot by slot
   from the sorted features (`_cell_starts`, `_slot_sources`,
   `table_from_sorted`) and only `slot_of`, which the pull reads, is
   still un-sorted by a scatter.  `build_cell_table_pair`, the build
   every tick makes, adds a SUBSET table (combat: this tick's
   attackers) whose irregular passes are priced by the subset, not by
   the bank: a second sort compacts the members to the front in cell
   order, their ranks and slots are streaming passes over that list,
   and only `sub_rows`-sized chunks of it are gathered and scattered.
2. `stencil_fold` walks the 3x3 neighborhood as NINE DENSE SHIFTS of the
   [H, W, K, F] grid view (one pad + nine fused slices — no index math,
   no gathers).  The caller folds candidate blocks against the resident
   victim block with plain vectorized arithmetic: [H, W, K, 9K] pairwise
   masked reductions, fully fused by XLA onto the VPU.
3. `pull` maps per-slot results back to per-row results with a single
   row-gather through `slot_of` (dropped/inactive rows read the appended
   identity element).

A cell far over its K slots (a spawn camp, a city) is not answered by
a deeper grid: both builds hang a SECOND LEVEL off the same sort
(`build_cell_table_pair(..., spill=...)`, `build_cell_table(...,
spill_cells, spill_bucket)`), priced by the over-full cells.  Their rows
beyond K get slots behind the dump slot (`_spill_slots`: streaming
passes over the ranks), filled by the same gather (victims) and the
same chunked scatter (attackers) as the base level's; the caller folds
the pairs the grid's fold cannot see (game/combat.py
`combat_fold_spill`) and `pull_slots(..., spill=...)` brings both
levels back in one gather; the interest scan reads, beside an
observer's nine cells, the second-level rows of those of the nine that
are over-full (`CellTable.hot_of`, ops/interest.py).  When a breach of
a caller's overflow budget is answered by the level and how large it
is made is one rule for both callers (`deep_cell`, `few_hot_cells`,
`second_level_size`).

Everything is static-shaped, jit/vmap/shard_map-friendly, and
deterministic (stable sort, gathers, unique-index scatters, fixed fold
order).

Reference parity note: this implements the spatial layer behind the
"AOI" broadcast of NFCSceneAOIModule (the reference's own AOI is
group-granular, NFCSceneAOIModule.cpp:531-593; the 2D-grid scan is
BASELINE config 3, and the AoE damage resolve of NFCSkillModule::OnUseSkill
is BASELINE config 4).
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional, Tuple, TypeVar

import jax
import jax.numpy as jnp

from .aoi import cell_of

A = TypeVar("A")

# 3x3 stencil in (dy, dx) order — must match ops.aoi._STENCIL so candidate
# iteration order (and therefore argmax tie-breaking) is identical across
# both engines.
STENCIL = [(dy, dx) for dy in (-1, 0, 1) for dx in (-1, 0, 1)]


class CellStats(NamedTuple):
    """What one build saw of its cells' depths: traced int32 scalars,
    streaming reductions over the sorted keys and ranks.

    hot_cells:  cells holding more members than the table's `bucket`
    rows_max:   members of the fullest cell
    spill_rows: members the second level placed (0 without one)
    """

    hot_cells: jnp.ndarray
    rows_max: jnp.ndarray
    spill_rows: jnp.ndarray


class CellTable(NamedTuple):
    """Sorted cell-dense payload table.

    payload: [n_cells*K + 1, F+1] — caller features + occupancy column
             (last col, 1.0 = slot holds a live entity).  The final row is
             the dump slot for inactive/overflowed entities; `grid_view`
             excludes it.
    slot_of: [N] int32 — flat payload slot per input row; dump slot
             (== n_cells*K) for rows not placed.
    dropped: scalar int32 — active entities that overflowed their cell.
    width, cell_size, bucket: static grid geometry.

    A table built with a SECOND LEVEL carries, behind the dump slot, `spill_cells` rows of
    `spill_bucket` slots: the rows the first `spill_cells` over-full
    cells (in cell order) hold beyond `bucket`, in row order.  A member
    placed there has `slot_of = n_cells*K + 1 + hot*spill_bucket + j`;
    `spill_view` is that part, `grid_view` stays the base level.
    """

    payload: jnp.ndarray
    slot_of: jnp.ndarray
    dropped: jnp.ndarray
    width: int
    cell_size: float
    bucket: int
    # rectangular grids (spatial slab sharding): rows of the grid; -1
    # means square (height == width).  Trailing default keeps the many
    # existing 6-field positional constructions valid.
    height: int = -1
    spill_cells: int = 0
    spill_bucket: int = 0
    stats: Optional[CellStats] = None
    # [n_cells] int32: a cell's row of the second level, -1 for a cell
    # that has none.  Carried where a caller reads the level by cell
    # (`build_cell_table`: the interest scan); combat reads it by rank.
    hot_of: Optional[jnp.ndarray] = None

    @property
    def n_cells(self) -> int:
        return (self.height if self.height > 0 else self.width) * self.width

    def grid_view(self) -> jnp.ndarray:
        """[H, W, K, F+1] dense view (dump slot and second level
        excluded)."""
        h = self.height if self.height > 0 else self.width
        w = self.width
        k = self.bucket
        return self.payload[: h * w * k].reshape(
            h, w, k, self.payload.shape[-1])

    def spill_view(self) -> jnp.ndarray:
        """[spill_cells, spill_bucket, F+1]: the second level's rows."""
        return self.payload[self.n_cells * self.bucket + 1:].reshape(
            self.spill_cells, self.spill_bucket, self.payload.shape[-1])


def auto_bucket(
    capacity: int, width: int, lo: int = 8, hi: int = 256, align: int = 4
) -> int:
    """Pick K so uniform occupancy ~Poisson(capacity/cells) stays under
    the overflow budget: mean + 2.5*sqrt(mean) + 2, rounded up to a
    multiple of `align` within [lo, hi].  Fold cost scales with K^2, so
    the margin is the thinnest that keeps expected drops well below 0.1%
    of entities (capacity already overstates live density by up to 2x,
    which is extra headroom; the bound is pinned by tests/test_stencil.py).
    Sparse candidate tables (the combat attacker side) pass align=2 —
    at occupancy ~0.2/cell the rounding from 6 to 8 alone would cost
    +33% fold work.

    Entities beyond a cell's K slots are dropped from that query (counted
    in CellTable.dropped) — they neither see nor are seen by neighbors
    that tick.  Callers passing an explicit small bucket accept drops
    under crowding."""
    lam = capacity / float(max(width * width, 1))
    k = int(math.ceil(lam + 2.5 * math.sqrt(max(lam, 1.0)) + 2.0))
    k = max(lo, min(hi, k))
    return -(-k // align) * align


# When a budget breach is answered by the second level, and how large
# the level is made: one rule behind combat's tables (game/combat.py
# `_answer_breach`, which has the counts behind each constant) and the
# interest table (net/roles/game.py `_answer_interest_breach`).
SPILL_MIN_OVERDEPTH = 4  # times over the base depth before it is thought of
SPILL_MAX_GRID_SHARE = 0.5  # of the base table's slots, what was seen
SPILL_CELLS_HEADROOM = 1.5
SPILL_DEPTH_HEADROOM = 1.75


def deep_cell(rows_max: int, bucket: int,
              min_overdepth: float = SPILL_MIN_OVERDEPTH) -> bool:
    """The fullest cell is so far over the base depth that doubling the
    depth of every cell is the wrong answer to it."""
    return rows_max > min_overdepth * bucket


def few_hot_cells(hot_cells: int, rows_max: int, bucket: int, n_cells: int,
                  max_grid_share: float = SPILL_MAX_GRID_SHARE) -> bool:
    """A second level that just holds what was seen (over-full cells x
    the deepest one's excess) takes at most `max_grid_share` of the base
    table's slots: its streaming passes go by its slots as the base
    level's go by the grid's."""
    return (hot_cells * max(rows_max - bucket, 0)
            <= max_grid_share * n_cells * bucket)


def second_level_size(
    hot_cells: int, rows_max: int, bucket: int,
    cells_headroom: float = SPILL_CELLS_HEADROOM,
    depth_headroom: float = SPILL_DEPTH_HEADROOM,
) -> Tuple[int, int]:
    """(cells, depth) that hold what a build observed, with headroom so
    that the crowd's drift brings no second retrace, each rounded up to
    a power of two so that worlds that differ by a seed trace the same
    program; the depth 32 at least (whole blocks)."""
    cells = 1 << math.ceil(math.log2(cells_headroom * max(hot_cells, 1)))
    over = max(rows_max - bucket, 1)
    depth = 1 << max(5, math.ceil(math.log2(depth_headroom * over)))
    return cells, depth


def _cell_keys(pos, active, cell_size: float, width: int,
               cell=None, n_cells: int | None = None):
    """The key pass of every build: per-row sort key (cell id, or n_cells
    for inactive rows).  Returns (n_cells, key).

    cell/n_cells: precomputed per-row cell ids over a caller-defined
    (possibly rectangular) grid — the spatial slab shards pass local
    slab-relative ids; default derives square-grid ids from pos."""
    n = pos.shape[0]
    if n >= 1 << 24:
        # row ids (and other int-valued columns) ride in f32 payload
        # columns, exact only below 2^24 — refuse silent corruption
        raise ValueError(f"cell table capacity {n} >= 2^24 breaks f32 row ids")
    if cell is None:
        n_cells = width * width
        cell = cell_of(pos, cell_size, width)
    elif n_cells is None:
        raise ValueError("precomputed cell ids need n_cells")
    key = jnp.where(active, cell, n_cells)
    return n_cells, key


def _key_segments(key: jnp.ndarray):
    """The build's one sort and the streaming passes behind it, for any
    per-row key: (order, skey, rank), `rank` being every sorted element's
    ordinal inside its run of equal keys.  The sort is the stable sort of
    `(key, row)` that `jnp.argsort(key)` runs inside, with both results
    kept: `skey` is the sort's own key result, not a `key[order]` gather
    (an N-row irregular pass, 9 ms at 2^20 on a v5e, to re-read what the
    sort had just put in order)."""
    # stable: preserves row order within a cell
    rows = jnp.arange(key.shape[0], dtype=jnp.int32)
    skey, order = jax.lax.sort((key, rows), num_keys=1, is_stable=True)
    idx = jnp.arange(skey.shape[0], dtype=jnp.int32)
    seg_start = jnp.concatenate(
        [jnp.ones((1,), bool), skey[1:] != skey[:-1]]
    )
    # index of each sorted element's segment head, via running max
    start_idx = jax.lax.cummax(jnp.where(seg_start, idx, 0))
    return order, skey, idx - start_idx


def _sorted_slots(n_cells: int, skey, rank, bucket: int) -> jnp.ndarray:
    """Flat payload slot of every SORTED element: `skey * bucket + rank`
    where the rank fits the cell, the dump slot otherwise (overflow,
    inactive).  The one placement rule."""
    dump = n_cells * bucket
    placed = (rank < bucket) & (skey < n_cells)
    return jnp.where(placed, skey * bucket + rank, dump)


def _spill_slots(
    n_cells: int, skey, rank, bucket: int, cells: int, depth: int
) -> Tuple[jnp.ndarray, CellStats]:
    """`_sorted_slots` with a second level, and what the build saw.

    Streaming passes over the sorted keys and ranks, nothing irregular.
    A cell is over-full when it holds more than `bucket` members; its
    member of rank `bucket` heads its overflow, and the running count of
    heads numbers the over-full cells in cell order.  A member of rank
    `bucket + j` in the `hot`-th over-full cell is placed in the second
    level (slot `dump + 1 + hot * depth + j`) when `hot < cells` and
    `j < depth`, and in the dump slot otherwise: so what is dropped is
    still the highest rows of a cell, whatever level the others sit in.
    With `cells == 0` the slots are `_sorted_slots`' own."""
    i32 = jnp.int32
    valid = skey < n_cells
    head = valid & (rank == bucket)
    slots = _sorted_slots(n_cells, skey, rank, bucket)
    hot_cells = jnp.sum(head, dtype=i32)
    rows_max = jnp.max(jnp.where(valid, rank + 1, 0))
    if cells <= 0 or depth <= 0:
        return slots, CellStats(hot_cells, rows_max, jnp.zeros((), i32))
    hot = jnp.cumsum(head.astype(i32)) - 1
    j = rank - bucket
    placed = valid & (j >= 0) & (hot < cells) & (j < depth)
    slots = jnp.where(placed, n_cells * bucket + 1 + hot * depth + j, slots)
    return slots, CellStats(hot_cells, rows_max, jnp.sum(placed, dtype=i32))


def _cell_starts(n_cells: int, skey: jnp.ndarray) -> jnp.ndarray:
    """`start[c]`, c in 0..n_cells: the sorted index of cell c's first
    member, that is the number of sorted keys below c (so `start[c + 1] -
    start[c]` members, and `start[n_cells]` placed keys in all).

    No pass here is priced by the bank's rows but a sort: the heads of
    the runs are compacted to the front by a third sort (1 ms at 2^20
    keys on a v5e, where a scatter of as many single words is 4.85), the
    first `n_cells` of them are scattered into the table by their cell,
    and a reverse running minimum gives an empty cell the start of the
    next cell that has a member."""
    n = skey.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    valid = skey < n_cells
    n_valid = jnp.sum(valid, dtype=jnp.int32)
    head = valid & jnp.concatenate(
        [jnp.ones((1,), bool), skey[1:] != skey[:-1]])
    # a head's key is its cell and no other head's; everything else
    # sorts behind them and writes `n_valid` where `n_valid` stands
    hcell, hidx = jax.lax.sort(
        (jnp.where(head, skey, n_cells), idx), num_keys=1, is_stable=False)
    m = min(n, n_cells)
    hcell, hidx = hcell[:m], hidx[:m]
    starts = jnp.full((n_cells + 1,), n_valid, jnp.int32).at[hcell].set(
        jnp.where(hcell < n_cells, hidx, n_valid))
    return jax.lax.cummin(starts, reverse=True)


def _slot_sources(
    start: jnp.ndarray, n_cells: int, bucket: int, cells: int, depth: int
) -> list:
    """Where the slots of the full table find their rows in the sorted
    list, a level at a time: `(first, count, depth)`, slot `j` of the
    level's cell `i` holding sorted entry `first[i] + j` while `j <
    count[i]`, and zeros otherwise.  The members of a cell are one run
    of the sorted list, rows ascending, which is the order its slots
    hold them in: so the base level reads `start[c]` onwards, and the
    second level (`_spill_slots`' numbering: the `hot`-th over-full cell
    in cell order) reads `start[c] + bucket` onwards of that cell `c`,
    found by a search over the running count of over-full cells, `cells`
    queries: nothing here is priced by the bank."""
    i32 = jnp.int32
    count = start[1:] - start[:-1]
    levels = [(start[:-1], count, bucket)]
    if cells > 0 and depth > 0:
        hot_no = _hot_numbers(count, bucket)
        cell_at = jnp.searchsorted(
            hot_no, jnp.arange(1, cells + 1, dtype=i32), side="left",
            method="scan_unrolled").astype(i32)
        there = cell_at < n_cells
        cell_at = jnp.minimum(cell_at, n_cells - 1)
        levels.append((
            start[cell_at] + bucket,
            jnp.where(there, count[cell_at] - bucket, 0), depth))
    return levels


def _hot_numbers(count: jnp.ndarray, bucket: int) -> jnp.ndarray:
    """Running count of over-full cells, in cell order: an over-full
    cell `c` is the `hot_no[c] - 1`-th (`_spill_slots`' numbering)."""
    return jnp.cumsum((count > bucket).astype(jnp.int32))


def hot_index(start: jnp.ndarray, bucket: int, cells: int) -> jnp.ndarray:
    """`CellTable.hot_of` from the cells' starts: the second-level row
    of every over-full cell among the first `cells`, -1 elsewhere."""
    count = start[1:] - start[:-1]
    hot = _hot_numbers(count, bucket) - 1
    return jnp.where((count > bucket) & (hot < cells), hot, -1)


# a run of the run table: this many sorted entries, in one lane tile
RUN_ENTRIES = 16
RUN_WORDS = 128


def run_length(depth: int, n_feats: int) -> int:
    """Consecutive slots of a cell that `table_from_sorted` fills from
    one gathered row: `RUN_ENTRIES` where that divides the cell's
    `depth` and a run of `n_feats`-word entries fits a lane tile, else 1
    (a slot a gathered row).  Read on the chip, in the tick (PERF.md §6
    PR 31): at 32 deep runs of 16 take the 1M tick from 94.2 ms to 74.2
    where runs of 4 and 8 read 90.5 and 99.3 (a run that fills under
    half a lane tile costs more to lay out than its fewer indices save);
    at 20 deep under `vmap` runs of 4, 5 and 10 read 63.0, 74.1 and 64.7
    against 59.6 row by row."""
    if depth % RUN_ENTRIES == 0 and RUN_ENTRIES * n_feats <= RUN_WORDS:
        return RUN_ENTRIES
    return 1


def table_from_sorted(
    features: jnp.ndarray, order: jnp.ndarray, levels: list
) -> jnp.ndarray:
    """Payload `[slots, F + 1]` GATHERED from the sorted list, no row
    sent anywhere.  Bit for bit what `table_from_slots` scatters from
    the same assignment; the dump slot (behind the first level) is a row
    of zeros by construction.

    A gather on a v5e costs ~5 ns for every index it follows when the
    row behind it is five words (16.8 ns when it is one:
    `scripts/scatter_probe.py`), a scatter 85 ns for every row it sends.
    So the features are gathered once into sorted order
    (`features[order]`) and laid out as RUNS: row `i` of the run table
    holds sorted entries `i .. i + g - 1`, feature by feature, `g` from
    `run_length`.  A cell's slots are consecutive entries
    (`_slot_sources`), so one gathered row fills `g` of them and a
    feature's plane of the table is a slice of lanes: `depth / g`
    indices a cell where the scatter sent a row a member.  With `g` 1
    the run table is the sorted features themselves."""
    n, f = features.shape
    dtype = features.dtype
    feats = features[order]
    runs = {}

    def run_table(g):
        if g not in runs:
            padded = jnp.pad(feats, ((0, g - 1), (0, 0)))
            runs[g] = jnp.stack(
                [padded[k:k + n, i] for i in range(f) for k in range(g)],
                axis=-1)
        return runs[g]

    planes = [[] for _ in range(f + 1)]
    for level, (first, count, depth) in enumerate(levels):
        g = run_length(depth, f)
        heads = first[:, None] + g * jnp.arange(depth // g, dtype=jnp.int32)
        # (a table of occupancy alone gathers nothing)
        got = run_table(g)[jnp.minimum(heads, n - 1)] if f else None
        live = jnp.arange(depth, dtype=jnp.int32) < count[:, None]
        for i in range(f):
            plane = got[..., i * g:(i + 1) * g].reshape(live.shape)
            planes[i].append(
                jnp.where(live, plane, jnp.zeros((), dtype)).reshape(-1))
        planes[f].append(live.astype(dtype).reshape(-1))
        if level == 0:  # the dump slot
            for plane in planes:
                plane.append(jnp.zeros((1,), dtype))
    return jnp.stack([jnp.concatenate(p) for p in planes], axis=-1)


def _slots_from_ranks(
    n: int, n_cells: int, order, skey, rank, bucket: int
) -> jnp.ndarray:
    """Per-row slot assignment from sorted segment ranks: un-sort
    `skey * bucket + rank` back to row order (one scatter).  Shared by
    both builders and the Verlet rebuild (ops/verlet.py) so the placement
    math cannot drift between them."""
    dump = n_cells * bucket
    flat_sorted = _sorted_slots(n_cells, skey, rank, bucket)
    return jnp.full((n,), dump, jnp.int32).at[order].set(flat_sorted)


def sub_chunks(sub_mask: jnp.ndarray, sub_rows: int) -> jnp.ndarray:
    """Chunks of `sub_rows` sorted entries that `build_cell_table_pair`
    sends for this subset: ceil(members / sub_rows) as a traced i32
    scalar, and 1 with no member (the first chunk always goes).  The
    build sends exactly this many; callers count it to see how the chunk
    they chose engages."""
    sub_rows = max(1, min(sub_rows, sub_mask.shape[0]))
    n_sub = jnp.sum(sub_mask, dtype=jnp.int32)
    return jnp.maximum((n_sub + (sub_rows - 1)) // sub_rows, 1)


def _chunked_payload(
    features, order, flat_sorted, n_chunks, dump: int, sub_rows: int,
    spill_slots: int = 0,
) -> jnp.ndarray:
    """Payload table of a COMPACTED sorted list (members first): gather
    and scatter `sub_rows` sorted entries a chunk, `n_chunks` chunks.
    An entry past the members carries the dump slot, so a chunk that
    reaches beyond them writes nothing that stays.  `spill_slots` more
    slots lie behind the dump slot (the second level: a member placed
    there rides the same chunk, gathered once, scattered once).

    The first chunk is a static slice and always goes: it is the whole
    job whenever `sub_rows` was sized for the subset.  Further chunks
    run in a loop with a traced trip count (under `vmap` it runs to the
    busiest lane's) and take their entries by an index vector, not by
    `dynamic_slice`: under `vmap` the trip counter is per lane, and
    XLA:TPU lowers a dynamic slice with a batched start as a loop over
    the batch.  The last chunk of a list `sub_rows` does not divide is
    clamped back inside it; writing an entry twice writes the same row
    to the same slot."""
    n, f = features.shape
    occ = jnp.ones((sub_rows, 1), features.dtype)
    lanes = jnp.arange(sub_rows, dtype=jnp.int32)

    def put(payload, rows, slots):
        feats = jnp.concatenate([features[rows], occ], axis=-1)
        return payload.at[slots].set(feats)

    def one_chunk(c, payload):
        at = jnp.minimum(c * sub_rows, n - sub_rows) + lanes
        return put(payload, order[at], flat_sorted[at])

    payload = put(
        jnp.zeros((dump + 1 + spill_slots, f + 1), features.dtype),
        order[:sub_rows], flat_sorted[:sub_rows],
    )
    payload = jax.lax.fori_loop(1, n_chunks, one_chunk, payload)
    # dump slot may have been written by any loser; force it empty
    return payload.at[dump].set(0.0)


def table_from_slots(
    features, active, slot_of, n_cells: int,
    cell_size: float, width: int, bucket: int, height: int = -1,
    spill: Tuple[int, int] = (0, 0), stats: Optional[CellStats] = None,
) -> CellTable:
    """Materialize a CellTable from a PRECOMPUTED slot assignment: ONE
    deterministic payload scatter (unique slot indices for placed rows),
    dump-slot zeroing, drop count.  This is the sort-free half of the
    build — the Verlet cache (ops/verlet.py) replays it every reuse tick
    against the cached `slot_of` while skipping the argsort entirely.
    Where the sorted list is at hand (both builds of this file),
    `table_from_sorted` makes the same payload with no row sent.
    Rows not `active` are forced to the dump slot regardless of their
    cached assignment (a cache is only reused while the active set is
    unchanged, but a zero-initialized cache must stay harmless).

    `spill = (cells, depth)`: that many second-level slots lie behind
    the dump slot, and `slot_of` may name them (`_spill_slots`): a row
    placed there rides the same scatter, at no row more."""
    n = features.shape[0]
    dump = n_cells * bucket
    spill_cells, spill_bucket = spill
    slot_of = jnp.where(active, slot_of, dump)
    occ = jnp.ones((n, 1), features.dtype)
    feats = jnp.concatenate([features, occ], axis=-1)
    payload = (
        jnp.zeros((dump + 1 + spill_cells * spill_bucket, feats.shape[-1]),
                  features.dtype)
        .at[slot_of]
        .set(feats)
    )
    # dump slot may have been written by any loser; force it empty
    payload = payload.at[dump].set(0.0)
    dropped = jnp.sum(active & (slot_of == dump), dtype=jnp.int32)
    return CellTable(payload, slot_of, dropped, width, cell_size, bucket,
                     height, spill_cells, spill_bucket, stats)


def _rank_full(
    n_cells: int, key, active, bucket: int, spill_cells: int,
    spill_bucket: int,
):
    """What a full table is ranked from, nothing here priced by a row
    but the sorts and the un-sort of `slot_of`: (order, slot_of,
    dropped, stats, start).  One sort of the keys, the slots of both
    levels from the ranks (`_spill_slots`), their un-sort back to row
    order (one scatter: what a pull reads; a caller that reads no
    `slot_of` has it dropped by the compiler), the count of what fits
    neither level, and every cell's start in the sorted list (a third
    sort, a scatter of the cells' heads, streaming)."""
    n = key.shape[0]
    order, skey, rank = _key_segments(key)
    sorted_slots, stats = _spill_slots(
        n_cells, skey, rank, bucket, spill_cells, spill_bucket)
    dump = n_cells * bucket
    slot_of = jnp.full((n,), dump, jnp.int32).at[order].set(sorted_slots)
    dropped = (
        jnp.sum(active, dtype=jnp.int32)
        - jnp.sum(sorted_slots != dump, dtype=jnp.int32)
    )
    return order, slot_of, dropped, stats, _cell_starts(n_cells, skey)


def build_cell_table(
    pos: jnp.ndarray,
    active: jnp.ndarray,
    features: jnp.ndarray,
    cell_size: float,
    width: int,
    bucket: int,
    spill_cells: int = 0,
    spill_bucket: int = 0,
) -> CellTable:
    """Bin `active` entities into the uniform grid, carrying `features`:
    the one-table case of `build_cell_table_pair`.

    pos: [N, >=2] positions; active: [N] bool; features: [N, F] float32.
    Two sorts and a scatter of the cells' heads (`_rank_full`), the
    payload gathered from the sorted list (`table_from_sorted`: no row
    is sent; bit for bit what `table_from_slots` scatters to the same
    slots), and `slot_of` un-sorted by one scatter for the callers that
    pull through it.

    `spill_cells`, `spill_bucket`: the SECOND LEVEL, as the pair build's
    (`_spill_slots`): the first `spill_cells` over-full cells in cell
    order keep `spill_bucket` rows beyond `bucket`, `dropped` counts
    what fits neither level, `stats` what the build saw of its cells,
    and `hot_of` names each cell's row of the level for a caller that
    reads it by cell (ops/interest.py).  0 and 0: one level, and
    nothing of the second is traced."""
    n_cells, key = _cell_keys(pos, active, cell_size, width)
    if spill_cells and spill_bucket <= 0:
        raise ValueError(
            f"second level ({spill_cells}, {spill_bucket}): a depth of 0")
    if spill_cells <= 0:
        spill_cells = spill_bucket = 0
    order, slot_of, dropped, stats, start = _rank_full(
        n_cells, key, active, bucket, spill_cells, spill_bucket)
    levels = _slot_sources(start, n_cells, bucket, spill_cells, spill_bucket)
    hot_of = hot_index(start, bucket, spill_cells) if spill_cells else None
    return CellTable(
        table_from_sorted(features, order, levels), slot_of, dropped, width,
        cell_size, bucket, -1, spill_cells, spill_bucket, stats, hot_of,
    )


def build_cell_table_pair(
    pos: jnp.ndarray,
    active: jnp.ndarray,
    features: jnp.ndarray,
    sub_mask: jnp.ndarray,
    sub_features: jnp.ndarray,
    cell_size: float,
    width: int,
    bucket: int,
    sub_bucket: int,
    cell: jnp.ndarray | None = None,
    height: int = -1,
    sub_rows: int | None = None,
    spill: Tuple[int, int, int] = (0, 0, 0),
) -> Tuple[CellTable, CellTable]:
    """Build the full table AND a subset table from ONE key pass: two
    sorts (the whole population, then the subset's keys), both tables
    ranked from the sorted keys, and a third sort that finds where each
    cell's run starts.

    `sub_mask` must be a subset of `active` (combat: attackers among all
    alive entities).  Placement is bit-identical to two independent
    `build_cell_table` calls — within a cell both tables hold rows in
    ascending order, and the subset ranks are the subset's own ordinal
    positions.  What differs is the price.  The full table is priced by
    its slots and not by the bank's rows: its payload is gathered from
    the sorted list (`table_from_sorted`; nothing is scattered but
    `slot_of`, which the pull reads), bit for bit what
    `table_from_slots` would scatter to the same slots.  The subset's
    irregular passes go by its members: the second sort (1 ms at 2^20
    rows on a v5e, where one N-row gather or scatter is 5-40) puts the
    members first, so their features are gathered and their payload rows
    scattered `sub_rows` sorted entries at a time, ceil(members /
    sub_rows) chunks (`sub_chunks`): one chunk when the caller sized
    `sub_rows` for the subset it expects, more when a tick exceeds it —
    slower then, never different.  Default `sub_rows` is the whole bank
    (one chunk: an N-row gather and scatter).

    cell/height: precomputed cell ids over a rectangular [height, width]
    grid (spatial slab shards); default square grid derived from pos.

    `spill = (cells, depth, sub_depth)`: a SECOND LEVEL behind both
    tables, priced by the over-full cells and not by the grid.  The
    first `cells` over-full cells of either table (in cell order, each
    table counting its own) keep `depth` (`sub_depth`) members beyond
    the bucket, in row order; `_spill_slots` makes their slots from the
    ranks this build has anyway, and the rows behind each table's dump
    slot are filled by the gather (full table: `_slot_sources` finds the
    over-full cells' runs) and the chunked scatter (subset) it makes
    anyway.  What fits neither level is dropped and counted as before.
    (0, 0, 0) is the one-level build, the same program but for the
    reductions of `CellTable.stats` (both tables carry them either way).

    The one call here that both ranks and builds, and only combat makes
    it, so it opens the device scopes `nf.aoe.rank` (sorts, heads, ranks,
    slots, the cells' starts) and `nf.aoe.table` (the victim gathers, the
    subset's chunk gather and scatter) itself; every other scope of the
    neighbour engine is opened by the caller (game/combat.py), because
    the interest programs share this file."""
    n_rows = height if height > 0 else width
    n = pos.shape[0]
    sub_rows = n if sub_rows is None else max(1, min(sub_rows, n))
    with jax.named_scope("nf.aoe.rank"):
        n_cells, key = _cell_keys(
            pos, active, cell_size, width, cell=cell,
            n_cells=(n_rows * width if cell is not None else None),
        )
        spill_cells, spill_bucket, sub_spill_bucket = spill
        if spill_cells and (spill_bucket <= 0 or sub_spill_bucket <= 0):
            raise ValueError(f"second level {spill}: a depth of 0")
        # ranks, slots, the un-sort of `slot_of` (what the pull reads)
        # and where every slot of the full table finds its row in the
        # sorted list: a third sort, a scatter of the cells' heads
        order, slot_of, dropped, stats, start = _rank_full(
            n_cells, key, active, bucket, spill_cells, spill_bucket)
        levels = _slot_sources(
            start, n_cells, bucket, spill_cells, spill_bucket)
        # the subset, compacted by a second sort: members first, in cell
        # order, rows ascending inside a cell — the order their ranks
        # count in.  Heads, ranks and slots are streaming passes over
        # that list; nothing here gathers or scatters by row.
        sub_key = jnp.where(sub_mask, key, n_cells)
        sub_order, sub_skey, sub_rank = _key_segments(sub_key)
        sub_sorted_slots, sub_stats = _spill_slots(
            n_cells, sub_skey, sub_rank, sub_bucket, spill_cells,
            sub_spill_bucket)
        sub_dump = n_cells * sub_bucket
        sub_dropped = (
            jnp.sum(sub_mask, dtype=jnp.int32)
            - jnp.sum(sub_sorted_slots != sub_dump, dtype=jnp.int32)
        )
        n_chunks = sub_chunks(sub_mask, sub_rows)
        # per-row slots keep their contract for the callers that pull
        # through them (one un-sort scatter); the combat fold does not,
        # and the compiler drops the scatter from the tick program
        sub_slot_of = jnp.full((n,), sub_dump, jnp.int32).at[sub_order].set(
            sub_sorted_slots)
    with jax.named_scope("nf.aoe.table"):
        full = CellTable(
            table_from_sorted(features, order, levels), slot_of, dropped,
            width, cell_size, bucket, height, spill_cells, spill_bucket,
            stats,
        )
        sub_payload = _chunked_payload(
            sub_features, sub_order, sub_sorted_slots, n_chunks, sub_dump,
            sub_rows, spill_cells * sub_spill_bucket,
        )
    sub = CellTable(
        sub_payload, sub_slot_of, sub_dropped, width, cell_size, sub_bucket,
        height, spill_cells, sub_spill_bucket, sub_stats,
    )
    return full, sub


def stencil_fold(
    table: CellTable,
    fold: Callable[[A, jnp.ndarray], A],
    init: A,
) -> A:
    """Fold `fold(acc, cand)` over the nine shifted candidate blocks.

    cand: [H, W, K, F+1] — the neighbor cell's payload aligned onto every
    cell (edge neighbors read zero payload => occupancy 0).  Iteration
    order is STENCIL order; keep reductions order-insensitive or rely on
    that fixed order for tie-breaking.
    """
    v = table.grid_view()
    h, w, k, f = v.shape
    vp = jnp.pad(v, ((1, 1), (1, 1), (0, 0), (0, 0)))
    acc = init
    for dy, dx in STENCIL:
        cand = jax.lax.slice(
            vp, (dy + 1, dx + 1, 0, 0), (dy + 1 + h, dx + 1 + w, k, f)
        )
        acc = fold(acc, cand)
    return acc


def pull_slots(
    slot_of: jnp.ndarray, values: jnp.ndarray,
    fill: float | Tuple[float, ...] = 0.0,
    spill: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Map per-slot results [H, W, K] or [H, W, K, V] back to rows [N] /
    [N, V] with one gather through a raw slot array; unplaced rows (dump
    slot) read `fill`.  The slot-only half of `pull` (the combat phase
    pulls two values through one victim slot array)."""
    squeeze = values.ndim == 3
    if squeeze:
        values = values[..., None]
    nv = values.shape[-1]
    flat = values.reshape(-1, nv)
    fill_row = jnp.broadcast_to(
        jnp.asarray(fill, values.dtype).reshape(-1), (nv,)
    )
    parts = [flat, fill_row[None, :]]
    if spill is not None:
        parts.append(spill.reshape(-1, nv).astype(values.dtype))
    flat = jnp.concatenate(parts, axis=0)
    out = flat[slot_of]
    return out[..., 0] if squeeze else out


def pull(
    table: CellTable, values: jnp.ndarray, fill: float | Tuple[float, ...] = 0.0
) -> jnp.ndarray:
    """`pull_slots` through a CellTable's slot assignment."""
    return pull_slots(table.slot_of, values, fill)
