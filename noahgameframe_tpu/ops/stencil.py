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
   `[n_cells*K + 1, F+1]` payload table with ONE un-sort scatter of the
   slots and ONE payload scatter (unique slot indices, deterministic),
   and remembers each row's slot (`slot_of`).  Entities beyond a cell's
   K slots land in the dump slot and are counted in `dropped` — size K
   from `auto_bucket` to keep that ~zero.  `build_cell_table_pair` adds
   a SUBSET table (combat: this tick's attackers) whose irregular passes
   are priced by the subset, not by the bank: a second sort compacts the
   members to the front in cell order, their ranks and slots are
   streaming passes over that list, and only `sub_rows`-sized chunks of
   it are gathered and scattered.
2. `stencil_fold` walks the 3x3 neighborhood as NINE DENSE SHIFTS of the
   [H, W, K, F] grid view (one pad + nine fused slices — no index math,
   no gathers).  The caller folds candidate blocks against the resident
   victim block with plain vectorized arithmetic: [H, W, K, 9K] pairwise
   masked reductions, fully fused by XLA onto the VPU.
3. `pull` maps per-slot results back to per-row results with a single
   row-gather through `slot_of` (dropped/inactive rows read the appended
   identity element).

Everything is static-shaped, jit/vmap/shard_map-friendly, and
deterministic (stable sort + unique-index scatter + fixed fold order).

Reference parity note: this implements the spatial layer behind the
"AOI" broadcast of NFCSceneAOIModule (the reference's own AOI is
group-granular, NFCSceneAOIModule.cpp:531-593; the 2D-grid scan is
BASELINE config 3, and the AoE damage resolve of NFCSkillModule::OnUseSkill
is BASELINE config 4).
"""

from __future__ import annotations

import math
import os
from typing import Callable, NamedTuple, Tuple, TypeVar

import jax
import jax.numpy as jnp

from .aoi import cell_of

A = TypeVar("A")

# NF_BINNING picks the slot-assignment engine behind build_cell_table /
# build_cell_table_pair (and the Verlet rebuild arm).  "sort" is the
# original stable-argsort path; "count" is the sort-free counting path
# (_cell_counts / _counting_ranks / _counting_slots) — bit-identical
# tables, O(K*(N + n_cells)) streaming work instead of an O(N log N)
# comparison network.  Trace-time like NF_RADIX: flip it, then retrace.
ENV_BINNING = "NF_BINNING"
BINNING_MODES = ("sort", "count")


def binning_mode() -> str:
    """The validated NF_BINNING mode; unset/empty means "sort".

    Unknown values raise instead of falling through — a typo'd mode
    silently running the default would invalidate any A/B it labeled.
    This is the ONLY place the env var is read (pinned by
    tests/test_binning.py's lint guard)."""
    # nf-lint: disable=trace-safety -- sanctioned A/B knob: read once at
    # trace time and baked into the compiled tick; tests pin this as the
    # only NF_BINNING read and flipping it requires a fresh jit cache
    raw = os.environ.get(ENV_BINNING, "").strip()
    if not raw:
        return "sort"
    if raw not in BINNING_MODES:
        raise ValueError(
            f"{ENV_BINNING}={raw!r}: expected one of {BINNING_MODES}"
        )
    return raw

# 3x3 stencil in (dy, dx) order — must match ops.aoi._STENCIL so candidate
# iteration order (and therefore argmax tie-breaking) is identical across
# both engines.
STENCIL = [(dy, dx) for dy in (-1, 0, 1) for dx in (-1, 0, 1)]


class CellSlots(NamedTuple):
    """A slot assignment WITHOUT the payload materialization.

    The fused Pallas engine (ops/stencil_pallas.py, NF_PALLAS=2) gathers
    features straight from the SoA banks via these slots, so the padded
    `[n_cells*K + 1, F+1]` payload table — the biggest per-frame HBM
    materialization of the split path — is never written.  Same slot
    semantics as CellTable (dump slot == n_cells*K for unplaced rows,
    `dropped` counts active overflow), minus the scatter.
    """

    slot_of: jnp.ndarray
    dropped: jnp.ndarray
    width: int
    cell_size: float
    bucket: int
    height: int = -1


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

    def grid_view(self) -> jnp.ndarray:
        """[H, W, K, F+1] dense view (dump slot excluded)."""
        h = self.height if self.height > 0 else self.width
        w = self.width
        k = self.bucket
        return self.payload[:-1].reshape(h, w, k, self.payload.shape[-1])


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


def _radix_argsort(
    key: jnp.ndarray, n_bits: int, bits_per_pass: int = 1
) -> jnp.ndarray:
    """Stable LSD radix argsort for small non-negative int keys.

    XLA's TPU `sort` is a comparison network with poor large-N
    efficiency; per docs/ROOFLINE.md it is the prime suspect for the
    1M-tick gap.  This replaces it with ceil(n_bits / bits_per_pass)
    stable partition passes — streaming cumsums plus two unique-index
    scatters per pass over [N] i32 — instead of O(log^2 N) comparison
    stages.  Bit-identical to `jnp.argsort(key)` (both stable).

    bits_per_pass trades cumsum work for scatter count: the two
    permutation scatters are the irregular (bandwidth-hostile) part of
    a pass, so 2-3 bits per pass cuts them 2-3x while the added
    per-digit cumsum planes ([N, 2^b] one-hot) stay cheap streaming
    work.  Opt-in via NF_RADIX=<bits_per_pass> until chip time ranks
    the variants against XLA's sort (virtual-CPU timing cannot)."""
    n = key.shape[0]
    order = jnp.arange(n, dtype=jnp.int32)
    b = max(1, int(bits_per_pass))
    n_digits = 1 << b
    n_passes = -(-n_bits // b)
    mask = n_digits - 1

    if b == 1:
        def one_pass(i, kv):
            k, o = kv
            bit = (k >> (i * 1)) & 1
            zeros = jnp.cumsum(1 - bit)  # inclusive; stable in each half
            ones = jnp.cumsum(bit)
            pos = jnp.where(bit == 0, zeros - 1, zeros[-1] + ones - 1)
            return (
                jnp.zeros_like(k).at[pos].set(k),
                jnp.zeros_like(o).at[pos].set(o),
            )
    else:
        def one_pass(i, kv):
            k, o = kv
            digit = (k >> (i * b)) & mask
            onehot = (
                digit[:, None] == jnp.arange(n_digits, dtype=k.dtype)[None, :]
            ).astype(jnp.int32)
            incl = jnp.cumsum(onehot, axis=0)  # [N, D] running count per digit
            totals = incl[-1]
            base = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32), jnp.cumsum(totals)[:-1]]
            )
            rank = jnp.take_along_axis(incl, digit[:, None], axis=1)[:, 0]
            pos = base[digit] + rank - 1
            return (
                jnp.zeros_like(k).at[pos].set(k),
                jnp.zeros_like(o).at[pos].set(o),
            )

    _, order = jax.lax.fori_loop(0, n_passes, one_pass, (key, order))
    return order


def _bits_for(n_cells: int) -> int:
    """Bits needed for keys in [0, n_cells] (the inactive key IS
    n_cells, so it must be representable)."""
    return max(1, int(n_cells).bit_length())


def _cell_keys(pos, active, cell_size: float, width: int,
               cell=None, n_cells: int | None = None):
    """Shared key pass for BOTH binning engines: per-row sort/bin key
    (cell id, or n_cells for inactive rows).  Returns (n_cells, key).

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


def _segment_ranks(skey: jnp.ndarray):
    """Streaming half of a sorted build: from the SORTED keys, the head
    flag of each run of equal keys and every element's ordinal inside its
    run.  Returns (seg_start, rank)."""
    idx = jnp.arange(skey.shape[0], dtype=jnp.int32)
    seg_start = jnp.concatenate(
        [jnp.ones((1,), bool), skey[1:] != skey[:-1]]
    )
    # index of each sorted element's segment head, via running max
    start_idx = jax.lax.cummax(jnp.where(seg_start, idx, 0))
    return seg_start, idx - start_idx


def _key_segments(key: jnp.ndarray, n_cells: int):
    """The SORT engine's one sort and the streaming passes behind it, for
    any per-row key in [0, n_cells]: (order, skey, seg_start, rank).
    The sort is the stable sort of `(key, row)` that `jnp.argsort(key)`
    runs inside, with both results kept: `skey` is the sort's own key
    result, not a `key[order]` gather (an N-row irregular pass, 9 ms at
    2^20 on a v5e, to re-read what the sort had just put in order).
    Only the NF_RADIX knob path, which produces an order alone, gathers
    it."""
    # nf-lint: disable=trace-safety -- sanctioned A/B knob: trace-time
    # read baked into the compilation; flipping needs a fresh jit cache
    radix = os.environ.get("NF_RADIX", "")
    if radix.isdigit() and int(radix) > 0:
        # NF_RADIX=<bits per pass>: 1 = binary partition passes,
        # 2/3 = 4-way/8-way digits (fewer irregular scatters)
        order = _radix_argsort(key, _bits_for(n_cells), int(radix))
        skey = key[order]
    else:
        # stable: preserves row order within a cell
        rows = jnp.arange(key.shape[0], dtype=jnp.int32)
        skey, order = jax.lax.sort((key, rows), num_keys=1, is_stable=True)
    seg_start, rank = _segment_ranks(skey)
    return order, skey, seg_start, rank


def _sorted_segments(pos, active, cell_size: float, width: int,
                     cell=None, n_cells: int | None = None):
    """Shared build prefix of the SORT engine: the key pass, the ONE
    stable sort by cell id (which returns the sorted keys with the
    order) and per-element segment ranks.  Returns (n_cells, order,
    skey, seg_start, rank) — everything both table builders derive slots
    from."""
    n_cells, key = _cell_keys(
        pos, active, cell_size, width, cell=cell, n_cells=n_cells
    )
    return (n_cells,) + _key_segments(key, n_cells)


# --- the COUNT engine (NF_BINNING=count): histogram + bounded-rank
# selection + scatter.  No sort or argsort anywhere (pinned by the AST
# guard in tests/test_binning.py) — the super-linear comparison network
# is gone from the build.


def _cell_counts(key: jnp.ndarray, n_cells: int) -> jnp.ndarray:
    """Histogram pass: [n_cells + 1] i32 occupancy per cell (last bin
    counts inactive rows, key == n_cells) via ONE segment_sum — a single
    streaming scatter-add over [N].  In the fixed-stride dense layout the
    exclusive-cumsum offsets this histogram implies are simply
    `cell * bucket`, so no scan materializes on the hot path; the
    histogram itself feeds occupancy telemetry and the per-pass profile
    (scripts/profile_passes.py times it in isolation)."""
    return jax.ops.segment_sum(
        jnp.ones_like(key), key, num_segments=n_cells + 1
    )


def _counting_ranks(key: jnp.ndarray, n_cells: int, kmax: int) -> jnp.ndarray:
    """Deterministic within-cell rank in stable row-id order, WITHOUT a
    sort: `kmax` rounds of scatter-min selection.  Round r finds each
    cell's smallest not-yet-ranked row id (one `.at[key].min` scatter +
    one gather), assigns it rank r, and retires it.  Rows never selected
    (rank >= kmax, or inactive key == n_cells) keep rank == kmax.

    This matches the stable-argsort rank EXACTLY wherever it matters:
    both engines place the `kmax` smallest row ids of each cell (stable
    sort ranks ascending row ids ascending) and dump the rest, so tables
    — including overflow drops — are bit-identical.  Cost is
    O(kmax * (N + n_cells)) streaming work with static shapes; at the 1M
    benchmark geometry that is ~16 passes over ~4 MB for the victim
    table versus the ~400-stage comparison network XLA's sort runs over
    8 MB of (key, row) pairs."""
    n = key.shape[0]
    sentinel = jnp.int32(n)  # > any live row id; also the "retired" mark
    remaining = jnp.where(key < n_cells, jnp.arange(n, dtype=jnp.int32),
                          sentinel)
    rank = jnp.full((n,), kmax, jnp.int32)

    def one_round(r, state):
        remaining, rank = state
        win = (
            jnp.full((n_cells + 1,), sentinel, jnp.int32)
            .at[key]
            .min(remaining)
        )
        # the `< sentinel` guard keeps retired rows of an EXHAUSTED cell
        # (win == sentinel) from matching sentinel == sentinel
        is_win = (remaining < sentinel) & (remaining == win[key])
        rank = jnp.where(is_win, r, rank)
        remaining = jnp.where(is_win, sentinel, remaining)
        return remaining, rank

    _, rank = jax.lax.fori_loop(0, kmax, one_round, (remaining, rank))
    return rank


def _counting_slots(key: jnp.ndarray, n_cells: int, bucket: int) -> jnp.ndarray:
    """Per-row flat payload slot from the counting ranks: placed rows get
    `cell * bucket + rank` (the histogram's trivially-dense exclusive
    offsets), everything else the dump slot.  Drop-in replacement for the
    sort path's un-sorted `_finish_table` slot assignment."""
    rank = _counting_ranks(key, n_cells, bucket)
    dump = n_cells * bucket
    return jnp.where(rank < bucket, key * bucket + rank, dump).astype(jnp.int32)


def _build_pair_counting(
    features, active, sub_mask, sub_features,
    key, n_cells: int, cell_size: float, width: int,
    bucket: int, sub_bucket: int, height: int = -1,
) -> Tuple[CellTable, CellTable]:
    """COUNT-engine pair build from a precomputed key: full and subset
    tables each run their own bounded-rank selection + payload scatter.
    The subset re-ranks over `sub_key` so a sub member's rank is its
    ordinal among SUB members of its cell — same contract as the sort
    path's segmented cumsum (a row overflowing the full table can still
    hold a valid subset slot)."""
    with jax.named_scope("nf.aoe.rank"):
        slot_of = _counting_slots(key, n_cells, bucket)
        sub_key = jnp.where(sub_mask, key, n_cells)
        sub_slots = _counting_slots(sub_key, n_cells, sub_bucket)
    with jax.named_scope("nf.aoe.table"):
        full = table_from_slots(
            features, active, slot_of, n_cells, cell_size, width, bucket,
            height,
        )
        sub = table_from_slots(
            sub_features, sub_mask, sub_slots, n_cells, cell_size, width,
            sub_bucket, height,
        )
    return full, sub


def _sorted_slots(n_cells: int, skey, rank, bucket: int) -> jnp.ndarray:
    """Flat payload slot of every SORTED element: `skey * bucket + rank`
    where the rank fits the cell, the dump slot otherwise (overflow,
    inactive).  The one placement rule of the sort engine."""
    dump = n_cells * bucket
    placed = (rank < bucket) & (skey < n_cells)
    return jnp.where(placed, skey * bucket + rank, dump)


def _slots_from_ranks(
    n: int, n_cells: int, order, skey, rank, bucket: int
) -> jnp.ndarray:
    """SORT-engine slot assignment from sorted segment ranks: un-sort
    `skey * bucket + rank` back to row order (one scatter).  Shared by
    _finish_table, the Verlet rebuild (ops/verlet.py) and the slots-only
    builders below so the placement math cannot drift between the
    payload and fused engines."""
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
    features, order, flat_sorted, n_chunks, dump: int, sub_rows: int
) -> jnp.ndarray:
    """Payload table of a COMPACTED sorted list (members first): gather
    and scatter `sub_rows` sorted entries a chunk, `n_chunks` chunks.
    An entry past the members carries the dump slot, so a chunk that
    reaches beyond them writes nothing that stays.

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
        jnp.zeros((dump + 1, f + 1), features.dtype),
        order[:sub_rows], flat_sorted[:sub_rows],
    )
    payload = jax.lax.fori_loop(1, n_chunks, one_chunk, payload)
    # dump slot may have been written by any loser; force it empty
    return payload.at[dump].set(0.0)


def slots_from_assignment(
    active, slot_of, n_cells: int,
    cell_size: float, width: int, bucket: int, height: int = -1,
) -> CellSlots:
    """CellSlots from a precomputed per-row slot array: force inactive
    rows to the dump slot and count active overflow — exactly the
    bookkeeping half of table_from_slots, minus the payload scatter."""
    dump = n_cells * bucket
    slot_of = jnp.where(active, slot_of, dump)
    dropped = jnp.sum(active & (slot_of == dump), dtype=jnp.int32)
    return CellSlots(slot_of, dropped, width, cell_size, bucket, height)


def build_cell_slots_pair(
    pos: jnp.ndarray,
    active: jnp.ndarray,
    sub_mask: jnp.ndarray,
    cell_size: float,
    width: int,
    bucket: int,
    sub_bucket: int,
    cell: jnp.ndarray | None = None,
    height: int = -1,
) -> Tuple[CellSlots, CellSlots]:
    """build_cell_table_pair minus the payloads: the same NF_BINNING
    dispatch, key pass, ranks and dump-slot rules, returning only the
    two slot assignments (full population + subset).  Placement is
    bit-identical to the table pair — including which rows drop — so
    the fused engine inherits the split engine's overflow semantics."""
    n_rows = height if height > 0 else width
    n = pos.shape[0]
    mode = binning_mode()
    if mode == "count":
        n_cells, key = _cell_keys(
            pos, active, cell_size, width, cell=cell,
            n_cells=(n_rows * width if cell is not None else None),
        )
        full = slots_from_assignment(
            active, _counting_slots(key, n_cells, bucket), n_cells,
            cell_size, width, bucket, height,
        )
        sub_key = jnp.where(sub_mask, key, n_cells)
        sub = slots_from_assignment(
            sub_mask, _counting_slots(sub_key, n_cells, sub_bucket), n_cells,
            cell_size, width, sub_bucket, height,
        )
        return full, sub
    if mode != "sort":
        raise ValueError(f"unhandled binning mode {mode!r}")  # pragma: no cover
    n_cells, order, skey, seg_start, rank = _sorted_segments(
        pos, active, cell_size, width, cell=cell,
        n_cells=(n_rows * width if cell is not None else None),
    )
    full = slots_from_assignment(
        active, _slots_from_ranks(n, n_cells, order, skey, rank, bucket),
        n_cells, cell_size, width, bucket, height,
    )
    # subset ranks via the same segmented exclusive cumsum as the pair
    # builder (see build_cell_table_pair for the derivation)
    sub_sorted = sub_mask[order]
    ex = jnp.cumsum(sub_sorted.astype(jnp.int32)) - sub_sorted.astype(jnp.int32)
    head_ex = jax.lax.cummax(jnp.where(seg_start, ex, -1))
    sub_rank = jnp.where(sub_sorted, ex - head_ex, n_cells * sub_bucket + 1)
    sub = slots_from_assignment(
        sub_mask,
        _slots_from_ranks(n, n_cells, order, skey, sub_rank, sub_bucket),
        n_cells, cell_size, width, sub_bucket, height,
    )
    return full, sub


def table_from_slots(
    features, active, slot_of, n_cells: int,
    cell_size: float, width: int, bucket: int, height: int = -1,
) -> CellTable:
    """Materialize a CellTable from a PRECOMPUTED slot assignment: ONE
    deterministic payload scatter (unique slot indices for placed rows),
    dump-slot zeroing, drop count.  This is the sort-free half of the
    build — the Verlet cache (ops/verlet.py) replays it every reuse tick
    against the cached `slot_of` while skipping the argsort entirely.
    Rows not `active` are forced to the dump slot regardless of their
    cached assignment (a cache is only reused while the active set is
    unchanged, but a zero-initialized cache must stay harmless)."""
    n = features.shape[0]
    dump = n_cells * bucket
    slot_of = jnp.where(active, slot_of, dump)
    occ = jnp.ones((n, 1), features.dtype)
    feats = jnp.concatenate([features, occ], axis=-1)
    payload = (
        jnp.zeros((dump + 1, feats.shape[-1]), features.dtype)
        .at[slot_of]
        .set(feats)
    )
    # dump slot may have been written by any loser; force it empty
    payload = payload.at[dump].set(0.0)
    dropped = jnp.sum(active & (slot_of == dump), dtype=jnp.int32)
    return CellTable(payload, slot_of, dropped, width, cell_size, bucket, height)


def _finish_table(
    features, active, n_cells: int, order, skey, rank,
    cell_size: float, width: int, bucket: int, height: int = -1,
) -> CellTable:
    """Shared build suffix: slots from ranks, then the payload scatter.
    Un-sorting the slot assignment costs one scatter instead of a
    sorted-gather + scatter (each N-sized irregular op costs ~1 ms per
    131k rows on a v5e; this is the hot per-tick build)."""
    n = features.shape[0]
    slot_of = _slots_from_ranks(n, n_cells, order, skey, rank, bucket)
    return table_from_slots(
        features, active, slot_of, n_cells, cell_size, width, bucket, height
    )


def build_cell_table(
    pos: jnp.ndarray,
    active: jnp.ndarray,
    features: jnp.ndarray,
    cell_size: float,
    width: int,
    bucket: int,
) -> CellTable:
    """Bin `active` entities into the uniform grid, carrying `features`.

    pos: [N, >=2] positions; active: [N] bool; features: [N, F] float32.
    Slot assignment dispatches on NF_BINNING (bit-identical either way):
    sort = one argsort + permutation-gather + scatter; count = bounded
    scatter-min ranks, no sort.  All slot indices are unique so the
    payload scatter is deterministic.
    """
    mode = binning_mode()
    if mode == "count":
        n_cells, key = _cell_keys(pos, active, cell_size, width)
        slot_of = _counting_slots(key, n_cells, bucket)
        return table_from_slots(
            features, active, slot_of, n_cells, cell_size, width, bucket
        )
    if mode == "sort":
        n_cells, order, skey, _seg_start, rank = _sorted_segments(
            pos, active, cell_size, width
        )
        return _finish_table(
            features, active, n_cells, order, skey, rank, cell_size, width,
            bucket,
        )
    raise ValueError(f"unhandled binning mode {mode!r}")  # pragma: no cover


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
) -> Tuple[CellTable, CellTable]:
    """Build the full table AND a subset table from ONE key pass.

    Dispatches on NF_BINNING: the sort engine sorts twice (the whole
    population, then the subset's keys) and ranks both from the sorted
    keys; the count engine runs bounded scatter-min selection per table
    (no sort at all).  Both produce bit-identical tables — including
    which rows overflow to the dump slot.

    `sub_mask` must be a subset of `active` (combat: attackers among all
    alive entities).  Placement is bit-identical to two independent
    `build_cell_table` calls — within a cell both tables hold rows in
    ascending order, and the subset ranks are the subset's own ordinal
    positions.  What differs is the price: the subset's irregular passes
    go by its members, not by the bank.  The second sort (1 ms at 2^20
    rows on a v5e, where one N-row gather or scatter is 5-40) puts the
    members first, so their features are gathered and their payload rows
    scattered `sub_rows` sorted entries at a time, ceil(members /
    sub_rows) chunks (`sub_chunks`): one chunk when the caller sized
    `sub_rows` for the subset it expects, more when a tick exceeds it —
    slower then, never different.  Default `sub_rows` is the whole bank
    (one chunk: an N-row gather and scatter).

    cell/height: precomputed cell ids over a rectangular [height, width]
    grid (spatial slab shards); default square grid derived from pos.

    The one call here that both ranks and builds, and only combat makes
    it, so it opens the device scopes `nf.aoe.rank` (sorts, heads, ranks,
    slots) and `nf.aoe.table` (the victim scatter, the subset's chunk
    gather and scatter) itself; every other scope of the neighbour
    engine is opened by the caller (game/combat.py), because the
    interest programs share this file."""
    n_rows = height if height > 0 else width
    n = pos.shape[0]
    sub_rows = n if sub_rows is None else max(1, min(sub_rows, n))
    mode = binning_mode()
    if mode == "count":
        with jax.named_scope("nf.aoe.rank"):
            n_cells, key = _cell_keys(
                pos, active, cell_size, width, cell=cell,
                n_cells=(n_rows * width if cell is not None else None),
            )
        return _build_pair_counting(
            features, active, sub_mask, sub_features, key, n_cells,
            cell_size, width, bucket, sub_bucket, height,
        )
    if mode != "sort":
        raise ValueError(f"unhandled binning mode {mode!r}")  # pragma: no cover
    with jax.named_scope("nf.aoe.rank"):
        n_cells, key = _cell_keys(
            pos, active, cell_size, width, cell=cell,
            n_cells=(n_rows * width if cell is not None else None),
        )
        order, skey, _seg_start, rank = _key_segments(key, n_cells)
        slot_of = _slots_from_ranks(n, n_cells, order, skey, rank, bucket)
        # the subset, compacted by a second sort: members first, in cell
        # order, rows ascending inside a cell — the order their ranks
        # count in.  Heads, ranks and slots are streaming passes over
        # that list; nothing here gathers or scatters by row.
        sub_key = jnp.where(sub_mask, key, n_cells)
        sub_order, sub_skey, _sub_start, sub_rank = _key_segments(
            sub_key, n_cells)
        sub_sorted_slots = _sorted_slots(
            n_cells, sub_skey, sub_rank, sub_bucket)
        sub_dump = n_cells * sub_bucket
        sub_dropped = (
            jnp.sum(sub_mask, dtype=jnp.int32)
            - jnp.sum(sub_sorted_slots != sub_dump, dtype=jnp.int32)
        )
        n_chunks = sub_chunks(sub_mask, sub_rows)
        # per-row slots keep their contract for the callers that pull
        # through them (one un-sort scatter); the combat fold does not,
        # and the compiler drops the scatter from the tick program
        sub_slot_of = _slots_from_ranks(
            n, n_cells, sub_order, sub_skey, sub_rank, sub_bucket)
    with jax.named_scope("nf.aoe.table"):
        full = table_from_slots(
            features, active, slot_of, n_cells, cell_size, width, bucket,
            height,
        )
        sub_payload = _chunked_payload(
            sub_features, sub_order, sub_sorted_slots, n_chunks, sub_dump,
            sub_rows,
        )
    sub = CellTable(
        sub_payload, sub_slot_of, sub_dropped, width, cell_size, sub_bucket,
        height,
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
) -> jnp.ndarray:
    """Map per-slot results [H, W, K] or [H, W, K, V] back to rows [N] /
    [N, V] with one gather through a raw slot array; unplaced rows (dump
    slot) read `fill`.  The slot-only half of `pull` — the fused engine
    (CellSlots) has no table to pass."""
    squeeze = values.ndim == 3
    if squeeze:
        values = values[..., None]
    nv = values.shape[-1]
    flat = values.reshape(-1, nv)
    fill_row = jnp.broadcast_to(
        jnp.asarray(fill, values.dtype).reshape(-1), (nv,)
    )
    flat = jnp.concatenate([flat, fill_row[None, :]], axis=0)
    out = flat[slot_of]
    return out[..., 0] if squeeze else out


def pull(
    table: CellTable, values: jnp.ndarray, fill: float | Tuple[float, ...] = 0.0
) -> jnp.ndarray:
    """`pull_slots` through a CellTable's slot assignment."""
    return pull_slots(table.slot_of, values, fill)
