"""The cell-table build against an independent build.

`build_cell_table` / `build_cell_table_pair` (ops/stencil.py) and the
Verlet cache's tables (ops/verlet.py) are held bit for bit (payload,
slot_of and dropped, including WHICH rows overflow to the dump slot) to
`np_table` below: a plain numpy build that imports nothing of `ops/`
(stable argsort by key, ordinal in run, `cell * bucket + rank` or the
dump slot).  The pair build's attacker side is chunked (`sub_rows`), so
every shape of input runs as one whole-bank chunk, as many small chunks
and, in the matrix, as chunks sized for the subset.  The pair build's
full table is gathered from the sorted list (PR 31) and sends no row: it
is held, both levels, to `table_from_slots` scattering the same rows to
the same slots, alone, under `vmap` and under `scan`.  At the end, the
guard rails: what the key pass refuses, and a census of the `NF_*` names
the package reads from the environment."""

import ast
import re
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from noahgameframe_tpu.ops.stencil import (
    build_cell_table,
    build_cell_table_pair,
    table_from_slots,
)
from noahgameframe_tpu.ops.verlet import (
    full_table,
    init_cache,
    refresh,
    sub_table,
)

# None = the whole bank in one chunk; 8 = many chunks
SUB_ROWS = [None, 8]


# ---------------------------------------------------- the independent build

def np_cells(pos, cell_size, width, height=None):
    """Row-major cell ids, clipped to the grid (power-of-two cell sizes
    only: the division is then exact in any arithmetic)."""
    height = width if height is None else height
    pos = np.asarray(pos, np.float32)
    cx = np.clip(np.floor(pos[:, 0] / np.float32(cell_size)), 0, width - 1)
    cy = np.clip(np.floor(pos[:, 1] / np.float32(cell_size)), 0, height - 1)
    return (cy * width + cx).astype(np.int64)


def np_table(cell, mask, feats, n_cells, bucket):
    """(payload [n_cells*bucket + 1, F + 1], slot_of [N], dropped) of the
    rows in `mask`: within a cell rows sit in ascending row order, the
    first `bucket` of them placed, the rest (and every row outside the
    mask) at the dump slot, whose payload row stays zero."""
    cell, mask = np.asarray(cell), np.asarray(mask, bool)
    feats = np.asarray(feats, np.float32)
    n, f = feats.shape
    key = np.where(mask, cell, n_cells)
    order = np.argsort(key, kind="stable")
    skey = key[order]
    idx = np.arange(n)
    head = np.r_[True, skey[1:] != skey[:-1]]
    rank = idx - np.maximum.accumulate(np.where(head, idx, 0))
    dump = n_cells * bucket
    slot_of = np.full(n, dump, np.int32)
    slot_of[order] = np.where(
        (rank < bucket) & (skey < n_cells), skey * bucket + rank, dump)
    placed = slot_of < dump
    payload = np.zeros((dump + 1, f + 1), np.float32)
    payload[slot_of[placed], :f] = feats[placed]
    payload[slot_of[placed], f] = 1.0
    return payload, slot_of, int((mask & ~placed).sum())


def _assert_table(got, want, label=""):
    payload, slot_of, dropped = want
    np.testing.assert_array_equal(
        np.asarray(got.payload), payload, err_msg=f"{label} payload")
    np.testing.assert_array_equal(
        np.asarray(got.slot_of), slot_of, err_msg=f"{label} slot_of")
    assert int(got.dropped) == dropped, f"{label} dropped"


def _assert_pair(got, cell, case, n_cells, bucket, sub_bucket, label=""):
    _pos, active, feats, sub, sfeats = case
    _assert_table(got[0], np_table(cell, active, feats, n_cells, bucket),
                  f"{label} full")
    _assert_table(got[1], np_table(cell, sub, sfeats, n_cells, sub_bucket),
                  f"{label} sub")


def _case(seed, n, width, cell, p_active=0.85, p_sub=0.3):
    rng = np.random.default_rng(seed)
    pos = jnp.asarray(rng.uniform(0, width * cell, (n, 2)).astype(np.float32))
    active = jnp.asarray(rng.random(n) < p_active)
    sub = jnp.asarray(rng.random(n) < p_sub) & active
    feats = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    sfeats = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
    # pair-builder positional order: (pos, active, features, sub_mask,
    # sub_features) — splat-ready
    return pos, active, feats, sub, sfeats


# ------------------------------------------------- pair-builder bit parity

@pytest.mark.parametrize("sub_rows", SUB_ROWS + [96])
@pytest.mark.parametrize("bucket,sub_bucket", [(16, 8), (4, 2), (1, 1)])
def test_pair_matrix_bit_identical(sub_rows, bucket, sub_bucket):
    """build_cell_table_pair, whatever the chunk (96: one chunk sized
    for the case's 84 members, as the combat phase sizes its own),
    including the forced-overflow (1, 1) geometry where MOST rows drop:
    the winners are the smallest row ids of each cell."""
    case = _case(7, 311, 8, 4.0)
    assert int(case[3].sum()) == 84
    got = build_cell_table_pair(*case, 4.0, 8, bucket, sub_bucket,
                                sub_rows=sub_rows)
    _assert_pair(got, np_cells(case[0], 4.0, 8), case, 64, bucket,
                 sub_bucket, f"sub_rows={sub_rows} bucket={bucket}")


def test_single_table_bit_identical():
    pos, active, feats, _sub, _sf = _case(3, 257, 8, 4.0)
    got = build_cell_table(pos, active, feats, 4.0, 8, 12)
    _assert_table(
        got, np_table(np_cells(pos, 4.0, 8), active, feats, 64, 12), "single")


@pytest.mark.parametrize("sub_rows", SUB_ROWS)
@pytest.mark.parametrize("name,case_kw", [
    ("all_inactive", dict(p_active=0.0)),
    ("all_active", dict(p_active=1.0, p_sub=1.0)),
    ("sub_empty", dict(p_sub=0.0)),
])
def test_degenerate_masks_bit_identical(name, case_kw, sub_rows):
    case = _case(11, 200, 8, 4.0, **case_kw)
    got = build_cell_table_pair(*case, 4.0, 8, 8, 4, sub_rows=sub_rows)
    _assert_pair(got, np_cells(case[0], 4.0, 8), case, 64, 8, 4, name)


@pytest.mark.parametrize("sub_rows", SUB_ROWS)
def test_all_one_cell_and_one_overfull_cell(sub_rows):
    """Worst-case occupancy skew: every entity in a single cell (every
    other cell empty), then one packed cell among a uniform field.
    Exactly the bucket smallest row ids of the cell are placed."""
    n, width, cell = 300, 8, 4.0
    rng = np.random.default_rng(13)
    active = jnp.ones(n, bool)
    sub = jnp.asarray(rng.random(n) < 0.4)
    feats = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
    sfeats = feats[:, :1]

    one_cell = jnp.broadcast_to(
        jnp.float32([cell * 2.5, cell * 2.5]), (n, 2)
    )
    packed = jnp.asarray(
        rng.uniform(0, width * cell, (n, 2)).astype(np.float32)
    ).at[: n // 2].set(jnp.float32([cell * 5.5, cell * 5.5]))

    for label, pos in (("one_cell", one_cell), ("packed", packed)):
        case = (pos, active, feats, sub, sfeats)
        got = build_cell_table_pair(*case, cell, width, 8, 4,
                                    sub_rows=sub_rows)
        assert int(got[0].dropped) > 0, f"{label}: no overflow exercised"
        _assert_pair(got, np_cells(pos, cell, width), case, 64, 8, 4, label)


@pytest.mark.parametrize("sub_rows", SUB_ROWS)
def test_rect_grid_precomputed_cells_bit_identical(sub_rows):
    """The spatial slab path: precomputed cell ids over a rectangular
    [height, width] grid (cell=..., height=...)."""
    h, w, cell = 4, 8, 4.0
    n = 220
    rng = np.random.default_rng(17)
    pos = jnp.asarray(
        np.c_[rng.uniform(0, w * cell, n), rng.uniform(0, h * cell, n)]
        .astype(np.float32)
    )
    active = jnp.asarray(rng.random(n) < 0.9)
    sub = jnp.asarray(rng.random(n) < 0.3) & active
    feats = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
    cid = np_cells(pos, cell, w, h)
    case = (pos, active, feats, sub, feats)
    got = build_cell_table_pair(*case, cell, w, 6, 4,
                                cell=jnp.asarray(cid, jnp.int32), height=h,
                                sub_rows=sub_rows)
    assert got[0].height == got[1].height == h
    _assert_pair(got, cid, case, h * w, 6, 4, "rect")


@pytest.mark.parametrize("sub_rows", SUB_ROWS)
def test_fuzz_overflow_sweep(sub_rows):
    """Random densities x tiny buckets: whatever drops, the rows the
    plain build drops (slot_of equality is the strong form of that
    claim)."""
    for seed in range(6):
        rng = np.random.default_rng(100 + seed)
        n = int(rng.integers(16, 400))
        width = int(rng.integers(2, 10))
        bucket = int(rng.integers(1, 6))
        sub_bucket = int(rng.integers(1, bucket + 1))
        case = _case(seed, n, width, 4.0,
                     p_active=float(rng.uniform(0.1, 1.0)),
                     p_sub=float(rng.uniform(0.0, 1.0)))
        got = build_cell_table_pair(*case, 4.0, width, bucket, sub_bucket,
                                    sub_rows=sub_rows)
        _assert_pair(got, np_cells(case[0], 4.0, width), case,
                     width * width, bucket, sub_bucket, f"fuzz seed={seed}")


# ------------------------- the gathered table against the scattered one

def _one_cell(n=300, cell=4.0):
    rng = np.random.default_rng(13)
    feats = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
    pos = jnp.broadcast_to(jnp.float32([cell * 2.5, cell * 2.5]), (n, 2))
    return (pos, jnp.ones(n, bool), feats,
            jnp.asarray(rng.random(n) < 0.4), feats[:, :1])


def _packed(n=300, width=8, cell=4.0, share=0.5, at=5.5):
    rng = np.random.default_rng(13)
    feats = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
    pos = jnp.asarray(
        rng.uniform(0, width * cell, (n, 2)).astype(np.float32)
    ).at[: int(n * share)].set(jnp.float32([cell * at, cell * at]))
    return (pos, jnp.ones(n, bool), feats,
            jnp.asarray(rng.random(n) < 0.4), feats[:, :1])


def _fuzz(seed):
    rng = np.random.default_rng(100 + seed)
    n = int(rng.integers(16, 400))
    width = int(rng.integers(2, 10))
    bucket = int(rng.integers(1, 6))
    case = _case(seed, n, width, 4.0,
                 p_active=float(rng.uniform(0.1, 1.0)),
                 p_sub=float(rng.uniform(0.0, 1.0)))
    return case, width, bucket


# name -> (case, width, bucket, spill); the square grid of 4.0-unit cells
GATHERED = {
    "all_inactive": (_case(11, 200, 8, 4.0, p_active=0.0), 8, 8, (0, 0, 0)),
    "all_active": (_case(11, 200, 8, 4.0, p_active=1.0, p_sub=1.0), 8, 8,
                   (0, 0, 0)),
    "sub_empty": (_case(11, 200, 8, 4.0, p_sub=0.0), 8, 8, (0, 0, 0)),
    "one_cell": (_one_cell(), 8, 8, (0, 0, 0)),
    "one_overfull_cell": (_packed(), 8, 8, (0, 0, 0)),
    "fewer_rows_than_cells": (_case(19, 40, 8, 4.0), 8, 4, (0, 0, 0)),
    **{f"fuzz{seed}": (*_fuzz(seed), (0, 0, 0)) for seed in range(6)},
    # the second level: 2 slots a cell leave most of the 64 cells
    # over-full, 3 hold them all
    "spill_hot_above_cells": (_case(23, 300, 8, 4.0), 8, 2, (5, 3, 2)),
    "spill_hot_below_cells": (_packed(share=0.3), 8, 12, (16, 8, 4)),
    "spill_cells_above_grid": (_case(23, 300, 4, 4.0), 4, 2, (40, 4, 2)),
    "spill_deeper_than_both": (_packed(), 8, 8, (4, 16, 4)),
    "spill_no_overfull_cell": (_case(29, 100, 8, 4.0), 8, 16, (4, 8, 4)),
    "spill_one_cell": (_one_cell(), 8, 8, (4, 64, 8)),
}


def np_stats(cell, mask, n_cells, bucket, spill_cells, depth):
    """(over-full cells, deepest cell, rows the second level holds) from
    the cells' counts alone."""
    counts = np.bincount(np.asarray(cell)[np.asarray(mask, bool)],
                         minlength=n_cells)
    over = counts[counts > bucket] - bucket
    return (len(over), int(counts.max(initial=0)),
            int(np.minimum(over[:spill_cells], depth).sum()))


def _assert_gathered(full, feats, active, n_cells, bucket, spill, cell,
                     label):
    """The full table of a pair build, gathered from the sorted list,
    against `table_from_slots` scattering the same rows to the same
    slots; its drops and counts against the cells' counts."""
    cells, depth = spill[:2]
    want = table_from_slots(
        feats, active, full.slot_of, n_cells, full.cell_size, full.width,
        bucket, full.height, (cells, depth))
    assert full.payload.dtype == want.payload.dtype
    np.testing.assert_array_equal(
        np.asarray(full.payload).view(np.uint32),
        np.asarray(want.payload).view(np.uint32), err_msg=f"{label} payload")
    assert int(full.dropped) == int(want.dropped), f"{label} dropped"
    got_stats = tuple(int(x) for x in full.stats)
    assert got_stats == np_stats(cell, active, n_cells, bucket, cells,
                                 depth), f"{label} stats"
    assert (full.spill_cells, full.spill_bucket) == (cells, depth)
    # every active row is placed or counted, and a placed row's slot
    # holds it
    slot_of = np.asarray(full.slot_of)
    placed = slot_of != n_cells * bucket
    assert int(placed.sum()) + int(full.dropped) == int(
        np.asarray(active).sum()), f"{label} census"
    np.testing.assert_array_equal(
        np.asarray(full.payload)[slot_of[placed], :-1],
        np.asarray(feats)[placed], err_msg=f"{label} rows")


@pytest.mark.parametrize("name", sorted(GATHERED))
def test_gathered_table_is_the_scattered_table(name):
    case, width, bucket, spill = GATHERED[name]
    pos, active, feats = case[:3]
    full, _sub = build_cell_table_pair(
        *case, 4.0, width, bucket, max(1, bucket // 2), spill=spill)
    if name.startswith("spill") and "no_overfull" not in name:
        assert int(full.stats.hot_cells) > 0, "no second level exercised"
    _assert_gathered(full, feats, active, width * width, bucket, spill,
                     np_cells(pos, 4.0, width), name)


@pytest.mark.parametrize("spill", [(0, 0, 0), (3, 4, 2)])
def test_gathered_table_rect_grid_precomputed_cells(spill):
    h, w, cell, n = 4, 8, 4.0, 220
    rng = np.random.default_rng(17)
    pos = jnp.asarray(
        np.c_[rng.uniform(0, w * cell, n), rng.uniform(0, h * cell, n)]
        .astype(np.float32))
    active = jnp.asarray(rng.random(n) < 0.9)
    sub = jnp.asarray(rng.random(n) < 0.3) & active
    feats = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
    cid = np_cells(pos, cell, w, h)
    full, _sub = build_cell_table_pair(
        pos, active, feats, sub, feats, cell, w, 6, 4,
        cell=jnp.asarray(cid, jnp.int32), height=h, spill=spill)
    assert full.height == h
    _assert_gathered(full, feats, active, h * w, 6, spill, cid, "rect")


@pytest.mark.parametrize("bucket,spill", [
    (3, (0, 0, 0)), (3, (6, 16, 2)), (16, (0, 0, 0))])
@pytest.mark.parametrize("under", ["vmap", "scan"])
def test_gathered_table_under_vmap_and_scan(under, bucket, spill):
    """A fleet's rooms (`vmap`) and a fused run's ticks (`scan`): every
    lane and every step builds the table its inputs build alone, a slot
    a gathered row (3 deep) and in runs of 16 (16 deep)."""
    import jax

    width, lanes = 6, 4
    cases = [_case(40 + i, 150, width, 4.0, p_active=0.5 + 0.1 * i)
             for i in range(lanes)]
    stacked = tuple(jnp.stack(xs) for xs in zip(*cases))

    def build(case):
        full, _sub = build_cell_table_pair(
            *case, 4.0, width, bucket, 2, sub_rows=16, spill=spill)
        return full.payload, full.slot_of, full.dropped, tuple(full.stats)

    if under == "vmap":
        outs = jax.jit(jax.vmap(build))(stacked)
    else:
        _, outs = jax.lax.scan(lambda c, case: (c, build(case)), 0, stacked)
    for i, case in enumerate(cases):
        alone, _sub = build_cell_table_pair(
            *case, 4.0, width, bucket, 2, sub_rows=16, spill=spill)
        payload, slot_of, dropped, stats = jax.tree.map(
            lambda x, i=i: x[i], outs)
        got = alone._replace(payload=payload, slot_of=slot_of,
                             dropped=dropped, stats=type(alone.stats)(*stats))
        np.testing.assert_array_equal(
            np.asarray(payload).view(np.uint32),
            np.asarray(alone.payload).view(np.uint32))
        _assert_gathered(got, case[2], case[1], width * width, bucket,
                         spill, np_cells(case[0], 4.0, width),
                         f"{under} lane {i}")


# --------------------------------------------------- verlet cache parity

@pytest.mark.parametrize("skin", [0.0, 2.0])
def test_verlet_tables_match_the_plain_build(skin):
    """A freshly anchored cache reproduces the plain build through
    full_table / sub_table (the subset ranked by the segmented cumsum
    over the cached order, not by a second sort)."""
    n, width, cell = 257, 8, 4.0
    case = _case(5, n, width, cell)
    pos, active, feats, sub, sfeats = case
    cache, rebuilt = refresh(
        init_cache(n), pos, active, cell, width, 12, skin
    )
    assert int(rebuilt) == 1
    got_full = full_table(cache, feats, active, width * width, cell,
                          width, 12)
    got_sub = sub_table(cache, sub, sfeats, width * width, cell, width, 8)
    _assert_pair((got_full, got_sub), np_cells(pos, cell, width), case,
                 width * width, 12, 8, f"verlet skin={skin}")


def test_sub_overflow_independent_of_full():
    """A row that overflows the FULL table can still hold a valid SUB
    slot (the subset is ranked on its own)."""
    n = 40
    pos = jnp.broadcast_to(jnp.float32([2.0, 2.0]), (n, 2))  # one cell
    active = jnp.ones(n, bool)
    # sub members are the LAST rows: all overflow the size-4 full table,
    # but the first 4 of them fit the size-4 sub table
    sub = jnp.arange(n) >= n - 8
    feats = jnp.asarray(np.arange(n * 2, dtype=np.float32).reshape(n, 2))
    full, subt = build_cell_table_pair(
        pos, active, feats, sub, feats, 4.0, 4, 4, 4
    )
    assert int(full.dropped) == n - 4
    assert int(subt.dropped) == 4  # 8 members, 4 slots
    # the sub winners are the 4 smallest row ids AMONG sub members
    placed = np.asarray(subt.slot_of[sub])
    dump = 4 * 4 * 4
    assert (np.sort(placed[placed < dump]) ==
            np.asarray(subt.slot_of)[n - 8:n - 4]).all()


def test_key_pass_refuses_what_it_cannot_place():
    """Row ids ride f32 payload columns, exact only below 2^24: a bank
    that large is refused when the build traces (no array is made
    here); and precomputed cell ids without the grid's cell count are
    refused too."""
    import jax

    n = 1 << 24

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype)

    with pytest.raises(ValueError, match="2\\^24"):
        jax.eval_shape(
            lambda p, a, f: build_cell_table(p, a, f, 4.0, 8, 4),
            shape(n, 2), shape(n, dtype=bool), shape(n, 1))
    pos, active, _f, _s, _sf = _case(1, 32, 4, 4.0)
    with pytest.raises(ValueError, match="need n_cells"):
        refresh(init_cache(32), pos, active, 4.0, 4, 4, 1.0,
                cell=jnp.zeros(32, jnp.int32))


# ------------------------------------------------------------ guard rails

PKG = Path(__file__).resolve().parent.parent / "noahgameframe_tpu"

# every name the package reads from the environment (each appears as a
# whole string literal where it is read): a knob added or left behind
# changes this list in the PR that does it
NF_ENV_NAMES = {
    "NF_FAILOVER_DEADLINE_S", "NF_NATIVE_DIR", "NF_PARK_MAX_FRAMES",
    "NF_ROOM_SLOTS", "NF_SERVE_BATCH", "NF_SERVE_CHUNK",
    "NF_SERVE_OVERLAP", "NF_SERVE_SLOTS", "NF_STAGE_TIMING",
    "NF_TICK_TRAIN", "NF_TRACE_SAMPLE", "NF_VERLET_SKIN",
}


def _dotted(node):
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def test_census_of_nf_environment_names():
    found = set()
    for path in PKG.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text(), str(path))):
            if isinstance(node, ast.Constant) and isinstance(
                    node.value, str) and re.fullmatch(
                        r"NF_[A-Z0-9_]+", node.value):
                found.add(node.value)
    assert found == NF_ENV_NAMES


def test_neighbour_engine_reads_one_name_from_the_environment():
    """Under `ops/` and in `game/combat.py` the environment is read in
    one place, the Verlet skin: the fold's engine is chosen from the
    grid (`stencil_pallas.fold_engine`), not from a variable."""
    reads = {}
    for path in sorted(PKG.glob("ops/*.py")) + [PKG / "game" / "combat.py"]:
        tree = ast.parse(path.read_text(), str(path))
        consts = {
            t.id: n.value.value for n in tree.body
            if isinstance(n, ast.Assign) and isinstance(n.value, ast.Constant)
            for t in n.targets if isinstance(t, ast.Name)}
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and (
                    _dotted(node.func) or "").endswith(
                        ("environ.get", "getenv")):
                arg = node.args[0]
            elif isinstance(node, ast.Subscript) and (
                    _dotted(node.value) or "").endswith("environ"):
                arg = node.slice
            else:
                continue
            name = (arg.value if isinstance(arg, ast.Constant)
                    else consts.get(getattr(arg, "id", None), ast.dump(arg)))
            reads.setdefault(path.name, set()).add(name)
    assert reads == {"verlet.py": {"NF_VERLET_SKIN"}}
