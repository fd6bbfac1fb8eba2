#!/usr/bin/env python
"""Chip microbenchmark of the victim cell table: scattered row by row, as
`table_from_slots` makes it, against gathered slot by slot from the
sorted list, as `build_cell_table_pair` makes it since PR 31.

    chiprun -- python scripts/scatter_probe.py > chiprun_out/scatter_probe.jsonl

No cell runs this file.  It times, on whatever device jax has, three
calls each (after one that compiles), at three shapes:

- `tick-1m`: 2^20 rows (1M live) on 395 x 395 cells, 32 deep;
- `rooms-fleet`: 8,192 rooms of 128 rows (96 live) on 4 x 4 cells, 20
  deep, every variant under `vmap`;
- `siege-zipf`: the first shape with its rows on 4,096 Zipf-sized camps
  and a second level of 8,192 cells x 512 behind the dump slot;

of these variants, each handed the same sort and the same slots:

- `scatter`: 2^20 rows of `f32[6]` sent to their slots, in row order;
- `gather.planes`: `features[order]`, then a single-word gather a
  feature through every slot's index into the sorted list;
- `gather.rows`: `features[order]`, then one gather of `f32[5]` rows;
- `gather.runs`: what ships, `ops.stencil.table_from_sorted`;
- `gather.rows.by<g>`: the row form with g slots an index (the sorted
  features as overlapping runs of g entries, g x 5 words a row; g among
  4, 8, 10, 16, 20, 32 where it divides the depths);
- `gather.inverse.planes` / `.rows`: `row_of_slot = order[src]` (one
  single-word gather by slots) and the features gathered by it from the
  bank as it lies, with no sorted copy;
- `index`: the passes that make `start`, `src` and `live` from the
  sorted keys (a third sort, a scatter of the cells' heads, streaming).

`--section scatter` times what PR 27 read at `tick-1m`'s shape instead:
the scatter in row order and in cell-sorted order, with `mode="drop"`
and the index flags, and the attacker side's chunk at three sizes.

One JSON line per variant: {"shape", "variant", "rows", "slots", "ms":
[t1, t2, t3], "temp_bytes", "sums", "device"}.  Every variant returns
the table's column sums behind an optimization barrier (the table is
made whole; a table returned whole would be held to the row-major
result layout, six columns padded to 128 lanes); the sums of one shape
agree across variants.  Host clock around `block_until_ready`; a number
from a CPU names the CPU in `device` and says nothing about the chip.
`--rooms 64 --width 40` rehearses it small.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from noahgameframe_tpu.ops.stencil import (  # noqa: E402
    _cell_keys,
    _cell_starts,
    _key_segments,
    _slot_sources,
    _slots_from_ranks,
    _sorted_slots,
    _spill_slots,
    table_from_sorted,
)

N = 1 << 20
CELL = 4.0
ATT_BUCKET = 12


def scatter_layouts(text) -> list:
    """Result shape and layout of each table scatter in the compiled
    program: the tick's tables are column-major (`{0,1}`), and a probe
    whose compiler chose otherwise times another instruction."""
    return sorted(set(re.findall(
        r"= (f32\[\d+,\d\]\{[\d,]+)[^ ]* scatter\(", text)))


def timed(name, rows, fn, *args, shape="tick-1m", slots=None):
    try:
        compiled = fn.lower(*args).compile()
    except Exception as e:  # noqa: BLE001 -- the compiler's refusal
        print(json.dumps({"shape": shape, "variant": name,
                          "refused": str(e)[:200]}), flush=True)
        return
    out = jax.block_until_ready(fn(*args))  # compiles
    ms = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        ms.append(round((time.perf_counter() - t0) * 1e3, 3))
    d = jax.devices()[0]
    print(json.dumps({
        "shape": shape, "variant": name, "rows": rows, "slots": slots,
        "ms": ms, "scatters": scatter_layouts(compiled.as_text()),
        "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
        "sums": np.asarray(out, np.float64).round(3).tolist(),
        "device": f"{d.platform}:{d.device_kind}"}), flush=True)


# ------------------------------------------------------- the table's forms

def _sums(payload):
    return jax.lax.optimization_barrier(payload).sum(axis=0)


def _stack(cols):
    # as game/combat.py makes its features: columns stacked in the program
    return jnp.stack(cols, axis=-1)


def form_scatter(n_slots, dump):
    def fn(cols, slot_of):
        feats = _stack(cols + (jnp.ones_like(cols[0]),))
        payload = jnp.zeros((n_slots, feats.shape[-1]), feats.dtype).at[
            slot_of].set(feats)
        return _sums(payload.at[dump].set(0.0))
    return fn


def _masked(got, live):
    """The payload of gathered rows `[slots, F]` or of gathered planes
    (a list of `[slots]`): zeros where no row lives, the occupancy word
    behind the features."""
    occ = live.astype(jnp.float32)
    if isinstance(got, list):
        return _sums(jnp.stack(
            [jnp.where(live, plane, 0.0) for plane in got] + [occ], -1))
    return _sums(jnp.concatenate(
        [jnp.where(live[:, None], got, 0.0), occ[:, None]], -1))


def _clamped(src, order):
    return jnp.minimum(src, order.shape[0] - 1)


def form_planes(cols, order, src, live):
    feats = _stack(cols)[order]
    src = _clamped(src, order)
    return _masked([feats[:, i][src] for i in range(len(cols))], live)


def form_shipped(depths):
    """`ops.stencil.table_from_sorted` as `build_cell_table_pair` calls
    it: runs of `run_length` entries, a level at a time."""
    def fn(cols, order, firsts, counts):
        return _sums(table_from_sorted(
            _stack(cols), order, list(zip(firsts, counts, depths))))
    return fn


def slots_of(levels):
    """(src, live) of every slot, the dump slot behind the first level
    included: `_slot_sources`' levels a slot at a time."""
    src, live = [], []
    for level, (first, count, depth) in enumerate(levels):
        lanes = jnp.arange(depth, dtype=jnp.int32)
        src.append((first[:, None] + lanes).reshape(-1))
        live.append((lanes < count[:, None]).reshape(-1))
        if level == 0:
            src.append(jnp.zeros((1,), jnp.int32))
            live.append(jnp.zeros((1,), bool))
    return jnp.concatenate(src), jnp.concatenate(live)


def form_rows(cols, order, src, live):
    return _masked(_stack(cols)[order][_clamped(src, order)], live)


def form_inverse_planes(cols, order, src, live):
    row = order[_clamped(src, order)]
    return _masked([c[row] for c in cols], live)


def form_inverse_rows(cols, order, src, live):
    return _masked(_stack(cols)[order[_clamped(src, order)]], live)


def form_grouped(g, n_base):
    """`gather.rows` with `g` slots an index: the sorted features laid
    out as overlapping runs (`runs[i]` = entries i .. i + g - 1, feature
    by feature, 5 x g words a row), so that one gathered row fills g
    consecutive slots of a cell and a feature's plane of the table is a
    slice of lanes.  `g` divides the bucket and the second level's
    depth; `n_base` base slots lie before the dump slot."""
    def fn(cols, order, heads, live):
        n, f = order.shape[0], len(cols)
        feats = jnp.pad(_stack(cols)[order], ((0, g - 1), (0, 0)))
        runs = jnp.stack(
            [feats[k:k + n, i] for i in range(f) for k in range(g)], axis=-1)
        got = runs[_clamped(heads, order)]
        dump = jnp.zeros((1,), feats.dtype)
        planes = []
        for i in range(f):
            plane = got[:, i * g:(i + 1) * g].reshape(-1)
            planes.append(
                jnp.concatenate([plane[:n_base], dump, plane[n_base:]]))
        return _masked(planes, live)
    return fn


GATHERS = [("gather.planes", form_planes), ("gather.rows", form_rows),
           ("gather.inverse.planes", form_inverse_planes),
           ("gather.inverse.rows", form_inverse_rows)]


def probe_shape(shape, pos, active, cols, width, bucket, spill, batched,
                only=None):
    """Every variant at one shape.  `batched`: the arrays carry a leading
    room axis and every function runs under `vmap`, sums added over it."""
    n_cells = width * width
    cells, depth = spill
    n_slots = n_cells * bucket + 1 + cells * depth

    def assign(pos, active):
        _, key = _cell_keys(pos, active, CELL, width)
        order, skey, rank = _key_segments(key)
        sorted_slots, _ = _spill_slots(
            n_cells, skey, rank, bucket, cells, depth)
        slot_of = jnp.full(key.shape, n_cells * bucket, jnp.int32).at[
            order].set(sorted_slots)
        levels = _slot_sources(
            _cell_starts(n_cells, skey), n_cells, bucket, cells, depth)
        firsts, counts, _ = zip(*levels)
        return (order, skey, slot_of, firsts, counts) + slots_of(levels)

    def index(skey):
        levels = _slot_sources(
            _cell_starts(n_cells, skey), n_cells, bucket, cells, depth)
        return jnp.stack([x.sum(dtype=jnp.int32)
                          for level in levels for x in level[:2]])

    def lift(fn):
        if not batched:
            return jax.jit(fn)
        return jax.jit(lambda *a: jax.vmap(fn)(*a).sum(axis=0))

    order, skey, slot_of, firsts, counts, src, live = jax.jit(
        jax.vmap(assign) if batched else assign)(pos, active)
    rows = int(np.prod(active.shape))
    slots = n_slots * (active.shape[0] if batched else 1)
    kw = dict(shape=shape, slots=slots)
    if only is None:
        timed("scatter", rows,
              lift(form_scatter(n_slots, n_cells * bucket)), cols, slot_of,
              **kw)
    for name, fn in GATHERS:
        if only is None or name in only:
            timed(name, rows, lift(fn), cols, order, src, live, **kw)
    if only is None or "gather.runs" in only:
        depths = (bucket, depth) if cells else (bucket,)
        timed("gather.runs", rows, lift(form_shipped(depths)), cols, order,
              firsts, counts, **kw)
    n_base = n_cells * bucket
    for g in (4, 8, 10, 16, 20, 32):
        name = f"gather.rows.by{g}"
        if bucket % g or (cells and depth % g) or (only and name not in only):
            continue
        # every g-th slot's source heads a run (made outside the timed call)
        heads = jnp.concatenate(
            [src[..., :n_base:g], src[..., n_base + 1::g]], axis=-1)
        timed(name, rows, lift(form_grouped(g, n_base)), cols, order, heads,
              live, **kw)
    if only is None:
        timed("index", rows, lift(index), skey, **kw)


def camp_positions(rng, n, extent, camps=4096, zipf=0.99, leash=64.0):
    """Rows on Zipf-sized camps, uniform on a square about each: the
    siege world's crowding, near enough for a timing."""
    weights = 1.0 / np.arange(1, camps + 1) ** zipf
    camp = rng.choice(camps, n, p=weights / weights.sum())
    centre = rng.uniform(0, extent, (camps, 2))
    at = centre[camp] + rng.uniform(-leash, leash, (n, 2))
    return np.clip(at, 0, extent - 1e-3).astype(np.float32)


def build_section(width, rooms, only) -> None:
    rng = np.random.default_rng(31)
    extent = width * CELL
    n = N if width == 395 else 1 << int(np.ceil(np.log2(width * width * 6)))
    live = n * 1_000_000 // N

    def world(pos):
        pos = jnp.asarray(pos)
        cols = (pos[:, 0], pos[:, 1]) + tuple(
            jnp.asarray(rng.integers(0, 4, n).astype(np.float32))
            for _ in range(3))
        return pos, jnp.asarray(np.arange(n) < live), cols

    pos, active, cols = world(
        rng.uniform(0, extent, (n, 2)).astype(np.float32))
    probe_shape("tick-1m", pos, active, cols, width, 32, (0, 0), False, only)

    rpos = jnp.asarray(rng.uniform(0, 16.0, (rooms, 128, 2)).astype(
        np.float32))
    ractive = jnp.broadcast_to(jnp.arange(128) < 96, (rooms, 128))
    rcols = (rpos[..., 0], rpos[..., 1]) + tuple(
        jnp.asarray(rng.integers(0, 4, (rooms, 128)).astype(np.float32))
        for _ in range(3))
    probe_shape("rooms-fleet", rpos, ractive, rcols, 4, 20, (0, 0), True,
                only)

    pos, active, cols = world(camp_positions(rng, n, extent))
    probe_shape("siege-zipf", pos, active, cols, width, 32,
                (8192 if width == 395 else 64, 512), False, only)


# --------------------------------------- PR 27's section: what a scatter pays

def scatter_section() -> None:
    width, live, vic_bucket = 395, 1_000_000, 32
    n_cells = width * width
    rng = np.random.default_rng(27)
    pos = jnp.asarray(rng.uniform(0, 1581.0, (N, 2)).astype(np.float32))
    active = jnp.asarray(np.arange(N) < live)
    feats = jnp.asarray(rng.standard_normal((N, 6)).astype(np.float32))

    @jax.jit
    def assign(pos, active):
        n_cells, key = _cell_keys(pos, active, CELL, width)
        order, skey, rank = _key_segments(key)
        sorted_slots = _sorted_slots(n_cells, skey, rank, vic_bucket)
        slot_of = _slots_from_ranks(N, n_cells, order, skey, rank, vic_bucket)
        return order, sorted_slots, slot_of

    order, sorted_slots, slot_of = assign(pos, active)
    dump = n_cells * vic_bucket
    table = (dump + 1, 6)
    # unplaced rows (24 rows in 2^20 dropped, 48,576 inactive): unique
    # indices past the table, ascending in sorted order
    past = dump + 1 + jnp.arange(N, dtype=jnp.int32)
    sorted_unique = jnp.where(sorted_slots == dump, past, sorted_slots)
    # ascending, as the flag below claims (a timing probe: the handful of
    # rows dropped mid-list shift the features of what follows them)
    sorted_unique = jnp.sort(sorted_unique)
    row_unique = jnp.where(slot_of == dump, past, slot_of)
    sorted_feats = feats[order]

    def scatter(**kw):
        return jax.jit(lambda idx, f: jnp.zeros(table, f.dtype).at[idx].set(
            f, **kw).sum(axis=0))

    timed("victim.as_it_is", N, scatter(), slot_of, feats)
    timed("victim.sorted.scatter_only", N, scatter(), sorted_slots,
          sorted_feats)
    timed("victim.sorted.with_gather", N, jax.jit(
        lambda idx, f, o: jnp.zeros(table, f.dtype).at[idx].set(
            f[o]).sum(axis=0)),
        sorted_slots, feats, order)
    timed("victim.row_order.drop", N, scatter(mode="drop"), row_unique, feats)
    timed("victim.row_order.drop.unique", N,
          scatter(mode="drop", unique_indices=True), row_unique, feats)
    timed("victim.sorted.drop", N, scatter(mode="drop"), sorted_unique,
          sorted_feats)
    timed("victim.sorted.drop.unique", N,
          scatter(mode="drop", unique_indices=True), sorted_unique,
          sorted_feats)
    timed("victim.sorted.drop.unique.flagged_sorted", N,
          scatter(mode="drop", unique_indices=True, indices_are_sorted=True),
          sorted_unique, sorted_feats)

    # the attacker side's chunk: 17,000 members first, the rest to the dump
    att_dump = n_cells * ATT_BUCKET
    att_feats = jnp.asarray(rng.standard_normal((N, 7)).astype(np.float32))
    members = np.sort(rng.choice(att_dump, 17_000, replace=False))
    att_slots = jnp.asarray(np.concatenate(
        [members, np.full(N - 17_000, att_dump)]).astype(np.int32))
    att_order = jnp.asarray(rng.permutation(N).astype(np.int32))
    for rows in (N, 131_072, 69_912):
        fn = jax.jit(lambda idx, f, o: jnp.zeros(
            (att_dump + 1, 8), f.dtype).at[idx].set(jnp.concatenate(
                [f[o], jnp.ones((o.shape[0], 1), f.dtype)], axis=-1)
            ).sum(axis=0))
        timed("attacker.chunk.gather_and_scatter", rows, fn,
              att_slots[:rows], att_feats, att_order[:rows])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", choices=["build", "scatter"],
                    default="build")
    ap.add_argument("--width", type=int, default=395)
    ap.add_argument("--rooms", type=int, default=8192)
    ap.add_argument("--forms", default=None,
                    help="comma-separated gather forms; default every variant")
    args = ap.parse_args()
    if args.section == "build":
        build_section(args.width, args.rooms,
                      args.forms and args.forms.split(","))
    else:
        scatter_section()


if __name__ == "__main__":
    main()
