#!/usr/bin/env python
"""Chip microbenchmark of the cell-table scatters at `tick-1m`'s shapes.

    chiprun -- python scripts/scatter_probe.py > chiprun_out/scatter_probe.jsonl

No cell runs this file.  It times, on whatever device jax has, three
calls each (after one that compiles) of:

- the victim scatter as `table_from_slots` makes it: 2^20 rows of
  `f32[6]` into `f32[4992801,6]` (395 x 395 cells, 32 deep, a dump slot),
  in row order;
- the same rows in cell-sorted order (the scatter alone, its operands
  sorted beforehand, and with the gather that sorts them);
- the same with the unplaced rows given unique out-of-range indices and
  `mode="drop"`, plain and with `indices_are_sorted` / `unique_indices`;
- the attacker side's chunk at three sizes (a gather of `f32[7]` rows
  and a scatter into `f32[1872301,8]`, 17,000 members and the rest to
  the dump slot).

One JSON line per variant: {"variant", "rows", "ms": [t1, t2, t3],
"device"}.  Host clock around `block_until_ready`; a number from a CPU
names the CPU in `device` and says nothing about the chip.
"""

from __future__ import annotations

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
    _key_segments,
    _slots_from_ranks,
    _sorted_slots,
)

N = 1 << 20
LIVE = 1_000_000
WIDTH, CELL = 395, 4.0
EXTENT = 1581.0
VIC_BUCKET, ATT_BUCKET = 32, 12
N_CELLS = WIDTH * WIDTH


def scatter_layouts(fn, *args) -> list:
    """Result shape and layout of each table scatter in the compiled
    program: the tick's tables are column-major (`{0,1}`), and a probe
    whose compiler chose otherwise times another instruction."""
    text = fn.lower(*args).compile().as_text()
    return sorted(set(re.findall(
        r"= (f32\[\d+,\d\]\{[\d,]+)[^ ]* scatter\(", text)))


def timed(name, rows, fn, *args):
    layouts = scatter_layouts(fn, *args)
    out = jax.block_until_ready(fn(*args))  # compiles
    ms = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        ms.append(round((time.perf_counter() - t0) * 1e3, 3))
    del out
    d = jax.devices()[0]
    print(json.dumps({"variant": name, "rows": rows, "ms": ms,
                      "scatters": layouts, "device": f"{d.platform}:{d.device_kind}"}), flush=True)


def main() -> None:
    rng = np.random.default_rng(27)
    pos = jnp.asarray(rng.uniform(0, EXTENT, (N, 2)).astype(np.float32))
    active = jnp.asarray(np.arange(N) < LIVE)
    feats = jnp.asarray(rng.standard_normal((N, 6)).astype(np.float32))

    @jax.jit
    def assign(pos, active):
        n_cells, key = _cell_keys(pos, active, CELL, WIDTH)
        order, skey, rank = _key_segments(key)
        sorted_slots = _sorted_slots(n_cells, skey, rank, VIC_BUCKET)
        slot_of = _slots_from_ranks(N, n_cells, order, skey, rank, VIC_BUCKET)
        return order, sorted_slots, slot_of

    order, sorted_slots, slot_of = assign(pos, active)
    dump = N_CELLS * VIC_BUCKET
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

    # every variant returns the table's column sums (one streaming pass):
    # a table returned whole would be held to the row-major result layout,
    # six columns padded to 128 lanes
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
    att_dump = N_CELLS * ATT_BUCKET
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


if __name__ == "__main__":
    main()
