#!/usr/bin/env python
"""Chip microbenchmark of the two combat folds alone, over the same tables.

    chiprun -- python scripts/fold_probe.py > chiprun_out/fold_probe.jsonl

No cell runs this file.  It placed the crossover that
`ops/stencil_pallas.fold_engine` chooses by (PERF.md section 7).  For
each grid width it seeds a world at the benchmark's density (0.4 NPCs a
unit^2, cells of 4 units, a thirtieth of the live rows attacking), builds
the two cell tables with `build_cell_table_pair` at the depths
`CombatModule` resolves for that world (and at the boosted depths the
cells run at), and times `combat_fold_xla` and `combat_fold_pallas` over
them: three calls each after one that compiles, host clock around
`block_until_ready`, every call returning checksums of the two results,
which have to agree between the folds (`equal`; the tests hold the whole
results equal bit for bit).

Two shapes a width: one world, and up to width 64 a fleet of
`CELLS // width^2` such worlds under `vmap` (the room fleet's shape:
8,192 rooms at width 4), so that every fleet holds the same 131,072
cells and a narrow grid's time is not the dispatch's.

One JSON line per (width, depths, worlds): {"width", "kv", "ka",
"worlds", "lane_fill", "vmem_bytes", "rule", "xla_ms": [t1, t2, t3],
"pallas_ms": [...] or "pallas_error", "equal", "device"}.  A number from
a CPU names the CPU in `device` (the kernel is interpreted there) and
says nothing about the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from noahgameframe_tpu.game.combat import (  # noqa: E402
    CombatModule,
    combat_fold_xla,
)
from noahgameframe_tpu.ops import stencil_pallas as sp  # noqa: E402
from noahgameframe_tpu.ops.stencil import (  # noqa: E402
    CellTable,
    build_cell_table_pair,
)

CELL = RADIUS = 4.0
DENSITY = 0.4
DUTY = 1.0 / 30.0
CELLS = 131_072  # a fleet's cells: 8,192 rooms of 4 x 4
WIDTHS = (4, 8, 16, 32, 64, 125, 256, 395)


def geometry(width: int):
    """(capacity, live rows, victim depth, attacker depth) of a world
    `width` cells wide as `build_benchmark_world` would size it."""
    extent = width * CELL
    live = max(1, int(DENSITY * extent * extent))
    cap = 1 << int(np.ceil(np.log2(max(live, 64))))
    m = CombatModule(extent=extent, radius=RADIUS)
    m._attacker_duty = DUTY
    return cap, live, m.resolved_bucket(cap), m.resolved_att_bucket(cap)


def seeded(width: int, worlds: int, seed: int):
    cap, live, _kv, _ka = geometry(width)
    rng = np.random.default_rng(seed)
    shape = (worlds, cap)
    pos = rng.uniform(0, width * CELL, shape + (2,)).astype(np.float32)
    active = np.broadcast_to(np.arange(cap) < live, shape)
    attacking = active & (rng.random(shape) < DUTY)
    camp = rng.integers(1, 3, shape).astype(np.float32)
    atk = rng.integers(1, 30, shape).astype(np.float32)
    one = np.ones(shape, np.float32)
    rows = np.broadcast_to(np.arange(cap, dtype=np.float32), shape)
    vic = np.stack([pos[..., 0], pos[..., 1], camp, one, one], -1)
    att = np.stack([pos[..., 0], pos[..., 1],
                    np.where(attacking, atk, 0).astype(np.float32),
                    camp, one, one, rows], -1)
    return tuple(jnp.asarray(x) for x in (pos, active, vic, attacking, att))


def timed(fn, tables):
    jax.block_until_ready(fn(*tables))  # compiles
    ms = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*tables))
        ms.append(round((time.perf_counter() - t0) * 1e3, 3))
    return ms


def probe(width: int, kv: int, ka: int, worlds: int, seed: int) -> dict:
    interpret = sp.pallas_interpret()

    def build(p, a, vf, atk, af):
        vt, at = build_cell_table_pair(p, a, vf, atk, af, CELL, width, kv, ka)
        return ((vt.payload, vt.slot_of, at.payload, at.slot_of),
                (vt.dropped, at.dropped))

    tables, dropped = jax.block_until_ready(
        jax.jit(jax.vmap(build))(*seeded(width, worlds, seed)))

    def checked(fold):
        # the static geometry does not cross `jit`: the tables are put
        # back together around their arrays, as the tick holds them.
        # What comes back is four checksums (a streaming pass the fold's
        # last fusion takes in): whole results would be held to the
        # row-major result layout, K padded to 128 lanes
        def one(vp, vs, ap, as_):
            return fold(CellTable(vp, vs, jnp.int32(0), width, CELL, kv),
                        CellTable(ap, as_, jnp.int32(0), width, CELL, ka))

        def sums(*t):
            inc, bestr = jax.vmap(one)(*t)
            at = jnp.arange(inc.size, dtype=jnp.int32).reshape(inc.shape)
            return jnp.stack([inc.sum(), bestr.sum(), (inc * at).sum(),
                              (bestr * at).sum(), (inc > 0).sum()])

        return jax.jit(sums)

    def xla(v, a):
        return combat_fold_xla(v, a, RADIUS)

    def pallas(v, a):
        return sp.combat_fold_pallas(v, a, RADIUS, interpret=interpret)

    d = jax.devices()[0]
    line = {"width": width, "kv": kv, "ka": ka, "worlds": worlds,
            "lane_fill": round(sp.fold_lane_fill(width), 4),
            "vmem_bytes": sp.fold_vmem_bytes(width, kv, ka),
            "rule": sp.fold_engine("tpu", width, kv, ka),
            "dropped": [int(x.sum()) for x in dropped],
            "device": f"{d.platform}:{d.device_kind}"}
    fn = checked(xla)
    line["xla_ms"] = timed(fn, tables)
    want = np.asarray(fn(*tables))
    line["hits"] = int(want[-1])
    try:
        fn = checked(pallas)
        line["pallas_ms"] = timed(fn, tables)
        line["equal"] = bool((np.asarray(fn(*tables)) == want).all())
    except Exception as e:  # noqa: BLE001 -- a refusal is a reading
        line["pallas_error"] = f"{type(e).__name__}: {str(e)[:300]}"
    return line


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--widths", default=",".join(map(str, WIDTHS)))
    ap.add_argument("--seed", type=int, default=29)
    ap.add_argument("--boosts", default="1,2",
                    help="depth multipliers probed at widths 125 and up "
                         "(the cells' windows run at a boost of 2); the "
                         "widest grid also gets the next doubling, which "
                         "Mosaic refuses")
    ap.add_argument("--cells", type=int, default=CELLS,
                    help="cells a fleet holds (a CPU rehearsal wants few)")
    args = ap.parse_args()
    boosts = [int(b) for b in args.boosts.split(",")]
    for width in (int(w) for w in args.widths.split(",")):
        _cap, _live, kv, ka = geometry(width)
        fleet = args.cells // (width * width)
        for worlds in sorted({1, fleet if width <= 64 else 1} - {0}):
            deeper = boosts if width >= 125 else [1]
            if width == max(WIDTHS):
                deeper = deeper + [2 * deeper[-1]]
            for boost in (deeper if worlds == 1 else [1]):
                print(json.dumps(probe(width, kv * boost, ka * boost,
                                       worlds, args.seed)), flush=True)


if __name__ == "__main__":
    main()
