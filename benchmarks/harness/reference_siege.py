"""The plain reference for the siege world: `reference.tick`'s frame
with ONE difference, that a walker has a home.

Upstream spawns an NPC at its seed's position and brings it back there
(NFCSceneAOIModule.cpp:82-160, NFCNPCRefreshModule.cpp:44-130), so the
siege configuration stands its NPCs on Zipf-sized spawn camps and a
walker that arrives draws its fresh target on the square of half-width
`leash` about its own camp's centre, clipped to the extent, where the
uniform worlds draw it over the whole extent.  Everything else of the
frame (heartbeats, the walk's arithmetic, AoE combat through a k-d tree
that finds every pair whatever the density, death and respawn, regen,
stat recompute) is `harness/reference.py`'s, called and not copied.

The camps and every row's home are made here, from the seed, by this
file's own code (`camps_from_seed`): what the seed decides is part of
the configuration, so a program that homes a row elsewhere draws another
target and the comparison reads a wrong row.

The program's neighbour engine has two levels in this world: a cell
keeps `bucket` rows in the grid and, if it is one of the first
`spill_cells` over-full cells in cell order, `spill_bucket` more behind
it; what fits neither is dropped from that tick's combat, the highest
rows first.  `dropped_rows` works that out from positions and the sizes
the program states, as `compare.dropped_rows` does for one level, and
`compare_ticks` is `compare.compare_ticks` over this file's frame and
drop model.

It imports `reference.py` and `compare.py` where they serve and nothing
of the program under test.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

from . import compare, reference


@dataclasses.dataclass
class Params(reference.Params):
    """`reference.Params` and where every row's walk is at home."""

    home_centres: Optional[np.ndarray] = None  # float32 [rows, 2]
    leash: float = 0.0


def camp_sizes(n: int, camps: int, zipf: float) -> np.ndarray:
    """Camp k (from 1) of `camps` holds n k^-zipf / sum_j j^-zipf NPCs:
    the shares rounded down, the remainder to the largest fractions."""
    weight = np.arange(1, camps + 1, dtype=np.float64) ** -float(zipf)
    share = n * weight / weight.sum()
    sizes = np.floor(share).astype(np.int64)
    short = int(n - sizes.sum())
    sizes[np.argsort(-(share - sizes), kind="stable")[:short]] += 1
    return sizes


def camps_from_seed(seed: int, n: int, extent: float, camps: int,
                    zipf: float, leash: float):
    """(centres float32 [camps, 2], home int [n]): the first thing the
    seed's generator decides is the camps' centres, uniform over the
    extent less the leash on every side (a camp's square lies inside
    the world); rows are handed out camp by camp, the largest first."""
    rng = np.random.default_rng(int(seed))
    margin = min(float(leash), extent / 2.0)
    centres = rng.uniform(margin, extent - margin,
                          (camps, 2)).astype(np.float32)
    return centres, np.repeat(np.arange(camps), camp_sizes(n, camps, zipf))


def home_centres(seed: int, config: dict, extent: float, rows: int
                 ) -> np.ndarray:
    """Every row's camp centre, for the configuration's world built
    from `seed` (rows beyond the population: the first camp's)."""
    w = config["world"]
    centres, home = camps_from_seed(seed, int(w["entities"]), extent,
                                    int(w["camps"]), float(w["camp_zipf"]),
                                    float(w["leash"]))
    of_row = np.zeros(rows, np.int64)
    of_row[:home.size] = home
    return centres[of_row]


def homed_targets(rng_key: np.ndarray, tick: int, centres: np.ndarray,
                  leash: float, extent: float) -> np.ndarray:
    """The frame's fresh walk targets: `centre + leash * (2u - 1)`,
    clipped to the extent, u the frame's first uniform draw (the world's
    key folded with the tick and 1, as `reference.new_targets` folds
    it).  The bits of u are jax.random's, drawn on the host CPU (counter
    based: the same on any backend); the arithmetic is numpy's float32,
    sums and products only."""
    import jax

    f32 = np.float32
    with jax.default_device(jax.devices("cpu")[0]):
        key = jax.numpy.asarray(np.asarray(rng_key, np.uint32))
        key = jax.random.fold_in(jax.random.fold_in(key, int(tick)), 1)
        u = np.asarray(jax.random.uniform(key, centres.shape), f32)
    return np.clip(centres + f32(leash) * (f32(2.0) * u - f32(1.0)),
                   f32(0.0), f32(extent)).astype(f32)


def move(s: reference.State, p: Params, precision: str = "float32") -> None:
    """`reference.move`, but that a walker that arrives takes its fresh
    target about its home: the uniform frame's walk, then the arrived
    rows' targets (the only thing the fresh draw decides) drawn again."""
    before = s.target
    reference.move(s, p, precision)
    # arrived and alive: exactly the rows whose target the frame
    # replaced (a fresh float32 pair equal to the old one is no draw)
    fresh = np.any(s.target.view(np.int32) != before.view(np.int32), axis=1)
    homed = homed_targets(s.rng_key, s.tick, p.home_centres, p.leash,
                          p.extent)
    s.target = np.where(fresh[:, None], homed, before).astype(np.float32)


def tick(s: reference.State, p: Params, precision: str = "float32",
         observed_pos: Optional[np.ndarray] = None, margin: float = 0.0):
    """One frame: the homed walk, then `reference.tick` for everything
    else (heartbeats and the walk touch nothing of each other, so the
    order between them is free)."""
    s = s.copy()
    if p.movement:
        move(s, p, precision)
    rest = dataclasses.replace(p, movement=False)
    return reference.tick(s, rest, precision, observed_pos, margin)


def dropped_rows(pos: np.ndarray, alive: np.ndarray, attacking: np.ndarray,
                 geometry: Dict[str, float]):
    """(victims, attackers) that the stated sizes drop: a cell's rows in
    row order fill `bucket` slots of the grid and, in the first
    `spill_cells` over-full cells (cell order; each side counts its
    own), `spill_bucket` slots more; the rest are dropped."""
    size, width = np.float32(geometry["cell_size"]), int(geometry["width"])
    cx = np.clip(np.floor(pos[:, 0] / size).astype(np.int64), 0, width - 1)
    cy = np.clip(np.floor(pos[:, 1] / size).astype(np.int64), 0, width - 1)
    cell = cy * width + cx
    hot_max = int(geometry.get("spill_cells", 0))

    def beyond(mask: np.ndarray, depth: int, more: int) -> np.ndarray:
        rows = np.flatnonzero(mask)
        order = np.argsort(cell[rows], kind="stable")
        sorted_cells = cell[rows][order]
        head = np.ones(rows.size, bool)
        head[1:] = sorted_cells[1:] != sorted_cells[:-1]
        at = np.arange(rows.size)
        rank = at - np.maximum.accumulate(np.where(head, at, 0))
        # the over-full cells, numbered in cell order by their first
        # row beyond the depth
        hot = np.cumsum(rank == depth) - 1
        held = (rank < depth) | ((hot < hot_max) & (rank - depth < more))
        return rows[order][~held]

    return (beyond(alive, int(geometry["bucket"]),
                   int(geometry.get("spill_bucket", 0))),
            beyond(attacking, int(geometry["att_bucket"]),
                   int(geometry.get("spill_att_bucket", 0))))


def compare_ticks(host: compare.HostSnapshots, params: Params,
                  population: Optional[int], geometry=None,
                  control: bool = False, keep: Optional[dict] = None
                  ) -> Dict[str, float]:
    """`compare.compare_ticks` over this file's frame and drop model:
    the same numbers, reduced the same way.  `keep`, if given, is
    handed the first compared tick's positions, alive flags and
    attackers (for `work_siege`)."""
    lay = host.layout
    ulp = float(np.spacing(np.float32(params.extent)))
    margin = compare.D2_MARGIN_ULPS * float(
        np.spacing(np.float32(params.aoe_radius ** 2)))
    out = {"pos_err_ulp": 0.0, "state_wrong_rows": 0, "ambiguous_rows": 0.0,
           "diff_cells_off": 0, "ledger_wrong_rows": 0, "dropped_off": 0,
           "ticks_compared": 0}
    rows = 1
    for t, before_l, after_l in host.pairs():
        before = compare.to_state(lay, before_l, host.stat_sums)
        if control:
            got = tick(before, params, precision="bfloat16")[0]
        else:
            got = compare.to_state(lay, after_l, host.stat_sums)
        rows = before.alive.shape[0]
        moved = before.copy()
        if params.movement:
            move(moved, params)
        live = got.alive
        err = np.abs(got.pos.astype(np.float64) - moved.pos) / ulp
        out["pos_err_ulp"] = max(out["pos_err_ulp"],
                                 float(err[live].max()) if live.any() else 0.0)
        ref, ambiguous, attacking = tick(
            before, params, observed_pos=got.pos, margin=margin)
        if keep is not None and not keep:
            keep.update(pos=got.pos, alive=before.alive, attacking=attacking,
                        state=before)
        c = None if control else host.counters.get(t + 1)
        if geometry and params.combat:
            vic, att = dropped_rows(got.pos, before.alive, attacking, geometry)
            ambiguous[vic] = True
            ambiguous |= compare.in_reach(got.pos, att, params.aoe_radius)
            if c is not None:
                out["dropped_off"] += abs(
                    int(c["aoi_victim_overflow_drops"]) - vic.size) + abs(
                    int(c["aoi_attacker_overflow_drops"]) - att.size)
        bad = compare.wrong_rows(ref, got)
        out["state_wrong_rows"] += int(np.sum(bad & ~ambiguous))
        out["ambiguous_rows"] += float(ambiguous.sum())
        if got.tick != ref.tick:
            out["state_wrong_rows"] += rows
        if not control:
            still = compare._frozen(lay, after_l)
            for k, v in compare._frozen(lay, before_l).items():
                out["state_wrong_rows"] += int(np.sum(
                    v.view(np.int32) != still[k].view(np.int32)))
            if c is not None and "diff_cells" in c:
                out["diff_cells_off"] += abs(
                    int(c["diff_cells"]) - compare.changed_cells(
                        lay, before_l, after_l))
        out["ledger_wrong_rows"] += compare.ledger_wrong_rows(got, population)
        out["ticks_compared"] += 1
    n = max(1, out["ticks_compared"])
    out["ambiguous_rows"] = out["ambiguous_rows"] / n / rows * 1e6
    return out
