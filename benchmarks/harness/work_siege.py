"""The work of the hot cells, counted from positions and the
configuration file: what `aoe_spill_roofline` divides into.

A cell is HOT when more than the file's `hot_cell_rows` alive rows stand
in it (cells of the AoE radius on the file's extent).  Whatever second
level a program has, the frame needs for the hot cells at least:

    operations   8 flops (two subtractions, two products, a sum, a
                 compare, a masked add, a max: `work.tick_work`'s pair)
                 for every in-radius enemy pair of this tick's attackers
                 and live victims with an end in a hot cell
    bytes        every alive row in a hot cell read once as a victim
                 (x, y, camp, scene, group) and its result (incoming,
                 strongest attacker) written once; every attacker in a
                 hot cell read once more (attack, row)

Nothing the program states enters: not its depths, not its count of hot
cells, not which rows it placed where.  So a different second level
later is held to the same work.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from . import reference

WORD = 4
VICTIM_READ_WORDS = 5   # x, y, camp, scene, group
VICTIM_WRITE_WORDS = 2  # incoming, strongest attacker
ATTACKER_READ_WORDS = 2  # attack value, row
PAIR_FLOPS = 8.0


def cells_of(pos: np.ndarray, cell_size: float, width: int) -> np.ndarray:
    size = np.float32(cell_size)
    at = np.clip(np.floor(pos[:, :2] / size).astype(np.int64), 0, width - 1)
    return at[:, 1] * width + at[:, 0]


def occupancy(pos: np.ndarray, alive: np.ndarray, cell_size: float,
              extent: float, hot_cell_rows: int) -> Dict[str, float]:
    """The counts a configuration states about its crowd."""
    width = max(1, int(float(extent) / float(cell_size)))
    count = np.bincount(cells_of(pos, cell_size, width)[alive],
                        minlength=width * width)
    hot = count > int(hot_cell_rows)
    return {"deepest_cell_rows": int(count.max()),
            "hot_cells": int(hot.sum()),
            "rows_in_hot_cells": int(count[hot].sum()),
            "rows_beyond_hot_depth": int((count[hot] - hot_cell_rows).sum()),
            "share_of_rows_in_hot_cells":
                float(count[hot].sum()) / max(1, int(alive.sum()))}


def spill_work(state: reference.State, params: reference.Params,
               pos: np.ndarray, attacking: np.ndarray,
               hot_cell_rows: int) -> Dict[str, float]:
    """Bytes and operations of one frame's hot cells, from the state
    before the frame, the positions combat was decided on and the rows
    that attacked."""
    cell_size = float(params.aoe_radius)
    width = max(1, int(float(params.extent) / cell_size))
    cell = cells_of(pos, cell_size, width)
    count = np.bincount(cell[state.alive], minlength=width * width)
    in_hot = (count > int(hot_cell_rows))[cell]
    atk = state.props["ATK_VALUE"]
    att_rows = np.flatnonzero(attacking & (atk != 0))
    vic_rows = np.flatnonzero(state.alive & (state.props["HP"] > 0))
    pairs = 0
    if att_rows.size and vic_rows.size:
        from scipy.spatial import cKDTree

        r = float(params.aoe_radius)
        found = cKDTree(pos[att_rows].astype(np.float64)) \
            .sparse_distance_matrix(cKDTree(pos[vic_rows].astype(np.float64)),
                                    r, output_type="coo_matrix")
        a, v = att_rows[found.row], vic_rows[found.col]
        camp, scene, group = (state.props[k]
                              for k in ("Camp", "SceneID", "GroupID"))
        enemy = (camp[a] != camp[v]) & (scene[a] == scene[v]) \
            & (group[a] == group[v])
        pairs = int(np.sum(enemy & (in_hot[a] | in_hot[v])))
    victims = int(np.sum(in_hot & state.alive))
    attackers = int(np.sum(in_hot[att_rows]))
    return {"flops": PAIR_FLOPS * pairs,
            "bytes": float(WORD * (
                victims * (VICTIM_READ_WORDS + VICTIM_WRITE_WORDS)
                + attackers * ATTACKER_READ_WORDS)),
            "pairs": pairs, "victims": victims, "attackers": attackers}
