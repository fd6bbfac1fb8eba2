"""The plain reference for a fleet of rooms: `reference.tick` applied to
ONE room's rows alone, room by room.

A fleet is thousands of private rooms that share nothing: upstream's
clone scenes give every enter request a group of its own
(NFCSceneProcessModule.cpp:74-134), and a group's objects see, hit and
are broadcast to their own group only.  So the reference of a fleet is
the reference of a world (`harness/reference.py`: NoahGameFrame's
per-object Execute() loop in plain numpy) run on each room as if no
other room existed.  The driver keeps the fleet's banks, `[slots, rows,
...]` arrays, from right before and right after a fleet tick; here each
occupied slot is cut out and replayed from the state the program had
before the tick, from that room's rows and nothing else, and held
against what the program made of that room (`compare.compare_ticks`,
the comparison of the one-world cells, number for number).  Any effect
of one room on another is then a wrong row: the reference cannot see
the other room, so it cannot reproduce it.

It imports nothing of the program under test.
"""

from __future__ import annotations

from typing import Dict, Iterable, Optional

import numpy as np

from . import compare, reference

SUMMED = ("state_wrong_rows", "diff_cells_off", "ledger_wrong_rows",
          "dropped_off")


def room_leaves(fleet: Dict[str, np.ndarray], slot: int
                ) -> Dict[str, np.ndarray]:
    """One room's banks out of the fleet's: every leaf has the slot
    axis first."""
    return {name: leaf[slot] for name, leaf in fleet.items()}


def room_snapshots(layout: compare.Layout,
                   pre: Dict[int, Dict[str, np.ndarray]],
                   post: Dict[int, Dict[str, np.ndarray]],
                   counters: Dict[int, Dict[str, np.ndarray]],
                   stat_sums: np.ndarray, slot: int
                   ) -> compare.HostSnapshots:
    """What `compare.compare_ticks` takes, for the room in `slot`."""
    return compare.HostSnapshots(
        layout,
        {t: room_leaves(leaves, slot) for t, leaves in pre.items()},
        {t: room_leaves(leaves, slot) for t, leaves in post.items()},
        {t: {name: int(col[slot]) for name, col in cols.items()}
         for t, cols in counters.items()},
        stat_sums[slot])


def compare_fleet(layout: compare.Layout,
                  pre: Dict[int, Dict[str, np.ndarray]],
                  post: Dict[int, Dict[str, np.ndarray]],
                  counters: Dict[int, Dict[str, np.ndarray]],
                  stat_sums: np.ndarray, params: reference.Params,
                  slots: Iterable[int], population: Optional[int],
                  geometry=None, control: bool = False) -> Dict[str, float]:
    """Replay every kept tick of every room in `slots` and reduce to the
    numbers compared: the worst position error of any room, the wrong
    rows, the counters off and the ledger faults of all rooms summed,
    the rows set aside per million rows and tick over all rooms, the
    fewest ticks any room was compared on, and the rooms compared.

    `pre[t]` / `post[t]`: the fleet's leaves before tick `t` ran / once
    tick `t` was reached; `counters[t]`: the per-room counter columns of
    the tick that reached `t`; `stat_sums`: `[slots, rows, stats]`."""
    out: Dict[str, float] = {"pos_err_ulp": 0.0, "ambiguous_rows": 0.0,
                             "rooms_compared": 0, "ticks_compared": 0}
    out.update({name: 0 for name in SUMMED})
    fewest = None
    for slot in slots:
        got = compare.compare_ticks(
            room_snapshots(layout, pre, post, counters, stat_sums,
                           int(slot)),
            params, population=population, geometry=geometry,
            control=control)
        out["pos_err_ulp"] = max(out["pos_err_ulp"], got["pos_err_ulp"])
        out["ambiguous_rows"] += got["ambiguous_rows"]
        for name in SUMMED:
            out[name] += got[name]
        ticks = got["ticks_compared"]
        fewest = ticks if fewest is None else min(fewest, ticks)
        out["rooms_compared"] += int(ticks > 0)
    # every room has the same rows, so the mean of the rooms' shares is
    # the fleet's share
    out["ambiguous_rows"] /= max(1, out["rooms_compared"])
    out["ticks_compared"] = fewest or 0
    return out
