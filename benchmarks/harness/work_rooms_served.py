"""The work of one frame's visibility answer for the seated clients of
a clone fleet, counted from positions: what `rooms_interest_roofline`
divides into.

Whatever a program does to tell every seated client which rows of its
room it sees, a frame needs at least:

    bytes   every alive row of every served room read once (x, y,
            scene, group; a room's NPC rows and its avatar's row) and
            every (client, visible row) written once (the row's id)

No operation is counted, as in `work_interest.py`: a distance test a
row is bound by bytes a thousand times over.  Nothing the program
states enters but where each client is seated; the rows come from the
banks the driver kept, the avatars' spots from the run's seed
(`reference_rooms_served.avatar_spots`).  The program also hands the
served rooms' public columns and their diff planes to the host in the
same program: that is not counted, so the share is a lower bound.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

from . import compare, reference_rooms_served

WORD = 4
ROW_READ_WORDS = 4   # x, y, scene, group
PAIR_WRITE_WORDS = 1  # the visible row's id


def interest_work(lay: compare.Layout, post: Dict[str, np.ndarray],
                  seats: Sequence[Tuple[int, int, int]],
                  spots: np.ndarray, extent: float,
                  radius: float) -> Dict[str, float]:
    """Bytes of one frame's answer, from the fleet's NPC banks once a
    fleet tick was reached (`post`: leaves `[slots, rows, ...]`)."""
    rows = pairs = widest = 0
    for client, (_room, slot, _row) in enumerate(seats):
        mine = {name: leaf[slot] for name, leaf in post.items()}
        rows += int(mine["alive"].sum()) + 1  # and the avatar's row
        want, either = reference_rooms_served.want_rows(
            lay, mine, spots[client], extent, radius)
        seen = int(want.size + either.size) + 1  # and itself
        pairs += seen
        widest = max(widest, seen)
    return {"bytes": float(WORD * (rows * ROW_READ_WORDS
                                   + pairs * PAIR_WRITE_WORDS)),
            "flops": 0.0, "rows": rows, "pairs": pairs,
            "widest_view": widest}
