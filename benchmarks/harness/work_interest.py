"""The work of one frame's visibility answer, counted from positions:
what `interest_roofline` divides into.

Whatever table a program bins its world into, a frame that tells every
session which NPCs stand within its interest radius needs at least:

    bytes   every alive row inside the extent read once (x, y, scene,
            group) and every (session, visible row) written once (the
            row's id)

No operation is counted: a distance test a candidate is what a table
saves or spends, and the answer is bound by bytes a thousand times over
either way.  Nothing the program states enters: not its cell depths,
not its second level, not the candidates it read.  So a different
interest engine later is held to the same work.
"""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

WORD = 4
ROW_READ_WORDS = 4   # x, y, scene, group
PAIR_WRITE_WORDS = 1  # the visible row's id


def interest_work(post: dict, session_rows: Sequence[int], lay,
                  extent: float, radius: float) -> Dict[str, float]:
    """Bytes of one frame's answer, from the banks the frame was served
    from (`compare.HostSnapshots.post[tick]`: the NPCs' and, as
    `obs_*`, the players')."""
    names = lay.i32_names
    scene_c, group_c = names.index("SceneID"), names.index("GroupID")
    pos = post["vec"][:, lay.position_col, :2]
    inside = np.all((pos >= 0) & (pos <= np.float32(extent)), axis=1)
    binned = post["alive"] & inside
    i32 = post["i32"]
    r2 = np.float32(radius) * np.float32(radius)
    pairs, widest = 0, 0
    for me in session_rows:
        d = pos - post["obs_vec"][me, 0, :2][None, :]
        seen = binned & (d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1] <= r2) \
            & (i32[:, scene_c] == post["obs_i32"][me, scene_c]) \
            & ((i32[:, group_c] == 0)
               | (i32[:, group_c] == post["obs_i32"][me, group_c]))
        count = int(seen.sum())
        pairs += count
        widest = max(widest, count)
    rows = int(binned.sum())
    return {"bytes": float(WORD * (rows * ROW_READ_WORDS
                                   + pairs * PAIR_WRITE_WORDS)),
            "flops": 0.0, "rows": rows, "pairs": pairs,
            "widest_view": widest}
