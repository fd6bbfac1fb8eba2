"""The plain reference of the served siege: what each client's mirror
has to hold when its avatar stands in the crowd.

The world's frame is `reference_siege`'s (the driver calls it, nothing
of it is copied here).  This file is the MIRROR's reference: from the
positions and flags a sampled frame was served from, the NPCs a client
must mirror are exactly those alive, inside the extent, in scope (same
scene; group 0 or the same group) and within the interest radius of its
avatar, each at its u16-quantised position.  Set aside, and counted per
million entries checked (`mirror_ambiguous`), are only

- rows within float32 rounding of r^2, which may be on either side, and
- rows that the program's interest table drops BY THE SIZES IT STATES:
  cells of one radius; a cell's rows in row order fill `bucket` slots
  and, in the first `spill_cells` over-full cells in cell order,
  `spill_depth` more (`reference_siege.dropped_rows`' model of two
  levels, at the interest grid's geometry).  What is set aside follows
  from the world's state and the stated sizes, never from what the
  program did; and the count of rows so dropped is held, exactly,
  against the program's own counter of the frame (`dropped_off`).

At 2^20 rows the drop model is an argsort: it is worked out once a
sampled frame and shared by that frame's clients.

Where the avatars stand is the traffic's: avatar i of S stands where
NPC row floor((i + 0.5) * entities / S) stood at tick 0.  What the seed
decides about tick 0 is worked out here by this file's own code
(`tick0_positions`: the generator consumed in the configuration's one
order), so a run that stands its avatars elsewhere reads wrong entries.

Numpy; imports `reference_siege` and `compare` (the harness's own) and
nothing of the program under test.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

from . import compare, reference_siege

QMAX = 65535  # the interest stream's u16 quantisation


def interest_geometry(extent: float, radius: float,
                      sizes: Sequence[int]) -> Dict[str, float]:
    """The interest grid with the sizes the program states
    (`GameRole.resolved_interest`: bucket, spill cells, spill depth), in
    the words `reference_siege.dropped_rows` reads."""
    bucket, cells, depth = (int(v) for v in sizes)
    return {"cell_size": float(radius),
            "width": max(1, int(np.ceil(float(extent) / float(radius)))),
            "bucket": bucket, "att_bucket": bucket,
            "spill_cells": cells, "spill_bucket": depth,
            "spill_att_bucket": depth}


def frame_drops(pos: np.ndarray, binned: np.ndarray, extent: float,
                radius: float, sizes: Sequence[int]) -> np.ndarray:
    """bool [rows]: the rows of `binned` (alive and inside the extent:
    what the interest table bins) that fit neither level."""
    out = np.zeros(binned.shape, bool)
    out[reference_siege.dropped_rows(
        pos, binned, np.zeros_like(binned),
        interest_geometry(extent, radius, sizes))[0]] = True
    return out


def tick0_positions(seed: int, config: dict, extent: float) -> np.ndarray:
    """float32 [entities, 2]: where every NPC stands at tick 0.  The
    seed's generator decides, in this order, the camps' centres, two
    walk targets a row about its camp (a float32 uniform pair each,
    `centre + leash * (2u - 1)` clipped to the extent) and a float32
    uniform that puts the row on the segment between them."""
    w = config["world"]
    n, camps = int(w["entities"]), int(w["camps"])
    leash, f32 = np.float32(w["leash"]), np.float32
    rng = np.random.default_rng(int(seed))
    margin = min(float(leash), extent / 2.0)
    centres = rng.uniform(margin, extent - margin,
                          (camps, 2)).astype(np.float32)
    home = np.repeat(np.arange(camps), reference_siege.camp_sizes(
        n, camps, float(w["camp_zipf"])))

    def about():
        u = rng.random((n, 2), dtype=np.float32)
        return np.clip(centres[home] + leash * (f32(2.0) * u - f32(1.0)),
                       f32(0.0), f32(extent)).astype(np.float32)

    start, target = about(), about()
    along = rng.random((n, 1), dtype=np.float32)
    return (start + along * (target - start)).astype(np.float32)


def avatar_rows(entities: int, sessions: int) -> np.ndarray:
    """NPC row whose tick-0 spot avatar i takes: a stratified draw over
    the rows, which are handed out camp by camp, largest first."""
    i = np.arange(sessions, dtype=np.float64)
    return np.floor((i + 0.5) * entities / sessions).astype(np.int64)


def avatar_spots(seed: int, config: dict, extent: float,
                 sessions: int) -> np.ndarray:
    """float32 [sessions, 2]: where each avatar has to stand."""
    n = int(config["world"]["entities"])
    return tick0_positions(seed, config, extent)[avatar_rows(n, sessions)]


def mirror_wrong(host, mirrors: dict, idents: np.ndarray,
                 session_rows: Sequence[int], lay, extent: float,
                 radius: float, sizes_of: Dict[int, Sequence[int]],
                 spots: np.ndarray,
                 program_dropped: Optional[Dict[int, int]] = None) -> dict:
    """Hold each client's mirror at each sampled frame against the world
    the frame was served from.

    host            compare.HostSnapshots (`post[tick]`: the NPC banks
                    and the players' after the tick)
    mirrors         (client, tick) -> {ident key: position}
    idents          [rows, 2] the NPC rows' guids as the wire names them
    session_rows    each client's avatar row in the Player bank
    sizes_of        tick -> the sizes the program stated for the frame
    spots           where each avatar has to stand (`avatar_spots`)
    program_dropped tick -> the program's own `dropped` of the frame

    Returns mirror_wrong, mirror_checked, mirror_ambiguous (per million
    checked) and interest_dropped_off (sum over the sampled frames of
    |program's counter - rows the stated sizes drop|)."""
    key_of = {(int(h), int(d)): r for r, (h, d) in enumerate(idents)}
    names = lay.i32_names
    scene_c, group_c = names.index("SceneID"), names.index("GroupID")
    r2 = np.float32(radius) * np.float32(radius)
    margin = compare.D2_MARGIN_ULPS * float(np.spacing(r2))
    scale = extent / QMAX
    wrong = checked = ambiguous = dropped_off = 0
    frame: dict = {}  # what a sampled frame's clients share
    by_frame = sorted(mirrors.items(), key=lambda kv: (kv[0][1], kv[0][0]))
    for (client, tick), mirror in by_frame:
        post = host.post.get(tick)
        if post is None:
            continue
        if frame.get("tick") != tick:
            pos = post["vec"][:, lay.position_col, :]
            inside = np.all((pos[:, :2] >= 0)
                            & (pos[:, :2] <= np.float32(extent)), axis=1)
            binned = post["alive"] & inside
            overfull = frame_drops(pos, binned, extent, radius,
                                   sizes_of[tick])
            q = np.clip(np.round(pos * np.float32(QMAX / extent)), 0, QMAX)
            frame = {"tick": tick, "pos": pos, "binned": binned,
                     "overfull": overfull, "q": q}
            if program_dropped is not None:
                dropped_off += abs(int(program_dropped.get(tick, -1))
                                   - int(overfull.sum()))
        pos, binned, overfull, q = (frame[k] for k in
                                    ("pos", "binned", "overfull", "q"))
        me = session_rows[client]
        obs = post["obs_vec"][me, 0, :2]  # Position is the first vector
        i32 = post["i32"]
        d = pos[:, :2] - obs[None, :]
        d2 = d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1]
        scoped = binned & (i32[:, scene_c] == post["obs_i32"][me, scene_c]) \
            & ((i32[:, group_c] == 0)
               | (i32[:, group_c] == post["obs_i32"][me, group_c]))
        near = np.abs(d2.astype(np.float64) - float(r2)) <= margin
        near |= overfull & (d2 <= r2)
        want = set(np.flatnonzero(scoped & (d2 <= r2) & ~near).tolist())
        either = set(np.flatnonzero(scoped & near).tolist())
        got = {}
        for key, p in mirror.items():
            row = key_of.get(key)
            if row is not None:
                got[row] = p
        rows = set(got)
        if not np.array_equal(obs, spots[client]):
            # the avatar stands where the traffic does not put it: the
            # whole of this view is somebody else's
            wrong += max(1, len(want | rows))
        wrong += len(want - rows) + len(rows - want - either)
        for row in rows & (want | either):
            mine = np.round(np.asarray(got[row], np.float64) / scale)
            if not np.array_equal(mine, q[row].astype(np.float64)):
                wrong += 1
        checked += len(want)
        ambiguous += len(either)
    return {"mirror_wrong": wrong, "mirror_checked": checked,
            "mirror_ambiguous": 1e6 * ambiguous / max(1, checked),
            "interest_dropped_off": dropped_off}
