"""The plain reference of what a client seated in a clone room mirrors.

Upstream's clone scenes give every enter request a private group
(NFCSceneProcessModule.cpp:74-134), and a group's objects are broadcast
to that group only (NFCSceneAOIModule.cpp:531-593).  So a client seated
in a room mirrors, after a frame is served:

- the NPCs of ITS room that are alive, inside the room's extent, in
  scope (the room's scene and group 0: a room's NPCs are scene-wide,
  so every group of the scene sees them) and within the interest
  radius of its avatar, at their u16-quantised positions (`QMAX` over the room's extent), each with the HP the
  room's banks hold after the served tick;
- its own avatar, at the quantised spot the room's seed draws;
- nothing of any other room, and nothing of the role's single world but
  the client's own avatar there (its identity, which it keeps).

The room's rows are those the driver copied right after the fleet tick
of the sampled frame (`drivers/rooms.FleetSnapshots`); the fleet's tick
itself is held by `reference_rooms.py`, called and not copied.  Where a
client's room is, which row holds its avatar and which frame ticked
which fleet tick, the program states; the avatar's spot is worked out
here from the run's seed and the client's account by this file's own
code, so an avatar standing anywhere else turns its whole view wrong.

Departures, each stated:

- an NPC within float32 rounding of r^2 of the avatar may be on either
  side (`compare.D2_MARGIN_ULPS`); it is set aside and counted
  (`mirror_ambiguous`, per million entries checked), its position and
  HP still held if the mirror has it;
- the avatar's own HP is not held: it is the single world's, copied by
  the program into the room when it seated the avatar;
- a room holds 128 NPC rows and every row is tested: there is no cell
  depth to drop anything.

It imports nothing of the program under test.
"""

from __future__ import annotations

import zlib
from typing import Dict, Sequence, Tuple

import numpy as np

from . import compare

QMAX = 65535  # the interest stream's u16 quantisation
# the wire's Ident of a room's row, a contract of the wire stated here
# again: svrid = TAG + room id * CODES + class code (NPC 0, Player 1); index = row
ROOM_IDENT_TAG = 1 << 48
ROOM_CLASS_CODES = 8
NPC_CODE, PLAYER_CODE = 0, 1
ROOM_SCENE = 1  # build_benchmark_world stands a room's NPCs in scene 1


def room_head(room_id: int, code: int) -> int:
    return ROOM_IDENT_TAG + int(room_id) * ROOM_CLASS_CODES + int(code)


def avatar_spots(seed: int, accounts: Sequence[str],
                 extent: float) -> np.ndarray:
    """[clients, 2] where each client's avatar stands in its room: the
    room's seed is the run's (mod 2^32) times 1000003 plus the CRC-32 of
    the account, mod 2^32, and the spot is uniform over the room from
    the generator seeded (room seed, 1)."""
    out = np.zeros((len(accounts), 2), np.float32)
    for i, account in enumerate(accounts):
        room = ((int(seed) % 2 ** 32) * 1000003
                + zlib.crc32(account.encode())) % 2 ** 32
        out[i] = np.random.default_rng([room, 1]).uniform(
            0.0, float(extent), 2).astype(np.float32)
    return out


def _quantise(pos: np.ndarray, extent: float) -> np.ndarray:
    return np.clip(np.round(pos * np.float32(QMAX / extent)), 0, QMAX)


def want_rows(lay: compare.Layout, leaves: Dict[str, np.ndarray],
              spot: np.ndarray, extent: float, radius: float
              ) -> Tuple[np.ndarray, np.ndarray]:
    """(rows the client must mirror, rows either way) of one room's NPC
    banks `leaves` (no slot axis) for an avatar at `spot`."""
    names = lay.i32_names
    i32 = leaves["i32"]
    pos = leaves["vec"][:, lay.position_col, :2]
    inside = np.all((pos >= 0) & (pos <= np.float32(extent)), axis=1)
    scoped = (i32[:, names.index("SceneID")] == ROOM_SCENE) \
        & (i32[:, names.index("GroupID")] == 0)
    d = pos - spot[None, :].astype(np.float32)
    d2 = d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1]
    r2 = np.float32(radius) * np.float32(radius)
    margin = compare.D2_MARGIN_ULPS * float(np.spacing(r2))
    near = np.abs(d2.astype(np.float64) - float(r2)) <= margin
    live = leaves["alive"] & inside & scoped
    return (np.flatnonzero(live & (d2 <= r2) & ~near),
            np.flatnonzero(live & near))


def mirror_wrong(lay: compare.Layout, post: Dict[int, Dict[str, np.ndarray]],
                 mirrors: Dict[Tuple[int, int], dict],
                 mirror_ticks: Dict[int, int],
                 seats: Sequence[Tuple[int, int, int]],
                 spots: np.ndarray, extent: float, radius: float,
                 own: Sequence[Tuple[int, int]]) -> Dict[str, float]:
    """Hold every kept mirror to its room's banks after the served tick.

    `post[t]`: the fleet's NPC leaves `[slots, rows, ...]` once fleet
    tick `t` was reached; `mirrors[(client, frame)]`: key -> (position,
    HP); `mirror_ticks[frame]`: the fleet tick that frame reached;
    `seats[client]`: (room id, slot, avatar row) as the program states;
    `own[client]`: the key of the client's avatar in the single world.
    A missing, extra or misplaced entry is `mirror_wrong` (an entry of
    another room or of the single world counts there, and in
    `mirror_foreign`), an NPC at the wrong HP `mirror_hp_wrong`; a
    client whose mirror was never checked is a room unseen."""
    hp_c = lay.i32_names.index("HP")
    scale = extent / QMAX
    wrong = hp_wrong = foreign = checked = ambiguous = 0
    seen_clients = set()
    for (client, frame), mirror in sorted(mirrors.items()):
        leaves = post.get(mirror_ticks.get(frame))
        if leaves is None:
            continue
        room_id, slot, row = seats[client]
        mine = {name: leaf[slot] for name, leaf in leaves.items()}
        want, either = want_rows(lay, mine, spots[client], extent, radius)
        q = _quantise(mine["vec"][:, lay.position_col, :], extent)
        npc_head = room_head(room_id, NPC_CODE)
        me = (room_head(room_id, PLAYER_CODE), int(row))
        got: Dict[int, tuple] = {}
        for key, entry in mirror.items():
            if key[0] == npc_head:
                got[int(key[1])] = entry
            elif key == me:
                q_me = _quantise(np.asarray([*spots[client], 0.0],
                                            np.float32), extent)
                mine_q = np.round(np.asarray(entry[0], np.float64) / scale)
                wrong += int(not np.array_equal(mine_q, q_me))
            elif tuple(key) != tuple(own[client]):
                wrong += 1
                foreign += 1
        if me not in mirror:
            wrong += 1
        rows = set(got)
        want_s, either_s = set(want.tolist()), set(either.tolist())
        wrong += len(want_s - rows) + len(rows - want_s - either_s)
        for r in rows & (want_s | either_s):
            pos, hp = got[r]
            mine_q = np.round(np.asarray(pos, np.float64) / scale)
            if not np.array_equal(mine_q, q[r].astype(np.float64)):
                wrong += 1
            if hp is None or int(hp) != int(mine["i32"][r, hp_c]):
                hp_wrong += 1
        checked += len(want_s) + 1
        ambiguous += len(either_s)
        seen_clients.add(client)
    return {"mirror_wrong": wrong, "mirror_hp_wrong": hp_wrong,
            "mirror_foreign": foreign, "mirror_checked": checked,
            "mirror_ambiguous": 1e6 * ambiguous / max(1, checked),
            "rooms_unseen": len(seats) - len(seen_clients)}


def control_mirrors(lay: compare.Layout, post, mirrors, mirror_ticks,
                    seats, spots: np.ndarray, extent: float,
                    radius: float) -> Dict[Tuple[int, int], dict]:
    """The control of the mirror comparison: what each client would
    hold had the interest filter and the quantisation run on bfloat16
    positions, put in the mirrors' place."""
    from .reference import _bf16

    hp_c = lay.i32_names.index("HP")
    out = {}
    for (client, frame) in mirrors:
        leaves = post.get(mirror_ticks.get(frame))
        if leaves is None:
            continue
        room_id, slot, row = seats[client]
        mine = {name: leaf[slot] for name, leaf in leaves.items()}
        pos = _bf16(mine["vec"][:, lay.position_col, :])
        spot = _bf16(spots[client])
        d = pos[:, :2] - spot[None, :]
        seen = mine["alive"] & (d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1]
                                <= np.float32(radius * radius))
        q = _quantise(pos, extent) * (extent / QMAX)
        held = {(room_head(room_id, NPC_CODE), int(r)):
                (tuple(q[r].tolist()), int(mine["i32"][r, hp_c]))
                for r in np.flatnonzero(seen)}
        held[(room_head(room_id, PLAYER_CODE), int(row))] = (
            tuple((_quantise(np.asarray([*spot, 0.0], np.float32), extent)
                   * (extent / QMAX)).tolist()), None)
        out[(client, frame)] = held
    return out
