"""The serve edge of the clone rooms a game role hosts.

Upstream's clone scenes (NFCSceneProcessModule.cpp:74-134) give every
enter request a private group of the scene.  A `GameRole` with a
`parallel.rooms.RoomDirectory` attached backs that group with a room of
the fleet: the swap into a CLONE scene (`ReqAckSwapScene`) makes a room
from a seed, seats the avatar in the room's Player bank, routes the
session there (`SessionTable.bind_room`), and from then on the session
is served its room and nothing else:

- the position lane: one device program over the served rooms
  (`rooms.interest`, module `jit_rooms_interest`, scope
  `nf.rooms.interest`) gathers each served room's rows from the fleet's
  banks, quantises their positions over the room's extent and tells
  each seat which rows are alive, in scope and within the interest
  radius of its avatar (a room holds 128 NPC and 4 Player rows: every
  row is tested, no cell table);
- the property lane: the same program compares the served rooms'
  public integer columns with what the last hand-over read and packs
  the changed cells as bit planes by column (`kernel.pack_diff_planes`,
  as the world's tick hands its diff over); a seat is sent the changed
  cells of the rows it sees, and every public column of a row that
  comes into its view, in one message a class a frame
  (`BatchPropertyColumns`);
- the wire: a room's rows ride `Ident`s whose `svrid` carries the room
  (`wire.room_ident_head`), so a client mirror keys them apart from
  the world's and from every other room's.

The served rooms sit at fixed positions of a padded index vector (the
next power of two of the rooms with a session); it grows by an
announced retrace (`CostBook.generation_bump`), as the interest table
does.  Leaving the scene, the game or the proxy link, and destroying
the room, release the room and return the session to the single world.

None of this runs for a role with no room seated: the world's enter,
swap and frame take the code they took before rooms were served.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, Hashable, List, Optional, Tuple

import numpy as np

from ...core.datatypes import Bank, DataType, Guid, next_pow2
from ...telemetry.tracing import span
from ..defines import MsgID
from ..wire import BatchPropertyColumns, InterestPosSync, room_ident_head

#: the classes a room serves, in the order of their ident class codes
ROOM_CLASSES = ("NPC", "Player")


def room_seed(seed: int, account: str) -> int:
    """The seed of the room a clone enter makes: the role's room seed
    and the account that entered, so a player's room is the same
    whatever order a burst of enters arrives in."""
    return (int(seed) * 1000003 + zlib.crc32(account.encode())) % 2 ** 32


def avatar_spot(seed: int, extent: float) -> np.ndarray:
    """Where the avatar stands in the room made from `seed`: x, y
    uniform over the room, from a stream of its own."""
    rng = np.random.default_rng([int(seed), 1])
    return rng.uniform(0.0, float(extent), 2).astype(np.float32)


@dataclasses.dataclass
class Seat:
    """One session seated in a room."""

    room_id: int
    pos: int  # position in the served index vector
    row: int  # the avatar's row in the room's Player bank
    world_guid: Guid  # the avatar in the role's single world
    # class -> (rows, quantised positions) last sent, rows ascending
    seen: Dict[str, Tuple[np.ndarray, np.ndarray]] = dataclasses.field(
        default_factory=dict)
    fresh: bool = True  # no hand-over of this seat's room read yet


class RoomServe:
    """Clone enters into a `RoomDirectory`'s rooms and the lanes that
    serve them (module docstring)."""

    def __init__(self, role, directory, seed: int = 0):
        self.role = role
        self.directory = directory
        self.seed = int(seed)
        world = directory.template_world
        self.extent = float(world.config.extent)
        # the room's own scene: where the template room's NPCs stand
        self.scene_id = min(world.scene.scenes)
        kernel = directory.batch.kernel
        self.classes = tuple(c for c in ROOM_CLASSES
                             if c in role.sync_classes
                             and c in kernel.store.class_index)
        # class -> [(column, name)]: the public integer columns whose
        # changes the tick tracks, the property lane's columns
        self.plan: Dict[str, List[Tuple[int, str]]] = {}
        for cname in self.classes:
            spec = kernel.store.spec(cname)
            tracked = kernel.diff_columns(cname, Bank.I32)
            self.plan[cname] = sorted(
                (s.col, name) for name, s in spec.slots.items()
                if s.bank == Bank.I32 and s.prop.public
                and s.prop.type == DataType.INT and tracked[s.col])
        self.seats: Dict[Hashable, Seat] = {}
        self._by_pos: List[Optional[Hashable]] = []
        self.pad = 0  # the served index vector's length
        self._prev = None  # class -> [pad, rows, columns] last read
        self._jit = None
        reg = role.telemetry.registry
        self._served_total = reg.counter(
            "nf_rooms_served_slots_total",
            "room slots handed to the serve edge, one per seated room a "
            "served frame")
        self._enters_total = reg.counter(
            "nf_rooms_clone_enters_total",
            "clone-scene enters seated in a room of the fleet")
        self._pad_gauge = reg.gauge(
            "nf_rooms_served_pad",
            "length of the served rooms' padded index vector")

    # ------------------------------------------------------------ seats
    def swap(self, sess, key: Hashable, scene_id: int) -> bool:
        """A swap request of a session with a live avatar: a CLONE scene
        seats it in a new room (True: handled); any other scene takes a
        seated session back to the single world first and leaves the
        swap to the role (False)."""
        from ...game.scene_process import SCENE_TYPE_CLONE

        sp = getattr(self.role.game_world, "scene_process", None)
        if sp is not None and sp.scene_type(scene_id) == SCENE_TYPE_CLONE:
            self.enter(sess, key, scene_id)
            return True
        if key in self.seats:
            self.leave(key, sess)
        return False

    def enter(self, sess, key: Hashable, scene_id: int) -> int:
        """The clone enter: the world's private group (as the role's
        own enter makes it), a room made from the seed, the avatar in
        the room's Player bank, the session routed to the room."""
        role = self.role
        with span("rooms.enter", scene=int(scene_id)):
            if key in self.seats:
                self.leave(key, sess, back=False)
            group = role._enter_scene(sess.guid, scene_id)
            seed = room_seed(self.seed, sess.account)
            leaves, row = self._avatar_leaves(sess.guid, seed, group)
            d = self.directory
            room_id = d.create_rooms([seed], rows=leaves)[0]
            table = role._session_table
            k = role.kernel
            table.ensure(key, sess.conn_id, k.store.row_of(sess.guid)[1])
            table.bind_room(key, room_id)
            table.invalidate(key)  # no observer of the single world
            role.reset_view(sess)
            role._guid_session.pop(sess.guid, None)
            pos = next((i for i, k2 in enumerate(self._by_pos)
                        if k2 is None), len(self._by_pos))
            if pos == len(self._by_pos):
                self._by_pos.append(None)
            self._by_pos[pos] = key
            self.seats[key] = Seat(room_id, pos, row, sess.guid)
            self._enters_total.inc()
        return room_id

    def _avatar_leaves(self, guid: Guid, seed: int,
                       group: int) -> Tuple[Dict[str, np.ndarray], int]:
        """The room's Player leaves with the avatar seated: its row of
        the single world's Player bank, in the room's scene and the
        private group, at the spot the seed gives, no heartbeat armed."""
        k = self.role.kernel
        d = self.directory
        spec = d.batch.kernel.store.spec("Player")
        world_row = k.store.row_of(guid)[1]
        i32 = d.proto_leaf("classes.Player.i32").copy()
        vec = d.proto_leaf("classes.Player.vec").copy()
        alive = d.proto_leaf("classes.Player.alive").copy()
        row = int(np.flatnonzero(~alive)[0])
        i32[row] = np.asarray(k.state.classes["Player"].i32[world_row])
        i32[row, spec.slot("SceneID").col] = self.scene_id
        i32[row, spec.slot("GroupID").col] = int(group)
        pos = spec.slot("Position").col
        vec[row, pos] = 0.0
        vec[row, pos, :2] = avatar_spot(seed, self.extent)
        alive[row] = True
        return ({"classes.Player.i32": i32[None],
                 "classes.Player.vec": vec[None],
                 "classes.Player.alive": alive[None]}, row)

    def leave(self, key: Hashable, sess=None, back: bool = True,
              destroy: bool = True) -> None:
        """Unseat a session: its room destroyed (unless the caller
        destroys it), its routing back to the single world, and with
        `back` its world view reset so the world is sent anew."""
        seat = self.seats.pop(key)
        self._by_pos[seat.pos] = None
        while self._by_pos and self._by_pos[-1] is None:
            self._by_pos.pop()
        if destroy:
            self.directory.destroy_room(seat.room_id)
        role = self.role
        table = role._session_table
        if key in table.slot_of:
            table.bind_room(key, -1)
        if sess is not None and back:
            role._guid_session[seat.world_guid] = key
            role.reset_view(sess)

    def world_guids(self) -> set:
        """The seated sessions' avatars in the single world."""
        return {seat.world_guid for seat in self.seats.values()}

    def avatar_gone(self, guid: Guid) -> None:
        """A seated session's avatar left the single world (its session
        despawned or lost): the room goes; a session still bound to it
        is left with no avatar, as the world leaves one."""
        key = next((k for k, s in self.seats.items()
                    if s.world_guid == guid), None)
        if key is None:
            return
        self.leave(key, back=False)
        sess = self.role.sessions.get(key)
        if sess is not None and sess.guid == guid:
            sess.guid = None
            self.role.reset_view(sess)

    def release_room(self, room_id: int) -> None:
        """Every session seated in `room_id`, back to the single world
        (the room's destroy is the caller's)."""
        for key in [k for k, s in self.seats.items()
                    if s.room_id == int(room_id)]:
            self.leave(key, self.role.sessions.get(key), destroy=False)

    # ----------------------------------------------------------- frames
    def _layout(self):
        """(served slots, fresh, avatar rows, valid), each [pad]: a
        position's slot, whether its seat is new, the seat's avatar
        row; holes and padding repeat the first seat's slot, invalid.
        Grows the vector by an announced retrace."""
        want = next_pow2(max(1, len(self._by_pos)))
        if want > self.pad:
            self.directory.batch.costbook.generation_bump(
                f"rooms_served:{self.pad}->{want}")
            self.pad = want
            self._prev = None
            self._pad_gauge.set(want)
        slot_of = self.directory.slot_of  # a re-homed room moves slot
        first = next(iter(self.seats.values()))
        slots = np.full(self.pad, slot_of(first.room_id), np.int32)
        fresh = np.ones(self.pad, bool)
        rows = np.zeros(self.pad, np.int32)
        valid = np.zeros(self.pad, bool)
        for seat in self.seats.values():
            slots[seat.pos] = slot_of(seat.room_id)
            fresh[seat.pos] = seat.fresh or self._prev is None
            rows[seat.pos] = seat.row
            valid[seat.pos] = True
        return slots, fresh, rows, valid

    def _program(self):
        """The served rooms' program: gather, diff planes, visibility."""
        if self._jit is not None:
            return self._jit
        import jax
        import jax.numpy as jnp

        from ...kernel.kernel import pack_diff_planes
        from ...ops.interest import quantize, scope_mask

        store = self.directory.batch.kernel.store
        extent = self.extent
        r2 = float(self.role.interest_radius) ** 2
        cols = {c: np.asarray([col for col, _ in self.plan[c]], np.int32)
                for c in self.classes}
        geo = {c: tuple(store.spec(c).slot(n).col
                        for n in ("Position", "SceneID", "GroupID"))
               for c in set(self.classes) | {"Player"}}
        ppos, psc, pgr = geo["Player"]

        def rooms_interest(banks, prev, slots, fresh, obs_row, obs_valid):
            with jax.named_scope("nf.rooms.interest"):
                me = jnp.arange(slots.shape[0])
                pvec = banks["Player"]["vec"][slots]
                pi32 = banks["Player"]["i32"][slots]
                obs = pvec[me, obs_row, ppos, :2]
                osc = pi32[me, obs_row, psc].astype(jnp.float32)
                ogr = pi32[me, obs_row, pgr].astype(jnp.float32)
                out = {}
                for cname in self.classes:
                    pos_c, sc_c, gr_c = geo[cname]
                    alive = banks[cname]["alive"][slots]
                    i32 = banks[cname]["i32"][slots]
                    pos = banks[cname]["vec"][slots][:, :, pos_c, :]
                    q, inside = jax.vmap(
                        lambda p, a: quantize(p, a, extent))(pos, alive)
                    dx = pos[..., 0] - obs[:, None, 0]
                    dy = pos[..., 1] - obs[:, None, 1]
                    seen = inside & (dx * dx + dy * dy <= r2) & scope_mask(
                        i32[..., sc_c].astype(jnp.float32),
                        i32[..., gr_c].astype(jnp.float32),
                        osc[:, None], ogr[:, None]) & obs_valid[:, None]
                    cur = i32[:, :, cols[cname]]
                    changed = ((cur != prev[cname]) | fresh[:, None, None]) \
                        & alive[:, :, None]
                    planes = jax.vmap(pack_diff_planes)(changed)
                    out[cname] = {
                        "seen": seen, "q": q, "cur": cur, "planes": planes,
                        "counts": jnp.sum(jax.lax.population_count(planes),
                                          axis=2, dtype=jnp.int32)}
                return out

        self._jit = self.directory.batch.costbook.wrap(
            "rooms.interest", rooms_interest, stage="interest")
        return self._jit

    def flush(self) -> None:
        """Serve every seated session its room after a fleet tick: one
        dispatch, one read, then each seat's messages."""
        import jax
        import jax.numpy as jnp

        role = self.role
        sc = role.stage_clock
        with span("rooms.serve", rooms=len(self.seats)):
            with sc.stage("interest"):
                slots, fresh, rows, valid = self._layout()
                batch = self.directory.batch
                banks = {c: {"i32": batch.state.classes[c].i32,
                             "vec": batch.state.classes[c].vec,
                             "alive": batch.state.classes[c].alive}
                         for c in self.classes}
                if self._prev is None:
                    self._prev = {
                        c: jnp.zeros(
                            (self.pad, banks[c]["i32"].shape[1],
                             len(self.plan[c])), np.int32)
                        for c in self.classes}
                out = self._program()(banks, self._prev, slots, fresh,
                                      rows, valid)
                self._prev = {c: out[c]["cur"] for c in self.classes}
                with span("rooms.serve.fetch"):
                    got = jax.device_get(out)
            for seat in self.seats.values():
                seat.fresh = False
            self._served_total.inc(len(self.seats))
            with sc.stage("assemble"), span("rooms.serve.views"):
                self._views(got)

    def _views(self, got) -> None:
        """Each seat's position message, then its property messages."""
        from ...kernel.kernel import unpack_diff_plane
        from ...ops.interest import QMAX

        role = self.role
        scale = self.extent / QMAX
        for key, seat in self.seats.items():
            sess = role.sessions.get(key)
            if sess is None:
                continue
            p = seat.pos
            heads, idx, qs, gone_h, gone_i, props = [], [], [], [], [], []
            for code, cname in enumerate(self.classes):
                g = got[cname]
                head = room_ident_head(seat.room_id, code)
                vis = np.flatnonzero(g["seen"][p])
                q = g["q"][p][vis].astype(np.uint16)
                prev = seat.seen.get(cname)
                if prev is None or prev[0].size == 0:
                    known = np.zeros(vis.size, bool)
                    send = ~known
                    gone = np.empty(0, np.int64)
                else:
                    p_rows, p_q = prev
                    at = np.minimum(np.searchsorted(p_rows, vis),
                                    max(p_rows.size - 1, 0))
                    known = (p_rows.size > 0) & (p_rows[at] == vis)
                    send = ~(known & np.all(p_q[at] == q, axis=-1))
                    gone = np.setdiff1d(p_rows, vis)
                seat.seen[cname] = (vis, q)
                heads.append(np.full(int(send.sum()), head, np.int64))
                idx.append(vis[send].astype(np.int64))
                qs.append(q[send])
                gone_h.append(np.full(gone.size, head, np.int64))
                gone_i.append(gone.astype(np.int64))
                # the property lane: changed cells of the rows in view,
                # every column of the rows that came into view, all of
                # the class's columns in one message
                plan = self.plan[cname]
                n = g["cur"].shape[1]
                out = np.zeros((n, len(plan)), bool)
                for j in np.flatnonzero(g["counts"][p]):
                    hit = unpack_diff_plane(g["planes"][p, j])
                    out[hit[hit < n], j] = True
                out[vis[~known]] = True
                in_view = np.zeros(n, bool)
                in_view[vis] = True
                out &= in_view[:, None]
                cols, rows_ = np.nonzero(out.T)
                if cols.size:
                    per_col = np.bincount(cols, minlength=len(plan))
                    used = np.flatnonzero(per_col)
                    props.append(BatchPropertyColumns(
                        class_name=cname.encode(),
                        names=[plan[j][1].encode() for j in used],
                        ptype=int(DataType.INT),
                        counts=per_col[used].astype(np.int32).tobytes(),
                        svrid=np.full(rows_.size, head, np.int64).tobytes(),
                        index=rows_.astype(np.int64).tobytes(),
                        data=np.ascontiguousarray(
                            g["cur"][p][rows_, cols], np.int32).tobytes()))
            count = sum(h.size for h in heads)
            n_gone = sum(h.size for h in gone_h)
            if count or n_gone:
                role._send_to_session(sess, MsgID.ACK_INTEREST_POS,
                                      InterestPosSync(
                    scale=scale, count=count,
                    svrid=np.concatenate(heads).tobytes(),
                    index=np.concatenate(idx).tobytes(),
                    qpos=np.ascontiguousarray(
                        np.concatenate(qs), np.uint16).tobytes(),
                    gone_svrid=np.concatenate(gone_h).tobytes(),
                    gone_index=np.concatenate(gone_i).tobytes()))
            for msg in props:
                role._send_to_session(
                    sess, MsgID.ACK_BATCH_PROPERTY_COLUMNS, msg)
