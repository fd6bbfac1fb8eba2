"""Sessions served clone rooms of the fleet (net/roles/room_serve.py).

A game role with a `RoomDirectory` attached seats a session that swaps
into a CLONE scene in a room of its own, and from then on serves it that
room and nothing else.  Small fleets on the CPU, seeded worlds; each
client mirror is the SDK's own, fed the bytes the role sent, and held to
the plain reference of the benchmark (`reference_rooms_served`).
"""

from __future__ import annotations

import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import compare, reference_rooms_served  # noqa: E402
from noahgameframe_tpu.client import GameClient  # noqa: E402
from noahgameframe_tpu.game import BenchmarkRoomRecipe  # noqa: E402
from noahgameframe_tpu.game.scene_process import SCENE_TYPE_CLONE  # noqa: E402
from noahgameframe_tpu.game.world import GameWorld, WorldConfig  # noqa: E402
from noahgameframe_tpu.net.defines import MsgID, ServerType  # noqa: E402
from noahgameframe_tpu.net.roles.base import RoleConfig  # noqa: E402
from noahgameframe_tpu.net.roles.game import GameRole, Session  # noqa: E402
from noahgameframe_tpu.net.wire import (  # noqa: E402
    BatchPropertyColumns,
    Ident,
    InterestPosSync,
    MsgBase,
    ReqAckSwapScene,
    ident_key,
    ident_room,
    room_ident_head,
    wrap,
)
from noahgameframe_tpu.parallel.rooms import RoomDirectory  # noqa: E402

DUNGEON, FIELD = 2, 5
EXTENT = 9.237604307034012  # clone-rooms-5k's rehearsal room
RADIUS = 8.0
ROOM_SEED = 77
SERVED = {int(MsgID.ACK_INTEREST_POS),
          int(MsgID.ACK_BATCH_PROPERTY_COLUMNS)}


class Fleet:
    """A town role with a fleet of 8 slots attached, 2 rooms admitted
    in bulk, and `sessions` sessions standing in the town; every byte
    the role sends is kept by connection."""

    def __init__(self, seed: int, sessions: int = 3,
                 serve_batch: bool = False):
        # the town: walkers only (the rooms fight; the town need not)
        w = GameWorld(WorldConfig(
            npc_capacity=64, player_capacity=8, extent=64.0, seed=seed,
            middleware=False, combat=False, movement=True,
            regen=True)).start()
        w.scene.create_scene(1, width=64.0)
        w.seed_npcs(24, rng=np.random.default_rng(seed))
        w.kernel.elements.add_element("Scene", str(DUNGEON),
                                      {"SceneType": SCENE_TYPE_CLONE})
        self.world = w
        self.role = GameRole(
            RoleConfig(6, int(ServerType.GAME), "Rooms", "127.0.0.1", 0,
                       targets=[]),
            backend="auto", world=w, interest_radius=RADIUS,
            cross_server_sync=False, serve_batch=serve_batch)
        self.role.kernel.store.guids.pin(1000)
        self.sent = []
        self.role.server.send_raw = \
            lambda conn, msg, body: self.sent.append((conn, msg, body)) \
            or True
        self.d = RoomDirectory(
            BenchmarkRoomRecipe(32, EXTENT, player_capacity=4),
            capacity=8, template_seed=seed,
            registry=self.role.telemetry.registry)
        self.role.attach_rooms(self.d, seed=ROOM_SEED)
        self.d.create_rooms([seed * 10 + 1, seed * 10 + 2])
        self.now = 1000.0
        self.sessions = []
        rng = np.random.default_rng(seed + 1)
        for i in range(sessions):
            ident = Ident(svrid=99, index=i + 1)
            sess = Session(ident=ident, conn_id=2000 + i,
                           account=f"bot{i}")
            g = self.role.kernel.create_object(
                "Player", {"Name": f"Bot{i}"}, scene=1, group=0)
            self.role.kernel.set_property(
                g, "Position", (*rng.uniform(20.0, 44.0, 2), 0.0))
            sess.guid = g
            self.role.sessions[ident_key(ident)] = sess
            self.role._guid_session[g] = ident_key(ident)
            self.sessions.append(sess)

    def frame(self, n: int = 1) -> None:
        for _ in range(n):
            self.now += self.world.config.dt * 1.001
            self.role.execute(now=self.now)

    def swap(self, sess, scene: int) -> None:
        self.role._on_swap_scene(
            sess.conn_id, int(MsgID.REQ_SWAP_SCENE),
            wrap(ReqAckSwapScene(scene_id=scene), player_id=sess.ident))

    def take(self, conn=None) -> list:
        """(conn, msg id, MsgBase) sent since the last take."""
        got = [(c, m, MsgBase.decode(b)) for c, m, b in self.sent
               if conn is None or c == conn]
        self.sent = [x for x in self.sent
                     if conn is not None and x[0] != conn]
        return got

    def room(self, sess) -> int:
        """The room a session is seated in, -1: the single world."""
        seat = self.role._room_serve.seats.get(ident_key(sess.ident))
        return -1 if seat is None else seat.room_id

    def shut(self) -> None:
        self.role.shut()


def client_of(sess) -> GameClient:
    """An SDK mirror for a session that has swapped into the dungeon."""
    c = GameClient(sess.account)
    c.player_guid = Ident(svrid=sess.guid.head, index=sess.guid.data)
    return c


def feed(client: GameClient, msgs) -> None:
    for _conn, msg_id, base in msgs:
        handler = client._handlers.get(msg_id)
        if handler is not None:
            handler(base)


def held(fleet: Fleet, clients) -> dict:
    """`reference_rooms_served.mirror_wrong` over the clients' mirrors
    against the fleet's banks right now."""
    import jax

    lay = compare.layout_of(fleet.d.batch.kernel, "NPC",
                            "CommPropertyValue")
    cs = fleet.d.batch.state.classes["NPC"]
    post = {0: {k: np.asarray(v) for k, v in jax.device_get(
        {"i32": cs.i32, "vec": cs.vec, "alive": cs.alive}).items()}}
    seats = {s.world_guid: s for s in fleet.role._room_serve.seats.values()}
    rows = [seats[sess.guid] for sess in fleet.sessions[:len(clients)]]
    mirrors = {(i, 1): {k: (o.position, o.properties.get("HP"))
                        for k, o in c.objects.items()
                        if "Position" in o.properties}
               for i, c in enumerate(clients)}
    spots = reference_rooms_served.avatar_spots(
        ROOM_SEED, [c.account for c in clients], EXTENT)
    own = [(c.player_guid.svrid, c.player_guid.index) for c in clients]
    return reference_rooms_served.mirror_wrong(
        lay, post, mirrors, {1: 0},
        [(s.room_id, fleet.d.slot_of(s.room_id), s.row) for s in rows],
        spots, EXTENT, RADIUS,
        own)


@pytest.mark.parametrize("seed", [3, 4])
def test_seated_clients_mirror_their_rooms_as_the_reference_does(seed):
    f = Fleet(seed)
    try:
        f.frame(3)
        clients = [client_of(s) for s in f.sessions]
        for s in f.sessions:
            f.swap(s, DUNGEON)
        assert len(f.d.rooms) == 5  # 2 in bulk, 3 made by the swaps
        for frame in range(6):
            f.frame()
            msgs = f.take()
            for sess, c in zip(f.sessions, clients):
                mine = [m for m in msgs if m[0] == sess.conn_id]
                feed(c, mine)
                # at most one property message a class a seat a frame
                names = [BatchPropertyColumns.decode(b.msg_data).class_name
                         for _c, m, b in mine
                         if m == int(MsgID.ACK_BATCH_PROPERTY_COLUMNS)]
                assert len(names) == len(set(names))
            got = held(f, clients)
            assert got["mirror_wrong"] == 0, (frame, got)
            assert got["mirror_hp_wrong"] == 0, (frame, got)
            assert got["mirror_checked"] >= 3 * 2
            assert got["rooms_unseen"] == 0
        # a row in view carries every public column, at the bank's value
        import jax

        i32 = np.asarray(jax.device_get(
            f.d.batch.state.classes["NPC"].i32))
        plan = f.role._room_serve.plan["NPC"]
        checked = 0
        for sess, c in zip(f.sessions, clients):
            seat = f.role._room_serve.seats[ident_key(sess.ident)]
            bank = i32[f.d.slot_of(seat.room_id)]
            head = room_ident_head(seat.room_id, 0)
            for (h, row), o in c.objects.items():
                if h != head or "Position" not in o.properties:
                    continue
                for col, name in plan:
                    assert o.properties[name] == int(bank[row, col]), name
                checked += 1
        assert checked >= 3
        assert f.role.telemetry.registry.value(
            "nf_rooms_clone_enters_total") == 3
        assert f.role.telemetry.registry.value(
            "nf_rooms_served_slots_total") == 3 * 6
        assert f.role.telemetry.registry.value("nf_rooms_served_pad") == 4
    finally:
        f.shut()


def test_a_rehomed_room_is_served_from_its_new_slot():
    f = Fleet(11, sessions=2)
    try:
        f.frame(2)
        clients = [client_of(s) for s in f.sessions]
        for s in f.sessions:
            f.swap(s, DUNGEON)
        f.frame(2)
        room = f.room(f.sessions[0])
        src, dst = f.role.rehome_room(room)
        assert src != dst and f.d.slot_of(room) == dst
        f.frame(3)
        msgs = f.take()
        for sess, c in zip(f.sessions, clients):
            feed(c, [m for m in msgs if m[0] == sess.conn_id])
        got = held(f, clients)
        assert got["mirror_wrong"] == 0 and got["mirror_hp_wrong"] == 0
    finally:
        f.shut()


def test_an_hp_off_and_a_room_swapped_are_caught():
    """The comparison the test above passes fails a planted fault."""
    f = Fleet(5, sessions=2)
    try:
        f.frame(2)
        clients = [client_of(s) for s in f.sessions]
        for s in f.sessions:
            f.swap(s, DUNGEON)
        f.frame(2)
        msgs = f.take()
        for sess, c in zip(f.sessions, clients):
            feed(c, [m for m in msgs if m[0] == sess.conn_id])
        assert held(f, clients)["mirror_wrong"] == 0
        some = next(k for k, o in clients[0].objects.items()
                    if ident_room(Ident(svrid=k[0], index=k[1])) >= 0
                    and "HP" in o.properties)
        clients[0].objects[some].properties["HP"] += 1
        assert held(f, clients)["mirror_hp_wrong"] == 1
        clients[0].objects[some].properties["HP"] -= 1
        clients[0].objects, clients[1].objects = (clients[1].objects,
                                                  clients[0].objects)
        got = held(f, clients)
        assert got["mirror_foreign"] > 0 and got["mirror_wrong"] > 0
    finally:
        f.shut()


def test_no_row_of_another_room_or_the_world_reaches_a_seated_session():
    f = Fleet(6)
    try:
        f.frame(3)
        assert any(m == int(MsgID.ACK_INTEREST_POS)
                   for _c, m, _b in f.take(f.sessions[0].conn_id))
        for s in f.sessions[:2]:
            f.swap(s, DUNGEON)
        rooms = {s.conn_id: f.room(s) for s in f.sessions[:2]}
        assert len(set(rooms.values())) == 2 and -1 not in rooms.values()
        f.take()
        for _ in range(8):
            # the town churns: a town NPC dies, the third session moves
            f.role.kernel.set_property(
                f.sessions[2].guid, "Position",
                (30.0 + f.now % 3, 30.0, 0.0))
            f.frame()
        msgs = f.take()
        for conn, room in rooms.items():
            mine = [(m, b) for c, m, b in msgs if c == conn]
            assert {m for m, _ in mine} <= SERVED | {int(MsgID.FRAME_TRACE)}
            mine = [(m, b) for m, b in mine if m in SERVED]
            assert mine
            for m, base in mine:
                cls = InterestPosSync if m == int(MsgID.ACK_INTEREST_POS) \
                    else BatchPropertyColumns
                msg = cls.decode(base.msg_data)
                heads = np.frombuffer(msg.svrid, np.int64).tolist()
                if m == int(MsgID.ACK_INTEREST_POS):
                    heads += np.frombuffer(msg.gone_svrid,
                                           np.int64).tolist()
                assert {ident_room(Ident(svrid=h, index=0))
                        for h in heads} <= {room}
        # the town session is still served the town, and no room
        town = [b for c, m, b in msgs if c == f.sessions[2].conn_id
                and m == int(MsgID.ACK_INTEREST_POS)]
        assert town
        for base in town:
            msg = InterestPosSync.decode(base.msg_data)
            assert all(h < (1 << 48) for h in
                       np.frombuffer(msg.svrid, np.int64).tolist())
    finally:
        f.shut()


def test_a_swap_into_a_normal_scene_leaves_the_session_on_the_world():
    f = Fleet(7, sessions=2)
    try:
        f.frame(2)
        a, b = f.sessions
        f.swap(a, FIELD)
        assert f.room(a) == -1 and len(f.d.rooms) == 2
        assert not f.role._room_serve.seats
        f.swap(b, DUNGEON)
        room = f.room(b)
        assert room in f.d.rooms
        f.frame(2)
        # back out of the dungeon: the room goes, the world comes back
        f.swap(b, FIELD)
        assert f.room(b) == -1 and room not in f.d.rooms
        assert f.role._guid_session[b.guid] == ident_key(b.ident)
        f.take()
        f.frame(2)
        for s in (a, b):
            pos = [base for c, m, base in f.take(s.conn_id)
                   if m == int(MsgID.ACK_INTEREST_POS)]
            assert pos
            for base in pos:
                msg = InterestPosSync.decode(base.msg_data)
                assert all(h < (1 << 48) for h in
                           np.frombuffer(msg.svrid, np.int64).tolist())
    finally:
        f.shut()


@pytest.mark.parametrize("how", ["destroy", "leave"])
def test_destroying_a_room_or_leaving_releases_the_seat(how):
    f = Fleet(8, sessions=2)
    try:
        f.frame(2)
        a, b = f.sessions
        for s in f.sessions:
            f.swap(s, DUNGEON)
        f.frame(2)
        room, key = f.room(a), ident_key(a.ident)
        table = f.role._session_table
        assert table.room_of(key) == room
        if how == "destroy":
            f.role.destroy_room(room)
            assert f.room(a) == -1 and a.guid is not None
            assert f.role._guid_session[a.guid] == key
        else:
            f.role._on_leave_game(a.conn_id, int(MsgID.REQ_LEAVE_GAME),
                                  wrap(ReqAckSwapScene(),
                                       player_id=a.ident))
            assert key not in f.role.sessions
        assert room not in f.d.rooms
        assert key not in f.role._room_serve.seats
        assert table.room_of(key) == -1
        assert ident_key(b.ident) in f.role._room_serve.seats
        f.take()
        f.frame(2)  # the other seat is still served
        assert any(m in SERVED for _c, m, _b in f.take(b.conn_id))
    finally:
        f.shut()


def test_the_rooms_step_is_the_same_program_with_rooms_served():
    """Serving rooms adds a program of its own (`jit_rooms_interest`)
    and leaves the fleet's step as it was: the step a role compiles
    while sessions are seated is the step of a fleet nobody serves,
    instruction for instruction, metadata set aside."""
    from benchmarks.harness.npcworld import compiled_texts

    f = Fleet(9, sessions=2)
    try:
        f.frame(1)
        for s in f.sessions:
            f.swap(s, DUNGEON)
        f.frame(2)
        served = compiled_texts(f.d.batch._jit_step)
        extra = compiled_texts(f.role._room_serve._program())
    finally:
        f.shut()
    alone = RoomDirectory(BenchmarkRoomRecipe(32, EXTENT, player_capacity=4),
                          capacity=8, template_seed=9)
    alone.create_rooms([91, 92])
    alone.tick()
    bare = compiled_texts(alone.batch._jit_step)

    def lines(texts):
        out = []
        for text in texts:
            for ln in text.splitlines():
                ln = re.sub(r", metadata=\{[^}]*\}", "", ln).strip()
                if ln.startswith("%") or ln.startswith("ROOT"):
                    out.append(ln)
        return out

    assert len(served) == len(bare) == 1
    assert lines(served) == lines(bare)
    assert "rooms_interest" in extra[0].split("\n", 1)[0]


def test_both_serve_engines_send_a_seated_session_the_same_bytes():
    streams = []
    for batch in (False, True):
        f = Fleet(10, serve_batch=batch)
        try:
            f.frame(2)
            for s in f.sessions[:2]:
                f.swap(s, DUNGEON)
            f.take()
            f.frame(5)
            seated = {s.conn_id for s in f.sessions[:2]}
            streams.append([(c, m, b) for c, m, b in f.sent
                            if c in seated and m in SERVED])
        finally:
            f.shut()
    assert streams[0] and streams[0] == streams[1]


def test_a_role_with_no_rooms_takes_the_world_path(monkeypatch):
    """The path a served world's set-up runs, pinned: in a LocalCluster
    whose game role has no RoomDirectory, an enter, a swap into a normal
    scene and the frames after them make no room call and compile no
    program of the rooms."""
    from noahgameframe_tpu.net.roles import LocalCluster, room_serve
    from noahgameframe_tpu.parallel import rooms

    calls = []

    def refuse(name):
        def call(*_a, **_k):
            calls.append(name)
            raise AssertionError(f"{name} on a role with no rooms")
        return call

    for cls, names in ((room_serve.RoomServe,
                        ("__init__", "swap", "enter", "leave", "flush",
                         "release_room")),
                       (rooms.RoomDirectory,
                        ("__init__", "tick", "create_rooms", "destroy_room")),
                       (rooms.RoomBatch, ("tick", "admit_rooms"))):
        for name in names:
            monkeypatch.setattr(cls, name, refuse(f"{cls.__name__}.{name}"))
    gw = GameWorld(WorldConfig(npc_capacity=64, player_capacity=8,
                               extent=64.0, seed=12, middleware=False,
                               combat=True, movement=True, regen=True)).start()
    gw.scene.create_scene(1, width=64.0)
    gw.seed_npcs(24, rng=np.random.default_rng(12))
    cluster = LocalCluster(game_world=gw,
                           game_kwargs={"interest_radius": RADIUS})
    cluster.start(timeout=60.0)
    try:
        c = GameClient("plain")

        def until(cond):
            assert cluster.pump_until(cond, extra=c.execute, timeout=60.0)

        c.connect("127.0.0.1", cluster.login.config.port)
        until(lambda: c.connected)
        for act, done in (
                (c.login, lambda: c.logged_in),
                (c.request_world_list, lambda: c.worlds),
                (lambda: c.connect_world(c.worlds[0].server_id),
                 lambda: c.world_grant is not None),
                (c.connect_proxy, lambda: c.connected),
                (c.verify_key, lambda: c.key_verified),
                (lambda: c.select_server(cluster.game.config.server_id),
                 lambda: c.server_selected),
                (lambda: c.create_role("Plain"), lambda: c.roles),
                (lambda: c.enter_game("Plain"), lambda: c.entered)):
            act()
            until(done)
        game = cluster.game
        c.move_to(32.0, 32.0)
        c.swap_scene(1)  # the town again: a normal scene
        until(lambda: c.swaps)
        t0 = game.kernel.tick_count
        until(lambda: game.kernel.tick_count >= t0 + 5)
        until(lambda: any(ident_room(o.ident) == -1 and k != ident_key(
            c.player_guid) for k, o in c.objects.items()))
        assert not calls
        assert game.rooms is None and game._room_serve is None
        compiled = {r.get("entry") for r in game.kernel.costbook.compile_log}
        assert compiled and not any("rooms" in str(n) for n in compiled)
    finally:
        cluster.shut()


def test_the_room_spans_and_the_device_module_are_in_the_trace(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    f = Fleet(13, sessions=2)
    try:
        f.frame(2)
        f.swap(f.sessions[0], DUNGEON)
        f.frame(2)  # the served rooms' program compiled, off the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            f.swap(f.sessions[1], DUNGEON)
            f.frame(3)
        finally:
            jax.profiler.stop_trace()
    finally:
        f.shut()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    host, modules = [], set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("nf."):
                    host.append((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name))
                elif "rooms_interest" in e.name:
                    modules.add(e.name)
    names = [n for _s, _e, n in host]
    assert names.count("nf.rooms.enter") == 1
    assert names.count("nf.rooms.serve") == 3
    for child in ("nf.rooms.serve.fetch", "nf.rooms.serve.views"):
        assert names.count(child) == 3
        for s, e, n in host:
            if n == child:
                assert any(ps <= s and e <= pe for ps, pe, pn in host
                           if pn == "nf.rooms.serve")
    assert modules
