"""One interest answer per class per flush, shared by every lane.

The position lane fetches each session's visible rows; the property and
record lanes of the same flush filter that answer to their diff's rows
and build nothing.  Where the position lane did not run in a flush, the
first lane that asks runs its build and scan once.  `tests/
test_serve_batch.py`'s scripted churn drives both serve engines; every
HP diff a session receives is held to the rows its client mirrors from
the position stream that frame and to a brute-force distance and scope
computation from the banks, and the two counters to the lanes that ran.
"""

from collections import Counter

import numpy as np
import pytest
from test_serve_batch import RADIUS, Driver, build_role

from noahgameframe_tpu.net.defines import MsgID

FRAMES = 40  # HP diffs at frames 3, 10, 17, 24, 31, 38


def _counts(counter) -> Counter:
    return Counter({(labels["cls"], labels["lane"]): value
                    for _s, labels, value in counter.samples()})


def _npc(counts: Counter) -> Counter:
    return Counter({key: v for key, v in counts.items()
                    if key[0] == "NPC" and v})


def brute_visible(role, rows: np.ndarray):
    """{conn: (rows of `rows` the session's avatar sees, rows within
    float rounding of the radius)}: alive, inside the extent, same scene
    and group (or group 0), within the radius; from the banks."""
    k = role.kernel
    store = k.store
    ext = role.game_world.config.extent
    spec, pspec = store.spec("NPC"), store.spec("Player")
    npc, pl = k.state.classes["NPC"], k.state.classes["Player"]
    pos = np.asarray(npc.vec)[rows, spec.slots["Position"].col, :2]
    i32 = np.asarray(npc.i32)[rows]
    scene = i32[:, spec.slots["SceneID"].col]
    group = i32[:, spec.slots["GroupID"].col]
    live = np.asarray(npc.alive)[rows] & store._hosts["NPC"].alloc_mask[rows]
    inside = np.all((pos >= 0) & (pos <= ext), axis=1)
    out = {}
    for sess in role.sessions.values():
        if sess.guid is None or sess.guid not in store.guid_map:
            continue
        prow = store.row_of(sess.guid)[1]
        at = np.asarray(pl.vec)[prow, pspec.slots["Position"].col, :2]
        p_sc, p_gr = (int(np.asarray(pl.i32)[prow, pspec.slots[n].col])
                      for n in ("SceneID", "GroupID"))
        d2 = ((pos.astype(np.float64) - at) ** 2).sum(axis=1)
        near = np.abs(d2 - RADIUS * RADIUS) < 1e-3
        seen = (live & inside & (scene == p_sc)
                & ((group == 0) | (group == p_gr)) & (d2 <= RADIUS * RADIUS))
        out[sess.conn_id] = (set(rows[seen & ~near].tolist()),
                             set(rows[near].tolist()))
    return out


class Recorder:
    """Taps a role's sends: each client's mirror from the position
    stream, and per HP diff the rows each session received beside the
    diff, the mirrors of that moment and the brute force."""

    def __init__(self, role):
        self.role = role
        self.mirror = {}  # conn -> {(head, data)}
        self.hp = []  # one dict per HP diff
        self.pos_msgs = 0
        self._cur = None
        send, prop = role._send_to_session, role._send_batch_property
        host = role.kernel.store._hosts["NPC"]

        def pairs(heads: bytes, datas: bytes):
            return set(zip(np.frombuffer(heads, np.int64).tolist(),
                           np.frombuffer(datas, np.int64).tolist()))

        def send_to_session(sess, msg_id, msg):
            if msg_id == MsgID.ACK_INTEREST_POS:
                m = self.mirror.setdefault(sess.conn_id, set())
                m -= pairs(msg.gone_svrid, msg.gone_index)
                m |= pairs(msg.svrid, msg.index)
                self.pos_msgs += 1
            elif (msg_id == MsgID.ACK_BATCH_PROPERTY
                  and msg.property_name == b"HP" and self._cur is not None):
                got = np.frombuffer(msg.svrid, np.int64)
                rows = [self._row_of[(h, d)] for h, d in zip(
                    got.tolist(),
                    np.frombuffer(msg.index, np.int64).tolist())]
                self._cur["got"][sess.conn_id] = rows
            return send(sess, msg_id, msg)

        def send_batch_property(cname, pname, rows, player_idx):
            if (cname, pname) != ("NPC", "HP"):
                return prop(cname, pname, rows, player_idx)
            live = rows[host.alloc_mask[rows]]
            self._row_of = {
                (int(host.guid_head[r]), int(host.guid_data[r])): int(r)
                for r in np.flatnonzero(host.alloc_mask)}
            diff = {(int(host.guid_head[r]), int(host.guid_data[r]))
                    for r in live}
            self._cur = {
                "got": {}, "brute": brute_visible(self.role, live),
                "diff": {self._row_of[p] for p in diff},
            }
            prop(cname, pname, rows, player_idx)
            # the mirrors hold this frame's position answer: the stream
            # of the interest stage went out before the encode stage
            self._cur["mirror"] = {
                conn: {self._row_of[p] for p in m & diff}
                for conn, m in self.mirror.items()}
            self.hp.append(self._cur)
            self._cur = None

        role._send_to_session = send_to_session
        role._send_batch_property = send_batch_property


def check_hp(rec: dict):
    for conn, (want, near) in rec["brute"].items():
        got = rec["got"].get(conn, [])
        assert got == sorted(got), "rows of a message are ascending"
        assert set(got) - near == want, (conn, sorted(got), sorted(want))
        assert set(got) == rec["mirror"].get(conn, set()), conn
    assert set(rec["got"]) <= set(rec["brute"])


@pytest.fixture(scope="module", params=[False, True],
                ids=["per_session", "batched"])
def churn(request):
    role, world, _sent = build_role(serve_batch=request.param)
    rec = Recorder(role)
    driver = Driver(role, world)
    frames = []
    for f in range(FRAMES):
        b0, s0 = _counts(role._interest_builds), _counts(role._interest_shared)
        n_hp = len(rec.hp)
        driver.frame(f)
        frames.append({
            "hp": len(rec.hp) - n_hp,
            "builds": _npc(_counts(role._interest_builds) - b0),
            "shared": _npc(_counts(role._interest_shared) - s0),
            "dropped": role.interest_last["NPC"]["dropped"],
        })
    role.shut()
    return {"rec": rec, "frames": frames}


def test_hp_diff_reaches_exactly_the_visible_rows_it_changed(churn):
    rec = churn["rec"]
    assert len(rec.hp) == 6
    assert all(f["dropped"] == 0 for f in churn["frames"])
    reached = 0
    for one in rec.hp:
        check_hp(one)
        reached += sum(len(rows) for rows in one["got"].values())
    assert reached > 0, "no session saw any HP diff: nothing was tested"


def test_one_build_a_frame_and_the_property_lanes_share_it(churn):
    frames = churn["frames"]
    hp_frames = [f for f in frames if f["hp"]]
    assert len(hp_frames) == 6
    for f in hp_frames:
        # the position lane's build alone: the HP lane read its answer
        assert f["builds"] == Counter({("NPC", "pos"): 1}), f["builds"]
        assert f["shared"][("NPC", "HP")] == 1, f["shared"]
    # movers every frame: every NPC build of the run is the position lane
    assert all(set(f["builds"]) <= {("NPC", "pos")} for f in frames)
    assert sum(f["shared"][("NPC", "HP")] for f in frames) == 6


@pytest.fixture(params=[False, True], ids=["per_session", "batched"])
def batched(request):
    return request.param


def test_a_flush_without_a_position_lane_builds_once(batched):
    role, world, sent = build_role(serve_batch=batched)
    rec = Recorder(role)
    driver = Driver(role, world)
    driver.run(12)

    def frame():
        driver.now += driver.dt
        role.execute(driver.now)

    frame()  # quiet: nothing moved, nothing to serve
    k = role.kernel
    live = [g for g in driver.npcs if g in k.store.guid_map][:6]
    for i, g in enumerate(live):
        k.set_property(g, "HP", 500 + i)
        k.set_property(g, "MP", 700 + i)
    b0, s0 = _counts(role._interest_builds), _counts(role._interest_shared)
    pos0, n_hp = rec.pos_msgs, len(rec.hp)
    frame()
    builds = _counts(role._interest_builds) - b0
    shared = _counts(role._interest_shared) - s0
    role.shut()
    # no position, zone or observer change: the first property lane
    # builds, on the current state, the other reads what it fetched
    assert rec.pos_msgs == pos0, "the fallback sends no positions"
    assert sum(builds.values()) == 1, builds
    (first,) = builds
    assert first[0] == "NPC" and first[1] in ("HP", "MP"), builds
    other = ("NPC", "MP" if first[1] == "HP" else "HP")
    assert shared == Counter({other: 1}), shared
    assert len(rec.hp) == n_hp + 1
    check_hp(rec.hp[-1])
    assert sum(len(r) for r in rec.hp[-1]["got"].values()) > 0
