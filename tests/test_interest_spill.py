"""The interest table's second level (ISSUE 32).

A crowd stands far deeper in an interest cell than `auto_bucket` sizes
for; `ops/stencil.build_cell_table` keeps the rows of the over-full
cells beyond the bucket in a second level, `ops/interest._scan_observers`
reads it for those of an observer's nine cells that are over-full, and
the game role sizes it from what a frame's own build counted
(`GameRole._observe_interest`), with no option anywhere.  Held here:

- the answer against brute force on a clustered world: every row within
  the radius and in scope is a candidate exactly once;
- with nothing over-full, the answer is today's, bit for bit;
- the drops the table counts are the two-level model's;
- both serve engines send the same bytes before and after a resize, and
  the frame after a resize carries the enter set alone;
- the policy sizes the level once and then stays put;
- a uniform world at the served cell's geometry states no level.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from noahgameframe_tpu.game.world import GameWorld, WorldConfig
from noahgameframe_tpu.net.defines import MsgID
from noahgameframe_tpu.net.roles.base import RoleConfig
from noahgameframe_tpu.net.roles.game import GameRole, Session
from noahgameframe_tpu.net.wire import Ident, InterestPosSync, ident_key, unwrap
from noahgameframe_tpu.ops.interest import (
    STAT_NAMES,
    _interest_feats,
    _scan_observers,
    visible_candidates,
)
from noahgameframe_tpu.ops.stencil import (
    _cell_keys,
    _key_segments,
    _slots_from_ranks,
    auto_bucket,
    table_from_slots,
)

RADIUS = 8.0
EXTENT = 64.0
WIDTH = 8
N = 2048


def clustered(seed: int, crowd: int = 700):
    """`crowd` rows on a 12 x 12 patch that straddles four cells, the
    rest uniform; some rows dead, three scenes' and groups' worth of
    scoping; observers in, beside and far from the crowd."""
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0.5, EXTENT - 0.5, (N, 2)).astype(np.float32)
    pos[:crowd] = rng.uniform(18.0, 30.0, (crowd, 2)).astype(np.float32)
    rng.shuffle(pos)
    active = rng.random(N) < 0.9
    scene = rng.choice([1.0, 1.0, 1.0, 2.0], N).astype(np.float32)
    group = rng.choice([0.0, 0.0, 1.0, 2.0], N).astype(np.float32)
    obs = np.array([[24.0, 24.0], [20.5, 29.0], [31.9, 16.1], [12.0, 24.0],
                    [50.0, 50.0], [0.2, 0.2], [63.5, 20.0], [26.0, 21.0]],
                   np.float32)
    obs_scene = np.array([1, 1, 1, 2, 1, 1, 1, 1], np.float32)
    obs_group = np.array([0, 1, 2, 1, 1, 0, 0, 2], np.float32)
    return pos, active, scene, group, obs, obs_scene, obs_group


def brute_force(pos, active, scene, group, obs, obs_scene, obs_group):
    out = []
    for o, sc, gr in zip(obs, obs_scene, obs_group):
        d = pos - o[None, :]
        near = d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1] <= np.float32(
            RADIUS * RADIUS)
        scoped = (scene == sc) & ((group == 0) | (group == gr))
        out.append(set(np.flatnonzero(active & near & scoped).tolist()))
    return out


def two_level_drops(pos, active, bucket, cells, depth):
    """Rows the stated sizes drop: a cell's rows in row order fill
    `bucket` slots and, in the first `cells` over-full cells in cell
    order, `depth` more (numpy, its own arithmetic)."""
    c = np.clip(np.floor(pos / np.float32(RADIUS)).astype(np.int64), 0,
                WIDTH - 1)
    cell = c[:, 1] * WIDTH + c[:, 0]
    dropped, hot = set(), 0
    for cid in range(WIDTH * WIDTH):
        rows = np.flatnonzero(active & (cell == cid))
        if rows.size <= bucket:
            continue
        keep = bucket + (depth if hot < cells else 0)
        hot += 1
        dropped.update(rows[keep:].tolist())
    return dropped


def candidates(world, bucket, spill):
    pos, active, scene, group, obs, obs_scene, obs_group = world
    res = jax.jit(lambda *a: visible_candidates(
        *a, radius=RADIUS, cell_size=RADIUS, width=WIDTH, bucket=bucket,
        spill=spill))(
        jnp.asarray(pos), jnp.asarray(active), jnp.asarray(scene),
        jnp.asarray(group), jnp.asarray(obs), jnp.asarray(obs_scene),
        jnp.asarray(obs_group))
    return (np.asarray(res.rows), np.asarray(res.ok),
            dict(zip(STAT_NAMES, np.asarray(res.stats).tolist())))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_second_level_against_brute_force(seed):
    world = clustered(seed)
    bucket, spill = 40, (8, 256)
    rows, ok, stats = candidates(world, bucket, spill)
    assert rows.shape == (8, 9 * (bucket + spill[1]))
    want = brute_force(*world)
    assert stats["dropped"] == 0 and stats["hot_cells"] >= 4
    assert stats["cell_rows_max"] > 4 * bucket and stats["spill_rows"] > 0
    for s in range(8):
        got = rows[s][ok[s]]
        assert len(got) == len(set(got.tolist()))  # exactly once
        assert set(got.tolist()) == want[s]
    # the crowd's observers see far more than the base level holds
    assert max(len(w) for w in want) > 9 * bucket // 2


@pytest.mark.parametrize("seed", [4, 5])
def test_what_fits_neither_level_is_dropped_and_counted(seed):
    """A level too small for the crowd: the answer is brute force less
    the rows the two-level model drops, and `dropped` counts them."""
    world = clustered(seed)
    pos, active = world[0], world[1]
    bucket, spill = 24, (2, 64)
    rows, ok, stats = candidates(world, bucket, spill)
    gone = two_level_drops(pos, active, bucket, *spill)
    assert gone and stats["dropped"] == len(gone)
    for s, want in enumerate(brute_force(*world)):
        assert set(rows[s][ok[s]].tolist()) == want - gone


def parent_candidates(world, bucket):
    """The one-level answer as the tree before this level built it: the
    slots un-sorted from the ranks, the payload scattered."""
    pos, active, scene, group, obs, obs_scene, obs_group = (
        jnp.asarray(a) for a in world)

    def build(pos, active, scene, group, obs, obs_scene, obs_group):
        n_cells, key = _cell_keys(pos, active, RADIUS, WIDTH)
        order, skey, rank = _key_segments(key)
        slot_of = _slots_from_ranks(N, n_cells, order, skey, rank, bucket)
        table = table_from_slots(_interest_feats(pos, scene, group), active,
                                 slot_of, n_cells, RADIUS, WIDTH, bucket)
        return _scan_observers(table, obs, obs_scene, obs_group, RADIUS,
                               RADIUS)

    res = jax.jit(build)(pos, active, scene, group, obs, obs_scene,
                         obs_group)
    return np.asarray(res.rows), np.asarray(res.ok)


def test_with_nothing_hot_the_answer_is_the_parents_bit_for_bit():
    world = clustered(6, crowd=0)  # uniform: 32 rows a cell on average
    bucket = auto_bucket(N, WIDTH)
    was_rows, was_ok = parent_candidates(world, bucket)
    rows, ok, stats = candidates(world, bucket, (0, 0))
    assert stats["hot_cells"] == 0 and stats["dropped"] == 0
    np.testing.assert_array_equal(rows, was_rows)
    np.testing.assert_array_equal(ok, was_ok)
    # a level that is there and holds nothing adds no candidate
    rows2, ok2, stats2 = candidates(world, bucket, (4, 32))
    k9 = 9 * bucket
    np.testing.assert_array_equal(rows2[:, :k9], was_rows)
    np.testing.assert_array_equal(ok2[:, :k9], was_ok)
    assert not ok2[:, k9:].any() and stats2["spill_rows"] == 0
    # and over a crowd the base level is still the parent's, slot for slot
    crowd = clustered(7)
    was_rows, was_ok = parent_candidates(crowd, 40)
    rows3, ok3, _ = candidates(crowd, 40, (8, 256))
    np.testing.assert_array_equal(rows3[:, :360][ok3[:, :360]],
                                  was_rows[was_ok])
    np.testing.assert_array_equal(ok3[:, :360], was_ok)


# ------------------------------------------------- the role, both engines
GUID_SEED = 9_000_000
CROWD = 120


def build_role(serve_batch: bool):
    world = GameWorld(WorldConfig(
        npc_capacity=256, player_capacity=64, extent=EXTENT,
        combat=False, movement=False, regen=False, middleware=False,
    ))
    world.start()
    world.scene.create_scene(1, width=EXTENT)
    role = GameRole(
        RoleConfig(6, 0, "CrowdGame", "127.0.0.1", 0),
        backend="py", world=world, cross_server_sync=False,
        interest_radius=RADIUS, batch_sync_min=4, serve_batch=serve_batch,
    )
    role.kernel.store.guids.pin(GUID_SEED)
    sent = []
    role.server.send_raw = lambda c, m, b: (sent.append((c, m, b)), True)[1]
    return role, world, sent


class Crowd:
    """`CROWD` NPCs inside one interest cell, 30 more elsewhere, three
    sessions: two in the crowd, one far away.  The same seed replays
    the same frames against either engine."""

    def __init__(self, role, world, seed: int = 5, still: bool = False):
        self.role, self.world, self.k = role, world, role.kernel
        self.rng = np.random.default_rng(seed)
        self.now, self.dt = 1000.0, world.config.dt * 1.0001
        self.still = still
        self.npcs = []
        for i in range(CROWD + 30):
            g = self.k.create_object("NPC", {}, scene=1, group=0)
            lo, hi = (17.0, 23.0) if i < CROWD else (33.0, EXTENT - 1.0)
            self.k.set_property(g, "Position", (
                float(self.rng.uniform(lo, hi)),
                float(self.rng.uniform(lo, hi)), 0.0))
            self.npcs.append(g)
        for i, at in enumerate([(20.0, 20.0), (22.5, 18.0), (55.0, 55.0)]):
            ident = Ident(svrid=99, index=i + 1)
            sess = Session(ident=ident, conn_id=2001 + i, account=f"bot{i}")
            g = self.k.create_object("Player", {"Name": f"Bot{i}"},
                                     scene=1, group=0)
            self.k.set_property(g, "Position", (at[0], at[1], 0.0))
            sess.guid = g
            role.sessions[ident_key(ident)] = sess
            role._guid_session[g] = ident_key(ident)

    def frame(self, f: int):
        k, rng = self.k, self.rng
        if not self.still:
            # a third of the crowd shuffles inside its cell; HP diffs
            # ride the interest-scoped batch lane
            for g in self.npcs[f % 3:CROWD:3]:
                k.set_property(g, "Position", (
                    float(rng.uniform(17.0, 23.0)),
                    float(rng.uniform(17.0, 23.0)), 0.0))
            if f % 4 == 1:
                for g in self.npcs[100:108]:
                    k.set_property(g, "HP", 50 + f)
        self.now += self.dt
        self.role.execute(self.now)


def pos_messages(sent, since: int = 0):
    """[(conn, {guids sent}, {guids gone})] of the position lane."""
    out = []
    for conn, msg_id, body in sent[since:]:
        if msg_id != int(MsgID.ACK_INTEREST_POS):
            continue
        _base, m = unwrap(body, InterestPosSync)
        heads = np.frombuffer(m.svrid, np.int64)
        datas = np.frombuffer(m.index, np.int64)
        gone = np.frombuffer(m.gone_index, np.int64)
        out.append((conn, set(zip(heads.tolist(), datas.tolist())),
                    set(gone.tolist())))
    return out


def test_engines_bit_identical_before_and_after_a_resize():
    streams, roles = [], []
    for serve_batch in (False, True):
        role, world, sent = build_role(serve_batch)
        crowd = Crowd(role, world)
        marks = []
        for f in range(24):
            crowd.frame(f)
            marks.append((len(sent), role.interest_resizes))
        streams.append(sent)
        roles.append((role, marks))
    a, b = streams
    assert len(a) == len(b), (len(a), len(b))
    for i, (pa, pb) in enumerate(zip(a, b)):
        assert pa == pb, f"stream diverges at packet {i}: {pa[:2]} vs {pb[:2]}"
    for role, marks in roles:
        bucket, cells, depth = role.resolved_interest("NPC")
        assert bucket == auto_bucket(256, WIDTH) and cells > 0
        assert depth >= CROWD - bucket
        # sized by the first served frame's breach, once; 22 frames of a
        # shuffling crowd later it has not moved (no oscillation)
        assert [r for _n, r in marks] == [1] * len(marks)
        assert role.interest_last["NPC"]["dropped"] == 0
        assert role.interest_last["NPC"]["spill_rows"] >= CROWD - bucket
        assert role.resolved_interest("Player")[1:] == (0, 0)
    ids = {m for _, m, _ in a}
    assert {int(MsgID.ACK_INTEREST_POS), int(MsgID.ACK_BATCH_PROPERTY)} <= ids
    # packets flowed after the resize, through the widened engines
    assert roles[1][1][-1][0] > roles[1][1][1][0]


@pytest.mark.parametrize("serve_batch", [False, True])
def test_the_frame_after_a_resize_carries_the_enter_set_alone(serve_batch):
    """Nobody is resent the world because a table grew: in a world that
    stands still, the frame after the resize sends each session in the
    crowd exactly the NPCs the first frame's table dropped, names none
    it had sent, and despawns nothing."""
    role, world, sent = build_role(serve_batch)
    crowd = Crowd(role, world, still=True)
    crowd.frame(0)
    first = {conn: got for conn, got, _gone in pos_messages(sent)}
    assert role.interest_resizes == 1
    dropped = role.interest_last["NPC"]["dropped"]
    assert dropped == CROWD - auto_bucket(256, WIDTH)
    mark = len(sent)
    # a Player heartbeat-free, NPC-still world: wake the lane as a
    # session change would
    role._interest_dirty.add("NPC")
    crowd.frame(1)
    after = pos_messages(sent, mark)
    in_crowd = {2001, 2002}
    assert {conn for conn, _g, _x in after} == in_crowd
    host = role.kernel.store._hosts["NPC"]
    npc_keys = set(zip(np.asarray(host.guid_head).tolist(),
                       np.asarray(host.guid_data).tolist()))
    for conn, got, gone in after:
        assert not gone
        assert got and got <= npc_keys
        assert not (got & first[conn])  # nothing it already mirrors
        assert len(got) == dropped  # both stand within 8 of the whole cell
    crowd.frame(2)  # and then the world is quiet again
    role._interest_dirty.add("NPC")
    mark = len(sent)
    crowd.frame(3)
    assert not pos_messages(sent, mark)
    assert role.interest_resizes == 1


def test_breach_policy_follows_combats_rule():
    """A few cells far over the bucket get the level; a class over-full
    everywhere gets its bucket doubled; sizes only grow."""
    role, _world, _sent = build_role(False)
    bucket = auto_bucket(256, WIDTH)  # 12
    deep = {"dropped": 100, "hot_cells": 1, "cell_rows_max": 120,
            "spill_rows": 0, "candidates_max": 0}
    assert "second level" in role._answer_interest_breach("NPC", deep)
    assert role.resolved_interest("NPC") == (bucket, 2, 256)
    # the same crowd again changes nothing: nothing is left to resize
    # but the doubling, and the level already holds what was seen
    shallow = dict(deep, hot_cells=40, cell_rows_max=3 * bucket)
    assert "boosted x2" in role._answer_interest_breach("NPC", shallow)
    assert role.resolved_interest("NPC") == (2 * bucket, 2, 256)
    wider = dict(deep, hot_cells=3, cell_rows_max=200)
    assert "second level" in role._answer_interest_breach("NPC", wider)
    assert role.resolved_interest("NPC") == (2 * bucket, 8, 512)
    # the level never shrinks, and the doubling is bounded
    role._interest_boost["NPC"] = role.interest_max_boost
    assert role._answer_interest_breach("NPC", shallow) is None


def test_counters_and_gauges_are_published():
    role, world, _sent = build_role(False)
    crowd = Crowd(role, world, still=True)
    crowd.frame(0)
    role._interest_dirty.add("NPC")
    crowd.frame(1)
    text = role.telemetry.registry.exposition()
    bucket, cells, depth = role.resolved_interest("NPC")
    for line in (
        f'nf_interest_dropped_total{{cls="NPC"}} {CROWD - bucket}',
        f'nf_interest_spill_cells{{cls="NPC"}} {cells}',
        f'nf_interest_spill_depth{{cls="NPC"}} {depth}',
        f'nf_interest_cell_rows_max{{cls="NPC"}} {CROWD}',
        f'nf_interest_spill_rows{{cls="NPC"}} {CROWD - bucket}',
        f'nf_interest_candidates_max{{cls="NPC"}} {CROWD}',
        'nf_interest_hot_cells{cls="NPC"} 1',
    ):
        assert any(ln.startswith(line) for ln in text.splitlines()), line


def compiled_texts(dispatch):
    out = []
    for cell in getattr(dispatch, "__closure__", None) or ():
        v = cell.cell_contents
        if isinstance(v, dict):
            out += [c.as_text() for c in v.values() if hasattr(c, "as_text")]
    return out


def test_a_uniform_world_at_the_served_cells_geometry_states_no_level():
    """`served-100k-s32`: 131,072 rows, 63 x 63 interest cells.  Its
    driver sizes the depth it compares against by `auto_bucket` itself,
    so the role has to state the same, and the level is not traced."""
    from noahgameframe_tpu.game import build_benchmark_world

    world = build_benchmark_world(100_000, seed=11, player_capacity=64)
    assert world.kernel.store.capacity("NPC") == 131_072
    role = GameRole(
        RoleConfig(6, 0, "UniformGame", "127.0.0.1", 0),
        backend="py", world=world, cross_server_sync=False,
        interest_radius=RADIUS,
    )
    role.server.send_raw = lambda c, m, b: True
    assert role._interest_grid() == (RADIUS, 63)
    ext = float(world.config.extent)
    for i, f in enumerate((0.25, 0.5, 0.75)):  # the middle is the fullest
        ident = Ident(svrid=99, index=i + 1)
        sess = Session(ident=ident, conn_id=2001 + i, account=f"bot{i}")
        g = role.kernel.create_object("Player", {"Name": f"Bot{i}"},
                                      scene=1, group=0)
        role.kernel.set_property(g, "Position", (ext * f, ext * (1 - f), 0.0))
        sess.guid = g
        role.sessions[ident_key(ident)] = sess
        role._guid_session[g] = ident_key(ident)
    role._send_interest_pos("NPC")
    assert role.resolved_interest("NPC") == (52, 0, 0)
    assert role.interest_resizes == 0
    last = role.interest_last["NPC"]
    assert last["dropped"] == 0 and last["spill_rows"] == 0
    assert 0 < last["candidates_max"] <= 9 * 52
    texts = {key[0]: "".join(compiled_texts(fn))
             for key, fn in role._interest_jit.items()}
    assert set(texts) == {"build", "scan"}
    assert "nf.interest.bin" in texts["build"]
    assert "nf.interest.scan" in texts["scan"]
    assert not any("nf.interest.spill" in t for t in texts.values())
