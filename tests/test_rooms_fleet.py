"""The clone-scene fleet (ISSUE 26): rooms admitted in bulk, counted,
and ticked on the game role's frame.

1. a room made by `create_rooms` from the recipe's seeded rows, with no
   world of its own, is digest-equal to `recipe(seed)` built and
   admitted alone; `create_room` is the batch of one;
2. the packer's loads, the `nf_rooms_*` gauges and the new counters
   follow a bulk admit and a fleet tick;
3. the fleet's cell depth is readable from the directory and its
   per-room overflow columns are summed over the occupied slots;
4. a `GameRole` with a directory attached ticks it once a frame;
5. `bench.py`'s CPU rooms ladder is gone, its assertions kept here:
   a recycled slot serves a new room without a compile.
"""

import jax
import numpy as np
import pytest

from noahgameframe_tpu.game import BenchmarkRoomRecipe, GameWorld
from noahgameframe_tpu.game.world import WorldConfig
from noahgameframe_tpu.parallel.rooms import (
    RoomDirectory,
    RoomSlotsFull,
    room_digest,
)
from noahgameframe_tpu.telemetry import MetricsRegistry

SEEDS = (5, 17, 2 ** 31 + 9, 123456789, 4294967295)


@pytest.fixture(scope="module")
def fleet():
    recipe = BenchmarkRoomRecipe(24, 12.0, player_capacity=4)
    reg = MetricsRegistry()
    d = RoomDirectory(recipe, capacity=8, registry=reg)
    return recipe, reg, d, d.create_rooms(SEEDS)


def test_bulk_rooms_equal_recipe_rooms_admitted_one_by_one(fleet):
    recipe, _reg, d, ids = fleet
    one = RoomDirectory(recipe, capacity=8)
    order = d.batch.kernel.store.class_order
    for rid, seed in zip(ids, SEEDS):
        world = recipe(seed)
        world.kernel._ensure_aux()
        alone = one.create_room(seed=seed, control=True)  # a world a room
        want = room_digest(world.kernel.state, order)
        assert d.digest(rid) == want == one.digest(alone), (rid, seed)
    # and they stay equal: ticked as a fleet, a bulk room follows the
    # lockstep single-room kernel of its seed
    d.run(3)
    one.run(3)
    for rid, alone in zip(ids, sorted(one.controls)):
        assert d.digest(rid) == one.control_digest(alone)


def test_bulk_admit_builds_no_world_per_room():
    calls = []

    class Counting(BenchmarkRoomRecipe):
        def __call__(self, seed):
            calls.append(seed)
            return super().__call__(seed)

    d = RoomDirectory(Counting(24, 12.0, player_capacity=4), capacity=8,
                      template_seed=3)
    mark = d.batch.costbook.mark()
    d.create_rooms([1, 2, 3, 4])
    d.create_room(seed=9)
    assert calls == [3]  # the template's, and no other
    book = d.batch.costbook
    compiled = {r["entry"] for r in book.compiles_since(mark)}
    assert compiled == {"rooms.admit"}  # no program a room
    assert len(d.rooms) == 5


def test_gauges_counters_and_loads_follow_a_bulk_admit(fleet):
    _recipe, reg, d, ids = fleet
    assert reg.value("nf_rooms_active") == len(ids)
    assert reg.value("nf_rooms_slots_free") == d.batch.capacity - len(ids)
    assert reg.value("nf_rooms_created_total") == len(ids)
    assert reg.value("nf_rooms_admitted_rows_total") == 24 * len(ids) \
        == d.admitted_rows
    assert reg.value("nf_rooms_admit_bytes_total") == d.batch.admit_bytes > 0
    for rid in ids:
        assert d.packer.load[d.slot_of(rid)] == 24.0
    before = reg.value("nf_rooms_slots_ticked_total")
    d.tick()
    # every slot rides the frame, occupied or not
    assert reg.value("nf_rooms_slots_ticked_total") - before \
        == d.batch.capacity == 8


def test_cell_depth_is_stated_and_drops_are_summed(fleet):
    _recipe, _reg, d, ids = fleet
    combat = d.template_world.combat
    cap = d.batch.kernel.store.capacity("NPC")
    assert d.combat_geometry() == {
        "cell_size": combat.cell_size, "width": combat.width,
        "bucket": combat.resolved_bucket(cap),
        "att_bucket": combat.resolved_att_bucket(cap)}
    was = dict(d.counter_totals)
    cols = d.tick()
    used = d.packer.used
    assert used.sum() == len(ids)
    for name in ("aoi_victim_overflow_drops", "aoi_attacker_overflow_drops",
                 "aoe_attacker_chunks", "aoe_attacker_rows_sent",
                 "combat_hits", "diff_cells"):
        assert cols[name].shape == (d.batch.capacity,)
        assert d.counter_totals[name] - was.get(name, 0) \
            == int(cols[name][used].sum())
    # a room's attacker chunk is sized from its arming (1/30 of its rows,
    # twice over): one chunk a room-tick holds every room's attackers
    rows = combat.resolved_att_rows(cap)
    assert rows < cap
    assert (cols["aoe_attacker_chunks"][used] == 1).all()
    assert (cols["aoe_attacker_rows_sent"][used] == rows).all()
    assert "tick" not in d.counter_totals


def test_slots_full_leaves_the_packer_as_it_was(fleet):
    _recipe, _reg, d, ids = fleet
    free = d.packer.free_count
    with pytest.raises(RoomSlotsFull):
        d.create_rooms(range(100, 100 + free + 1))
    assert d.packer.free_count == free and len(d.rooms) == len(ids)
    with pytest.raises(ValueError):
        d.create_rooms([1, 2], room_ids=[ids[0], 999])


def test_churn_recycles_a_slot_without_a_compile():
    """What bench.py's rooms ladder gated (removed with it)."""
    d = RoomDirectory(BenchmarkRoomRecipe(24, 12.0, player_capacity=4),
                      capacity=4)
    a, b = d.create_rooms([1, 2])
    d.destroy_room(d.create_room(seed=3))  # warm the batch of one
    d.run(2)
    d.tick()
    d.digest(a)
    mark = d.batch.costbook.mark()
    freed = d.destroy_room(a)
    c = d.create_room(seed=7)
    assert d.slot_of(c) == freed
    d.run(2)
    d.tick()
    assert d.batch.costbook.unexplained_since(mark) == []
    world = d._recipe(7)
    world.kernel._ensure_aux()
    world.kernel.run_device(3, reconcile=False)
    assert d.digest(c) == room_digest(world.kernel.state,
                                      world.kernel.store.class_order)
    assert b in d.rooms


@pytest.mark.parametrize("train", [0, 3])
def test_role_ticks_attached_rooms_once_a_world_tick(train):
    from noahgameframe_tpu.net.defines import ServerType
    from noahgameframe_tpu.net.roles.base import RoleConfig
    from noahgameframe_tpu.net.roles.game import GameRole

    w = GameWorld(WorldConfig(npc_capacity=32, player_capacity=8,
                              extent=64.0, seed=11, middleware=False,
                              combat=True, movement=True, regen=True)).start()
    w.scene.create_scene(1, width=64.0)
    w.seed_npcs(16, rng=np.random.default_rng(111))
    role = GameRole(
        RoleConfig(6, int(ServerType.GAME), "Rooms", "127.0.0.1", 0,
                   targets=[]),
        backend="auto", world=w, tick_train=train)
    role.server.send_raw = lambda _conn, _msg, _body: True
    d = RoomDirectory(BenchmarkRoomRecipe(24, 12.0, player_capacity=4),
                      capacity=4, registry=role.telemetry.registry)
    try:
        role.attach_rooms(d)
        role.create_room(seed=1)
        now, per_frame = 1000.0, train or 1
        for frame in range(1, 4):
            now += w.config.dt + 1e-6
            role.execute(now=now)
            assert role.kernel.tick_count == frame * per_frame
            assert d.batch.tick_count == frame * per_frame
            role.execute(now=now)  # no tick due: nothing moves
            assert d.batch.tick_count == frame * per_frame
        room = d.batch.extract(d.slot_of(1))
        assert int(np.asarray(room.tick)) == 3 * per_frame
        assert role.telemetry.registry.value(
            "nf_rooms_slots_ticked_total") == 3 * per_frame * 4
    finally:
        role.shut()
