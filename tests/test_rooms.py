"""Many-worlds room engine (ISSUE 19): batched rooms are bit-identical
to independent single-room worlds.

The correctness spine, exercised once by a module-scoped scenario and
asserted piecewise:

1. K rooms admitted into one vmapped batch and ticked together digest
   bit-identically, per room, to K lockstep single-room control worlds
   (24 combat+movement+regen ticks in tier-1; the 120-tick churn soak
   is ``slow``-marked);
2. churn — destroy, create into the recycled slot, re-home mid-combat —
   triggers ZERO unexplained recompiles after the warm-up mark (one
   compile per CostBook entry serves every slot, because slot indices
   are traced scalars) and zero dropped rows;
3. re-homing is slot-invariant: the blob walk excludes device placement
   so the digest is unchanged by the move itself, and parity with the
   control holds through subsequent ticks;
4. the cross-engine door: a plain single-world snapshot packs into a
   room blob, admits into a batch slot, and both engines advance to the
   same bytes;
5. growing the batch is a sanctioned generation bump — the retrace is
   explained, and parity survives the widening;
6. blobs fail closed: frame CRC corruption and CRC-valid payload
   tampering (caught by the embedded room digest) are both rejected.

Host-only pieces (bin packer policies, slot exhaustion) need no jax.
"""

import jax
import numpy as np
import pytest

from noahgameframe_tpu.game import GameWorld
from noahgameframe_tpu.game.world import WorldConfig
from noahgameframe_tpu.parallel.rooms import (
    _LEAF_HEADER,
    _ROOM_HEADER,
    RoomBinPacker,
    RoomDirectory,
    RoomSlotsFull,
    pack_room_blob,
    room_digest,
    unpack_room_blob,
)
from noahgameframe_tpu.persist.rowblob import (
    RowBlobError,
    frame_blob,
    unframe_blob,
)


def _recipe(seed):
    w = GameWorld(WorldConfig(npc_capacity=48, player_capacity=8,
                              extent=48.0, seed=seed, middleware=False,
                              combat=True, movement=True, regen=True,
                              verlet_skin=2.0))
    w.start()
    w.scene.create_scene(1, width=48.0)
    w.seed_npcs(16, rng=np.random.default_rng(seed + 100))
    return w


@pytest.fixture(scope="module")
def scenario():
    """One end-to-end choreography; tests assert on the recording."""
    rec = {}
    d = RoomDirectory(_recipe, capacity=8, template_seed=0)
    rooms = [d.create_room(seed=s, control=True) for s in (1, 2, 3)]
    rec["slots0"] = {r: d.slot_of(r) for r in rooms}

    # warm-up compiles every CostBook entry once (admit via create,
    # step/run, extract via digest), then the no-recompile gate arms
    d.run(2)
    d.digest(rooms[0])
    mark = d.batch.costbook.mark()

    d.run(22)  # 24 ticks total — mid-combat by construction
    rec["parity_24"] = {r: (d.digest(r), d.control_digest(r))
                       for r in rooms}

    # churn: destroy room 2, create room 4 (must recycle the slot),
    # then re-home room 1 to a fresh slot mid-combat
    freed = d.destroy_room(rooms[1])
    r4 = d.create_room(seed=9, control=True)
    rec["freed_slot"], rec["recycled_slot"] = freed, d.slot_of(r4)
    src, dst = d.rehome_room(rooms[0])
    rec["rehome"] = (src, dst)
    rec["parity_after_rehome"] = (d.digest(rooms[0]),
                                  d.control_digest(rooms[0]))

    d.run(12)
    live = [rooms[0], rooms[2], r4]
    rec["parity_churn"] = {r: (d.digest(r), d.control_digest(r))
                           for r in live}
    rec["unexplained"] = d.batch.costbook.unexplained_since(mark)
    rec["loads"] = {r: int(np.asarray(
        d.batch.extract(d.slot_of(r)).classes["NPC"].alive).sum())
        for r in live}
    rec["status"] = d.status()

    # grow: sanctioned retrace, parity survives the widening
    mark2 = d.batch.costbook.mark()
    d.grow(16)
    d.run(3)
    rec["grow_unexplained"] = d.batch.costbook.unexplained_since(mark2)
    rec["parity_grow"] = {r: (d.digest(r), d.control_digest(r))
                          for r in live}

    # cross-engine door: single world snapshot -> batch slot, advance 7
    # (batch.run skews the other rooms past their controls, so this
    # segment runs last; the template is copied to host before the
    # donated device buffers are consumed by the final run)
    w = _recipe(77)
    w.kernel._ensure_aux()
    w.kernel.run_device(5, reconcile=False)
    blob = pack_room_blob(w.kernel.state, w.kernel.store.class_order)
    rec["blob"] = blob
    rec["template"] = (
        jax.tree.map(lambda a: np.asarray(a).copy(), w.kernel.state),
        w.kernel.store.class_order)
    slot = d.packer.alloc()
    d.batch.admit_blob(slot, blob)
    d.batch.run(7)
    w.kernel.run_device(7, reconcile=False)
    rec["snapshot_parity"] = (
        d.batch.digest(slot),
        room_digest(w.kernel.state, w.kernel.store.class_order))
    d.packer.free(slot)
    return rec


def test_batched_rooms_match_single_room_controls(scenario):
    for r, (live, want) in scenario["parity_24"].items():
        assert live == want, f"room {r} diverged at tick 24"


def test_destroy_recycles_the_slot(scenario):
    assert scenario["recycled_slot"] == scenario["freed_slot"]


def test_rehome_mid_combat_is_slot_invariant(scenario):
    src, dst = scenario["rehome"]
    assert src != dst
    live, want = scenario["parity_after_rehome"]
    assert live == want, "the move itself changed the room's bytes"


def test_parity_survives_churn(scenario):
    for r, (live, want) in scenario["parity_churn"].items():
        assert live == want, f"room {r} diverged after churn"


def test_churn_causes_zero_unexplained_recompiles(scenario):
    assert scenario["unexplained"] == [], scenario["unexplained"]


def test_zero_dropped_rows_across_rehomes(scenario):
    # every surviving room still carries its 16 seeded npcs (combat in
    # these short runs wounds but does not kill) — nothing stranded
    assert all(n == 16 for n in scenario["loads"].values()), \
        scenario["loads"]


def test_occupancy_status_is_consistent(scenario):
    st = scenario["status"]
    assert st["active"] == len(st["occupancy"]) == 3
    assert st["capacity"] - st["active"] == st["slots_free"]
    assert st["destroyed"] == 1 and st["rehomed"] == 1


def test_cross_engine_snapshot_load(scenario):
    live, want = scenario["snapshot_parity"]
    assert live == want


def test_grow_is_sanctioned_and_preserves_parity(scenario):
    assert scenario["grow_unexplained"] == []
    for r, (live, want) in scenario["parity_grow"].items():
        assert live == want, f"room {r} diverged across grow"


def test_blob_roundtrip_and_fail_closed(scenario):
    blob = scenario["blob"]
    state, class_order = scenario["template"]
    back = unpack_room_blob(blob, state, class_order)
    assert room_digest(back, class_order) == room_digest(state,
                                                         class_order)
    # frame CRC catches a flipped byte
    corrupt = bytearray(blob)
    corrupt[len(corrupt) // 2] ^= 0xFF
    with pytest.raises(RowBlobError):
        unpack_room_blob(bytes(corrupt), state, class_order)
    # CRC-valid tampering (re-framed) is caught by the embedded digest:
    # flip the low byte of the first leaf's DATA (the tick scalar) so
    # every structural check still passes
    payload = bytearray(unframe_blob(blob, allow_legacy=False))
    tick = np.asarray(state.tick)
    off = (_ROOM_HEADER.size + _LEAF_HEADER.size
           + len("tick") + len(tick.dtype.str))
    payload[off] ^= 0x01
    with pytest.raises(RowBlobError, match="digest"):
        unpack_room_blob(frame_blob(bytes(payload)), state, class_order)


# -- host-only: the bin packer ----------------------------------------------


def test_batch_state_keeps_the_rooms_sharding_on_every_leaf():
    """Every program that hands back ``batch.state`` pins the batch's
    shardings: after admit, grow and a train each leaf — the zero-width
    banks included, which the compiler would otherwise return
    replicated — is laid out room-major over ROOMS_AXIS, so the next
    pinned-input program (rooms.step) accepts it."""
    from jax.sharding import NamedSharding, PartitionSpec

    from noahgameframe_tpu.parallel.mesh import ROOMS_AXIS, make_mesh
    from noahgameframe_tpu.parallel.rooms import RoomBatch

    mesh = make_mesh(4, axis=ROOMS_AXIS)
    w = _recipe(0)
    w.kernel._ensure_aux()
    batch = RoomBatch(w.kernel, 4, mesh=mesh)

    def assert_room_major(when):
        leaves = jax.tree_util.tree_leaves_with_path(batch.state)
        for path, leaf in leaves:
            want = NamedSharding(mesh, PartitionSpec(ROOMS_AXIS))
            assert leaf.sharding.is_equivalent_to(want, leaf.ndim), (
                when, jax.tree_util.keystr(path), leaf.shape, leaf.sharding)
        return [leaf.shape for _, leaf in leaves]

    for slot in range(4):
        batch.admit(slot, w.kernel.state.replace(
            rng=jax.random.PRNGKey(7 + slot)))
    shapes = assert_room_major("admit")
    assert any(0 in shp for shp in shapes), "recipe has no zero-width bank"
    batch.tick()  # the pinned-input program takes what admit returned

    assert batch.grow(8) == 8
    assert_room_major("grow")
    batch.admit(5, w.kernel.state.replace(rng=jax.random.PRNGKey(99)))
    assert_room_major("admit after grow")

    batch.configure_train(2)
    lanes = batch.train(3)  # one 2-tick train + one ragged single
    assert lanes.shape[:2] == (3, 8)
    assert_room_major("train")
    batch.run(2)
    assert_room_major("run")


def test_packer_least_loaded_spreads_across_blocks():
    p = RoomBinPacker(8, n_blocks=4)
    slots = [p.alloc(load=1.0) for _ in range(4)]
    assert sorted(p.block_of(s) for s in slots) == [0, 1, 2, 3]
    p.set_load(slots[2], 9.0)
    nxt = p.alloc(load=1.0)
    assert p.block_of(nxt) != p.block_of(slots[2])


def test_packer_first_fit_fills_in_order():
    p = RoomBinPacker(4, n_blocks=2, policy="first-fit")
    assert [p.alloc() for _ in range(4)] == [0, 1, 2, 3]


def test_packer_exhaustion_and_recycle():
    p = RoomBinPacker(2)
    a, b = p.alloc(), p.alloc()
    with pytest.raises(RoomSlotsFull) as ei:
        p.alloc()
    assert ei.value.capacity == 2
    p.free(a)
    assert p.alloc() == a
    assert b == 1


def test_packer_grow_keeps_assignments():
    p = RoomBinPacker(2, n_blocks=2)
    a = p.alloc(load=3.0)
    p.grow(8, n_blocks=4)
    assert p.capacity == 8 and p.used[a]
    with pytest.raises(ValueError):
        p.grow(4)


@pytest.mark.slow
def test_long_churn_soak_stays_bit_identical():
    """120 ticks with churn every 24: create/destroy/re-home mid-run,
    digest parity for every surviving room, zero unexplained."""
    d = RoomDirectory(_recipe, capacity=8, template_seed=0)
    rooms = [d.create_room(seed=s, control=True) for s in (1, 2)]
    d.run(2)
    d.digest(rooms[0])
    src, dst = d.rehome_room(rooms[0])  # warm the re-home path too
    mark = d.batch.costbook.mark()
    next_seed = 10
    for phase in range(5):
        d.run(24)
        if phase % 2 == 0:
            rid = d.create_room(seed=next_seed, control=True)
            rooms.append(rid)
            next_seed += 1
        else:
            d.destroy_room(rooms.pop(0))
            d.rehome_room(rooms[0])
        for r in rooms:
            assert d.digest(r) == d.control_digest(r), \
                f"room {r} diverged at phase {phase}"
    assert d.batch.costbook.unexplained_since(mark) == []
