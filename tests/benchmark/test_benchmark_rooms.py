"""The fleet's plain reference against the program, its control and its
planted faults (CPU, 8 rooms of 32 NPCs), and the `rooms` driver broken
underneath a whole rehearsed run."""

import contextlib
import io
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import manifest, reference, reference_rooms  # noqa: E402

with open(os.path.join(ROOT,
                       "benchmarks/traffic/fleet-observed-closed.json")) as f:
    LIMITS = json.load(f)["limits"]

ROOMS, NPCS, EXTENT = 8, 32, 9.237604307034012  # 0.375 NPCs a unit^2


def within_limits(got):
    return all(got[k] <= lim for k, lim in LIMITS.items() if k in got)


@pytest.fixture(scope="module")
def driver():
    return manifest.load_module(
        os.path.join(ROOT, "benchmarks/drivers/rooms.py"), "driver_rooms")


@pytest.fixture(scope="module")
def fleet(driver):
    """Soak a fleet, keep three observed fleet ticks."""
    from noahgameframe_tpu.game import BenchmarkRoomRecipe
    from noahgameframe_tpu.parallel.rooms import RoomDirectory

    d = RoomDirectory(BenchmarkRoomRecipe(NPCS, EXTENT, player_capacity=4),
                      capacity=ROOMS, template_seed=1)
    ids, seeds = driver.room_seeds(2147483659, ROOMS)
    d.create_rooms(seeds, ids)
    snaps = driver.FleetSnapshots(d)
    snaps.warm()
    d.run(170)
    hits = 0
    for _ in range(3):
        hits += int(snaps.around(d.tick)["combat_hits"].sum())
    assert snaps.page_unchanged() and snaps.totals_off == 0
    geo = d.combat_geometry()
    cfg = d.template_world.config
    params = reference.Params(dt=cfg.dt, extent=cfg.extent,
                              aoe_radius=cfg.aoe_radius,
                              respawn_s=cfg.respawn_s)
    slots = np.flatnonzero(d.packer.used)
    layout = snaps.layout
    pre, post, counters, stat_sums = snaps.to_host()
    return dict(layout=layout, pre=pre, post=post, counters=counters,
                stat_sums=stat_sums, params=params, slots=slots,
                population=NPCS, geometry=geo), hits


def compared(kept, **changed):
    return reference_rooms.compare_fleet(**{**kept, **changed})


def test_reference_follows_every_room_tick_for_tick(fleet):
    kept, hits = fleet
    assert hits > 0  # blows land at this density
    assert kept["stat_sums"].shape[:2] == (ROOMS, 64)
    got = compared(kept)
    assert got["rooms_compared"] == ROOMS and got["ticks_compared"] == 3
    assert got["state_wrong_rows"] == 0 and got["diff_cells_off"] == 0
    assert got["ledger_wrong_rows"] == 0 and got["dropped_off"] == 0
    assert got["pos_err_ulp"] <= 2.0
    assert within_limits(got)


def test_lower_precision_control_fails_the_comparison(fleet):
    kept, _ = fleet
    got = compared(kept, control=True)
    assert got["pos_err_ulp"] > 100 * LIMITS["pos_err_ulp"]
    assert not within_limits(got)


def altered_post(kept, edit):
    """The kept ticks with the last one's i32 bank edited in place."""
    last = max(kept["post"])
    post = {t: dict(v) for t, v in kept["post"].items()}
    post[last]["i32"] = post[last]["i32"].copy()
    edit(post[last]["i32"], post[last]["alive"])
    return post


def test_a_cross_room_write_is_a_wrong_row(fleet):
    """One room's NPC hit by another room's attacker: the victim loses
    the HP and takes the attacker's row as its LastAttacker, as a tick
    that let the two rooms see each other would have left it."""
    kept, _ = fleet
    names = kept["layout"].i32_names
    hp, atk, deff, last = (names.index(n) for n in (
        "HP", "ATK_VALUE", "DEF_VALUE", "LastAttacker"))
    a, b = (int(s) for s in kept["slots"][:2])

    def edit(i32, alive):
        victim = int(np.flatnonzero(alive[a] & (i32[a, :, hp] > 20))[0])
        attacker = int(np.flatnonzero(alive[b] & (i32[b, :, hp] > 0))[0])
        i32[a, victim, hp] -= i32[b, attacker, atk] - i32[a, victim, deff]
        i32[a, victim, last] = (kept["layout"].handle_class
                                << kept["layout"].handle_row_bits) | attacker

    got = compared(kept, post=altered_post(kept, edit))
    assert got["state_wrong_rows"] == 1
    assert not within_limits(got)


def test_one_altered_hp_is_a_wrong_row(fleet):
    kept, _ = fleet
    hp = kept["layout"].i32_names.index("HP")
    slot = int(kept["slots"][-1])

    def edit(i32, alive):
        i32[slot, int(np.flatnonzero(alive[slot])[5]), hp] += 1

    got = compared(kept, post=altered_post(kept, edit))
    assert got["state_wrong_rows"] == 1 and not within_limits(got)


def test_a_room_that_is_not_compared_is_missing(fleet):
    kept, _ = fleet
    short = {t: v for t, v in kept["post"].items() if t != max(kept["post"])}
    got = compared(kept, post=short)
    assert got["ticks_compared"] == 2 and got["rooms_compared"] == ROOMS
    assert compared(kept, slots=kept["slots"][:3])["rooms_compared"] == 3


# ---- the timed path broken underneath a whole (rehearsed) run ---------

def one_answer_altered(batch):
    spec = batch.kernel.store.spec("NPC")
    cs = batch.state.classes["NPC"]
    i32 = cs.i32.at[2, 7, spec.slot("HP").col].add(1)
    batch.state = batch.state.replace(classes={
        **batch.state.classes, "NPC": cs.replace(i32=i32)})


def rooms_leak(batch):
    """Room 1's rows written over room 0's: a scatter gone to the wrong
    slot."""
    import jax

    cs = jax.tree.map(lambda leaf: leaf.at[0].set(leaf[1]),
                      batch.state.classes["NPC"])
    batch.state = batch.state.replace(classes={
        **batch.state.classes, "NPC": cs})


@pytest.mark.parametrize("fault", [one_answer_altered, rooms_leak],
                         ids=lambda f: f.__name__)
def test_a_broken_fleet_tick_comes_out_not_correct(monkeypatch, fault):
    from noahgameframe_tpu.parallel.rooms import RoomBatch

    tick = RoomBatch.tick

    def broken(self):
        out = tick(self)
        if self.tick_count > 172:  # past set-up: the window's ticks
            fault(self)
        return out

    monkeypatch.setattr(RoomBatch, "tick", broken)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", "rooms-fleet", "--seed",
                             "4242424242", "--seconds", "0.5",
                             "--trace", "0", "--rehearse"])
    assert rc == 0
    last = json.loads(out.getvalue().splitlines()[-1])
    assert last["correct"] is False, last["compared"]
    assert last["compared"]["state_wrong_rows"][0] > 0
