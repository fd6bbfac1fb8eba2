"""The siege cell's reference, drop model and planted faults (CPU, the
configuration's rehearsal world: 4,096 NPCs on 16 Zipf-sized camps)."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import (compare, manifest,  # noqa: E402
                                reference_siege, work_siege)

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
with open(os.path.join(
        ROOT, "benchmarks/traffic/siege-observed-closed.json")) as f:
    LIMITS = json.load(f)["limits"]
with open(os.path.join(ROOT, "benchmarks/traffic/observed-closed.json")) as f:
    UNIFORM_LIMITS = json.load(f)["limits"]
SEED = 2147483659
TICKS = 6


def within_limits(got):
    return all(got[k] <= lim for k, lim in LIMITS.items() if k in got)


def kept_ticks(second_level=True):
    """The rehearsal world through the driver's own set-up (observed
    ticks until both levels are sized, a soak, settled again), then
    TICKS observed ticks kept on both sides."""
    man = manifest.Manifest(MANIFEST)
    cell = man.cell("siege-zipf", rehearse=True)
    driver = manifest.load_module(cell.driver_path, "driver_siege")
    from benchmarks.harness.npcworld import build_world, until_settled

    world = build_world(cell.config, SEED,
                        spawn_camps=driver.spawn_camps_of(cell.config))
    k = world.kernel
    if not second_level:
        world.combat.SPILL_MIN_OVERDEPTH = 1 << 20  # never deep enough
        world.combat.max_bucket_boost = 2
    until_settled(k.costbook, world.tick, tries=8)
    k.run_device(60)
    until_settled(k.costbook, world.tick, tries=8)
    snaps = compare.Snapshots(k, "NPC", "CommPropertyValue")
    snaps.warm()
    for _ in range(TICKS):
        snaps.around(world.tick)
    geo = driver.siege_geometry(world)
    params = driver.siege_params(cell.config, world, SEED)
    homes = (world.movement.centres, world.movement.home_rows)
    return snaps.to_host(), params, geo, cell.config, homes


@pytest.fixture(scope="module")
def siege():
    return kept_ticks()


def test_the_mix_holds_the_uniform_cells_limits_number_for_number():
    assert LIMITS == UNIFORM_LIMITS and LIMITS["ambiguous_rows"] == 100.0


def test_reference_makes_the_programs_homes_from_the_seed(siege):
    _, params, _, config, (centres, home_rows) = siege
    w = config["world"]
    mine, home = reference_siege.camps_from_seed(
        SEED, w["entities"], params.extent, w["camps"], w["camp_zipf"],
        w["leash"])
    assert mine.min() >= w["leash"]
    assert mine.max() <= params.extent - w["leash"]
    np.testing.assert_array_equal(mine, centres)
    np.testing.assert_array_equal(home, home_rows[:home.size])
    np.testing.assert_array_equal(params.home_centres, centres[home_rows])
    assert reference_siege.camp_sizes(1_000_000, 4096, 0.99)[0] == 108107


def test_reference_follows_the_two_level_program_tick_for_tick(siege):
    host, params, geo, _, _ = siege
    assert geo["spill_cells"] > 0 and geo["spill_bucket"] >= 96
    kept = {}
    got = reference_siege.compare_ticks(host, params, population=4096,
                                        geometry=geo, keep=kept)
    assert got["ticks_compared"] == TICKS
    assert got["state_wrong_rows"] == 0 and got["dropped_off"] == 0
    assert got["ambiguous_rows"] == 0 and got["pos_err_ulp"] <= 2.0
    assert within_limits(got)
    # the crowd is there: cells far over the base depth, rows in the
    # second level, and the work counted for them
    last = host.counters[max(host.counters)]
    assert last["aoe_cell_rows_max"] > 4 * geo["bucket"]
    assert last["aoe_spill_rows"] > 500
    w = work_siege.spill_work(kept["state"], params, kept["pos"],
                              kept["attacking"], 32)
    assert w["victims"] > 1000 and w["bytes"] > 0


def test_leashed_draw_is_the_references_bit_for_bit_and_inside_the_leash(
        siege):
    """Walkers that arrived in a kept tick took the target the
    reference draws about their home, and no other."""
    host, params, _, config, _ = siege
    leash = config["world"]["leash"]
    fresh_rows = 0
    for t, before_l, after_l in host.pairs():
        before = compare.to_state(host.layout, before_l, host.stat_sums)
        after = compare.to_state(host.layout, after_l, host.stat_sums)
        fresh = np.any(before.target.view(np.int32)
                       != after.target.view(np.int32), axis=1)
        want = reference_siege.homed_targets(
            before.rng_key, before.tick, params.home_centres, leash,
            params.extent)
        np.testing.assert_array_equal(after.target[fresh].view(np.int32),
                                      want[fresh].view(np.int32))
        assert np.abs(after.target - params.home_centres)[after.alive] \
            .max() <= leash
        fresh_rows += int(fresh.sum())
    assert fresh_rows > 20


def test_homes_ignored_fail_on_targets(siege):
    """A program that homes its rows elsewhere (here: the reference told
    another seed's camps) draws other targets: wrong rows."""
    host, params, geo, config, _ = siege
    import dataclasses

    other = dataclasses.replace(
        params, home_centres=reference_siege.home_centres(
            SEED + 1, config, params.extent, 4096))
    got = reference_siege.compare_ticks(host, other, population=4096,
                                        geometry=geo)
    assert got["state_wrong_rows"] > 20 and not within_limits(got)
    # and the uniform frame in the siege world's place: the same fault
    blind = compare.compare_ticks(host, params, population=4096)
    assert blind["state_wrong_rows"] > 20


def test_one_spilled_victims_hp_altered_is_a_wrong_row(siege):
    host, params, geo, _, _ = siege
    t = max(host.post)
    leaves = dict(host.post[t])
    # a row the second level held on that tick: in a cell over the base
    # depth, beyond its first `bucket` rows
    state = compare.to_state(host.layout, host.pre[t - 1], host.stat_sums)
    cell = work_siege.cells_of(leaves["vec"][:, host.layout.position_col],
                               geo["cell_size"], geo["width"])
    deepest = np.bincount(cell[state.alive]).argmax()
    row = np.flatnonzero(state.alive & (cell == deepest))[geo["bucket"] + 3]
    i32 = leaves["i32"].copy()
    i32[row, host.layout.i32_names.index("HP")] += 1
    leaves["i32"] = i32
    got = reference_siege.compare_ticks(
        compare.HostSnapshots(host.layout, host.pre, {**host.post, t: leaves},
                              host.counters, host.stat_sums),
        params, population=4096, geometry=geo)
    assert got["state_wrong_rows"] == 1 and not within_limits(got)


def test_second_level_not_stated_fails_the_drop_count(siege):
    """The program's counters (no drops) against a geometry that states
    one level: the implied drops are not the counted ones."""
    host, params, geo, _, _ = siege
    one_level = {k: v for k, v in geo.items() if not k.startswith("spill")}
    got = reference_siege.compare_ticks(host, params, population=4096,
                                        geometry=one_level)
    assert got["dropped_off"] > 500
    assert got["ambiguous_rows"] > LIMITS["ambiguous_rows"]
    assert not within_limits(got)


def test_second_level_switched_off_fails_ambiguous_rows():
    """The world with its second level never sized (the parent's policy:
    doubling, used up): the crowd's rows are dropped every tick, the
    counters agree with the stated depths, and what has to be set aside
    is far beyond what the comparison may excuse."""
    host, params, geo, _, _ = kept_ticks(second_level=False)
    assert geo["spill_cells"] == 0
    got = reference_siege.compare_ticks(host, params, population=4096,
                                        geometry=geo)
    assert got["dropped_off"] == 0 and got["state_wrong_rows"] == 0
    assert got["ambiguous_rows"] > 100 * LIMITS["ambiguous_rows"]
    assert not within_limits(got)


def test_lower_precision_control_fails_pos_err_ulp(siege):
    host, params, geo, _, _ = siege
    got = reference_siege.compare_ticks(host, params, population=4096,
                                        geometry=geo, control=True)
    assert got["pos_err_ulp"] > 100 * LIMITS["pos_err_ulp"]
    assert not within_limits(got)


def test_drop_model_of_two_levels():
    """Rows in row order fill the grid's depth, then the second level's
    in the first over-full cells by cell order; each side its own."""
    geo = {"cell_size": 4.0, "width": 4, "bucket": 2, "att_bucket": 1,
           "spill_cells": 1, "spill_bucket": 3, "spill_att_bucket": 1}
    pos = np.zeros((20, 2), np.float32)
    pos[:8] = (1.0, 1.0)    # cell 0: 8 rows
    pos[8:14] = (5.0, 1.0)  # cell 1: 6 rows
    pos[14:] = (9.0, 9.0)   # cell 10: 6 rows, one not alive
    alive = np.ones(20, bool)
    alive[15] = False
    attacking = np.zeros(20, bool)
    attacking[[0, 3, 5, 9, 10]] = True
    vic, att = reference_siege.dropped_rows(pos, alive, attacking, geo)
    # cell 0 is the first over-full cell: 2 + 3 held; cells 1 and 10
    # come after the second level's last cell: 2 held
    assert list(vic) == [5, 6, 7, 10, 11, 12, 13, 17, 18, 19]
    # attackers: cell 0 holds 1 + 1 of its three; cell 1 holds 1 of two
    assert list(att) == [5, 10]
    one = {k: v for k, v in geo.items() if not k.startswith("spill")}
    for got, want in zip(reference_siege.dropped_rows(pos, alive, attacking,
                                                      one),
                         compare.dropped_rows(pos, alive, attacking, one)):
        np.testing.assert_array_equal(got, want)


def test_occupancy_counts_and_hot_cell_work():
    pos = np.zeros((100, 2), np.float32)
    pos[:40] = (1.0, 1.0)
    pos[40:] = np.random.default_rng(0).uniform(8.0, 64.0, (60, 2))
    got = work_siege.occupancy(pos, np.ones(100, bool), 4.0, 64.0, 32)
    assert got["deepest_cell_rows"] == 40 and got["hot_cells"] == 1
    assert got["rows_beyond_hot_depth"] == 8
    assert got["share_of_rows_in_hot_cells"] == pytest.approx(0.4)
