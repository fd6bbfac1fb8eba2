"""The siege world's program side: the breach policy that reads what
breached, walkers with homes, and the uniform worlds left as they
were."""

import types

import numpy as np
import pytest

from noahgameframe_tpu.game import build_benchmark_world
from noahgameframe_tpu.game.combat import CombatModule
from noahgameframe_tpu.game.world import (draw_camp_npcs, draw_npcs,
                                          zipf_camp_sizes)

CAP = 1 << 20
EXTENT_1M = float(np.sqrt(1_000_000 / 0.4))


def module_seeing(boost=1, spill=(0, 0, 0), **seen):
    """A 1M world's combat module (395 wide, staggered arming) whose
    kernel's last tick counted `seen`."""
    m = CombatModule(extent=EXTENT_1M)
    m._attacker_duty = 1.0 / 30.0
    m._bucket_boost = boost
    m._spill = spill
    m.kernel = types.SimpleNamespace(last_counters=dict(seen))
    return m


# what a breaching tick of the siege world counts (the configuration
# file's occupancy counts), at the shipped depth and after a doubling
SIEGE_16 = dict(aoe_hot_cells=8800, aoe_cell_rows_max=250,
                aoe_hot_att_cells=40, aoe_cell_attackers_max=17)
SIEGE_32 = dict(aoe_hot_cells=3600, aoe_cell_rows_max=256,
                aoe_hot_att_cells=12, aoe_cell_attackers_max=18)
# and of a uniform world whose walkers drift to the middle
UNIFORM_16 = dict(aoe_hot_cells=95, aoe_cell_rows_max=21,
                  aoe_hot_att_cells=0, aoe_cell_attackers_max=5)


def test_policy_doubles_a_world_that_is_dense_everywhere():
    m = module_seeing(**UNIFORM_16)
    assert m._answer_breach(CAP) == "bucket boosted x2"
    assert (m.resolved_bucket(CAP), m.resolved_att_bucket(CAP)) == (32, 12)
    assert m.resolved_spill(CAP) == (0, 0, 0)


def test_policy_doubles_when_the_hot_cells_would_outgrow_the_grid():
    """~8,800 over-full cells at 16 deep, the deepest 234 over: 2.1 M
    slots where half the grid's 156,025 x 16 is 1.2 M."""
    m = module_seeing(**SIEGE_16)
    assert m._answer_breach(CAP) == "bucket boosted x2"
    assert m.resolved_spill(CAP) == (0, 0, 0)


def test_policy_sizes_the_second_level_for_a_few_deep_cells():
    m = module_seeing(boost=2, **SIEGE_32)
    answer = m._answer_breach(CAP)
    assert answer.startswith("second level sized")
    cells, depth, att_depth = m.resolved_spill(CAP)
    assert m._bucket_boost == 2  # the grid keeps its depth
    # headroom: half again the cells seen and 1.75 times the depth seen,
    # both to a power of two; attackers from the victims' depth
    assert cells == 8192 and cells >= 1.5 * 3600
    assert depth == 512 and depth >= 1.75 * (256 - 32)
    assert att_depth % 8 == 0 and 12 + att_depth >= 1.5 * 18
    # a later, deeper breach grows it and never shrinks it
    m.kernel.last_counters = dict(SIEGE_32, aoe_cell_rows_max=400,
                                  aoe_hot_cells=100)
    assert m._answer_breach(CAP).startswith("second level sized")
    assert m.resolved_spill(CAP)[0] == 8192
    assert m.resolved_spill(CAP)[1] == 1024  # 1.75 x 368 = 644
    # the same breach again: nothing left to size, so it doubles
    assert m._answer_breach(CAP) == "bucket boosted x4"


def test_policy_with_the_doubling_used_up_takes_the_second_level():
    m = module_seeing(boost=8, **dict(SIEGE_16, aoe_cell_rows_max=2000))
    assert m._answer_breach(CAP).startswith("second level sized")
    m = module_seeing(boost=8, **UNIFORM_16)
    assert m._answer_breach(CAP) is None  # not deep: nothing left


def test_policy_does_not_fire_under_budget():
    """100 rows of a million are the budget: 99 dropped change nothing,
    101 do."""
    m = module_seeing(**UNIFORM_16)
    retraced = []
    m.kernel.invalidate = lambda: retraced.append(1)
    m.kernel.store = types.SimpleNamespace(
        _hosts={"NPC": types.SimpleNamespace(
            alloc_mask=np.ones(1_000_000, bool))},
        capacity=lambda cname: CAP)
    m._on_overflow("NPC", None, {"dropped_victims": [90],
                                 "dropped_attackers": [9]})
    assert (m._bucket_boost, m.resolved_spill(CAP), retraced) == \
        (1, (0, 0, 0), [])
    m._on_overflow("NPC", None, {"dropped_victims": [101],
                                 "dropped_attackers": [0]})
    assert (m._bucket_boost, retraced) == (2, [1])


def test_verlet_world_keeps_the_doubling():
    m = CombatModule(extent=EXTENT_1M, verlet_skin=1.0)
    m._bucket_boost = 2
    m.kernel = types.SimpleNamespace(last_counters=dict(SIEGE_32))
    assert m._answer_breach(CAP) == "bucket boosted x4"
    assert m.resolved_spill(CAP) == (0, 0, 0)


def test_zipf_camp_sizes_are_the_configuration_files():
    sizes = zipf_camp_sizes(1_000_000, 4096, 0.99)
    assert sizes.sum() == 1_000_000
    assert list(sizes[:3]) == [108107, 54429, 36434] and sizes[-1] == 29
    assert sizes[:10].sum() / 1e6 == pytest.approx(0.32, abs=0.01)


def test_camp_placement_keeps_the_leash_and_the_uniform_draw_its_order():
    rng = np.random.default_rng(5)
    pos, target, team, centres, home = draw_camp_npcs(
        rng, 5000, 200.0, 2, 16, 0.99, 8.0)
    assert pos.shape == (5000, 3) and not pos[:, 2].any()
    assert np.abs(target - centres[home]).max() <= 8.0
    assert np.abs(pos[:, :2] - centres[home]).max() <= 8.0
    assert pos.min() >= 0.0 and pos.max() <= 200.0
    assert centres.min() >= 8.0 and centres.max() <= 192.0  # inside
    assert np.all(np.diff(home) >= 0) and set(team) == {0, 1}
    # the uniform case consumes its generator as it always did
    a = draw_npcs(np.random.default_rng(5), 100, 64.0, 2)
    b = np.random.default_rng(5)
    np.testing.assert_array_equal(
        a[0][:, :2], b.uniform(0.0, 64.0, (100, 3)).astype(np.float32)[:, :2])


# state digests of the parent tree (PR 29): ticks 1, 20 and 40 and the
# sum of all forty, of two uniform worlds
PARENT_DIGESTS = {
    (2000, 7): (-841721565, -97861858, -1355484577, 4255562082),
    (512, 11): (-235899651, -523987562, 2025389467, 230574373),
}


@pytest.mark.parametrize("n,seed", sorted(PARENT_DIGESTS))
def test_uniform_worlds_are_digest_equal_to_the_parents(n, seed):
    """Existing seeds build and tick the worlds they built: the new
    counters are reductions beside the tick, no part of its state."""
    w = build_benchmark_world(n, seed=seed)
    w.kernel.enable_digest()
    digests = []
    for _ in range(40):
        w.tick()
        digests.append(int(w.kernel.last_counters["state_digest"]))
    assert (digests[0], digests[19], digests[39],
            sum(digests) & 0xFFFFFFFF) == PARENT_DIGESTS[n, seed]
    assert w.movement.centres is None
    assert w.combat.resolved_spill(w.kernel.store.capacity("NPC")) == (0, 0, 0)
    assert "aoe_hot_cells" in w.kernel.last_counters


def test_siege_world_sizes_its_own_second_level_and_stops_dropping():
    """No variable, no option: the first observed ticks double, then
    size the second level, and the drops stop; walkers stay leashed."""
    w = build_benchmark_world(
        4096, extent=160.0, seed=3,
        spawn_camps={"camps": 16, "zipf": 0.99, "leash": 8.0})
    k, cap = w.kernel, 4096
    assert w.combat.resolved_spill(cap) == (0, 0, 0)
    for _ in range(4):
        w.tick()
    cells, depth, att_depth = w.combat.resolved_spill(cap)
    assert w.combat._bucket_boost == 2 and cells >= 64 and depth >= 96
    for _ in range(30):
        w.tick()
        last = k.last_counters
        assert last["aoi_victim_overflow_drops"] == 0
        assert last["aoi_attacker_overflow_drops"] == 0
        assert last["aoe_spill_rows"] > 500
        assert last["aoe_cell_rows_max"] > 4 * 24
    spec = k.store.spec("NPC")
    vec = np.asarray(k.state.classes["NPC"].vec)
    alive = np.asarray(k.state.classes["NPC"].alive)
    home = w.movement.centres[w.movement.home_rows]
    for col in (spec.slot("Position").col, spec.slot("TargetPos").col):
        assert np.abs(vec[alive, col, :2] - home[alive]).max() <= 8.0
