"""Pallas combat-fold kernel vs the XLA stencil fold: bit-identical
results (interpret mode on CPU), including tie-breaks and edge cells."""

import numpy as np
import pytest

from noahgameframe_tpu.game import GameWorld, WorldConfig
from noahgameframe_tpu.game.defines import PropertyGroup
from noahgameframe_tpu.ops import stencil_pallas as sp

# (platform, width, victim depth, attacker depth) -> engine
RULE_CASES = [
    # the three cells' grids at the depths they soak and run at
    ("tpu", 395, 16, 6, 1),    # tick-1m, the soak
    ("tpu", 395, 32, 12, 1),   # tick-1m, the window (boost 2)
    ("tpu", 125, 20, 6, 1),    # served-100k-s32, the soak
    ("tpu", 125, 40, 12, 1),   # served-100k-s32, the window
    ("tpu", 4, 20, 6, 0),      # a room of rooms-fleet
    # off the chip the kernel is interpreted: a test device
    ("cpu", 395, 32, 12, 0),
    ("cpu", 125, 20, 6, 0),
    ("cpu", 4, 20, 6, 0),
    ("gpu", 395, 32, 12, 0),
    # the lane-fill boundary, on both sides, in the first lane tile
    # and past it
    ("tpu", 63, 20, 6, 0),
    ("tpu", 64, 20, 6, 1),
    ("tpu", 128, 20, 6, 1),
    ("tpu", 129, 20, 6, 1),
    # VMEM: the depths a further boost reaches
    ("tpu", 395, 64, 24, 0),
    ("tpu", 395, 128, 48, 0),
    ("tpu", 125, 80, 24, 1),
    ("tpu", 125, 160, 48, 0),
    ("tpu", 640, 16, 6, 1),
    ("tpu", 640, 32, 12, 0),
]


def build(n, seed, use_pallas, attack_period_s=1.0 / 30.0):
    rng = np.random.RandomState(seed)
    extent = 40.0
    w = GameWorld(
        WorldConfig(
            npc_capacity=256, extent=extent, aoe_radius=5.0,
            attack_period_s=attack_period_s, movement=True, regen=False,
            middleware=False, seed=7,
        )
    )
    w.combat.use_pallas = use_pallas
    w.start()
    w.scene.create_scene(1, width=extent)
    k = w.kernel
    pos = rng.uniform(0, extent, (n, 2)).astype(np.float32)
    camps = rng.randint(0, 2, n)
    atks = rng.randint(0, 30, n)
    for i in range(n):
        g = k.create_object(
            "NPC",
            {"Position": (float(pos[i, 0]), float(pos[i, 1]), 0.0),
             "Camp": int(camps[i]), "HP": 500},
            scene=1,
        )
        w.properties.set_group_value(g, "ATK_VALUE", PropertyGroup.EFFECTVALUE, int(atks[i]))
        w.properties.set_group_value(g, "DEF_VALUE", PropertyGroup.EFFECTVALUE, 2)
        w.properties.set_group_value(g, "MAXHP", PropertyGroup.EFFECTVALUE, 500)
        w.properties.set_group_value(g, "MOVE_SPEED", PropertyGroup.EFFECTVALUE, 30000)
    w.combat.arm_all()
    return w


@pytest.mark.parametrize("seed", [3, 11])
def test_pallas_fold_matches_xla_fold(seed):
    a = build(120, seed, use_pallas=False)
    b = build(120, seed, use_pallas=True)
    for _ in range(6):
        a.tick()
        b.tick()
    ia = np.asarray(a.kernel.state.classes["NPC"].i32)
    ib = np.asarray(b.kernel.state.classes["NPC"].i32)
    np.testing.assert_array_equal(ia, ib)  # HP AND LastAttacker identical
    va = np.asarray(a.kernel.state.classes["NPC"].vec)
    vb = np.asarray(b.kernel.state.classes["NPC"].vec)
    np.testing.assert_array_equal(va, vb)


def test_pallas_fold_matches_xla_fold_asymmetric_buckets():
    """Staggered arming makes the attacker bucket SMALLER than the victim
    bucket (Ka < Kv) — the [Kv, Ka] pairwise broadcasts and tie-break
    reductions must stay bit-identical in that regime, not just at
    Ka == Kv."""
    a = build(150, 23, use_pallas=False, attack_period_s=0.2)
    b = build(150, 23, use_pallas=True, attack_period_s=0.2)
    cap = a.kernel.state.classes["NPC"].alive.shape[0]
    ka = a.combat.resolved_att_bucket(cap)
    kv = a.combat.resolved_bucket(cap)
    assert ka < kv, (ka, kv)
    for _ in range(8):  # > one full 6-tick period: every phase fires
        a.tick()
        b.tick()
    np.testing.assert_array_equal(
        np.asarray(a.kernel.state.classes["NPC"].i32),
        np.asarray(b.kernel.state.classes["NPC"].i32),
    )


# ------------------------------------- both folds against a brute-force fold


def _combat_arrays(n, seed, width=6, cell_size=5.0, clump=None):
    """Random combat-shaped population; clump=(x0, x1) squeezes every
    position into that interval on both axes (siege shapes)."""
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    extent = width * cell_size
    lo, hi = clump if clump is not None else (0.0, extent)
    pos = rng.uniform(lo, hi, (n, 2)).astype(np.float32)
    active = rng.rand(n) < 0.9
    attacking = (rng.rand(n) < 0.5) & active
    atk = rng.randint(0, 30, n).astype(np.float32)
    camp = rng.randint(1, 3, n).astype(np.float32)
    scene = rng.randint(1, 3, n).astype(np.float32)
    group = rng.randint(0, 2, n).astype(np.float32)
    eff = np.where(attacking, atk, 0.0).astype(np.float32)
    rows = np.arange(n, dtype=np.float32)
    vic_feats = jnp.asarray(
        np.stack([pos[:, 0], pos[:, 1], camp, scene, group], -1)
    )
    att_feats = jnp.asarray(
        np.stack([pos[:, 0], pos[:, 1], eff, camp, scene, group, rows], -1)
    )
    return (
        jnp.asarray(pos), jnp.asarray(active), jnp.asarray(attacking),
        vic_feats, att_feats,
    )


def _brute_fold(att_feats, vic_placed, att_placed, radius):
    """Per-row (incoming, best attacker row or -1): every placed victim
    against every placed attacker, pair by pair, in numpy.  The fold's
    rules (game/combat.combat_fold_closure): within the radius, a real
    attack, another camp, the same scene and group; the strongest
    attacker wins, the smallest row among equals."""
    a = np.asarray(att_feats)
    x, y, eff, camp, scene, group = (a[:, c] for c in range(6))
    n = len(x)
    inc = np.zeros(n, np.int64)
    best = np.full(n, -1, np.int64)
    r2 = np.float32(radius) * np.float32(radius)
    for i in np.flatnonzero(vic_placed):
        dx, dy = x[i] - x, y[i] - y
        ok = (
            att_placed & (dx * dx + dy * dy <= r2) & (eff != 0)
            & (camp != camp[i]) & (scene == scene[i]) & (group == group[i])
        )
        if ok.any():
            inc[i] = int(eff[ok].sum())
            best[i] = np.flatnonzero(ok & (eff == eff[ok].max()))[0]
    return inc, best


@pytest.mark.parametrize("engine", [0, 1])
@pytest.mark.parametrize("name,n,seed,bucket,sub_bucket,clump", [
    ("seed3", 300, 3, 16, 12, None),
    ("seed11", 300, 11, 16, 12, None),
    # the whole population inside ONE cell, far over its depth (ROADMAP
    # item 5b's siege shape)
    ("siege_one_cell", 200, 13, 8, 8, (0.5, 4.5)),
    # moderate overflow (small buckets, random spread): which rows drop
    # is part of the contract
    ("overfull_cells", 400, 17, 4, 4, None),
])
def test_fold_matches_brute_force(engine, name, n, seed, bucket, sub_bucket,
                                  clump):
    """The fold over `build_cell_table_pair`'s tables (engine 0 the XLA
    fold, 1 the Pallas kernel in interpret mode) equals the pairwise fold
    over the rows the tables placed; a row a table dropped (read off its
    `slot_of`) neither hits nor is hit."""
    import jax.numpy as jnp

    from noahgameframe_tpu.game.combat import combat_fold_xla
    from noahgameframe_tpu.ops.stencil import (
        build_cell_table_pair,
        pull_slots,
    )
    from noahgameframe_tpu.ops.stencil_pallas import combat_fold_pallas

    width, cell_size, radius = 6, 5.0, 5.0
    pos, active, attacking, vic_feats, att_feats = _combat_arrays(
        n, seed, width, cell_size, clump)
    vt, at = build_cell_table_pair(
        pos, active, vic_feats, attacking, att_feats,
        cell_size, width, bucket, sub_bucket,
    )
    if engine == 1:
        inc, bestr = combat_fold_pallas(vt, at, radius, interpret=True)
    else:
        inc, bestr = combat_fold_xla(vt, at, radius)
    got = np.asarray(pull_slots(
        vt.slot_of, jnp.stack([inc, bestr], axis=-1), fill=(0, -1)))
    vic_placed = np.asarray(vt.slot_of) < width * width * bucket
    att_placed = np.asarray(at.slot_of) < width * width * sub_bucket
    if name in ("siege_one_cell", "overfull_cells"):
        assert int(vt.dropped) > 0 and int(at.dropped) > 0
        assert int(vt.dropped) == int((np.asarray(active) & ~vic_placed).sum())
    want_inc, want_best = _brute_fold(att_feats, vic_placed, att_placed,
                                      radius)
    assert want_inc.any(), "the case lost its shape: nobody is hit"
    np.testing.assert_array_equal(got[:, 0], want_inc)
    np.testing.assert_array_equal(got[:, 1], want_best)


def _digest_stream(use_pallas, ticks, n=200, seed=3):
    w = build(n, seed, use_pallas=use_pallas)
    k = w.kernel
    k.enable_digest()
    out = []
    for _ in range(ticks):
        k.tick()
        out.append(int(k.last_counters["state_digest"]) & 0xFFFFFFFF)
    return out


def _digest_after(use_pallas, ticks, n=200, seed=3):
    w = build(n, seed, use_pallas=use_pallas)
    k = w.kernel
    k.enable_digest()
    k.run_device(ticks)
    k.tick()
    return int(k.last_counters["state_digest"]) & 0xFFFFFFFF


def test_engine_digest_parity_24():
    """24 churn ticks: the world ends in the EXACT same state under both
    engines (0 = XLA fold, 1 = Pallas fold)."""
    assert _digest_after(0, 24) == _digest_after(1, 24)


@pytest.mark.slow
def test_engine_digest_parity_120():
    assert _digest_after(0, 120) == _digest_after(1, 120)


def test_pallas_replay_digest_stream_clean():
    """Per-tick digest STREAMS (not just the end state) are identical
    with the engine pin flipped: a replay of the same seed under the
    other fold stays digest-clean at every tick."""
    assert _digest_stream(0, 12) == _digest_stream(1, 12)


def test_engine_flip_soak_unexplained_clean():
    """Flipping the engine mid-run is a SANCTIONED retrace: the flip
    rides kernel.invalidate()'s generation bump, so the CostBook soak
    gate stays empty over the fused window."""
    w = build(150, 5, use_pallas=0)
    k = w.kernel
    k.enable_digest()
    k.run_device(6)
    mark = k.costbook.mark()
    w.combat.use_pallas = 1
    k.invalidate()  # engine choice is baked into the trace
    k.run_device(12)
    k.tick()
    assert w.combat.engine_baked == 1
    assert k.costbook.unexplained_since(mark) == []


@pytest.mark.parametrize("engine", [0, 1])
def test_engine_baked_names_the_traced_engine(engine):
    """`engine_baked` (the benchmark's tick driver, bench.py and
    chip_smoke.py read it) and the `nf_combat_fold_engine` gauge: -1
    before the first trace, then the engine the tick compiled with."""
    w = build(40, 2, use_pallas=engine)
    assert w.combat.engine_baked is None
    assert "nf_combat_fold_engine -1" in w.telemetry.exposition()
    w.tick()
    assert w.combat.engine_baked == engine
    assert f"nf_combat_fold_engine {engine}" in w.telemetry.exposition()
    # the attacker chunk's counters do not depend on the fold
    assert w.kernel.last_counters["aoe_attacker_chunks"] == 1


@pytest.mark.parametrize("pin", [2, 3, -1])
def test_an_unknown_pin_is_refused_when_the_tick_traces(pin):
    """`use_pallas` pins 0 or 1 (the deleted fused engine's 2 is an
    unknown value like any other): the tick raises at trace time and
    bakes nothing."""
    w = build(8, 1, use_pallas=pin)
    with pytest.raises(ValueError, match=f"use_pallas={pin}"):
        w.tick()
    assert w.combat.engine_baked is None


def test_pallas_fold_over_verlet_tables_digest_parity():
    """The two forks that remain, together: the Pallas fold over the
    Verlet cache's tables (rebuild and reuse ticks) ends in the state
    the XLA fold reaches over the same tables."""
    def digests(engine):
        w = GameWorld(WorldConfig(
            npc_capacity=256, extent=48.0, aoe_radius=4.0, seed=5,
            middleware=False, verlet_skin=2.0))
        w.combat.use_pallas = engine
        w.start()
        w.scene.create_scene(1, width=48.0)
        w.seed_npcs(200)
        k = w.kernel
        k.enable_digest()
        out = []
        for _ in range(12):
            k.tick()
            out.append(int(k.last_counters["state_digest"]) & 0xFFFFFFFF)
        assert k.counter_totals["grid_reuses"] > 0
        assert k.counter_totals["combat_hits"] > 0
        return out

    assert digests(0) == digests(1)


def test_pallas_fold_under_vmap_matches_xla():
    """The room fleet's shape: the tick vmapped over a room axis with
    the Pallas fold inside (two 96-NPC rooms, 35 ticks, past the first
    attacks) leaves every leaf of both rooms as the XLA fold does."""
    import jax
    import jax.numpy as jnp

    from noahgameframe_tpu.game import BenchmarkRoomRecipe

    recipe = BenchmarkRoomRecipe(96, 16.0, player_capacity=4)

    def run(engine):
        worlds = [recipe(seed) for seed in (3, 4)]
        for w in worlds:
            w.combat.use_pallas = engine
            w.kernel._ensure_aux()
        k = worlds[0].kernel
        st = jax.tree.map(lambda *xs: jnp.stack(xs),
                          *[w.kernel.state for w in worlds])
        step = jax.jit(lambda s: jax.vmap(k._trace_step)(s)[0])
        for _ in range(35):
            st = step(st)
        assert worlds[0].combat.engine_baked == engine
        return st

    a, b = run(0), run(1)
    hp = np.asarray(a.classes["NPC"].i32)
    assert (hp != np.asarray(recipe(3).kernel.state.classes["NPC"].i32)
            ).any(), "nothing happened in 35 ticks"
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------ the fold's choice of engine


@pytest.mark.parametrize("platform,width,kv,ka,want", RULE_CASES)
def test_fold_engine_rule(platform, width, kv, ka, want):
    """`fold_engine` is a function of the platform, the grid's width and
    the two depths, and answers as the cells' grids need it to."""
    assert sp.fold_engine(platform, width, kv, ka) == want


def test_fold_engine_rule_in_its_own_terms():
    """The rule's two measures: the share of lanes that carry cells
    goes by the width alone, and the VMEM count grows with either depth
    and with the lanes, so a boost can only move a grid from the kernel
    to the XLA fold, never back."""
    assert sp.fold_lane_fill(395) == 395 / 512
    assert sp.fold_lane_fill(125) == 125 / 128
    assert sp.fold_lane_fill(4) == 4 / 128
    assert sp.fold_lane_fill(128) == 1.0 > sp.fold_lane_fill(129)
    for width in (125, 395):
        need = [sp.fold_vmem_bytes(width, 16 * b, 6 * b) for b in (1, 2, 4, 8)]
        assert need == sorted(need) and len(set(need)) == 4
        answers = [sp.fold_engine("tpu", width, 16 * b, 6 * b)
                   for b in (1, 2, 4, 8)]
        assert answers == sorted(answers, reverse=True)
    # K rides the sublanes in whole tiles of 8: 12 deep costs what 16 does
    assert sp.fold_vmem_bytes(395, 32, 12) == sp.fold_vmem_bytes(395, 32, 16)
    assert sp.fold_vmem_bytes(512, 32, 12) > sp.fold_vmem_bytes(395, 32, 12)


@pytest.mark.parametrize("boost,want", [(1, 1), (2, 1), (4, 0), (8, 0)])
def test_a_bucket_boost_retraces_into_a_fold_that_compiles(
        monkeypatch, boost, want):
    """`resolved_engine` hands the rule this module's own width and
    resolved depths: `npc-1m`'s grid gets the kernel at the depths it
    soaks (16/6) and runs (32/12) at, and the XLA fold from the next
    doubling on, which Mosaic refuses (tests/test_tpu_compile.py)."""
    from noahgameframe_tpu.game.combat import CombatModule

    monkeypatch.setattr(sp, "trace_platform", lambda: "tpu")
    m = CombatModule(extent=float(np.sqrt(1_000_000 / 0.4)), radius=4.0)
    m._attacker_duty = 1.0 / 30.0
    m._bucket_boost = boost
    cap = 1 << 20
    assert (m.width, m.resolved_bucket(cap), m.resolved_att_bucket(cap)) \
        == (395, 16 * boost, 6 * boost)
    assert m.resolved_engine(cap) == want
    for pin in (0, 1, False, True):
        m.use_pallas = pin
        assert m.resolved_engine(cap) == int(pin)


def test_a_world_traced_on_the_cpu_bakes_the_xla_fold():
    """Nothing pinned, the tick traced for the CPU bakes engine 0 at
    any grid (the kernel is interpreted here, a test device); pinned to
    1 the same world bakes the kernel, and the two digest streams are
    equal."""
    def stream(pin):
        w = build(120, 3, use_pallas=pin)
        k = w.kernel
        k.enable_digest()
        out = []
        for _ in range(8):
            k.tick()
            out.append(int(k.last_counters["state_digest"]) & 0xFFFFFFFF)
        return w.combat.engine_baked, out

    assert sp.trace_platform() == "cpu" and sp.pallas_interpret()
    chosen, a = stream(None)
    pinned, b = stream(1)
    assert (chosen, pinned) == (0, 1)
    assert a == b
