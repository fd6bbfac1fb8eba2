"""The neighbour engine's second level (ops/stencil.py `_spill_slots`,
game/combat.py `combat_fold_spill`) against brute-force all pairs on a
seeded Zipf crowd: every incoming total and strongest attacker exact
whatever level a row sits in, the drop rule, and the one-level build
left as it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from noahgameframe_tpu.game.combat import (combat_fold_spill,
                                           combat_fold_xla)
from noahgameframe_tpu.ops import stencil_pallas
from noahgameframe_tpu.ops.stencil import (build_cell_table_pair,
                                           pull_slots)

RADIUS = 4.0
CELL = 4.0


def zipf_crowd(seed, n=4096, camps=16, extent=128.0, leash=6.0):
    """n NPCs on Zipf-sized camps: the first camp's middle cells hold
    some twenty times the base depth the tests build with."""
    rng = np.random.default_rng(seed)
    centres = rng.uniform(0.0, extent, (camps, 2))
    w = np.arange(1, camps + 1, dtype=np.float64) ** -0.99
    home = rng.choice(camps, n, p=w / w.sum())
    pos = centres[home] + leash * rng.uniform(-1.0, 1.0, (n, 2))
    pos = np.clip(pos, 0.0, extent).astype(np.float32)
    return {
        "pos": pos, "extent": extent,
        "alive": rng.random(n) < 0.97,
        "attacking": rng.random(n) < 0.2,
        "atk": rng.integers(0, 4, n) * 5,  # few values: many ties; some 0
        "camp": rng.integers(0, 2, n),
    }


def cells_of(c):
    width = int(c["extent"] / CELL)
    at = np.clip(np.floor(c["pos"] / np.float32(CELL)).astype(int), 0,
                 width - 1)
    return at[:, 1] * width + at[:, 0]


def brute_force(c):
    """All pairs: (incoming, strongest attacker's row, lowest among
    equals; -1 none), float32 distances as the fold takes them."""
    pos = c["pos"]
    att = np.flatnonzero(c["attacking"] & c["alive"] & (c["atk"] != 0))
    dx = pos[:, None, 0] - pos[None, att, 0]
    dy = pos[:, None, 1] - pos[None, att, 1]
    ok = (dx * dx + dy * dy <= np.float32(RADIUS * RADIUS)) \
        & (c["camp"][:, None] != c["camp"][None, att]) & c["alive"][:, None]
    atk = c["atk"][att].astype(np.int64)
    incoming = (ok * atk[None, :]).sum(axis=1)
    sa = np.where(ok, atk[None, :], -1)
    top = sa.max(axis=1, initial=-1)
    first = np.where(sa >= top[:, None], att[None, :], 1 << 30).min(
        axis=1, initial=1 << 30)
    return incoming, np.where(top >= 0, first, -1)


def engine(pos, alive, attacking, atk, camp, width, bucket, att_bucket,
           spill=(0, 0, 0), pallas=False, sub_rows=None):
    """The combat phase's engine on traced arrays: both tables, the base
    fold, the second level's, one pull.  Returns ([n, 2] incoming and
    strongest attacker, victim table, attacker table)."""
    n = pos.shape[0]
    f32 = jnp.float32
    camp = camp.astype(f32)
    zero = jnp.zeros((n,), f32)
    vic_feats = jnp.stack([pos[:, 0], pos[:, 1], camp, zero, zero], axis=-1)
    eff = jnp.where(attacking, atk, 0).astype(f32)
    att_feats = jnp.stack([pos[:, 0], pos[:, 1], eff, camp, zero, zero,
                           jnp.arange(n, dtype=f32)], axis=-1)
    vic, att = build_cell_table_pair(
        pos, alive, vic_feats, attacking, att_feats, CELL, width, bucket,
        att_bucket, sub_rows=sub_rows, spill=spill)
    raw = spill[0] > 0
    if pallas:
        folded = stencil_pallas.combat_fold_pallas(
            vic, att, RADIUS, interpret=True, raw=raw)
    else:
        folded = combat_fold_xla(vic, att, RADIUS, raw=raw)
    hot = None
    if raw:
        inc, bestr, hot_inc, hot_bestr = combat_fold_spill(
            vic, att, RADIUS, *folded)
        hot = jnp.stack([hot_inc, hot_bestr], axis=-1)
    else:
        inc, bestr = folded
    pulled = pull_slots(vic.slot_of, jnp.stack([inc, bestr], axis=-1),
                        fill=(0, -1), spill=hot)
    return pulled, vic, att


def crowd_arrays(c):
    return (jnp.asarray(c["pos"]), jnp.asarray(c["alive"]),
            jnp.asarray(c["attacking"] & c["alive"]), jnp.asarray(c["atk"]),
            jnp.asarray(c["camp"]))


def resolve(c, bucket, att_bucket, spill=(0, 0, 0), pallas=False,
            sub_rows=None):
    """The engine from a crowd to per-row results on the host."""
    pulled, vic, att = engine(
        *crowd_arrays(c), int(c["extent"] / CELL), bucket, att_bucket,
        spill=spill, pallas=pallas, sub_rows=sub_rows)
    return {"incoming": np.asarray(pulled[:, 0]),
            "best": np.asarray(pulled[:, 1]),
            "slot_of": np.asarray(vic.slot_of),
            "att_slot_of": np.asarray(att.slot_of),
            "dropped": (int(vic.dropped), int(att.dropped)),
            "stats": tuple(int(x) for x in vic.stats + att.stats),
            "payloads": (np.asarray(vic.payload), np.asarray(att.payload))}


BASE = (8, 4)  # a cell of the first camp holds up to ~20 times 8


@pytest.mark.parametrize("pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("seed", [5, 17])
def test_two_levels_equal_all_pairs(seed, pallas):
    c = zipf_crowd(seed)
    hot_cells, rows_max, _, att_hot, att_max, _ = resolve(c, *BASE)["stats"]
    assert rows_max >= 20 * BASE[0] // 2 and hot_cells > 8 and att_hot > 0
    spill = (256, -(-(rows_max - BASE[0]) // 32) * 32, att_max)
    two = resolve(c, *BASE, spill=spill, pallas=pallas)
    assert two["dropped"] == (0, 0)
    incoming, best = brute_force(c)
    np.testing.assert_array_equal(two["incoming"], incoming)
    np.testing.assert_array_equal(two["best"], best)
    # the counters: cells over the base depth, the deepest, rows placed
    cell = cells_of(c)
    count = np.bincount(cell[c["alive"]], minlength=32 * 32)
    acount = np.bincount(cell[c["attacking"] & c["alive"]],
                         minlength=32 * 32)
    assert two["stats"] == (
        int((count > BASE[0]).sum()), int(count.max()),
        int(np.maximum(count - BASE[0], 0).sum()),
        int((acount > BASE[1]).sum()), int(acount.max()),
        int(np.maximum(acount - BASE[1], 0).sum()))


def uniform_crowd(seed, n=1500, extent=64.0):
    rng = np.random.default_rng(seed)
    return {
        "pos": rng.uniform(0.0, extent, (n, 2)).astype(np.float32),
        "extent": extent, "alive": rng.random(n) < 0.95,
        "attacking": rng.random(n) < 0.3,
        "atk": rng.integers(0, 4, n) * 5, "camp": rng.integers(0, 2, n),
    }


@pytest.mark.parametrize("pallas", [False, True], ids=["xla", "pallas"])
def test_an_idle_second_level_changes_nothing(pallas):
    """No cell over-full: the engine with a second level sized and the
    one-level engine give the same bits, in the tables' base part, the
    slots and every result."""
    c = uniform_crowd(3)
    one = resolve(c, 24, 12, pallas=pallas)
    two = resolve(c, 24, 12, spill=(8, 32, 8), pallas=pallas)
    assert one["stats"] == two["stats"] and one["stats"][0] == 0
    assert one["dropped"] == two["dropped"] == (0, 0)
    for name in ("incoming", "best", "slot_of", "att_slot_of"):
        np.testing.assert_array_equal(one[name], two[name])
    for a, b in zip(one["payloads"], two["payloads"]):
        np.testing.assert_array_equal(a, b[:a.shape[0]])
        assert not b[a.shape[0]:].any()
    incoming, best = brute_force(c)
    np.testing.assert_array_equal(one["incoming"], incoming)
    np.testing.assert_array_equal(one["best"], best)


def test_no_second_level_is_the_one_level_build():
    """`spill=(0, 0, 0)`: tables, slots and drop counts of the parent's
    build (two independent one-table builds), over-full cells
    included."""
    from noahgameframe_tpu.ops.stencil import build_cell_table

    c = zipf_crowd(9, n=1024)
    got = resolve(c, *BASE)
    width = int(c["extent"] / CELL)
    pos = jnp.asarray(c["pos"])
    alive = jnp.asarray(c["alive"])
    lone = build_cell_table(pos, alive, jnp.zeros((1024, 1)), CELL, width,
                            BASE[0])
    np.testing.assert_array_equal(got["slot_of"], np.asarray(lone.slot_of))
    assert got["dropped"][0] == int(lone.dropped) > 0
    lone = build_cell_table(pos, jnp.asarray(c["attacking"] & c["alive"]),
                            jnp.zeros((1024, 1)), CELL, width, BASE[1])
    np.testing.assert_array_equal(got["att_slot_of"],
                                  np.asarray(lone.slot_of))
    assert got["dropped"][1] == int(lone.dropped) > 0


def two_level_drops(c, mask, depth, cells, more):
    """The drop rule in one sentence, in numpy: a cell's rows in row
    order fill `depth` slots and, in the first `cells` over-full cells
    in cell order, `more` slots beyond; the rest are dropped."""
    cell = cells_of(c)
    dropped, hot = [], 0
    for at in np.unique(cell[mask]):
        rows = np.flatnonzero(mask & (cell == at))  # ascending
        if rows.size > depth:
            kept = depth + (more if hot < cells else 0)
            dropped += list(rows[kept:])
            hot += 1
    return np.asarray(sorted(dropped), np.int64)


@pytest.mark.parametrize("spill", [(256, 32, 8), (4, 64, 8), (16, 32, 2)],
                         ids=["depth", "cells", "attackers"])
def test_what_fits_neither_level_is_dropped_highest_row_first(spill):
    """Rows beyond the second level's depth, or in an over-full cell
    beyond its last, go to the dump slot and are counted by the base
    level's counters; every other row's result is exact but for what a
    dropped attacker would have dealt."""
    c = zipf_crowd(23, n=2048)
    got = resolve(c, *BASE, spill=spill)
    att_mask = c["attacking"] & c["alive"]
    vic = two_level_drops(c, c["alive"], BASE[0], spill[0], spill[1])
    att = two_level_drops(c, att_mask, BASE[1], spill[0], spill[2])
    assert vic.size > 0
    assert got["dropped"] == (vic.size, att.size)
    dump = 32 * 32 * BASE[0]
    np.testing.assert_array_equal(
        np.flatnonzero(c["alive"] & (got["slot_of"] == dump)), vic)
    np.testing.assert_array_equal(
        np.flatnonzero(att_mask & (got["att_slot_of"] == 32 * 32 * BASE[1])),
        att)
    # the survivors against all pairs of the attackers that were placed
    placed = dict(c, attacking=c["attacking"].copy())
    placed["attacking"][att] = False
    incoming, best = brute_force(placed)
    seen = np.ones(2048, bool)
    seen[vic] = False
    np.testing.assert_array_equal(got["incoming"][seen], incoming[seen])
    np.testing.assert_array_equal(got["best"][seen], best[seen])
    assert not got["incoming"][vic].any()


def test_both_levels_add_up_to_all_pairs_each_pair_once():
    """The share test: with every attack value 1, a row's incoming total
    is its count of in-radius enemy attackers, whatever level either
    end sits in: a pair folded twice or not at all moves it."""
    c = zipf_crowd(31, n=3000)
    c["atk"] = np.ones(3000, np.int64)
    stats = resolve(c, *BASE)["stats"]
    got = resolve(c, *BASE, spill=(256, -(-stats[1] // 32) * 32, stats[4]))
    incoming, _ = brute_force(c)
    assert got["dropped"] == (0, 0) and incoming.max() > 20
    np.testing.assert_array_equal(got["incoming"], incoming)
    # and the levels each carry a share: rows of both got hit
    dump = 32 * 32 * BASE[0]
    assert incoming[got["slot_of"] > dump].sum() > 0
    assert incoming[got["slot_of"] < dump].sum() > 0


def test_second_level_under_vmap():
    """Two worlds in one batched build and fold equal each alone."""
    crowds = [zipf_crowd(s, n=1024) for s in (41, 43)]
    spill = (64, 96, 16)
    alone = [resolve(c, *BASE, spill=spill) for c in crowds]

    def one(*arrays):
        return engine(*arrays, 32, *BASE, spill=spill)[0]

    batched = jax.vmap(one)(*(
        jnp.stack(leaves)
        for leaves in zip(*(crowd_arrays(c) for c in crowds))))
    for got, want in zip(np.asarray(batched), alone):
        np.testing.assert_array_equal(got[:, 0], want["incoming"])
        np.testing.assert_array_equal(got[:, 1], want["best"])
