"""Runtime combat-overflow surfacing: the tick's drop signal reaches a
module counter, alerts on budget breach, and auto-resizes the bucket so
the drops STOP (VERDICT r4 item 5 — previously bench-only)."""

from __future__ import annotations

import numpy as np
import pytest

from noahgameframe_tpu.game import GameWorld, WorldConfig


def crowded_world(bucket=1, auto_resize=True):
    """Everyone piled into one cell with a bucket of 1: guaranteed
    overflow on the first combat tick."""
    w = GameWorld(WorldConfig(
        combat=True, movement=False, regen=False, middleware=False,
        npc_capacity=64, player_capacity=8, extent=64.0,
        aoe_radius=8.0, aoi_bucket=bucket,
        attack_period_s=1 / 30, respawn_s=1e6,
    )).start()
    w.combat.auto_resize = auto_resize
    w.scene.create_scene(1)
    w.seed_npcs(32)
    k = w.kernel
    # cram every NPC into the same spot (same cell)
    host = k.store._hosts["NPC"]
    for row in np.flatnonzero(host.alloc_mask):
        k.set_property(host.row_guid[int(row)], "Position",
                       (10.0, 10.0, 0.0))
    return w


def test_overflow_alerts_and_counts_without_resize():
    w = crowded_world(auto_resize=False)
    for _ in range(3):
        w.tick()
    c = w.combat
    assert c.overflow_total > 0  # the runtime SAW the drops
    assert c.overflow_alerts >= 1  # and alerted on the budget breach
    assert c._bucket_boost == 1  # resize disabled: bucket untouched


def test_auto_resize_stops_the_drops():
    w = crowded_world(auto_resize=True)
    c = w.combat
    c.max_bucket_boost = 64  # enough headroom for 32 piled into bucket 1
    c.SPILL_MIN_OVERDEPTH = 1 << 20  # the doubling alone (no second level)
    for _ in range(20):
        w.tick()
        if c._bucket_boost >= 32:
            break
    assert c.overflow_alerts >= 1
    assert c._bucket_boost >= 32  # grew until the pile-up fits
    assert c.resolved_spill(64) == (0, 0, 0)
    # the boosted bucket holds all 32 entities: drops actually STOP
    w.tick()
    w.tick()
    assert c.overflow_last == (0, 0)


def test_one_deep_cell_gets_the_second_level_and_the_drops_stop():
    """32 piled into a cell of depth 1 are one cell 32 times over: the
    breach sizes the second level for it, the grid keeps its depth, and
    one retrace later nothing is dropped."""
    w = crowded_world(auto_resize=True)
    c = w.combat
    for _ in range(4):
        w.tick()
    assert c.overflow_alerts == 1 and c._bucket_boost == 1
    cells, depth, att_depth = c.resolved_spill(64)
    assert cells >= 1 and depth >= 31 and att_depth >= 8
    assert c.overflow_last == (0, 0)
    assert w.kernel.last_counters["aoe_spill_rows"] >= 31


def test_no_overflow_no_alert():
    """A well-bucketed world never alerts (auto_bucket default)."""
    w = GameWorld(WorldConfig(
        combat=True, movement=False, regen=False, middleware=False,
        npc_capacity=64, player_capacity=8, extent=64.0,
        aoe_radius=4.0, attack_period_s=1 / 30, respawn_s=1e6,
    )).start()
    w.scene.create_scene(1)
    w.seed_npcs(32)
    for _ in range(3):
        w.tick()
    assert w.combat.overflow_alerts == 0
    assert w.combat.overflow_last == (0, 0)
