"""The served siege's mirror reference and planted faults (CPU, the
configuration's rehearsal world: 4,096 NPCs on 16 Zipf-sized camps
behind the five roles, three sessions standing in the crowd).

The cluster is driven by the cell's own driver, as on the chip (its
`CrowdCluster`, its `prepare`, the sampled ticks' bank copies, every
client's mirror at the sampled frames); each fault is then planted where
it would arise and has to fail the limit it belongs to."""

import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import (compare, manifest,  # noqa: E402
                                reference_siege_served, work_interest)
from benchmarks.harness.run import Run  # noqa: E402

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
CELL = "siege-served-s32"
with open(os.path.join(
        ROOT, "benchmarks/traffic/siege-login-burst-32.json")) as f:
    MIX = json.load(f)
with open(os.path.join(ROOT, "benchmarks/traffic/login-burst-32.json")) as f:
    SERVED_MIX = json.load(f)
LIMITS = MIX["limits"]
SEED = 2147483659
FRAMES = 3


def within_limits(got):
    return all(got[k] <= lim for k, lim in LIMITS.items() if k in got)


def served_frames(tmp, sized=True):
    """The rehearsal cluster through the driver's own set-up, then
    FRAMES sampled frames: the banks around their ticks, every client's
    mirror, the interest sizes and counters of each."""
    import jax

    man = manifest.Manifest(MANIFEST)
    cell = man.cell(CELL, rehearse=True)
    os.environ.update({k: str(v) for k, v in cell.mix["env"].items()})
    driver = manifest.load_module(cell.driver_path, "driver_siege_served")
    run = Run(cell=cell, seed=SEED, seconds=1.0, trace=False, rehearse=True,
              control=False, devices=jax.devices()[:1], trace_dir=str(tmp),
              process_t0_ns=time.perf_counter_ns())
    sessions = int(cell.mix["sessions"])
    live = driver.CrowdCluster(run, SEED, sessions, live=True)
    try:
        if not sized:  # the parent's role: the capacity's depth, for good
            live.game.interest_overflow_budget = 1.0
        driver.prepare(live, cell.mix)
        k, game = live.kernel, live.game
        snaps = compare.Snapshots(k, "NPC", "CommPropertyValue",
                                  observers="Player")
        snaps.warm()
        first = int(k.tick_count) + 2
        live.tap_ticks(snaps, range(first, first + FRAMES))
        want = FRAMES * sessions
        ok = live.cluster.pump_until(lambda: len(live.mirrors) >= want,
                                     extra=live.pump_clients, timeout=120)
        assert ok, (len(live.mirrors), want)
        from benchmarks.drivers import served

        out = {
            "host": snaps.to_host(), "mirrors": dict(live.mirrors),
            "idents": served.npc_idents(k),
            "avatar_rows": served.session_rows(game, live.clients),
            "extent": float(live.world.config.extent),
            "radius": float(cell.config["served"]["interest_radius"]),
            "sizes_of": {t: v[0] for t, v in live.interest.items()},
            "dropped": {t: v[1]["dropped"] for t, v in live.interest.items()},
            "counted": {t: v[1] for t, v in live.interest.items()},
            "spots_stood": live.spots.copy(), "config": cell.config,
            "sessions": sessions, "sized": live.sized(),
            "resizes": int(game.interest_resizes),
        }
    finally:
        live.close()
    out["spots"] = reference_siege_served.avatar_spots(
        SEED, cell.config, out["extent"], sessions)
    return out


def check(kept, **changed):
    a = dict(kept, **changed)
    return reference_siege_served.mirror_wrong(
        a["host"], a["mirrors"], a["idents"], a["avatar_rows"],
        a["host"].layout, a["extent"], a["radius"], a["sizes_of"],
        a["spots"], program_dropped=a["dropped"])


@pytest.fixture(scope="module")
def kept(tmp_path_factory):
    return served_frames(tmp_path_factory.mktemp("trace"))


def test_the_mix_holds_the_served_cells_limits_number_for_number():
    assert LIMITS == SERVED_MIX["limits"]
    assert LIMITS["mirror_wrong"] == 0 and LIMITS["dropped_off"] == 0
    assert LIMITS["mirror_ambiguous"] == 50000.0
    assert MIX["sessions"] == 32 and MIX["env"] == SERVED_MIX["env"]
    for key in ("soak_ticks", "warm_frames", "compare_ticks",
                "trace_seconds", "warm_cluster"):
        assert MIX[key] == SERVED_MIX[key], key


def test_the_configuration_is_the_sieges_world_behind_the_served_roles():
    def load(name):
        with open(os.path.join(ROOT, "benchmarks/configs", name)) as f:
            return json.load(f)

    mine, siege, npc = (load(n + ".json") for n in (
        "siege-zipf-1m-served", "siege-zipf-1m", "npc-100k"))
    assert mine["world"] == siege["world"] and mine["row"] == siege["row"]
    assert mine["served"] == npc["served"]
    assert mine["hot_cell_rows"] == siege["hot_cell_rows"]
    assert set(siege["guarantees"]) | set(npc["guarantees"]) \
        <= set(mine["guarantees"])
    assert mine["reduced"] == ["sessions"] == npc["reduced"]
    assert len(mine["source"]) <= 200
    for key, value in siege["assumed"].items():
        assert key in mine["assumed"], key
    with open(MANIFEST) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == mine["name"])
    assert entry["source"] == mine["source"]
    assert entry["reduced"] == mine["reduced"]


def test_the_avatars_stand_where_the_seed_puts_them(kept):
    """The reference's own tick-0 positions are the program's, and the
    avatars stand on the stratified rows' spots, bit for bit."""
    np.testing.assert_array_equal(kept["spots"], kept["spots_stood"])
    n = kept["config"]["world"]["entities"]
    rows = reference_siege_served.avatar_rows(n, kept["sessions"])
    assert list(rows) == [int((i + 0.5) * n / kept["sessions"])
                          for i in range(kept["sessions"])]
    assert list(reference_siege_served.avatar_rows(1_000_000, 32)[:3]) \
        == [15625, 46875, 78125]  # all three in the largest camp (108,107)
    t = min(kept["host"].post)
    stood = kept["host"].post[t]["obs_vec"][kept["avatar_rows"], 0, :2]
    np.testing.assert_array_equal(stood, kept["spots"])


def test_sound_frames_hold_every_limit(kept):
    """The crowd is there, the level holds it, nothing is set aside, and
    the program's drop counter is the count its stated sizes imply."""
    bucket, cells, depth = (kept["sized"][k] for k in (
        "interest_bucket", "interest_spill_cells", "interest_spill_depth"))
    assert cells > 0 and depth >= 256 and kept["resizes"] >= 1
    assert len(kept["mirrors"]) == FRAMES * kept["sessions"]
    got = check(kept)
    assert got["mirror_wrong"] == 0 and got["interest_dropped_off"] == 0
    assert got["mirror_ambiguous"] == 0 and within_limits(got)
    # a client in the crowd mirrors far more than nine base cells hold
    assert got["mirror_checked"] > FRAMES * 9 * bucket
    for t in {t for _c, t in kept["mirrors"]}:
        assert kept["sizes_of"][t] == (bucket, cells, depth)
        seen = kept["counted"][t]
        assert seen["dropped"] == 0 and seen["spill_rows"] > 500
        assert seen["cell_rows_max"] > 4 * bucket
        assert seen["candidates_max"] > 9 * bucket
    w = work_interest.interest_work(
        kept["host"].post[min(kept["host"].post)], kept["avatar_rows"],
        kept["host"].layout, kept["extent"], kept["radius"])
    assert w["rows"] == 4096 and w["pairs"] * FRAMES == pytest.approx(
        got["mirror_checked"], rel=0.05)
    assert w["bytes"] == 4 * (4 * w["rows"] + w["pairs"])


def test_the_level_never_sized_fails_mirror_ambiguous(tmp_path):
    """The parent's role in the crowd: the depth the capacity sizes and
    no policy.  Its counters agree with its stated depth and no entry is
    wrong; what has to be set aside is far beyond what the comparison
    may excuse."""
    parent = served_frames(tmp_path, sized=False)
    assert parent["resizes"] == 0
    assert parent["sized"]["interest_spill_cells"] == 0
    got = check(parent)
    assert got["mirror_wrong"] == 0 and got["interest_dropped_off"] == 0
    assert max(parent["dropped"].values()) > 1000
    assert got["mirror_ambiguous"] > 4 * LIMITS["mirror_ambiguous"]
    assert not within_limits(got)


def test_the_level_sized_and_not_stated_fails_dropped_off(kept):
    """The program's counter (no drops) against sizes that state one
    level: the implied drops are not the counted ones."""
    bucket = kept["sized"]["interest_bucket"]
    got = check(kept, sizes_of={t: (bucket, 0, 0)
                                for t in kept["sizes_of"]})
    res = {"dropped_off": got["interest_dropped_off"], **got}
    assert res["dropped_off"] > 1000 and not within_limits(res)
    # (the rows the unstated level showed are then excused, not wrong)
    assert got["mirror_wrong"] == 0


def deepest_view(kept):
    """(client, tick) of the widest mirror, and a row of it that only
    the second level can have shown: one of the highest rows of the
    fullest cell."""
    key = max(kept["mirrors"], key=lambda k: len(kept["mirrors"][k]))
    post = kept["host"].post[key[1]]
    lay = kept["host"].layout
    pos = post["vec"][:, lay.position_col, :2]
    width = int(np.ceil(kept["extent"] / kept["radius"]))
    c = np.clip(np.floor(pos / np.float32(kept["radius"])).astype(int), 0,
                width - 1)
    cell = c[:, 1] * width + c[:, 0]
    count = np.bincount(cell[post["alive"]], minlength=width * width)
    mirror = kept["mirrors"][key]
    mine = {(int(h), int(d)): r for r, (h, d) in enumerate(kept["idents"])}
    rows = sorted(mine[k] for k in mirror if k in mine)
    bucket = kept["sized"]["interest_bucket"]
    hot = [r for r in rows if count[cell[r]] > 4 * bucket
           and (np.flatnonzero(post["alive"] & (cell == cell[r])) < r).sum()
           >= bucket]
    assert hot
    ident = tuple(int(v) for v in kept["idents"][hot[-1]])
    return key, ident


def test_one_mirrored_position_off_by_a_quantum_fails_mirror_wrong(kept):
    key, ident = deepest_view(kept)
    x, y, z = kept["mirrors"][key][ident]
    off = dict(kept["mirrors"][key])
    off[ident] = (x + kept["extent"] / 65535, y, z)
    got = check(kept, mirrors={**kept["mirrors"], key: off})
    assert got["mirror_wrong"] == 1 and not within_limits(got)


def test_one_npc_withheld_from_a_hot_cells_view_fails_mirror_wrong(kept):
    key, ident = deepest_view(kept)
    less = dict(kept["mirrors"][key])
    del less[ident]
    got = check(kept, mirrors={**kept["mirrors"], key: less})
    assert got["mirror_wrong"] == 1 and not within_limits(got)


def test_avatars_placed_from_another_seed_fail_mirror_wrong(kept):
    """The traffic's spots are the seed's: a run that stood its avatars
    on another seed's crowd mirrors somebody else's view."""
    other = reference_siege_served.avatar_spots(
        SEED + 1, kept["config"], kept["extent"], kept["sessions"])
    assert not np.array_equal(other, kept["spots"])
    got = check(kept, spots=other)
    assert got["mirror_wrong"] >= got["mirror_checked"] > 0
    assert not within_limits(got)


def test_the_drop_model_is_worked_out_once_a_frame(kept, monkeypatch):
    calls = []
    real = reference_siege_served.frame_drops

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(reference_siege_served, "frame_drops", counting)
    check(kept)
    assert len(calls) == FRAMES


def test_lower_precision_control_fails_the_mirror(kept):
    from benchmarks.drivers import served

    ctl = served.control_mirrors(
        kept["host"], kept["mirrors"], kept["idents"], kept["avatar_rows"],
        kept["host"].layout, kept["extent"], kept["radius"])
    got = check(kept, mirrors=ctl)
    assert got["mirror_wrong"] > 100 and not within_limits(got)


def test_the_parent_cannot_import_the_driver(monkeypatch):
    """A tree whose game role states no interest sizes fails the cell at
    import, at once: the driver tries nothing there."""
    from noahgameframe_tpu.net.roles.game import GameRole

    man = manifest.Manifest(MANIFEST)
    cell = man.cell(CELL, rehearse=True)
    monkeypatch.delattr(GameRole, "resolved_interest")
    with pytest.raises(ImportError, match="resolved_interest"):
        manifest.load_module(cell.driver_path, "driver_siege_served_parent")
