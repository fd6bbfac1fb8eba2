"""The plain reference against the program, and the comparison against
its control and its planted faults (CPU, small worlds)."""

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
from benchmarks.harness import compare, reference  # noqa: E402

with open(os.path.join(ROOT, "benchmarks/traffic/observed-closed.json")) as f:
    LIMITS = json.load(f)["limits"]


def world_and_ticks(n, seed, soak, ticks, bucket=None):
    """Soak a benchmark world, then keep `ticks` observed ticks."""
    from benchmarks.harness.npcworld import combat_geometry
    from noahgameframe_tpu.game import build_benchmark_world

    w = build_benchmark_world(n, seed=seed)
    k = w.kernel
    if bucket is not None:
        # an over-full cell: a bucket no boost may widen
        w.combat.bucket = bucket
        w.combat.auto_resize = False
        k.invalidate()
    w.tick()
    snaps = compare.Snapshots(k, "NPC", "CommPropertyValue")
    snaps.warm()
    k.run_device(soak)
    seen = {"hits": 0, "respawns": 0, "dead": 0}
    for _ in range(ticks):
        snaps.around(w.tick)
        seen["hits"] += k.last_counters["combat_hits"]
        seen["respawns"] += k.last_counters["respawns"]
    assert snaps.page_unchanged()
    params = reference.Params(dt=w.config.dt, extent=w.config.extent,
                              aoe_radius=w.config.aoe_radius,
                              respawn_s=w.config.respawn_s)
    seen["geometry"] = combat_geometry(w)
    seen["drops"] = k.counter_totals.get("aoi_victim_overflow_drops", 0)
    host = snaps.to_host()
    last = host.post[max(host.post)]
    hp = last["i32"][:, host.layout.i32_names.index("HP")]
    seen["dead"] = int(np.sum(last["alive"] & (hp <= 0)))
    return host, params, seen


def within_limits(got):
    return all(got[k] <= lim for k, lim in LIMITS.items() if k in got)


@pytest.fixture(scope="module")
def soaked():
    return world_and_ticks(2048, seed=3, soak=170, ticks=36)


def test_reference_follows_the_program_tick_for_tick(soaked):
    host, params, seen = soaked
    assert seen["hits"] > 0 and seen["respawns"] > 0 and seen["dead"] > 0
    got = compare.compare_ticks(host, params, population=2048,
                                geometry=seen["geometry"])
    assert got["ticks_compared"] == 36
    assert got["state_wrong_rows"] == 0 and got["diff_cells_off"] == 0
    assert got["ledger_wrong_rows"] == 0 and got["dropped_off"] == 0
    assert got["pos_err_ulp"] <= 2.0
    assert within_limits(got)


def test_lower_precision_control_fails_the_comparison(soaked):
    """The reference in bfloat16, put in the program's place."""
    host, params, _ = soaked
    got = compare.compare_ticks(host, params, population=2048, control=True)
    assert got["pos_err_ulp"] > 100 * LIMITS["pos_err_ulp"]
    assert not within_limits(got)


def test_positions_cast_down_on_the_programs_side_fail(soaked):
    """The program's own output with its positions rounded to bfloat16."""
    host, params, _ = soaked
    col = host.layout.position_col
    cast = {t: dict(v) for t, v in host.post.items()}
    for leaves in cast.values():
        vec = leaves["vec"].copy()
        vec[:, col, :2] = reference._bf16(vec[:, col, :2])
        leaves["vec"] = vec
    got = compare.compare_ticks(
        compare.HostSnapshots(host.layout, host.pre, cast, host.counters,
                              host.stat_sums), params, population=2048)
    assert not within_limits(got)


def test_over_full_cells_are_set_aside_counted_and_bounded():
    """The program drops entities beyond a cell's bucket; the reference
    drops none.  The comparison works out which rows the stated depths
    drop, holds the program's counters to that count, sets those rows
    aside and compares the rest exactly; this many set aside is beyond
    what a cell may carry, and without the depths the drops read as
    wrong rows."""
    host, params, seen = world_and_ticks(512, seed=5, soak=30, ticks=4,
                                         bucket=2)
    assert seen["geometry"]["bucket"] == 2 and seen["drops"] > 0
    got = compare.compare_ticks(host, params, population=512,
                                geometry=seen["geometry"])
    assert got["dropped_off"] == 0 and got["state_wrong_rows"] == 0
    assert got["ambiguous_rows"] > LIMITS["ambiguous_rows"]
    assert not within_limits(got)
    blind = compare.compare_ticks(host, params, population=512)
    assert blind["state_wrong_rows"] > 0


@pytest.mark.parametrize("n", [512, 4096])
def test_reference_at_other_sizes(n):
    host, params, seen = world_and_ticks(n, seed=n, soak=40, ticks=6)
    got = compare.compare_ticks(host, params, population=n)
    assert seen["hits"] > 0
    assert got["state_wrong_rows"] == 0 and got["pos_err_ulp"] <= 2.0


# ---- the timed path broken underneath a whole (rehearsed) run ---------

def unchanged(kernel, before):
    kernel.state = kernel.state.replace(classes={
        **kernel.state.classes, "NPC": before})


def half_left_out(kernel, before):
    import jax

    now = kernel.state.classes["NPC"]
    half = now.alive.shape[0] // 2
    mixed = jax.tree.map(
        lambda new, old: new.at[half:].set(old[half:]), now, before)
    kernel.state = kernel.state.replace(classes={
        **kernel.state.classes, "NPC": mixed})


def one_answer_altered(kernel, before):
    now = kernel.state.classes["NPC"]
    spec = kernel.store.spec("NPC")
    i32 = now.i32.at[7, spec.slot("HP").col].add(1)
    kernel.state = kernel.state.replace(classes={
        **kernel.state.classes, "NPC": now.replace(i32=i32)})


def rehearsed_run(monkeypatch, fault):
    from noahgameframe_tpu.kernel.kernel import Kernel

    tick = Kernel.tick

    def broken(self):
        import jax
        import jax.numpy as jnp

        before = jax.tree.map(jnp.copy, self.state.classes["NPC"])
        out = tick(self)
        fault(self, before)
        return out

    if fault is not None:
        monkeypatch.setattr(Kernel, "tick", broken)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", "tick-1m", "--seed", "4242424242",
                             "--seconds", "0.5", "--trace", "0", "--rehearse"])
    assert rc == 0
    return json.loads(out.getvalue().splitlines()[-1])


@pytest.mark.parametrize("fault", [None, unchanged, half_left_out,
                                   one_answer_altered],
                         ids=lambda f: getattr(f, "__name__", "sound"))
def test_a_broken_timed_path_comes_out_not_correct(monkeypatch, fault):
    last = rehearsed_run(monkeypatch, fault)
    assert last["correct"] is (fault is None), last["compared"]
