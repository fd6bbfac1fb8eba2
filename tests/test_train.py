"""Observed tick trains (ISSUE 20): K ticks per dispatch, zero lost history.

``NF_TICK_TRAIN=K`` compiles a ``lax.scan`` over K kernel ticks into ONE
dispatch, scan-stacking every host-consumed per-tick lane ``[K, ...]``
(the ``TRAIN_LANE_SPEC`` contract in ``kernel/kernel.py``).  The spine:

1. digest parity — ``Kernel.train`` over 120 ticks is bit-identical,
   tick by tick, to a single-ticking control for K ∈ {1, 4, 8} and a
   ragged K=7 (120 = 17·7 + 1: the tail rides the plain step);
2. the sharded and many-worlds engines reproduce the same digests
   through their own train dispatches;
3. per-lane host fan-out — an in-trace death at a chosen mid-train tick
   is attributed to EXACTLY that tick's lane (the post-train alive scan
   would pin it to the train's last tick), fires its destroy event once,
   and frees the row;
4. a journaled ``GameRole`` run with ``tick_train=4`` writes one mark
   per stacked frame from the in-lane tick/digest stamps, declares the
   staleness contract in the run meta, and replays digest-clean with
   the knob OFF (one real tick per mark);
5. soak hygiene — train dispatch accounting is exact (⌈n/K⌉), a
   mid-soak ``invalidate()`` is a sanctioned generation bump
   (``unexplained_since`` stays empty), and ``configure_train``
   re-pins K without an unexplained retrace;
6. the trace-time ``_assert_train_lanes`` gate and the StageClock
   per-tick amortization hold up under direct prodding.

``RoomBatch.run``'s refreshed ``last_counters`` regression rides along
(the fused loop used to return the pre-run snapshot).

Tier-1 runs the combined kernel contract test, death attribution, the
rooms run() regression, the role journal/election pair and the
plumbing checks (~80 s); the per-K parity matrix, the invalidate soak
and the sharded/rooms engine parities are ``slow`` (each is its own
world build + scan compile against a shared 1500 s tier-1 wall).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from noahgameframe_tpu.core.store import with_class
from noahgameframe_tpu.game import GameWorld
from noahgameframe_tpu.game.world import WorldConfig
from noahgameframe_tpu.kernel.kernel import (
    TRAIN_LANE_SPEC,
    ObjectEvent,
    _assert_train_lanes,
)
from noahgameframe_tpu.kernel.module import Phase

TICKS = 120


def _recipe(seed=7):
    w = GameWorld(WorldConfig(npc_capacity=32, player_capacity=8,
                              extent=64.0, seed=seed, middleware=False,
                              combat=True, movement=True, regen=True,
                              verlet_skin=2.0))
    w.start()
    w.scene.create_scene(1, width=64.0)
    w.seed_npcs(16, rng=np.random.default_rng(seed + 100))
    w.kernel.enable_digest()
    return w


@pytest.fixture(scope="module")
def control_digests():
    """120 per-tick digests from a single-ticking control world."""
    w = _recipe()
    return [w.kernel.tick().counters["state_digest"] for _ in range(TICKS)]


# ------------------------------------------------------- kernel parity
#
# Tier-1 runs ONE kernel world through the whole contract (parity,
# reconfigure, ragged tail, dispatch accounting, CostBook hygiene) —
# the per-K matrix and the invalidate soak are `slow`: each K is its
# own world build + scan compile (~15 s apiece) and the tier-1 wall
# budget is shared with the rest of the suite.

def test_kernel_train_parity_reconfigure_and_ragged(control_digests):
    """120 ticks bit-identical to the control through a mid-run K
    change (4 -> 7): 10 whole K=4 trains, then 11 K=7 trains + 3
    ragged singles.  The reconfigure drops only the train executable
    (a NEW costbook entry, nothing unexplained), and the in-lane tick
    stamps are the per-tick identity the journal marks use."""
    w = _recipe()
    kern = w.kernel
    kern.configure_train(4)
    outs = kern.train(40)
    mark = kern.costbook.mark()
    kern.configure_train(7)
    outs += kern.train(80)
    assert len(outs) == TICKS
    assert [o.counters["state_digest"] for o in outs] == control_digests
    assert [o.counters["tick"] for o in outs] == list(range(1, TICKS + 1))
    assert kern.tick_count == TICKS
    assert kern.train_dispatches == 40 // 4 + 80 // 7
    assert kern.train_ticks == 40 + 77
    assert kern.train_fetch_bytes > 0
    assert kern.costbook.unexplained_since(mark) == []


@pytest.mark.slow
@pytest.mark.parametrize("k", [1, 4, 8, 7])
def test_kernel_train_digest_parity(k, control_digests):
    """train(120) is bit-identical tick-by-tick to the control for
    whole trains (K | 120) and ragged tails (K=7: 17 trains + 1 step)."""
    w = _recipe()
    kern = w.kernel
    kern.configure_train(k)
    outs = kern.train(TICKS)
    assert len(outs) == TICKS
    assert [o.counters["state_digest"] for o in outs] == control_digests
    # in-lane tick stamps are the per-tick identity the journal marks use
    assert [o.counters["tick"] for o in outs] == list(range(1, TICKS + 1))
    assert kern.tick_count == TICKS
    assert kern.train_dispatches == TICKS // k
    assert kern.train_ticks == (TICKS // k) * k
    if k > 1:
        assert kern.train_fetch_bytes > 0


@pytest.mark.slow
def test_train_soak_mid_invalidate_unexplained_clean(control_digests):
    """An invalidate() mid-soak retraces the train under a sanctioned
    generation bump: unexplained_since(mark) stays empty and parity
    holds through the retrace."""
    w = _recipe()
    kern = w.kernel
    kern.configure_train(4)
    digs = [o.counters["state_digest"] for o in kern.train(8)]  # warm
    mark = kern.costbook.mark()
    digs += [o.counters["state_digest"] for o in kern.train(52)]
    kern.invalidate()
    digs += [o.counters["state_digest"] for o in kern.train(60)]
    assert digs == control_digests
    assert kern.costbook.unexplained_since(mark) == []
    assert kern.train_dispatches == TICKS // 4


# -------------------------------------------------- death attribution

def _kill_phase(row, at_tick):
    """In-trace device kill: clear NPC `row`'s alive bit so the death
    lands in output tick `at_tick` (ctx.tick is pre-increment)."""
    def fn(state, ctx):
        cs = state.classes["NPC"]
        hit = ctx.tick == (at_tick - 1)
        alive = cs.alive.at[row].set(
            jnp.where(hit, False, cs.alive[row]))
        return with_class(state, "NPC", cs.replace(alive=alive))
    return fn


def test_train_death_attributed_to_exact_lane():
    """A device kill at tick 6 (lane 1 of the second K=4 train) shows in
    exactly that lane's died mask, frees the row once, and fires the
    destroy hook with the tick-6 guid — the post-train alive scan
    could only have blamed tick 8."""
    wt = _recipe()
    kt = wt.kernel
    row = 0
    guid_t = kt.store._hosts["NPC"].row_guid[row]
    kt.set_phases(list(kt._composed)
                  + [Phase("test.kill", _kill_phase(row, 6), order=999)])

    live_before = kt.store.live_count("NPC")
    kt.configure_train(4)
    destroyed = []
    kt.register_class_event(
        lambda g, cn, ev: destroyed.append((g, int(ev))), "NPC")
    outs_t = kt.train(8)
    died_lanes = [i for i, o in enumerate(outs_t)
                  if np.asarray(o.died["NPC"]).any()]
    assert died_lanes == [5]  # tick 6, not the train boundary at tick 8
    assert np.flatnonzero(np.asarray(outs_t[5].died["NPC"])).tolist() == [row]
    assert guid_t not in kt.store.guid_map
    assert [d for d in destroyed if d[1] == int(ObjectEvent.DESTROY)] \
        == [(guid_t, int(ObjectEvent.DESTROY))]
    assert kt.store.live_count("NPC") == live_before - 1


# ------------------------------------------------------ other engines
#
# The sharded/rooms train parities are `slow` (each is ~20-40 s of
# virtual-device compiles): tier-1 keeps the rooms run() regression
# below, and the committed bench artifact (`bench_runs/
# r13_train_cpu.json`) re-proves rooms train parity over 120 ticks at
# 256 rooms on every regeneration.

@pytest.mark.slow
def test_sharded_train_digest_parity(control_digests):
    from noahgameframe_tpu.parallel.shard import ShardedKernel

    w = _recipe()
    sk = ShardedKernel(w.kernel, n_devices=8)
    sk.place()
    sk.configure_train(4)
    outs = sk.train(30)  # 7 trains + 2 ragged singles
    assert [o.counters["state_digest"] for o in outs] == control_digests[:30]
    assert w.kernel.train_dispatches == 7


@pytest.mark.slow
def test_rooms_train_digest_parity():
    from noahgameframe_tpu.parallel.mesh import ROOMS_AXIS, make_mesh
    from noahgameframe_tpu.parallel.rooms import RoomBatch, RoomBinPacker

    mesh = make_mesh(8, axis=ROOMS_AXIS)
    w = _recipe()
    w.kernel._ensure_aux()

    def build():
        batch = RoomBatch(w.kernel, 16, mesh=mesh)
        packer = RoomBinPacker(batch.capacity, n_blocks=8)
        for i in range(16):
            batch.admit(packer.alloc(), w.kernel.state.replace(
                rng=jax.random.PRNGKey(50 + i)))
        return batch

    b_train, b_ctl = build(), build()
    b_train.configure_train(4)
    lanes = b_train.train(10)  # [10, R, L]: 2 trains + 2 ragged singles
    assert lanes.shape[0] == 10
    assert b_train.train_dispatches == 2
    assert b_train.tick_count == 10
    ctl = [b_ctl.tick() for _ in range(10)]
    for i in range(10):
        c = b_train.kernel.decode_counters(lanes[i])
        assert np.array_equal(c["state_digest"], ctl[i]["state_digest"]), i
        assert np.array_equal(c["tick"], ctl[i]["tick"]), i


def test_rooms_run_refreshes_last_counters():
    """Regression (this PR): the fused run() used to leave last_counters
    at the pre-run snapshot; it must return the FINAL tick's decoded
    row, and run(0) is a no-op.  Single batch: a stale snapshot would
    carry tick stamp 1 (and the tick-1 digests) after run(5)."""
    from noahgameframe_tpu.parallel.mesh import ROOMS_AXIS, make_mesh
    from noahgameframe_tpu.parallel.rooms import RoomBatch, RoomBinPacker

    mesh = make_mesh(8, axis=ROOMS_AXIS)
    w = _recipe()
    w.kernel._ensure_aux()
    batch = RoomBatch(w.kernel, 16, mesh=mesh)
    packer = RoomBinPacker(batch.capacity, n_blocks=8)
    for i in range(16):
        batch.admit(packer.alloc(), w.kernel.state.replace(
            rng=jax.random.PRNGKey(50 + i)))

    c1 = batch.tick()
    assert np.asarray(c1["tick"]).tolist() == [1] * 16
    got = batch.run(5)
    assert np.asarray(got["tick"]).tolist() == [6] * 16
    assert not np.array_equal(got["state_digest"], c1["state_digest"])
    before = batch.tick_count
    again = batch.run(0)
    assert batch.tick_count == before
    assert np.array_equal(again["state_digest"], got["state_digest"])
    assert np.array_equal(again["tick"], got["tick"])


# --------------------------------------------- role journal + replay

def test_role_train_journal_replays_clean(tmp_path):
    """A serving role with tick_train=4 journals one mark PER stacked
    frame (from in-lane tick/digest stamps), declares the K-1 staleness
    contract in the run meta, moves the train metrics, and an offline
    replay with the knob OFF is digest-clean."""
    from noahgameframe_tpu.net.defines import ServerType
    from noahgameframe_tpu.net.roles.base import RoleConfig
    from noahgameframe_tpu.net.roles.game import GameRole
    from noahgameframe_tpu.replay import (
        make_offline_role,
        read_ticks,
        replay_journal,
    )
    from noahgameframe_tpu.replay.journal import JournalReader

    def build_world(seed=11):
        w = GameWorld(WorldConfig(npc_capacity=32, player_capacity=8,
                                  extent=64.0, seed=seed, middleware=False,
                                  combat=True, movement=True, regen=True,
                                  verlet_skin=2.0)).start()
        if 1 not in w.scene.scenes:
            w.scene.create_scene(1, width=64.0)
        w.seed_npcs(16, rng=np.random.default_rng(seed + 100))
        return w

    jdir = tmp_path / "journal"
    role = GameRole(
        RoleConfig(6, int(ServerType.GAME), "TrainTest", "127.0.0.1", 0,
                   targets=[]),
        backend="auto", world=build_world(), tick_train=4,
        journal_dir=jdir,
    )
    role.server.send_raw = lambda _conn, _msg, _body: True
    assert role.tick_train == 4
    role.kernel.enable_digest()
    dt = role.game_world.config.dt
    now = 1000.0
    for _ in range(6):  # 6 train frames = 24 journaled ticks
        now += dt + 1e-6
        role.execute(now=now)
    assert role.kernel.tick_count == 24
    reg = role.telemetry.registry
    assert reg.value("nf_train_dispatches_total") == 6
    assert reg.value("nf_train_ticks_total") == 24
    assert reg.value("nf_train_fetch_bytes_total") > 0
    role.shut()

    assert len(read_ticks(jdir)) == 24
    meta = JournalReader(jdir).meta
    assert meta["tick_train"] == 4
    assert meta["serve_staleness_ticks"] == 3

    role2 = make_offline_role(world=build_world())
    role2.kernel.enable_digest()
    try:
        rep = replay_journal(jdir, role=role2)
        assert rep.ticks_replayed == 24
        assert rep.ok
        assert role2.telemetry.registry.value(
            "nf_replay_divergences_total") == 0
    finally:
        role2.shut()


def test_role_train_election_yields_to_overlap():
    """tick_train needs the whole frame budget in one dispatch;
    serve_overlap needs a host window between ticks.  Overlap wins."""
    from noahgameframe_tpu.net.defines import ServerType
    from noahgameframe_tpu.net.roles.base import RoleConfig
    from noahgameframe_tpu.net.roles.game import GameRole

    w = GameWorld(WorldConfig(npc_capacity=32, player_capacity=8,
                              extent=64.0, seed=3, middleware=False,
                              combat=False, movement=False,
                              regen=True)).start()
    role = GameRole(
        RoleConfig(6, int(ServerType.GAME), "Overlap", "127.0.0.1", 0,
                   targets=[]),
        backend="auto", world=w, interest_radius=8.0,
        serve_batch=True, serve_overlap=True, tick_train=8,
    )
    try:
        assert role.tick_train == 0
        assert role.serve_overlap
    finally:
        role.shut()


@pytest.mark.parametrize("mode", ["plain", "train", "overlap"])
def test_role_mirrors_fanout_mask_counters(mode):
    """Each of the role's three ways to tick carries the kernel's
    property fan-out accounting into the registry."""
    from noahgameframe_tpu.net.defines import ServerType
    from noahgameframe_tpu.net.roles.base import RoleConfig
    from noahgameframe_tpu.net.roles.game import GameRole

    w = GameWorld(WorldConfig(npc_capacity=32, player_capacity=8,
                              extent=64.0, seed=11, middleware=False,
                              combat=True, movement=True, regen=True,
                              verlet_skin=2.0)).start()
    w.scene.create_scene(1, width=64.0)
    w.seed_npcs(16, rng=np.random.default_rng(111))
    kwargs = {"plain": {}, "train": {"tick_train": 4},
              "overlap": {"interest_radius": 8.0, "serve_batch": True,
                          "serve_overlap": True}}[mode]
    role = GameRole(
        RoleConfig(6, int(ServerType.GAME), "Fanout", "127.0.0.1", 0,
                   targets=[]),
        backend="auto", world=w, **kwargs)
    role.server.send_raw = lambda _conn, _msg, _body: True
    try:
        now = 1000.0
        for _ in range(4):
            now += w.config.dt + 1e-6
            role.execute(now=now)
        reg = role.telemetry.registry
        k = role.kernel
        assert k.fanout_mask_fetches > 0  # NPCs walk every tick
        assert reg.value("nf_fanout_mask_fetches_total") == k.fanout_mask_fetches
        assert reg.value("nf_fanout_mask_bytes_total") == k.fanout_mask_bytes
        assert k.fanout_mask_columns >= k.fanout_mask_fetches
        assert reg.value("nf_fanout_mask_columns_total") == \
            k.fanout_mask_columns
    finally:
        role.shut()


# ------------------------------------------------- contract plumbing

def test_assert_train_lanes_gates_both_directions():
    ok = {name: None for name in TRAIN_LANE_SPEC}
    _assert_train_lanes(ok)  # exact coverage: quiet
    with pytest.raises(AssertionError, match="unlisted.*aggro"):
        _assert_train_lanes({**ok, "aggro": None})
    short = dict(ok)
    del short["died"]
    with pytest.raises(AssertionError, match="stale.*died"):
        _assert_train_lanes(short)


def test_stage_clock_train_scale_amortizes_histogram_only():
    from noahgameframe_tpu.telemetry.pipeline import StageClock
    from noahgameframe_tpu.telemetry.registry import MetricsRegistry

    reg = MetricsRegistry()
    sc = StageClock(registry=reg)
    sc.frame_begin(0)
    sc.add_ns("tick", 8_000_000)  # one 8ms span covering a K=8 train
    sc.set_scale("tick", 8)
    sc.frame_end()
    h = sc._hists["tick"]
    assert h.count == 1
    assert h.sum == pytest.approx(0.001)  # banked PER-TICK: 8ms / 8
    assert sc.last["tick"] == 8_000_000  # waterfall stays exact
    # the divisor is per-frame state: the next plain frame banks 1:1
    sc.frame_begin(1)
    sc.add_ns("tick", 2_000_000)
    sc.frame_end()
    assert h.sum == pytest.approx(0.003)
