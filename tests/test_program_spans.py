"""The program's span seam (telemetry/tracing.py): one vocabulary
(`nf.*`), on the profiler's clock.

One profiler session is opened around a tiny world's `GameWorld.tick()`,
a bare `StageClock` frame and a few served frames of a three-session
`LocalCluster` (every session's FRAME_TRACE sampled, the game role
journaling).  Every name of the vocabulary in docs/OBSERVABILITY.md must
then be in the trace's host plane, nested under its stated parent, and
the wire spans must carry the frame's tick; the device scopes must be in
the tick program's op names.  With no session open the seam records
nothing, StageClock's waterfall still sums to the frame's wall time
exactly, and the journaled run replays bit-identically.
"""

import glob
import os

import jax
import pytest

from noahgameframe_tpu.telemetry import tracing
from noahgameframe_tpu.telemetry.pipeline import StageClock
from noahgameframe_tpu.telemetry.tracing import SpanTracer

SESSIONS = 3

# host span -> the span it nests under (None: opened at the top)
HOST = {
    "nf.role.master": None, "nf.role.login": None, "nf.role.world": None,
    "nf.role.proxy": None, "nf.role.game": None,
    "nf.client.pump": None,
    "nf.frame": "nf.role.game",
    "nf.stage.tick": "nf.frame", "nf.stage.migrate": "nf.frame",
    "nf.stage.harvest": "nf.frame", "nf.stage.interest": "nf.frame",
    "nf.stage.encode": "nf.frame", "nf.stage.assemble": "nf.frame",
    "nf.stage.send": "nf.frame", "nf.stage.reshard": "nf.frame",
    "nf.game.flush": "nf.frame",
    "nf.tick.modules": "nf.stage.tick",
    "nf.module.CombatModule": "nf.tick.modules",
    "nf.module.KernelModule": "nf.tick.modules",
    "nf.kernel.dispatch": "nf.stage.tick",
    "nf.kernel.fetch": "nf.stage.tick",
    "nf.kernel.fanout": "nf.stage.tick",
    "nf.fanout.events": "nf.kernel.fanout",
    "nf.fanout.deaths": "nf.kernel.fanout",
    "nf.fanout.props": "nf.kernel.fanout",
    "nf.fanout.props.fetch": "nf.fanout.props",
    "nf.fanout.props.unpack": "nf.fanout.props",
    "nf.fanout.records": "nf.kernel.fanout",
    "nf.trace.emit": "nf.role.game",
    "nf.trace.relay": "nf.role.proxy",
    "nf.trace.recv": "nf.client.pump",
}
# device scope -> the scope it nests under
DEVICE = {
    "nf.aoe.rank": "nf.phase.CombatModule.aoe",
    "nf.aoe.table": "nf.phase.CombatModule.aoe",
    "nf.aoe.fold": "nf.phase.CombatModule.aoe",
    "nf.aoe.pull": "nf.phase.CombatModule.aoe",
    "nf.digest": None, "nf.summary": None,
}
CARRY_TICK = ("nf.frame", "nf.trace.emit", "nf.trace.relay", "nf.trace.recv")


def _admit(cluster, clients):
    """Every client through the reference handshake into the game."""
    def pump(reached):
        def extra():
            for c in clients:
                c.execute()
        assert cluster.pump_until(lambda: all(reached(c) for c in clients),
                                  extra=extra, timeout=120)
        return extra

    login_port = cluster.login.config.port
    game_id = cluster.game.config.server_id
    for act, reached in (
        (lambda c, i: c.connect("127.0.0.1", login_port),
         lambda c: c.connected),
        (lambda c, i: c.login(), lambda c: c.logged_in),
        (lambda c, i: c.request_world_list(), lambda c: c.worlds),
        (lambda c, i: c.connect_world(c.worlds[0].server_id),
         lambda c: c.world_grant is not None),
        (lambda c, i: c.connect_proxy(), lambda c: c.connected),
        (lambda c, i: c.verify_key(), lambda c: c.key_verified),
        (lambda c, i: c.select_server(game_id),
         lambda c: c.server_selected),
        (lambda c, i: c.create_role(f"Span{i}"), lambda c: c.roles),
        (lambda c, i: c.enter_game(f"Span{i}"), lambda c: c.entered),
    ):
        for i, c in enumerate(clients):
            act(c, i)
        extra = pump(reached)
    for i, c in enumerate(clients):
        c.move_to(10.0 + i, 10.0 + i)
    return extra


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Everything the cases read, from one profiler session."""
    from jax.profiler import ProfileData

    from noahgameframe_tpu.client import GameClient
    from noahgameframe_tpu.game import build_benchmark_world
    from noahgameframe_tpu.net.roles.cluster import LocalCluster
    from noahgameframe_tpu.replay import replay_journal

    tmp = tmp_path_factory.mktemp("spans")
    jdir = tmp / "journal"
    was = os.environ.get("NF_TRACE_SAMPLE")
    os.environ["NF_TRACE_SAMPLE"] = "1"  # read when the game role is made
    def recipe():
        return build_benchmark_world(256, seed=5, player_capacity=8)

    try:
        cluster = LocalCluster(
            game_world=recipe(),
            game_kwargs={"interest_radius": 8.0, "journal_dir": jdir})
    finally:
        if was is None:
            os.environ.pop("NF_TRACE_SAMPLE", None)
        else:
            os.environ["NF_TRACE_SAMPLE"] = was
    game = cluster.game
    clients = [GameClient(f"span{i}") for i in range(SESSIONS)]
    bare = build_benchmark_world(64, seed=6)
    bare.tick()  # compiled before the session opens
    out = {}
    try:
        cluster.start(timeout=60)
        extra = _admit(cluster, clients)

        def serve(frames):
            f0 = game.stage_clock.frames
            assert cluster.pump_until(
                lambda: game.stage_clock.frames - f0 >= frames,
                extra=extra, timeout=120)

        serve(4)  # every shape warm
        waterfalls = []
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(tmp / "trace"), profiler_options=opts)
        try:
            bare.tick()
            sc = StageClock()
            sc.frame_begin(7)
            for name in StageClock.STAGES[:-1]:
                with sc.stage(name):
                    pass
            sc.frame_end()
            waterfalls.append((dict(sc.last), sc.last_wall_ns))
            for _ in range(3):
                serve(1)
                waterfalls.append((dict(game.stage_clock.last),
                                   game.stage_clock.last_wall_ns))
        finally:
            jax.profiler.stop_trace()
        out["waterfalls"] = waterfalls
        out["client_traces"] = [list(c.traces) for c in clients]
        k = game.kernel
        out["hlo"] = jax.jit(k._trace_step).lower(k.state).as_text(
            debug_info=True)
    finally:
        for c in clients:
            c.close()
        cluster.shut()
    path = sorted(glob.glob(str(
        tmp / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb")))[-1]
    threads = []  # per host thread: [(start, end, name, args)]
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("nf.")]
            if evs:
                threads.append(evs)
    out["threads"] = threads
    out["replay"] = replay_journal(jdir, world_factory=recipe)
    return out


def _instances(traced, name):
    return [(ev, evs) for evs in traced["threads"] for ev in evs
            if ev[2] == name]


@pytest.mark.parametrize("name", sorted(HOST))
def test_host_span_is_in_the_trace_under_its_parent(traced, name):
    found = _instances(traced, name)
    assert found, f"{name} opened no span in the profiler's host plane"
    parent = HOST[name]
    if parent is not None:
        assert any(
            any(p[2] == parent and p[0] <= ev[0] and ev[1] <= p[1]
                for p in evs)
            for ev, evs in found), f"no {name} nests under {parent}"
    if name in CARRY_TICK:
        assert all("tick" in ev[3] for ev, _ in found)
    if name.startswith("nf.trace."):
        assert all("seq" in ev[3] for ev, _ in found)


def test_wire_spans_of_one_sidecar_share_tick_and_seq(traced):
    keys = {
        name: {(ev[3]["tick"], ev[3]["seq"]): ev
               for ev, _ in _instances(traced, name)}
        for name in ("nf.trace.emit", "nf.trace.relay", "nf.trace.recv")}
    joined = (set(keys["nf.trace.emit"]) & set(keys["nf.trace.relay"])
              & set(keys["nf.trace.recv"]))
    assert len(joined) >= SESSIONS  # at least one traced frame, whole
    for k in joined:
        emit, relay, recv = (keys[n][k] for n in (
            "nf.trace.emit", "nf.trace.relay", "nf.trace.recv"))
        assert emit[0] <= relay[0] <= recv[0]
    # the frame's own tick is the one its sidecars carry
    frames = {ev[3]["tick"] for ev, _ in _instances(traced, "nf.frame")}
    assert {t for t, _ in joined} <= frames


@pytest.mark.parametrize("scope", sorted(DEVICE))
def test_device_scope_is_in_the_tick_programs_op_names(traced, scope):
    hlo = traced["hlo"]
    assert scope in hlo
    parent = DEVICE[scope]
    if parent is not None:
        assert f"{parent}/{scope}" in hlo


def test_the_sdk_keeps_all_four_stamps(traced):
    for log in traced["client_traces"]:
        assert log
        for t in log:
            assert 0 < t["t_encode_ns"] <= t["proxy_in_ns"] \
                <= t["proxy_out_ns"] <= t["client_recv_ns"]
            assert t["proxy_relay_ms"] == pytest.approx(
                (t["proxy_out_ns"] - t["proxy_in_ns"]) / 1e6)


def test_waterfall_sums_to_the_frames_wall_exactly(traced):
    assert len(traced["waterfalls"]) == 4
    for last, wall in traced["waterfalls"]:  # a profiler session open
        assert sum(last.values()) == wall
    sc = StageClock()  # and none
    sc.frame_begin(1)
    with sc.stage("encode"):
        with sc.stage("send"):
            pass
    sc.frame_end()
    assert sum(sc.last.values()) == sc.last_wall_ns
    assert set(sc.last) == {"encode", "send", "other"}


def test_journal_replays_bit_identically_under_a_profiler_session(traced):
    rep = traced["replay"]
    assert rep.ticks_replayed > 0
    assert rep.ok


def test_no_session_no_record():
    """The open profiler session is the only switch: without one the
    seam hands back one shared no-op, and the ring stays empty."""
    assert not jax.profiler.TraceAnnotation.is_enabled()
    a, b = tracing.span("kernel.fetch"), tracing.span("frame", tick=3)
    assert a is b
    with a:
        pass
    ring = SpanTracer(enabled=False)
    with ring.span("kernel.fetch"):
        pass
    assert len(ring) == 0


def test_the_ring_records_the_same_names_without_a_session():
    ring = SpanTracer(enabled=True)
    with ring.span("stage.tick", tick=4):
        with ring.span("kernel.fetch"):
            pass
    names = [e[0] for e in ring.events()]
    assert names == ["nf.kernel.fetch", "nf.stage.tick"]
    assert ring.events()[1][4] == {"tick": 4}
