"""Network stack tests: framing, proto2 wire codec, transports (python +
native C++), dispatch modules, consistent-hash pool with reconnect FSM."""

from __future__ import annotations

import time

import pytest

from noahgameframe_tpu.core.chash import ConsistentHash
from noahgameframe_tpu.net import framing, wire
from noahgameframe_tpu.net.defines import MsgID, ServerType
from noahgameframe_tpu.net.module import (
    NORMAL,
    RECONNECT,
    NetClientModule,
    NetServerModule,
)
from noahgameframe_tpu.net.transport import (
    EV_CONNECTED,
    EV_DISCONNECTED,
    EV_MSG,
    PyNetClient,
    PyNetServer,
)


def pump(*endpoints, rounds=50, sleep=0.002):
    """Drive poll() on all endpoints, collecting events per endpoint."""
    out = [[] for _ in endpoints]
    for _ in range(rounds):
        for i, ep in enumerate(endpoints):
            out[i].extend(ep.poll())
        time.sleep(sleep)
    return out


# ---------------------------------------------------------------- framing


def test_frame_roundtrip():
    blob = framing.pack_frame(150, b"hello")
    assert len(blob) == 11
    msg_id, body_len = framing.unpack_head(blob[:6])
    assert (msg_id, body_len) == (150, 5)
    frames = list(framing.iter_frames(blob * 3))
    assert frames == [(150, b"hello")] * 3


def test_frame_incremental_odd_chunks():
    payload = bytes(range(256)) * 10
    blob = framing.pack_frame(1230, payload) + framing.pack_frame(3, b"")
    dec = framing.FrameDecoder()
    got = []
    for i in range(0, len(blob), 7):
        got.extend(dec.feed(blob[i : i + 7]))
    assert got == [(1230, payload), (3, b"")]
    assert dec.pending() == 0


def test_frame_protocol_error():
    dec = framing.FrameDecoder()
    with pytest.raises(framing.ProtocolError):
        dec.feed(b"\x00\x01\x00\x00\x00\x01")  # total_size < header


# ------------------------------------------------------------------- wire


def test_wire_known_bytes():
    # protobuf wire format: field1 varint=1 -> 0x08 0x01, field2 varint=2
    assert wire.Ident(svrid=1, index=2).encode() == b"\x08\x01\x10\x02"


def test_wire_roundtrip_envelope():
    inner = wire.ServerInfoReport(
        server_id=3,
        server_name=b"game1",
        server_ip=b"127.0.0.1",
        server_port=9001,
        server_max_online=5000,
        server_cur_count=17,
        server_state=1,
        server_type=int(ServerType.GAME),
    )
    env = wire.MsgBase(
        player_id=wire.Ident(svrid=7, index=42),
        msg_data=inner.encode(),
        player_client_list=[wire.Ident(svrid=1, index=1), wire.Ident(svrid=2, index=2)],
    )
    base, report = wire.unwrap(env.encode(), wire.ServerInfoReport)
    assert base.player_id == wire.Ident(svrid=7, index=42)
    assert len(base.player_client_list) == 2
    assert report == inner
    assert report.server_name == b"game1"


def test_wire_negative_and_unknown_fields():
    m = wire.PropertyInt(property_name=b"HP", data=-12345)
    decoded = wire.PropertyInt.decode(m.encode())
    assert decoded.data == -12345
    # unknown field (tag 9 varint) must be skipped
    extra = m.encode() + b"\x48\x05"
    assert wire.PropertyInt.decode(extra) == m


def test_wire_repeated_nested():
    row = wire.RecordAddRowStruct(
        row=4,
        record_int_list=[wire.RecordInt(row=4, col=0, data=99)],
        record_string_list=[wire.RecordString(row=4, col=1, data=b"sword")],
    )
    rec = wire.ObjectRecordList(
        player_id=wire.Ident(svrid=1, index=5),
        record_list=[wire.ObjectRecordBase(record_name=b"Bag", row_struct=[row])],
    )
    back = wire.ObjectRecordList.decode(rec.encode())
    assert back.record_list[0].row_struct[0].record_int_list[0].data == 99
    assert back.record_list[0].row_struct[0].record_string_list[0].data == b"sword"


def test_wire_float_fields():
    mv = wire.ReqAckPlayerMove(
        mover=wire.Ident(svrid=1, index=9),
        move_type=1,
        target_pos=[wire.Position(x=1.5, y=-2.25, z=0.0)],
    )
    back = wire.ReqAckPlayerMove.decode(mv.encode())
    assert back.target_pos[0].x == pytest.approx(1.5)
    assert back.target_pos[0].y == pytest.approx(-2.25)


# -------------------------------------------------------------- transports


def _loopback_roundtrip(server, client):
    client.connect()
    sev, cev = pump(server, client, rounds=60)
    assert any(e.kind == EV_CONNECTED for e in sev)
    assert client.connected
    conn_id = next(e.conn_id for e in sev if e.kind == EV_CONNECTED)

    assert client.send_msg(int(MsgID.REQ_LOGIN), b"account-data")
    server.send(conn_id, int(MsgID.ACK_LOGIN), b"ok" * 5000)  # multi-KB frame
    sev, cev = pump(server, client, rounds=60)
    smsgs = [e for e in sev if e.kind == EV_MSG]
    cmsgs = [e for e in cev if e.kind == EV_MSG]
    assert smsgs and smsgs[0].msg_id == int(MsgID.REQ_LOGIN)
    assert smsgs[0].body == b"account-data"
    assert cmsgs and cmsgs[0].body == b"ok" * 5000

    client.disconnect()
    sev, _ = pump(server, client, rounds=60)
    assert any(e.kind == EV_DISCONNECTED for e in sev)


def test_py_transport_loopback():
    server = PyNetServer()
    try:
        _loopback_roundtrip(server, PyNetClient("127.0.0.1", server.port))
    finally:
        server.close()


def test_native_transport_loopback():
    native = pytest.importorskip("noahgameframe_tpu.net.native")
    server = native.NativeNetServer()
    try:
        client = native.NativeNetClient("127.0.0.1", server.port)
        _loopback_roundtrip(server, client)
    finally:
        server.close()


def test_native_py_interop():
    """Native server <-> python client must speak the same bytes."""
    native = pytest.importorskip("noahgameframe_tpu.net.native")
    server = native.NativeNetServer()
    try:
        _loopback_roundtrip(server, PyNetClient("127.0.0.1", server.port))
    finally:
        server.close()


# ----------------------------------------------------------------- modules


def test_server_client_modules_envelope():
    server = NetServerModule(backend="py")
    got = []
    server.on(int(MsgID.STS_SERVER_REPORT), lambda c, m, b: got.append((c, m, b)))

    pool = NetClientModule(backend="py", keepalive_seconds=1e9)
    pool.add_server(11, int(ServerType.MASTER), "127.0.0.1", server.port)
    deadline = time.monotonic() + 3.0
    while time.monotonic() < deadline and pool.servers[11].state != NORMAL:
        pool.execute()
        server.execute()
        time.sleep(0.002)
    assert pool.servers[11].state == NORMAL

    report = wire.ServerInfoReport(server_id=5, server_name=b"g", server_ip=b"x",
                                   server_port=1, server_max_online=10,
                                   server_cur_count=2, server_state=1,
                                   server_type=int(ServerType.GAME))
    assert pool.send_pb_by_server_id(11, int(MsgID.STS_SERVER_REPORT), report)
    deadline = time.monotonic() + 3.0
    while time.monotonic() < deadline and not got:
        pool.execute()
        server.execute()
        time.sleep(0.002)
    assert got
    _, pb = wire.unwrap(got[0][2], wire.ServerInfoReport)
    assert pb.server_id == 5 and pb.server_type == int(ServerType.GAME)
    pool.shut()
    server.shut()


def test_server_inbound_budget_defers_in_order():
    """With a budget, one execute() round stops dispatching once it has
    spent it: a burst of slow handlers is served over several rounds, in
    arrival order, and the dispatch tap (the journal's seam) sees each
    event only in the round that dispatches it."""
    from noahgameframe_tpu.net.transport import EV_MSG, NetEvent

    server = NetServerModule(backend="py")
    try:
        handled, tapped = [], []
        server.dispatch.tap = lambda ev: tapped.append(ev.body)

        def slow(_conn, _mid, body):
            time.sleep(0.02)
            handled.append(body)

        server.on(7, slow)
        burst = [NetEvent(EV_MSG, 1, 7, bytes([i])) for i in range(6)]
        arrivals = [list(burst)]
        server.transport.poll = lambda: arrivals.pop() if arrivals else []

        server.execute(budget_seconds=0.03)  # 2 x 20 ms reaches the budget
        assert handled == tapped == [b"\x00", b"\x01"]
        assert server.backlog_max == 4
        arrivals.append([NetEvent(EV_MSG, 1, 7, b"new")])
        server.execute(budget_seconds=0.0)  # one event is always served
        assert handled == tapped == [b"\x00", b"\x01", b"\x02"]
        server.execute()  # no budget: everything, backlog before the new
        assert handled == tapped == [bytes([i]) for i in range(6)] + [b"new"]
        assert server.backlog_max == 4
    finally:
        server.shut()


def test_client_pool_reconnect_fsm():
    server = NetServerModule(backend="py")
    port = server.port
    pool = NetClientModule(backend="py", reconnect_seconds=0.05,
                           keepalive_seconds=1e9)
    pool.add_server(1, int(ServerType.WORLD), "127.0.0.1", port)

    def spin(cond, extra=(), timeout=3.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline and not cond():
            pool.execute()
            for e in extra:
                e.execute()
            time.sleep(0.002)
        assert cond()

    spin(lambda: pool.servers[1].state == NORMAL, extra=[server])
    server.shut()  # kill the server -> link must fall to RECONNECT
    spin(lambda: pool.servers[1].state in (RECONNECT,) or not pool.servers[1].client.connected)
    # bring a new server up on the same port; FSM must re-establish
    server2 = NetServerModule(host="127.0.0.1", port=port, backend="py")
    spin(lambda: pool.servers[1].state == NORMAL, extra=[server2])
    pool.shut()
    server2.shut()


def test_keepalive_hook_fires():
    pool = NetClientModule(backend="py", keepalive_seconds=0.0)
    fired = []
    pool.on_keepalive(lambda: fired.append(1))
    pool.execute(now=100.0)
    pool.execute(now=200.0)
    assert len(fired) == 2


# -------------------------------------------------------- consistent hash


def test_consistent_hash_routing_stability():
    ring = ConsistentHash(virtual_nodes=100)
    for sid in (1, 2, 3, 4):
        ring.add(str(sid), sid)
    keys = [f"player-{i}" for i in range(2000)]
    before = {k: ring.get(k) for k in keys}
    counts = {sid: sum(1 for v in before.values() if v == sid) for sid in (1, 2, 3, 4)}
    assert all(c > 100 for c in counts.values()), counts  # roughly balanced
    ring.remove("3")
    after = {k: ring.get(k) for k in keys}
    moved = sum(1 for k in keys if before[k] != after[k] and before[k] != 3)
    assert all(after[k] != 3 for k in keys)
    # only keys that lived on the removed node may move
    assert moved == 0


_NATIVE_REBUILD_PROBE = """
import sys
from pathlib import Path
from noahgameframe_tpu.net import native

first = native.lib_path()
native._load()
assert first.exists()
src = Path(sys.argv[1]) / "nfnet.cc"
src.write_text(src.read_text() + "\\n// edited\\n")
native._lib = None  # what a fresh process would see
second = native.lib_path()
assert second != first
native._load()
print("LIBS", sorted(p.name for p in second.parent.glob("*.so")) == [second.name])
"""


def test_native_library_is_rebuilt_when_its_sources_change(tmp_path):
    """The library's name carries a hash of nfnet.cc and the Makefile: an
    edited source is built under a new name, and the binary of the old
    source is removed, never loaded."""
    import os
    import shutil
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    if shutil.which("g++") is None or shutil.which("make") is None:
        pytest.skip("no C++ toolchain here")
    for name in ("nfnet.cc", "Makefile"):
        shutil.copy(repo / "native" / name, tmp_path / name)
    r = subprocess.run(
        [sys.executable, "-c", _NATIVE_REBUILD_PROBE, str(tmp_path)],
        capture_output=True, text=True, timeout=300, cwd=repo,
        env=dict(os.environ, NF_NATIVE_DIR=str(tmp_path)))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "LIBS True" in r.stdout
