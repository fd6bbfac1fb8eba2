"""Telemetry subsystem: registry semantics, /metrics end-to-end over the
HttpServer pump, the on-device counter bank vs a host-side recount, and
Chrome trace-event export."""

from __future__ import annotations

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from noahgameframe_tpu.telemetry import (
    Counter,
    Histogram,
    MetricsRegistry,
    SpanTracer,
    escape_label_value,
)
from noahgameframe_tpu.telemetry.registry import CONTENT_TYPE


# ---------------------------------------------------------------- registry
def test_counter_monotonic():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "test counter")
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value() == 3.5


def test_counter_labels_independent():
    c = Counter("msgs_total", "x", ("op",))
    c.inc(op="1")
    c.inc(3, op="2")
    assert c.value(op="1") == 1
    assert c.value(op="2") == 3
    # unknown labelname rejected
    with pytest.raises(ValueError):
        c.inc(bogus="x")


def test_label_escaping():
    assert escape_label_value('a\\b\n"c"') == 'a\\\\b\\n\\"c\\"'
    reg = MetricsRegistry()
    g = reg.gauge("t_gauge", "with tricky label", ("k",))
    g.set(1, k='v"\n\\')
    text = reg.exposition()
    assert 't_gauge{k="v\\"\\n\\\\"} 1' in text


def test_histogram_buckets_cumulative():
    h = Histogram("lat_seconds", "x", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.5, 10.0):
        h.observe(v)
    by_le = {}
    total = None
    s = None
    for suffix, labels, value in h.samples():
        if suffix == "_bucket":
            by_le[labels["le"]] = value
        elif suffix == "_count":
            total = value
        elif suffix == "_sum":
            s = value
    assert by_le == {"1": 1, "2": 2, "5": 2, "+Inf": 3}
    assert total == 3
    assert s == pytest.approx(12.0)


def test_histogram_percentile_exact():
    h = Histogram("p_seconds", "x", window=16)
    for v in range(1, 11):  # 1..10
        h.observe(float(v))
    assert h.percentile(50) == pytest.approx(5.5)
    assert h.percentile(100) == pytest.approx(10.0)
    assert h.percentile(0) == pytest.approx(1.0)


def test_registry_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("dup_total", "x")
    with pytest.raises(Exception):
        reg.gauge("dup_total", "x")


def test_callback_metric_survives_exception():
    reg = MetricsRegistry()

    def boom():
        raise RuntimeError("scrape must not die")

    reg.register_callback("t_cb", boom, kind="gauge", help="x")
    text = reg.exposition()  # no raise
    assert "# TYPE t_cb gauge" in text


# ------------------------------------------------------- /metrics over http
def test_metrics_http_end_to_end():
    from noahgameframe_tpu.net.http import HttpServer

    reg = MetricsRegistry()
    reg.counter("e2e_total", "end to end").inc(7)
    srv = HttpServer("127.0.0.1", 0)
    srv.route("/metrics", reg.handler)
    stop = threading.Event()

    def pump():
        while not stop.is_set():
            srv.execute()
            time.sleep(0.002)

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=5
        ) as r:
            body = r.read().decode()
            ctype = r.headers.get("Content-Type")
    finally:
        stop.set()
        t.join(timeout=2)
        srv.close()
    assert ctype == CONTENT_TYPE
    assert "# TYPE e2e_total counter" in body
    assert "e2e_total 7" in body


# ------------------------------------------------------------ counter bank
def test_counter_bank_matches_host_recount():
    """The jitted tick's counter vector must equal a recount from the raw
    per-tick outputs (masks fetched lazily by the host)."""
    from noahgameframe_tpu.game.world import build_benchmark_world

    w = build_benchmark_world(128, seed=7)
    k = w.kernel
    for _ in range(6):
        out = k.tick()
        deaths = sum(int(np.asarray(m).sum()) for m in out.died.values())
        events = sum(int(np.asarray(ev.mask).sum()) for ev in out.events)
        diff_cells = sum(  # the bits set in the diff's planes
            int(np.unpackbits(np.asarray(p).view(np.uint8)).sum())
            for planes in out.diff.values()
            for p in planes.values()
        )
        rec_cells = sum(
            int((np.asarray(code) != 0).sum())
            for recs in out.rec_diff.values()
            for code in recs.values()
        )
        assert out.counters["deaths"] == deaths
        assert out.counters["events_fired"] == events
        assert out.counters["diff_cells"] == diff_cells
        assert out.counters["rec_diff_cells"] == rec_cells
        # combat counters exist in a combat world
        assert "combat_hits" in out.counters
        assert "aoi_victim_overflow_drops" in out.counters
    # totals accumulate across ticks
    assert k.counter_totals["diff_cells"] >= k.last_counters["diff_cells"]
    # registry exposes the bank
    text = w.telemetry.exposition()
    assert 'nf_tick_counters_total{counter="deaths"}' in text


def test_counter_bank_zero_when_no_combat():
    from noahgameframe_tpu.game.world import GameWorld, WorldConfig

    w = GameWorld(WorldConfig(combat=False, movement=False, regen=True,
                              npc_capacity=64, player_capacity=16)).start()
    out = w.kernel.tick()
    # builtins always present; combat counters absent without the phase
    assert "deaths" in out.counters
    assert "combat_hits" not in out.counters


# ------------------------------------------------------------- trace export
def test_chrome_trace_export(tmp_path):
    tr = SpanTracer(capacity=64, enabled=True)
    with tr.span("outer", tick=1):
        with tr.span("inner"):
            pass
    tr.instant("marker")
    path = tmp_path / "trace.json"
    n = tr.export(path)
    assert n == 3
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"nf.outer", "nf.inner"}
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0
    assert any(e["ph"] == "i" and e["name"] == "nf.marker" for e in evs)


def test_tracer_disabled_records_nothing():
    tr = SpanTracer(enabled=False)
    with tr.span("x"):
        pass
    assert len(tr) == 0


def test_tracer_ring_overwrites():
    tr = SpanTracer(capacity=4, enabled=True)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    names = [e[0] for e in tr.events()]
    assert len(names) == 4
    assert names == ["nf.s6", "nf.s7", "nf.s8", "nf.s9"]


# ------------------------------------------------- satellites: utils.metrics
def test_tick_metrics_shares_histogram_math():
    from noahgameframe_tpu.utils.metrics import TickMetrics

    m = TickMetrics(window=8)
    for _ in range(3):
        with m.frame():
            pass
    assert len(m._durations) == 3
    p = m.percentiles()
    # one percentile implementation: facade values == histogram values
    assert p["p50_ms"] == pytest.approx(m.hist.percentile(50) * 1e3)
    assert p["mean_ms"] == pytest.approx(m.hist.window_mean() * 1e3)


def test_memory_census_logs_failing_probe_once(caplog):
    import logging

    from noahgameframe_tpu.utils.metrics import MemoryCensus

    c = MemoryCensus()

    def bad():
        raise RuntimeError("probe down")

    c.register_probe("broken", bad)
    with caplog.at_level(logging.WARNING, logger="nf.metrics"):
        assert c.census()["broken"] == -1
        assert c.census()["broken"] == -1
    warnings = [r for r in caplog.records if "broken" in r.getMessage()]
    assert len(warnings) == 1  # once per failing probe kind, not per scrape
    # re-registering clears the once-latch
    c.register_probe("broken", bad)
    with caplog.at_level(logging.WARNING, logger="nf.metrics"):
        c.census()
    warnings = [r for r in caplog.records if "broken" in r.getMessage()]
    assert len(warnings) == 2


# -------------------------------------------------------- thread safety
# The registry is written from two threads in production: the pump thread
# (tick metrics, stage clock) and the write-behind flusher (persist
# telemetry).  Unlocked float += drops increments under contention; these
# hammers assert exact totals (ISSUE 7 satellite).
def _hammer(fn, threads=8, rounds=2000):
    start = threading.Barrier(threads)

    def work():
        start.wait()
        for _ in range(rounds):
            fn()

    ts = [threading.Thread(target=work) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return threads * rounds


def test_counter_inc_is_thread_safe():
    c = Counter("stress_total", "x")
    n = _hammer(lambda: c.inc(1.0))
    assert c.value() == n


def test_histogram_observe_is_thread_safe():
    h = Histogram("stress_seconds", "x", window=128, buckets=(0.5, 1.0))
    n = _hammer(lambda: h.observe(0.25))
    assert h.count == n
    assert h.sum == pytest.approx(0.25 * n)
    by_le = {labels["le"]: v for suffix, labels, v in h.samples()
             if suffix == "_bucket"}
    assert by_le["0.5"] == n and by_le["+Inf"] == n


def test_histogram_percentile_during_concurrent_observe():
    h = Histogram("race_seconds", "x", window=64)
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            h.percentile(50)
            h.window_mean()

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    try:
        _hammer(lambda: h.observe(1.0), threads=4, rounds=3000)
    finally:
        stop.set()
        t.join(timeout=2)
    assert h.count == 12000
    assert h.percentile(50) == pytest.approx(1.0)


# ------------------------------------------------------------- net counters
def test_net_counters_per_opcode():
    from noahgameframe_tpu.net.module import NetServerModule
    from noahgameframe_tpu.net.transport import create_client

    srv = NetServerModule(backend="py")
    cli = create_client("127.0.0.1", srv.port, backend="py")
    cli.connect()
    got = []
    srv.on(42, lambda conn, mid, body: got.append((mid, body)))
    deadline = time.monotonic() + 5
    sent = False
    while time.monotonic() < deadline and not got:
        srv.execute()
        for ev in cli.poll():
            pass
        if not sent and cli.connected:
            cli.send_msg(42, b"hello")
            sent = True
        time.sleep(0.002)
    assert got, "message did not arrive"
    assert srv.counters.in_msgs.get(42) == 1
    assert srv.counters.in_bytes.get(42) == 5
    # outbound via send_raw
    conn_id = next(iter(srv.conn_tags))
    srv.send_raw(conn_id, 43, b"abc")
    assert srv.counters.out_msgs.get(43) == 1
    assert srv.counters.out_bytes.get(43) == 3
    srv.shut()
    cli.close()


def test_relay_counters_exposed_per_opcode():
    """Proxy forward-latency attribution (ISSUE 7 satellite): NetCounters
    absorbs count_relay and the TelemetryModule exposes both the count
    and cumulative seconds under link/opcode labels."""
    from noahgameframe_tpu.net.module import NetCounters
    from noahgameframe_tpu.telemetry.module import TelemetryModule

    c = NetCounters()
    c.count_relay(301, 2_000_000)  # 2 ms
    c.count_relay(301, 1_000_000)
    c.count_relay(8004, 500_000)
    assert c.relay_msgs == {301: 2, 8004: 1}
    assert c.relay_ns == {301: 3_000_000, 8004: 500_000}

    tm = TelemetryModule()
    tm.add_net_source("games", c)
    text = tm.exposition()
    assert 'nf_relay_msgs_total{link="games",opcode="301"} 2' in text
    assert 'nf_relay_seconds_total{link="games",opcode="301"} 0.003' in text
    assert 'nf_relay_msgs_total{link="games",opcode="8004"} 1' in text
