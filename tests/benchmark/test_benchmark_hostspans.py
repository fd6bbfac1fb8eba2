"""The reduction of the program's own host spans (harness/hostspans.py),
held against numbers read by hand off a small recorded trace.

`served-100k-3passes.xplane.pb.gz` is three passes of the pump cut out of
a traced `served-100k-s32` run on a TPU v5e (PR 24; a scratch tool kept
the device plane's `XLA Ops` and `XLA Modules`, the host plane's `nf.*`
and `bench.*` events, and a `bench.window` span over the cut).  The
numbers below were read off it by a second, quadratic reading written
for the purpose (each event's parent found by search, idle time found on
the elementary intervals between all events' ends), not by the module
under test.
"""

import os
import statistics
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import hostspans, xplane  # noqa: E402

DATA = os.path.join(ROOT, "benchmarks", "harness", "testdata")
TRACE = os.path.join(DATA, "served-100k-3passes.xplane.pb.gz")
MS = 1e-3


@pytest.fixture(scope="module")
def spans():
    return hostspans.reduce_spans(TRACE)


def test_window_and_pump_thread(spans):
    assert spans.window_s == pytest.approx(0.42987752)
    assert spans.pump_thread.startswith("python")
    # the pump's thread is inside some nf.* span for all but 0.7 ms
    assert spans.pump_self_s == pytest.approx(0.42916376, rel=1e-6)
    name, sec = spans.longest
    assert name == "nf.role.game" and sec == pytest.approx(0.122845237)


@pytest.mark.parametrize("name,total_ms,self_ms", [
    ("nf.fanout.props", 199.876645, 199.876645),  # a leaf
    ("nf.kernel.fanout", 204.971145, 0.1049),  # all but its four blocks
    ("nf.stage.tick", 269.500464, 1.14999),
    ("nf.frame", 349.667512, 0.0462),
    ("nf.role.proxy", 32.42539, 23.865751),  # less its 96 relay spans
    ("nf.trace.relay", 8.559639, 8.559639),
    ("nf.client.pump", 31.887345, 27.090805),  # less its recv spans
    ("nf.stage.send", 17.011288, 17.011288),
])
def test_self_time_is_a_spans_time_less_its_childrens(spans, name, total_ms,
                                                      self_ms):
    assert spans.total_s[name] == pytest.approx(total_ms * MS, abs=1e-9)
    assert spans.self_s[name] == pytest.approx(self_ms * MS, abs=1e-9)


@pytest.mark.parametrize("name,idle_ms", [
    ("nf.fanout.props", 198.205022),  # the device runs 1.7 ms of its 199.9
    ("nf.kernel.fetch", 6.601932),  # of 46.5: the device is busy in it
    ("nf.kernel.fanout", 0.1049),  # only its own part, not its children's
    ("nf.stage.tick", 1.09551),
    ("nf.role.proxy", 23.865751),
    (hostspans.NO_SPAN, 0.71376),
])
def test_idle_time_is_charged_to_the_innermost_span(spans, name, idle_ms):
    assert spans.idle_s[name] == pytest.approx(idle_ms * MS, abs=1e-9)


def test_idle_time_sums_to_the_windows_idle_time(spans):
    got = xplane.reduce_trace(TRACE)
    assert sum(spans.idle_s.values()) == pytest.approx(0.364056996, abs=1e-8)
    assert sum(spans.idle_s.values()) == pytest.approx(
        got.window_s - got.busy_s, abs=1e-8)


def test_device_programs_are_counted_under_the_span_they_started_in(spans):
    # the 41 eager slices and squeezes a frame, and who issues them
    assert spans.programs["nf.fanout.props"] == {
        "jit_dynamic_slice": 123, "jit_squeeze": 123,
        "jit_convert_element_type": 6}
    assert spans.programs["nf.fanout.events"] == {
        "jit_dynamic_slice": 3, "jit_squeeze": 3}
    assert spans.programs["nf.kernel.dispatch"] == {"jit__trace_step": 3}


def test_wire_spans_join_by_tick_and_seq(spans):
    emit, relay, recv = (spans.wire["nf.trace." + n]
                         for n in ("emit", "relay", "recv"))
    assert (len(emit), len(relay), len(recv)) == (96, 96, 97)
    assert min(emit) == (323, 1921) and max(recv) == (324, 1984)
    # emitted in one pass, relayed in the next, read in that pass's
    # clients' turn: two of the three passes' sidecars cross the proxy
    # inside the cut, and all the relayed ones reach a client
    proxy = spans.wire_gaps_ms("nf.trace.emit", "nf.trace.relay", False)
    client = spans.wire_gaps_ms("nf.trace.relay", "nf.trace.recv", True)
    assert (len(proxy), len(client)) == (64, 96)
    assert statistics.median(proxy) == pytest.approx(21.4890895)
    assert (min(proxy), max(proxy)) == pytest.approx((20.75285, 22.440109))
    assert statistics.median(client) == pytest.approx(128.352202)
    assert (min(client), max(client)) == pytest.approx(
        (121.892047, 133.284837))


def _run(**counters):
    """What a reader is handed, as far as these readers look."""
    notes = []
    run = types.SimpleNamespace(
        trace_file=TRACE, counters=counters, hlo_scopes={},
        note=lambda what, **f: notes.append((what, f)))
    return run, notes


def test_readers_divide_by_the_cells_unit_and_note_the_waterfall_once():
    run, notes = _run(frames=3, ticks=3)
    trace = xplane.reduce_trace(TRACE)
    assert hostspans.per_unit_ms(run, ("nf.kernel.fetch",), "frames") \
        == pytest.approx(46.479689 / 3)
    assert hostspans.per_unit_ms(run, ("nf.kernel.fanout",), "ticks") \
        == pytest.approx(204.971145 / 3)
    # master + login + world + proxy over the three bench.pump spans
    assert len(trace.annotations["bench.pump"]) == 3
    assert hostspans.roles_pump_ms(run, trace) == pytest.approx(
        (32.42539 + 1.101701 + 0.692081 + 1.262651) / 3, abs=1e-5)
    assert hostspans.wire_wait_ms(run, "nf.trace.emit", "nf.trace.relay",
                                  frm_end=False) == pytest.approx(21.4890895)
    assert [w for w, _ in notes] == ["host_waterfall"]
    fields = notes[0][1]
    assert fields["per"] == "frames" and fields["units"] == 3
    assert fields["self_ms"]["nf.fanout.props"] == pytest.approx(
        199.876645 / 3)
    assert fields["device_programs"]["nf.fanout.props"][
        "jit_dynamic_slice"] == 41
    assert fields["longest_span"]["name"] == "nf.role.game"


def test_a_program_without_spans_gives_nothing_and_raises_nothing():
    """The parent of the PR that added the spans: the same readers over
    a trace with no `nf.*` event (PR 23's recorded 1M trace)."""
    run, notes = _run(ticks=2)
    run.trace_file = os.path.join(DATA, "tick-1m-2ticks.xplane.pb.gz")
    trace = xplane.reduce_trace(run.trace_file)
    assert hostspans.per_unit_ms(run, ("nf.kernel.fetch",), "ticks") is None
    assert hostspans.roles_pump_ms(run, trace) is None
    assert hostspans.wire_wait_ms(run, "nf.trace.emit", "nf.trace.relay",
                                  frm_end=False) is None
    assert hostspans.scope_device_ms(run, trace, "nf.aoe.rank") is None
    assert notes == []
