"""The trace reduction against numbers read off a recorded trace by hand,
the table of peaks, and the work count."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import peaks, work, xplane  # noqa: E402

DATA = os.path.join(ROOT, "benchmarks", "harness", "testdata")
# Two observed ticks of `tick-1m` on a TPU v5e, cut from a traced run of
# PR 23 (seed 2400000031): the device plane's `XLA Modules` and `XLA Ops`
# lines and the benchmark's own host spans; beside it the instruction ->
# op_name map of the same compiled program (`--keep-trace`).
TRACE = os.path.join(DATA, "tick-1m-2ticks.xplane.pb.gz")
SCOPES = os.path.join(DATA, "tick-1m-hlo-scopes.json")
# Read off the protobuf by hand (picoseconds in the file):
WINDOW_S = 0.490376999328        # the `bench.window` span
BUSY_S = 0.47130101352           # union of the 3,126 `XLA Ops` events
STEP_S = (0.23565879375, 0.235683142578)   # the two `jit__trace_step`
LONGEST_GAP_S = 0.011729217422   # between the two ticks' programs
# idle under `bench.window` alone: 21.47 us before the first tick's span
# opens and 23.58 us between the two spans
OUTSIDE_TICKS_S = 21.47e-6 + 23.58e-6
AOE_S = 0.41131735547            # self time under nf.phase.CombatModule.aoe
UNCLAIMED_S = 0.048731410312     # self time under no nf.* scope


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce_trace(TRACE)


def test_busy_share_and_window(reduced):
    assert reduced.chips == 1
    assert reduced.window_s == pytest.approx(WINDOW_S, rel=2e-5)
    assert reduced.busy_s == pytest.approx(BUSY_S, rel=2e-5)
    assert reduced.busy_s / reduced.window_s == pytest.approx(0.96110, abs=1e-5)


def test_tick_modules_time(reduced):
    assert reduced.module_runs("_trace_step") == 2
    assert reduced.module_seconds("_trace_step") == pytest.approx(
        sum(STEP_S), rel=2e-5)
    assert set(reduced.modules) == {"jit__trace_step"}


def test_self_times_add_up_to_busy_time(reduced):
    """Instructions nest (a loop encloses its body), so summed durations
    overshoot; self times do not."""
    assert sum(reduced.op_self_s.values()) == pytest.approx(BUSY_S, rel=2e-4)


def test_idle_gaps_are_charged_to_the_host_span_open_in_them(reduced):
    gaps = dict(reduced.idle_gaps)
    assert set(gaps) == {"bench.tick", "bench.window"}
    assert sum(gaps.values()) == pytest.approx(WINDOW_S - BUSY_S, rel=2e-4)
    assert gaps["bench.window"] == pytest.approx(OUTSIDE_TICKS_S, rel=2e-2)
    assert gaps["bench.tick"] > LONGEST_GAP_S


def test_aoe_scope_time(reduced):
    with open(SCOPES) as f:
        scopes = json.load(f)
    aoe = reduced.scope_seconds(scopes, "nf.phase.CombatModule.aoe")
    assert aoe == pytest.approx(AOE_S, rel=2e-4)
    assert aoe / sum(STEP_S) == pytest.approx(0.8727, abs=1e-3)
    unclaimed = reduced.unclaimed_seconds(scopes)
    assert unclaimed == pytest.approx(UNCLAIMED_S, rel=2e-4)


def test_scopes_from_hlo_text():
    text = '''
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fc, metadata={op_name="jit(_trace_step)/nf.phase.CombatModule.aoe/mul" source_file="x.py"}
  ROOT %copy.1 = f32[8]{0} copy(f32[8]{0} %fusion.7), metadata={op_name="jit(_trace_step)/nf.diff/ne"}
  %bare.2 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)
'''
    got = xplane.scopes_from_hlo_text(text)
    assert got == {"fusion.7": "jit(_trace_step)/nf.phase.CombatModule.aoe/mul",
                   "copy.1": "jit(_trace_step)/nf.diff/ne"}
    assert xplane.instruction_name(
        "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p)") == "fusion.7"


def test_self_times_and_union():
    events = [(0.0, 10.0, "loop"), (1.0, 4.0, "body"), (5.0, 9.0, "body"),
              (12.0, 13.0, "tail")]
    assert xplane.self_times(events) == {"loop": 3.0, "body": 7.0,
                                         "tail": 1.0}
    assert xplane.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_work_is_counted_from_the_schema_not_the_arrays():
    with open(os.path.join(ROOT, "benchmarks/configs/npc-1m.json")) as f:
        config = json.load(f)
    w = work.tick_work(config, 1_000_000)
    assert w["row_read_bytes"] == 1368 and w["row_write_bytes"] == 243
    assert w["bytes"] == 1_611_000_000
    # bucket settings, engines and layouts are not arguments of the count:
    # two configurations that differ only in them do the same work
    other = json.loads(json.dumps(config))
    other["world"]["aoi_bucket"] = 64
    other["assumed"]["engines_and_buckets"] = "bucket 64, engine 1"
    assert work.tick_work(other, 1_000_000) == w
    assert "bucket" not in work.tick_work.__code__.co_varnames
    least_s, bound_by = work.roofline_seconds(w, peaks.peaks_for("TPU v5 lite"))
    assert bound_by == "bytes"
    assert least_s == pytest.approx(1.611e9 / 8.19e11)
