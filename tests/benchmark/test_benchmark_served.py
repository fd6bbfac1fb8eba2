"""The served cell with an answer altered where it is produced: the
whole (rehearsed) run has to come out not correct."""

import contextlib
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402


def test_a_position_altered_on_the_wire_comes_out_not_correct(monkeypatch):
    """Every quantised position the game role streams is moved by one
    quantum: the tick is sound, each client's mirror is not."""
    from noahgameframe_tpu.ops import interest

    quantize = interest.quantize

    def off_by_one(pos, alive, extent):
        q, in_extent = quantize(pos, alive, extent)
        return q + 1, in_extent

    monkeypatch.setattr(interest, "quantize", off_by_one)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", "served-100k-s32", "--seed",
                             "2718281828", "--seconds", "1", "--trace", "0",
                             "--rehearse"])
    assert rc == 0
    last = json.loads(out.getvalue().splitlines()[-1])
    compared = last["compared"]
    assert last["correct"] is False
    assert compared["mirror_wrong"][0] > compared["mirror_wrong"][1]
    assert compared["state_wrong_rows"][0] == 0  # the tick itself is sound
