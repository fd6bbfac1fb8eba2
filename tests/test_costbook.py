"""Device cost observatory (ISSUE 14): CostBook unit + soak + e2e.

Unit coverage of the wrap dispatcher (signature cache, retrace cause
attribution, generation allowlist, HBM census, roofline fold) plus the
two gates the issue names:

- a 120-tick churn soak (joins/leaves/HP lanes/group swaps, reusing
  test_serve_batch's deterministic Driver) asserting ZERO compiles
  after warmup that are not covered by a sanctioned generation bump;
- scripts/costbook_smoke.py wired as a test: /costbook on every role,
  nf_* compile/HBM metrics on /metrics, and the master aggregate.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import jax.numpy as jnp
import pytest

from noahgameframe_tpu.telemetry.costbook import CostBook, roofline_fold

from test_serve_batch import Driver, build_role


# ------------------------------------------------------------- unit

def test_wrap_cache_and_attribution():
    book = CostBook()
    f = book.wrap("t.add", lambda a, b: a + b, stage="tick")
    x4 = jnp.ones((4,), jnp.float32)
    f(x4, x4)
    f(x4, x4)  # cache hit: same signature never re-lowers
    e = book.entries["t.add"]
    assert e.calls == 2 and e.compiles == 1 and e.recompiles == 0
    assert e.compile_s_total + e.lower_s_total > 0
    assert e.last["flops"] >= 0 and "bytes_accessed" in e.last

    x8 = jnp.ones((8,), jnp.float32)
    f(x8, x8)
    assert e.compiles == 2
    assert any(c.startswith("shape:") for c in e.causes)

    f(x8.astype(jnp.int32), x8.astype(jnp.int32))
    assert e.compiles == 3
    assert any(c.startswith("dtype:") for c in e.causes)


def test_wrap_static_argnums_attribution():
    book = CostBook()
    g = book.wrap("t.scale", lambda a, s: a * s, static_argnums=1)
    x = jnp.ones((4,), jnp.float32)
    assert float(g(x, 2.0)[0]) == 2.0
    assert float(g(x, 3.0)[0]) == 3.0
    e = book.entries["t.scale"]
    assert e.compiles == 2
    assert any(c.startswith("static:") for c in e.causes)


def test_generation_allowlist_gates_the_soak():
    book = CostBook()
    f = book.wrap("t.gen", lambda a: a * 2)
    f(jnp.ones((4,)))
    mark = book.mark()
    f(jnp.ones((8,)))  # unsanctioned: no bump announced it
    bad = book.unexplained_since(mark)
    assert len(bad) == 1 and bad[0]["entry"] == "t.gen"

    mark2 = book.mark()
    book.generation_bump("test-resize")
    f(jnp.ones((16,)))  # sanctioned: carries the bumped generation
    assert book.unexplained_since(mark2) == []
    assert len(book.compiles_since(mark2)) == 1
    assert book.gen_events[-1]["cause"] == "test-resize"


def test_hbm_census_and_snapshot_schema():
    book = CostBook()
    f = book.wrap("t.sum", lambda a: a.sum())
    x = jnp.ones((128,), jnp.float32)
    y = f(x)  # keep refs: the live_arrays fallback counts exactly these
    hbm = book.hbm_sample()
    assert hbm["source"] in ("memory_stats", "live_arrays")
    assert hbm["live_bytes"] > 0
    assert hbm["peak_bytes"] >= hbm["live_bytes"] or hbm["peak_bytes"] > 0
    snap = book.snapshot()
    assert snap["compiles"] == 1 and snap["recompiles"] == 0
    assert "t.sum" in snap["entries"]
    assert snap["hbm"]["samples"] == 1
    json.dumps(snap)  # must be wire-safe as served on /costbook


def test_roofline_fold_fractions():
    book = CostBook()
    f = book.wrap("t.mm", lambda a: a @ a, stage="tick")
    x = jnp.ones((64, 64), jnp.float32)
    for _ in range(4):
        f(x)
    stats = {"frames": 4, "stages": {"tick": {"mean_ms": 2.0}}}
    fold = roofline_fold(book, stats, device_kind="TPU v5 lite")
    assert fold["device_kind"] == "TPU v5 lite"
    assert fold["peaks"]["bytes_per_s"] == 8.19e11  # v5e: 819 GB/s
    assert "TPU v5e" in fold["peaks"]["source"]
    s = fold["stages"]["tick"]
    assert s["entries"] == ["t.mm"]
    assert s["device_s_per_frame"] == 0.002
    # 4 calls / 4 frames: per-frame cost is one dispatch's cost
    assert s["flops_per_frame"] == book.entries["t.mm"].last["flops"]
    if s["flops_per_frame"] > 0:
        assert 0 < s["frac_of_peak_flops"] < 1


def test_roofline_fold_unknown_device_is_an_error():
    """A device without a row in PEAKS has no roofline: no CPU
    placeholder, no default (the suite's own CPU device included)."""
    book = CostBook()
    stats = {"frames": 1, "stages": {}}
    with pytest.raises(KeyError, match="no peaks for device_kind"):
        roofline_fold(book, stats)
    with pytest.raises(KeyError, match="TPU v9"):
        roofline_fold(book, stats, device_kind="TPU v9")


# ------------------------------------------------- 120-tick churn soak

WARMUP = 48
TICKS = 120


class SoakDriver(Driver):
    """The serve-batch churn schedule, with the session population
    capped at the observer pad floor (next_pow2 lo=8) so steady-state
    churn is shape-stable by construction; growth past the pad is a
    real, intentionally shape-attributed retrace and gets its own
    assertion below."""

    MAX_SESSIONS = 8

    def join(self):
        if len(self.role.sessions) >= self.MAX_SESSIONS:
            return
        super().join()


def test_soak_120_ticks_recompile_free():
    role, world, _sent = build_role(serve_batch=True)
    book = role.kernel.costbook
    drv = SoakDriver(role, world)
    # warmup: one pass over every churn lane's cadence compiles the
    # full entry set (kernel.step + the interest/serve edge)
    for f in range(WARMUP):
        drv.frame(f)
    assert "kernel.step" in book.entries
    assert any(n.startswith(("interest.", "serve.")) for n in book.entries)
    assert book.total_compiles > 0

    mark = book.mark()
    for f in range(WARMUP, WARMUP + TICKS):
        if f == WARMUP + 60:
            # a sanctioned mid-soak retrace: invalidate() bumps the
            # generation, so the recompile it forces is allowlisted
            role.kernel.invalidate()
        drv.frame(f)

    unexplained = book.unexplained_since(mark)
    assert unexplained == [], (
        "retraces during steady-state churn not covered by a sanctioned "
        f"generation bump: {json.dumps(unexplained, indent=1)}"
    )
    # the invalidate DID retrace — and the allowlist explains it
    sanctioned = [r for r in book.compiles_since(mark)
                  if r["generation"] > mark["generation"]]
    assert sanctioned, "mid-soak invalidate() should have recompiled"
    assert any(e["cause"] == "invalidate"
               for e in book.gen_events if e["seq"] >= mark["seq"])


# --------------------------------------------------------------- e2e

def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, REPO / "scripts" / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def test_costbook_smoke_e2e():
    smoke = _load_script("costbook_smoke")
    checks = smoke.run()
    failed = [name for name, ok in checks.items() if not ok]
    assert not failed, f"costbook smoke checks failed: {failed}"
