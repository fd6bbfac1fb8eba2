"""Driver-contract smoke tests: bench.py and __graft_entry__ produce
their artifacts on an explicit CPU rehearsal, and bench.py refuses to
measure at all when it is not told to rehearse and finds no TPU."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout):
    return subprocess.run(
        [sys.executable, *args],
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=REPO,
    )


def test_bench_smoke_emits_parseable_json():
    r = _run(
        ["bench.py", "--platform", "cpu", "--entities", "2000", "--ticks", "5"],
        timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    line = r.stdout.strip().splitlines()[-1]
    d = json.loads(line)
    # a CPU rate never goes under the device metric's name
    assert d["metric"] == "entities_ticked_per_sec_cpu_rehearsal"
    assert d["value"] > 0
    assert d["detail"]["platform"] == "cpu"
    assert "tick_ms_p99" in d["detail"]


def test_bench_without_a_tpu_fails_and_prints_no_metric():
    """The default platform is the chip: with none visible (the suite's
    environment holds jax to the CPU) bench.py exits non-zero and no
    line of its output is a metric."""
    r = _run(["bench.py", "--entities", "2000", "--ticks", "5"], timeout=120)
    assert r.returncode != 0
    assert "metric" not in r.stdout
    assert "no TPU" in r.stderr


def test_bench_served_smoke():
    r = _run(
        ["bench.py", "--served", "--platform", "cpu",
         "--entities", "2000", "--ticks", "4", "--sessions", "5"],
        timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    d = json.loads(r.stdout.strip().splitlines()[-1])
    assert d["metric"] == "served_entity_ticks_per_sec_cpu_rehearsal"
    assert d["value"] > 0
    assert d["detail"]["sync_msgs"] > 0  # fan-out actually happened


def test_bench_mesh_migrate_smoke():
    """The r09 unified-engine ladder at toy scale: full-row migration
    actually moves rows, drops nothing, and the post-warmup sweep loop
    compiles nothing new (the zero-unexplained-recompiles gate)."""
    r = _run(
        ["bench.py", "--mesh-migrate", "4", "--mig-entities", "4096",
         "--mig-widths", "2,4", "--mig-budgets", "64", "--mig-ticks", "3"],
        timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    d = json.loads(r.stdout.strip().splitlines()[-1])
    assert d["metric"] == "mesh_migrate_entity_ticks_per_sec"
    assert "error" not in d, d.get("error")
    assert d["value"] > 0
    assert d["detail"]["unexplained_recompiles"] == 0
    pts = d["detail"]["points"]
    assert len(pts) == 2  # 1 entity count x 2 widths x 1 budget
    for p in pts:
        assert p["migrated_total"] > 0, "ladder exercised no migration"
        assert p["mig_dropped_total"] == 0
        assert p["row_bytes"] > 0
        assert p["costbook"]["compiles"] >= 1


def test_dryrun_multichip_forces_cpu_and_finishes():
    r = _run(["__graft_entry__.py", "multichip", "4"], timeout=180)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "dryrun_multichip OK" in r.stdout


def test_entry_compiles_and_steps():
    """The driver compile-checks entry() single-chip; keep it compiling
    (conftest has already forced the CPU platform in-process)."""
    sys.path.insert(0, REPO)
    try:
        import jax

        import __graft_entry__ as ge

        fn, args = ge.entry()
        st, summary = jax.jit(fn)(*args)
        jax.block_until_ready(summary)
        assert summary.ndim == 1
    finally:
        sys.path.remove(REPO)
