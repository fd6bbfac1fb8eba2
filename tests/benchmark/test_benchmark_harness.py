"""CPU rehearsal of the benchmark harness (benchmarks/run.py).

A rehearsal proves the control flow, the manifest and the last line's
shape; it says nothing about speed, and every metric it prints carries a
`rehearsal_` name.
"""

import contextlib
import io
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# a reader that finds nothing to read returns nothing: a CPU has no row
# in the table of peaks, so a rehearsal has no roofline share
NOT_ON_CPU = {"tick_roofline"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def manifest():
    with open(MANIFEST) as f:
        return json.load(f)


def rehearse(workload, trace, manifest_path=MANIFEST, seed=2147483659):
    """One in-process rehearsal; returns (exit code, stdout lines)."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main([
            "--workload", workload, "--seed", str(seed), "--seconds", "1",
            "--trace", str(trace), "--rehearse", "--manifest", manifest_path])
    return rc, [ln for ln in out.getvalue().splitlines() if ln.strip()]


def reports(metric, cell):
    return metric.get("workloads") is None or cell in metric["workloads"]


CELLS = [w["name"] for w in manifest()["workloads"]]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contracts_last_line(cell, trace):
    rc, lines = rehearse(cell, trace)
    assert rc == 0
    last = json.loads(lines[-1])
    extra = {"compared"} | ({"breakdown"} if trace else set())
    assert LINE_KEYS <= set(last) <= LINE_KEYS | extra
    assert list(last)[-1] == "compared"  # each number beside its limit
    assert last["correct"] is True, last["compared"]
    assert last["device"]["platform"] == "cpu"
    man = manifest()
    listed = man["per_layer"] if trace else man["end_to_end"]
    want = {"rehearsal_" + m["name"] for m in listed if reports(m, cell)
            and m["name"] not in NOT_ON_CPU}
    assert want <= set(last["metrics"])
    assert all(k.startswith("rehearsal_") for k in last["metrics"])
    units = {m["name"]: m["unit"] for m in listed}
    for k, v in last["metrics"].items():
        assert v["unit"] == units[k[len("rehearsal_"):]]
        assert v["value"] > 0
    if trace:
        assert 0 < last["device"]["busy_s"] <= last["device"]["window_s"] * 8
        assert len(last["breakdown"]["device_ops"]) <= 10
        assert len(last["breakdown"]["idle_gaps"]) <= 10
    for line in lines[:-1]:
        json.loads(line)  # earlier lines are notes, one object each


def run_cli(args, cwd=ROOT, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_it_fails_and_prints_no_result():
    got = run_cli(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert got.returncode != 0
    assert got.stdout.strip() == ""
    assert "--rehearse" in got.stderr


def test_without_the_program_it_fails_and_prints_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths` there is no system to measure."""
    shutil.copy(MANIFEST, tmp_path / "BENCHMARK.json")
    for p in manifest()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    got = run_cli(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0", "--rehearse"], cwd=str(tmp_path),
                  env_extra={"PYTHONPATH": ""})
    assert got.returncode != 0
    assert got.stdout.strip() == ""


def test_a_new_cell_is_new_files_and_one_entry(tmp_path):
    """`tick-walk`: a new configuration file, a new mix file and one
    `workloads` entry, in a temporary copy of the manifest.  No file
    that exists is touched; the drivers, readers and harness are found
    where they are."""
    man = manifest()
    with open(os.path.join(ROOT, "benchmarks/configs/npc-1m.json")) as f:
        config = json.load(f)
    walk = dict(config["rehearse"]["world"], combat=False, entities=512)
    config.update(name="walk-tiny", world=walk, rehearse={})
    extra = tmp_path / "extra"
    (extra / "configs").mkdir(parents=True)
    (extra / "traffic").mkdir()
    (extra / "configs" / "walk-tiny.json").write_text(json.dumps(config))
    with open(os.path.join(ROOT, "benchmarks/traffic/observed-closed.json")) as f:
        mix = json.load(f)
    mix.update(name="observed-brief", soak_ticks=20, rehearse={})
    (extra / "traffic" / "observed-brief.json").write_text(json.dumps(mix))
    man["paths"] = [os.path.join(ROOT, p) for p in man["paths"]] + [str(extra)]
    for c in man["configs"]:
        c["file"] = os.path.join(ROOT, c["file"])
    man["configs"].append({
        "name": "walk-tiny", "source": config["source"], "reduced": [],
        "file": str(extra / "configs" / "walk-tiny.json"), "why": "test"})
    man["workloads"].append({
        "name": "tick-walk", "config": "walk-tiny",
        "traffic": "observed-brief", "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "tick-1m" in m.get("workloads", ()):
            m["workloads"].append("tick-walk")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(man))
    rc, lines = rehearse("tick-walk", 0, manifest_path=str(path))
    assert rc == 0
    last = json.loads(lines[-1])
    assert last["correct"] is True, last["compared"]
    assert {"rehearsal_tick_ms", "rehearsal_tick_p95_ms",
            "rehearsal_setup_s"} <= set(last["metrics"])


def test_manifest_keeps_to_the_contract():
    man = manifest()
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= man["run_seconds"] <= 51 and int(man["run_seconds"]) == man["run_seconds"]
    assert os.path.getsize(MANIFEST) <= 64 * 1024
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in man[k]]
    for n in names + [w["traffic"] for w in man["workloads"]] + [
            r for c in man["configs"] for r in c["reduced"]]:
        assert NAME.match(n), n
    metrics = man["end_to_end"] + man["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    cells = {w["name"] for w in man["workloads"]}
    e2e = {m["name"] for m in man["end_to_end"]}
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", cells)) <= cells
    for m in man["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in man["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and "bound" not in m
        moved = next(e for e in man["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    configs = {c["name"]: c for c in man["configs"]}
    assert {w["config"] for w in man["workloads"]} == set(configs)
    for c in configs.values():
        assert any(c["file"].startswith(p + "/") for p in man["paths"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    assert sum(w["chips"] == 4 for w in man["workloads"]) <= max(
        1, len(cells) // 2)
    for w in man["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))
        mine = [m for m in man["end_to_end"] if reports(m, w["name"])]
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        assert any(reports(m, w["name"]) for m in man["per_layer"])
    for word in man["command"]:
        assert not word.startswith("/") and ".." not in word
