"""Bring-up contracts: one process per chip, one compile cache, and the
chip smoke's control flow.

- the four control-plane roles never initialise a JAX backend, so in the
  five-process deployment the game role is the chip's only owner;
- `init_compile_cache()` is the only place that places the persistent
  compile cache, and the environment can place it from outside;
- `chip_smoke.py --platform cpu --tiny` (a REHEARSAL: tiny sizes,
  interpret-mode kernels) keeps the script's control flow guarded on
  every PR, and without that option the script refuses to run here.
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(args, env=None, timeout=600):
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True,
        timeout=timeout, cwd=REPO, env=env,
    )


_ROLE_PROBE = """
import sys
from noahgameframe_tpu.net import roles
from noahgameframe_tpu.net.defines import ServerType
from noahgameframe_tpu.net.roles.base import RoleConfig

name, stype = sys.argv[1], sys.argv[2]
cls = getattr(roles, name)
role = cls(RoleConfig(1, int(ServerType[stype]), name, "127.0.0.1", 0),
           backend="py")
for _ in range(20):
    role.execute()
role.shut()

from jax._src import xla_bridge
print("BACKENDS_INITIALIZED", xla_bridge.backends_are_initialized())
"""


@pytest.mark.parametrize("name,stype", [
    ("MasterRole", "MASTER"), ("LoginRole", "LOGIN"),
    ("WorldRole", "WORLD"), ("ProxyRole", "PROXY"),
])
def test_control_plane_role_initialises_no_jax_backend(name, stype):
    """Construct and execute() the role in a child: importing the roles
    package imports jax, but no backend may come up.  The child names a
    platform that does not exist, so a backend touch would also raise."""
    env = dict(os.environ, JAX_PLATFORMS="no_such_platform")
    r = _run(["-c", _ROLE_PROBE, name, stype], env=env, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "BACKENDS_INITIALIZED False" in r.stdout


_RUN_ROLE_PROBE = """
import runpy, sys, time

def stop(_seconds):  # the role's serve loop ends after one execute()
    raise KeyboardInterrupt

time.sleep = stop
script, xml, crash = sys.argv[1:4]
sys.argv = [script, "--role", "master", "--id", "1", "--server-xml", xml,
            "--crash-log-dir", crash]
try:
    runpy.run_path(script, run_name="__main__")
except SystemExit as e:
    assert e.code == 0, e.code

import jax
print("BACKEND", jax.default_backend())
"""


def test_run_role_holds_control_plane_roles_to_the_cpu(tmp_path):
    """scripts/run_role.py derives the platform from --role: a master
    started where the environment names another platform (here one that
    does not exist) serves, and jax in that process resolves to the CPU."""
    xml = tmp_path / "cluster.xml"
    xml.write_text('<XML><Server ID="1" Type="MASTER" Name="M" '
                   'IP="127.0.0.1" Port="0" MaxOnline="100"/></XML>')
    env = dict(os.environ, JAX_PLATFORMS="no_such_platform")
    r = _run(["-c", _RUN_ROLE_PROBE, str(REPO / "scripts" / "run_role.py"),
              str(xml), str(tmp_path / "crash")], env=env, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "master id=1 listening on" in r.stdout
    assert "BACKEND cpu" in r.stdout


_CACHE_PROBE = """
import jax
from noahgameframe_tpu.utils import platform
print("PATH", platform.init_compile_cache())
print("CONFIG", jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_is_placed_from_outside_or_fixed(tmp_path):
    from noahgameframe_tpu.utils import platform

    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    r = _run(["-c", _CACHE_PROBE], env=env, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    fixed = str(REPO / ".jax_cache")
    assert str(platform.DEFAULT_CACHE_DIR) == fixed
    assert f"PATH {fixed}\n" in r.stdout and f"CONFIG {fixed}\n" in r.stdout

    placed = str(tmp_path / "elsewhere")
    r = _run(["-c", _CACHE_PROBE],
             env=dict(env, JAX_COMPILATION_CACHE_DIR=placed), timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"PATH {placed}\n" in r.stdout and f"CONFIG {placed}\n" in r.stdout


def test_only_init_compile_cache_sets_the_cache_dir():
    """No other module of the repo places the cache (and the knob that
    used to, NF_COMPILE_CACHE, is gone)."""
    offenders = []
    for path in REPO.rglob("*.py"):
        rel = path.relative_to(REPO)
        # hidden top-level directories (caches, scratch copies of the
        # tree) and what the chip tool brings back are not the repo
        if (rel.parts[0].startswith(".") or rel.parts[0] == "chiprun_out"
                or rel == Path("tests/test_bring_up.py")):
            continue
        text = path.read_text()
        if "NF_COMPILE_CACHE" in text:
            offenders.append((str(rel), "NF_COMPILE_CACHE"))
        if re.search(r"jax_compilation_cache_dir", text) and rel != Path(
                "noahgameframe_tpu/utils/platform.py"):
            offenders.append((str(rel), "jax_compilation_cache_dir"))
    assert not offenders, offenders


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """As the driver runs it (no arguments) in a sandbox held to the CPU:
    non-zero exit and no result line."""
    r = _run(["chip_smoke.py"], timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_cpu_rehearsal(tmp_path, chips):
    """The whole control flow at tiny sizes on the CPU backend (four
    virtual devices under --chips 4); the compile cache goes where the
    environment says."""
    cache = tmp_path / "cache"
    r = _run(["chip_smoke.py", "--platform", "cpu", "--tiny",
              "--chips", str(chips)],
             env=dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache)),
             timeout=900)
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    lines = r.stdout.strip().splitlines()
    assert lines[0].startswith("# REHEARSAL")
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": chips}}
    phases = {d["phase"]: d for d in map(json.loads, lines[1:-1])}
    assert phases["start"]["compile_cache_dir"] == str(cache)
    assert any(cache.iterdir()), "the cache was not written where placed"
    if chips == 4:
        assert list(phases) == ["start", "mesh", "mesh_npc", "rooms", "done"]
        assert phases["mesh"]["digest_equal"] is True
        assert phases["mesh"]["mesh"]["migrated_total"] > 0
        assert len(phases["mesh"]["mesh"]["bank_bytes_per_device"]) == 4
        npc = phases["mesh_npc"]
        assert npc["digest_equal_but_last_attacker"] is True
        assert npc["mesh"]["migrated_total"] > 0
        assert npc["mesh"]["mig_dropped_total"] == 0
        assert len(npc["mesh"]["bank_bytes_per_device"]) == 4
        assert (phases["rooms"]["rooms_equal_to_control"]
                == phases["rooms"]["rooms"])
        return
    assert list(phases) == ["start", "tick", "determinism",
                            "determinism_vs_cpu", "engines", "served", "done"]
    assert phases["tick"]["unexplained_recompiles"] == 0
    # what the warm-up and the overflow retrace dropped is in the line
    for p in (phases["tick"]["compile_passes"]
              + phases["tick"]["overflow_retrace"]):
        assert {"victim_drops", "geometry_from", "geometry_to"} <= set(p)
    assert phases["tick"]["compile_passes"], "the warm-up compiled nothing?"
    assert phases["determinism"]["identical"] is True
    assert phases["engines"]["pallas_interpret"] is True
    assert phases["engines"]["banks_equal"] is True
    # traced for the CPU the module chooses the XLA fold; the pin gives
    # the other one
    assert (phases["engines"]["engine_chosen"],
            phases["engines"]["engine_pinned"]) == (0, 1)
    assert phases["served"]["leases_not_up"] == []
    assert "inbound_backlog_max" in phases["served"]
    assert phases["served"]["per_client_min"]["interest_msgs"] > 0
    assert phases["served"]["transport"] in ("native", "py")
