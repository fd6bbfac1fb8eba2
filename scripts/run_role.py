#!/usr/bin/env python
"""Run one server role as a standalone process (NFPluginLoader equivalent).

The reference launches each role as `NFPluginLoader Server=GameServer ID=6`
reading Server.xml (`_Out/Tester/rund_*.sh`); here:

    python scripts/run_role.py --role master --id 1 --server-xml cluster.xml
    python scripts/run_role.py --role game --id 6 --server-xml cluster.xml

Server.xml lists every instance in the cluster; each process picks its own
row by (role, id) and derives its upstream targets from the others
(login/world dial the master; proxy/game dial the world).
"""

from __future__ import annotations

import argparse
import atexit
import faulthandler
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

ROLES = ("game", "login", "master", "proxy", "world")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", required=True, choices=ROLES)
    ap.add_argument("--id", type=int, required=True, help="server id in Server.xml")
    ap.add_argument("--server-xml", required=True, type=Path)
    ap.add_argument("--http-port", type=int, default=None,
                    help="HTTP port: the master serves /json + /metrics "
                         "on it; every other role serves /metrics")
    ap.add_argument("--tick-sleep", type=float, default=0.001,
                    help="main-loop sleep (reference: 1 ms)")
    ap.add_argument("--crash-log-dir", type=Path, default=Path("crashlogs"),
                    help="where crash tracebacks are written")
    ap.add_argument(
        "--platform", choices=("default", "cpu"), default="default",
        help="game role only: cpu holds its jax backend to the CPU "
             "(tests, rehearsals).  The four control-plane roles never "
             "touch an accelerator whatever this says",
    )
    ap.add_argument("--checkpoint-dir", type=Path, default=None,
                    help="game role: directory for periodic atomic "
                         "whole-world checkpoints")
    ap.add_argument("--checkpoint-seconds", type=float, default=30.0,
                    help="game role: seconds between checkpoints")
    ap.add_argument("--resume", action="store_true",
                    help="game role: restore the latest checkpoint from "
                         "--checkpoint-dir before serving")
    ap.add_argument("--journal", type=Path, default=None,
                    help="game role: record every host->device input "
                         "(commands, migrations, tick digests) to this "
                         "flight-recorder directory")
    ap.add_argument("--journal-segment-bytes", type=int, default=1 << 20,
                    help="journal segment rotation threshold")
    ap.add_argument("--replay", type=Path, default=None,
                    help="game role: do not serve; rebuild device state "
                         "offline from --checkpoint-dir + this journal, "
                         "verify every per-tick digest, exit 0 iff "
                         "bit-identical")
    args = ap.parse_args()
    # One process per chip: the game role is the accelerator's only
    # owner, so every other role is held to the CPU by construction —
    # in the environment, before the package (and with it jax) is
    # imported — not by a flag the operator has to remember.
    if args.role != "game" or args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    from noahgameframe_tpu.net import roles
    from noahgameframe_tpu.net.defines import ServerType

    if args.role == "game":
        from noahgameframe_tpu.utils.platform import init_compile_cache

        init_compile_cache()

    # crash capture: the reference installs a minidump handler around its
    # main loop (NFPluginLoader.cpp:42-69); the Python equivalent dumps
    # every thread's traceback to a per-process crash file on SIGSEGV/
    # SIGFPE/SIGABRT/SIGBUS and on hard faults in native extensions
    args.crash_log_dir.mkdir(parents=True, exist_ok=True)
    crash_path = args.crash_log_dir / f"{args.role}_{args.id}_{os.getpid()}.crash"
    crash_file = open(crash_path, "w")  # noqa: SIM115 — must outlive main
    faulthandler.enable(file=crash_file, all_threads=True)

    def _tidy_crash_file() -> None:
        # keep only real fault dumps; a clean exit leaves the file empty
        try:
            crash_file.flush()
            if crash_path.stat().st_size == 0:
                crash_path.unlink()
        except OSError:
            pass

    atexit.register(_tidy_crash_file)

    if args.replay is not None:
        if args.role != "game":
            print("--replay is a game-role mode", file=sys.stderr)
            return 2
        from noahgameframe_tpu.replay import replay_journal

        report = replay_journal(args.replay, checkpoint=args.checkpoint_dir)
        print(report.summary(), flush=True)
        return 0 if report.ok else 1

    # role -> (class, own ServerType, upstream ServerType it dials)
    master, world = int(ServerType.MASTER), int(ServerType.WORLD)
    cls, stype, upstream_type = {
        "master": (roles.MasterRole, master, None),
        "login": (roles.LoginRole, int(ServerType.LOGIN), master),
        "world": (roles.WorldRole, world, master),
        "proxy": (roles.ProxyRole, int(ServerType.PROXY), world),
        "game": (roles.GameRole, int(ServerType.GAME), world),
    }[args.role]
    rows = roles.load_server_xml(args.server_xml)
    mine = [r for r in rows if r.server_type == stype and r.server_id == args.id]
    if not mine:
        print(f"no <Server> row with Type={args.role} ID={args.id}", file=sys.stderr)
        return 2
    config = mine[0]
    if upstream_type is not None:
        config.targets = [r for r in rows if r.server_type == upstream_type]

    kwargs = {}
    if args.role == "master" and args.http_port is not None:
        kwargs["http_port"] = args.http_port
    if args.role == "game" and args.checkpoint_dir is not None:
        kwargs["checkpoint_dir"] = args.checkpoint_dir
        kwargs["checkpoint_seconds"] = args.checkpoint_seconds
        kwargs["resume"] = args.resume
    if args.role == "game" and args.journal is not None:
        kwargs["journal_dir"] = args.journal
        kwargs["journal_segment_bytes"] = args.journal_segment_bytes
    role = cls(config, **kwargs)
    if args.role != "master" and args.http_port is not None:
        h = role.serve_metrics(args.http_port)
        print(f"{args.role} id={config.server_id} /metrics on "
              f"{config.ip}:{h.port}", flush=True)
    print(f"{args.role} id={config.server_id} listening on "
          f"{config.ip}:{config.port}", flush=True)
    try:
        while True:
            # frame percentiles ride the 10 s report's ext map to the
            # master dashboard (the reference reports raw counts only)
            with role.metrics.frame():
                role.execute()
            time.sleep(args.tick_sleep)
    except KeyboardInterrupt:
        pass
    finally:
        role.shut()
    return 0


if __name__ == "__main__":
    sys.exit(main())
