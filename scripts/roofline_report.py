"""Measured roofline: CostBook compiled cost x StageClock device time.

docs/ROOFLINE.md's original tables were hand-derived FLOP/byte counts
divided by spec-sheet peaks.  This script replaces the estimate half
with measurement: it runs the served path (GameRole over a benchmark
world, simulated sessions, NF_STAGE_TIMING=1 so each stage blocks on its
device work) and folds the CostBook's per-entry `cost_analysis()`
FLOPs/bytes against the StageClock's per-stage seconds into
achieved-vs-peak fractions per stage (telemetry/costbook.roofline_fold).

The peaks come from costbook.PEAKS, keyed by `device_kind` with their
source; a device that has no row there (the CPU backend included) is an
error, so this script only produces a report on the chip.

Usage:
    NF_STAGE_TIMING=1 python scripts/roofline_report.py \
        [--entities 20000] [--sessions 32] [--ticks 120] [--round r08]

Writes bench_runs/roofline_<round>.json (stdout gets the same JSON).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# honest device seconds are the whole point: force the stage clock's
# block_until_ready spans on before any role code reads the env
os.environ["NF_STAGE_TIMING"] = "1"


def run(args) -> dict:
    import jax

    from noahgameframe_tpu.core.datatypes import next_pow2
    from noahgameframe_tpu.game import build_benchmark_world
    from noahgameframe_tpu.net.roles.base import RoleConfig
    from noahgameframe_tpu.net.roles.game import GameRole, Session
    from noahgameframe_tpu.net.wire import Ident, ident_key
    from noahgameframe_tpu.telemetry.costbook import roofline_fold
    from noahgameframe_tpu.utils.platform import init_compile_cache

    init_compile_cache()
    world = build_benchmark_world(
        args.entities, combat=True, seed=args.seed,
        player_capacity=next_pow2(args.sessions + 8, lo=64),
    )
    role = GameRole(
        RoleConfig(6, 0, "RooflineGame", "127.0.0.1", 0),
        backend="py", world=world, cross_server_sync=False,
        interest_radius=args.interest_radius,
    )
    role.server.send_raw = lambda conn_id, msg_id, body: True
    for i in range(args.sessions):
        ident = Ident(svrid=99, index=i + 1)
        sess = Session(ident=ident, conn_id=1000 + (i % 8),
                       account=f"bot{i}")
        sess.guid = role.kernel.create_object(
            "Player", {"Name": f"Bot{i}"}, scene=1, group=0)
        role.sessions[ident_key(ident)] = sess
        role._guid_session[sess.guid] = ident_key(ident)

    dt = world.config.dt * 1.0001
    now = 1000.0
    for _ in range(3):  # warmup: compile + first flush
        now += dt
        role.execute(now)
    jax.block_until_ready(role.kernel.state.classes["NPC"].i32)
    for _ in range(args.ticks):
        now += dt
        role.execute(now)
    jax.block_until_ready(role.kernel.state.classes["NPC"].i32)

    book = role.kernel.costbook
    hbm = book.hbm_sample()
    fold = roofline_fold(book, role.pipeline_stats())
    return {
        "metric": "roofline_frac_of_peak",
        "entities": args.entities,
        "sessions": args.sessions,
        "ticks": args.ticks,
        "seed": args.seed,
        "interest_radius": args.interest_radius,
        "stage_timing": True,
        "device": str(jax.devices()[0]),
        "hbm": hbm,
        "compile_ms": round(book.compile_s_total * 1e3, 1),
        "compiles": book.total_compiles,
        "recompiles": book.total_recompiles,
        "roofline": fold,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--entities", type=int, default=20_000)
    ap.add_argument("--sessions", type=int, default=32)
    ap.add_argument("--ticks", type=int, default=120)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--interest-radius", type=float, default=16.0)
    ap.add_argument("--round", default="r08",
                    help="bench round tag for the output filename")
    ap.add_argument("--out-dir", default=os.path.join(
        os.path.dirname(__file__), "..", "bench_runs"))
    args = ap.parse_args()

    out = run(args)
    path = os.path.join(args.out_dir, f"roofline_{args.round}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    print(f"# wrote {os.path.normpath(path)}", file=sys.stderr)


if __name__ == "__main__":
    main()
