"""Per-phase tick profiler: where does the world tick's time go on chip?

Times jit'd PREFIXES of the phase chain (schedule advance -> phase 1 ->
... -> phase i) and reports per-phase deltas, plus the diff-extraction
epilogue (full _trace_step minus the all-phases prefix) and isolated
combat sub-kernels (cell-table build / stencil fold).  Prefix deltas are
the honest attribution under XLA fusion: a phase's cost includes the
bank copies it forces, measured in composition, not in isolation.

Usage:  python scripts/profile_tick.py --entities 1000000 --iters 10
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp


def _timeit(f, arg, iters: int) -> float:
    out = f(arg)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(arg)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1000.0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--entities", type=int, default=1_000_000)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--no-combat", action="store_true")
    ap.add_argument(
        "--platform", choices=("default", "cpu"), default="default",
        help="cpu: hold the jax backend to the CPU (harness smoke test)",
    )
    args = ap.parse_args()
    from noahgameframe_tpu.utils.platform import force_cpu, init_compile_cache

    if args.platform == "cpu":
        force_cpu()
    init_compile_cache()

    from noahgameframe_tpu.game import build_benchmark_world
    from noahgameframe_tpu.kernel.kernel import TickCtx

    world = build_benchmark_world(args.entities, combat=not args.no_combat, seed=42)
    k = world.kernel
    state = k.state
    # every timed prefix is a CostBook entry: phase attribution, compile
    # wall and compiled FLOPs/bytes share one ledger with bench.py
    # instead of re-deriving the phase list
    book = k.costbook

    def prefix_fn(n_phases: int):
        def f(st):
            new_classes = {}
            fired = {}
            for cname in k.store.class_order:
                cs, fm = k.schedule.advance_class(st.classes[cname], st.tick)
                new_classes[cname] = cs
                fired[cname] = fm
            st = st.replace(classes=new_classes)
            rng = jax.random.fold_in(st.rng, st.tick)
            ctx = TickCtx(k, st.tick, rng, fired)
            for ph in k._composed[:n_phases]:
                st = ph.fn(st, ctx)
            return st.replace(tick=st.tick + 1)

        return f

    names = ["schedule"] + [p.name for p in k._composed]
    report = {}
    prev = 0.0
    for i in range(len(k._composed) + 1):
        label = names[i] if i < len(names) else f"phase{i}"
        fn = book.wrap(f"prefix.{label}", prefix_fn(i), stage="profile")
        ms = _timeit(fn, state, args.iters)
        report[label] = round(ms - prev, 3)
        report[f"_cum_{label}"] = round(ms, 3)
        prev = ms
        print(f"  prefix {i:2d} ({label:12s}): {ms:8.2f} ms  (+{report[label]:.2f})", flush=True)

    full = book.wrap("prefix.full_step", lambda st: k._trace_step(st),
                     stage="profile")
    ms_full = _timeit(full, state, args.iters)
    report["diff_epilogue"] = round(ms_full - prev, 3)
    report["full_step"] = round(ms_full, 3)
    print(f"  full step (incl diff):   {ms_full:8.2f} ms  (diff +{report['diff_epilogue']:.2f})", flush=True)

    if world.combat is not None:
        from noahgameframe_tpu.ops.stencil import build_cell_table

        combat = world.combat
        spec = k.store.spec(combat.class_name)
        cs = k.state.classes[combat.class_name]
        pos = cs.vec[:, spec.slot("Position").col, :2]
        n = pos.shape[0]
        bucket = combat.resolved_bucket(n)
        att_bucket = combat.resolved_att_bucket(n)
        vic_feats = jnp.zeros((n, 5), jnp.float32)
        att_feats = jnp.zeros((n, 7), jnp.float32)
        att_mask = cs.alive & (jnp.arange(n) % 30 == 0)  # ~one residue class

        def both_builds(p):
            vt = build_cell_table(
                p, cs.alive, vic_feats, combat.cell_size, combat.width, bucket
            )
            at = build_cell_table(
                p, att_mask, att_feats, combat.cell_size, combat.width, att_bucket
            )
            return vt.payload, at.payload

        build = book.wrap("pass.combat_build_only", both_builds,
                          stage="profile")
        report["combat_build_only"] = round(_timeit(build, pos, args.iters), 3)
        report["combat_geometry"] = {
            "width": combat.width,
            "bucket": bucket,
            "att_bucket": att_bucket,
            "cells": combat.width * combat.width,
        }
        print(
            f"  cell-table builds alone: {report['combat_build_only']:8.2f} ms  "
            f"(width={combat.width}, Kv={bucket}, Ka={att_bucket})",
            flush=True,
        )

    dev = jax.devices()[0]
    print(json.dumps({"device": str(dev), "entities": args.entities,
                      "profile": report, "costbook": book.snapshot()}))


if __name__ == "__main__":
    main()
