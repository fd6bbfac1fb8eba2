"""Capture a jax.profiler trace of the fused 1M tick on the live
backend and tar it into bench_runs/ for offline op-level analysis
(docs/ROOFLINE.md step 1 — the per-pass profiler ranks passes, the
xplane trace attributes time op by op inside them).

Usage: python scripts/capture_trace.py [--entities 1000000] [--ticks 3]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tarfile
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--entities", type=int, default=1_000_000)
    ap.add_argument("--ticks", type=int, default=3)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "bench_runs", "r05_trace_1m.tar.gz"))
    args = ap.parse_args()

    from noahgameframe_tpu.utils.platform import init_compile_cache

    init_compile_cache()

    import jax

    from noahgameframe_tpu.game import build_benchmark_world

    world = build_benchmark_world(args.entities, combat=True, seed=42)
    k = world.kernel
    k.run_device(1)  # compile outside the trace
    jax.block_until_ready(k.state.classes["NPC"].i32)

    tmp = tempfile.mkdtemp(prefix="nf_trace_")
    t0 = time.perf_counter()
    with jax.profiler.trace(tmp):
        for _ in range(args.ticks):
            k.run_device(1, reconcile=False)
        jax.block_until_ready(k.state.classes["NPC"].i32)
    elapsed = time.perf_counter() - t0

    with tarfile.open(args.out, "w:gz") as tar:
        tar.add(tmp, arcname="trace")
    n_files = sum(len(fs) for _, _, fs in os.walk(tmp))
    print(json.dumps({
        "metric": "trace_capture",
        "entities": args.entities,
        "ticks": args.ticks,
        "traced_wall_s": round(elapsed, 3),
        "files": n_files,
        "archive": os.path.basename(args.out),
        "bytes": os.path.getsize(args.out),
        "device": str(jax.devices()[0]),
    }))


if __name__ == "__main__":
    main()
