#!/usr/bin/env python
"""One process, one run of one cell of BENCHMARK.json.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's world from --seed, warms up only that cell's shapes,
measures for --seconds, holds what the timed path produced against the
plain reference, and prints ONE JSON object as the last line of stdout:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "compared": {...}}

--trace 0 reports the cell's end-to-end metrics with the profiler off;
--trace 1 reports its per-layer metrics from a traced window.  Without a
TPU (or with fewer chips than the cell asks for) it exits non-zero and
prints no result line.  `--rehearse` is the only way to run on the CPU:
tiny sizes, every metric under a `rehearsal_` name; it proves the control
flow and nothing about speed.

Everything a cell is made of is data (see benchmarks/README.md): the
manifest names the configuration's file and the traffic mix; the mix
names the driver; each per-layer metric has a reader of its own.
"""

from __future__ import annotations

import time

PROCESS_T0_NS = time.perf_counter_ns()  # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import manifest as manifest_mod  # noqa: E402
from benchmarks.harness.run import Run, RunFailed  # noqa: E402

EXIT_NO_CHIP = 3


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes; rehearsal_ names")
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the traced window's xplane.pb into DIR, with "
                         "the instruction -> op_name map beside it")
    ap.add_argument("--control", action="store_true",
                    help="also print what the lower-precision control "
                         "reads (for setting limits; not part of a run)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    man = manifest_mod.Manifest(args.manifest)
    cell = man.cell(args.workload, rehearse=args.rehearse)
    for k, v in cell.mix.get("env", {}).items():
        os.environ[k] = str(v)  # knobs the program reads at start-up
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"benchmark: no accelerator: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    if args.rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        if devices[0].platform != "tpu" or len(devices) < cell.chips:
            print(f"benchmark: cell {cell.name!r} needs {cell.chips} TPU "
                  f"chip(s); jax.devices() is {len(devices)} x "
                  f"{devices[0].platform}; --rehearse runs the control "
                  "flow on the CPU", file=sys.stderr)
            return EXIT_NO_CHIP
        from noahgameframe_tpu.utils.platform import init_compile_cache

        print(f"compile cache: {init_compile_cache()}", file=sys.stderr)
    devices = devices[:cell.chips]

    trace_dir = os.path.join(ROOT, ".bench_trace", cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), rehearse=args.rehearse,
              control=args.control, devices=devices, trace_dir=trace_dir,
              process_t0_ns=PROCESS_T0_NS)
    driver = manifest_mod.load_module(cell.driver_path,
                                      "driver_" + cell.mix["driver"])
    try:
        driver.run(run)
        result = run.result(man)
        if args.keep_trace and run.trace_file:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(run.trace_file, args.keep_trace)
            with open(os.path.join(args.keep_trace, "hlo_scopes.json"),
                      "w") as f:
                json.dump(run.hlo_scopes, f)
    except RunFailed as e:
        print(f"benchmark: run failed: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    for line in run.notes:
        print(line)
    compared = result["compared"]
    print("compared (number, limit): " + json.dumps(compared),
          file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
