"""What a driver is handed and what the last line is made from.

A driver (`benchmarks/drivers/<name>.py`) exposes `run(run: Run)`.  It
builds its cell, calls `run.setup_done()`, measures inside
`with run.window():`, frees the program's state and then makes the
comparison, handing each number compared to `run.hold(name, value,
limit)`.  Along the way it leaves

    run.e2e[name]       the end-to-end metrics (whole window, all work)
    run.series[name]    per-tick / per-frame samples, for the readers
    run.counters[name]  counts, for the readers
    run.hlo_scopes      instruction -> op_name of the timed programs

and the harness does the rest: the profiler around the window, the peak
memory after it, the trace reduction, the per-layer readers and the last
line.  A per-layer reader is `read(run, trace) -> number or None`; None
(nothing to read in this cell) leaves the metric out of the line.
"""

from __future__ import annotations

import contextlib
import glob
import json
import os
from typing import Any, Dict, List, Optional

from . import clock, xplane

REHEARSAL_PREFIX = "rehearsal_"


class RunFailed(RuntimeError):
    """The run cannot give a result line (set-up failed, no window)."""


class Run:
    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 rehearse: bool, control: bool, devices, trace_dir: str,
                 process_t0_ns: int):
        self.cell = cell
        self.config: Dict[str, Any] = cell.config
        self.mix: Dict[str, Any] = cell.mix
        self.seed = int(seed)
        self.trace = trace
        self.rehearse = rehearse
        self.control = control
        self.devices = devices
        self.trace_dir = trace_dir
        self.process_t0_ns = process_t0_ns
        # a traced run measures a short window of its own
        self.seconds = float(seconds)
        if trace:
            self.seconds = min(self.seconds,
                               float(self.mix.get("trace_seconds", 10)))
        self.notes: List[str] = []
        self.e2e: Dict[str, float] = {}
        self.series: Dict[str, List[float]] = {}
        self.counters: Dict[str, float] = {}
        self.hlo_scopes: Dict[str, str] = {}
        self.compared: Dict[str, List[float]] = {}
        self.attempted = 0
        self.failed = 0
        self.window_ns: Optional[List[int]] = None
        self.memory_peak_bytes = 0
        self.reduced: Optional[xplane.Reduced] = None
        self.trace_file: Optional[str] = None
        self._tracing = False

    # ---------------------------------------------------------- set-up
    def note(self, what: str, **fields) -> None:
        """An earlier line of stdout: anything worth a number that is
        not a metric."""
        self.notes.append(json.dumps({"note": what, **fields}))

    def setup_done(self) -> None:
        self.e2e["setup_s"] = (clock.now_ns() - self.process_t0_ns) / 1e9

    # ---------------------------------------------------------- window
    def annotate(self, name: str):
        """A host span in the profiler's own trace (free when off)."""
        if not self._tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(xplane.ANNOTATION_PREFIX + name)

    @contextlib.contextmanager
    def window(self):
        import jax

        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # our annotations, not every call
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._tracing = True
        t0 = clock.now_ns()
        try:
            with self.annotate("window"):
                yield
        finally:
            t1 = clock.now_ns()
            if self._tracing:
                self._tracing = False
                jax.profiler.stop_trace()
            self.window_ns = [t0, t1]
            self.memory_peak_bytes = _memory_peak(self.devices)

    # ------------------------------------------------------ comparison
    def hold(self, name: str, value: float, limit: float) -> bool:
        """One number compared, beside its limit (value <= limit)."""
        self.compared[name] = [value, limit]
        return value <= limit

    @property
    def correct(self) -> bool:
        return bool(self.compared) and all(
            v <= lim for v, lim in self.compared.values())

    # ------------------------------------------------------------ line
    def _reduce(self) -> None:
        if not self.trace or self.reduced is not None:
            return
        found = glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not found:
            raise RunFailed("the traced window left no xplane.pb")
        self.trace_file = sorted(found)[-1]
        self.reduced = xplane.reduce_trace(self.trace_file)
        if self.reduced.busy_s <= 0:
            raise RunFailed("no operation ran on the device in the "
                            "traced window")

    def result(self, man) -> Dict[str, Any]:
        if self.window_ns is None:
            raise RunFailed("the driver measured no window")
        self._reduce()
        metrics: Dict[str, Dict[str, Any]] = {}
        if self.trace:
            for m in self.cell.per_layer:
                value = man.reader(m["name"])(self, self.reduced)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
        else:
            for m in self.cell.end_to_end:
                if m["name"] not in self.e2e:
                    raise RunFailed(f"driver reported no {m['name']}")
                metrics[m["name"]] = {"value": float(self.e2e[m["name"]]),
                                      "unit": m["unit"]}
        if self.rehearse:
            metrics = {REHEARSAL_PREFIX + k: v for k, v in metrics.items()}
        dev = self.devices[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(self.devices),
                  "memory_peak_bytes": self.memory_peak_bytes}
        out: Dict[str, Any] = {
            "correct": self.correct, "attempted": self.attempted,
            "failed": self.failed, "metrics": metrics, "device": device}
        if self.reduced is not None:
            device["busy_s"] = self.reduced.busy_s
            device["window_s"] = self.reduced.window_s
            out["breakdown"] = {"device_ops": self.reduced.device_ops,
                                "idle_gaps": self.reduced.idle_gaps}
        out["compared"] = self.compared
        return out


def _memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # a backend that keeps no such statistics
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
