"""Host-side spans: one seam, one vocabulary (``nf.*``), one clock.

Two timelines answer "where does a tick go", and both carry the same
names:

- DEVICE: the kernel wraps every phase of the compiled tick in
  ``jax.named_scope`` (``nf.schedule``, ``nf.phase.*``, ``nf.aoe.*``,
  ``nf.diff``, ``nf.digest``, ``nf.summary``); the scopes ride the HLO
  metadata, so a profiler capture attributes device time to them.
- HOST: :func:`span` is the only way the program opens a host span.
  It enters a ``jax.profiler.TraceAnnotation("nf." + name, **args)``,
  which lands in the ``/host:CPU`` plane of the same ``xplane.pb`` as
  the device's ``XLA Ops``, on the same clock.  An open profiler session
  (``jax.profiler.start_trace``) is the one switch: with none open a
  span is a shared no-op context manager, and without jax (the client
  SDK imports this module) it always is.

:class:`SpanTracer` adds the operator's ring: with ``enabled`` set,
``tracer.span`` ALSO records the span (same ``nf.*`` name, wall clock)
into a fixed-size buffer that exports Chrome trace-event JSON for
``chrome://tracing`` / https://ui.perfetto.dev — no profiler needed.
scripts/export_trace.py shows that capture workflow;
docs/OBSERVABILITY.md lists the vocabulary.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Dict, List, Optional

try:
    from jax.profiler import TraceAnnotation as _Annotation
except ImportError:  # the SDK runs without jax: every span is a no-op
    _Annotation = None

PREFIX = "nf."
_NULL_CTX = contextlib.nullcontext()


def span(name: str, **args):
    """Context manager: the host span ``nf.<name>`` in the profiler's
    trace while a profiler session is open, else a shared no-op.  Keep
    it out of loops over entities or over all sessions."""
    if _Annotation is None or not _Annotation.is_enabled():
        return _NULL_CTX
    return _Annotation(PREFIX + name, **args)


class _Span:
    """Re-entrant-safe timed block writing one complete ("X") event to
    the ring, around the profiler's span of the same name."""

    __slots__ = ("tracer", "name", "args", "t0", "inner")

    def __init__(self, tracer: "SpanTracer", name: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.t0 = 0
        self.inner = span(name, **args)

    def __enter__(self) -> "_Span":
        self.inner.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self.tracer._record(PREFIX + self.name, self.t0, t1 - self.t0,
                            self.args or None)
        self.inner.__exit__(*exc)


class SpanTracer:
    """Fixed-capacity ring buffer of (name, ts, dur) spans."""

    def __init__(self, capacity: int = 65536, enabled: bool = False) -> None:
        self.capacity = int(capacity)
        self.enabled = bool(enabled)
        self._events: List[tuple] = []  # (name, ts_ns, dur_ns, tid, args)
        self._head = 0  # next write slot once the ring is full
        self._lock = threading.Lock()
        self._epoch_ns = time.perf_counter_ns()

    # ------------------------------------------------------------ record
    def span(self, name: str, **args):
        """:func:`span`, and with the ring ``enabled`` a record there."""
        if not self.enabled:
            return span(name, **args)
        return _Span(self, name, args)

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker event."""
        if not self.enabled:
            return
        self._record(PREFIX + name, time.perf_counter_ns(), -1, args or None)

    def _record(self, name: str, t0_ns: int, dur_ns: int,
                args: Optional[dict]) -> None:
        ev = (name, t0_ns, dur_ns, threading.get_ident(), args)
        with self._lock:
            if len(self._events) < self.capacity:
                self._events.append(ev)
            else:
                self._events[self._head] = ev
                self._head = (self._head + 1) % self.capacity

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._head = 0
            self._epoch_ns = time.perf_counter_ns()

    def __len__(self) -> int:
        return len(self._events)

    @property
    def epoch_ns(self) -> int:
        """perf_counter_ns at construction/clear — ts=0 in the export.
        To merge same-clock tracers, pass
        ``offset_us=(tracer.epoch_ns - ref_epoch_ns) / 1e3``."""
        return self._epoch_ns

    # ------------------------------------------------------------ export
    def events(self) -> List[tuple]:
        """Chronological (name, ts_ns, dur_ns, tid, args) tuples."""
        with self._lock:
            ring = self._events[self._head:] + self._events[:self._head]
        return ring

    def chrome_trace(self, process_name: str = "noahgameframe_tpu",
                     pid: Optional[int] = None,
                     offset_us: float = 0.0) -> dict:
        """Chrome trace-event JSON object (Perfetto/about:tracing).

        ``pid`` overrides the OS pid so several tracers captured in one
        process (LocalCluster roles) still render as distinct Perfetto
        process tracks; ``offset_us`` shifts all timestamps onto a
        reference clock (feed it a ClockSync offset / 1e3) so a
        multi-process merge lines up — see
        :func:`noahgameframe_tpu.telemetry.pipeline.merge_chrome_traces`.
        """
        pid = os.getpid() if pid is None else int(pid)
        tid_map: Dict[int, int] = {}
        trace_events: List[dict] = [
            {
                "ph": "M", "pid": pid, "tid": 0,
                "name": "process_name",
                "args": {"name": process_name},
            }
        ]
        for name, ts_ns, dur_ns, tid, args in self.events():
            small_tid = tid_map.setdefault(tid, len(tid_map) + 1)
            ev = {
                "name": name,
                "pid": pid,
                "tid": small_tid,
                # trace-event timestamps are microseconds
                "ts": (ts_ns - self._epoch_ns) / 1000.0 + offset_us,
            }
            if dur_ns < 0:
                ev["ph"] = "i"
                ev["s"] = "t"
            else:
                ev["ph"] = "X"
                ev["dur"] = dur_ns / 1000.0
            if args:
                ev["args"] = {k: _jsonable(v) for k, v in args.items()}
            trace_events.append(ev)
        return {"traceEvents": trace_events, "displayTimeUnit": "ms"}

    def export(self, path: str, process_name: str = "noahgameframe_tpu") -> int:
        """Write the Chrome trace JSON; returns the span count."""
        doc = self.chrome_trace(process_name)
        with open(path, "w") as f:
            json.dump(doc, f)
        return len(doc["traceEvents"]) - 1  # minus the metadata event


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)
