"""TelemetryModule: one registry per role/world, every source wired in.

Sources absorbed (all sampled lazily at scrape time — a role that nobody
scrapes pays nothing per frame):

- frame latency: :class:`~noahgameframe_tpu.utils.metrics.TickMetrics`
  observing into a registry-owned histogram (``nf_frame_seconds``), plus
  precomputed quantile gauges (``nf_frame_latency_ms``) so dashboards
  don't need server-side histogram math;
- the kernel's ON-DEVICE counter bank (``nf_tick_counters_total`` /
  ``nf_tick_counters``): events fired, diff cells, deaths, combat hits,
  AOI/stencil overflow drops — accumulated inside the jitted tick and
  decoded from the summary vector the host already fetches (zero extra
  device syncs; kernel/kernel.py);
- per-opcode net counters (``nf_net_msgs_total`` / ``nf_net_bytes_total``
  with direction/link/opcode labels) from every NetServerModule /
  NetClientModule pool the role owns;
- the memory census (``nf_census`` per kind, ``nf_device_bytes``) and
  per-class live-entity gauges.

``mount(http)`` exposes the registry at ``/metrics`` on any
net/http.py HttpServer; ServerRole.serve_metrics() spins up a dedicated
one for roles without a status server.
"""

from __future__ import annotations

import json
from typing import Dict, Iterable, Optional, Tuple

from ..kernel.module import Module
from .costbook import CostBook
from .registry import MetricsRegistry, CONTENT_TYPE  # noqa: F401
from .tracing import SpanTracer


class TelemetryModule(Module):
    name = "TelemetryModule"

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 window: int = 512) -> None:
        super().__init__()
        self.registry = registry if registry is not None else MetricsRegistry()
        self.tracer = SpanTracer(enabled=False)
        # import here: utils.metrics imports telemetry.registry
        from ..utils.metrics import MemoryCensus, TickMetrics

        self.tick = TickMetrics(
            window=window,
            histogram=self.registry.histogram(
                "nf_frame_seconds", "main-loop frame latency (seconds)",
                window=window,
            ),
        )
        self.census = MemoryCensus()
        # the device cost observatory: replaced by the kernel's book in
        # attach_kernel so one ledger covers kernel + serve-edge entries;
        # roles without a kernel keep this (empty) one so /costbook is
        # uniform across all five roles
        self.costbook = CostBook()
        self._net_sources: Dict[str, object] = {}
        self._pool_sources: Dict[str, object] = {}  # link -> NetClientModule
        self._chaos_sources: list = []  # (link prefix, ChaosDirector)
        self._kernel_attached = False
        self._role_attached = False
        self.registry.register_callback(
            "nf_frame_latency_ms", self._frame_quantiles, kind="gauge",
            help="frame latency quantiles in ms (exact, window-based)",
        )
        self.registry.register_callback(
            "nf_net_msgs_total", lambda: self._net_samples(0),
            kind="counter", help="messages per link/direction/opcode",
        )
        self.registry.register_callback(
            "nf_net_bytes_total", lambda: self._net_samples(1),
            kind="counter", help="payload bytes per link/direction/opcode",
        )
        self.registry.register_callback(
            "nf_relay_msgs_total", lambda: self._relay_samples(0),
            kind="counter", help="proxy-forwarded messages per link/opcode",
        )
        self.registry.register_callback(
            "nf_relay_seconds_total", lambda: self._relay_samples(1),
            kind="counter",
            help="cumulative proxy forward latency per link/opcode",
        )
        self.registry.register_callback(
            "nf_reconnects_total", self._pool_samples, kind="counter",
            help="re-dial attempts after a link failure, per pool/server",
        )
        self.registry.register_callback(
            "nf_chaos_faults_total", self._chaos_samples, kind="counter",
            help="injected faults per link and kind (net/chaos.py)",
        )
        # cost observatory (telemetry/costbook.py): lambdas read
        # self.costbook dynamically so attach_kernel's adoption of the
        # kernel's book retargets every series
        self.registry.register_callback(
            "nf_recompiles_total",
            lambda: self.costbook.recompile_samples(), kind="counter",
            help="jit retraces per entry with cause attribution",
        )
        self.registry.register_callback(
            "nf_compiles_total",
            lambda: self.costbook.compile_samples(0), kind="counter",
            help="XLA compiles per jit entry (first trace included)",
        )
        self.registry.register_callback(
            "nf_compile_seconds_total",
            lambda: self.costbook.compile_samples(1), kind="counter",
            help="cumulative lowering+compile wall seconds per entry",
        )
        self.registry.register_callback(
            "nf_entry_flops",
            lambda: self.costbook.cost_samples("flops"), kind="gauge",
            help="cost_analysis FLOPs of each entry's latest executable",
        )
        self.registry.register_callback(
            "nf_entry_bytes_accessed",
            lambda: self.costbook.cost_samples("bytes_accessed"),
            kind="gauge",
            help="cost_analysis bytes accessed per entry (latest)",
        )
        self.registry.register_callback(
            "nf_entry_temp_bytes",
            lambda: self.costbook.cost_samples("temp_bytes"), kind="gauge",
            help="memory_analysis temp buffer bytes per entry (latest)",
        )
        self.registry.register_callback(
            "nf_hbm_bytes_in_use", self._hbm_samples_live, kind="gauge",
            help="device allocator live bytes (memory_stats; "
                 "live-array fallback on backends without stats)",
        )
        self.registry.register_callback(
            "nf_hbm_peak_bytes", lambda: self._hbm_samples_cached(
                "peak_bytes"), kind="gauge",
            help="device allocator peak bytes since process start",
        )
        self.registry.register_callback(
            "nf_hbm_bytes_limit", lambda: self._hbm_samples_cached(
                "limit_bytes"), kind="gauge",
            help="device allocator capacity (0 when unknown)",
        )

    # ------------------------------------------------------------ sources
    def _hbm_samples_live(self) -> Iterable[Tuple[dict, float]]:
        """Scrape-time census pass (the periodic frame-loop sampling in
        GameRole covers unscraped stretches); the peak/limit gauges read
        the refreshed cache so one scrape is one census."""
        hbm = self.costbook.hbm_sample()
        yield ({}, float(hbm["live_bytes"]))
        for d in hbm["per_device"]:
            yield ({"device": d["device"]}, float(d["live_bytes"]))

    def _hbm_samples_cached(self, key: str) -> Iterable[Tuple[dict, float]]:
        hbm = self.costbook.hbm or self.costbook.hbm_sample()
        yield ({}, float(hbm.get(key, 0)))
        for d in hbm.get("per_device", ()):
            yield ({"device": d["device"]}, float(d.get(key, 0)))

    def _frame_quantiles(self) -> Iterable[Tuple[dict, float]]:
        h = self.tick.hist
        for q in (50, 95, 99):
            yield ({"quantile": f"p{q}"}, h.percentile(q) * 1e3)

    def add_net_source(self, link: str, counters) -> None:
        """Register a NetCounters (net/module.py) under a link label."""
        self._net_sources[str(link)] = counters

    def add_pool_source(self, link: str, pool) -> None:
        """Register a NetClientModule whose ``retries_total`` feeds
        ``nf_reconnects_total`` under a link label."""
        self._pool_sources[str(link)] = pool

    def _pool_samples(self) -> Iterable[Tuple[dict, float]]:
        for link, pool in sorted(self._pool_sources.items()):
            for sid in sorted(pool.retries_total):
                yield (
                    {"link": link, "server_id": str(sid)},
                    pool.retries_total[sid],
                )

    def add_chaos_source(self, director, prefix: str = "") -> None:
        """Register a ChaosDirector (net/chaos.py); only links starting
        with `prefix` are exposed (one role sees its own links)."""
        self._chaos_sources.append((str(prefix), director))

    def _chaos_samples(self) -> Iterable[Tuple[dict, float]]:
        for prefix, director in self._chaos_sources:
            for link in sorted(director.counts):
                if prefix and not link.startswith(prefix):
                    continue
                for kind, v in sorted(director.counts[link].items()):
                    yield ({"link": link, "kind": kind}, v)

    def _net_samples(self, which: int) -> Iterable[Tuple[dict, float]]:
        for link, c in sorted(self._net_sources.items()):
            for direction, d in (
                ("in", (c.in_msgs, c.in_bytes)[which]),
                ("out", (c.out_msgs, c.out_bytes)[which]),
            ):
                for opcode in sorted(d):
                    yield (
                        {"link": link, "direction": direction,
                         "opcode": str(opcode)},
                        d[opcode],
                    )

    def _relay_samples(self, which: int) -> Iterable[Tuple[dict, float]]:
        """which: 0 = relayed message count, 1 = forward latency seconds.
        Sourced from NetCounters.count_relay (net/module.py) — only the
        proxy feeds these, so most roles yield nothing."""
        for link, c in sorted(self._net_sources.items()):
            msgs = getattr(c, "relay_msgs", None)
            if not msgs:
                continue
            for opcode in sorted(msgs):
                v = (msgs[opcode] if which == 0
                     else c.relay_ns.get(opcode, 0) / 1e9)
                yield ({"link": link, "opcode": str(opcode)}, v)

    def attach_role(self, role) -> None:
        """Wire a ServerRole: identity gauge + its net counter sources.
        (Frame timing attaches by the role adopting ``self.tick``.)"""
        if self._role_attached:
            return
        self._role_attached = True
        info = self.registry.gauge(
            "nf_role_info", "role identity (value is always 1)",
            ("role", "server_id"),
        )
        info.set(1, role=type(role).__name__,
                 server_id=str(role.config.server_id))
        self.add_net_source("server", role.server.counters)

    def attach_kernel(self, kernel) -> None:
        """Wire a Kernel: counter bank, tick count, entities, census."""
        if self._kernel_attached or kernel is None:
            return
        self._kernel_attached = True
        self.census.kernel = kernel
        kernel.tracer = self.tracer
        # one CostBook per world: the kernel built its own at
        # construction (bare-kernel benches record into it before any
        # telemetry exists); adopt it so role-level entries (serve,
        # interest) and kernel entries share a ledger
        kbook = getattr(kernel, "costbook", None)
        if kbook is not None:
            self.costbook = kbook
        else:
            kernel.costbook = self.costbook
        reg = self.registry
        reg.register_callback(
            "nf_ticks_total", lambda: kernel.tick_count, kind="counter",
            help="world ticks advanced (tick + run_device)",
        )
        reg.register_callback(
            "nf_tick_counters_total",
            lambda: (
                ({"counter": k}, v)
                for k, v in sorted(kernel.counter_totals.items())
            ),
            kind="counter",
            help="on-device counter bank, cumulative over observed ticks",
        )
        reg.register_callback(
            "nf_tick_counters",
            lambda: (
                ({"counter": k}, v)
                for k, v in sorted(kernel.last_counters.items())
            ),
            kind="gauge",
            help="on-device counter bank, last observed tick",
        )
        reg.register_callback(
            "nf_entities_live",
            lambda: (
                ({"class": c}, kernel.store.live_count(c))
                for c in kernel.store.class_order
            )
            if kernel.store is not None
            else (),
            kind="gauge", help="live entity rows per class",
        )
        reg.register_callback(
            "nf_census",
            lambda: (
                ({"kind": k}, v) for k, v in sorted(self.census.census().items())
            ),
            kind="gauge", help="memory census: live objects per kind",
        )
        reg.register_callback(
            "nf_device_bytes", self.census.device_bytes, kind="gauge",
            help="bytes held by live device arrays (best effort)",
        )
        # Verlet neighbor-cache effectiveness (ops/verlet.py): caches ride
        # WorldState.aux under "verlet/<grid>"; sampled lazily at scrape
        # time (np.asarray of three i32 scalars per grid) so the knob
        # costs nothing when nobody scrapes
        reg.register_callback(
            "nf_grid_rebuilds_total", lambda: self._verlet_samples(0),
            kind="counter",
            help="cell-table sort+build executions per Verlet-cached grid",
        )
        reg.register_callback(
            "nf_grid_rebuild_interval_ticks",
            lambda: self._verlet_samples(3), kind="gauge",
            help="mean ticks between rebuilds (builds+reuses per build)",
        )
        reg.register_callback(
            "nf_grid_staleness_ticks", lambda: self._verlet_samples(2),
            kind="gauge",
            help="ticks since each Verlet grid's last rebuild (cache age)",
        )

    def _verlet_samples(self, which: int) -> Iterable[Tuple[dict, float]]:
        """which: 0=rebuilds, 1=reuses, 2=age, 3=mean rebuild interval."""
        import numpy as np

        kernel = self.census.kernel
        state = getattr(kernel, "state", None)
        for key, cache in sorted((getattr(state, "aux", None) or {}).items()):
            if not key.startswith("verlet/"):
                continue
            grid = key[len("verlet/"):]
            if which == 3:
                reb = float(np.asarray(cache.rebuilds))
                reu = float(np.asarray(cache.reuses))
                yield ({"grid": grid}, (reb + reu) / max(reb, 1.0))
            else:
                v = (cache.rebuilds, cache.reuses, cache.age)[which]
                yield ({"grid": grid}, float(np.asarray(v)))

    # ------------------------------------------------- module lifecycle
    def after_init(self) -> None:
        # when registered in a world's PluginManager the kernel is bound
        # by now (pm runs after_init post kernel.build)
        self.attach_kernel(self.kernel)
        if self.census.log_module is None and self.kernel is not None:
            # discover a LogModule sibling for census probe failures
            pass

    # ------------------------------------------------------------ expose
    def costbook_handler(self, _path=None, _params=None):
        """HTTP handler for ``/costbook``: the book's full snapshot with
        a fresh HBM census folded in."""
        self.costbook.hbm_sample()
        body = json.dumps(self.costbook.snapshot()).encode()
        return 200, "application/json", body

    def mount(self, http) -> None:
        """Route /metrics and /costbook on an existing HttpServer."""
        http.route("/metrics", self.registry.handler)
        http.route("/costbook", self.costbook_handler)

    def exposition(self) -> str:
        return self.registry.exposition()
