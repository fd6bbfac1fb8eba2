"""CostBook: the device cost observatory for every jit entry point.

PR 7's StageClock answers "where did the frame's *time* go"; the
CostBook answers "what did the compiled program *cost*" — and keeps the
two joinable.  Every jit entry point (kernel tick, fused run window,
serve prepare/scan/query, interest step/query, the spatial slab, the
profile scripts' pass list) routes through :meth:`CostBook.wrap`, which
replaces the bare ``jax.jit(fn)`` dispatch with an AOT-compiled cache
keyed by the call's abstract signature.  Per entry it records:

- **lowering + compile wall time** (``jit.lower()`` and
  ``lowered.compile()`` timed separately);
- **compiled cost**: ``cost_analysis()`` FLOPs / bytes-accessed and
  ``memory_analysis()`` argument/output/temp/alias bytes;
- **donation accounting**: which argnums donate and how many bytes the
  donated buffers alias back into the output;
- **every retrace, with cause attribution**: the new signature is
  diffed against the previous one leaf by leaf, so the event says
  *which* arg's shape/dtype/weak-type (or declared-static value)
  changed — surfaced as ``nf_recompiles_total{entry,cause}``.

Retraces are either bugs or sanctioned **generation bumps** (bucket
auto-resize doubling a cell table, ``Kernel.invalidate()``'s
``_trace_gen``).  Sanctioned sites call :meth:`generation_bump`; the
recompile-free soak gate (tests/test_costbook.py) marks the book after
warmup and asserts every later compile is covered by a bump —
``unexplained_since()`` is that query.

The book also owns the **HBM census**: :meth:`hbm_sample` reads
``device.memory_stats()`` live/peak/limit bytes per device (the real
allocator's numbers), falling back to summing ``jax.live_arrays()`` on
backends that expose no stats (CPU) with a host-tracked peak — replacing
the probe-once MemoryCensus guess with a periodic gauge
(``nf_hbm_*``, sampled every ``HBM_SAMPLE_FRAMES`` served frames and at
every scrape).

Finally :func:`roofline_fold` joins CostBook FLOPs/bytes with
StageClock device seconds (``NF_STAGE_TIMING=1``) into achieved-vs-peak
fractions per stage — the measured roofline
(``scripts/roofline_report.py``, ``docs/ROOFLINE.md``).

Everything here is host-side bookkeeping around the dispatch; nothing
reaches the trace, so observability on vs off cannot perturb the
simulation (same contract as the frame observatory).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax
from jax import tree_util

__all__ = [
    "CostBook", "CostEntry", "roofline_fold", "PEAKS",
    "HBM_SAMPLE_FRAMES",
]

#: served-frame cadence of the periodic HBM census (GameRole.execute)
HBM_SAMPLE_FRAMES = 64

#: retrace events kept in the book's ring (the web monitor's feed)
_EVENT_RING = 128

#: compile records kept per book (the soak gate reads these; a healthy
#: run compiles a few dozen programs, so the cap is a runaway backstop)
_COMPILE_LOG_CAP = 4096

#: peak FLOPs/s and HBM bytes/s for the roofline fold, keyed by
#: ``jax.Device.device_kind``.  A device that is not in the table is an
#: error in :func:`roofline_fold`, not a default: a fraction of some
#: other device's peak is not a measurement.
PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "flops_per_s": 1.97e14, "bytes_per_s": 8.19e11,
        "hbm_bytes": 16 * 1024 ** 3,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 819 GB/s HBM, 16 GB per chip",
    },
}


def _leaf_sharding(x):
    """Hashable input-sharding component of a leaf's signature.

    AOT-compiled executables are pinned to their argument shardings: the
    same (shape, dtype) arriving replicated vs NamedSharding'd over a
    mesh (e.g. kernel state after the first sharded-tick/migration
    round lands it on the mesh) needs a DIFFERENT executable, and
    handing it the cached one is a pxla ValueError, not a retrace."""
    s = getattr(x, "sharding", None)
    if s is None:
        return None
    try:
        hash(s)
        return s
    except TypeError:  # pragma: no cover - exotic sharding types
        return str(s)


def _leaf_sig(x) -> Tuple:
    """Abstract signature of one pytree leaf — cheap on the hot path.

    Python scalars collapse to their type (jit retraces on a *type*
    change, not a value change); arrays to (shape, dtype, weak_type,
    sharding)."""
    if x is None or isinstance(x, (bool, int, float, complex, str, bytes)):
        return ("py", type(x).__name__)
    aval = getattr(x, "aval", None)
    if aval is not None:
        return (tuple(aval.shape), str(aval.dtype),
                bool(getattr(aval, "weak_type", False)),
                str(_leaf_sharding(x)))
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype), False, str(None))
    return ("py", type(x).__name__)


def _leaf_key(x):
    """Hot-path cache key for one leaf.  jax arrays key on their aval
    object (hashable, equal iff shape/dtype/weak-type equal) plus their
    committed sharding, so the per-call cost is two attribute reads
    instead of the shape/dtype stringification `_leaf_sig` does;
    everything else falls back to the descriptive sig.  Equal keys imply
    equal `_leaf_sig`s, so the compile ledger and cause attribution are
    unchanged — only the dict-lookup key is cheaper."""
    aval = getattr(x, "aval", None)
    if aval is not None:
        return (aval, _leaf_sharding(x))
    return _leaf_sig(x)


def _leaf_bytes(x) -> int:
    n = getattr(x, "nbytes", None)
    return int(n) if n is not None else 0


class CostEntry:
    """One named jit entry point's ledger."""

    def __init__(self, name: str, stage: Optional[str] = None) -> None:
        self.name = name
        self.stage = stage
        self.calls = 0
        self.compiles = 0
        self.lower_s_total = 0.0
        self.compile_s_total = 0.0
        self.causes: Dict[str, int] = {}
        self.last: Dict[str, Any] = {}
        self._last_sig = None   # (treedef, leaf sigs, static reprs)
        self._last_paths: Optional[List[str]] = None

    @property
    def recompiles(self) -> int:
        return max(0, self.compiles - 1)

    def attribute(self, sig, args) -> str:
        """Why did this signature miss the cache?  Diffs against the
        PREVIOUS signature leaf by leaf; paths are computed lazily (only
        when a compile actually happens)."""
        prev = self._last_sig
        if prev is None:
            return "first"
        if prev[2] != sig[2]:
            for i, (a, b) in enumerate(zip(prev[2], sig[2])):
                if a != b:
                    return f"static:arg{i}"
            return "static:arity"
        if prev[0] != sig[0]:
            return "tree-structure"
        paths = self._last_paths or [f"leaf{i}"
                                     for i in range(len(sig[1]))]
        for p, a, b in zip(paths, prev[1], sig[1]):
            if a == b:
                continue
            if a[0] == "py" or b[0] == "py":
                return f"pytype:{p}"
            if a[0] != b[0]:
                return f"shape:{p}"
            if a[1] != b[1]:
                return f"dtype:{p}"
            if a[2] != b[2]:
                return f"weak-type:{p}"
            return f"sharding:{p}"
        # identical signature: a fresh dispatcher re-wrapped the entry —
        # the retrace is about traced CONSTANTS (invalidate/set_phases
        # close over new tables), not about the arguments
        return "rewrap"

    def note_compile(self, sig, args, dyn_args) -> None:
        self._last_sig = sig
        flat, _ = tree_util.tree_flatten_with_path(dyn_args)
        self._last_paths = [tree_util.keystr(p) for p, _ in flat]

    def to_dict(self) -> Dict[str, Any]:
        return {
            "stage": self.stage,
            "calls": self.calls,
            "compiles": self.compiles,
            "recompiles": self.recompiles,
            "lower_ms_total": round(self.lower_s_total * 1e3, 3),
            "compile_ms_total": round(self.compile_s_total * 1e3, 3),
            "causes": dict(self.causes),
            "last": dict(self.last),
        }


class CostBook:
    """Registry of :class:`CostEntry` ledgers + HBM census + the
    sanctioned-retrace generation counter."""

    def __init__(self) -> None:
        self.entries: Dict[str, CostEntry] = {}
        self.generation = 0
        self.gen_events: List[Dict[str, Any]] = []
        self.compile_log: List[Dict[str, Any]] = []
        self.events: List[Dict[str, Any]] = []  # retrace ring
        self._seq = 0
        self.hbm: Dict[str, Any] = {}
        self._hbm_samples = 0
        self._fallback_peak = 0

    # --------------------------------------------------------- entries
    def entry(self, name: str, stage: Optional[str] = None) -> CostEntry:
        e = self.entries.get(name)
        if e is None:
            e = self.entries[name] = CostEntry(name, stage=stage)
        elif stage is not None and e.stage is None:
            e.stage = stage
        return e

    def wrap(self, name: str, fn: Callable, *,
             static_argnums: Tuple[int, ...] = (),
             donate_argnums: Tuple[int, ...] = (),
             stage: Optional[str] = None,
             jit_kwargs: Optional[Dict[str, Any]] = None) -> Callable:
        """``jax.jit(fn, ...)`` with the ledger attached.

        Returns a dispatcher with identical call semantics (donation
        included) that keeps its own signature→executable cache: every
        miss is lowered + compiled AOT under a timer, its
        cost/memory analysis recorded, and its cause attributed.  The
        nf-lint callgraph treats ``*.wrap("name", fn)`` as a jit root,
        so trace-safety coverage survives the indirection."""
        if isinstance(static_argnums, int):
            static_argnums = (static_argnums,)
        if isinstance(donate_argnums, int):
            donate_argnums = (donate_argnums,)
        static_set = frozenset(static_argnums)
        entry = self.entry(name, stage=stage)
        jkw = dict(jit_kwargs or {})
        jfn = jax.jit(fn, static_argnums=static_argnums,
                      donate_argnums=donate_argnums, **jkw)
        cache: Dict[Any, Any] = {}
        book = self

        tree_flatten = tree_util.tree_flatten
        leaf_key = _leaf_key
        cache_get = cache.get

        def dispatch(*args):
            if static_set:
                dyn = tuple(a for i, a in enumerate(args)
                            if i not in static_set)
                statics = tuple(repr(args[i]) for i in sorted(static_set)
                                if i < len(args))
            else:
                dyn = args
                statics = ()
            leaves, treedef = tree_flatten(dyn)
            key = (treedef, tuple(map(leaf_key, leaves)), statics)
            compiled = cache_get(key)
            if compiled is None:
                sig = (treedef, tuple(_leaf_sig(x) for x in leaves),
                       statics)
                compiled = book._compile(entry, jfn, args, dyn, sig,
                                         donate_argnums)
                cache[key] = compiled
            entry.calls += 1
            return compiled(*dyn)

        dispatch.costbook_entry = entry
        return dispatch

    def _compile(self, entry: CostEntry, jfn, args, dyn_args, sig,
                 donate_argnums) -> Callable:
        cause = entry.attribute(sig, args)
        t0 = time.perf_counter()
        lowered = jfn.lower(*args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        entry.note_compile(sig, args, dyn_args)
        lower_s, compile_s = t1 - t0, t2 - t1
        entry.compiles += 1
        entry.lower_s_total += lower_s
        entry.compile_s_total += compile_s
        if cause != "first":
            entry.causes[cause] = entry.causes.get(cause, 0) + 1
        rec: Dict[str, Any] = {
            "entry": entry.name,
            "cause": cause,
            "generation": self.generation,
            "seq": self._seq,
            "lower_ms": round(lower_s * 1e3, 3),
            "compile_ms": round(compile_s * 1e3, 3),
            "donated_argnums": list(donate_argnums),
            "donated_bytes": sum(
                _leaf_bytes(leaf)
                for i in donate_argnums if i < len(args)
                for leaf in tree_util.tree_leaves(args[i])
            ),
        }
        self._seq += 1
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            rec["flops"] = float(ca.get("flops", 0.0))
            rec["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
        except Exception:  # backends without HLO cost analysis
            rec["flops"] = 0.0
            rec["bytes_accessed"] = 0.0
        try:
            ma = compiled.memory_analysis()
        except Exception:
            ma = None
        if ma is not None:
            for key, attr in (
                ("argument_bytes", "argument_size_in_bytes"),
                ("output_bytes", "output_size_in_bytes"),
                ("temp_bytes", "temp_size_in_bytes"),
                ("alias_bytes", "alias_size_in_bytes"),
                ("code_bytes", "generated_code_size_in_bytes"),
            ):
                v = getattr(ma, attr, None)
                if v is not None:
                    rec[key] = int(v)
        entry.last = rec
        if len(self.compile_log) < _COMPILE_LOG_CAP:
            self.compile_log.append(rec)
        if cause != "first":
            self.events.append(rec)
            del self.events[:-_EVENT_RING]
        return compiled

    # ------------------------------------------------ sanctioned bumps
    def generation_bump(self, cause: str) -> int:
        """A legitimate retrace is coming (bucket auto-resize, kernel
        invalidate).  Compiles after this carry the new generation and
        the soak gate's allowlist covers them."""
        self.generation += 1
        self.gen_events.append({"generation": self.generation,
                                "cause": str(cause), "seq": self._seq})
        return self.generation

    def mark(self) -> Dict[str, int]:
        """Snapshot for the recompile-free gate: compare with
        :meth:`unexplained_since` after the churn window."""
        return {"seq": self._seq, "generation": self.generation}

    def compiles_since(self, mark: Dict[str, int]) -> List[Dict[str, Any]]:
        return [r for r in self.compile_log if r["seq"] >= mark["seq"]]

    def unexplained_since(self, mark: Dict[str, int]) -> List[Dict[str, Any]]:
        """Compiles after `mark` NOT covered by a generation bump —
        the live complement of nf-lint's static recompile-hazard rule."""
        return [r for r in self.compiles_since(mark)
                if r["generation"] <= mark["generation"]]

    # ------------------------------------------------------ HBM census
    def hbm_sample(self) -> Dict[str, Any]:
        """One census pass: per-device allocator stats when the backend
        exposes them, live-array fallback (host-tracked peak) otherwise."""
        per_dev: List[Dict[str, Any]] = []
        live = peak = limit = 0
        source = None
        try:
            devices = list(jax.local_devices())
        except Exception:
            devices = []
        for d in devices:
            try:
                ms = d.memory_stats()
            except Exception:
                ms = None
            if not ms:
                continue
            source = "memory_stats"
            d_live = int(ms.get("bytes_in_use", 0))
            d_peak = int(ms.get("peak_bytes_in_use", d_live))
            d_limit = int(ms.get("bytes_limit", 0))
            live += d_live
            peak += d_peak
            limit += d_limit
            per_dev.append({
                "device": f"{d.platform}:{d.id}", "live_bytes": d_live,
                "peak_bytes": d_peak, "limit_bytes": d_limit,
            })
        if source is None:
            source = "live_arrays"
            live = sum(_leaf_bytes(a) for a in jax.live_arrays())
            self._fallback_peak = max(self._fallback_peak, live)
            peak = self._fallback_peak
            limit = 0
        self._hbm_samples += 1
        self.hbm = {
            "live_bytes": live, "peak_bytes": peak, "limit_bytes": limit,
            "source": source, "samples": self._hbm_samples,
            "per_device": per_dev,
        }
        return self.hbm

    # -------------------------------------------------------- exposure
    @property
    def total_compiles(self) -> int:
        return sum(e.compiles for e in self.entries.values())

    @property
    def total_recompiles(self) -> int:
        return sum(e.recompiles for e in self.entries.values())

    @property
    def compile_s_total(self) -> float:
        return sum(e.lower_s_total + e.compile_s_total
                   for e in self.entries.values())

    def snapshot(self) -> Dict[str, Any]:
        """The ``/costbook`` JSON document (docs/OBSERVABILITY.md has
        the schema)."""
        return {
            "generation": self.generation,
            "gen_events": list(self.gen_events[-_EVENT_RING:]),
            "compiles": self.total_compiles,
            "recompiles": self.total_recompiles,
            "compile_ms": round(self.compile_s_total * 1e3, 3),
            "hbm": dict(self.hbm),
            "entries": {n: e.to_dict()
                        for n, e in sorted(self.entries.items())},
            "events": list(self.events),
        }

    def summary(self) -> Dict[str, Any]:
        """Compact blob for the heartbeat ext map (master aggregation):
        per-entry compiles/recompiles/flops/bytes plus the HBM totals."""
        return {
            "compiles": self.total_compiles,
            "recompiles": self.total_recompiles,
            "compile_ms": round(self.compile_s_total * 1e3, 1),
            "generation": self.generation,
            "hbm_live": int(self.hbm.get("live_bytes", 0)),
            "hbm_peak": int(self.hbm.get("peak_bytes", 0)),
            "hbm_source": self.hbm.get("source", ""),
            "entries": {
                n: {"c": e.compiles, "r": e.recompiles,
                    "f": e.last.get("flops", 0.0),
                    "b": e.last.get("bytes_accessed", 0.0)}
                for n, e in sorted(self.entries.items())
            },
        }

    # ------------------------------------------- registry sample feeds
    def recompile_samples(self) -> Iterable[Tuple[dict, float]]:
        for name, e in sorted(self.entries.items()):
            for cause, n in sorted(e.causes.items()):
                yield ({"entry": name, "cause": cause}, float(n))

    def compile_samples(self, which: int) -> Iterable[Tuple[dict, float]]:
        """which: 0=compiles, 1=compile seconds (lower+compile)."""
        for name, e in sorted(self.entries.items()):
            v = (float(e.compiles) if which == 0
                 else e.lower_s_total + e.compile_s_total)
            yield ({"entry": name}, v)

    def cost_samples(self, key: str) -> Iterable[Tuple[dict, float]]:
        """Latest compiled cost per entry (flops / bytes_accessed /
        argument_bytes / output_bytes / temp_bytes / donated_bytes)."""
        for name, e in sorted(self.entries.items()):
            if key in e.last:
                yield ({"entry": name}, float(e.last[key]))


def roofline_fold(book: CostBook, pipeline_stats: Dict[str, Any],
                  device_kind: Optional[str] = None) -> Dict[str, Any]:
    """Join CostBook FLOPs/bytes with StageClock device seconds into
    achieved-vs-peak fractions per stage.

    ``pipeline_stats`` is ``GameRole.pipeline_stats()`` (frames + per-
    stage mean/p50/p95 ms).  Per-frame cost of a stage is the sum over
    that stage's entries of (per-dispatch cost x dispatches) / frames;
    honest device seconds require the run to have had
    ``NF_STAGE_TIMING=1`` (otherwise the tick stage times only the
    async dispatch and the fractions are upper bounds).

    ``device_kind`` defaults to the first device's; a kind that has no
    row in :data:`PEAKS` raises ``KeyError``."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}: the roofline "
            f"table knows {sorted(PEAKS)}; add a row with its source")
    peaks = PEAKS[device_kind]
    frames = max(1, int(pipeline_stats.get("frames", 0)))
    stage_ms = pipeline_stats.get("stages", {})
    per_stage: Dict[str, Dict[str, Any]] = {}
    for name, e in sorted(book.entries.items()):
        if e.stage is None or not e.last:
            continue
        s = per_stage.setdefault(e.stage, {
            "entries": [], "flops_per_frame": 0.0,
            "bytes_per_frame": 0.0,
        })
        s["entries"].append(name)
        s["flops_per_frame"] += e.last.get("flops", 0.0) * e.calls / frames
        s["bytes_per_frame"] += (
            e.last.get("bytes_accessed", 0.0) * e.calls / frames)
    for stage, s in per_stage.items():
        ms = stage_ms.get(stage, {})
        dev_s = float(ms.get("mean_ms", 0.0)) / 1e3
        s["device_s_per_frame"] = dev_s
        if dev_s > 0:
            s["achieved_flops_per_s"] = s["flops_per_frame"] / dev_s
            s["achieved_bytes_per_s"] = s["bytes_per_frame"] / dev_s
            s["frac_of_peak_flops"] = (
                s["achieved_flops_per_s"] / peaks["flops_per_s"])
            s["frac_of_peak_bytes"] = (
                s["achieved_bytes_per_s"] / peaks["bytes_per_s"])
        else:
            s["achieved_flops_per_s"] = 0.0
            s["achieved_bytes_per_s"] = 0.0
            s["frac_of_peak_flops"] = 0.0
            s["frac_of_peak_bytes"] = 0.0
    return {
        "device_kind": device_kind,
        "peaks": dict(peaks),
        "frames": frames,
        "stages": per_stage,
    }
