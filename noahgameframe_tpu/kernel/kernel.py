"""The kernel: object lifecycle + the jit-compiled world tick.

Reference equivalent: NFCKernelModule (object store, create/destroy with the
COE_* create-event chain, property/record access by GUID, common event
fan-out) plus the per-frame Execute loop over every object
(NFCKernelModule.cpp:70-99, 251-308).  Here the per-frame work is ONE
compiled function:

    state', outputs = step(state)

where `step` = schedule advance (vectorised heartbeats) → registered module
phases in order → dirty-diff extraction + death detection, all fused by XLA.
Host-side reactive semantics (the mutate → flags decide visibility →
subscribers converge chain, SURVEY §3.3) are preserved batch-wise: the tick
returns per-bank changed masks (pre-masked by the Public/Upload flags) and
per-class death masks; the kernel fans those out to host subscribers after
each tick, fetching device data only when someone is listening.
"""

from __future__ import annotations

import dataclasses
import enum
from fnmatch import fnmatch
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.datatypes import Bank, Guid, Value
from ..core.element import ElementStore
from ..core.schema import ClassRegistry
from ..core.store import EntityStore, StoreConfig, WorldState
from .events import DeviceEvent, EventModule
from .module import Module, Phase
from .schedule import ScheduleModule
# after .module: telemetry's package imports kernel.module back
from ..telemetry.tracing import SpanTracer


class ObjectEvent(enum.IntEnum):
    """Create/destroy state chain, mirroring the reference's
    CLASS_OBJECT_EVENT / COE_* states (NFIObject.h:22-30)."""

    CREATE_NODATA = 0
    CREATE_LOADDATA = 1
    CREATE_BEFORE_EFFECT = 2
    CREATE_EFFECTDATA = 3
    CREATE_AFTER_EFFECT = 4
    CREATE_HASDATA = 5
    CREATE_FINISH = 6
    BEFORE_DESTROY = 7
    DESTROY = 8

ClassEventFn = Callable[[Guid, str, "ObjectEvent"], None]
PropertyEventFn = Callable[[str, str, np.ndarray], None]  # (class, prop, changed_rows)
# (class, record, codes[C, R] int8) — 0 none, 1 added, 2 removed, 3 updated
RecordDiffFn = Callable[[str, str, np.ndarray], None]

REC_NONE, REC_ADDED, REC_REMOVED, REC_UPDATED = 0, 1, 2, 3

# multiplier for the rolling state-digest fold (odd, so it is invertible
# mod 2^32 and single-bit flips diffuse instead of cancelling)
_DIGEST_MULT = 1000003

# rows a word of a diff's bit planes holds (pack_diff_planes)
DIFF_WORD = 32

# Every per-tick output lane of _trace_step that host code consumes must
# match one of these patterns (or appear in TRAIN_EXCLUDED with a
# reason): the K-tick train stacks exactly these lanes into [K, ...]
# device arrays, and a lane missing from the stack would silently lose
# its per-tick history inside a train (journal digests, death masks,
# event params all ride here).  The train-lanes-covered nf-lint rule
# cross-checks this tuple against every `out[...]` consumer statically;
# _assert_train_lanes enforces it at trace time.  Keep it a plain
# literal (the ROW_LEAF_SPEC / ROOM_PACK_SPEC contract).
TRAIN_LANE_SPEC = (
    "fired",
    "diff",
    "diff_count",
    "rec_diff",
    "rec_diff_count",
    "died",
    "died_count",
    "events",
    "summary",
)

# Out-dict lanes waived from train stacking, with a reason each.
# (none today — every per-tick output is host-consumed)
TRAIN_EXCLUDED = ()


def _assert_train_lanes(out: Dict[str, object]) -> None:
    """Trace-time coverage assert for the train's stacked lane set.

    Both directions, like world_room_leaf_items: an out lane not named
    by TRAIN_LANE_SPEC/TRAIN_EXCLUDED means a new per-tick output was
    added without deciding its train fate; a spec pattern matching no
    lane is stale and must be pruned."""
    spec = TRAIN_LANE_SPEC + TRAIN_EXCLUDED
    unlisted = [k for k in out if not any(fnmatch(k, p) for p in spec)]
    stale = [p for p in spec if not any(fnmatch(k, p) for k in out)]
    if unlisted or stale:
        raise AssertionError(
            "TRAIN_LANE_SPEC drift: "
            f"unlisted out lanes {unlisted}, stale patterns {stale}"
        )


def pack_diff_planes(m: jnp.ndarray) -> jnp.ndarray:
    """A diff mask `bool[capacity, cols]` as bit planes by column,
    `uint32[cols, W]` with `W = ceil(capacity / 32)`: bit `b` of word `w`
    of plane `col` is `m[b * W + w, col]` (rows past the capacity read
    0).  `unpack_diff_plane` is the inverse.

    The bit order follows how the chip keeps a bank: column-major, a
    column's rows on the lanes (`s32[1048576,47]{0,1}`).  A word's 32
    rows lie `W` rows apart, so row block `b` lands on bit `b` of the
    words lane for lane and the fold is 32 elementwise passes, no
    reduction across lanes (which 32 adjacent rows to a word would be:
    PERF.md section 6, PR 34).  The mask stays a `bool` up to the fold
    (a select of the bit's weight, not a widened mask shifted): what
    the fold reads is a byte a cell."""
    cap, cols = m.shape
    words = -(-cap // DIFF_WORD)
    if words * DIFF_WORD != cap:
        m = jnp.pad(m, ((0, words * DIFF_WORD - cap), (0, 0)))
    weight = np.uint32(1) << np.arange(DIFF_WORD, dtype=np.uint32)
    bits = jnp.where(m.T.reshape(cols, DIFF_WORD, words),
                     weight[None, :, None], np.uint32(0))
    return jnp.sum(bits, axis=1, dtype=jnp.uint32)


def unpack_diff_plane(plane: np.ndarray) -> np.ndarray:
    """The rows one fetched plane of `pack_diff_planes` names, ascending
    (`intp[n]`): the words' bits laid out bit-major as `bool[32, W]`,
    whose flat index is the row, and scanned once (numpy scans a flat
    `bool` array fastest)."""
    bit = np.uint32(1) << np.arange(DIFF_WORD, dtype=np.uint32)
    return np.flatnonzero((np.asarray(plane)[None, :] & bit[:, None]) != 0)


def _digest_u32(x: jnp.ndarray) -> jnp.ndarray:
    """Reinterpret any bank dtype as uint32 words, bit-exactly for f32
    (a digest over *rounded* floats would call two bitwise-different
    states equal — the one thing replay must never do)."""
    if x.dtype == jnp.bool_:
        return x.astype(jnp.uint32)
    if x.dtype in (jnp.float32, jnp.int32):
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    return x.astype(jnp.uint32)


def state_digest(state, class_order: Sequence[str]) -> jnp.ndarray:
    """One uint32 digest of the whole device-resident world.

    Position-weighted modular sums per bank, folded across banks with a
    rolling multiply — pure uint32 arithmetic, so the reduction is
    associative/commutative (wraparound add) and the result is
    bit-identical across backends and shardings whenever the state
    arrays are.  WorldState.aux is deliberately EXCLUDED: caches there
    (Verlet tables) are rebuilt from scratch on resume and masked out of
    results, so their contents differ between a live run and a
    checkpoint-restored replay of the same world.
    """

    def fold(acc: jnp.ndarray, arr: jnp.ndarray) -> jnp.ndarray:
        x = _digest_u32(arr).ravel()
        w = jnp.arange(x.shape[0], dtype=jnp.uint32) * 2 + 1
        return acc * jnp.uint32(_DIGEST_MULT) + jnp.sum(x * w, dtype=jnp.uint32)

    acc = jnp.uint32(0x9E3779B9)
    acc = fold(acc, state.tick)
    acc = fold(acc, state.rng)
    for cname in class_order:
        cs = state.classes[cname]
        for arr in (cs.i32, cs.f32, cs.vec, cs.alive,
                    cs.timers.next_fire, cs.timers.interval,
                    cs.timers.remain, cs.timers.active):
            acc = fold(acc, arr)
        for rname in sorted(cs.records):
            rec = cs.records[rname]
            for arr in (rec.i32, rec.f32, rec.vec, rec.used):
                acc = fold(acc, arr)
    return acc


class TickCtx:
    """Per-tick context handed to device phases during tracing."""

    def __init__(
        self,
        kernel: "Kernel",
        tick: jnp.ndarray,
        rng: jnp.ndarray,
        fired_masks: Dict[str, jnp.ndarray],
    ):
        self.kernel = kernel
        self.store = kernel.store
        self.tick = tick
        self.dt = kernel.schedule.dt
        self._rng = rng
        self._rng_count = 0
        self._fired = fired_masks
        self.emitted: List[DeviceEvent] = []
        # named int32 scalars accumulated on device across phases; the
        # kernel packs them into the per-tick summary fetch (counter bank)
        self._counters: Dict[str, jnp.ndarray] = {}
        # rooms sharing this trace: 1 for an ordinary world, R when the
        # kernel is a room-batch template (the step is vmapped, so every
        # traced value a phase sees is ONE room's slice; this is static
        # trace-time metadata for phases that size host mirrors)
        self.room_count = (
            1 if kernel.room_batch is None else kernel.room_batch.capacity
        )

    def fired(self, class_name: str, timer_name: str) -> jnp.ndarray:
        """[C] bool — which entities' `timer_name` fired this tick."""
        slot = self.kernel.schedule.slot(class_name, timer_name)
        return self._fired[class_name][:, slot]

    def remap_fired(self, class_name: str, fired: jnp.ndarray) -> None:
        """Republish a class's [C, T] fired mask after a phase permuted its
        rows.  The schedule computes fired masks BEFORE phases run, so a
        phase that moves rows (cross-shard migration) must move the mask
        with them — otherwise a row that migrates mid-tick leaves its fire
        behind on a now-dead slot and later handlers silently skip it."""
        self._fired[class_name] = fired

    def rng(self) -> jnp.ndarray:
        """A fresh PRNG key (deterministic per tick + call position)."""
        self._rng_count += 1
        return jax.random.fold_in(self._rng, self._rng_count)

    def emit(
        self, event_id: int, class_name: str, mask: jnp.ndarray, **params: jnp.ndarray
    ) -> None:
        """Emit a batch event from inside the tick; delivered to host/batch
        subscribers after the step (device replacement for DoEvent).

        The (event_id, class_name) metadata is static per compilation; only
        mask/params are traced values."""
        self.emitted.append(DeviceEvent(int(event_id), class_name, mask, dict(params)))

    def count(self, name: str, value) -> None:
        """Accumulate into the tick's on-device counter bank.  `value` is
        any traced array — bool masks and int vectors are summed to one
        int32 scalar.  Counters ride the packed summary vector the host
        already fetches each tick, so observing them adds ZERO device
        syncs; the name set is static per compilation (phases decide what
        they count at trace time, like event metadata)."""
        v = jnp.asarray(value)
        if v.ndim:
            v = jnp.sum(v, dtype=jnp.int32)
        v = v.astype(jnp.int32)
        prev = self._counters.get(name)
        self._counters[name] = v if prev is None else prev + v


@dataclasses.dataclass
class TickOutputs:
    """Device-resident tick results; host fetches lazily."""

    fired: Dict[str, jnp.ndarray]  # class -> [C, T] bool
    # class -> bank -> uint32[ncols, ceil(C / 32)]: the changed cells as
    # bit planes by column (pack_diff_planes / unpack_diff_plane)
    diff: Dict[str, Dict[str, jnp.ndarray]]
    diff_count: Dict[str, jnp.ndarray]  # class -> scalar changed-cell count
    died: Dict[str, jnp.ndarray]  # class -> [C] bool
    died_count: Dict[str, jnp.ndarray]  # class -> scalar
    events: List[DeviceEvent]
    # class -> record -> [C, R] int8 row-change codes (REC_* constants);
    # only populated for (class, record) pairs with a registered
    # record-diff subscriber — unsubscribed records cost zero device work
    rec_diff: Dict[str, Dict[str, jnp.ndarray]] = dataclasses.field(
        default_factory=dict
    )
    rec_diff_count: Dict[str, jnp.ndarray] = dataclasses.field(default_factory=dict)
    # counter bank decoded from the summary fetch: name -> host int
    # (events fired, diff cells, deaths, combat hits, AOI overflow drops
    # + anything phases ctx.count()ed) — already on host, free to read
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)
    # class -> bank -> int[ncols] changed cells a column, decoded from
    # the summary fetch like the counters (their sum is diff_count)
    diff_cols: Dict[str, Dict[str, np.ndarray]] = dataclasses.field(
        default_factory=dict
    )


class Kernel(Module):
    """Owns the world: registry + store + state + the compiled tick."""

    name = "KernelModule"

    def __init__(
        self,
        registry: ClassRegistry,
        store_config: Optional[StoreConfig] = None,
        dt: float = 1.0 / 30.0,
        seed: int = 0,
        class_names: Optional[Sequence[str]] = None,
        diff_flags: Tuple[str, ...] = ("public", "upload"),
    ):
        super().__init__()
        self.registry = registry
        self.store_config = store_config or StoreConfig()
        self.schedule = ScheduleModule(dt=dt)
        self.events = EventModule()
        self.elements = ElementStore(registry)
        self._class_names = class_names
        self._seed = seed
        self._diff_flags = diff_flags
        self.store: Optional[EntityStore] = None
        self.state: Optional[WorldState] = None
        # device cost observatory (telemetry/costbook.py): every jit
        # entry the kernel owns dispatches through this ledger — compile
        # time, cost/memory analysis, retrace cause attribution.  Built
        # here (not by telemetry) so bare-kernel benches record too;
        # TelemetryModule.attach_kernel adopts it for /costbook+metrics.
        # Deferred import: telemetry.module imports kernel.module.
        from ..telemetry.costbook import CostBook

        self.costbook = CostBook()
        # the composed, sorted phase chain the tick runs; the kernel's OWN
        # phases (added via Module.add_phase) stay in self._phases like any
        # other module's so composition can't double-count them
        self._composed: List[Phase] = []
        self._jit_step = None
        self._jit_run = None
        # K-tick train (NF_TICK_TRAIN): one lax.scan dispatch covering
        # _train_k frames with every host-consumed lane stacked [K, ...]
        # (TRAIN_LANE_SPEC).  K is a compile-time constant of the train
        # executable (lax.scan lengths are static by construction);
        # ragged tails ride kernel.step, so one train compile + the
        # always-present step compile serve every run length.
        self._jit_train = None
        self._train_k = 0
        # train accounting, surfaced as nf_train_*_total by telemetry
        self.train_dispatches = 0
        self.train_ticks = 0
        self.train_fetch_bytes = 0
        # property fan-out accounting (nf_fanout_mask_*_total): the
        # (class, bank) diffs read as bit planes, one each on the ticks
        # whose summary says a subscribed column of the bank changed,
        # their bytes, and the columns unpacked to row lists
        self.fanout_mask_fetches = 0
        self.fanout_mask_bytes = 0
        self.fanout_mask_columns = 0
        # monotonically bumped whenever the compiled tick is dropped
        # (invalidate / set_phases) so WRAPPING compilers — ShardedKernel
        # keeps its own jitted variants of _trace_step — can notice and
        # drop theirs too instead of dispatching a stale trace
        self._trace_gen = 0
        self._class_event_subs: List[ClassEventFn] = []
        self._class_event_by_class: Dict[str, List[ClassEventFn]] = {}
        self._prop_event_subs: Dict[Tuple[str, str], List[PropertyEventFn]] = {}
        # class -> props opted into diff extraction beyond diff_flags
        # (debug tools — the property trail); changes invalidate the tick
        self._forced_diff: Dict[str, set] = {}
        self._rec_event_subs: Dict[Tuple[str, str], List[RecordDiffFn]] = {}
        self._pending_destroy: List[Guid] = []
        self._event_meta: List[Tuple[int, str, Tuple[str, ...]]] = []
        self.tick_count = 0
        # module-registered carried tick state (WorldState.aux): name ->
        # zero-arg init fn.  Entries are primed lazily right before
        # dispatch (_ensure_aux) so registration order vs build order
        # doesn't matter, and invalidate() drops them (aux layouts bake
        # trace-time geometry, e.g. Verlet slot assignments)
        self._aux_init: Dict[str, Callable[[], Any]] = {}
        # counter-bank decode order, captured at trace time like
        # _event_meta (static per compilation)
        self._counter_names: Tuple[str, ...] = ()
        self.last_counters: Dict[str, int] = {}  # latest observed tick
        self.counter_totals: Dict[str, int] = {}  # cumulative over tick()s
        # when set, the tick folds a uint32 digest of the post-tick state
        # into the counter bank ("state_digest") — the flight recorder's
        # per-tick fingerprint, riding the summary fetch at zero extra
        # syncs.  Flip via enable_digest() so the tick is retraced.
        self.digest_enabled = False
        # host-side tick spans (nf.kernel.dispatch / .fetch / .fanout):
        # in the profiler's trace whenever a session is open; a
        # TelemetryModule swaps in its own tracer (the operator's ring)
        self.tracer = SpanTracer(enabled=False)
        # back-pointer set by parallel/rooms.RoomBatch.attach() when this
        # kernel is the TEMPLATE for a room-batched world: its _trace_step
        # is vmapped over a leading [R] room axis and its own state/jit
        # entries go unused.  None for every ordinary single-world kernel.
        self.room_batch = None
        # honest per-stage timing (NF_STAGE_TIMING=1, set by GameRole /
        # telemetry/pipeline.stage_timing_enabled): block after dispatch
        # so the nf.kernel.dispatch span measures device time, not async
        # enqueue latency.  Never on by default — it serializes the
        # device queue and kills dispatch/fetch overlap.
        self.stage_timing = False

    # -- build --------------------------------------------------------------

    def build(self, modules: Sequence[Module] = ()) -> None:
        """Freeze timer slots, construct the store + initial state, and
        collect device phases from `modules` (plus any added directly)."""
        timer_slots = self.schedule.freeze()
        self.store_config.timer_slots = {
            **timer_slots,
            **{
                k: v
                for k, v in self.store_config.timer_slots.items()
                if k not in timer_slots
            },
        }
        self.store = EntityStore(
            self.registry,
            self.store_config,
            strings=self.elements.strings,
            class_names=self._class_names,
        )
        self.state = self.store.init_state(self._seed)
        phases: List[Phase] = []
        seen_modules = set()
        for m in modules:
            phases.extend(m.phases)
            seen_modules.add(id(m))
        if id(self) not in seen_modules:
            phases.extend(self.phases)
        self.set_phases(phases)

    def set_phases(self, phases: Sequence[Phase]) -> None:
        self._composed = sorted(phases, key=lambda p: p.order)
        self._jit_step = None
        self._jit_run = None
        self._jit_train = None
        self._trace_gen += 1
        self.costbook.generation_bump("set_phases")

    # -- the compiled tick --------------------------------------------------

    def _trace_step(self, state: WorldState):
        old = state
        fired: Dict[str, jnp.ndarray] = {}
        new_classes = {}
        # per-stage named scopes ride the HLO metadata: an XProf/profiler
        # capture attributes device time to "nf.schedule", "nf.phase.*",
        # "nf.diff" instead of one opaque fused computation
        with jax.named_scope("nf.schedule"):
            for cname in self.store.class_order:
                cs, f = self.schedule.advance_class(state.classes[cname], state.tick)
                new_classes[cname] = cs
                fired[cname] = f
            state = state.replace(classes=new_classes)

        rng = jax.random.fold_in(state.rng, state.tick)
        ctx = TickCtx(self, state.tick, rng, fired)
        for phase in self._composed:
            with jax.named_scope(f"nf.phase.{phase.name}"):
                state = phase.fn(state, ctx)

        diff: Dict[str, Dict[str, jnp.ndarray]] = {}
        diff_cols: Dict[str, Dict[str, jnp.ndarray]] = {}
        diff_count: Dict[str, jnp.ndarray] = {}
        rec_diff: Dict[str, Dict[str, jnp.ndarray]] = {}
        rec_diff_count: Dict[str, jnp.ndarray] = {}
        died: Dict[str, jnp.ndarray] = {}
        died_count: Dict[str, jnp.ndarray] = {}
        with jax.named_scope("nf.diff"):
            for cname in self.store.class_order:
                spec = self.store.spec(cname)
                oc, nc = old.classes[cname], state.classes[cname]
                planes: Dict[str, jnp.ndarray] = {}
                cols: Dict[str, jnp.ndarray] = {}
                totals: List[jnp.ndarray] = []
                for bank in (Bank.I32, Bank.F32, Bank.VEC):
                    fm = self.diff_columns(cname, bank)
                    if not fm.any():
                        continue
                    nm = bank.value
                    ne = getattr(oc, nm) != getattr(nc, nm)
                    if bank == Bank.VEC:
                        ne = jnp.any(ne, axis=-1)
                    m = ne & nc.alive[:, None] & fm[None, :]
                    if self.room_batch is not None:
                        # no room reads a diff: a fleet's tick keeps the
                        # changed cells' count and makes no planes
                        totals.append(jnp.sum(m, dtype=jnp.int32))
                        continue
                    # the mask leaves the device as bit planes by column
                    # and changed cells a column, counted off the planes
                    # so the banks are compared, and read, once
                    planes[nm] = pack_diff_planes(m)
                    cols[nm] = jnp.sum(
                        jax.lax.population_count(planes[nm]),
                        axis=1, dtype=jnp.int32)
                    totals.append(jnp.sum(cols[nm]))
                if planes:
                    diff[cname] = planes
                    diff_cols[cname] = cols
                if totals:
                    diff_count[cname] = sum(totals, jnp.zeros((), jnp.int32))
                # record-row diffs: add/remove/update codes per (entity, row),
                # only for subscribed records (device phases mutate records —
                # buff expiry, stat groups — and those changes must reach the
                # same sync spine as host record ops;
                # reference NFCRecord per-op callbacks, NFCRecord.h:17-156)
                rec_codes: Dict[str, jnp.ndarray] = {}
                rec_total = jnp.zeros((), jnp.int32)
                for rname in spec.record_order:
                    if (cname, rname) not in self._rec_event_subs:
                        continue
                    rs = spec.records[rname]
                    orec, nrec = oc.records[rname], nc.records[rname]
                    cell_changed = jnp.zeros(nrec.used.shape, bool)
                    if rs.n_i32:
                        cell_changed |= jnp.any(orec.i32 != nrec.i32, axis=-1)
                    if rs.n_f32:
                        cell_changed |= jnp.any(orec.f32 != nrec.f32, axis=-1)
                    if rs.n_vec:
                        cell_changed |= jnp.any(orec.vec != nrec.vec, axis=(-2, -1))
                    code = jnp.where(
                        ~orec.used & nrec.used,
                        REC_ADDED,
                        jnp.where(
                            orec.used & ~nrec.used,
                            REC_REMOVED,
                            jnp.where(nrec.used & cell_changed, REC_UPDATED, REC_NONE),
                        ),
                    ).astype(jnp.int8)
                    code = code * nc.alive[:, None].astype(jnp.int8)
                    rec_codes[rname] = code
                    rec_total = rec_total + jnp.sum(code != 0, dtype=jnp.int32)
                if rec_codes:
                    rec_diff[cname] = rec_codes
                    rec_diff_count[cname] = rec_total
                d = oc.alive & ~nc.alive
                died[cname] = d
                died_count[cname] = jnp.sum(d, dtype=jnp.int32)

        state = state.replace(tick=state.tick + 1)
        # static event metadata is captured on self at trace time; only the
        # traced arrays cross the jit boundary (dataclasses aren't pytrees)
        self._event_meta = [(e.event_id, e.class_name, tuple(e.params)) for e in ctx.emitted]
        # on-device counter bank: phase-accumulated ctx.count() values plus
        # kernel builtins.  Names are static per compilation (same contract
        # as _event_meta); values ride the summary fetch below, so the
        # telemetry surface costs ZERO extra device syncs per tick.
        digest = None
        if self.digest_enabled:
            # post-increment state, i.e. exactly what a checkpoint taken
            # after this tick would capture — replay compares like for like
            with jax.named_scope("nf.digest"):
                digest = jax.lax.bitcast_convert_type(
                    state_digest(state, self.store.class_order), jnp.int32
                )
        with jax.named_scope("nf.summary"):
            ev_counts = [jnp.sum(e.mask, dtype=jnp.int32) for e in ctx.emitted]
            counters = dict(ctx._counters)
            zero = jnp.zeros((), jnp.int32)
            counters["deaths"] = sum(died_count.values(), zero)
            counters["diff_cells"] = sum(diff_count.values(), zero)
            counters["rec_diff_cells"] = sum(rec_diff_count.values(), zero)
            counters["events_fired"] = sum(ev_counts, zero)
            # the tick's own logical number (post-increment, i.e. the value
            # tick_count reaches once this frame lands) rides in-lane so a
            # K-tick train can stamp journal marks and death attribution
            # with the REAL tick of each stacked frame, not the train's end
            counters["tick"] = state.tick
            if digest is not None:
                counters["state_digest"] = digest
            self._counter_names = tuple(sorted(counters))
            # ONE packed scalar vector per tick — the only thing the host
            # ever synchronously fetches.  Anything else (masks, params,
            # fired) is fetched lazily and only when this summary says
            # there's something to see; every fetch is a device->host
            # round trip, so this is the difference between 1 and
            # O(classes+events) syncs per tick.
            summary = jnp.concatenate(
                [
                    jnp.stack([died_count[c] for c in self.store.class_order])
                    if self.store.class_order
                    else jnp.zeros((0,), jnp.int32),
                    jnp.stack([diff_count[c] for c in sorted(diff_count)])
                    if diff_count
                    else jnp.zeros((0,), jnp.int32),
                    jnp.stack([rec_diff_count[c] for c in sorted(rec_diff_count)])
                    if rec_diff_count
                    else jnp.zeros((0,), jnp.int32),
                    jnp.stack(ev_counts)
                    if ctx.emitted
                    else jnp.zeros((0,), jnp.int32),
                    # changed cells a column, by (class, bank) in sorted
                    # order: which planes the fan-out fetches, learnt
                    # with no read of its own
                    *(
                        diff_cols[c][b]
                        for c in sorted(diff_cols)
                        for b in sorted(diff_cols[c])
                    ),
                    jnp.stack([counters[k] for k in self._counter_names]),
                ]
            )
        out = {
            "fired": fired,
            "diff": diff,
            "diff_count": diff_count,
            "rec_diff": rec_diff,
            "rec_diff_count": rec_diff_count,
            "died": died,
            "died_count": died_count,
            "events": [(e.mask, e.params) for e in ctx.emitted],
            "summary": summary,
        }
        return state, out

    def diff_columns(self, class_name: str, bank: Bank) -> np.ndarray:
        """`bool[ncols]`: the columns of a class's bank the tick extracts
        a diff for (a diff flag, or opted in by force_diff_property)."""
        spec = self.store.spec(class_name)
        fm = np.zeros(spec.bank_size(bank), bool)
        for fl in self._diff_flags:
            fm |= spec.mask(bank, fl)
        for pname in self._forced_diff.get(class_name, ()):
            slot = spec.slot(pname)
            if slot.bank == bank:
                fm[slot.col] = True
        return fm

    def compile(self) -> None:
        if self._jit_step is None:
            self._jit_step = self.costbook.wrap(
                "kernel.step", self._trace_step,
                donate_argnums=0, stage="tick",
            )

    def invalidate(self) -> None:
        """Force retrace of the compiled tick.  Call after changing
        anything phases close over (config tables, phase lists) — traced
        constants do NOT update on their own.  Registered aux entries are
        dropped too: their layouts bake the same trace-time geometry
        (bucket sizes, grid widths), so a stale Verlet slot assignment
        must not survive a retrace — _ensure_aux re-primes zero caches
        and the first new tick rebuilds them."""
        self._jit_step = None
        self._jit_run = None
        self._jit_train = None
        self._trace_gen += 1
        # sanctioned retrace: anything compiled after this bump is an
        # expected recompile, not a hazard (soak-gate allowlist seam)
        self.costbook.generation_bump("invalidate")
        if self._aux_init and self.state is not None and self.state.aux:
            kept = {
                k: v for k, v in self.state.aux.items()
                if k not in self._aux_init
            }
            if len(kept) != len(self.state.aux):
                self.state = self.state.replace(aux=kept)

    def enable_digest(self) -> None:
        """Turn on the per-tick state digest (flight-recorder fingerprint).
        A no-op when already on; otherwise the compiled tick is retraced
        so the counter bank grows the "state_digest" slot."""
        if not self.digest_enabled:
            self.digest_enabled = True
            self.invalidate()

    # -- carried aux state ---------------------------------------------------

    def register_aux(self, name: str, init_fn: Callable[[], Any]) -> None:
        """Register module-owned carried tick state (WorldState.aux).

        `init_fn` returns a pytree of arrays; it is called lazily before
        the next dispatch (so store capacities exist by then) and again
        after every invalidate().  Phases read `state.aux[name]` and
        write back via `state.replace(aux={**state.aux, name: new})`."""
        self._aux_init[name] = init_fn

    def _ensure_aux(self) -> None:
        """Prime any registered-but-missing aux entries before dispatch —
        keeps the carried pytree structure stable across every tick()/
        run_device() call of one compilation."""
        if not self._aux_init:
            return
        missing = [k for k in self._aux_init if k not in self.state.aux]
        if missing:
            aux = dict(self.state.aux)
            for k in missing:
                aux[k] = self._aux_init[k]()
            self.state = self.state.replace(aux=aux)

    def tick(self) -> TickOutputs:
        """Advance the world one frame and fan out host-visible effects."""
        return self.tick_finish(self.tick_begin())

    def tick_begin(self) -> Dict[str, object]:
        """Dispatch one frame's step and return the raw output handle
        WITHOUT fetching anything.  The device runs asynchronously until
        `tick_finish(raw)` syncs on the summary — the seam the serving
        edge's overlap mode uses to assemble/encode frame N's packets on
        the host while the device computes frame N+1.

        Donation hazard: `_jit_step` donates the carried state, so the
        PRE-dispatch buffers are invalid the moment this returns.  Any
        reader of pre-tick state (snapshot fetches, serve kernels) must
        run before tick_begin."""
        self.compile()
        self._ensure_aux()
        with self.tracer.span("kernel.dispatch"):
            self.state, raw = self._jit_step(self.state)
            if self.stage_timing:
                jax.block_until_ready((self.state, raw))
        self.tick_count += 1
        return raw

    def tick_finish(self, raw: Dict[str, object]) -> TickOutputs:
        """Fetch a dispatched frame's outputs and fan out host-visible
        effects (events, diffs, death reconciliation, counters)."""
        out = TickOutputs(
            fired=raw["fired"],
            diff=raw["diff"],
            diff_count=raw["diff_count"],
            rec_diff=raw["rec_diff"],
            rec_diff_count=raw["rec_diff_count"],
            died=raw["died"],
            died_count=raw["died_count"],
            events=[
                DeviceEvent(eid, cname, mask, dict(params))
                for (eid, cname, pnames), (mask, params) in zip(
                    self._event_meta, raw["events"]
                )
            ],
        )
        with self.tracer.span("kernel.fetch"):
            summary = np.asarray(raw["summary"])
        # decode the counter bank from the summary tail (names captured at
        # trace time, same static-metadata contract as _event_meta)
        if self._counter_names:
            out.counters = {
                k: int(v) for k, v in self.decode_counters(summary).items()
            }
            self.last_counters = dict(out.counters)
            for k, v in out.counters.items():
                if k in ("state_digest", "tick"):
                    continue  # a hash / a stamp; summing either is noise
                self.counter_totals[k] = self.counter_totals.get(k, 0) + v
        with self.tracer.span("kernel.fanout"):
            self._post_tick(out, summary)
        return out

    def decode_counters(self, summary) -> Dict[str, np.ndarray]:
        """Slice the named counter bank off a summary vector's tail.

        The bank rides the LAST ``len(self._counter_names)`` lanes of
        the packed summary, so the decode is a trailing-axis slice and
        works unchanged on a room-batched ``[R, L]`` summary (the room
        engine vmaps the step, giving every lane a leading room axis):
        scalars come back for a single world, per-room ``[R]`` columns
        for a batch."""
        names = self._counter_names
        if not names:
            return {}
        arr = np.asarray(summary)
        tail = arr[..., arr.shape[-1] - len(names):]
        return {k: tail[..., i] for i, k in enumerate(names)}

    def run_device(self, n: int, reconcile: bool = True) -> int:
        """Advance n frames entirely on device (lax.fori_loop over the
        step) with ZERO host syncs — the headless/benchmark fast path.

        Per-tick host observation is skipped: device events, per-tick
        diffs and fired masks are not delivered (XLA dead-code-eliminates
        them); deaths are reconciled once at the end.  Use tick() when
        host subscribers must see every frame.

        reconcile=False skips the end-of-run death reconciliation (one
        device→host fetch per class, which would dominate short timing
        windows).  Host free-lists then
        lag the device until the next reconciling call; benchmark latency
        sampling is the intended user."""
        self.compile()
        self._ensure_aux()
        key = int(n)
        if self._jit_run is None:
            # trip count rides in as a TRACED scalar so ONE compile
            # serves every n — a fresh 1M-entity compile per window size
            # cost the round-4 bench minutes of wall per variant
            def body(_, st):
                st2, _out = self._trace_step(st)
                return st2

            self._jit_run = self.costbook.wrap(
                "kernel.run",
                lambda st, k: jax.lax.fori_loop(0, k, body, st),
                donate_argnums=0, stage="tick",
            )
        self.state = self._jit_run(self.state, jnp.int32(key))
        self.tick_count += key
        if not reconcile:
            return 0
        freed = 0
        for cname in self.store.class_order:
            for g in self.store.reconcile_deaths(self.state, cname):
                self._fire_class_event(g, cname, ObjectEvent.DESTROY)
                freed += 1
        return freed

    # -- K-tick trains (NF_TICK_TRAIN) --------------------------------------

    def configure_train(self, k: int) -> None:
        """Pin the train length.  Changing K drops only the train
        executable (the step/run traces are K-independent); the retrace
        is announced like every other sanctioned recompile so soak
        gates armed across a reconfigure stay clean."""
        k = int(k)
        if k < 1:
            raise ValueError(f"train length must be >= 1, got {k}")
        if k == self._train_k:
            return
        self._train_k = k
        if self._jit_train is not None:
            self._jit_train = None
            self.costbook.generation_bump(f"train_k:{k}")

    def _trace_train(self, state: WorldState):
        """K steps under ONE lax.scan whose per-tick outputs scan-stack
        into [K, ...] lanes — the whole observed surface of K frames in
        one dispatch + one summary fetch.  Plain scan, not unrolled:
        measured on the rooms flagship shape the rolled loop both runs
        faster and compiles ~7x faster than an unrolled body."""

        def body(st, _):
            st2, out = self._trace_step(st)
            return st2, out

        state, lanes = jax.lax.scan(body, state, None, length=self._train_k)
        _assert_train_lanes(lanes)
        return state, lanes

    def compile_train(self) -> None:
        if self._jit_train is None:
            if self._train_k < 1:
                raise RuntimeError("configure_train(k) before train()")
            self._jit_train = self.costbook.wrap(
                "kernel.train", self._trace_train,
                donate_argnums=0, stage="tick",
            )

    def train_begin(self) -> Dict[str, object]:
        """Dispatch one K-tick train; same donation hazard and async
        contract as tick_begin, K frames deep."""
        self.compile_train()
        self._ensure_aux()
        with self.tracer.span("kernel.dispatch"):
            self.state, raw = self._jit_train(self.state)
            if self.stage_timing:
                jax.block_until_ready((self.state, raw))
        self.tick_count += self._train_k
        self.train_dispatches += 1
        self.train_ticks += self._train_k
        return raw

    def train_finish(self, raw: Dict[str, object]) -> List[TickOutputs]:
        """Fetch one train's stacked lanes and fan out K frames of
        host-visible effects IN TICK ORDER: lane i's events fire before
        lane i's deaths free rows, before anything from lane i+1 — the
        same per-frame sequencing tick_finish gives a single frame.
        Deaths are attributed from each lane's own died mask (the final
        carried state cannot say WHICH tick killed a row)."""
        k = self._train_k
        with self.tracer.span("kernel.fetch"):
            summary = np.asarray(raw["summary"])  # [K, L]
        self.train_fetch_bytes += summary.nbytes
        stacked = {kk: vv for kk, vv in raw.items() if kk != "summary"}
        outs: List[TickOutputs] = []
        for i in range(k):
            lane = jax.tree.map(lambda x: x[i], stacked)
            out = TickOutputs(
                fired=lane["fired"],
                diff=lane["diff"],
                diff_count=lane["diff_count"],
                rec_diff=lane["rec_diff"],
                rec_diff_count=lane["rec_diff_count"],
                died=lane["died"],
                died_count=lane["died_count"],
                events=[
                    DeviceEvent(eid, cname, mask, dict(params))
                    for (eid, cname, pnames), (mask, params) in zip(
                        self._event_meta, lane["events"]
                    )
                ],
            )
            row = summary[i]
            if self._counter_names:
                out.counters = {
                    kk: int(v)
                    for kk, v in self.decode_counters(row).items()
                }
                self.last_counters = dict(out.counters)
                for kk, v in out.counters.items():
                    if kk in ("state_digest", "tick"):
                        continue
                    self.counter_totals[kk] = (
                        self.counter_totals.get(kk, 0) + v
                    )
            with self.tracer.span("kernel.fanout"):
                self._post_tick(out, row, exact_deaths=True)
            outs.append(out)
        return outs

    def train(self, n: int) -> List[TickOutputs]:
        """Advance n frames in ⌊n/K⌋ train dispatches plus a per-tick
        ragged tail, delivering every frame's host effects — the
        observed-mode counterpart of run_device.  Returns one
        TickOutputs per frame, in order; out.counters["tick"] carries
        each frame's logical number."""
        n = int(n)
        k = self._train_k
        if k < 1:
            raise RuntimeError("configure_train(k) before train()")
        outs: List[TickOutputs] = []
        for _ in range(n // k):
            outs.extend(self.train_finish(self.train_begin()))
        for _ in range(n % k):
            outs.append(self.tick())
        return outs

    def _post_tick(self, out: TickOutputs, summary: np.ndarray,
                   exact_deaths: bool = False) -> None:
        n_cls = len(self.store.class_order)
        died_counts = summary[:n_cls]
        off = n_cls + len(out.diff_count)
        rec_keys = sorted(out.rec_diff_count)
        rec_counts = dict(zip(rec_keys, summary[off : off + len(rec_keys)]))
        off2 = off + len(rec_keys)
        # bounded slice: the per-column diff counts and the on-device
        # counter bank ride AFTER the event counts, so an open-ended
        # slice would absorb them
        event_counts = summary[off2 : off2 + len(out.events)]
        off3 = off2 + len(out.events)
        for cname in sorted(out.diff):
            out.diff_cols[cname] = {}
            for bank_name in sorted(out.diff[cname]):
                n = out.diff[cname][bank_name].shape[0]
                out.diff_cols[cname][bank_name] = summary[off3 : off3 + n]
                off3 += n
        # device-emitted events FIRST — entities that died this tick must
        # still deliver their events (the reference fires events before
        # destroy), so guid identities are intact here
        span = self.tracer.span
        with span("fanout.events"):
            live_events = [
                ev for ev, cnt in zip(out.events, event_counts) if cnt > 0
            ]
            if live_events:
                self.events.dispatch_device_events(live_events, self.store)
        # deaths: reconcile host allocation + fire destroy events.
        # exact_deaths (the train path) frees the rows named by THIS
        # frame's died mask — the carried post-train state's alive mask
        # would pin every death to the train's last tick, so attribution
        # must come from the lane, not from reconcile's final-state scan
        with span("fanout.deaths"):
            for cname, cnt in zip(self.store.class_order, died_counts):
                if int(cnt) == 0:
                    continue
                if exact_deaths:
                    rows = np.flatnonzero(np.asarray(out.died[cname]))
                    dead = self.store.release_rows(cname, rows)
                else:
                    dead = self.store.reconcile_deaths(self.state, cname)
                for g in dead:
                    self._fire_class_event(g, cname, ObjectEvent.DESTROY)
        # property-change host subscribers (batch granularity)
        with span("fanout.props"):
            self._fanout_props(out)
        # record-diff subscribers (device-path record mutations)
        with span("fanout.records"):
            for (cname, rname), fns in self._rec_event_subs.items():
                if int(rec_counts.get(cname, 0)) == 0:
                    continue
                codes_dev = out.rec_diff.get(cname, {}).get(rname)
                if codes_dev is None:
                    continue
                codes = np.asarray(codes_dev)
                if codes.any():
                    for fn in fns:
                        fn(cname, rname, codes)

    def _fanout_props(self, out: TickOutputs) -> None:
        """Call the property subscribers with the rows this frame's diff
        names.  The summary's per-column counts say which subscribed
        columns changed; the bit planes of the (class, bank) pairs that
        hold one come over in ONE transfer, an eighth of a byte a cell,
        and each such column's plane is unpacked to its row list.  No
        device array is indexed: a column taken on the device costs a
        dispatch and a blocking read each (~1.65 ms on a v5e, 41 of them
        a served frame)."""
        span = self.tracer.span
        cols: Dict[Tuple[str, str], Tuple[str, int]] = {}
        need: Dict[Tuple[str, str], Any] = {}
        for cname, pname in self._prop_event_subs:
            slot = self.store.spec(cname).slot(pname)
            bank_name = slot.bank.value
            counts = out.diff_cols.get(cname, {}).get(bank_name)
            if counts is None or not counts[slot.col]:
                continue
            cols[cname, pname] = (bank_name, slot.col)
            need[cname, bank_name] = out.diff[cname][bank_name]
        if not need:
            return
        with span("fanout.props.fetch"):
            host = jax.device_get(need)
        self.fanout_mask_fetches += len(host)
        self.fanout_mask_bytes += sum(p.nbytes for p in host.values())
        self.fanout_mask_columns += len(cols)
        with span("fanout.props.unpack"):
            rows = [
                unpack_diff_plane(host[cname, bank_name][col])
                for (cname, _), (bank_name, col) in cols.items()
            ]
        for (cname, pname), changed in zip(cols, rows):
            for fn in self._prop_event_subs[cname, pname]:
                fn(cname, pname, changed)

    # -- object lifecycle (host control plane) ------------------------------

    def create_object(
        self,
        class_name: str,
        values: Optional[Dict[str, Value]] = None,
        guid: Optional[Guid] = None,
        scene: int = 0,
        group: int = 0,
    ) -> Guid:
        vals = dict(values or {})
        if self.store.spec(class_name).has_property("SceneID"):
            vals.setdefault("SceneID", scene)
        if self.store.spec(class_name).has_property("GroupID"):
            vals.setdefault("GroupID", group)
        if self.store.spec(class_name).has_property("ClassName"):
            vals.setdefault("ClassName", class_name)
        self.state, g, _ = self.store.create_object(self.state, class_name, guid, vals)
        if self.store.spec(class_name).has_property("ID"):
            self.state = self.store.set_property(self.state, g, "ID", str(g))
        # full create chain, in order (reference NFCKernelModule.cpp:251-267)
        for ev in (
            ObjectEvent.CREATE_NODATA,
            ObjectEvent.CREATE_LOADDATA,
            ObjectEvent.CREATE_BEFORE_EFFECT,
            ObjectEvent.CREATE_EFFECTDATA,
            ObjectEvent.CREATE_AFTER_EFFECT,
            ObjectEvent.CREATE_HASDATA,
            ObjectEvent.CREATE_FINISH,
        ):
            self._fire_class_event(g, class_name, ev)
        return g

    def create_from_element(
        self,
        class_name: str,
        elem_id: str,
        overrides: Optional[Dict[str, Value]] = None,
        scene: int = 0,
        group: int = 0,
    ) -> Guid:
        """Create seeded from element config (reference CreateObject applies
        the element's Ref/IOBJECT property defaults)."""
        e = self.elements.element(elem_id)
        vals = dict(e.values)
        vals["ConfigID"] = elem_id
        vals.update(overrides or {})
        vals = {
            k: v for k, v in vals.items() if self.store.spec(class_name).has_property(k)
        }
        return self.create_object(class_name, vals, scene=scene, group=group)

    def destroy_object(self, guid: Guid, deferred: bool = False) -> None:
        """Destroy now, or at end of current frame if deferred (reference
        defers self-destroys mid-tick, NFCKernelModule.cpp:273-308)."""
        if deferred:
            self._pending_destroy.append(guid)
            return
        class_name, _ = self.store.row_of(guid)
        self._fire_class_event(guid, class_name, ObjectEvent.BEFORE_DESTROY)
        self.state = self.store.destroy_object(self.state, guid)
        self._fire_class_event(guid, class_name, ObjectEvent.DESTROY)

    def flush_pending_destroy(self) -> int:
        n = 0
        for g in self._pending_destroy:
            if g in self.store.guid_map:
                self.destroy_object(g)
                n += 1
        self._pending_destroy.clear()
        return n

    def execute(self) -> None:
        self.flush_pending_destroy()
        self.events.execute()

    # -- property access with host-callback parity --------------------------

    def set_property(self, guid: Guid, prop_name: str, value: Value) -> None:
        """Host-originated write; fires property subscribers synchronously
        like the reference's SetProperty -> OnEventHandler chain."""
        class_name, row = self.store.row_of(guid)
        old = self.store.get_property(self.state, guid, prop_name)
        self.state = self.store.set_property(self.state, guid, prop_name, value)
        if old != value:
            for fn in self._prop_event_subs.get((class_name, prop_name), ()):
                fn(class_name, prop_name, np.asarray([row]))

    def get_property(self, guid: Guid, prop_name: str) -> Value:
        return self.store.get_property(self.state, guid, prop_name)

    # -- event registration --------------------------------------------------

    def register_class_event(
        self, fn: ClassEventFn, class_name: Optional[str] = None
    ) -> None:
        """Subscribe to create/destroy chains — all classes or one
        (reference RegisterCommonClassEvent / AddClassCallBack)."""
        if class_name is None:
            self._class_event_subs.append(fn)
        else:
            self._class_event_by_class.setdefault(class_name, []).append(fn)

    def register_property_event(
        self, class_name: str, prop_name: str, fn: PropertyEventFn
    ) -> None:
        """Subscribe to a property's changes; called with changed row
        indices after each tick (and synchronously on host writes)."""
        self.store.spec(class_name).slot(prop_name)  # validate
        # diff extraction depends only on diff_flags (static), so no
        # recompilation is needed when subscribers change
        self._prop_event_subs.setdefault((class_name, prop_name), []).append(fn)

    def force_diff_property(self, class_name: str, prop_name: str) -> None:
        """Opt an unflagged property into device diff extraction so its
        tick-path changes reach property subscribers (diff_flags normally
        limit extraction to public/upload columns).  Debug-tool surface —
        the property trail uses it; the first new column per class
        invalidates the compiled tick."""
        self.store.spec(class_name).slot(prop_name)  # validate
        s = self._forced_diff.setdefault(class_name, set())
        if prop_name not in s:
            s.add(prop_name)
            self.invalidate()

    def register_record_diff(
        self, class_name: str, record_name: str, fn: RecordDiffFn
    ) -> None:
        """Subscribe to a record's device-path changes; called after each
        tick with an int8 [C, R] code array (REC_ADDED/REMOVED/UPDATED).
        The diff is computed on device ONLY for subscribed records, so
        registration invalidates the compiled tick."""
        spec = self.store.spec(class_name)
        if record_name not in spec.records:
            raise KeyError(f"{class_name!r} has no record {record_name!r}")
        key = (class_name, record_name)
        first = key not in self._rec_event_subs
        self._rec_event_subs.setdefault(key, []).append(fn)
        if first:
            self.invalidate()

    def subscribe_record_host(self, fn) -> None:
        """Host-path per-op record hook (store mutators; reference
        NFIRecord::AddRecordHook) — see EntityStore.subscribe_records."""
        self.store.subscribe_records(fn)

    def _fire_class_event(self, guid: Guid, class_name: str, ev: ObjectEvent) -> None:
        for fn in self._class_event_by_class.get(class_name, ()):
            fn(guid, class_name, ev)
        for fn in self._class_event_subs:
            fn(guid, class_name, ev)
