"""Structure-of-Arrays entity store: the device-resident world.

The reference keeps a GUID->object map of heap objects, each owning
name->property and name->record maps of tagged variants
(NFCKernelModule.h:30-33, NFCObject.h:19-108).  That layout is hostile to a
TPU, so here the *entire world is a pytree of dense arrays*:

    WorldState
      .classes: {class_name: ClassState}
      .tick:    int32 scalar   (frame counter; time = tick * dt on host)
      .rng:     PRNG key

    ClassState                       (capacity C, from StoreConfig)
      .i32:   int32  [C, n_i32]      int / interned-string / object-handle
      .f32:   float32[C, n_f32]      float properties
      .vec:   float32[C, n_vec, 3]   vector2/3 properties
      .alive: bool   [C]             row in use (a live entity)
      .timers: TimerState [C, n_timers]   (see kernel/schedule.py)
      .records: {record_name: RecordState}

    RecordState                      (R = max_rows per entity)
      .i32:  int32  [C, R, n_i32]
      .f32:  float32[C, R, n_f32]
      .vec:  float32[C, R, n_vec, 3]
      .used: bool   [C, R]

Row allocation is host-owned (free-list per class, like the reference's
deferred create/destroy lists, NFCKernelModule.cpp:76-84): device code only
ever *clears* `alive` (deaths inside a tick); the host reconciles via
`EntityStore.reconcile_deaths`.  GUIDs stay host-side in a Guid<->handle
map; object-valued properties store packed int32 handles
(class_index << 24 | row).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from .datatypes import (
    Bank,
    DataType,
    Guid,
    GuidAllocator,
    NULL_OBJECT,
    Value,
    coerce,
    default_value,
    next_pow2,
)
from .schema import ClassRegistry, ClassSpec, RecordSpec
from .strings import StringTable

HANDLE_ROW_BITS = 24
HANDLE_ROW_MASK = (1 << HANDLE_ROW_BITS) - 1


class RecordOp(enum.IntEnum):
    """Per-op record event types, value-compatible with the reference's
    NFIRecord::RecordOptype (NFIRecord.h:16-25)."""

    ADD = 0
    DEL = 1
    SWAP = 2
    CREATE = 3
    UPDATE = 4
    CLEARED = 5
    SORT = 6
    COVER = 7


# (class_name, record_name, op, entity_rows, rec_row, tags): fired by the
# host-side record mutators, batch-first — entity_rows is an int array so
# the bulk paths (record_write_rows) cost one call, not one per entity.
# tags is the touched-column subset for UPDATE, None for whole-row ops.
# For SWAP, rec_row is the (origin, target) row pair.
RecordEventFn = Callable[
    [str, str, "RecordOp", np.ndarray, Any, Optional[Tuple[str, ...]]], None
]


def with_class(state: "WorldState", class_name: str, cs: "ClassState") -> "WorldState":
    """Functional single-class replacement — the universal update idiom."""
    new_classes = dict(state.classes)
    new_classes[class_name] = cs
    return state.replace(classes=new_classes)


def pack_handle(class_idx: int, row: int) -> int:
    return (class_idx << HANDLE_ROW_BITS) | row


def unpack_handle(handle: int) -> Tuple[int, int]:
    return handle >> HANDLE_ROW_BITS, handle & HANDLE_ROW_MASK


@struct.dataclass
class TimerState:
    """Vectorised heartbeats (reference NFCScheduleModule walks per-object
    timer maps each tick, NFCScheduleModule.cpp:49-110; here firing is one
    compare over [C, n_timers])."""

    next_fire: jnp.ndarray  # int32 [C, T] tick index of next firing
    interval: jnp.ndarray  # int32 [C, T] ticks between firings
    remain: jnp.ndarray  # int32 [C, T] remaining count, -1 = forever
    active: jnp.ndarray  # bool  [C, T]


@struct.dataclass
class RecordState:
    i32: jnp.ndarray
    f32: jnp.ndarray
    vec: jnp.ndarray
    used: jnp.ndarray


@struct.dataclass
class ClassState:
    i32: jnp.ndarray
    f32: jnp.ndarray
    vec: jnp.ndarray
    alive: jnp.ndarray
    timers: TimerState
    records: Dict[str, RecordState]

    @property
    def capacity(self) -> int:
        return self.alive.shape[0]


@struct.dataclass
class WorldState:
    classes: Dict[str, ClassState]
    tick: jnp.ndarray  # int32 scalar
    rng: jnp.ndarray  # PRNG key
    # module-owned carried tick state (e.g. the Verlet grid caches of
    # ops/verlet.py), keyed by registering module; pytree-of-arrays only.
    # Kernel.register_aux primes entries lazily so worlds that use no
    # aux carry an empty dict (zero structural change).
    aux: Dict[str, Any] = dataclasses.field(default_factory=dict)


@jax.jit
def _reset_and_write_rows(cs: ClassState, rows, i32, f32, vec) -> ClassState:
    """One compiled row-(re)initialization: value banks from the staged
    payloads, timers disarmed, records cleared, alive on.  Cached per
    (class pytree structure, row-count bucket) — the host enter-game path
    calls this once per create instead of ~15 eager scatters."""
    t = cs.timers
    timers = TimerState(
        next_fire=t.next_fire.at[rows].set(0),
        interval=t.interval.at[rows].set(1),
        remain=t.remain.at[rows].set(0),
        active=t.active.at[rows].set(False),
    )
    # record pages are cleared by a row mask, not a scatter: a scatter
    # into a [cap, R, k] page makes the TPU compiler relayout the whole
    # page row-major, which pads (R, k) to (8, 128) tiles — at a 2^20
    # capacity the [cap, 9, 29] stat page became two 8 GB temporaries and
    # the program did not fit the chip.  The select keeps the page's own
    # layout; the result is the same.
    hit = jnp.zeros(cs.alive.shape, bool).at[rows].set(True)

    def clear(page, fill):
        mask = hit.reshape(hit.shape + (1,) * (page.ndim - 1))
        return jnp.where(mask, jnp.asarray(fill, page.dtype), page)

    records = {
        rname: RecordState(
            i32=clear(rec.i32, 0),
            f32=clear(rec.f32, 0.0),
            vec=clear(rec.vec, 0.0),
            used=clear(rec.used, False),
        )
        for rname, rec in cs.records.items()
    }
    return cs.replace(
        i32=cs.i32.at[rows].set(i32) if cs.i32.shape[1] else cs.i32,
        f32=cs.f32.at[rows].set(f32) if cs.f32.shape[1] else cs.f32,
        vec=cs.vec.at[rows].set(vec) if cs.vec.shape[1] else cs.vec,
        alive=cs.alive.at[rows].set(True),
        timers=timers,
        records=records,
    )


@dataclasses.dataclass
class StoreConfig:
    default_capacity: int = 1024
    capacities: Dict[str, int] = dataclasses.field(default_factory=dict)
    timer_slots: Dict[str, int] = dataclasses.field(default_factory=dict)

    def capacity_of(self, class_name: str) -> int:
        return int(self.capacities.get(class_name, self.default_capacity))


def _zeros_class_state(spec: ClassSpec, cap: int, n_timers: int) -> ClassState:
    recs = {}
    for rname in spec.record_order:
        rs: RecordSpec = spec.records[rname]
        recs[rname] = RecordState(
            i32=jnp.zeros((cap, rs.max_rows, rs.n_i32), jnp.int32),
            f32=jnp.zeros((cap, rs.max_rows, rs.n_f32), jnp.float32),
            vec=jnp.zeros((cap, rs.max_rows, rs.n_vec, 3), jnp.float32),
            used=jnp.zeros((cap, rs.max_rows), bool),
        )
    return ClassState(
        i32=jnp.zeros((cap, spec.n_i32), jnp.int32),
        f32=jnp.zeros((cap, spec.n_f32), jnp.float32),
        vec=jnp.zeros((cap, spec.n_vec, 3), jnp.float32),
        alive=jnp.zeros((cap,), bool),
        timers=TimerState(
            next_fire=jnp.zeros((cap, n_timers), jnp.int32),
            interval=jnp.ones((cap, n_timers), jnp.int32),
            remain=jnp.zeros((cap, n_timers), jnp.int32),
            active=jnp.zeros((cap, n_timers), bool),
        ),
        records=recs,
    )


class _ClassHost:
    """Host bookkeeping for one class: free rows + row->guid."""

    def __init__(self, spec: ClassSpec, class_idx: int, capacity: int):
        self.spec = spec
        self.class_idx = class_idx
        self.capacity = capacity
        self.free: List[int] = list(range(capacity - 1, -1, -1))
        self.row_guid: List[Optional[Guid]] = [None] * capacity
        # host-side allocation bitmap: lets reconcile_deaths find device
        # deaths with ONE vector op instead of a Python scan of every row
        self.alloc_mask = np.zeros(capacity, bool)
        # columnar guid mirror of row_guid — the batch sync path reads
        # guid identities for thousands of rows with one gather
        self.guid_head = np.zeros(capacity, np.int64)
        self.guid_data = np.zeros(capacity, np.int64)
        # allocation generation, +1 per free: guids never recycle (pure
        # counter), so within one generation row ⟺ guid.  The batched
        # serve edge (ops/serving.py) uploads this i32 vector instead of
        # comparing int64 guid pairs on device
        self.row_gen = np.zeros(capacity, np.int32)
        self.live_count = 0

    def alloc(self) -> int:
        if not self.free:
            raise RuntimeError(
                f"class {self.spec.name!r} capacity {self.capacity} exhausted"
            )
        self.live_count += 1
        row = self.free.pop()
        self.alloc_mask[row] = True
        return row

    def alloc_many(self, n: int) -> np.ndarray:
        if n <= 0:  # free[-0:] would slice the WHOLE list
            return np.zeros(0, np.int32)
        if len(self.free) < n:
            raise RuntimeError(
                f"class {self.spec.name!r} capacity {self.capacity} exhausted "
                f"({len(self.free)} free, {n} requested)"
            )
        rows = np.asarray(self.free[-n:][::-1], np.int32)
        del self.free[-n:]
        self.live_count += n
        self.alloc_mask[rows] = True
        return rows

    def release(self, row: int) -> None:
        self.row_guid[row] = None
        self.free.append(row)
        self.alloc_mask[row] = False
        self.guid_head[row] = 0
        self.guid_data[row] = 0
        self.row_gen[row] += 1  # row recycled ⇒ any future guid differs
        self.live_count -= 1


class EntityStore:
    """Host-side owner of the device world: allocation, identity, typed
    access.  All state mutation is functional — methods take and return
    WorldState."""

    def __init__(
        self,
        registry: ClassRegistry,
        config: Optional[StoreConfig] = None,
        strings: Optional[StringTable] = None,
        guid_alloc: Optional[GuidAllocator] = None,
        class_names: Optional[Sequence[str]] = None,
    ):
        self.registry = registry
        self.config = config or StoreConfig()
        self.strings = strings or StringTable()
        self.guids = guid_alloc or GuidAllocator()
        names = list(class_names) if class_names is not None else registry.names()
        self.class_order: List[str] = names
        self.class_index: Dict[str, int] = {n: i for i, n in enumerate(names)}
        self._hosts: Dict[str, _ClassHost] = {}
        self.guid_map: Dict[Guid, int] = {}  # guid -> packed handle
        # host-path record hooks (reference NFIRecord::AddRecordHook);
        # device-path record changes are diffed by the kernel tick instead
        self.record_subs: List[RecordEventFn] = []
        for n in names:
            spec = registry.spec(n)
            self._hosts[n] = _ClassHost(
                spec, self.class_index[n], self.config.capacity_of(n)
            )

    # -- construction -------------------------------------------------------

    def init_state(self, seed: int = 0) -> WorldState:
        classes = {}
        for n in self.class_order:
            h = self._hosts[n]
            n_timers = int(self.config.timer_slots.get(n, 0))
            classes[n] = _zeros_class_state(h.spec, h.capacity, n_timers)
        return WorldState(
            classes=classes,
            tick=jnp.zeros((), jnp.int32),
            rng=jax.random.PRNGKey(seed),
        )

    def spec(self, class_name: str) -> ClassSpec:
        return self._hosts[class_name].spec

    def capacity(self, class_name: str) -> int:
        return self._hosts[class_name].capacity

    def live_count(self, class_name: str) -> int:
        return self._hosts[class_name].live_count

    # -- value encoding -----------------------------------------------------

    def encode(self, t: DataType, v: Value):
        """Host value -> device scalar/vector for a property of type t."""
        if t != DataType.OBJECT:
            v = coerce(t, v)
        if t == DataType.INT:
            return np.int32(v)
        if t == DataType.FLOAT:
            return np.float32(v)
        if t == DataType.STRING:
            return np.int32(self.strings.intern(v))
        if t == DataType.OBJECT:
            if isinstance(v, (int, np.integer)) and not isinstance(v, bool):
                return np.int32(v)  # raw packed handle passed straight through
            v = coerce(t, v)
            if v.is_null():
                return np.int32(NULL_OBJECT)
            h = self.guid_map.get(v)
            if h is None:
                raise KeyError(f"unknown guid {v} for OBJECT property")
            return np.int32(h)
        if t == DataType.VECTOR2:
            return np.asarray([v[0], v[1], 0.0], np.float32)
        if t == DataType.VECTOR3:
            return np.asarray(v, np.float32)
        raise ValueError(f"cannot encode {t}")

    def decode(self, t: DataType, raw) -> Value:
        """Device scalar/vector -> host value."""
        if t == DataType.INT:
            return int(raw)
        if t == DataType.FLOAT:
            return float(raw)
        if t == DataType.STRING:
            return self.strings.lookup(int(raw))
        if t == DataType.OBJECT:
            h = int(raw)
            if h == NULL_OBJECT:
                return Guid()
            ci, row = unpack_handle(h)
            g = self._hosts[self.class_order[ci]].row_guid[row]
            return g if g is not None else Guid()
        if t == DataType.VECTOR2:
            a = np.asarray(raw)
            return (float(a[0]), float(a[1]))
        if t == DataType.VECTOR3:
            a = np.asarray(raw)
            return (float(a[0]), float(a[1]), float(a[2]))
        raise ValueError(f"cannot decode {t}")

    # -- create / destroy ---------------------------------------------------

    def handle_of(self, guid: Guid) -> int:
        return self.guid_map[guid]

    def guid_of_handle(self, handle: int) -> Optional[Guid]:
        h = int(handle)
        if h < 0:  # NULL_OBJECT and any other negative sentinel
            return None
        ci, row = unpack_handle(h)
        if ci >= len(self.class_order):
            return None
        return self._hosts[self.class_order[ci]].row_guid[row]

    def row_of(self, guid: Guid) -> Tuple[str, int]:
        ci, row = unpack_handle(self.guid_map[guid])
        return self.class_order[ci], row

    def create_object(
        self,
        state: WorldState,
        class_name: str,
        guid: Optional[Guid] = None,
        values: Optional[Dict[str, Value]] = None,
    ) -> Tuple[WorldState, Guid, int]:
        """Allocate one row; returns (state', guid, row).  Defaults and
        overrides are applied column-wise.  The create-event chain
        (COE_CREATE_* states, reference NFCKernelModule.cpp:251-267) is
        driven by the kernel module on top of this primitive."""
        state, guids, rows = self.create_many(
            state,
            class_name,
            1,
            guids=[guid] if guid is not None else None,
            values={k: [v] for k, v in (values or {}).items()},
        )
        return state, guids[0], rows[0]

    def create_many(
        self,
        state: WorldState,
        class_name: str,
        n: int,
        guids: Optional[Sequence[Guid]] = None,
        values: Optional[Dict[str, Sequence[Value]]] = None,
    ) -> Tuple[WorldState, List[Guid], np.ndarray]:
        """Bulk allocate n rows of class_name with per-property value
        columns.  One scatter per touched bank — this is the fast path used
        by NPC seeding and the benchmarks."""
        host = self._hosts[class_name]
        spec = host.spec
        # Stage ALL payloads and validate identities BEFORE touching any
        # host bookkeeping, so a bad property name, unknown guid, or full
        # class leaks nothing.
        i32 = np.zeros((n, spec.n_i32), np.int32)
        f32 = np.zeros((n, spec.n_f32), np.float32)
        vec = np.zeros((n, spec.n_vec, 3), np.float32)
        for slot in spec.slots.values():
            d = slot.prop.resolved_default()
            enc = self.encode(slot.prop.type, d)
            if slot.bank == Bank.I32:
                i32[:, slot.col] = enc
            elif slot.bank == Bank.F32:
                f32[:, slot.col] = enc
            else:
                vec[:, slot.col] = enc
        if values:
            for pname, col_vals in values.items():
                slot = spec.slot(pname)
                enc = [self.encode(slot.prop.type, v) for v in col_vals]
                if slot.bank == Bank.I32:
                    i32[:, slot.col] = np.asarray(enc, np.int32)
                elif slot.bank == Bank.F32:
                    f32[:, slot.col] = np.asarray(enc, np.float32)
                else:
                    vec[:, slot.col] = np.asarray(enc, np.float32)
        if guids is not None:
            if len(guids) != n:
                raise ValueError("guids length must equal n")
            if len({*guids}) != n:
                raise ValueError("duplicate guids in create_many batch")
            for g in guids:
                if g in self.guid_map:
                    raise ValueError(f"guid {g} already exists")
        if len(host.free) < n:
            raise RuntimeError(
                f"class {spec.name!r} capacity {host.capacity} exhausted "
                f"({len(host.free)} free, {n} requested)"
            )
        rows = host.alloc_many(n)
        out_guids: List[Guid] = (
            list(guids) if guids is not None else self.guids.next_batch(n)
        )
        ci = host.class_idx
        for g, row in zip(out_guids, rows.tolist()):
            self.guid_map[g] = pack_handle(ci, row)
            host.row_guid[row] = g
        host.guid_head[rows] = np.fromiter((g.head for g in out_guids), np.int64, n)
        host.guid_data[rows] = np.fromiter((g.data for g in out_guids), np.int64, n)

        cs = state.classes[class_name]
        # Fully reset the rows in ONE compiled call (banks to
        # defaults/overrides, timers off, every record cleared — recycled
        # rows must not leak the previous entity's records or heartbeat
        # schedule).  The row index and payloads pad to a power-of-2
        # bucket (repeating row 0 — idempotent duplicate writes) so
        # enter-game-sized creates reuse a cached executable instead of
        # dispatching ~15 eager scatters per object.
        if n == 0:
            return state, out_guids, rows
        m = next_pow2(n)
        if m != n:
            pad = m - n
            rows_p = np.concatenate([rows, np.repeat(rows[:1], pad)])
            i32 = np.concatenate([i32, np.repeat(i32[:1], pad, 0)])
            f32 = np.concatenate([f32, np.repeat(f32[:1], pad, 0)])
            vec = np.concatenate([vec, np.repeat(vec[:1], pad, 0)])
        else:
            rows_p = rows
        cs = _reset_and_write_rows(
            cs, jnp.asarray(rows_p), jnp.asarray(i32), jnp.asarray(f32),
            jnp.asarray(vec),
        )
        return with_class(state, class_name, cs), out_guids, rows

    def destroy_object(self, state: WorldState, guid: Guid) -> WorldState:
        class_name, row = self.row_of(guid)
        host = self._hosts[class_name]
        cs = state.classes[class_name]
        cs = cs.replace(
            alive=cs.alive.at[row].set(False),
            timers=cs.timers.replace(active=cs.timers.active.at[row].set(False)),
        )
        del self.guid_map[guid]
        host.release(row)
        return with_class(state, class_name, cs)

    def reconcile_deaths(self, state: WorldState, class_name: str) -> List[Guid]:
        """Sync host allocation with rows whose `alive` was cleared on
        device (in-tick deaths).  Returns the guids destroyed.  The device
        never allocates — it only kills — so host free-lists stay exact.
        One vector compare against the host alloc bitmap; Python touches
        only the dead rows (round-1: this scanned every capacity row)."""
        host = self._hosts[class_name]
        alive = np.asarray(state.classes[class_name].alive)
        dead_rows = np.flatnonzero(host.alloc_mask & ~alive)
        return self.release_rows(class_name, dead_rows)

    def release_rows(self, class_name: str, rows) -> List[Guid]:
        """Free exactly `rows` (device-killed) and return their guids.
        The tick-train fan-out uses this with each stacked frame's own
        died mask: the post-train alive scan of reconcile_deaths cannot
        say WHICH tick killed a row, but the per-lane mask can.  Rows
        already free are skipped, so replaying a lane is harmless."""
        host = self._hosts[class_name]
        dead: List[Guid] = []
        for row in np.asarray(rows).tolist():
            if not host.alloc_mask[row]:
                continue
            g = host.row_guid[row]
            if g is None:
                continue
            dead.append(g)
            del self.guid_map[g]
            host.release(row)
        return dead

    # -- typed property access (host control plane) -------------------------

    def set_property(
        self, state: WorldState, guid: Guid, prop_name: str, value: Value
    ) -> WorldState:
        class_name, row = self.row_of(guid)
        spec = self.spec(class_name)
        slot = spec.slot(prop_name)
        enc = self.encode(slot.prop.type, value)
        cs = state.classes[class_name]
        if slot.bank == Bank.I32:
            cs = cs.replace(i32=cs.i32.at[row, slot.col].set(enc))
        elif slot.bank == Bank.F32:
            cs = cs.replace(f32=cs.f32.at[row, slot.col].set(enc))
        else:
            cs = cs.replace(vec=cs.vec.at[row, slot.col].set(enc))
        return with_class(state, class_name, cs)

    def get_property(self, state: WorldState, guid: Guid, prop_name: str) -> Value:
        class_name, row = self.row_of(guid)
        spec = self.spec(class_name)
        slot = spec.slot(prop_name)
        cs = state.classes[class_name]
        if slot.bank == Bank.I32:
            raw = cs.i32[row, slot.col]
        elif slot.bank == Bank.F32:
            raw = cs.f32[row, slot.col]
        else:
            raw = cs.vec[row, slot.col]
        return self.decode(slot.prop.type, raw)

    # -- record access (host control plane) ---------------------------------

    def _rec(self, class_name: str, record_name: str) -> RecordSpec:
        return self.spec(class_name).records[record_name]

    def subscribe_records(self, fn: RecordEventFn) -> None:
        """Register a host-path record hook (NFIRecord::AddRecordHook):
        fired after every host record mutation with the op, the touched
        entity rows, the record row, and (for UPDATE) the column tags."""
        self.record_subs.append(fn)

    def _fire_record(
        self,
        class_name: str,
        record_name: str,
        op: RecordOp,
        entity_rows,
        rec_row: int,
        tags: Optional[Tuple[str, ...]] = None,
    ) -> None:
        if not self.record_subs:
            return
        rows = np.atleast_1d(np.asarray(entity_rows, np.int64))
        for fn in self.record_subs:
            fn(class_name, record_name, op, rows, rec_row, tags)

    def record_add_row(
        self,
        state: WorldState,
        guid: Guid,
        record_name: str,
        row_values: Dict[str, Value],
    ) -> Tuple[WorldState, int]:
        """Append a row into the first unused slot (reference
        NFCRecord::AddRow semantics)."""
        class_name, row = self.row_of(guid)
        rs = self._rec(class_name, record_name)
        rec = state.classes[class_name].records[record_name]
        used = np.asarray(rec.used[row])
        free = np.flatnonzero(~used)
        if free.size == 0:
            raise RuntimeError(f"record {record_name!r} full ({rs.max_rows} rows)")
        r = int(free[0])
        # write defaults for unspecified columns so a reused slot cannot
        # expose the deleted row's data (reference AddRow sets every cell)
        full: Dict[str, Value] = {
            tag: default_value(rs.cols[tag].col_def.type) for tag in rs.col_order
        }
        full.update(row_values)
        state = self._record_write(state, class_name, row, record_name, r, full)
        cs = state.classes[class_name]
        rec = cs.records[record_name]
        rec = rec.replace(used=rec.used.at[row, r].set(True))
        state = with_class(
            state, class_name, cs.replace(records={**cs.records, record_name: rec})
        )
        self._fire_record(class_name, record_name, RecordOp.ADD, row, r)
        return state, r

    def record_restore_row(
        self,
        state: WorldState,
        guid: Guid,
        record_name: str,
        rec_row: int,
        row_values: Dict[str, Value],
    ) -> WorldState:
        """Write a row at an exact index and mark it used — the
        persistence/load path, which must preserve row indices (the
        reference's protobuf record blobs are row-addressed)."""
        class_name, row = self.row_of(guid)
        rs = self._rec(class_name, record_name)
        full: Dict[str, Value] = {
            tag: default_value(rs.cols[tag].col_def.type) for tag in rs.col_order
        }
        full.update(row_values)
        state = self._record_write(state, class_name, row, record_name, rec_row, full)
        cs = state.classes[class_name]
        rec = cs.records[record_name]
        rec = rec.replace(used=rec.used.at[row, rec_row].set(True))
        state = with_class(
            state, class_name, cs.replace(records={**cs.records, record_name: rec})
        )
        self._fire_record(class_name, record_name, RecordOp.ADD, row, rec_row)
        return state

    def record_remove_row(
        self, state: WorldState, guid: Guid, record_name: str, rec_row: int
    ) -> WorldState:
        class_name, row = self.row_of(guid)
        cs = state.classes[class_name]
        rec = cs.records[record_name]
        rec = rec.replace(used=rec.used.at[row, rec_row].set(False))
        state = with_class(
            state, class_name, cs.replace(records={**cs.records, record_name: rec})
        )
        self._fire_record(class_name, record_name, RecordOp.DEL, row, rec_row)
        return state

    def record_swap_rows(
        self,
        state: WorldState,
        guid: Guid,
        record_name: str,
        row_origin: int,
        row_target: int,
    ) -> WorldState:
        """Exchange two record rows' contents and used flags in one op
        (reference NFCRecord::SwapRowInfo, NFCRecord.h:17-156)."""
        class_name, row = self.row_of(guid)
        cs = state.classes[class_name]
        rec = cs.records[record_name]
        pair = np.asarray([row_origin, row_target])
        swapped = np.asarray([row_target, row_origin])
        rec = rec.replace(
            i32=rec.i32.at[row, pair].set(rec.i32[row, swapped]),
            f32=rec.f32.at[row, pair].set(rec.f32[row, swapped]),
            vec=rec.vec.at[row, pair].set(rec.vec[row, swapped]),
            used=rec.used.at[row, pair].set(rec.used[row, swapped]),
        )
        state = with_class(
            state, class_name, cs.replace(records={**cs.records, record_name: rec})
        )
        self._fire_record(
            class_name, record_name, RecordOp.SWAP, row, (row_origin, row_target)
        )
        return state

    def record_set(
        self,
        state: WorldState,
        guid: Guid,
        record_name: str,
        rec_row: int,
        tag: str,
        value: Value,
    ) -> WorldState:
        class_name, row = self.row_of(guid)
        state = self._record_write(
            state, class_name, row, record_name, rec_row, {tag: value}
        )
        self._fire_record(
            class_name, record_name, RecordOp.UPDATE, row, rec_row, (tag,)
        )
        return state

    def record_get(
        self, state: WorldState, guid: Guid, record_name: str, rec_row: int, tag: str
    ) -> Value:
        class_name, row = self.row_of(guid)
        rs = self._rec(class_name, record_name)
        slot = rs.cols[tag]
        rec = state.classes[class_name].records[record_name]
        if slot.bank == Bank.I32:
            raw = rec.i32[row, rec_row, slot.col]
        elif slot.bank == Bank.F32:
            raw = rec.f32[row, rec_row, slot.col]
        else:
            raw = rec.vec[row, rec_row, slot.col]
        return self.decode(slot.col_def.type, raw)

    def record_used_rows(
        self, state: WorldState, guid: Guid, record_name: str
    ) -> List[int]:
        """Indices of used rows in an entity's record (the shared scan
        behind row-identified records: heroes, buildings, equips)."""
        class_name, row = self.row_of(guid)
        rec = state.classes[class_name].records.get(record_name)
        if rec is None:
            return []
        return [int(r) for r in np.flatnonzero(np.asarray(rec.used[row]))]

    def record_find_rows(
        self, state: WorldState, guid: Guid, record_name: str, tag: str, value: Value
    ) -> List[int]:
        """Find used rows whose `tag` column equals value (reference
        NFCRecord::FindInt/FindString family)."""
        class_name, row = self.row_of(guid)
        rs = self._rec(class_name, record_name)
        slot = rs.cols[tag]
        rec = state.classes[class_name].records[record_name]
        enc = self.encode(slot.col_def.type, value)
        if slot.bank == Bank.I32:
            col = np.asarray(rec.i32[row, :, slot.col])
        elif slot.bank == Bank.F32:
            col = np.asarray(rec.f32[row, :, slot.col])
        else:
            raise TypeError("find on vector columns unsupported")
        used = np.asarray(rec.used[row])
        return [int(i) for i in np.flatnonzero(used & (col == enc))]

    def record_write_rows(
        self,
        state: WorldState,
        class_name: str,
        rows: np.ndarray,
        record_name: str,
        rec_row: int,
        col_values: Dict[str, Sequence[Value]],
        mark_used: bool = True,
    ) -> WorldState:
        """Bulk write one record row (`rec_row`) across many entities: for
        each tag, col_values[tag][i] lands in entity rows[i].  One scatter
        per touched bank — the batch path stat seeding and equip systems
        use (host-loop-free counterpart of NFCRecord::SetInt per object)."""
        rs = self._rec(class_name, record_name)
        n = len(rows)
        staged: Dict[Bank, np.ndarray] = {}
        shapes = {
            Bank.I32: (n, rs.n_i32),
            Bank.F32: (n, rs.n_f32),
            Bank.VEC: (n, rs.n_vec, 3),
        }
        touched: Dict[Bank, List[int]] = {Bank.I32: [], Bank.F32: [], Bank.VEC: []}
        for tag, vals in col_values.items():
            slot = rs.cols[tag]
            if slot.bank not in staged:
                staged[slot.bank] = np.zeros(shapes[slot.bank], np.float32 if slot.bank != Bank.I32 else np.int32)
            enc = [self.encode(slot.col_def.type, v) for v in vals]
            staged[slot.bank][:, slot.col] = np.asarray(enc)
            touched[slot.bank].append(slot.col)
        cs = state.classes[class_name]
        rec = cs.records[record_name]
        i32, f32, vec = rec.i32, rec.f32, rec.vec
        if touched[Bank.I32]:
            cols = np.asarray(touched[Bank.I32])
            i32 = i32.at[rows[:, None], rec_row, cols[None, :]].set(staged[Bank.I32][:, cols])
        if touched[Bank.F32]:
            cols = np.asarray(touched[Bank.F32])
            f32 = f32.at[rows[:, None], rec_row, cols[None, :]].set(staged[Bank.F32][:, cols])
        if touched[Bank.VEC]:
            cols = np.asarray(touched[Bank.VEC])
            vec = vec.at[rows[:, None], rec_row, cols[None, :]].set(staged[Bank.VEC][:, cols])
        used = rec.used.at[rows, rec_row].set(True) if mark_used else rec.used
        rec = rec.replace(i32=i32, f32=f32, vec=vec, used=used)
        state = with_class(
            state, class_name, cs.replace(records={**cs.records, record_name: rec})
        )
        self._fire_record(
            class_name, record_name, RecordOp.UPDATE, rows, rec_row,
            tuple(col_values),
        )
        return state

    def _record_write(
        self,
        state: WorldState,
        class_name: str,
        row: int,
        record_name: str,
        rec_row: int,
        row_values: Dict[str, Value],
    ) -> WorldState:
        rs = self._rec(class_name, record_name)
        cs = state.classes[class_name]
        rec = cs.records[record_name]
        i32, f32, vec = rec.i32, rec.f32, rec.vec
        for tag, v in row_values.items():
            slot = rs.cols[tag]
            enc = self.encode(slot.col_def.type, v)
            if slot.bank == Bank.I32:
                i32 = i32.at[row, rec_row, slot.col].set(enc)
            elif slot.bank == Bank.F32:
                f32 = f32.at[row, rec_row, slot.col].set(enc)
            else:
                vec = vec.at[row, rec_row, slot.col].set(enc)
        rec = rec.replace(i32=i32, f32=f32, vec=vec)
        return with_class(state, class_name, cs.replace(records={**cs.records, record_name: rec}))

    # -- column views (device fast path) ------------------------------------

    def column(self, state: WorldState, class_name: str, prop_name: str) -> jnp.ndarray:
        """Whole property column [C] (or [C,3] for vectors) — the device
        fast path used inside jitted module phases."""
        slot = self.spec(class_name).slot(prop_name)
        cs = state.classes[class_name]
        if slot.bank == Bank.I32:
            return cs.i32[:, slot.col]
        if slot.bank == Bank.F32:
            return cs.f32[:, slot.col]
        return cs.vec[:, slot.col]

    def with_column(
        self, state: WorldState, class_name: str, prop_name: str, col: jnp.ndarray
    ) -> WorldState:
        slot = self.spec(class_name).slot(prop_name)
        cs = state.classes[class_name]
        if slot.bank == Bank.I32:
            cs = cs.replace(i32=cs.i32.at[:, slot.col].set(col))
        elif slot.bank == Bank.F32:
            cs = cs.replace(f32=cs.f32.at[:, slot.col].set(col))
        else:
            cs = cs.replace(vec=cs.vec.at[:, slot.col].set(col))
        return with_class(state, class_name, cs)
