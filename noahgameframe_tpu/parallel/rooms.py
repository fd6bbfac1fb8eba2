"""Many-worlds room engine: thousands of independent rooms on one mesh.

The reference's genre scales by INSTANCES, not by one giant world: the
scene/group AOI layer (NFCSceneAOIModule) partitions players into
~100-entity rooms and proxies route each session to the game server
hosting its scene — "millions of users" means tens of thousands of small
rooms.  The single-world engines here (ShardedKernel, ElasticMesh) shard
one world's ENTITY axis; this module adds the orthogonal scale shape:

    batched = stack(room_0, room_1, ..., room_{R-1})      # [R, ...]
    step_R  = jax.vmap(kernel._trace_step)                # one trace
    sharding = NamedSharding(mesh, PartitionSpec("rooms"))

Every WorldState leaf gains a leading room axis (tick and rng included —
rooms tick independently), the fused tick vmaps over it unchanged, and
the room axis block-partitions across the mesh so each device owns a
contiguous range of room SLOTS.  Rooms never interact on device by
construction (vmap semantics ARE the isolation proof), so per-room
results are bit-identical to R independent single-room kernels — the
parity spine tests/test_rooms.py pins.

Host side mirrors the serving layer's slot discipline:

* ``RoomBinPacker`` — slots group into per-device blocks; create picks
  the least-loaded block's lowest free slot (or first-fit).
* rooms are admitted in BULK: ``create_rooms(seeds)`` makes the rows
  of all of them (a recipe with ``seeded_rows`` makes only the leaves a
  seed decides, on the host, for every seed at once; a recipe that is
  only a world builder has its worlds' states stacked) and writes them
  with one scatter per leaf.  ``create_room`` is the batch of one.
* create/destroy are SLOT RECYCLING with lazy wipe (SessionTable's
  ``_stale`` discipline): destroy only frees the host slot; admit's
  full-leaf scatter overwrites every byte, so no device wipe runs and —
  critically — no shape changes, so room churn never retraces.  Growing
  the slot bank doubles capacity under a sanctioned
  ``costbook.generation_bump`` exactly like the combat bucket resize.
* re-home moves a room between slots/devices as BYTES: the packed leaves
  travel in a ``persist/rowblob.frame_blob`` CRC frame carrying the
  room's positional digest, so a torn or stale re-home is rejected
  before it ever reaches the destination slot.

``ROOM_PACK_SPEC`` below is the reviewed enumeration of what "a room"
is; the ``room-axis-covered`` nf-lint rule cross-checks it against the
WorldState dataclass statically, and :func:`world_room_leaf_items`
enforces it at runtime (the rowblob/migrate-covers-store pattern one
level up the pytree).  ``WorldState.aux`` is excluded on purpose: Verlet
and binning caches are dropped on admit and rebuilt by the next tick,
and the true-radius masking of ops/verlet.py keeps results bit-identical
to a warm-cache control (same contract checkpoint resume relies on).
"""

from __future__ import annotations

import os
import struct as _struct
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..core.datatypes import next_pow2
from ..core.store import WorldState
from ..kernel.kernel import Kernel
from ..persist.rowblob import (
    RowBlobError,
    class_row_leaf_items,
    frame_blob,
    rebuild_class_state,
    unframe_blob,
)
from .mesh import ROOMS_AXIS  # noqa: F401  (re-exported: the axis name)

__all__ = [
    "ROOMS_AXIS",
    "ROOM_EXCLUDED",
    "ROOM_PACK_SPEC",
    "RoomBatch",
    "RoomBinPacker",
    "RoomDirectory",
    "RoomSlotsFull",
    "pack_room_blob",
    "room_digest",
    "unpack_room_blob",
    "world_room_leaf_items",
]

#: default slot-bank capacity when RoomDirectory isn't told one
ENV_ROOM_SLOTS = "NF_ROOM_SLOTS"

# Every WorldState leaf path must match one of these patterns (or appear
# in ROOM_EXCLUDED with a reason).  The room-axis-covered lint rule
# cross-checks this tuple against the store dataclasses; keep it a plain
# literal.
ROOM_PACK_SPEC = (
    "tick",
    "rng",
    "classes.*.i32",
    "classes.*.f32",
    "classes.*.vec",
    "classes.*.alive",
    "classes.*.timers.next_fire",
    "classes.*.timers.interval",
    "classes.*.timers.remain",
    "classes.*.timers.active",
    "classes.*.records.*.i32",
    "classes.*.records.*.f32",
    "classes.*.records.*.vec",
    "classes.*.records.*.used",
)

# Leaves waived from the room pack, with a reason each.  aux holds
# module caches (Verlet tables) that are dropped on admit and rebuilt by
# the next tick — results stay bit-identical under true-radius masking,
# and the caches bake trace-time geometry that must not travel.
ROOM_EXCLUDED = (
    "aux.*",
)


class RoomSlotsFull(RuntimeError):
    """Every room slot is occupied — grow() the batch (a sanctioned
    generation bump) or shed rooms before creating more."""

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        super().__init__(
            f"all {capacity} room slots occupied — grow the RoomBatch "
            "(sanctioned retrace) or destroy rooms first"
        )


# -- the room leaf walk (pack/lint contract) --------------------------------


def world_room_leaf_items(
    state: WorldState, class_order: Optional[Sequence[str]] = None,
) -> List[Tuple[str, Any]]:
    """Ordered ``(path, array)`` pairs for every PACKED leaf of one
    room's WorldState (no leading room axis) — tick, rng, then every
    ClassState row leaf per class.  aux is skipped (ROOM_EXCLUDED) but
    its keys are still checked against the exclusion patterns, so an
    aux entry can never silently dodge the reviewed contract."""
    import fnmatch

    def covered(path: str, pats) -> bool:
        return any(fnmatch.fnmatch(path, p) for p in pats)

    items: List[Tuple[str, Any]] = [("tick", state.tick), ("rng", state.rng)]
    names = list(class_order) if class_order is not None \
        else sorted(state.classes)
    for cname in names:
        for path, arr in class_row_leaf_items(state.classes[cname]):
            items.append((f"classes.{cname}.{path}", arr))
    for path, _arr in items:
        if not covered(path, ROOM_PACK_SPEC):
            raise RowBlobError(
                f"WorldState leaf {path!r} not covered by ROOM_PACK_SPEC "
                "— re-homing would silently leave this bank behind")
    for key in getattr(state, "aux", {}) or {}:
        if not covered(f"aux.{key}", ROOM_EXCLUDED):
            raise RowBlobError(
                f"aux entry {key!r} matches neither ROOM_PACK_SPEC nor "
                "ROOM_EXCLUDED — waive it explicitly or pack it")
    return items


# -- placement-invariant per-room digest ------------------------------------


def room_digest(
    state: WorldState,
    class_order: Sequence[str],
    ident_cols: Optional[Dict[str, int]] = None,
) -> int:
    """Host-side uint32 digest of ONE room, bit-compatible with the
    device ``kernel.state_digest`` fold (same seed, weights, rolling
    multiply, aux exclusion).  Row layout inside a room never changes
    when the room moves slots — admit copies leaves verbatim — so the
    positional fold is already SLOT-invariant, and equality against a
    single-room control world is exact.  Pass ``ident_cols`` to delegate
    to ``rowmigrate.canonical_digest`` instead when rows themselves may
    have been permuted (a room extracted from a mesh-migrating world)."""
    if ident_cols is not None:
        from .rowmigrate import canonical_digest

        return canonical_digest(state, class_order, ident_cols)
    mult = np.uint64(1000003)
    mask = np.uint64(0xFFFFFFFF)

    def fold(acc: np.uint64, arr) -> np.uint64:
        a = np.ascontiguousarray(np.asarray(arr))
        if a.dtype == np.bool_:
            u = a.astype(np.uint32)
        elif a.dtype.itemsize == 4:
            u = a.view(np.uint32)
        else:
            u = a.astype(np.uint32)
        u = u.ravel().astype(np.uint64)
        w = np.arange(u.size, dtype=np.uint64) * 2 + 1
        s = np.uint64(int((u * w).sum(dtype=np.uint64)) & 0xFFFFFFFF)
        return (acc * mult + s) & mask

    acc = np.uint64(0x9E3779B9)
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
    return int(acc)


def _rebuild_room(template: WorldState, class_order: Sequence[str],
                  leaves: Sequence[Any]) -> WorldState:
    """Inverse of :func:`world_room_leaf_items`: ``template`` with its
    packed leaves replaced, in walk order, and no aux.  The leaves may
    carry a leading rooms axis; nothing here looks at shapes."""
    it = iter(leaves)
    tick, rng = next(it), next(it)
    classes = {}
    for cname in class_order:
        cs = template.classes[cname]
        n = len(class_row_leaf_items(cs))
        classes[cname] = rebuild_class_state(
            cs, [jnp.asarray(next(it)) for _ in range(n)])
    return template.replace(
        classes={**template.classes, **classes},
        tick=jnp.asarray(tick), rng=jnp.asarray(rng), aux={})


# -- room blob (re-home / cross-engine snapshot framing) --------------------

_ROOM_MAGIC = b"NFRM"
_ROOM_VERSION = 1
_ROOM_HEADER = _struct.Struct("<4sBHI")  # magic, version, n_leaves, digest
_LEAF_HEADER = _struct.Struct("<HHB")  # path_len, dtype_len, ndim


def pack_room_blob(state: WorldState, class_order: Sequence[str]) -> bytes:
    """Serialize one room's packed leaves (ROOM_PACK_SPEC order) into a
    CRC-framed blob carrying the room's positional digest.  The frame is
    ``persist/rowblob.frame_blob`` — the same envelope session snapshots
    cross hosts in — so torn re-homes are detected identically."""
    items = world_room_leaf_items(state, class_order)
    digest = room_digest(state, class_order)
    parts = [_ROOM_HEADER.pack(_ROOM_MAGIC, _ROOM_VERSION, len(items), digest)]
    for path, arr in items:
        # NOT ascontiguousarray: it promotes the 0-d tick to [1], and
        # tobytes() already emits a C-order copy for any layout
        a = np.asarray(arr)
        p = path.encode()
        d = a.dtype.str.encode()
        parts.append(_LEAF_HEADER.pack(len(p), len(d), a.ndim))
        parts.append(p)
        parts.append(d)
        parts.append(_struct.pack(f"<{a.ndim}I", *a.shape))
        parts.append(a.tobytes())
    return frame_blob(b"".join(parts))


def unpack_room_blob(blob: bytes, template: WorldState,
                     class_order: Sequence[str]) -> WorldState:
    """Validate + decode a room blob against ``template``'s structure.

    Fail-closed on every mismatch: frame CRC, magic/version, leaf order,
    dtype, shape — and finally the embedded digest is recomputed over
    the rebuilt room, so a blob corrupted in a way the CRC survived (or
    packed by a structurally different build) can never be admitted.
    Returns a room WorldState with ``aux={}`` (admit supplies fresh
    caches)."""
    payload = unframe_blob(blob, allow_legacy=False)
    if len(payload) < _ROOM_HEADER.size:
        raise RowBlobError("room blob truncated before header")
    magic, version, n_leaves, digest = _ROOM_HEADER.unpack_from(payload)
    if magic != _ROOM_MAGIC:
        raise RowBlobError("missing room blob magic")
    if version != _ROOM_VERSION:
        raise RowBlobError(f"unknown room blob version {version}")
    expect = world_room_leaf_items(template, class_order)
    if n_leaves != len(expect):
        raise RowBlobError(
            f"room blob carries {n_leaves} leaves, template has "
            f"{len(expect)} — cross-build re-home rejected")
    off = _ROOM_HEADER.size
    leaves: List[np.ndarray] = []
    for path, tarr in expect:
        plen, dlen, ndim = _LEAF_HEADER.unpack_from(payload, off)
        off += _LEAF_HEADER.size
        got_path = payload[off:off + plen].decode()
        off += plen
        dtype = np.dtype(payload[off:off + dlen].decode())
        off += dlen
        shape = _struct.unpack_from(f"<{ndim}I", payload, off)
        off += 4 * ndim
        t = np.asarray(tarr)
        if got_path != path or dtype != t.dtype or shape != t.shape:
            raise RowBlobError(
                f"room blob leaf {got_path!r} ({dtype}{list(shape)}) does "
                f"not match template {path!r} ({t.dtype}{list(t.shape)})")
        nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        leaves.append(np.frombuffer(
            payload[off:off + nbytes], dtype=dtype).reshape(shape))
        off += nbytes
    if off != len(payload):
        raise RowBlobError("room blob has trailing bytes")
    names = list(class_order) if class_order is not None \
        else sorted(template.classes)
    out = _rebuild_room(template, names, leaves)
    got = room_digest(out, class_order)
    if got != digest:
        raise RowBlobError(
            f"room blob digest mismatch: header {digest:#x}, rebuilt "
            f"{got:#x} — refusing to admit a corrupted room")
    return out


# -- host-side slot allocation ----------------------------------------------


class RoomBinPacker:
    """Assigns rooms to device slots by load.

    Slots group into ``n_blocks`` contiguous blocks — one per mesh
    device under the room-major NamedSharding, so "pick a block" IS
    "pick a device".  Policy ``least-loaded`` (default) admits into the
    block with the smallest total load that still has a free slot;
    ``first-fit`` takes the globally lowest free slot (deterministic
    packing for parity tests)."""

    def __init__(self, capacity: int, n_blocks: int = 1,
                 policy: str = "least-loaded"):
        capacity, n_blocks = int(capacity), max(1, int(n_blocks))
        if capacity % n_blocks:
            raise ValueError(
                f"{capacity} slots do not divide into {n_blocks} blocks")
        if policy not in ("least-loaded", "first-fit"):
            raise ValueError(f"unknown packer policy {policy!r}")
        self.capacity = capacity
        self.n_blocks = n_blocks
        self.policy = policy
        self.load = np.zeros(capacity, np.float64)
        self.used = np.zeros(capacity, bool)

    @property
    def block_size(self) -> int:
        return self.capacity // self.n_blocks

    def block_of(self, slot: int) -> int:
        return int(slot) // self.block_size

    @property
    def free_count(self) -> int:
        return int(self.capacity - self.used.sum())

    def block_loads(self) -> np.ndarray:
        return self.load.reshape(self.n_blocks, self.block_size).sum(axis=1)

    def alloc(self, load: float = 1.0) -> int:
        free = ~self.used
        if not free.any():
            raise RoomSlotsFull(self.capacity)
        if self.policy == "first-fit":
            slot = int(np.flatnonzero(free)[0])
        else:
            has_free = free.reshape(self.n_blocks, self.block_size).any(axis=1)
            loads = np.where(has_free, self.block_loads(), np.inf)
            b = int(np.argmin(loads))
            slot = b * self.block_size + int(
                np.flatnonzero(free[b * self.block_size:(b + 1) * self.block_size])[0])
        self.used[slot] = True
        self.load[slot] = float(load)
        return slot

    def free(self, slot: int) -> None:
        # lazy wipe: the slot's device bytes stay as-is (dead rooms are
        # never read; admit overwrites every leaf) — only host book-keeping
        self.used[int(slot)] = False
        self.load[int(slot)] = 0.0

    def set_load(self, slot: int, load: float) -> None:
        self.load[int(slot)] = float(load)

    def grow(self, new_capacity: int, n_blocks: Optional[int] = None) -> None:
        new_capacity = int(new_capacity)
        if new_capacity < self.capacity:
            raise ValueError("packer cannot shrink")
        n_blocks = self.n_blocks if n_blocks is None else int(n_blocks)
        if new_capacity % n_blocks:
            raise ValueError(
                f"{new_capacity} slots do not divide into {n_blocks} blocks")
        pad = new_capacity - self.capacity
        self.load = np.concatenate([self.load, np.zeros(pad)])
        self.used = np.concatenate([self.used, np.zeros(pad, bool)])
        self.capacity = new_capacity
        self.n_blocks = n_blocks


# -- the batched device engine ----------------------------------------------


class RoomBatch:
    """R independent rooms ticking as ONE vmapped program.

    Wraps a built template :class:`Kernel` (any recipe world's kernel);
    its ``_trace_step`` is vmapped over a leading ``[R]`` axis and the
    template's own state/jit entries go unused.  All jit entries ride
    the template's CostBook (``rooms.step`` / ``rooms.run`` /
    ``rooms.admit`` / ``rooms.extract``), slot indices are TRACED
    scalars, and capacity is pow2 — so create/destroy/re-home churn is
    recompile-free and the soak gate ``unexplained_since`` holds."""

    def __init__(self, template: Kernel, capacity: int,
                 mesh: Optional[Mesh] = None, *, seed: int = 0):
        if template.state is None:
            raise RuntimeError("template kernel must be built before "
                               "RoomBatch wraps it")
        self.kernel = template
        template.room_batch = self
        self.capacity = next_pow2(max(1, int(capacity)))
        self.mesh = mesh
        if mesh is not None and self.capacity % mesh.devices.size:
            raise ValueError(
                f"{self.capacity} room slots not divisible by "
                f"{mesh.devices.size} devices")
        self.costbook = template.costbook
        self.tick_count = 0
        self.last_counters: Dict[str, np.ndarray] = {}
        # room slots the device has ticked, occupied or not: the whole
        # bank rides every frame (= nf_rooms_slots_ticked_total)
        self.slots_ticked = 0
        # bytes of room leaves handed to the admit scatter so far
        self.admit_bytes = 0
        self._seed = int(seed)
        self._jit_step = None
        self._jit_run = None
        self._jit_train = None
        self._train_k = 0
        # train accounting (mirrors Kernel.train_*): dispatches land on
        # the batch, not the template — the template's own entries are
        # unused under a RoomBatch
        self.train_dispatches = 0
        self.train_ticks = 0
        self.train_fetch_bytes = 0
        self._jit_admit = None
        self._jit_extract = None
        self._seen_trace_gen = getattr(template, "_trace_gen", 0)
        template._ensure_aux()
        self._blank = self._blank_room()
        self.state = self._broadcast(self._blank, self.capacity)
        if mesh is not None:
            self.place()

    # ------------------------------------------------------------ state
    def _blank_room(self) -> WorldState:
        """A pristine single-room state: zeroed store + freshly primed
        aux caches — exactly what a just-built recipe world starts from,
        so an admitted room's first tick sees what a fresh single world's
        first tick would."""
        st = self.kernel.store.init_state(self._seed)
        aux = {k: fn() for k, fn in self.kernel._aux_init.items()}
        return st.replace(aux=aux)

    @staticmethod
    def _broadcast(room: WorldState, n: int):
        return jax.tree.map(
            lambda l: jnp.broadcast_to(
                jnp.asarray(l)[None], (n,) + jnp.asarray(l).shape), room)

    def shardings(self):
        from .shard import room_shardings

        return room_shardings(self.state, self.mesh)

    def place(self) -> None:
        self.state = jax.device_put(self.state, self.shardings())

    def _sync_generation(self) -> None:
        """Drop the vmapped traces when the template invalidated, and
        re-blank every aux cache: invalidate() means aux layouts changed
        (bucket resize, grid width), and the next vmapped trace rebuilds
        caches from zeros exactly like a fresh single world would."""
        gen = getattr(self.kernel, "_trace_gen", 0)
        if gen == self._seen_trace_gen:
            return
        self._seen_trace_gen = gen
        self._jit_step = self._jit_run = self._jit_train = None
        self._jit_admit = self._jit_extract = None
        self._blank = self._blank_room()
        for cname in self.kernel.store.class_order:
            want = np.asarray(self._blank.classes[cname].alive).shape[0]
            got = np.asarray(self.state.classes[cname].alive).shape[1]
            if want != got:
                raise RuntimeError(
                    f"store capacity of {cname!r} changed {got}->{want} "
                    "under a live RoomBatch — size recipe capacities so "
                    "auto-resize never fires in batched worlds")
        aux = {k: v for k, v in self.state.aux.items()
               if k not in self.kernel._aux_init}
        aux.update({k: self._broadcast_leafs(v)
                    for k, v in self._blank.aux.items()})
        self.state = self.state.replace(aux=aux)
        if self.mesh is not None:
            self.place()

    def _broadcast_leafs(self, tree):
        n = self.capacity
        return jax.tree.map(
            lambda l: jnp.broadcast_to(
                jnp.asarray(l)[None], (n,) + jnp.asarray(l).shape), tree)

    # ------------------------------------------------------------ ticks
    def _compile_step(self):
        if self._jit_step is not None:
            return self._jit_step
        k = self.kernel

        def rooms_step(st):  # the device module's name: jit_rooms_step
            st2, out = jax.vmap(k._trace_step)(st)
            # only the [R, L] summary survives to the host; everything
            # else (fired masks, diffs, events) is DCE'd like run_device
            return st2, out["summary"]

        jkw = {}
        if self.mesh is not None:
            sh = self.shardings()
            from jax.sharding import NamedSharding, PartitionSpec

            jkw = {"in_shardings": (sh,),
                   "out_shardings": (sh, NamedSharding(
                       self.mesh, PartitionSpec(ROOMS_AXIS)))}
        self._jit_step = self.costbook.wrap(
            "rooms.step", rooms_step, donate_argnums=0, stage="tick",
            jit_kwargs=jkw)
        return self._jit_step

    def tick(self) -> Dict[str, np.ndarray]:
        """One frame for EVERY room; returns the per-room counter bank
        (name -> [R] int column) decoded off the one summary fetch —
        per-room observability at the same zero-extra-syncs cost as the
        single-world counter bank."""
        self._sync_generation()
        step = self._compile_step()
        span = self.kernel.tracer.span
        with span("rooms.dispatch"):
            self.state, summary = step(self.state)
        self.tick_count += 1
        self.slots_ticked += self.capacity
        with span("rooms.fetch"):  # the frame's one blocking read
            summary = np.asarray(summary)
        with span("rooms.decode"):
            self.last_counters = self.kernel.decode_counters(summary)
        return self.last_counters

    def run(self, n: int) -> Dict[str, np.ndarray]:
        """n frames for every room, zero host syncs inside (fori_loop
        over the vmapped step, traced trip count — one compile serves
        every n).  The final frame's summary rides the carry out, so
        ``last_counters`` reflects the post-run world instead of going
        stale at the pre-run tick (the r12 bug: a drill sampling
        counters after run() read frame N-n's numbers as frame N's)."""
        self._sync_generation()
        if int(n) <= 0:
            return self.last_counters
        if self._jit_run is None:
            k = self.kernel

            def body(_, carry):
                st, _prev = carry
                st2, out = jax.vmap(k._trace_step)(st)
                return st2, out["summary"]

            def rooms_run(st, t):
                st1, out = jax.vmap(k._trace_step)(st)
                return jax.lax.fori_loop(0, t - 1, body, (st1, out["summary"]))

            jkw = {}
            if self.mesh is not None:
                sh = self.shardings()
                from jax.sharding import NamedSharding, PartitionSpec

                jkw = {"in_shardings": (sh, None),
                       "out_shardings": (sh, NamedSharding(
                           self.mesh, PartitionSpec(ROOMS_AXIS)))}
            self._jit_run = self.costbook.wrap(
                "rooms.run", rooms_run,
                donate_argnums=0, stage="tick", jit_kwargs=jkw)
        self.state, summary = self._jit_run(self.state, jnp.int32(int(n)))
        self.tick_count += int(n)
        self.slots_ticked += int(n) * self.capacity
        self.last_counters = self.kernel.decode_counters(np.asarray(summary))
        return self.last_counters

    # ---------------------------------------------------------- trains
    def configure_train(self, k: int) -> None:
        """Pin the train length (see Kernel.configure_train); the
        template's K is synced so its scan trace matches."""
        self.kernel.configure_train(k)
        if int(k) != self._train_k:
            self._train_k = int(k)
            self._jit_train = None

    def _compile_train(self):
        if self._jit_train is not None:
            return self._jit_train
        if self._train_k < 1:
            raise RuntimeError("configure_train(k) before train()")
        k = self.kernel
        kk = self._train_k

        def vtrain(st):
            # vmap INSIDE the scan: each scanned step advances all R
            # rooms, so the stacked summary comes out [K, R, L] with
            # the room axis sharding preserved on axis 1.  Only the
            # summary lane survives to the host — the rooms engine's
            # whole per-tick observed surface IS the counter bank
            # (rooms.step makes the same reduction), so fired/diff/
            # event lanes are DCE'd, not lost.
            def body(s, _):
                s2, out = jax.vmap(k._trace_step)(s)
                return s2, out["summary"]

            return jax.lax.scan(body, st, None, length=kk)

        jkw = {}
        if self.mesh is not None:
            sh = self.shardings()
            from jax.sharding import NamedSharding, PartitionSpec

            jkw = {"in_shardings": (sh,),
                   "out_shardings": (sh, NamedSharding(
                       self.mesh, PartitionSpec(None, ROOMS_AXIS)))}
        self._jit_train = self.costbook.wrap(
            "rooms.train", vtrain, donate_argnums=0, stage="tick",
            jit_kwargs=jkw)
        return self._jit_train

    def train(self, n: int) -> np.ndarray:
        """n frames for every room in ⌊n/K⌋ megadispatches plus a
        per-tick ragged tail; per-tick per-room counters survive as
        stacked ``[K, R, L]`` summary lanes fetched ONCE per train.

        Returns the concatenated ``[n, R, L]`` summary (one row per
        logical tick, in order — decode with ``kernel.decode_counters``
        for per-tick ``[R]`` counter columns, including the in-lane
        "tick" stamp and, when enabled, "state_digest").
        ``last_counters`` lands on the final frame."""
        self._sync_generation()
        n = int(n)
        kk = self._train_k
        if kk < 1:
            raise RuntimeError("configure_train(k) before train()")
        jt = self._compile_train()
        lanes: List[np.ndarray] = []
        for _ in range(n // kk):
            self.state, stacked = jt(self.state)
            self.tick_count += kk
            self.train_dispatches += 1
            self.train_ticks += kk
            arr = np.asarray(stacked)  # ONE [K, R, L] fetch per train
            self.train_fetch_bytes += arr.nbytes
            lanes.append(arr)
        for _ in range(n % kk):
            step = self._compile_step()
            self.state, summary = step(self.state)
            self.tick_count += 1
            lanes.append(np.asarray(summary)[None])
        out = (np.concatenate(lanes, axis=0) if lanes
               else np.zeros((0, self.capacity, 0), np.int32))
        if len(out):
            self.last_counters = self.kernel.decode_counters(out[-1])
        return out

    # ---------------------------------------------------- slot plumbing
    def _room_payload(self, room: WorldState) -> WorldState:
        """A full room pytree structurally matching one batched lane
        (its packed leaves may carry a leading rooms axis):
        the room's packed leaves + FRESH aux caches (blank for
        registered entries, zeros for trace-added ones like migration
        stats) — the admit scatter is then one tree_map."""
        aux = {}
        for key, cur in self.state.aux.items():
            if key in self._blank.aux:
                aux[key] = self._blank.aux[key]
            else:
                aux[key] = jax.tree.map(
                    lambda l: jnp.zeros(l.shape[1:], l.dtype), cur)
        for cname in self.kernel.store.class_order:
            want = np.asarray(self._blank.classes[cname].alive).shape[0]
            got = room.classes[cname].alive.shape[-1]
            if want != got:
                raise ValueError(
                    f"admitted room's {cname!r} capacity {got} != batch "
                    f"template {want} — recipes must share StoreConfig")
        return room.replace(aux=aux)

    def admit_rooms(self, slots: Sequence[int], rows: Dict[str, Any],
                    proto: Optional[WorldState] = None) -> None:
        """Scatter ``len(slots)`` rooms into their slots, one scatter
        per leaf.  ``rows`` holds, by ``ROOM_PACK_SPEC`` path, the
        ``[m, ...]`` leaves in which the rooms differ; every other leaf
        is ``proto``'s (the blank room's when None) for all of them.
        Full-leaf overwrite: whatever a slot held before (a destroyed
        room's remains, lazy wipe) is unreadable afterwards.  The slots
        are a TRACED vector padded to a power of two (repeating the
        first room: an idempotent duplicate write), so one compiled
        scatter serves every slot and a handful serve every count."""
        self._sync_generation()
        slots = np.asarray(slots, np.int32).reshape(-1)
        m = int(slots.size)
        if m == 0:
            return
        if slots.min() < 0 or slots.max() >= self.capacity:
            # a scatter drops an out-of-range index without a word
            raise IndexError(f"room slot outside 0..{self.capacity - 1}")
        proto = self._blank if proto is None else proto
        order = self.kernel.store.class_order
        items = world_room_leaf_items(proto, order)
        unknown = set(rows) - {path for path, _ in items}
        if unknown:
            raise RowBlobError(
                f"admit rows name no packed leaf: {sorted(unknown)}")
        pad = next_pow2(m) - m

        def padded(a):
            return jnp.concatenate(
                [a, jnp.repeat(a[:1], pad, axis=0)]) if pad else a

        leaves = []
        for path, leaf in items:
            got = rows.get(path)
            if got is not None:
                want = (m,) + tuple(leaf.shape)
                if tuple(got.shape) != want or got.dtype != leaf.dtype:
                    raise ValueError(
                        f"admit rows {path!r}: {got.dtype}"
                        f"{list(got.shape)} where the batch takes "
                        f"{leaf.dtype}{list(want)}")
                self.admit_bytes += int(got.nbytes)
                leaf = padded(jnp.asarray(got))
            leaves.append(leaf)
        payload = self._room_payload(_rebuild_room(proto, order, leaves))
        if self._jit_admit is None:
            def scatter(batch, rooms, s):
                def put(b, r):
                    if r.ndim < b.ndim:  # proto's leaf: the same for all
                        r = jnp.broadcast_to(r[None], s.shape + r.shape)
                    return b.at[s].set(r)

                return jax.tree.map(put, batch, rooms)

            # the batch comes back under the batch's own shardings:
            # left to the compiler, a zero-width bank returns
            # replicated, and rooms.step (pinned in_shardings) refuses it
            jkw = ({} if self.mesh is None
                   else {"out_shardings": self.shardings()})
            self._jit_admit = self.costbook.wrap(
                "rooms.admit", scatter,
                donate_argnums=0, stage="tick", jit_kwargs=jkw)
        self.state = self._jit_admit(self.state, payload,
                                     jnp.asarray(padded(slots)))

    def admit(self, slot: int, room: WorldState) -> int:
        """Admit one room's state into ``slot``: the batch of one, every
        packed leaf its own row."""
        rows = {path: jnp.asarray(leaf)[None] for path, leaf in
                world_room_leaf_items(room, self.kernel.store.class_order)}
        self.admit_rooms([slot], rows)
        return int(slot)

    def extract(self, slot: int) -> WorldState:
        """Gather one room's full state (aux included) off the batch;
        traced slot index — one compiled gather serves every slot."""
        self._sync_generation()
        if self._jit_extract is None:
            self._jit_extract = self.costbook.wrap(
                "rooms.extract",
                lambda b, s: jax.tree.map(lambda bb: bb[s], b),
                stage="tick")
        return self._jit_extract(self.state, jnp.int32(int(slot)))

    def digest(self, slot: int,
               ident_cols: Optional[Dict[str, int]] = None) -> int:
        return room_digest(self.extract(slot),
                           self.kernel.store.class_order, ident_cols)

    def pack_blob(self, slot: int) -> bytes:
        return pack_room_blob(self.extract(slot),
                              self.kernel.store.class_order)

    def admit_blob(self, slot: int, blob: bytes) -> int:
        """Admit a framed room blob — the re-home landing path, and the
        cross-engine door: a single-world snapshot packed by
        ``pack_room_blob(world.kernel.state, ...)`` loads into a slot."""
        room = unpack_room_blob(blob, self._blank,
                                self.kernel.store.class_order)
        return self.admit(slot, room)

    def rehome(self, src: int, dst: int) -> int:
        """Move a room between slots (and thus devices) as a framed,
        digest-carrying blob; the source slot is NOT wiped (lazy) — the
        caller frees it in its packer."""
        if int(src) == int(dst):
            raise ValueError(f"re-home src == dst slot {src}")
        blob = self.pack_blob(src)
        return self.admit_blob(dst, blob)

    # ------------------------------------------------------------- grow
    def grow(self, new_capacity: int) -> int:
        """Double (at least) the slot bank — the ONE sanctioned retrace
        of room churn, announced via ``generation_bump`` exactly like
        the combat bucket resize, so the soak gate stays clean."""
        new_cap = next_pow2(max(int(new_capacity), self.capacity + 1))
        if self.mesh is not None and new_cap % self.mesh.devices.size:
            raise ValueError(
                f"{new_cap} slots not divisible by mesh width")
        self.costbook.generation_bump(
            f"rooms_grow:{self.capacity}->{new_cap}")
        pad = new_cap - self.capacity
        blank_pad = self._broadcast(self._blank, pad)

        def widen(cur, pad_leaf):
            return jnp.concatenate([cur, pad_leaf], axis=0)

        aux = {}
        for key, cur in self.state.aux.items():
            if key in self._blank.aux:
                aux[key] = jax.tree.map(widen, cur, blank_pad.aux[key])
            else:
                aux[key] = jax.tree.map(
                    lambda l: jnp.concatenate(
                        [l, jnp.zeros((pad,) + l.shape[1:], l.dtype)],
                        axis=0), cur)
        self.state = self.state.replace(
            classes=jax.tree.map(widen, dict(self.state.classes),
                                 dict(blank_pad.classes)),
            tick=widen(self.state.tick, blank_pad.tick),
            rng=widen(self.state.rng, blank_pad.rng),
            aux=aux,
        )
        self.capacity = new_cap
        self._jit_step = self._jit_run = None
        self._jit_admit = self._jit_extract = None
        if self.mesh is not None:
            self.place()
        return new_cap


# -- host directory: room ids, packing, controls, metrics -------------------


class RoomDirectory:
    """The host face of the many-worlds engine: room ids -> slots.

    ``recipe(seed)`` builds one fresh single-room world (a GameWorld or
    a bare built Kernel); room 0's build becomes the vmap TEMPLATE.  A
    recipe that also has ``seeded_rows(template_kernel, seeds)`` (the
    ``[R, ...]`` host leaves a seed decides, by ``ROOM_PACK_SPEC``
    path; ``game.BenchmarkRoomRecipe``) is never called again: its
    rooms are the template room with those leaves replaced.
    create/destroy/re-home recycle slots through the bin-packer;
    ``attach_control`` keeps a room's recipe world alive and ticks it in
    LOCKSTEP with the batch — the parity oracle drill's RoomIsolation
    invariant compares per-room digests against."""

    def __init__(self, recipe: Callable[[int], Any],
                 capacity: Optional[int] = None,
                 mesh: Optional[Mesh] = None, *,
                 template_seed: int = 0,
                 policy: str = "least-loaded",
                 registry: Optional[Any] = None):
        if capacity is None:
            capacity = int(os.environ.get(ENV_ROOM_SLOTS, "16"))
        self._recipe = recipe
        self.template_world = recipe(template_seed)
        template = self._kernel_of(self.template_world)
        self.batch = RoomBatch(template, capacity, mesh=mesh,
                               seed=template_seed)
        # the template room as built: what every seeded room starts from
        self._proto = template.state
        n_blocks = mesh.devices.size if mesh is not None else 1
        self.packer = RoomBinPacker(self.batch.capacity, n_blocks,
                                    policy=policy)
        self.rooms: Dict[int, int] = {}  # room_id -> slot
        self.seeds: Dict[int, int] = {}  # room_id -> recipe seed
        self.controls: Dict[int, Any] = {}  # room_id -> lockstep world
        self._next_room_id = 1
        self.created = 0
        self.destroyed = 0
        self.rehomed = 0
        self.admitted_rows = 0  # entity rows of the rooms admitted
        # per-room counters summed over the OCCUPIED slots of every
        # observed tick() (a fused run() leaves only its last frame)
        self.counter_totals: Dict[str, int] = {}
        self._metrics = None
        if registry is not None:
            self._metrics = {
                "active": registry.gauge(
                    "nf_rooms_active", "rooms currently admitted"),
                "slots_free": registry.gauge(
                    "nf_rooms_slots_free", "free room slots"),
                "created": registry.counter(
                    "nf_rooms_created_total", "rooms created"),
                "destroyed": registry.counter(
                    "nf_rooms_destroyed_total", "rooms destroyed"),
                "rehomed": registry.counter(
                    "nf_rooms_rehomed_total", "room re-homes"),
                "admitted_rows": registry.counter(
                    "nf_rooms_admitted_rows_total",
                    "entity rows of the rooms admitted"),
                "admit_bytes": registry.counter(
                    "nf_rooms_admit_bytes_total",
                    "bytes of room leaves handed to the admit scatter"),
                "slots_ticked": registry.counter(
                    "nf_rooms_slots_ticked_total",
                    "room slots ticked, occupied or not (the whole "
                    "bank rides every frame)"),
            }
            self._publish()

    @staticmethod
    def _kernel_of(world: Any) -> Kernel:
        return world if isinstance(world, Kernel) else world.kernel

    def _publish(self) -> None:
        if self._metrics is None:
            return
        self._metrics["active"].set(len(self.rooms))
        self._metrics["slots_free"].set(self.packer.free_count)
        for name in ("admit_bytes", "slots_ticked"):  # the batch counts
            counter = self._metrics[name]
            counter.inc(getattr(self.batch, name) - counter.value())

    # ----------------------------------------------------------- churn
    def create_rooms(self, seeds: Sequence[int],
                     room_ids: Optional[Sequence[int]] = None,
                     control: bool = False,
                     rows: Optional[Dict[str, Any]] = None) -> List[int]:
        """Make the rooms of ``seeds`` and admit them all at once, each
        into the least-loaded free slot: the one way a room comes to be.
        A recipe with ``seeded_rows`` has its rooms made on the host for
        the whole batch, with no world, kernel or device program a
        room; one that is only a world builder is called per seed and
        the worlds' states are stacked.  With ``control=True`` the
        rooms are built as worlds either way, the worlds stay alive and
        ``tick``/``run`` advance them in lockstep: the independent
        oracle for isolation/parity gates.  ``rows`` (``[R, ...]``
        leaves by ``ROOM_PACK_SPEC`` path) replaces what the seeds made
        of those leaves, in the same scatter: a player seated in a
        fresh room (``net/roles/room_serve.py``)."""
        seeds = [int(s) for s in seeds]
        if room_ids is None:
            room_ids = range(self._next_room_id,
                             self._next_room_id + len(seeds))
        room_ids = [int(r) for r in room_ids]
        if len(room_ids) != len(seeds) or len(set(room_ids)) != len(seeds):
            raise ValueError("one distinct room id per seed")
        for room_id in room_ids:
            if room_id in self.rooms:
                raise ValueError(f"room {room_id} already exists")
        if not seeds:
            return []
        kernel = self.batch.kernel
        order = kernel.store.class_order
        with kernel.tracer.span("rooms.admit", rooms=len(seeds)):
            seeded = getattr(self._recipe, "seeded_rows", None)
            if control or seeded is None:
                worlds = [self._recipe(seed) for seed in seeds]
                items = []
                for world in worlds:
                    k = self._kernel_of(world)
                    k._ensure_aux()
                    items.append(world_room_leaf_items(k.state, order))
                made = {path: jnp.stack([room[i][1] for room in items])
                        for i, (path, _) in enumerate(items[0])}
                proto = None
            else:
                worlds = []
                made = seeded(kernel, seeds)
                proto = self._proto
            made.update(rows or {})
            loads = np.zeros(len(seeds))
            for cname in order:
                alive = made.get(f"classes.{cname}.alive")
                loads += (np.asarray(alive).sum(axis=1) if alive is not None
                          else float(np.asarray(
                              self._proto.classes[cname].alive).sum()))
            slots: List[int] = []
            try:
                for load in loads:
                    slots.append(self.packer.alloc(load=float(load)))
                self.batch.admit_rooms(slots, made, proto)
            except Exception:
                for slot in slots:
                    self.packer.free(slot)
                raise
        self._next_room_id = max(self._next_room_id, max(room_ids) + 1)
        for room_id, seed, slot in zip(room_ids, seeds, slots):
            self.rooms[room_id] = slot
            self.seeds[room_id] = seed
        if control:
            self.controls.update(zip(room_ids, worlds))
        self.created += len(seeds)
        self.admitted_rows += int(loads.sum())
        if self._metrics is not None:
            self._metrics["created"].inc(len(seeds))
            self._metrics["admitted_rows"].inc(int(loads.sum()))
        self._publish()
        return room_ids

    def proto_leaf(self, path: str) -> np.ndarray:
        """The template room's packed leaf at ``path`` (no room axis),
        on the host: what a room's leaf holds before its seed or an
        admit's ``rows`` change it."""
        items = dict(world_room_leaf_items(
            self._proto, self.batch.kernel.store.class_order))
        return np.asarray(items[path])

    def create_room(self, seed: Optional[int] = None,
                    room_id: Optional[int] = None,
                    control: bool = False) -> int:
        """:meth:`create_rooms` of one; the seed defaults to the id."""
        if room_id is None:
            room_id = self._next_room_id
        seed = int(room_id) if seed is None else seed
        return self.create_rooms([seed], [room_id], control=control)[0]

    def destroy_room(self, room_id: int) -> int:
        """Free the room's slot (lazy wipe — admit's full overwrite is
        the only writer a recycled slot ever needs)."""
        slot = self.rooms.pop(int(room_id))
        self.seeds.pop(int(room_id), None)
        self.controls.pop(int(room_id), None)
        self.packer.free(slot)
        self.destroyed += 1
        if self._metrics is not None:
            self._metrics["destroyed"].inc()
        self._publish()
        return slot

    def rehome_room(self, room_id: int) -> Tuple[int, int]:
        """Move a room to the (now) least-loaded block's free slot via
        the framed blob path; returns (old_slot, new_slot)."""
        room_id = int(room_id)
        src = self.rooms[room_id]
        load = float(self.packer.load[src])
        dst = self.packer.alloc(load=load)
        try:
            self.batch.rehome(src, dst)
        except Exception:
            self.packer.free(dst)
            raise
        self.packer.free(src)
        self.rooms[room_id] = dst
        self.rehomed += 1
        if self._metrics is not None:
            self._metrics["rehomed"].inc()
        self._publish()
        return src, dst

    def grow(self, new_capacity: Optional[int] = None) -> int:
        cap = self.batch.grow(new_capacity or self.batch.capacity * 2)
        self.packer.grow(cap)
        self._publish()
        return cap

    # ----------------------------------------------------------- ticks
    def tick(self) -> Dict[str, np.ndarray]:
        """One frame for every room + every lockstep control; returns
        the per-room counter columns (slot-indexed ``[capacity]``)."""
        with self.batch.kernel.tracer.span(
                "rooms.tick", tick=self.batch.tick_count + 1):
            counters = self.batch.tick()
            with self.batch.kernel.tracer.span("rooms.decode"):
                used = self.packer.used
                for name, column in counters.items():
                    if name not in ("state_digest", "tick"):
                        self.counter_totals[name] = self.counter_totals.get(
                            name, 0) + int(column[used].sum())
            for world in self.controls.values():
                self._kernel_of(world).run_device(1, reconcile=False)
        self._publish()
        return counters

    def run(self, n: int) -> None:
        self.batch.run(n)
        for world in self.controls.values():
            self._kernel_of(world).run_device(int(n), reconcile=False)
        self._publish()

    # ----------------------------------------------------- cell depth
    def combat_geometry(self, class_name: str = "NPC"
                        ) -> Optional[Dict[str, Any]]:
        """The cell depths every room's neighbour engine runs at: what
        the template world's combat module resolves for the room's
        capacity, baked into ``rooms.step`` when it was traced.  No
        per-room boost exists: what a room's cells drop at this depth
        is counted (``counter_totals["aoi_*_overflow_drops"]``) and
        stays dropped.  None for a recipe without a combat module."""
        combat = getattr(self.template_world, "combat", None)
        if combat is None:
            return None
        cap = int(self.batch.kernel.store.capacity(class_name))
        return {"cell_size": combat.cell_size, "width": combat.width,
                "bucket": combat.resolved_bucket(cap),
                "att_bucket": combat.resolved_att_bucket(cap)}

    # ---------------------------------------------------------- oracle
    def slot_of(self, room_id: int) -> int:
        return self.rooms[int(room_id)]

    def digest(self, room_id: int) -> int:
        return self.batch.digest(self.rooms[int(room_id)])

    def control_digest(self, room_id: int) -> int:
        world = self.controls[int(room_id)]
        k = self._kernel_of(world)
        return room_digest(k.state, k.store.class_order)

    def status(self) -> Dict[str, Any]:
        """Heartbeat/`/json` blob: totals + per-room occupancy."""
        return {
            "capacity": self.batch.capacity,
            "active": len(self.rooms),
            "slots_free": self.packer.free_count,
            "created": self.created,
            "destroyed": self.destroyed,
            "rehomed": self.rehomed,
            "admitted_rows": self.admitted_rows,
            "slots_ticked": self.batch.slots_ticked,
            "tick": self.batch.tick_count,
            "policy": self.packer.policy,
            "blocks": self.packer.n_blocks,
            "occupancy": {
                str(rid): {"slot": slot,
                           "block": self.packer.block_of(slot),
                           "load": float(self.packer.load[slot])}
                for rid, slot in sorted(self.rooms.items())
            },
        }
