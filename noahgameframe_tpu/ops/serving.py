"""Batched serving edge: vmap-over-sessions interest deltas, on device.

The legacy serve path (net/roles/game.py `_send_interest_pos`) walks a
Python loop over sessions — per session a numpy sort, two searchsorted
passes and half a dozen fancy gathers against a per-session
`_interest_seen` dict.  At 2000 sessions that loop alone is ~190 ms of
exclusive frame time (bench_runs/r05_served_100k_2000s_cpu.json).  This
module computes the SAME per-session delta stream for ALL sessions in
one static-shaped dispatch:

1. `bump_qver` — a device-carried version counter per entity row that
   increments exactly when the u16-quantized position changes.  Together
   with the host-bumped allocation generation (core/store.py
   `_ClassHost.row_gen`, +1 per row free) it replaces the legacy
   per-session `(rows, guid_head, guid_data, qpos)` seen tuples with two
   i32 vectors: a session has seen the CURRENT identity+position of row
   r iff its stored `(gen, qver)` for r equals the live `(gen[r],
   qver[r])`.  Guid equality ⟺ gen equality because guids are
   never reused (pure-counter allocator) and gen bumps on every free;
   qpos equality ⟺ qver equality because the serve kernel runs on
   every flush in which any position changed, so the version counter
   observes every quantum transition the legacy path would have stored.
2. `interest_delta` — per-session set algebra over the candidate slots
   from ops/interest (`scan_sorted` 3x3 reads): sort the visible
   rows (ascending, sentinel-padded — the legacy wire order), match
   them against the session's sorted seen-table by vmapped
   searchsorted, and emit `send` (enter or changed) and `gone`
   (previously seen, no longer visible or recycled) masks plus the next
   seen-table.  One dispatch for every session; the host's only job is
   slicing the fetched dense buffers into per-session packets
   (net/serving.py).
3. `slot_compact` — stable compaction of candidate slots in SLOT order
   (not sorted) for the interest-scoped BatchPropertySync lane, whose
   legacy wire order is candidate order.

Everything here is shape-static and jit-compiled by the caller (the
game role caches per-(class, padded-session-count) jits, same policy as
`_interest_step`).  No int64 on device: guids stay host-side (the wire
payload gathers guid_head/guid_data from the host mirrors by fetched
row id); the kernel deals only in i32 rows, generations and versions.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

# sentinel for "empty slot" in sorted row vectors: sorts after every
# real row id and never equals one (capacities are << 2^31)
SENTINEL = jnp.iinfo(jnp.int32).max


class SeenTable(NamedTuple):
    """Per-session device seen-state for one class: which entity rows the
    session's client currently mirrors, and at which (allocation
    generation, position version) it last received them.  `rows` is
    sorted ascending per session with SENTINEL padding — the invariant
    both searchsorted passes in `interest_delta` rely on."""

    rows: jnp.ndarray  # [S, M] i32, sorted asc, SENTINEL = empty
    gen: jnp.ndarray  # [S, M] i32 allocation generation at last send
    qver: jnp.ndarray  # [S, M] i32 position version at last send


class ServeDelta(NamedTuple):
    """One frame's serve output for all sessions of one class."""

    vis: jnp.ndarray  # [S, M] i32 visible rows, sorted asc, SENTINEL pad
    send: jnp.ndarray  # [S, M] bool — enter-view or changed since seen
    gone: jnp.ndarray  # [S, M] bool over the OLD seen slots
    gone_rows: jnp.ndarray  # [S, M] i32 old seen rows (garbage where ~gone)
    seen: SeenTable  # next frame's seen-state


def init_seen(sessions: int, slots: int) -> SeenTable:
    """All-empty seen state ([S, M]); also the per-slot reset value."""
    return SeenTable(
        rows=jnp.full((sessions, slots), SENTINEL, jnp.int32),
        gen=jnp.zeros((sessions, slots), jnp.int32),
        qver=jnp.zeros((sessions, slots), jnp.int32),
    )


def bump_qver(
    q: jnp.ndarray,  # [C, 3] i32 quantized positions (ops.interest.quantize)
    prev_q: jnp.ndarray,  # [C, 3] i32 last kernel run's q
    qver: jnp.ndarray,  # [C] i32
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(qver', prev_q'): bump a row's version when its quantum moved.
    Runs inside the serve kernel, so the counter advances exactly once
    per observed transition — two sessions comparing stored versions
    against it agree with the legacy per-session qpos equality test."""
    changed = jnp.any(q != prev_q, axis=-1)
    return qver + changed.astype(jnp.int32), q


def interest_delta(
    cand_rows: jnp.ndarray,  # [S, M] i32 candidate rows (ops.interest)
    cand_ok: jnp.ndarray,  # [S, M] bool — occupied, in-radius, in-zone
    gen: jnp.ndarray,  # [C] i32 live allocation generations (host upload)
    qver: jnp.ndarray,  # [C] i32 live position versions (bump_qver output)
    seen: SeenTable,
) -> ServeDelta:
    """The per-session delta set algebra, vmapped over the session axis.

    send[s,j] ⇔ vis[s,j] is visible and the session has NOT seen it at
    the current (gen, qver); gone[s,j] ⇔ seen row j is no longer in the
    visible set under the SAME generation (left radius, died, or row
    recycled to a new guid — the legacy guid-mismatch despawn)."""
    n_rows = gen.shape[0]
    # sorted visible set; stencil cells are disjoint so a row appears in
    # at most one candidate slot — no dedup pass needed
    vis = jnp.sort(jnp.where(cand_ok, cand_rows, SENTINEL), axis=1)
    vis_ok = vis < SENTINEL
    vr = jnp.clip(vis, 0, n_rows - 1)
    vis_gen = jnp.where(vis_ok, gen[vr], 0)
    vis_qver = jnp.where(vis_ok, qver[vr], 0)

    find = jax.vmap(lambda hay, needles: jnp.searchsorted(hay, needles))
    m = seen.rows.shape[1]
    idx = jnp.clip(find(seen.rows, vis), 0, m - 1)
    take = jnp.take_along_axis
    same = (
        vis_ok
        & (take(seen.rows, idx, 1) == vis)
        & (take(seen.gen, idx, 1) == vis_gen)
        & (take(seen.qver, idx, 1) == vis_qver)
    )
    send = vis_ok & ~same

    seen_ok = seen.rows < SENTINEL
    sr = jnp.clip(seen.rows, 0, n_rows - 1)
    j = jnp.clip(find(vis, seen.rows), 0, m - 1)
    still = (
        seen_ok
        & (take(vis, j, 1) == seen.rows)
        & (gen[sr] == seen.gen)  # same row AND same allocation = same guid
    )
    gone = seen_ok & ~still

    return ServeDelta(
        vis=vis,
        send=send,
        gone=gone,
        gone_rows=seen.rows,
        seen=SeenTable(rows=vis, gen=vis_gen, qver=vis_qver),
    )


def slot_compact(
    cand_rows: jnp.ndarray,  # [S, M] i32
    cand_ok: jnp.ndarray,  # [S, M] bool
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(rows [S, M], count [S]): ok slots compacted to the front of each
    session's lane in ORIGINAL slot order (stable) — the legacy
    BatchPropertySync wire order is candidate order, not sorted."""
    perm = jnp.argsort(~cand_ok, axis=1, stable=True)
    rows = jnp.take_along_axis(cand_rows, perm, axis=1)
    return rows, jnp.sum(cand_ok, axis=1, dtype=jnp.int32)
