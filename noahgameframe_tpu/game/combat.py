"""Combat: skill use, AoE damage resolution, NPC death & respawn.

Reference behavior being matched:
- NFCSkillModule::OnUseSkill — validate the skill element, damage the
  target (HP-10 floor 0) and stamp LastAttacker
  (NFCSkillModule.cpp:74-160, resolution :133-139).
- NFCNPCRefreshModule — watch HP; at <=0 fire ON_OBJECT_BE_KILLED with the
  LastAttacker and schedule a 5 s respawn heartbeat that restores the NPC
  from its seed/config (NFCNPCRefreshModule.cpp:115-135 and
  OnDeadDestroyHeart).

TPU inversion (BASELINE config 4's 1M-entity AoE resolve): all alive
entities are binned once into the cell-table (ops/stencil.py — one sort,
one scatter; this tick's attackers into a second, by a second sort and a
duty-sized chunk); every entity then PULLS incoming damage from the nine
dense-shifted neighbor blocks within the skill radius — a fused pairwise
masked reduction with zero gathers and zero scatter collisions — applies
`max(sum_atk - def, 0)`, picks the strongest in-range attacker as
LastAttacker, and the death sweep emits one batched BE_KILLED event and
arms device-side respawn (HP restored after `respawn_s`, keeping the row;
destroy-on-death is the host path via the event).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core.datatypes import Guid
from ..core.store import HANDLE_ROW_BITS, WorldState, with_class
from ..kernel.module import Module
from ..ops import stencil
from ..ops.aoi import cell_of
from ..ops.stencil import (
    STENCIL,
    auto_bucket,
    build_cell_table_pair,
    pull_slots,
    stencil_fold,
    sub_chunks,
)
from ..ops.verlet import (
    full_table,
    init_cache,
    refresh,
    skin_from_env,
    sub_table,
)
from .defines import GameEvent

ATTACK_TIMER = "Attack"

# "no attacker" sentinel for the f32 best-row accumulator: 2^24, exactly
# representable and strictly above every representable row id (< 2^24).
# Deliberately finite — see combat_fold_closure.
NO_ROW = 16777216.0


def combat_fold_closure(v, radius: float):
    """(fold, init) over a victim grid view v [H, W, Kv, F+1] — the
    fold body shared by combat_fold_xla (square grids) and the spatial
    slab shards (rectangular grids with real halo rows,
    parallel/spatial.py), so mask semantics and tie-breaks cannot
    drift between the single-chip and distributed paths."""
    vx, vy = v[..., 0], v[..., 1]
    vcamp, vscene, vgroup = v[..., 2], v[..., 3], v[..., 4]
    r2 = float(radius) * float(radius)
    idt = jnp.int32
    f32 = jnp.float32

    def fold(acc, cand):
        inc, besta, bestr = acc
        cx = cand[:, :, None, :, 0]
        cy = cand[:, :, None, :, 1]
        ca = cand[:, :, None, :, 2]
        cc = cand[:, :, None, :, 3]
        cscene = cand[:, :, None, :, 4]
        cgroup = cand[:, :, None, :, 5]
        cr = cand[:, :, None, :, 6]
        dx = vx[..., None] - cx
        dy = vy[..., None] - cy
        ok = (
            (dx * dx + dy * dy <= r2)
            & (ca != 0)  # a real attacker (empty slots carry 0)
            & (cc != vcamp[..., None])  # no friendly fire (also self)
            & (cscene == vscene[..., None])  # same scene...
            & (cgroup == vgroup[..., None])  # ...and group
        )
        inc = inc + jnp.sum(jnp.where(ok, ca, 0.0), axis=-1).astype(idt)
        # strongest attacker; ties resolve to the GLOBAL minimum row id
        # among equal-max in-range attackers.  Min-row (not first-in-
        # stencil-order) makes the answer independent of which cell each
        # attacker is binned in, so Verlet-cached anchor binnings
        # (ops/verlet.py) produce bit-identical LastAttacker to a fresh
        # rebuild.  bestr accumulates as f32 (NO_ROW = none: a finite
        # sentinel, not +inf — an inf loop carry sends the XLA CPU
        # algebraic simplifier into a non-terminating rewrite cycle) and
        # the XLA / Pallas wrappers convert to int32 at the end.
        sa = jnp.where(ok, ca, -1.0)
        m = jnp.max(sa, axis=-1)
        first = jnp.min(jnp.where(sa >= m[..., None], cr, NO_ROW), axis=-1)
        # a shift with zero ok attackers has m == -1 and `first` reads the
        # min over raw row columns — poison; neutralize before comparing
        first = jnp.where(m >= 0.0, first, NO_ROW)
        # merge (m, first) into (besta, bestr) as a lexicographic
        # (max attack, min row) reduction.  Phrased so `bestr` is
        # consumed exactly ONCE per shift: a second use (e.g. an extra
        # tie-select `where(tie, minimum(bestr, first), bestr)`) makes
        # the XLA CPU compiler blow up super-linearly on the 9-shift
        # select chain (minutes -> never returns at width 48)
        top = jnp.maximum(besta, m)
        bestr = jnp.minimum(
            jnp.where(m >= top, first, NO_ROW),
            jnp.where(besta >= top, bestr, NO_ROW),
        )
        besta = top
        return inc, besta, bestr

    zeros = jnp.zeros(v.shape[:3], idt)
    init = (
        zeros,
        jnp.zeros(v.shape[:3], f32) - 1.0,
        jnp.full(v.shape[:3], NO_ROW, f32),
    )
    return fold, init


def combat_fold_xla(vic_table, att_table, radius, raw: bool = False):
    """The XLA stencil fold over the split victim/attacker cell tables:
    nine shifted candidate blocks against the resident victim grid, with
    [Kv, Ka] pairwise masked reductions fused by XLA onto the VPU.

    Same contract as ops.stencil_pallas.combat_fold_pallas — returns
    (inc [H, W, Kv] int32 damage totals, bestr [H, W, Kv] int32 row id
    of the strongest in-range attacker, -1 = none) — and the single
    source of truth for the fold's feature-column layout and tie-break
    semantics.

    Victim payload columns: x, y, camp, scene, group (+occupancy).
    Attacker payload columns: x, y, eff_atk, camp, scene, group, row.
    No self-exclusion compare: self always shares its own camp, so the
    no-friendly-fire mask rules self out of every pair."""
    fold, init = combat_fold_closure(vic_table.grid_view(), radius)
    inc, besta, bestr = stencil_fold(att_table, fold, init)
    if raw:
        # the accumulators as the fold carries them, for the second
        # level to fold on into (combat_fold_spill)
        return inc, besta, bestr
    return inc, _best_rows(bestr)


def _best_rows(bestr):
    """NO_ROW (no attacker) -> -1; row ids are exact in f32 (< 2^24)."""
    return jnp.where(bestr >= NO_ROW, -1.0, bestr).astype(jnp.int32)


# Hot cells a trip of the second level's victim loop takes.  A trip's
# time goes by what its nine gathers of whole cells carry, so the size
# only trades loop turns against the last trip's idle lanes (one trip's
# worth a tick at most: the work list is flat).
SPILL_CHUNK = 512

# Attacker-hot cells a trip of the attacker loop takes.  A tick has a
# handful of them or none (a cell needs some 400 rows for 13 of them to
# fire on one tick), and a trip folds each against the whole depth of
# its nine neighbours: small trips, none when there is no such cell.
SPILL_ATT_CHUNK = 32

# Victims of a hot cell folded a trip.  The second level's rows are as
# deep as the deepest cell and most over-full cells are barely over, so
# the rows are folded in blocks of this many slots and a block nobody
# reaches is never folded: the fold is priced by the rows that spilled
# (rounded up to a block a cell), not by cells x depth.
SPILL_BLOCK = 32


def _neighbour(cell, dy: int, dx: int, width: int):
    """The cell (dy, dx) away from `cell` on a square grid, `width**2`
    (no cell) off the grid's edge or for no cell."""
    n_cells = width * width
    y, x = cell // width + dy, cell % width + dx
    ok = (cell < n_cells) & (y >= 0) & (y < width) & (x >= 0) & (x < width)
    return jnp.where(ok, y * width + x, n_cells)


def _first_cell(view, cell_size: float, width: int):
    """The cell each row of a second level stands for ([cells] int32):
    where its first member stands (a row is filled from slot 0), and
    `width**2` for a row that holds nobody."""
    held = view[:, 0, -1] > 0
    return jnp.where(
        held, cell_of(view[:, 0, :2], cell_size, width), width * width)


def _take(table, index, fill=0.0):
    """table[index] along axis 0, `fill` where index is out of range."""
    return table.at[index].get(mode="fill", fill_value=fill)


def combat_fold_spill(vic_table, att_table, radius, inc, besta, bestr):
    """The second level's share of the fold: the three classes of pairs
    the base fold cannot see, each pair once, with the base fold's own
    arithmetic (`combat_fold_closure`), so a row's result is the same
    whatever level it or its attacker sits in.

    `inc, besta, bestr`: the base fold's accumulators, [H, W, Kv] (base
    victims x base attackers, `raw=True`).  Returns (inc, bestr) of the
    base level, [H, W, Kv] int32, and of the second level, [cells,
    depth] int32, for one `pull_slots(..., spill=...)`.

    1. spilled victims x base attackers (the victim loop): the second
       level's rows in blocks of SPILL_BLOCK slots, every (hot cell,
       block) that holds somebody on one flat work list, SPILL_CHUNK of
       them a trip, each against the base attackers of its cell's nine
       neighbours, gathered by cell.
    2. base victims x spilled attackers and 3. spilled x spilled (the
       attacker loop): for every attacker-hot cell (SPILL_ATT_CHUNK a
       trip, and no trip when there is none) and each of its nine
       neighbours in turn, that cell's base victims and, if it is a hot
       cell, its spilled victims are gathered with their accumulators,
       folded on with the hot cell's spilled attackers, and written
       back.  Distinct hot cells have distinct neighbours in one
       direction, so each write has unique indices; the merge is the
       fold's own (sum; max attack, min row), so the order of trips is
       immaterial.

    Everything irregular here is priced by the hot cells: gathers and
    scatters of whole cells.  Square grids only (the slab shards run no
    second level)."""
    i32 = jnp.int32
    width, cell_size = vic_table.width, vic_table.cell_size
    cells = vic_table.spill_cells
    if vic_table.height > 0 or att_table.spill_cells != cells:
        raise ValueError("the second level needs a square grid and one "
                         "count of hot cells on both tables")
    n_cells = width * width
    kv, ka = vic_table.bucket, att_table.bucket
    kvh = vic_table.spill_bucket
    block = SPILL_BLOCK if kvh % SPILL_BLOCK == 0 else kvh
    n_blocks = kvh // block
    # by cell: the compiler copies both base tables into this tiling for
    # the gathers (a windowed gather on the payload as it lies is lowered
    # as a loop over the cells gathered, which is worse)
    vgrid = vic_table.payload[: n_cells * kv].reshape(n_cells, kv, -1)
    agrid = att_table.payload[: n_cells * ka].reshape(n_cells, ka, -1)
    vhot, ahot = vic_table.spill_view(), att_table.spill_view()
    vcell = _first_cell(vhot, cell_size, width)
    acell = _first_cell(ahot, cell_size, width)

    # -- 1. spilled victims x base attackers ------------------------------
    chunk = min(cells * n_blocks, SPILL_CHUNK)
    lanes = jnp.arange(chunk, dtype=i32)
    depth = jnp.sum(vhot[..., -1] > 0, axis=1, dtype=i32)  # [cells]
    deepest_first = jnp.argsort(-depth)  # stable: `cells` keys
    # hot cells that reach block b; the work list is block 0's cells,
    # then block 1's, ..., each in `deepest_first` order
    reach = jnp.sum(
        depth[None, :] > (jnp.arange(n_blocks, dtype=i32) * block)[:, None],
        axis=1, dtype=i32)
    reach_end = jnp.cumsum(reach)
    vblocks = vhot.reshape(cells, n_blocks, block, -1)

    def victim_trip(t, out):
        item = t * chunk + lanes
        mine = item < reach_end[-1]
        b = jnp.minimum(
            jnp.sum(item[:, None] >= reach_end[None, :], axis=1, dtype=i32),
            n_blocks - 1)
        row = deepest_first[
            jnp.clip(item - (reach_end[b] - reach[b]), 0, cells - 1)]
        centre = jnp.where(mine, vcell[row], n_cells)
        fold, acc = combat_fold_closure(vblocks[row, b][:, None], radius)
        for dy, dx in STENCIL:
            nbr = _neighbour(centre, dy, dx, width)
            acc = fold(acc, _take(agrid, nbr)[:, None])
        row = jnp.where(mine, row, cells)  # out of range: dropped
        return tuple(o.at[row, b].set(a[:, 0], mode="drop")
                     for o, a in zip(out, acc))

    out = (jnp.zeros((cells, n_blocks, block), i32),
           jnp.full((cells, n_blocks, block), -1.0, jnp.float32),
           jnp.full((cells, n_blocks, block), NO_ROW, jnp.float32))
    hot_acc = jax.lax.fori_loop(
        0, (reach_end[-1] + chunk - 1) // chunk, victim_trip, out)
    hot_acc = tuple(a.reshape(cells, kvh) for a in hot_acc)

    # -- 2., 3. base and spilled victims x spilled attackers --------------
    # cell -> victim-hot row (`cells`: none); a row that holds nobody
    # writes out of range and is dropped
    vrow_of = jnp.full((n_cells + 1,), cells, i32).at[
        jnp.where(vcell < n_cells, vcell, n_cells + 1)
    ].set(jnp.arange(cells, dtype=i32), mode="drop")
    a_chunk = min(cells, SPILL_ATT_CHUNK)
    a_lanes = jnp.arange(a_chunk, dtype=i32)
    fills = (0, -1.0, NO_ROW)

    def fold_on(acc, victims, at, cand):
        """Gather `at`'s victims and accumulators, fold the candidates
        on, write back (`at` out of range: nothing read or written)."""
        fold, _ = combat_fold_closure(_take(victims, at)[:, None], radius)
        got = fold(
            tuple(_take(a, at, f)[:, None] for a, f in zip(acc, fills)), cand)
        return tuple(a.at[at].set(g[:, 0], mode="drop")
                     for a, g in zip(acc, got))

    def attacker_trip(t, carry):
        base_acc, hot_acc = carry
        at = t * a_chunk + a_lanes
        mine = at < cells
        at = jnp.minimum(at, cells - 1)
        centre = jnp.where(mine, acell[at], n_cells)
        cand = ahot[at][:, None]  # [chunk, 1, kah, F]
        for dy, dx in STENCIL:
            # the victims' cell sees the attackers' cell at (dy, dx)
            nbr = _neighbour(centre, -dy, -dx, width)
            base_acc = fold_on(base_acc, vgrid, nbr, cand)
            hot_acc = fold_on(hot_acc, vhot, vrow_of[nbr], cand)
        return base_acc, hot_acc

    n_ahot = jnp.sum(acell < n_cells, dtype=i32)
    base_acc = tuple(a.reshape(n_cells, kv) for a in (inc, besta, bestr))
    base_acc, hot_acc = jax.lax.fori_loop(
        0, (n_ahot + a_chunk - 1) // a_chunk, attacker_trip,
        (base_acc, hot_acc))
    return (base_acc[0].reshape(width, width, kv),
            _best_rows(base_acc[2]).reshape(width, width, kv),
            hot_acc[0], _best_rows(hot_acc[2]))


class CombatModule(Module):
    """Batched AoE combat + death/respawn for one fighter class."""

    name = "CombatModule"

    def __init__(
        self,
        class_name: str = "NPC",
        extent: float = 512.0,
        radius: float = 4.0,
        cell_size: Optional[float] = None,
        bucket: Optional[int] = None,
        respawn_s: float = 5.0,
        attack_period_s: float = 1.0,
        order: int = 30,
        emit_events: bool = True,
        use_pallas: Optional[int] = None,
        verlet_skin: Optional[float] = None,
    ):
        super().__init__()
        self.class_name = class_name
        self.extent = float(extent)
        self.radius = float(radius)
        # Verlet skin (ops/verlet.py): None = NF_VERLET_SKIN env knob,
        # <= 0 = off (rebuild every tick, exactly the legacy path).  A
        # positive skin inflates the grid so the 3x3 stencil still covers
        # the true radius from positions up to skin/2 stale.
        self.verlet_skin = float(
            verlet_skin if verlet_skin is not None else skin_from_env()
        )
        self.cell_size = float(cell_size if cell_size is not None else max(radius, 1.0))
        if self.verlet_skin > 0.0:
            self.cell_size = max(self.cell_size, self.radius + self.verlet_skin)
        self.width = max(1, int(self.extent / self.cell_size))
        # None = size buckets from capacity/cell density at trace time so
        # overflow (entities silently missing combat) stays ~zero
        self.bucket = None if bucket is None else int(bucket)
        self.respawn_s = float(respawn_s)
        self.attack_period_s = float(attack_period_s)
        self.emit_events = emit_events
        # runtime overflow surfacing (round-4 verdict item 5): the tick
        # itself emits ON_COMBAT_TABLE_OVERFLOW; the module subscribes,
        # counts, logs on budget breach, and (auto_resize) doubles the
        # bucket + retraces so the drops stop — not just a bench number
        self.overflow_budget = 1e-4  # dropped/alive alert threshold
        self.auto_resize = True
        self.max_bucket_boost = 8
        self._bucket_boost = 1
        # the second level (ops/stencil.py `build_cell_table_pair`,
        # `combat_fold_spill`): (hot cells, victims, attackers) it holds
        # beyond the base depths; (0, 0, 0) until a breach that a few
        # deep cells caused sizes it (`_on_overflow`)
        self._spill = (0, 0, 0)
        self.overflow_last = (0, 0)  # (victims, attackers) latest tick
        self.overflow_total = 0
        self.overflow_alerts = 0
        self._overflow_log_muted = False
        # the one programmatic pin on the fold's engine: None (what every
        # deployment runs) leaves the choice to `resolved_engine`, which
        # makes it from the grid this module traces; 0/False forces the
        # XLA stencil fold, 1/True the Pallas kernel over the same tables
        # (ops/stencil_pallas.combat_fold_pallas).  It is here so that the
        # parity tests, chip_smoke.py's engine gate and an A/B on the chip
        # can force each side; nothing in a deployment sets it and no
        # environment variable reaches it.  (The stencil engine is the
        # only combat engine: at honest bucket sizes it beats the old
        # per-candidate-gather pipeline even on a single CPU core —
        # 103 ms vs 186 ms at 100k — and by ~25x on a v5e, where
        # irregular gathers run at ~1% of HBM bandwidth.)
        self.use_pallas = use_pallas
        # the engine the newest trace baked in (None until the first
        # trace) — read by the nf_combat_fold_engine gauge, bench.py,
        # chip_smoke.py and the benchmark's tick driver
        self.engine_baked: Optional[int] = None
        # fraction of the population the attacker candidate table is sized
        # for; 1.0 (safe default) means "everyone could fire on one tick".
        # arm_all(stagger=True) lowers it to dt/attack_period — staggered
        # phases make instantaneous attacker density ~duty * population,
        # and the candidate table (the 9x-scanned side of the fold)
        # shrinks by the same factor.
        self._attacker_duty = 1.0
        self.add_phase("aoe", self._combat_phase, order=order)
        self.add_phase("death", self._death_phase, order=order + 5)

    # -- lifecycle -----------------------------------------------------------

    def init(self) -> None:
        # timer slots must exist before the world is built
        self.kernel.schedule.register_timer(self.class_name, ATTACK_TIMER)
        if self.verlet_skin > 0.0:
            # the Verlet cache rides WorldState.aux as carried tick state;
            # a zero cache forces a rebuild on the first tick, and
            # kernel.invalidate() (bucket boost, duty change) drops it so
            # slot assignments baked against stale geometry cannot leak
            self.kernel.register_aux(
                f"verlet/{self.class_name}",
                lambda: init_cache(self.kernel.store.capacity(self.class_name)),
            )

    def after_init(self) -> None:
        if self.emit_events:
            self.kernel.events.subscribe_batch(
                int(GameEvent.ON_COMBAT_TABLE_OVERFLOW), self._on_overflow
            )

    def execute(self) -> None:
        # the overflow event only fires on drops — reset the per-tick
        # reading each frame so a drop-free tick reads (0, 0) instead of
        # the last bad tick forever (module execute runs before the
        # kernel's device step + event dispatch in the same frame)
        self.overflow_last = (0, 0)

    def _on_overflow(self, cname: str, _mask, params) -> None:
        """Host side of the tick's overflow signal: count, alert on
        budget breach, and answer the breach by what breached
        (`_answer_breach`: the second level for a few deep cells, the
        doubling for a world denser everywhere) with a retrace, so
        combat drops stop instead of staying a silent bench-only
        number."""
        import logging

        dv = int(params["dropped_victims"][0])
        da = int(params["dropped_attackers"][0])
        self.overflow_last = (dv, da)
        self.overflow_total += dv + da
        alive = int(self.kernel.store._hosts[cname].alloc_mask.sum())
        if alive <= 0 or (dv + da) / alive <= self.overflow_budget:
            return
        self.overflow_alerts += 1
        log = logging.getLogger("nf.combat")
        capacity = int(self.kernel.store.capacity(cname))
        answer = self._answer_breach(capacity) if self.auto_resize else None
        if answer is not None:
            self.kernel.invalidate()  # depths are baked into the trace
            log.warning(
                "combat cell-table overflow: dropped %d/%d victims+attackers "
                "(budget %.4f%%) — %s, tick retracing",
                dv + da, alive, self.overflow_budget * 100, answer,
            )
        elif not self._overflow_log_muted:
            # keep alert COUNTERS per-tick, but log the terminal state
            # once — a pile-up would otherwise spam every tick
            self._overflow_log_muted = True
            log.warning(
                "combat cell-table overflow: dropped %d/%d victims+attackers "
                "(budget %.4f%%) — auto-resize %s; further breaches are "
                "counted (overflow_alerts) but not logged",
                dv + da, alive, self.overflow_budget * 100,
                "exhausted" if self.auto_resize else "disabled",
            )

    # The breach policy's constants (PERF.md section 6, PR 30, has the
    # counts behind each):
    #
    # A cell has to be this many times over the base depth before the
    # second level is thought of.  Up to there the doubling that every
    # world has had cures the cell at a price that is known (`tick-1m`'s
    # breach at tick 245 reads a deepest cell of 25 or 26 rows at depth
    # 16); the siege world's deepest cell is 8 to 16 times over.
    SPILL_MIN_OVERDEPTH = stencil.SPILL_MIN_OVERDEPTH
    # The most a second level that just holds what was seen may take of
    # the base table's slots.  Its streaming passes (zero fill, depths,
    # results) go by its slots as the base level's go by the grid's, so
    # a level as large as the grid is priced like the grid.  The siege
    # world reads 0.8 to 0.95 of the grid at the shipped depth (8,700 to
    # 9,700 over-full cells, 226 to 246 over: it doubles on every seed)
    # and 0.15 to 0.17 after one doubling (3,500 to 3,900 cells): half
    # lies a factor of 1.6 and of 3 from them.
    SPILL_MAX_GRID_SHARE = stencil.SPILL_MAX_GRID_SHARE
    # Headroom over what the breaching tick observed, so that the crowd's
    # drift brings no second retrace.  On the chip, where the dead stand
    # still where the killing is, the over-full cells read 3,568 at tick
    # 0 and 3,975 at tick 309 (+11%) and the deepest cell 249 and 299
    # (+20%; 327 on another seed).  Both sizes are then rounded up to a
    # power of two, so that worlds that differ by a seed trace the same
    # program: sized at tick 4, the siege world's 3,550 to 4,100 cells
    # and 210 to 224 rows over come to 8,192 x 512 on every seed, the
    # next power a quarter away on either (at headroom 2 the seeds over
    # 4,096 cells took 16,384, and their tick read 4% longer).  A cell's
    # attackers are a 1-in-`interval` draw of its rows, so their depth
    # is sized from the victims' (mean + 5 sigma of a Poisson draw).
    SPILL_CELLS_HEADROOM = stencil.SPILL_CELLS_HEADROOM
    SPILL_DEPTH_HEADROOM = stencil.SPILL_DEPTH_HEADROOM
    SPILL_ATTACKER_SIGMAS = 5.0

    def _sized_spill(self, capacity: int, seen: dict):
        """The second level that holds what the breaching tick observed,
        with headroom, never smaller than the one there is: hot cells
        and the victims' depth powers of two, the attackers' depth whole
        sublanes (8)."""
        import math

        kv, ka = self.resolved_bucket(capacity), self.resolved_att_bucket(capacity)
        cells, depth = stencil.second_level_size(
            max(seen["aoe_hot_cells"], seen["aoe_hot_att_cells"]),
            seen["aoe_cell_rows_max"], kv,
            self.SPILL_CELLS_HEADROOM, self.SPILL_DEPTH_HEADROOM)
        # the attackers of the deepest cell the victims' side now holds
        mean = (kv + depth) * min(self._attacker_duty, 1.0)
        att = mean + self.SPILL_ATTACKER_SIGMAS * math.sqrt(mean) + 2.0
        att = max(att, 1.5 * seen["aoe_cell_attackers_max"]) - ka
        att_depth = -(-max(math.ceil(att), 8) // 8) * 8
        was = self._spill
        return (max(cells, was[0]), max(depth, was[1]), max(att_depth, was[2]))

    def _answer_breach(self, capacity: int) -> Optional[str]:
        """What a budget breach changes, from what the breaching tick
        observed (the tick's own counters, `Kernel.last_counters`), or
        None when nothing is left to change.

        - Some cell is more than `SPILL_MIN_OVERDEPTH` times over its
          depth, and a second level just large enough for what was seen
          (over-full cells x the deepest one's excess) is at most
          `SPILL_MAX_GRID_SHARE` of the base table (grid cells x
          bucket), on both sides: a few deep cells.  Size the second
          level for them, with headroom; the grid keeps its depth.
        - Otherwise the world is over-full everywhere, or the over-full
          cells are so many that their level would outgrow the grid's
          and be priced like it: double both buckets, as ever.
        - With the doubling used up, the second level is what is left,
          whatever its size."""
        seen = self.kernel.last_counters
        can_double = self._bucket_boost < self.max_bucket_boost
        names = ("aoe_hot_cells", "aoe_hot_att_cells", "aoe_cell_rows_max",
                 "aoe_cell_attackers_max")
        if self.verlet_skin <= 0.0 and all(k in seen for k in names):
            kv = self.resolved_bucket(capacity)
            ka = self.resolved_att_bucket(capacity)
            sides = (
                (seen["aoe_hot_cells"], seen["aoe_cell_rows_max"], kv),
                (seen["aoe_hot_att_cells"], seen["aoe_cell_attackers_max"],
                 ka),
            )
            deep = any(stencil.deep_cell(most, depth, self.SPILL_MIN_OVERDEPTH)
                       for _hot, most, depth in sides)
            sized = self._sized_spill(capacity, seen)
            # what was seen, as second-level slots, against the grid's
            few = all(
                stencil.few_hot_cells(hot, most, depth,
                                      self.width * self.width,
                                      self.SPILL_MAX_GRID_SHARE)
                for hot, most, depth in sides)
            if deep and (few or not can_double) and sized != self._spill:
                self._spill = sized
                return ("second level sized to %d hot cells, %d victims and "
                        "%d attackers deep" % sized)
        if can_double:
            self._bucket_boost *= 2
            return "bucket boosted x%d" % self._bucket_boost
        return None

    def arm_all(self, stagger: bool = True) -> None:
        """Arm the attack heartbeat on every live row (benchmark seeding).

        stagger=True spreads first firings evenly across the attack
        period (`1 + row % interval` ticks) — the batch equivalent of the
        reference arming each object's heartbeat at its own creation time
        (NFCScheduleModule AddSchedule at create).  Synchronized arming
        (stagger=False) makes every entity fire on the same tick, so the
        attacker candidate table must be sized for the full population."""
        import numpy as np

        k = self.kernel
        cs = k.state.classes[self.class_name]
        rows = np.flatnonzero(np.asarray(cs.alive))
        interval = k.schedule.ticks_of(self.attack_period_s)
        delays = 1 + (rows % interval) if (stagger and interval > 1) else None
        k.state = k.schedule.set_timer_rows(
            k.state, self.class_name, rows, ATTACK_TIMER, self.attack_period_s,
            start_delay_ticks=delays,
        )
        new_duty = (1.0 / interval) if delays is not None else 1.0
        if new_duty != self._attacker_duty:
            self._attacker_duty = new_duty
            # candidate-bucket size is baked into the traced tick
            k.invalidate()

    def resolved_bucket(self, capacity: int) -> int:
        """The victim cell-table bucket size the combat phase actually
        uses — shared with bench.py's overflow monitor so both stay in
        sync.  `_bucket_boost` doubles on an overflow-budget breach
        (auto-resize), bounded so a pathological pile-up cannot retrace
        toward capacity-sized buckets."""
        base = (
            self.bucket
            if self.bucket is not None
            else auto_bucket(capacity, self.width)
        )
        return min(int(base * self._bucket_boost), max(capacity, 1))

    def resolved_att_bucket(self, capacity: int) -> int:
        """The attacker candidate-table bucket size: sized for the
        instantaneous attacker density (capacity * duty), never larger
        than the victim bucket.  With staggered arming duty is
        dt/attack_period, so the 9x-scanned candidate side of the fold
        shrinks ~duty-fold while victims stay fully resident."""
        import math

        if self._attacker_duty >= 1.0:
            # synchronized arming: everyone can fire on one tick — the
            # candidate table must be exactly as deep as the victim table
            return self.resolved_bucket(capacity)
        eff = max(1, int(math.ceil(capacity * self._attacker_duty)))
        return min(
            auto_bucket(eff, self.width, lo=4, align=2) * self._bucket_boost,
            self.resolved_bucket(capacity),
        )

    def resolved_spill(self, capacity: int):
        """(hot cells, victims, attackers) the second level holds beyond
        the base depths in a tick traced now, as `resolved_bucket`
        states the base depth: (0, 0, 0) until `_on_overflow` has sized
        it.  The Verlet path runs no second level."""
        if self.verlet_skin > 0.0:
            return (0, 0, 0)
        cells, depth, att_depth = self._spill
        return (min(cells, max(capacity, 1)), depth, att_depth)

    def resolved_att_rows(self, capacity: int) -> int:
        """Rows of the sorted attacker list the table build gathers and
        scatters a trip (`build_cell_table_pair`'s `sub_rows`): about
        twice the attackers a tick can hold under the arming this module
        knows (capacity * duty), rounded up to whole sublanes of 8, the
        whole bank when everyone can fire at once.  A tick with more
        attackers than this sends more chunks, not fewer attacks."""
        import math

        if self._attacker_duty >= 1.0:
            return capacity
        eff = max(1, int(math.ceil(capacity * self._attacker_duty)))
        return min(-(-2 * eff // 8) * 8, capacity)

    def resolved_engine(self, capacity: int) -> int:
        """The fold engine a tick traced now over `capacity` rows bakes
        in: 0 (XLA fold) or 1 (Pallas fold, same tables, equal bit for
        bit).  Unpinned it is `stencil_pallas.fold_engine`'s answer from
        the platform the tick is traced for, this grid's width and the
        two resolved depths, so a bucket boost can change it on the
        retrace.  `use_pallas` pins it (bools keep their historical
        meaning: True == 1); unknown values raise instead of silently
        running the default — a typo'd engine would invalidate any A/B
        it labeled."""
        from ..ops import stencil_pallas

        if self.use_pallas is None:
            return stencil_pallas.fold_engine(
                stencil_pallas.trace_platform(), self.width,
                self.resolved_bucket(capacity),
                self.resolved_att_bucket(capacity),
            )
        mode = int(self.use_pallas)
        if mode not in (0, 1):
            raise ValueError(f"use_pallas={mode!r}: expected 0 or 1")
        return mode

    # -- device phases -------------------------------------------------------

    def _combat_phase(self, state: WorldState, ctx) -> WorldState:
        cname = self.class_name
        store = ctx.store
        if cname not in store.class_index:
            return state
        spec = store.spec(cname)
        cs = state.classes[cname]
        pos = cs.vec[:, spec.slot("Position").col, :2]
        hp_col = spec.slot("HP").col
        hp = cs.i32[:, hp_col]
        atk = cs.i32[:, spec.slot("ATK_VALUE").col]
        deff = cs.i32[:, spec.slot("DEF_VALUE").col]
        camp = (
            cs.i32[:, spec.slot("Camp").col]
            if spec.has_property("Camp")
            else jnp.zeros_like(hp)
        )

        attacking = ctx.fired(cname, ATTACK_TIMER) & cs.alive & (hp > 0)
        if spec.has_property("SKILL_GATE"):
            attacking &= cs.i32[:, spec.slot("SKILL_GATE").col] == 0

        # combat is (scene, group)-scoped like every broadcast in the
        # reference (NFCSceneAOIModule::GetBroadCastObject) — entities at
        # overlapping coordinates in different cells never interact
        n = pos.shape[0]
        bucket = self.resolved_bucket(n)
        att_bucket = self.resolved_att_bucket(n)
        engine = self.engine_baked = self.resolved_engine(n)
        # TWO tables: every alive entity is RESIDENT as a victim (K deep),
        # but only this tick's attackers ride the 9x-scanned candidate
        # side (K_att deep — with staggered attack phases K_att is
        # ~duty*K, which is where the fold's pairwise cost lives).  f32
        # carries each int column exactly for values < 2^24 (row <
        # capacity, atk, scene id, group id — scene and group ride in
        # separate columns so neither magnitude compounds); per-shift
        # damage sums stay < 2^24 because a shift has at most K_att
        # candidates, and the cross-shift total accumulates in exact
        # int32.  Entities beyond a cell's bucket are dropped from that
        # table for the tick (victim table: invisible AND invulnerable;
        # attacker table: the attack doesn't land) — `auto_bucket` keeps
        # both ~zero and CellTable.dropped counts them.
        f32 = jnp.float32
        rows_f = jnp.arange(n, dtype=f32)
        camp_f = camp.astype(f32)
        scene_f = cs.i32[:, spec.slot("SceneID").col].astype(f32)
        group_f = cs.i32[:, spec.slot("GroupID").col].astype(f32)
        # no explicit self-exclusion column: an entity always shares its
        # own camp, so the no-friendly-fire mask (cc != vcamp) already
        # rules self out of every pair.  (If friendly fire is ever
        # enabled, reintroduce a row compare here AND in the Pallas
        # kernel.)
        vic_feats = jnp.stack(
            [pos[:, 0], pos[:, 1], camp_f, scene_f, group_f],
            axis=-1,
        )
        eff_atk = jnp.where(attacking, atk, 0).astype(f32)
        att_feats = jnp.stack(
            [pos[:, 0], pos[:, 1], eff_atk, camp_f, scene_f, group_f, rows_f],
            axis=-1,
        )
        if self.verlet_skin > 0.0:
            # displacement-gated build (ops/verlet.py): the argsort only
            # runs when some entity drifted >= skin/2 from its binning
            # anchor (or the alive set changed); otherwise both payload
            # scatters replay against the cached slot assignment.  The
            # fold below masks by TRUE radius on current positions, so
            # results stay bit-identical to rebuilding every tick.
            aux_key = f"verlet/{cname}"
            with jax.named_scope("nf.aoe.rank"):
                cache, rebuilt = refresh(
                    state.aux[aux_key], pos, cs.alive,
                    self.cell_size, self.width, bucket, self.verlet_skin,
                )
            n_cells = self.width * self.width
            with jax.named_scope("nf.aoe.table"):
                vic_bin = full_table(
                    cache, vic_feats, cs.alive, n_cells,
                    self.cell_size, self.width, bucket,
                )
                att_bin = sub_table(
                    cache, attacking, att_feats, n_cells,
                    self.cell_size, self.width, att_bucket,
                )
            ctx.count("grid_rebuilds", rebuilt)
            ctx.count("grid_reuses", 1 - rebuilt)
            ctx.count("grid_cache_age", cache.age)
            # the replay scatters rows to cached slots and gathers none
            ctx.count("aoe_victim_slots_built", 0)
            state = state.replace(aux={**state.aux, aux_key: cache})
        else:
            # one key pass feeds both tables (attackers subset of alive);
            # this one call ranks and builds: it opens nf.aoe.rank and
            # nf.aoe.table itself.  The attacker side gathers and
            # scatters att_rows sorted attackers a trip, not the bank.
            att_rows = self.resolved_att_rows(n)
            vic_bin, att_bin = build_cell_table_pair(
                pos, cs.alive, vic_feats, attacking, att_feats,
                self.cell_size, self.width, bucket, att_bucket,
                sub_rows=att_rows, spill=self.resolved_spill(n),
            )
            # what the breach policy reads (streaming reductions over
            # the sorted keys and ranks)
            ctx.count("aoe_hot_cells", vic_bin.stats.hot_cells)
            ctx.count("aoe_cell_rows_max", vic_bin.stats.rows_max)
            ctx.count("aoe_hot_att_cells", att_bin.stats.hot_cells)
            ctx.count("aoe_cell_attackers_max", att_bin.stats.rows_max)
            ctx.count("aoe_spill_rows",
                      vic_bin.stats.spill_rows + att_bin.stats.spill_rows)
            # how the chunk engages: 1 a tick under the arming it was
            # sized for
            chunks = sub_chunks(attacking, att_rows)
            ctx.count("aoe_attacker_chunks", chunks)
            ctx.count("aoe_attacker_rows_sent", chunks * att_rows)
            # what the victim table is priced by: every slot of both
            # levels is gathered from the sorted list, no row is sent
            ctx.count("aoe_victim_slots_built", vic_bin.payload.shape[0] - 1)
        spilling = vic_bin.spill_cells > 0
        with jax.named_scope("nf.aoe.fold"):
            if engine == 1:
                from ..ops import stencil_pallas

                folded = stencil_pallas.combat_fold_pallas(
                    vic_bin,
                    att_bin,
                    self.radius,
                    interpret=stencil_pallas.pallas_interpret(),
                    raw=spilling,
                )
            else:
                folded = combat_fold_xla(
                    vic_bin, att_bin, self.radius, raw=spilling)
        hot = None
        if spilling:
            with jax.named_scope("nf.aoe.spill"):
                inc, bestr, hot_inc, hot_bestr = combat_fold_spill(
                    vic_bin, att_bin, self.radius, *folded)
                hot = jnp.stack([hot_inc, hot_bestr], axis=-1)
        else:
            inc, bestr = folded
        if self.emit_events:
            # runtime overflow signal: the duty-sized attacker bucket is
            # baked into the traced tick, so arming patterns that
            # concentrate attackers into one residue class (e.g. a spawn
            # wave armed synchronously AFTER arm_all's staggered seeding)
            # would otherwise drop attacks silently.  Subscribe batch to
            # ON_COMBAT_TABLE_OVERFLOW to observe it; bench.py replays
            # the residue classes offline for the same number.
            total_drop = vic_bin.dropped + att_bin.dropped
            mask0 = jnp.zeros((n,), bool).at[0].set(total_drop > 0)
            ctx.emit(
                int(GameEvent.ON_COMBAT_TABLE_OVERFLOW),
                cname,
                mask0,
                dropped_victims=jnp.broadcast_to(vic_bin.dropped, (n,)),
                dropped_attackers=jnp.broadcast_to(att_bin.dropped, (n,)),
            )
        # counter bank (rides the summary fetch; always on, unlike the
        # emit_events-gated overflow event above)
        ctx.count("aoi_victim_overflow_drops", vic_bin.dropped)
        ctx.count("aoi_attacker_overflow_drops", att_bin.dropped)
        with jax.named_scope("nf.aoe.pull"):
            pulled = pull_slots(
                vic_bin.slot_of, jnp.stack([inc, bestr], axis=-1),
                fill=(0, -1), spill=hot,
            )
        incoming = pulled[..., 0]
        # dead-but-not-yet-respawned victims take no damage
        incoming = jnp.where(cs.alive & (hp > 0), incoming, 0)
        dmg = jnp.maximum(incoming - deff, 0)
        dmg = jnp.where(incoming > 0, jnp.maximum(dmg, 1), 0)  # a hit always chips
        ctx.count("combat_hits", incoming > 0)
        ctx.count("combat_damage_total", dmg)
        new_hp = jnp.maximum(hp - dmg, 0)
        i32 = cs.i32.at[:, hp_col].set(new_hp)

        if spec.has_property("LastAttacker"):
            # strongest in-range attacker, packed as an object handle
            cls_idx = store.class_index[cname]
            best_row = pulled[..., 1]
            handle = (cls_idx << HANDLE_ROW_BITS) | jnp.maximum(best_row, 0)
            la_col = spec.slot("LastAttacker").col
            hit = incoming > 0
            i32 = i32.at[:, la_col].set(
                jnp.where(hit, handle, i32[:, la_col])
            )
        return with_class(state, cname, cs.replace(i32=i32))

    def _death_phase(self, state: WorldState, ctx) -> WorldState:
        cname = self.class_name
        store = ctx.store
        if cname not in store.class_index:
            return state
        spec = store.spec(cname)
        if not spec.has_property("DeadTick"):
            return state
        cs = state.classes[cname]
        hp_col = spec.slot("HP").col
        dead_col = spec.slot("DeadTick").col
        hp = cs.i32[:, hp_col]
        dead_tick = cs.i32[:, dead_col]

        just_died = cs.alive & (hp <= 0) & (dead_tick == 0)
        if self.emit_events:
            params = {}
            if spec.has_property("LastAttacker"):
                params["killer"] = cs.i32[:, spec.slot("LastAttacker").col]
            ctx.emit(int(GameEvent.ON_OBJECT_BE_KILLED), cname, just_died, **params)
        # DeadTick stores tick+1 so tick 0 deaths are distinguishable from 0
        i32 = cs.i32.at[:, dead_col].set(
            jnp.where(just_died, ctx.tick + 1, dead_tick)
        )

        respawn_ticks = max(1, int(round(self.respawn_s / ctx.dt)))
        due = (dead_tick > 0) & (ctx.tick + 1 - dead_tick >= respawn_ticks) & cs.alive
        if spec.has_property("MAXHP"):
            maxhp = cs.i32[:, spec.slot("MAXHP").col]
            # no MAXHP stat -> nothing to restore -> stay dead (otherwise
            # DeadTick would clear with HP still 0 and BE_KILLED would
            # re-fire every respawn interval forever)
            due &= maxhp > 0
            i32 = i32.at[:, hp_col].set(jnp.where(due, maxhp, i32[:, hp_col]))
        else:
            due &= False
        i32 = i32.at[:, dead_col].set(jnp.where(due, 0, i32[:, dead_col]))
        ctx.count("respawns", due)
        if self.emit_events:
            ctx.emit(int(GameEvent.ON_NPC_RESPAWN), cname, due)
        return with_class(state, cname, cs.replace(i32=i32))


class SkillModule(Module):
    """Host-side targeted skill use (reference NFCSkillModule parity)."""

    name = "SkillModule"

    def __init__(self, skill_damage: int = 10):
        super().__init__()
        self.skill_damage = int(skill_damage)

    def use_skill(self, attacker: Guid, skill_id: str, target: Guid) -> bool:
        """Validate the skill element, damage the target by 10 (floor 0),
        stamp LastAttacker (NFCSkillModule.cpp:113-139)."""
        k = self.kernel
        if not k.elements.exists(skill_id):
            return False
        if target not in k.store.guid_map:
            return False
        tclass, _ = k.store.row_of(target)
        cur = int(k.get_property(target, "HP"))
        if cur <= 0:
            return False
        if k.store.spec(tclass).has_property("LastAttacker"):
            k.set_property(target, "LastAttacker", attacker)
        k.set_property(target, "HP", max(cur - self.skill_damage, 0))
        return True
