"""The comparison that decides `correct` for the NPC-world cells.

Around a few ticks of the measured window the driver keeps device copies
of the NPC banks (`Snapshots`).  Once the window has closed and the
world is freed, each kept tick is replayed by the plain reference
(`reference.tick`) FROM THE STATE THE PROGRAM HAD BEFORE IT, and what
the program made of that state is held against what the reference makes
of it, row by row:

    pos_err_ulp        the worst position after movement, in float32
                       steps at the extent's magnitude
    state_wrong_rows   rows on which any integer property, heartbeat
                       column, walk target, alive flag or strongest
                       attacker differs (exact: every integer of the
                       frame); rows whose hit hangs on the last bit of
                       a float32 distance are set aside and counted
    ambiguous_rows     those rows, per million: a bound on how much the
                       exact comparison may set aside
    diff_cells_off     the frame's own changed-cell count against the
                       cells that changed between the two copies
    ledger_wrong_rows  the guarantees: HP <= 0 exactly when registered
                       dead, population conserved
    dropped_off        the program's overflow counters against the count
                       its stated buckets imply (see below)

The program bins entities into cells of a fixed depth and drops what
does not fit from that tick's combat, the highest rows of an over-full
cell first; its own budget for that is 0.01% of the live rows a tick,
beyond which it deepens the cells.  The reference has no cells and drops
nothing.  So the comparison works out, from the positions and the bucket
depths the program states, which rows the policy drops, sets aside those
victims and every victim in reach of a dropped attacker (they are counted
with the ambiguous rows, under the same bound), and holds the program's
counters to its own count exactly.

Replaying from the program's own previous state (and not from tick 0)
is what keeps the comparison exact: the chip and a CPU round `rsqrt`
differently, so two free-running worlds drift apart from the first
tick and after a few hundred nothing could be held to a limit.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from . import reference

# the float32 distance test d2 <= r2: a machine that fuses the multiply
# and the add differs from one that does not by a rounding of d2
D2_MARGIN_ULPS = 4


@dataclasses.dataclass
class Layout:
    """How the program lays the class out: column names, learned from
    its schema by the driver, so that the reference sees only names."""

    class_name: str
    i32_names: List[str]  # column order of the i32 bank
    position_col: int  # vec bank columns
    target_col: int
    timer_names: List[str]
    stat_names: List[str]
    stat_record_cols: List[int]  # columns of the stat page, per stat
    handle_class: int  # class index packed into an object handle
    handle_row_bits: int
    diff_i32: np.ndarray  # bool per i32 column: rides the sync diff
    diff_f32: np.ndarray
    diff_vec: np.ndarray


def layout_of(kernel, class_name: str, stat_record: str) -> Layout:
    """Read the class's layout off the program's schema."""
    from noahgameframe_tpu.core.datatypes import Bank
    from noahgameframe_tpu.core.store import HANDLE_ROW_BITS

    store = kernel.store
    spec = store.spec(class_name)
    i32_names = [""] * spec.bank_size(Bank.I32)
    for name in spec.prop_order:
        slot = spec.slot(name)
        if slot.bank == Bank.I32:
            i32_names[slot.col] = name
    rec = spec.records[stat_record]
    stat_names = [n for n in rec.col_order if spec.has_property(n)]
    masks = {}
    for bank in (Bank.I32, Bank.F32, Bank.VEC):
        m = np.zeros(spec.bank_size(bank), bool)
        for flag in kernel._diff_flags:
            m |= spec.mask(bank, flag)
        masks[bank] = m
    return Layout(
        class_name=class_name, i32_names=i32_names,
        position_col=spec.slot("Position").col,
        target_col=spec.slot("TargetPos").col,
        timer_names=list(kernel.schedule.timer_names(class_name)),
        stat_names=stat_names,
        stat_record_cols=[rec.cols[n].col for n in stat_names],
        handle_class=int(store.class_index[class_name]),
        handle_row_bits=int(HANDLE_ROW_BITS),
        diff_i32=masks[Bank.I32], diff_f32=masks[Bank.F32],
        diff_vec=masks[Bank.VEC])


class Snapshots:
    """Device copies of one class's banks right before and right after a
    tick, taken by one compiled copy program (warmed in set-up, so
    nothing compiles in the window).  `observers` names a second class
    (the players) whose banks ride along in the copy after the tick: the
    served cell needs to know where each session's avatar stood."""

    def __init__(self, kernel, class_name: str, stat_record: str,
                 observers: Optional[str] = None):
        import jax
        import jax.numpy as jnp

        self.kernel = kernel
        self.class_name = class_name
        self.stat_record = stat_record
        self.observers = observers
        self.layout = layout_of(kernel, class_name, stat_record)
        self._copy = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))
        self._page_sum = jax.jit(
            lambda page: jnp.sum(page, axis=1, dtype=jnp.int32))
        self.pre: Dict[int, dict] = {}  # tick about to run -> leaves
        self.post: Dict[int, dict] = {}  # tick reached -> leaves
        self.counters: Dict[int, dict] = {}  # tick reached -> its counters
        self.stat_sums = None

    def _leaves(self, observers: bool) -> dict:
        st = self.kernel.state
        cs = st.classes[self.class_name]
        t = cs.timers
        out = {"i32": cs.i32, "f32": cs.f32, "vec": cs.vec,
               "alive": cs.alive, "next_fire": t.next_fire,
               "interval": t.interval, "remain": t.remain,
               "active": t.active, "tick": st.tick, "rng": st.rng}
        if observers and self.observers:
            oc = st.classes[self.observers]
            out.update(obs_i32=oc.i32, obs_vec=oc.vec, obs_alive=oc.alive)
        return out

    def warm(self) -> None:
        """Compile the programs and take the stat page's group sums
        (the frame never writes the page; `page_unchanged` checks)."""
        import jax

        jax.block_until_ready(self._copy(self._leaves(False)))
        jax.block_until_ready(self._copy(self._leaves(True)))
        page = self.kernel.state.classes[self.class_name] \
            .records[self.stat_record].i32
        self.stat_sums = jax.block_until_ready(self._page_sum(page))

    def around(self, tick_fn):
        """Run one tick with a copy of the banks on either side."""
        self.pre[int(self.kernel.tick_count)] = self._copy(
            self._leaves(False))
        out = tick_fn()
        reached = int(self.kernel.tick_count)
        self.post[reached] = self._copy(self._leaves(True))
        self.counters[reached] = dict(self.kernel.last_counters)
        return out

    def page_unchanged(self) -> bool:
        import jax.numpy as jnp

        page = self.kernel.state.classes[self.class_name] \
            .records[self.stat_record].i32
        return bool(jnp.array_equal(self._page_sum(page), self.stat_sums))

    def to_host(self) -> "HostSnapshots":
        """Fetch everything kept; after this the world can be freed."""
        def fetch(kept):
            return {t: {k: np.asarray(v) for k, v in leaves.items()}
                    for t, leaves in kept.items()}

        host = HostSnapshots(self.layout, fetch(self.pre), fetch(self.post),
                             dict(self.counters), np.asarray(self.stat_sums))
        self.pre.clear()
        self.post.clear()
        self.stat_sums = None
        self.kernel = None
        return host


@dataclasses.dataclass
class HostSnapshots:
    layout: Layout
    pre: Dict[int, dict]
    post: Dict[int, dict]
    counters: Dict[int, dict]
    stat_sums: np.ndarray

    def pairs(self):
        """(tick, before, after) for every tick kept on both sides."""
        return [(t, self.pre[t], self.post[t + 1])
                for t in sorted(self.pre) if t + 1 in self.post]


def to_state(lay: Layout, leaves: dict, stat_sums: np.ndarray
             ) -> reference.State:
    """Name the program's columns for the reference."""
    i32 = leaves["i32"]
    props = {n: i32[:, c] for c, n in enumerate(lay.i32_names)
             if n and n != "LastAttacker"}
    last = np.full(i32.shape[0], -1, np.int32)
    if "LastAttacker" in lay.i32_names:
        h = i32[:, lay.i32_names.index("LastAttacker")]
        mine = (h >> lay.handle_row_bits) == lay.handle_class
        last = np.where(mine, h & ((1 << lay.handle_row_bits) - 1),
                        -1).astype(np.int32)
    timers = {
        n: {k: leaves[k][:, j]
            for k in ("next_fire", "interval", "remain", "active")}
        for j, n in enumerate(lay.timer_names)}
    totals = {n: stat_sums[:, c]
              for n, c in zip(lay.stat_names, lay.stat_record_cols)}
    vec = leaves["vec"]
    return reference.State(
        tick=int(leaves["tick"]),
        rng_key=np.asarray(leaves["rng"]).astype(np.uint32).reshape(-1)[-2:],
        alive=leaves["alive"], pos=vec[:, lay.position_col, :2],
        target=vec[:, lay.target_col, :2], props=props, timers=timers,
        stat_totals=totals, last_attacker=last)


def _frozen(lay: Layout, leaves: dict) -> Dict[str, np.ndarray]:
    """What no phase of the frame writes: the float bank and the z of
    both vectors.  Compared bit for bit."""
    vec = leaves["vec"]
    return {"f32": leaves["f32"], "pos_z": vec[:, lay.position_col, 2],
            "target_z": vec[:, lay.target_col, 2]}


def wrong_rows(ref: reference.State, got: reference.State) -> np.ndarray:
    """Rows on which any exact column differs."""
    bad = (ref.alive != got.alive) | (ref.last_attacker != got.last_attacker)
    bad |= np.any(ref.target.view(np.int32) != got.target.view(np.int32),
                  axis=1)
    for name, col in ref.props.items():
        bad |= col != got.props[name]
    for name, t in ref.timers.items():
        for k, col in t.items():
            bad |= col != got.timers[name][k]
    return bad


def changed_cells(lay: Layout, before: dict, after: dict) -> int:
    """The sync diff as the kernel states it: flagged cells of alive rows
    whose value changed over the frame (a vector counts once)."""
    alive = after["alive"][:, None]
    n = np.sum((before["i32"] != after["i32"]) & alive & lay.diff_i32[None])
    n += np.sum((before["f32"].view(np.int32) != after["f32"].view(np.int32))
                & alive & lay.diff_f32[None])
    n += np.sum(np.any(before["vec"].view(np.int32)
                       != after["vec"].view(np.int32), axis=-1)
                & alive & lay.diff_vec[None])
    return int(n)


def ledger_wrong_rows(state: reference.State, population: Optional[int]
                      ) -> int:
    """The configuration's guarantees, read off one state."""
    down = state.alive & (state.props["HP"] <= 0)
    registered = state.alive & (state.props["DeadTick"] > 0)
    wrong = int(np.sum(down != registered))
    if population is not None:
        wrong += abs(int(state.alive.sum()) - int(population))
    return wrong


def dropped_rows(pos: np.ndarray, alive: np.ndarray, attacking: np.ndarray,
                 geometry: Dict[str, float]):
    """(victims, attackers) that the stated cell depths drop: in a cell
    holding more than `bucket` alive rows (`att_bucket` attacking rows),
    those beyond the first `bucket` in row order."""
    size, width = np.float32(geometry["cell_size"]), int(geometry["width"])
    cx = np.clip(np.floor(pos[:, 0] / size).astype(np.int64), 0, width - 1)
    cy = np.clip(np.floor(pos[:, 1] / size).astype(np.int64), 0, width - 1)
    cell = cy * width + cx

    def beyond(mask: np.ndarray, depth: int) -> np.ndarray:
        rows = np.flatnonzero(mask)
        order = np.argsort(cell[rows], kind="stable")
        sorted_cells = cell[rows][order]
        head = np.ones(rows.size, bool)
        head[1:] = sorted_cells[1:] != sorted_cells[:-1]
        start = np.maximum.accumulate(np.where(head, np.arange(rows.size), 0))
        return rows[order][np.arange(rows.size) - start >= depth]

    return (beyond(alive, int(geometry["bucket"])),
            beyond(attacking, int(geometry["att_bucket"])))


def in_reach(pos: np.ndarray, of_rows: np.ndarray, radius: float
             ) -> np.ndarray:
    """Rows within `radius` (and a little) of any of `of_rows`."""
    out = np.zeros(pos.shape[0], bool)
    if of_rows.size:
        from scipy.spatial import cKDTree

        tree = cKDTree(pos.astype(np.float64))
        for near in tree.query_ball_point(
                pos[of_rows].astype(np.float64), radius * 1.001):
            out[near] = True
    return out


def compare_ticks(host: HostSnapshots, params: reference.Params,
                  population: Optional[int], geometry=None,
                  control: bool = False) -> Dict[str, float]:
    """Replay every kept tick and reduce to the numbers compared.

    `control=True` puts the reference computed in bfloat16 in the
    program's place: the numbers it returns have to break the limits."""
    lay = host.layout
    ulp = float(np.spacing(np.float32(params.extent)))
    margin = D2_MARGIN_ULPS * float(
        np.spacing(np.float32(params.aoe_radius ** 2)))
    out = {"pos_err_ulp": 0.0, "state_wrong_rows": 0, "ambiguous_rows": 0.0,
           "diff_cells_off": 0, "ledger_wrong_rows": 0, "dropped_off": 0,
           "ticks_compared": 0}
    rows = 1
    for t, before_l, after_l in host.pairs():
        before = to_state(lay, before_l, host.stat_sums)
        if control:
            got = reference.tick(before, params, precision="bfloat16")[0]
        else:
            got = to_state(lay, after_l, host.stat_sums)
        rows = before.alive.shape[0]
        moved = before.copy()
        if params.movement:
            reference.move(moved, params)
        live = got.alive
        err = np.abs(got.pos.astype(np.float64) - moved.pos) / ulp
        out["pos_err_ulp"] = max(out["pos_err_ulp"],
                                 float(err[live].max()) if live.any() else 0.0)
        ref, ambiguous, attacking = reference.tick(
            before, params, observed_pos=got.pos, margin=margin)
        c = None if control else host.counters.get(t + 1)
        if geometry and params.combat:
            vic, att = dropped_rows(got.pos, before.alive, attacking, geometry)
            ambiguous[vic] = True
            ambiguous |= in_reach(got.pos, att, params.aoe_radius)
            if c is not None:
                out["dropped_off"] += abs(
                    int(c["aoi_victim_overflow_drops"]) - vic.size) + abs(
                    int(c["aoi_attacker_overflow_drops"]) - att.size)
        bad = wrong_rows(ref, got)
        out["state_wrong_rows"] += int(np.sum(bad & ~ambiguous))
        out["ambiguous_rows"] += float(ambiguous.sum())
        if got.tick != ref.tick:
            out["state_wrong_rows"] += rows
        if not control:
            still = _frozen(lay, after_l)
            for k, v in _frozen(lay, before_l).items():
                out["state_wrong_rows"] += int(np.sum(
                    v.view(np.int32) != still[k].view(np.int32)))
            if c is not None and "diff_cells" in c:
                out["diff_cells_off"] += abs(
                    int(c["diff_cells"]) - changed_cells(lay, before_l,
                                                         after_l))
        out["ledger_wrong_rows"] += ledger_wrong_rows(got, population)
        out["ticks_compared"] += 1
    n = max(1, out["ticks_compared"])
    out["ambiguous_rows"] = out["ambiguous_rows"] / n / rows * 1e6
    return out
