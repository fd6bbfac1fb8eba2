"""The plain reference tick: NoahGameFrame's per-object Execute() loop.

One frame of the NPC world the benchmark's configurations run, written
from what the upstream modules do to one object at a time and held in
plain numpy arrays, one value per entity: no cell tables, no buckets, no
cache, no compiled program.  It imports nothing of the program under test.

    NFCScheduleModule::Execute      heartbeats that are due fire and re-arm
    (NPC MoveType / TargetPos)      walk towards the target, pick a new one
    NFCSkillModule::OnUseSkill      every attacker whose heartbeat fired
                                    damages every enemy within the radius
    NFCNPCRefreshModule             HP <= 0 registers the death; after the
                                    respawn delay the NPC is restored
    regen heartbeat                 HP/MP/SP += regen, capped at the maximum
    NFCPropertyModule               final stats = sum of the stat groups

The world is a dict of named arrays (see `State`), so nothing here knows
how the program lays its rows out; `benchmarks/drivers/` does the naming.

Neighbours are found with a k-d tree (scipy), which is exact: every pair
within the radius is found, whatever the density.  The program drops
entities from over-full cells; the reference never does.

`precision="bfloat16"` is the control of the comparison: the same frame
with positions and movement rounded to bfloat16, the step below the
float32 that the configurations state.  It has to FAIL the comparison.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

SPEED_UNIT = 10000.0  # Class/NPC.xml: MOVE_SPEED 10000 == 1 unit/s
CHANNELS = (("HP", "MAXHP", "HPREGEN"), ("MP", "MAXMP", "MPREGEN"),
            ("SP", "MAXSP", "SPREGEN"))


@dataclasses.dataclass
class Params:
    """What a configuration file states about the frame."""

    dt: float
    extent: float
    aoe_radius: float
    respawn_s: float
    movement: bool = True
    combat: bool = True
    regen: bool = True

    @property
    def respawn_ticks(self) -> int:
        return max(1, int(round(self.respawn_s / self.dt)))


@dataclasses.dataclass
class State:
    """One class of entities at one tick.  Every array has one entry per
    row.  `props` holds the integer properties by name; `timers` maps a
    heartbeat's name to its four columns; `stat_totals` maps a stat's
    name to the sum of its contribution groups (the record page is not
    written by the frame, so the sum is taken once)."""

    tick: int
    rng_key: np.ndarray  # uint32[2], the world's seed key
    alive: np.ndarray  # bool
    pos: np.ndarray  # float32 [N, 2]
    target: np.ndarray  # float32 [N, 2]
    props: Dict[str, np.ndarray]  # int32 each
    timers: Dict[str, Dict[str, np.ndarray]]  # next_fire/interval/remain/active
    stat_totals: Dict[str, np.ndarray]
    last_attacker: np.ndarray  # int32 row of the strongest attacker, -1 none

    def copy(self) -> "State":
        return State(
            tick=self.tick, rng_key=self.rng_key.copy(),
            alive=self.alive.copy(), pos=self.pos.copy(),
            target=self.target.copy(),
            props={k: v.copy() for k, v in self.props.items()},
            timers={n: {k: v.copy() for k, v in t.items()}
                    for n, t in self.timers.items()},
            stat_totals=self.stat_totals,
            last_attacker=self.last_attacker.copy())


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to bfloat16 (nearest even) and back."""
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def new_targets(rng_key: np.ndarray, tick: int, n: int,
                extent: float) -> np.ndarray:
    """The frame's fresh walk targets: uniform over the extent, drawn
    from the world's key folded with the tick and the draw's position in
    the frame (the first).  Drawn on the host CPU with jax.random, which
    is counter-based: the same key gives the same bits on any backend."""
    import jax

    with jax.default_device(jax.devices("cpu")[0]):
        key = jax.numpy.asarray(np.asarray(rng_key, np.uint32))
        key = jax.random.fold_in(jax.random.fold_in(key, int(tick)), 1)
        return np.asarray(jax.random.uniform(
            key, (n, 2), minval=0.0, maxval=float(extent)), np.float32)


def fire_heartbeats(s: State) -> Dict[str, np.ndarray]:
    """NFCScheduleModule: a heartbeat that is due fires, moves its next
    firing on by its interval, counts down if it is finite and stops at
    zero.  Rows that are not alive never fire.  Returns name -> fired."""
    fired = {}
    for name, t in s.timers.items():
        due = t["active"] & (s.tick >= t["next_fire"]) & s.alive
        t["next_fire"] = np.where(due, t["next_fire"] + t["interval"],
                                  t["next_fire"]).astype(np.int32)
        t["remain"] = np.where(due & (t["remain"] > 0), t["remain"] - 1,
                               t["remain"]).astype(np.int32)
        t["active"] = t["active"] & ~(due & (t["remain"] == 0))
        fired[name] = due
    return fired


def move(s: State, p: Params, precision: str = "float32") -> None:
    """Walk each living, ungated NPC towards its target at MOVE_SPEED;
    one that arrives (within one step) takes a fresh uniform target."""
    f32 = np.float32
    rnd = _bf16 if precision == "bfloat16" else (lambda x: x)
    pos, tgt = rnd(s.pos), rnd(s.target)
    speed = s.props["MOVE_SPEED"].astype(f32) / f32(SPEED_UNIT)
    if "MOVE_GATE" in s.props:
        speed = np.where(s.props["MOVE_GATE"] > 0, f32(0), speed)
    speed = np.where(s.props["HP"] > 0, speed, f32(0))
    step = rnd(speed * f32(p.dt))
    delta = rnd(tgt - pos)
    dist = rnd(np.sqrt(rnd(delta[:, 0] * delta[:, 0]
                           + delta[:, 1] * delta[:, 1]) + f32(1e-12)))
    arrived = dist <= np.maximum(step, f32(1e-6))
    fresh = new_targets(s.rng_key, s.tick, pos.shape[0], p.extent)
    s.target = np.where((arrived & s.alive)[:, None], fresh,
                        s.target).astype(f32)
    stride = rnd(rnd(delta / dist[:, None]) * step[:, None])
    walked = np.where(arrived[:, None], delta, stride)
    new_pos = np.clip(rnd(pos + walked), f32(0), f32(p.extent))
    s.pos = np.where(s.alive[:, None], new_pos, s.pos).astype(f32)


def resolve_attacks(s: State, p: Params, attacking: np.ndarray,
                    pos: np.ndarray, margin: float = 0.0):
    """NFCSkillModule::OnUseSkill for every attacker at once: each enemy
    (another camp, same scene and group) within the radius takes the
    attacker's ATK_VALUE.  Returns (incoming damage, row of the strongest
    attacker or -1, rows with a pair so close to the radius that float32
    rounding of the distance decides it)."""
    n = pos.shape[0]
    incoming = np.zeros(n, np.int64)
    best_row = np.full(n, -1, np.int64)
    ambiguous = np.zeros(n, bool)
    atk = s.props["ATK_VALUE"]
    att_rows = np.flatnonzero(attacking & (atk != 0))
    vic_rows = np.flatnonzero(s.alive & (s.props["HP"] > 0))
    if att_rows.size == 0 or vic_rows.size == 0:
        return incoming, best_row, ambiguous
    from scipy.spatial import cKDTree

    r = float(p.aoe_radius)
    vic_tree = cKDTree(pos[vic_rows].astype(np.float64))
    att_tree = cKDTree(pos[att_rows].astype(np.float64))
    pairs = att_tree.sparse_distance_matrix(
        vic_tree, r * (1.0 + 1e-3) + margin, output_type="coo_matrix")
    a = att_rows[pairs.row]
    v = vic_rows[pairs.col]
    camp, scene, group = (s.props[k] for k in ("Camp", "SceneID", "GroupID"))
    enemy = (camp[a] != camp[v]) & (scene[a] == scene[v]) \
        & (group[a] == group[v])
    a, v = a[enemy], v[enemy]
    # the distance test as a float32 machine makes it
    dx = pos[v, 0] - pos[a, 0]
    dy = pos[v, 1] - pos[a, 1]
    d2 = dx * dx + dy * dy
    r2 = np.float32(r * r)
    close = np.abs(d2.astype(np.float64) - float(r2)) <= margin
    ambiguous[v[close]] = True
    hit = d2 <= r2
    a, v = a[hit], v[hit]
    np.add.at(incoming, v, atk[a].astype(np.int64))
    # strongest attacker, the lowest row among equals
    order = np.lexsort((a, -atk[a].astype(np.int64), v))
    v_sorted = v[order]
    first = np.ones(v_sorted.shape[0], bool)
    first[1:] = v_sorted[1:] != v_sorted[:-1]
    best_row[v_sorted[first]] = a[order][first]
    return incoming, best_row, ambiguous


def tick(s: State, p: Params, precision: str = "float32",
         observed_pos: Optional[np.ndarray] = None,
         margin: float = 0.0):
    """One frame.  Returns (state after the frame, rows whose combat
    outcome hangs on float32 rounding of a distance, rows that attacked).

    `observed_pos`: positions that the system under test reported after
    its own movement.  The comparison checks them against `move()` first
    and then hands them in, so that the combat it checks next is decided
    on the same coordinates and a last-bit difference in a position does
    not read as a wrong hit."""
    s = s.copy()
    props = s.props
    i32 = np.int32
    fired = fire_heartbeats(s)
    if p.movement:
        move(s, p, precision)
    combat_pos = s.pos if observed_pos is None else observed_pos
    if precision == "bfloat16":
        combat_pos = _bf16(combat_pos)
    ambiguous = np.zeros(s.alive.shape[0], bool)
    attacking = np.zeros(s.alive.shape[0], bool)
    if p.combat:
        hp = props["HP"]
        attacking = fired["Attack"] & s.alive & (hp > 0)
        if "SKILL_GATE" in props:
            attacking &= props["SKILL_GATE"] == 0
        incoming, best_row, ambiguous = resolve_attacks(
            s, p, attacking, combat_pos, margin)
        dmg = np.maximum(incoming - props["DEF_VALUE"], 0)
        dmg = np.where(incoming > 0, np.maximum(dmg, 1), 0)  # a hit chips
        props["HP"] = np.maximum(hp - dmg, 0).astype(i32)
        s.last_attacker = np.where(incoming > 0, best_row,
                                   s.last_attacker).astype(i32)
        # NFCNPCRefreshModule: register the death, restore after the delay
        hp, dead = props["HP"], props["DeadTick"]
        just_died = s.alive & (hp <= 0) & (dead == 0)
        due = (dead > 0) & (s.tick + 1 - dead >= p.respawn_ticks) \
            & s.alive & (props["MAXHP"] > 0)
        props["HP"] = np.where(due, props["MAXHP"], hp).astype(i32)
        dead = np.where(just_died, s.tick + 1, dead)
        props["DeadTick"] = np.where(due, 0, dead).astype(i32)
    if p.regen:
        live = fired["Regen"] & s.alive & (props["HP"] > 0)
        for cur, cap, reg in CHANNELS:
            if cur in props and reg in props:
                val, r = props[cur], props[reg]
                up = np.minimum(val + r, np.maximum(props[cap], val))
                props[cur] = np.where(live & (r > 0), up, val).astype(i32)
    for name, total in s.stat_totals.items():
        props[name] = total.astype(i32)
    s.tick += 1
    return s, ambiguous, attacking
