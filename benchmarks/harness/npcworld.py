"""What the drivers of the NPC-world cells share: building the world a
configuration file states, settling its programs, choosing the ticks to
compare, and naming the configuration for the reference."""

from __future__ import annotations

import numpy as np

from . import reference
from .run import Run, RunFailed

NPC = "NPC"
STAT_RECORD = "CommPropertyValue"


def until_settled(book, one_pass, tries: int = 4) -> list:
    """Repeat one_pass() until a pass neither compiles nor bumps the
    CostBook generation (an observed tick that sees cell-table overflow
    boosts the buckets, a sanctioned bump, and the next tick retraces)."""
    unsettled = []
    for _ in range(tries):
        was = (book.total_compiles, book.generation)
        got = one_pass()
        if (book.total_compiles, book.generation) == was:
            break
        unsettled.append(got)
    return unsettled


def extent_of(config: dict) -> float:
    """The world's side: the count and the density give it."""
    w = config["world"]
    return max(64.0, float(np.sqrt(w["entities"] / w["density_per_unit2"])))


def build_world(config: dict, seed: int, **extra):
    from noahgameframe_tpu.game import build_benchmark_world

    w = config["world"]
    return build_benchmark_world(
        int(w["entities"]), extent=extent_of(config), seed=seed,
        combat=bool(w["combat"]),
        movement=bool(w["movement"]),
        attack_period_s=float(w["attack_period_s"]), **extra)


def reference_params(config: dict, world) -> reference.Params:
    """What the configuration FILE states, checked against the world
    where the program states the same thing."""
    w = config["world"]
    cfg = world.config
    for mine, theirs in ((extent_of(config), cfg.extent), (w["dt"], cfg.dt),
                         (w["aoe_radius"], cfg.aoe_radius),
                         (w["respawn_s"], cfg.respawn_s),
                         (w["regen_period_s"], cfg.regen_period_s)):
        if abs(float(mine) - float(theirs)) > 1e-6 * abs(float(mine)):
            raise RunFailed(f"the world runs {theirs} where the "
                            f"configuration file states {mine}")
    return reference.Params(
        dt=float(w["dt"]), extent=float(world.config.extent),
        aoe_radius=float(w["aoe_radius"]), respawn_s=float(w["respawn_s"]),
        movement=bool(w["movement"]), combat=bool(w["combat"]))


def combat_geometry(world, class_name: str = NPC):
    """The cell depths the program states for its neighbour engine."""
    combat = world.combat
    if combat is None:
        return None
    cap = int(world.kernel.store.capacity(class_name))
    return {"cell_size": combat.cell_size, "width": combat.width,
            "bucket": combat.resolved_bucket(cap),
            "att_bucket": combat.resolved_att_bucket(cap)}


def compiled_texts(dispatch) -> list:
    """The optimized HLO text of the programs behind one CostBook
    dispatcher (it keeps them in a closure)."""
    out = []
    for cell in getattr(dispatch, "__closure__", None) or ():
        v = cell.cell_contents
        if isinstance(v, dict):
            out += [c.as_text() for c in v.values() if hasattr(c, "as_text")]
    return out


def step_scopes(kernel) -> dict:
    """instruction -> op_name of the kernel's compiled step(s)."""
    from . import xplane

    out = {}
    for text in compiled_texts(kernel._jit_step):
        out.update(xplane.scopes_from_hlo_text(text))
    return out


def overflow_totals(kernel) -> dict:
    """Entities the cell tables dropped over every observed tick so far."""
    return {kind: kernel.counter_totals.get(f"aoi_{kind}_overflow_drops", 0)
            for kind in ("victim", "attacker")}


def sample_ticks(rng, first_tick: int, est_ticks: int, config: dict,
                 span: int) -> range:
    """`span` consecutive ticks inside the window, drawn from the seed,
    holding a tick on which the (unstaggered) regen heartbeat fires."""
    w = config["world"]
    regen_every = max(1, int(round(float(w["regen_period_s"])
                                   / float(w["dt"]))))
    last = first_tick + max(span, int(est_ticks * 0.8))
    fires = [t for t in range(first_tick + 1, last - 1)
             if t % regen_every == 0]
    if fires:
        centre = int(rng.choice(fires))
        return range(centre - 1, centre - 1 + span)
    start = first_tick + int(rng.integers(0, max(1, last - first_tick - span)))
    return range(start, start + span)


def hold_limits(run: Run, got: dict, limits: dict) -> None:
    for name, limit in limits.items():
        run.hold(name, got[name], limit)
