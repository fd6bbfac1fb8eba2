"""Movement module: batched random-walk / seek steering on device.

Reference NPCs move by writing TargetX/TargetY and letting client-side
interpolation play out; server-side movement is property writes on a
heartbeat (Class/NPC.xml MoveType, NFCNPCRefreshModule).  Here movement is
a device phase over the whole class: seek the TargetPos at MOVE_SPEED, and
when within one step (or on first activation) pick a fresh uniform target
inside the scene extent from the per-tick PRNG stream — BASELINE config 2's
100k-NPC random walk is exactly this phase.

A walker may have a HOME (`set_homes`): upstream spawns an NPC at its
seed's position and brings it back there (NFCSceneAOIModule.cpp:82-160,
NFCNPCRefreshModule.cpp:44-130), so a world's density is what its seed
lists make it.  A homed walker's fresh target is drawn on the square of
half-width `leash` about its camp's centre, and a world seeded on camps
stays on them.

MOVE_SPEED follows the reference's convention of 10000 = 1 m/s
(Class/NPC.xml MOVE_SPEED Desc); MOVE_GATE (stun/root) zeroes movement.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.store import WorldState, with_class
from ..kernel.module import Module

SPEED_UNIT = 10000.0  # reference convention: MOVE_SPEED 10000 == 1 world unit/s


class MovementModule(Module):
    name = "MovementModule"

    def __init__(
        self,
        class_name: str = "NPC",
        extent: float = 512.0,
        order: int = 20,
        respect_gates: bool = True,
    ):
        super().__init__()
        self.class_name = class_name
        self.extent = float(extent)
        self.respect_gates = respect_gates
        # the seed table (set_homes): camp centres [C, 2], each row's
        # camp, one leash; None = every walker roams the whole extent
        self.centres: Optional[np.ndarray] = None
        self.home_rows: Optional[np.ndarray] = None
        self.leash = 0.0
        self.add_phase("wander", self._move_phase, order=order)

    @property
    def aux_key(self) -> str:
        return f"homes/{self.class_name}"

    def set_homes(self, centres, home_rows, leash: float) -> None:
        """Give the class's walkers homes: `centres` [C, 2], `home_rows`
        [capacity] the camp of each row (rows that hold nobody: any),
        `leash` the half-width of the square about the centre a walker's
        targets are drawn on.  Each row's own centre rides
        `WorldState.aux` (carried tick state, beside the Verlet cache;
        not a property: the class's row stays as its schema states it),
        so the phase reads it in row order and gathers nothing."""
        self.centres = np.asarray(centres, np.float32).reshape(-1, 2)
        self.home_rows = np.asarray(home_rows, np.int32)
        self.leash = float(leash)
        home_centres = self.centres[self.home_rows]
        self.kernel.register_aux(
            self.aux_key, lambda: jnp.asarray(home_centres))
        self.kernel.invalidate()  # the draw is baked into the trace

    def _move_phase(self, state: WorldState, ctx) -> WorldState:
        cname = self.class_name
        store = ctx.store
        if cname not in store.class_index:
            return state
        spec = store.spec(cname)
        if not (spec.has_property("Position") and spec.has_property("TargetPos")):
            return state
        cs = state.classes[cname]
        pos_col = spec.slot("Position").col
        tgt_col = spec.slot("TargetPos").col
        pos = cs.vec[:, pos_col, :2]  # [C, 2]
        tgt = cs.vec[:, tgt_col, :2]

        speed = cs.i32[:, spec.slot("MOVE_SPEED").col].astype(jnp.float32) / SPEED_UNIT
        if self.respect_gates and spec.has_property("MOVE_GATE"):
            gate = cs.i32[:, spec.slot("MOVE_GATE").col]
            speed = jnp.where(gate > 0, 0.0, speed)
        if spec.has_property("HP"):
            speed = jnp.where(cs.i32[:, spec.slot("HP").col] > 0, speed, 0.0)
        step = speed * ctx.dt  # [C]

        delta = tgt - pos
        dist = jnp.sqrt(jnp.sum(delta * delta, axis=-1) + 1e-12)
        arrived = dist <= jnp.maximum(step, 1e-6)
        # fresh uniform target for arrived walkers (dead/rooted ones have
        # step 0 and never "arrive" once a target is outstanding)
        if self.centres is None:
            new_tgt = jax.random.uniform(
                ctx.rng(), (pos.shape[0], 2), minval=0.0, maxval=self.extent
            )
        else:
            # about the walker's home.  Sums and products of the uniform
            # bits only, so that a chip and a host agree on every bit
            # (with a leash that is a power of two, whatever fuses)
            u = jax.random.uniform(ctx.rng(), (pos.shape[0], 2))
            new_tgt = jnp.clip(
                state.aux[self.aux_key] + self.leash * (2.0 * u - 1.0),
                0.0, self.extent,
            )
        tgt = jnp.where((arrived & cs.alive)[:, None], new_tgt, tgt)
        move = jnp.where(
            arrived[:, None], delta, delta / dist[:, None] * step[:, None]
        )
        new_pos = jnp.where(cs.alive[:, None], pos + move, pos)
        new_pos = jnp.clip(new_pos, 0.0, self.extent)

        vec = cs.vec.at[:, pos_col, :2].set(new_pos)
        vec = vec.at[:, tgt_col, :2].set(tgt)
        return with_class(state, cname, cs.replace(vec=vec))
