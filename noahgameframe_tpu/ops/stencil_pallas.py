"""Pallas TPU kernel for the combat stencil fold (split-table form).

The XLA path (game/combat.py's fold over ops/stencil.stencil_fold) walks
the 3x3 neighborhood as nine shifted slices of the padded attacker
table — nine HBM passes over the candidate planes plus whatever
intermediates XLA materializes for the [Kv, Ka] pairwise masks.  This
kernel makes the whole fold ONE pass: the grid iterates over cell rows;
each program holds the victim row's planes plus the three neighboring
attacker rows in VMEM (the same padded attacker planes bound three times
with block index maps y, y+1, y+2 — overlapping, read-only), and the
nine shifted pairwise reductions run on-core against resident data.

Layout: planes ride as [rows, F, K, W(+2)] so the wide W axis lands on
vector lanes and K on sublanes.  Victims are resident (no padding, one
mid-row ref); attackers are the scanned side (padded, three refs).
Outputs are [H, 3, Kv, W] (incoming, best-atk, best-row planes).

Semantics are identical to CombatModule's XLA fold (same stencil order,
same tie-breaks) — pinned by tests/test_stencil_pallas.py, which runs
this kernel in interpret mode on CPU against the XLA path.  On real TPU
hardware the kernel compiles natively.  Which of the two folds a tick
bakes in is `fold_engine`'s answer, below: a function of the platform
the tick is traced for, the grid's width and the two cell depths, and of
nothing a deployment sets (game/combat.py `resolved_engine`).

Victim feature planes (CombatModule's vic_feats; occupancy dropped):
    0: x   1: y   2: camp   3: scene   4: group
Attacker feature planes (att_feats):
    0: x   1: y   2: eff_atk   3: camp   4: scene   5: group   6: row
(no self-exclusion compare: self always shares its own camp, so the
no-friendly-fire mask rules self out — keep in sync with CombatModule)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# "no attacker" sentinel (2^24) — must match game.combat.NO_ROW; finite
# on purpose, an inf loop carry hangs the XLA CPU algebraic simplifier
_NO_ROW = 16777216.0

V_X, V_Y, V_CAMP, V_SCENE, V_GROUP = range(5)
N_VFEATS = 5
A_X, A_Y, A_ATK, A_CAMP, A_SCENE, A_GROUP, A_ROW = range(7)
N_AFEATS = 7

# nf-lint pallas-parity-pinned registry (lint/rules_pallas.py): every
# jit-reachable `pl.pallas_call` site in this module must be named here,
# keyed by its enclosing function, with the interpret-mode parity test
# that pins it bit-identical to the XLA reference fold.  Paths are
# repo-relative; the rule checks the file exists and actually exercises
# the named function in interpret mode.
PALLAS_PARITY_TESTS = {
    "combat_fold_pallas": "tests/test_stencil_pallas.py",
}


# A vreg is 8 sublanes by 128 lanes of 32 bits: the kernel's blocks put
# W on the lanes and K on the sublanes, each rounded up to a whole tile.
LANES = 128
SUBLANES = 8

# The least share of the kernel's lanes that has to carry cells for it
# to be chosen.  A grid program computes over W rounded up to whole
# lanes whatever W is, so the kernel's time goes by the lanes and the
# XLA fold's by the cells.  Placed by scripts/fold_probe.py on a v5e
# (PERF.md section 7, PR 29), fleets of 131,072 cells at 20/6: the
# kernel loses by 21, 13, 6.6 and 3.5 times at widths 4, 8, 16 and 32
# (fill 0.03 to 0.25) and wins by 1.6 at 64 (0.5), 3.3 at 125, 3.0 at
# 256 and 3.8 at 395; between 0.25 and 0.5 nothing is read, so the XLA
# fold keeps it.
FOLD_MIN_LANE_FILL = 0.5

# The scoped VMEM the kernel asks Mosaic for, and so the most
# `fold_vmem_bytes` may count for it to be chosen: twice Mosaic's own
# default of 16 MiB, a quarter of a v5e core's VMEM.  At the default
# the 1M world's own window depth (395 wide, 32/12) needs 15.4 of the
# 16 MiB (found by bisecting the limit, compile-only), which no count
# made from shapes can both admit and be safe beside.
FOLD_VMEM_BUDGET = 32 * 1024 * 1024

# [Kv, Ka, W] f32 temporaries counted live in one grid program.  What
# Mosaic needs beyond the pipeline's blocks, over one such temporary,
# read 9.7 to 24.7 over eleven shapes from 125 to 640 wide and 16/6 to
# 80/24 deep (compile-only, PR 29): this is the most seen and a margin,
# so the count errs high and the rule errs towards the XLA fold.
FOLD_LIVE_TEMPS = 26


def trace_platform() -> str:
    """The platform a tick traced now is compiled for: the default
    backend.  The one seam through which the fold's choice and the
    kernel's interpret flag see the device (a compile-only test that
    describes a chip it does not have steers this, in the test)."""
    return jax.default_backend()


def pallas_interpret() -> bool:
    """Whether the kernel below runs in Pallas interpret mode: exactly
    when the tick is traced for the CPU (tests, rehearsals).  Every
    other backend, known or not, reaches its compiler and fails there
    rather than quietly interpreting."""
    return trace_platform() == "cpu"


def _tile(n: int, tile: int) -> int:
    return -(-n // tile) * tile


def fold_lane_fill(width: int) -> float:
    """Share of the lanes a grid program computes over that carry cells:
    the victim row's W cells in W rounded up to whole vregs (395/512 at
    1M entities, 125/128 at 100k, 4/128 in a 16-unit room)."""
    return width / _tile(width, LANES)


def fold_vmem_bytes(width: int, kv: int, ka: int) -> int:
    """VMEM one grid program of `combat_fold_pallas` asks for, counted
    from its shapes: the victim block [5, Kv, W], three attacker blocks
    [7, Ka, W + 2] and the output block [3, Kv, W], each double-buffered
    by the pipeline, plus `FOLD_LIVE_TEMPS` [Kv, Ka, W] f32
    temporaries, K in whole sublanes and W in whole lanes.  An upper
    bound by every compile read so far, not Mosaic's own count."""
    kv8, ka8 = _tile(kv, SUBLANES), _tile(ka, SUBLANES)
    wv, wa = _tile(width, LANES), _tile(width + 2, LANES)
    blocks = (N_VFEATS + 3) * kv8 * wv + 3 * N_AFEATS * ka8 * wa
    return 4 * (2 * blocks + FOLD_LIVE_TEMPS * kv8 * ka8 * wv)


def fold_engine(platform: str, width: int, kv: int, ka: int) -> int:
    """Which fold a tick traced for `platform` over a `width`-wide grid
    with cells `kv` victims and `ka` attackers deep bakes in: 1 (the
    kernel below) or 0 (game/combat.py `combat_fold_xla`).  The two are
    equal bit for bit, so this is a choice of speed alone, and it is 1
    only when all of these hold:

    - the platform is a TPU: on the CPU the kernel is interpreted, a
      test device;
    - the kernel's lanes are filled (`fold_lane_fill`): a narrow grid
      leaves most of every vreg empty and the XLA fold wins it;
    - a grid program fits the VMEM the kernel asks for
      (`fold_vmem_bytes` against `FOLD_VMEM_BUDGET`): a bucket boost
      doubles both depths on a live tick, and a retrace into a kernel
      Mosaic refuses would take the server down."""
    if platform != "tpu":
        return 0
    if fold_lane_fill(width) < FOLD_MIN_LANE_FILL:
        return 0
    if fold_vmem_bytes(width, kv, ka) > FOLD_VMEM_BUDGET:
        return 0
    return 1


def _kernel(vic_ref, top_ref, mid_ref, bot_ref, out_ref, *, w: int, r2: float):
    kv = vic_ref.shape[2]
    ka = top_ref.shape[2]
    vx = vic_ref[0, V_X]
    vy = vic_ref[0, V_Y]
    vcamp = vic_ref[0, V_CAMP]
    vscene = vic_ref[0, V_SCENE]
    vgroup = vic_ref[0, V_GROUP]

    inc = jnp.zeros((kv, w), jnp.int32)
    besta = jnp.full((kv, w), -1.0, jnp.float32)
    bestr = jnp.full((kv, w), _NO_ROW, jnp.float32)

    # stencil order (dy, dx) ascending — identical to ops.stencil.STENCIL
    for ref in (top_ref, mid_ref, bot_ref):
        for dx in (0, 1, 2):
            cx = ref[0, A_X, :, dx : dx + w]
            cy = ref[0, A_Y, :, dx : dx + w]
            ca = ref[0, A_ATK, :, dx : dx + w]
            cc = ref[0, A_CAMP, :, dx : dx + w]
            csc = ref[0, A_SCENE, :, dx : dx + w]
            cg = ref[0, A_GROUP, :, dx : dx + w]
            cr = ref[0, A_ROW, :, dx : dx + w]
            ddx = vx[:, None, :] - cx[None, :, :]
            ddy = vy[:, None, :] - cy[None, :, :]
            cab = ca[None, :, :]
            ok = (
                (ddx * ddx + ddy * ddy <= r2)
                & (cab != 0.0)
                & (cc[None, :, :] != vcamp[:, None, :])
                & (csc[None, :, :] == vscene[:, None, :])
                & (cg[None, :, :] == vgroup[:, None, :])
            )
            inc = inc + jnp.sum(
                jnp.where(ok, cab, 0.0), axis=1
            ).astype(jnp.int32)
            sa = jnp.where(ok, cab, -1.0)
            sa = jnp.broadcast_to(sa, (kv, ka, w))
            m = jnp.max(sa, axis=1)
            first = jnp.min(
                jnp.where(sa >= m[:, None, :],
                          jnp.broadcast_to(cr[None, :, :], (kv, ka, w)),
                          _NO_ROW),
                axis=1,
            )
            # global min-row tie-break, identical to combat_fold_closure:
            # neutralize empty shifts (m == -1), then lexicographic
            # (max attack, min row) merge with `bestr` consumed once
            first = jnp.where(m >= 0.0, first, _NO_ROW)
            top = jnp.maximum(besta, m)
            bestr = jnp.minimum(
                jnp.where(m >= top, first, _NO_ROW),
                jnp.where(besta >= top, bestr, _NO_ROW),
            )
            besta = top

    # bitcast keeps the exact int32 damage total through the f32 plane
    # (a value cast would round above 2^24)
    out_ref[0, 0] = jax.lax.bitcast_convert_type(inc, jnp.float32)
    out_ref[0, 1] = besta
    out_ref[0, 2] = bestr


def combat_fold_pallas(vic_table, att_table, radius: float,
                       interpret: bool = False, raw: bool = False):
    """Fused 3x3 stencil fold: victims resident, attackers scanned.

    vic_table / att_table: ops.stencil.CellTable over the SAME grid
    geometry (vic carries 5 feature cols, att 7 — see module docstring).
    Returns (inc [H, W, Kv] int32, bestr [H, W, Kv] int32), matching the
    XLA fold's outputs before `pull`; with `raw`, the accumulators as
    the fold carries them (inc, besta f32, bestr f32 with _NO_ROW for
    none), for the second level to fold on into (game/combat.py
    `combat_fold_spill`)."""
    width = vic_table.width
    assert att_table.width == width and att_table.cell_size == vic_table.cell_size
    vic = _planes(vic_table.payload, width, vic_table.bucket, N_VFEATS,
                  pad=False)
    att = _planes(att_table.payload, width, att_table.bucket, N_AFEATS,
                  pad=True)
    h = w = width
    kv = vic.shape[2]
    ka = att.shape[2]
    vic_spec = pl.BlockSpec((1, N_VFEATS, kv, w), lambda y: (y, 0, 0, 0))
    att_spec = lambda off: pl.BlockSpec(  # noqa: E731
        (1, N_AFEATS, ka, w + 2), lambda y, o=off: (y + o, 0, 0, 0)
    )
    out = pl.pallas_call(
        functools.partial(_kernel, w=w, r2=float(radius) * float(radius)),
        grid=(h,),
        in_specs=[vic_spec, att_spec(0), att_spec(1), att_spec(2)],
        out_specs=pl.BlockSpec((1, 3, kv, w), lambda y: (y, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((h, 3, kv, w), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=FOLD_VMEM_BUDGET),
        interpret=interpret,
    )(vic, att, att, att)
    inc = jax.lax.bitcast_convert_type(
        out[:, 0].transpose(0, 2, 1), jnp.int32
    )  # [H, W, Kv]
    bestr_f = out[:, 2].transpose(0, 2, 1)
    if raw:
        k = vic_table.bucket
        besta = out[:, 1].transpose(0, 2, 1)
        return inc[..., :k], besta[..., :k], bestr_f[..., :k]
    # _NO_ROW (no attacker) -> -1; row ids are exact in f32 (< 2^24)
    bestr = jnp.where(bestr_f >= _NO_ROW, -1.0, bestr_f).astype(jnp.int32)
    if kv > vic_table.bucket:
        inc = inc[..., : vic_table.bucket]
        bestr = bestr[..., : vic_table.bucket]
    return inc, bestr


def _planes(payload: jnp.ndarray, width: int, bucket: int, n_feats: int,
            pad: bool) -> jnp.ndarray:
    """CellTable payload [(H*W*K)+1(+second level), F+1] -> feature
    planes of the base level.

    pad=True (attacker side) adds the one-cell zero border the shifted
    reads need: [H+2, F, K, W+2]; border slots are all-zero => eff_atk 0
    => masked, exactly like the XLA fold's zero padding.  pad=False
    (victim side, resident) gives [H, F, K, W].  K pads up to a multiple
    of 8 so the sublane axis stays tile-aligned on real TPUs (pad slots
    are all-zero; for victims the caller slices outputs back to K —
    zero-slot victims never map back through `pull`)."""
    h = w = width
    k = bucket
    v = payload[: h * w * k, :n_feats].reshape(h, w, k, n_feats)
    planes = v.transpose(0, 3, 2, 1)  # [H, F, K, W]
    k_pad = (-k) % 8
    if pad:
        return jnp.pad(planes, ((1, 1), (0, 0), (0, k_pad), (1, 1)))
    if k_pad:
        return jnp.pad(planes, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
    return planes
