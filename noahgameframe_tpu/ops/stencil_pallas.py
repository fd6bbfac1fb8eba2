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
hardware the kernel compiles natively; enable with NF_PALLAS=1 (opt-in
until chip-time confirms a win over the already-fused XLA fold).

Victim feature planes (CombatModule's vic_feats; occupancy dropped):
    0: x   1: y   2: camp   3: scene   4: group
Attacker feature planes (att_feats):
    0: x   1: y   2: eff_atk   3: camp   4: scene   5: group   6: row
(no self-exclusion compare: self always shares its own camp, so the
no-friendly-fire mask rules self out — keep in sync with CombatModule)
"""

from __future__ import annotations

import functools
import logging
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# "no attacker" sentinel (2^24) — must match game.combat.NO_ROW; finite
# on purpose, an inf loop carry hangs the XLA CPU algebraic simplifier
_NO_ROW = 16777216.0

V_X, V_Y, V_CAMP, V_SCENE, V_GROUP = range(5)
N_VFEATS = 5
A_X, A_Y, A_ATK, A_CAMP, A_SCENE, A_GROUP, A_ROW = range(7)
N_AFEATS = 7

# SoA feature-bank columns of the FUSED engine (NF_PALLAS=2): one
# [N, 6] bank serves both sides of the fold — victims read the first
# five, attackers additionally read eff_atk, and the attacker "row"
# column of the split layout disappears (the gather index IS the row).
B_X, B_Y, B_CAMP, B_SCENE, B_GROUP, B_ATK = range(6)
N_BFEATS = 6

# nf-lint pallas-parity-pinned registry (lint/rules_pallas.py): every
# jit-reachable `pl.pallas_call` site in this module must be named here,
# keyed by its enclosing function, with the interpret-mode parity test
# that pins it bit-identical to the XLA reference fold.  Paths are
# repo-relative; the rule checks the file exists and actually exercises
# the named function in interpret mode.
PALLAS_PARITY_TESTS = {
    "combat_fold_pallas": "tests/test_stencil_pallas.py",
    "fused_neighborhood": "tests/test_stencil_pallas.py",
}


def pallas_interpret() -> bool:
    """Whether the kernels below run in Pallas interpret mode: exactly
    when the default backend is the CPU (tests, rehearsals).  Every
    other backend, known or not, reaches its compiler and fails there
    rather than quietly interpreting."""
    return jax.default_backend() == "cpu"


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


def combat_fold_pallas(vic_table, att_table, radius: float, interpret: bool = False):
    """Fused 3x3 stencil fold: victims resident, attackers scanned.

    vic_table / att_table: ops.stencil.CellTable over the SAME grid
    geometry (vic carries 5 feature cols, att 7 — see module docstring).
    Returns (inc [H, W, Kv] int32, bestr [H, W, Kv] int32), matching the
    XLA fold's outputs before `pull`.

    NF_PALLAS_ALIGN=<n> pads the lane (W) axis up to a multiple of n
    (128 = TPU lane width) with zero-occupancy ghost cells — masked out
    by the fold exactly like edge padding.  Insurance for grids whose W
    (395 at the 1M benchmark) Mosaic may reject or tile poorly; costs
    W_pad/W extra lanes, so it is opt-in until chip time ranks the two."""
    import os

    width = vic_table.width
    assert att_table.width == width and att_table.cell_size == vic_table.cell_size
    # nf-lint: disable=trace-safety -- sanctioned A/B knob: trace-time
    # read baked into the compilation; flipping needs a fresh jit cache
    align = int(os.environ.get("NF_PALLAS_ALIGN", "0") or 0)
    w_pad = ((-width) % align) if align > 1 else 0
    vic = _planes(vic_table.payload, width, vic_table.bucket, N_VFEATS,
                  pad=False, w_pad=w_pad)
    att = _planes(att_table.payload, width, att_table.bucket, N_AFEATS,
                  pad=True, w_pad=w_pad)
    h = width
    w = width + w_pad
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
        interpret=interpret,
    )(vic, att, att, att)
    inc = jax.lax.bitcast_convert_type(
        out[:, 0].transpose(0, 2, 1), jnp.int32
    )  # [H, W(+pad), Kv]
    bestr_f = out[:, 2].transpose(0, 2, 1)
    # _NO_ROW (no attacker) -> -1; row ids are exact in f32 (< 2^24)
    bestr = jnp.where(bestr_f >= _NO_ROW, -1.0, bestr_f).astype(jnp.int32)
    if w_pad:
        inc = inc[:, :width]
        bestr = bestr[:, :width]
    if kv > vic_table.bucket:
        inc = inc[..., : vic_table.bucket]
        bestr = bestr[..., : vic_table.bucket]
    return inc, bestr


# ---------------------------------------------------------------------------
# Fused neighborhood engine (NF_PALLAS=2)
#
# The split engine above still eats two `[n_cells*K+1, F+1]` payload
# scatters per frame (table_from_slots for victims AND attackers — the
# two biggest per-frame HBM materializations on the roofline).  The
# fused engine keeps only the slot ASSIGNMENT (ops.stencil.CellSlots —
# the counting-sort `slot_of` ranks) and inverts the data flow: the SoA
# feature bank rides into VMEM once per program, and each grid program
# GATHERS its victim row and the three neighboring attacker rows from
# the bank via per-cell row-id planes, then runs the nine shifted
# pairwise reductions on-core.  The AOI/interest occupancy count
# (ops/aoi.neighbor_counts semantics, ops/interest.scope_mask scoping)
# folds in the same VMEM residency — the padded payload tables are
# never written at all on this path.
# ---------------------------------------------------------------------------

_log = logging.getLogger(__name__)

# Per-core VMEM on current TPUs is ~16 MB; leave headroom for Mosaic's
# own scratch.  NF_PALLAS_VMEM_MB overrides (tests force it tiny to
# exercise the fallback arm without building a 1M-entity world).
FUSED_VMEM_MB_DEFAULT = 12.0
ENV_VMEM_MB = "NF_PALLAS_VMEM_MB"

_FUSED_FALLBACKS = {"total": 0}
_FUSED_LOGGED: set = set()


def fused_fallback_total() -> int:
    """Trace-time NF_PALLAS=2 -> split-path downgrades this process —
    scraped by telemetry as `nf_pallas_fallback_total`.  Counts per
    retrace (the engine choice is trace-time), not per tick."""
    return _FUSED_FALLBACKS["total"]


def note_fused_fallback(reason: str, need: int, budget: int) -> None:
    """Record a fused-path downgrade: bump the metric always, log once
    per distinct reason (a 1M-world retraces often; one line is signal,
    a thousand are noise)."""
    _FUSED_FALLBACKS["total"] += 1
    if reason not in _FUSED_LOGGED:
        _FUSED_LOGGED.add(reason)
        _log.warning(
            "NF_PALLAS=2 fused engine falling back to split tables: %s "
            "(tile footprint %d bytes > VMEM budget %d bytes)",
            reason, need, budget,
        )


def fused_vmem_bytes(
    n: int, width: int, vic_bucket: int, att_bucket: int, w_pad: int = 0
) -> int:
    """Host-side estimate of one fused program's VMEM residency: the
    whole feature bank + six bound idx tiles + the gathered per-band
    feature planes + the output tile, all f32/i32 (4 B), with the same
    sublane (K->8) and lane (bank->128) padding the wrapper applies.
    Deliberately counts the bank once and temporaries generously — the
    check gates a fallback, so overestimating is the safe direction."""
    w = width + w_pad + 2
    lanes = (n + 1) + ((-(n + 1)) % 128)
    kv = vic_bucket + ((-vic_bucket) % 8)
    ka = att_bucket + ((-att_bucket) % 8)
    bank = N_BFEATS * lanes * 4
    idx_tiles = 3 * (kv + ka) * w * 4
    # per band: 6 gathered victim-candidate planes (x/y/scene/group/
    # occ/row) and 7 attacker planes (those + eff_atk/camp, minus occ)
    gathered = 3 * (6 * kv + 7 * ka) * w * 4
    out = 4 * kv * (w - 2) * 4
    return bank + idx_tiles + gathered + out


def fused_fits_vmem(
    n: int, width: int, vic_bucket: int, att_bucket: int, w_pad: int = 0
) -> Tuple[bool, int, int]:
    """(fits, need_bytes, budget_bytes) for the fused engine at this
    static geometry.  Called at trace time from the engine dispatch in
    game/combat.py; oversize worlds downgrade to the split path instead
    of letting Mosaic (or the interpreter) blow VMEM."""
    import os

    # nf-lint: disable=trace-safety -- sanctioned sizing knob: read at
    # trace time to pick the engine baked into this compilation; tests
    # shrink it to force the fallback arm deterministically
    budget_mb = float(os.environ.get(ENV_VMEM_MB, "") or FUSED_VMEM_MB_DEFAULT)
    budget = int(budget_mb * 1024 * 1024)
    need = fused_vmem_bytes(n, width, vic_bucket, att_bucket, w_pad)
    return need <= budget, need, budget


def _idx_planes(
    slot_of: jnp.ndarray, n: int, width: int, bucket: int,
    height: int, w_pad: int,
) -> jnp.ndarray:
    """CellSlots.slot_of [N] -> bordered row-id planes [H+2, K8, W+2+pad]
    (i32).  Slot s holds the row scattered there by the slot assignment,
    or the sentinel `n` when empty — the bank carries an all-zero row at
    index n, so sentinel gathers read zero features exactly like the
    split path's zero payload slots.  Borders and K/W alignment pads are
    sentinel too (the split path pads payload with zeros; same mask
    outcome).  Placed slots are unique by construction; only the dump
    slot sees duplicate scatters, and it is re-pinned to the sentinel
    afterwards so the planes stay deterministic."""
    dump = height * width * bucket
    rows = jnp.arange(slot_of.shape[0], dtype=jnp.int32)
    idx = (
        jnp.full((dump + 1,), n, jnp.int32)
        .at[slot_of].set(rows)
        .at[dump].set(n)
    )
    planes = idx[:dump].reshape(height, width, bucket).transpose(0, 2, 1)
    k_pad = (-bucket) % 8
    return jnp.pad(
        planes, ((1, 1), (0, k_pad), (1, 1 + w_pad)), constant_values=n
    )


def _fused_kernel(
    bank_ref, vt_ref, vm_ref, vb_ref, at_ref, am_ref, ab_ref, out_ref,
    *, w: int, r2: float, n: int,
):
    """One grid program = one cell row: gather the resident victims and
    the three neighboring bands from the bank, fold combat AND the AOI
    occupancy count in one residency.

    Combat math is line-for-line the split `_kernel` above (same stencil
    order, same lexicographic tie-break with `bestr` consumed once) with
    the payload reads replaced by bank gathers; the attacker row id is
    the gather index itself.  Sentinel gathers (empty slots, borders)
    read the all-zero bank row => eff_atk 0 => masked, identical to the
    split path's zero padding.  Empty-shift neutralization (m == -1)
    also absorbs the one place sentinels differ — their row id is n, not
    0, but `first` is discarded whenever no real attacker set m."""
    from .interest import scope_mask

    kv = vt_ref.shape[1]
    ka = at_ref.shape[1]
    bank = bank_ref[:]
    vi = vm_ref[0][:, 1 : 1 + w]  # [kv, w] resident victim row ids
    vx = bank[B_X][vi]
    vy = bank[B_Y][vi]
    vcamp = bank[B_CAMP][vi]
    vscene = bank[B_SCENE][vi]
    vgroup = bank[B_GROUP][vi]
    vrow = vi.astype(jnp.float32)

    inc = jnp.zeros((kv, w), jnp.int32)
    besta = jnp.full((kv, w), -1.0, jnp.float32)
    bestr = jnp.full((kv, w), _NO_ROW, jnp.float32)
    nbr = jnp.zeros((kv, w), jnp.int32)

    # stencil order (dy, dx) ascending — identical to ops.stencil.STENCIL
    for a_ref, v_ref in (
        (at_ref, vt_ref), (am_ref, vm_ref), (ab_ref, vb_ref)
    ):
        ai = a_ref[0]  # [ka, w+2] attacker row ids for this band
        ax = bank[B_X][ai]
        ay = bank[B_Y][ai]
        aa = bank[B_ATK][ai]
        ac = bank[B_CAMP][ai]
        asc = bank[B_SCENE][ai]
        ag = bank[B_GROUP][ai]
        ar = ai.astype(jnp.float32)
        bi = v_ref[0]  # [kv, w+2] AOI candidates: the full population
        bx = bank[B_X][bi]
        by = bank[B_Y][bi]
        bsc = bank[B_SCENE][bi]
        bg = bank[B_GROUP][bi]
        bocc = bi < n
        br = bi.astype(jnp.float32)
        for dx in (0, 1, 2):
            cx = ax[:, dx : dx + w]
            cy = ay[:, dx : dx + w]
            ca = aa[:, dx : dx + w]
            cc = ac[:, dx : dx + w]
            csc = asc[:, dx : dx + w]
            cg = ag[:, dx : dx + w]
            cr = ar[:, dx : dx + w]
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
            first = jnp.where(m >= 0.0, first, _NO_ROW)
            top = jnp.maximum(besta, m)
            bestr = jnp.minimum(
                jnp.where(m >= top, first, _NO_ROW),
                jnp.where(besta >= top, bestr, _NO_ROW),
            )
            besta = top

            # AOI/interest occupancy in the same residency: occupied,
            # within radius, interest-scoped, not self (row compare —
            # combat needs no self-exclusion, camp does it; here self is
            # always in scope of itself and must be ruled out)
            nx = bx[:, dx : dx + w]
            ny = by[:, dx : dx + w]
            nsc = bsc[:, dx : dx + w]
            ng = bg[:, dx : dx + w]
            nocc = bocc[:, dx : dx + w]
            nrw = br[:, dx : dx + w]
            ndx = vx[:, None, :] - nx[None, :, :]
            ndy = vy[:, None, :] - ny[None, :, :]
            near = (
                (ndx * ndx + ndy * ndy <= r2)
                & nocc[None, :, :]
                & scope_mask(
                    nsc[None, :, :], ng[None, :, :],
                    vscene[:, None, :], vgroup[:, None, :],
                )
                & (nrw[None, :, :] != vrow[:, None, :])
            )
            nbr = nbr + jnp.sum(near, axis=1).astype(jnp.int32)

    out_ref[0, 0] = jax.lax.bitcast_convert_type(inc, jnp.float32)
    out_ref[0, 1] = besta
    out_ref[0, 2] = bestr
    out_ref[0, 3] = jax.lax.bitcast_convert_type(nbr, jnp.float32)


def fused_neighborhood(
    bank: jnp.ndarray,
    vic_slots,
    att_slots,
    radius: float,
    interpret: bool = False,
):
    """Fused table-free neighborhood fold (NF_PALLAS=2).

    bank: [N, 6] f32 SoA feature bank, columns B_X..B_ATK (victims read
    the first five, attackers all six; the attacker row id is implicit —
    it IS the bank row).  vic_slots / att_slots: ops.stencil.CellSlots
    over the same grid geometry (typically the full population and the
    attacking subset of the same frame).

    Returns (inc [H, W, Kv] i32, bestr [H, W, Kv] i32, nbr [H, W, Kv]
    i32): incoming damage and best-attacker row bit-identical to
    combat_fold_pallas / the XLA fold on equal slot assignments, plus
    the AOI/interest occupancy count per victim (scope per
    ops.interest.scope_mask, self excluded) — the split path would need
    a whole second stencil pass (ops.aoi.neighbor_counts) for that.

    NF_PALLAS_ALIGN pads the lane axis exactly like combat_fold_pallas
    (sentinel ghost cells instead of zero payload)."""
    import os

    width = vic_slots.width
    height = vic_slots.height if vic_slots.height > 0 else width
    assert att_slots.width == width
    assert att_slots.cell_size == vic_slots.cell_size
    n = bank.shape[0]
    # nf-lint: disable=trace-safety -- sanctioned A/B knob: trace-time
    # read baked into the compilation; flipping needs a fresh jit cache
    align = int(os.environ.get("NF_PALLAS_ALIGN", "0") or 0)
    w_pad = ((-width) % align) if align > 1 else 0
    w = width + w_pad
    lane_pad = (-(n + 1)) % 128
    # sentinel zero row at index n, then lane-align; pad rows are never
    # gathered (all plane ids are <= n)
    bank_t = jnp.pad(
        bank.astype(jnp.float32), ((0, 1 + lane_pad), (0, 0))
    ).T  # [6, NP]
    vic = _idx_planes(
        vic_slots.slot_of, n, width, vic_slots.bucket, height, w_pad
    )
    att = _idx_planes(
        att_slots.slot_of, n, width, att_slots.bucket, height, w_pad
    )
    kv = vic.shape[1]
    ka = att.shape[1]
    bank_spec = pl.BlockSpec(bank_t.shape, lambda y: (0, 0))
    band = lambda kk, off: pl.BlockSpec(  # noqa: E731
        (1, kk, w + 2), lambda y, o=off: (y + o, 0, 0)
    )
    out = pl.pallas_call(
        functools.partial(
            _fused_kernel, w=w, r2=float(radius) * float(radius), n=n
        ),
        grid=(height,),
        in_specs=[
            bank_spec,
            band(kv, 0), band(kv, 1), band(kv, 2),
            band(ka, 0), band(ka, 1), band(ka, 2),
        ],
        out_specs=pl.BlockSpec((1, 4, kv, w), lambda y: (y, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((height, 4, kv, w), jnp.float32),
        interpret=interpret,
    )(bank_t, vic, vic, vic, att, att, att)
    inc = jax.lax.bitcast_convert_type(
        out[:, 0].transpose(0, 2, 1), jnp.int32
    )  # [H, W(+pad), Kv]
    bestr_f = out[:, 2].transpose(0, 2, 1)
    bestr = jnp.where(bestr_f >= _NO_ROW, -1.0, bestr_f).astype(jnp.int32)
    nbr = jax.lax.bitcast_convert_type(
        out[:, 3].transpose(0, 2, 1), jnp.int32
    )
    if w_pad:
        inc = inc[:, :width]
        bestr = bestr[:, :width]
        nbr = nbr[:, :width]
    if kv > vic_slots.bucket:
        inc = inc[..., : vic_slots.bucket]
        bestr = bestr[..., : vic_slots.bucket]
        nbr = nbr[..., : vic_slots.bucket]
    return inc, bestr, nbr


def _planes(payload: jnp.ndarray, width: int, bucket: int, n_feats: int,
            pad: bool, w_pad: int = 0) -> jnp.ndarray:
    """CellTable payload [(H*W*K)+1, F+1] -> feature planes.

    pad=True (attacker side) adds the one-cell zero border the shifted
    reads need: [H+2, F, K, W+2]; border slots are all-zero => eff_atk 0
    => masked, exactly like the XLA fold's zero padding.  pad=False
    (victim side, resident) gives [H, F, K, W].  K pads up to a multiple
    of 8 so the sublane axis stays tile-aligned on real TPUs (pad slots
    are all-zero; for victims the caller slices outputs back to K —
    zero-slot victims never map back through `pull`).  w_pad appends
    zero-occupancy ghost cell columns for lane alignment (see
    combat_fold_pallas)."""
    h = w = width
    k = bucket
    v = payload[:-1, :n_feats].reshape(h, w, k, n_feats)
    planes = v.transpose(0, 3, 2, 1)  # [H, F, K, W]
    k_pad = (-k) % 8
    if pad:
        return jnp.pad(planes, ((1, 1), (0, 0), (0, k_pad), (1, 1 + w_pad)))
    if k_pad or w_pad:
        return jnp.pad(planes, ((0, 0), (0, 0), (0, k_pad), (0, w_pad)))
    return planes
