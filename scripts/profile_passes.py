"""Per-pass on-chip timing for the fused world tick.

docs/ROOFLINE.md puts the measured 1M tick ~25-30x above its bandwidth
roofline and names the global sort as prime suspect, the table-build
scatter grain second.  This script arbitrates: it times each pass of the
combat pipeline SEPARATELY on the live backend (full tick, XLA argsort,
radix argsort, pair-table build, stencil fold XLA/Pallas, payload
scatter, pull gather) and prints one JSON object, ready for
`bench_runs/`.

Each timed region issues `reps` async dispatches and blocks ONCE at the
end, so the per-dispatch host overhead amortizes over `reps`.

Usage: python scripts/profile_passes.py [--entities 1000000] [--reps 20]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--entities", type=int, default=1_000_000)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="cpu = smoke-test the harness off-chip")
    args = ap.parse_args()

    from noahgameframe_tpu.utils.platform import (
        force_cpu,
        init_compile_cache,
        require_tpu,
    )

    if args.platform == "cpu":
        force_cpu()
    else:
        require_tpu()
    init_compile_cache()

    import jax
    import jax.numpy as jnp

    from noahgameframe_tpu.game import build_benchmark_world
    from noahgameframe_tpu.ops.aoi import cell_of
    from noahgameframe_tpu.ops.stencil import (
        _bits_for,
        _build_pair_counting,
        _cell_counts,
        _counting_ranks,
        _radix_argsort,
        build_cell_table_pair,
        pull,
    )

    n = args.entities
    reps = args.reps
    world = build_benchmark_world(n, combat=True, seed=42)
    k = world.kernel
    combat = world.combat
    spec = k.store.spec("NPC")

    # every timed pass routes through the kernel's CostBook — the pass
    # list, per-pass compile wall time and compiled FLOPs/bytes land in
    # ONE ledger shared with bench.py's detail block (the fused tick is
    # already in it as "kernel.run")
    book = k.costbook

    def wrap(name, fn):
        return book.wrap(f"pass.{name}", fn, stage="profile")

    dev = jax.devices()[0]
    out: dict = {
        "metric": "pass_ms",
        "entities": n,
        "reps": reps,
        "device": str(dev),
        "platform": dev.platform,
        "passes": {},
    }

    def timed(name, fn, *a):
        """Median-free single measurement: warmup compile, then `reps`
        queued dispatches with one terminal block.  The accumulated
        JSON reprints after EVERY pass (last line wins) so a run cut
        short still leaves the passes it finished."""
        try:
            r = fn(*a)
            jax.block_until_ready(r)
            t0 = time.perf_counter()
            for _ in range(reps):
                r = fn(*a)
            jax.block_until_ready(r)
            ms = 1000 * (time.perf_counter() - t0) / reps
            out["passes"][name] = round(ms, 3)
            print(f"# {name}: {ms:.3f} ms", file=sys.stderr, flush=True)
        except Exception as e:  # noqa: BLE001 — record and keep going
            out["passes"][name] = f"ERROR {type(e).__name__}: {e}"
            print(f"# {name}: FAILED {e}", file=sys.stderr, flush=True)
        print(json.dumps(out), flush=True)

    # -- the whole fused tick (1 tick per dispatch) ---------------------------
    k.run_device(1)  # compile + host reconcile once
    def tick():
        k.run_device(1, reconcile=False)
        return k.state.classes["NPC"].i32
    timed("full_tick", tick)

    # -- geometry shared with CombatModule -----------------------------------
    # (read the class state AFTER the tick timing: the fused step donates
    # its input buffers, so references captured earlier are deleted)
    cs = k.state.classes["NPC"]
    pos = cs.vec[:, spec.slot("Position").col, :2]
    alive = cs.alive
    cap = alive.shape[0]  # bank capacity (pow2) >= n live entities
    cell_size, width = combat.cell_size, combat.width
    bucket = combat.resolved_bucket(cap)
    att_bucket = combat.resolved_att_bucket(cap)
    n_cells = width * width
    out["geometry"] = {
        "width": width, "cell_size": cell_size,
        "bucket": bucket, "att_bucket": att_bucket,
    }

    key = jnp.where(alive, cell_of(pos, cell_size, width), n_cells)
    key = jax.block_until_ready(jax.jit(lambda x: x)(key))

    timed("argsort_xla", wrap("argsort_xla", jnp.argsort), key)
    bits = _bits_for(n_cells)
    for b in (1, 2, 3):  # binary / 4-way / 8-way digit variants
        timed(
            f"argsort_radix_b{b}",
            wrap(f"argsort_radix_b{b}",
                 lambda kk, b=b: _radix_argsort(kk, bits, b)),
            key,
        )

    # -- pair-table build (argsort + rank + scatter), as combat runs it -------
    f32 = jnp.float32
    camp_f = cs.i32[:, spec.slot("Camp").col].astype(f32)
    scene_f = cs.i32[:, spec.slot("SceneID").col].astype(f32)
    group_f = cs.i32[:, spec.slot("GroupID").col].astype(f32)
    rows_f = jnp.arange(cap, dtype=f32)
    atk_f = cs.i32[:, spec.slot("ATK_VALUE").col].astype(f32)
    # attacker mask at the staggered duty the bench runs with
    interval = max(1, k.schedule.ticks_of(combat.attack_period_s))
    attacking = alive & ((jnp.arange(cap) % interval) == 0)
    vic_feats = jnp.stack([pos[:, 0], pos[:, 1], camp_f, scene_f, group_f], -1)
    att_feats = jnp.stack(
        [pos[:, 0], pos[:, 1], atk_f, camp_f, scene_f, group_f, rows_f], -1
    )

    # CellTable carries static geometry ints — passing one through jit
    # would trace them and break grid_view's reshape, so the jitted
    # pieces take raw arrays and rebuild tables against closed-over
    # static geometry.
    from noahgameframe_tpu.ops.stencil import CellTable

    def mk_vic(payload, slot_of):
        return CellTable(payload, slot_of, jnp.int32(0), width, cell_size, bucket)

    def mk_att(payload, slot_of):
        return CellTable(payload, slot_of, jnp.int32(0), width, cell_size,
                         att_bucket)

    build = wrap(
        "build_pair_tables",
        lambda p, al, vf, am, af: build_cell_table_pair(
            p, al, vf, am, af, cell_size, width, bucket, att_bucket
        ),
    )
    timed("build_pair_tables", build, pos, alive, vic_feats, attacking, att_feats)
    vic_table, att_table = jax.block_until_ready(
        build(pos, alive, vic_feats, attacking, att_feats)
    )

    # -- counting-sort binning passes (NF_BINNING=count, ops/stencil.py):
    # histogram, the K-round scatter-min rank selection, and the whole
    # sort-free pair build — timed directly against argsort_* and
    # build_pair_tables above so the A/B decomposes per pass -------------
    timed(
        "count_histogram",
        wrap("count_histogram", lambda kk: _cell_counts(kk, n_cells)),
        key,
    )
    timed(
        "count_rank_rounds",  # bucket rounds of scatter-min over [N]
        wrap("count_rank_rounds",
             lambda kk: _counting_ranks(kk, n_cells, bucket)),
        key,
    )
    timed(
        "count_build_pair",  # full sort-free twin of build_pair_tables
        wrap(
            "count_build_pair",
            lambda kk, al, vf, am, af: _build_pair_counting(
                vf, al, am, af, kk, n_cells, cell_size, width, bucket,
                att_bucket,
            ),
        ),
        key, alive, vic_feats, attacking, att_feats,
    )

    # -- Verlet cache passes (ops/verlet.py): what a rebuild tick, a reuse
    # vote, and the sort-free table replay each cost on this geometry ---------
    from noahgameframe_tpu.ops.verlet import (
        full_table as v_full,
        init_cache,
        refresh,
        sub_table as v_sub,
    )

    skin = 2.0  # representative; geometry stays the bench world's own
    fresh = init_cache(cap)  # all-False anchor: every refresh rebuilds
    reb = wrap(
        "verlet_rebuild",
        lambda c, p, al: refresh(c, p, al, cell_size, width, bucket, skin),
    )
    timed("verlet_rebuild", reb, fresh, pos, alive)
    warm, _ = jax.block_until_ready(reb(fresh, pos, alive))
    timed(
        "verlet_reuse",  # anchored at these exact positions: zero motion
        reb,             # same program — the cache vote decides at runtime
        warm, pos, alive,
    )
    timed(
        "verlet_cached_tables",  # the payload replay both tables run on a
        wrap(                    # reuse tick — the argsort-free build half
            "verlet_cached_tables",
            lambda c, al, vf, am, af: (
                v_full(c, vf, al, n_cells, cell_size, width, bucket),
                v_sub(c, am, af, n_cells, cell_size, width, att_bucket),
            ),
        ),
        warm, alive, vic_feats, attacking, att_feats,
    )

    # -- payload scatter / pull gather in isolation ---------------------------
    dump = n_cells * bucket
    occ = jnp.concatenate([vic_feats, jnp.ones((cap, 1), f32)], -1)
    timed(
        "payload_scatter",
        wrap(
            "payload_scatter",
            lambda so, ft: jnp.zeros((dump + 1, ft.shape[-1]),
                                     f32).at[so].set(ft),
        ),
        vic_table.slot_of, occ,
    )
    slot_res = jnp.zeros((width, width, bucket, 2), jnp.int32)
    timed(
        "pull_gather",
        wrap("pull_gather",
             lambda so, r: pull(mk_vic(vic_table.payload, so), r,
                                fill=(0, -1))),
        vic_table.slot_of, slot_res,
    )

    # -- the stencil fold, XLA and Pallas (the production fold functions —
    # combat_fold_xla is the single source of truth for layout/semantics) ----
    from noahgameframe_tpu.game.combat import combat_fold_xla

    def fold_xla(vt, at):
        return combat_fold_xla(vt, at, combat.radius)

    timed(
        "fold_xla",
        wrap("fold_xla",
             lambda vp, vs, ap, as_: fold_xla(mk_vic(vp, vs),
                                              mk_att(ap, as_))),
        vic_table.payload, vic_table.slot_of,
        att_table.payload, att_table.slot_of,
    )

    from noahgameframe_tpu.ops.stencil_pallas import pallas_interpret

    interp = pallas_interpret()
    try:
        from noahgameframe_tpu.ops.stencil_pallas import combat_fold_pallas

        pname = "fold_pallas" + ("_interpret" if interp else "")
        timed(
            pname,
            wrap(
                pname,
                lambda vp, vs, ap, as_: combat_fold_pallas(
                    mk_vic(vp, vs), mk_att(ap, as_), combat.radius,
                    interpret=interp,
                ),
            ),
            vic_table.payload, vic_table.slot_of,
            att_table.payload, att_table.slot_of,
        )
    except Exception as e:  # noqa: BLE001
        out["passes"]["fold_pallas"] = f"ERROR {type(e).__name__}: {e}"

    # -- fused table-free engine (NF_PALLAS=2, r11): the slots-only build
    # and the bank-gathering fused kernel, as separate CostBook entries so
    # the harvest attributes compile wall + FLOPs/bytes per variant from
    # the same ledger the split passes use ------------------------------------
    try:
        from noahgameframe_tpu.ops.stencil import (
            CellSlots,
            build_cell_slots_pair,
        )
        from noahgameframe_tpu.ops.stencil_pallas import (
            fused_fits_vmem,
            fused_neighborhood,
        )

        fits, need, budget = fused_fits_vmem(cap, width, bucket, att_bucket)
        out["pallas2_vmem"] = {
            "fits": bool(fits), "need_bytes": int(need),
            "budget_bytes": int(budget),
        }
        slots_pair = wrap(
            "pallas2_slots_pair",
            lambda p, al, am: build_cell_slots_pair(
                p, al, am, cell_size, width, bucket, att_bucket
            ),
        )
        timed("pallas2_slots_pair", slots_pair, pos, alive, attacking)
        vic_slots, att_slots = jax.block_until_ready(
            slots_pair(pos, alive, attacking)
        )
        bank = jnp.stack(
            [pos[:, 0], pos[:, 1], camp_f, scene_f, group_f, atk_f], -1
        )

        def mk_slots(so, kk):
            return CellSlots(so, jnp.int32(0), width, cell_size, kk)

        if fits:
            fname = "pallas2_fused" + ("_interpret" if interp else "")
            timed(
                fname,
                wrap(
                    fname,
                    lambda bk, vso, aso: fused_neighborhood(
                        bk, mk_slots(vso, bucket), mk_slots(aso, att_bucket),
                        combat.radius, interpret=interp,
                    ),
                ),
                bank, vic_slots.slot_of, att_slots.slot_of,
            )
        else:
            # the engine dispatch would downgrade here — record the
            # regime instead of timing a kernel production never runs
            out["passes"]["pallas2_fused"] = (
                f"VMEM_FALLBACK need={need} budget={budget}"
            )
    except Exception as e:  # noqa: BLE001
        out["passes"]["pallas2_fused"] = f"ERROR {type(e).__name__}: {e}"

    # compile/cost ledger for the whole pass list — same schema as the
    # /costbook route, so pass profiles and BENCH detail join on entry
    out["costbook"] = book.snapshot()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
