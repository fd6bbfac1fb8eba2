"""Promote measured A/B winners into bench_runs/tuning.json.

Chip captures under bench_runs/ time the 1M tick under the default
engines and under the opt-in variants (NF_RADIX=1/2 sort, NF_PALLAS=1 fold /
NF_PALLAS=2 fused table-free).  This
script compares whatever captures exist and records the winning flag
set, so later bench runs use the fastest measured configuration
instead of the defaults.  Env vars
still override (bench.py applies tuning via setdefault).

A variant must beat the baseline fused tick by >3% to be promoted —
within that margin the default (simpler) engine wins ties.
"""
from __future__ import annotations

import json
import os
import sys

RUNS = os.path.join(os.path.dirname(__file__), "..", "bench_runs")
MARGIN = 0.97


def tick_ms(name: str):
    path = os.path.join(RUNS, name)
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            d = json.load(f)
        if "error" in d:
            return None
        return float(d["detail"]["tick_ms"])
    except Exception:  # noqa: BLE001
        return None


def main() -> None:
    base = tick_ms("r06_tpu_1m.json")
    if base is None:
        base = tick_ms("r05_tpu_1m.json")
    if base is None:
        print("no baseline 1M capture; not writing tuning", file=sys.stderr)
        return
    tuning: dict = {}
    detail = {"baseline_tick_ms": base}

    radix_variants = [
        ("1", tick_ms("r05_tpu_1m_radix.json")),
        ("2", tick_ms("r05_tpu_1m_radix2.json")),
    ]
    best_flag, best_ms = None, base * MARGIN
    for flag, ms in radix_variants:
        detail[f"radix{flag}_tick_ms"] = ms
        if ms is not None and ms < best_ms:
            best_flag, best_ms = flag, ms
    if best_flag is not None:
        tuning["NF_RADIX"] = best_flag

    # NF_PALLAS tri-state election: 1 (fold-only kernel, plus its lane-
    # aligned variant) and 2 (fused table-free engine, r11) compete
    # against the same baseline; the fastest capture past the margin
    # wins.  Crash-immune like every rule here: a missing/errored
    # capture is None and simply doesn't compete (a 1M world may land in
    # the fused engine's VMEM-fallback regime, in which case its capture
    # ~equals baseline and loses the margin on its own).
    pallas_ms = tick_ms("r05_tpu_1m_pallas.json")
    pallas_al_ms = tick_ms("r05_tpu_1m_pallas_aligned.json")
    pallas2_ms = tick_ms("r11_tpu_1m_pallas2.json")
    detail["pallas_tick_ms"] = pallas_ms
    detail["pallas_aligned_tick_ms"] = pallas_al_ms
    detail["pallas2_tick_ms"] = pallas2_ms
    candidates = [
        ("1", pallas_ms),
        ("1", pallas_al_ms),
        ("2", pallas2_ms),
    ]
    best_mode, best_pallas = None, base * MARGIN
    for mode, ms in candidates:
        if ms is not None and ms < best_pallas:
            best_mode, best_pallas = mode, ms
    if best_mode is not None:
        tuning["NF_PALLAS"] = best_mode
        if (
            best_mode == "1"
            and best_pallas == pallas_al_ms
            and pallas_al_ms != pallas_ms
        ):
            tuning["NF_PALLAS_ALIGN"] = "128"

    # Verlet skin (ops/verlet.py): captures of the 1M tick at skins
    # 1/2/4; the fastest capture that beats the margin elects
    # NF_VERLET_SKIN.  A too-large skin loses through bucket inflation
    # (cell_size >= radius + skin), a too-small one through rebuild rate,
    # so this is a measured election, not a formula.
    best_skin, best_skin_ms = None, base * MARGIN
    for skin in ("1", "2", "4"):
        ms = tick_ms(f"r06_tpu_1m_verlet{skin}.json")
        detail[f"verlet{skin}_tick_ms"] = ms
        if ms is not None and ms < best_skin_ms:
            best_skin, best_skin_ms = skin, ms
    if best_skin is not None:
        tuning["NF_VERLET_SKIN"] = best_skin

    # Counting-sort binning (NF_BINNING, ops/stencil.py): the r07 A/B
    # pins its OWN baseline (a capture with NF_BINNING=sort in its
    # environment, immune to this file's previous output) — compare count against
    # that same-round capture when it exists, else the round baseline.
    count_base = tick_ms("r07_tpu_1m.json")
    if count_base is None:
        count_base = base
    count_ms = tick_ms("r07_tpu_1m_count.json")
    detail["binning_sort_tick_ms"] = count_base
    detail["binning_count_tick_ms"] = count_ms
    if count_ms is not None and count_ms < count_base * MARGIN:
        tuning["NF_BINNING"] = "count"

    # K-tick trains (NF_TICK_TRAIN, ISSUE 20): the r13 A/B captures the
    # 100k tick with --train 8 (tick_ms is already amortized PER TICK:
    # train wall / K), compared against the same-shape 100k baseline —
    # the 1M `base` above is the wrong shape for this election.  Trains
    # only pay off where the per-dispatch host round-trip is a real
    # fraction of the tick, so the promotion is measured, never assumed.
    # Crash-immune like every rule here: a missing/errored capture is
    # None and doesn't compete.
    train_base = tick_ms("r07_tpu_100k.json")
    if train_base is None:
        train_base = tick_ms("r05_tpu_100k_v2.json")
    train_ms = tick_ms("r13_tpu_100k_train8.json")
    detail["train_base_100k_tick_ms"] = train_base
    detail["train8_100k_tick_ms"] = train_ms
    if (train_base is not None and train_ms is not None
            and train_ms < train_base * MARGIN):
        tuning["NF_TICK_TRAIN"] = "8"

    out = {"env": tuning, "detail": detail}
    with open(os.path.join(RUNS, "tuning.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
