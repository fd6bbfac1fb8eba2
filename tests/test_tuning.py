"""Measured-tuning promotion (scripts/decide_tuning.py): A/B captures
taken on the chip elect the engine flags bench.py runs with.  Wrong
promotion logic would silently pessimize (or break) the benchmark, so
the election rules are pinned here."""

import importlib.util
import json
import os
import sys


def _load(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "decide_tuning",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "decide_tuning.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.RUNS = str(tmp_path)
    return mod


def _w(tmp_path, name, ms=None, error=None):
    d = {"metric": "m", "detail": {"tick_ms": ms}}
    if error:
        d["error"] = error
    with open(os.path.join(str(tmp_path), name), "w") as f:
        json.dump(d, f)


def _run(mod, capsys):
    mod.main()
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]) if out and out[-1].startswith("{") else None


def test_no_baseline_writes_nothing(tmp_path, capsys):
    mod = _load(tmp_path)
    mod.main()
    assert not os.path.exists(os.path.join(str(tmp_path), "tuning.json"))


def test_winner_must_beat_margin(tmp_path, capsys):
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r05_tpu_1m_radix.json", 98.0)   # within 3%: tie -> default
    _w(tmp_path, "r05_tpu_1m_pallas.json", 96.0)  # beats margin
    got = _run(mod, capsys)
    assert got["env"] == {"NF_PALLAS": "1"}


def test_best_radix_digit_wins(tmp_path, capsys):
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r05_tpu_1m_radix.json", 80.0)
    _w(tmp_path, "r05_tpu_1m_radix2.json", 70.0)
    got = _run(mod, capsys)
    assert got["env"] == {"NF_RADIX": "2"}


def test_aligned_pallas_promotes_align_flag(tmp_path, capsys):
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r05_tpu_1m_pallas.json", 90.0)
    _w(tmp_path, "r05_tpu_1m_pallas_aligned.json", 60.0)
    got = _run(mod, capsys)
    assert got["env"]["NF_PALLAS"] == "1"
    assert got["env"]["NF_PALLAS_ALIGN"] == "128"


def test_fused_pallas2_elected_when_fastest(tmp_path, capsys):
    """The r11 tri-state: the fused engine's capture beats both the
    baseline margin and the fold-only variants -> NF_PALLAS=2, and no
    ALIGN flag rides along (it belongs to the fold-only kernel)."""
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r05_tpu_1m_pallas.json", 90.0)
    _w(tmp_path, "r05_tpu_1m_pallas_aligned.json", 85.0)
    _w(tmp_path, "r11_tpu_1m_pallas2.json", 70.0)
    got = _run(mod, capsys)
    assert got["env"]["NF_PALLAS"] == "2"
    assert "NF_PALLAS_ALIGN" not in got["env"]
    assert got["detail"]["pallas2_tick_ms"] == 70.0


def test_fused_pallas2_loses_to_faster_fold(tmp_path, capsys):
    """Fold-only still wins when it measures faster (e.g. a 1M world in
    the fused engine's VMEM-fallback regime measures ~baseline)."""
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r05_tpu_1m_pallas.json", 80.0)
    _w(tmp_path, "r11_tpu_1m_pallas2.json", 99.5)  # fallback regime
    got = _run(mod, capsys)
    assert got["env"]["NF_PALLAS"] == "1"


def test_fused_pallas2_crash_capture_not_elected(tmp_path, capsys):
    """Crash-immunity, same contract as the NF_BINNING rules: an error
    payload (however fast its phantom tick_ms) never elects the engine."""
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r11_tpu_1m_pallas2.json", 5.0, error="mosaic OOM")
    got = _run(mod, capsys)
    assert "NF_PALLAS" not in got["env"]


def test_fused_pallas2_within_margin_keeps_default(tmp_path, capsys):
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r11_tpu_1m_pallas2.json", 98.0)  # within 3%: tie -> off
    got = _run(mod, capsys)
    assert "NF_PALLAS" not in got["env"]


def test_verlet_skin_best_variant_wins(tmp_path, capsys):
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r06_tpu_1m_verlet1.json", 90.0)
    _w(tmp_path, "r06_tpu_1m_verlet2.json", 70.0)
    _w(tmp_path, "r06_tpu_1m_verlet4.json", 98.0)  # within margin: loses
    got = _run(mod, capsys)
    assert got["env"] == {"NF_VERLET_SKIN": "2"}


def test_r06_baseline_preferred_over_r05(tmp_path, capsys):
    """A fresh r06 baseline supersedes the archived r05 one — electing
    against a stale baseline would promote phantom wins."""
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 200.0)
    _w(tmp_path, "r06_tpu_1m.json", 100.0)
    _w(tmp_path, "r06_tpu_1m_verlet2.json", 150.0)  # beats r05, not r06
    got = _run(mod, capsys)
    assert got["env"] == {}
    assert got["detail"]["baseline_tick_ms"] == 100.0


def test_error_payloads_are_ignored(tmp_path, capsys):
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r05_tpu_1m_radix.json", 10.0, error="crashed")
    got = _run(mod, capsys)
    assert got["env"] == {}  # a 10x "win" from a crash payload is not real


def test_binning_count_elected_when_it_beats_margin(tmp_path, capsys):
    """The r07 A/B: count wins against its OWN pinned sort baseline."""
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r07_tpu_1m.json", 95.0)        # pinned NF_BINNING=sort
    _w(tmp_path, "r07_tpu_1m_count.json", 80.0)  # beats 95 * 0.97
    got = _run(mod, capsys)
    assert got["env"] == {"NF_BINNING": "count"}
    assert got["detail"]["binning_sort_tick_ms"] == 95.0
    assert got["detail"]["binning_count_tick_ms"] == 80.0


def test_binning_within_margin_keeps_sort(tmp_path, capsys):
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r07_tpu_1m.json", 95.0)
    _w(tmp_path, "r07_tpu_1m_count.json", 93.0)  # within 3%: tie -> default
    got = _run(mod, capsys)
    assert "NF_BINNING" not in got["env"]


def test_binning_compares_against_round_baseline_when_r07_sort_missing(
        tmp_path, capsys):
    """No pinned r07 sort capture: fall back to the round baseline rather
    than electing against nothing (a crashed sort run must not hand the
    election to count by default)."""
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r07_tpu_1m_count.json", 90.0)
    got = _run(mod, capsys)
    assert got["env"] == {"NF_BINNING": "count"}
    assert got["detail"]["binning_sort_tick_ms"] == 100.0


def test_binning_error_capture_not_elected(tmp_path, capsys):
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r07_tpu_1m_count.json", 10.0, error="oom")
    got = _run(mod, capsys)
    assert "NF_BINNING" not in got["env"]


def test_train8_elected_when_it_beats_100k_margin(tmp_path, capsys):
    """The r13 A/B: NF_TICK_TRAIN=8 wins against the same-shape 100k
    baseline (never the 1M one — wrong shape for the election)."""
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r07_tpu_100k.json", 20.0)
    _w(tmp_path, "r13_tpu_100k_train8.json", 15.0)  # beats 20 * 0.97
    got = _run(mod, capsys)
    assert got["env"] == {"NF_TICK_TRAIN": "8"}
    assert got["detail"]["train_base_100k_tick_ms"] == 20.0
    assert got["detail"]["train8_100k_tick_ms"] == 15.0


def test_train8_within_margin_keeps_single_ticks(tmp_path, capsys):
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r07_tpu_100k.json", 20.0)
    _w(tmp_path, "r13_tpu_100k_train8.json", 19.6)  # within 3%: tie -> off
    got = _run(mod, capsys)
    assert "NF_TICK_TRAIN" not in got["env"]


def test_train8_needs_a_100k_baseline(tmp_path, capsys):
    """No 100k capture at all: the train election does NOT fall back to
    the 1M baseline — a cross-shape 'win' would be phantom."""
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r13_tpu_100k_train8.json", 5.0)
    got = _run(mod, capsys)
    assert "NF_TICK_TRAIN" not in got["env"]


def test_train8_falls_back_to_v2_baseline(tmp_path, capsys):
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r05_tpu_100k_v2.json", 20.0)
    _w(tmp_path, "r13_tpu_100k_train8.json", 15.0)
    got = _run(mod, capsys)
    assert got["env"] == {"NF_TICK_TRAIN": "8"}


def test_train8_error_capture_not_elected(tmp_path, capsys):
    mod = _load(tmp_path)
    _w(tmp_path, "r05_tpu_1m.json", 100.0)
    _w(tmp_path, "r07_tpu_100k.json", 20.0)
    _w(tmp_path, "r13_tpu_100k_train8.json", 1.0, error="run cut short")
    got = _run(mod, capsys)
    assert "NF_TICK_TRAIN" not in got["env"]


def test_bench_applies_tuning_env(tmp_path, monkeypatch):
    """bench.py's loader: setdefault semantics (explicit env wins)."""
    runs = tmp_path / "bench_runs"
    runs.mkdir()
    (runs / "tuning.json").write_text(
        json.dumps({"env": {"NF_RADIX": "2", "NF_PALLAS": "1"}})
    )
    monkeypatch.setenv("NF_PALLAS", "0")  # operator override
    monkeypatch.delenv("NF_RADIX", raising=False)
    applied = {}
    with open(runs / "tuning.json") as f:
        for k, v in (json.load(f).get("env") or {}).items():
            if os.environ.setdefault(k, str(v)) == str(v):
                applied[k] = str(v)
    assert applied == {"NF_RADIX": "2"}
    assert os.environ["NF_PALLAS"] == "0"
