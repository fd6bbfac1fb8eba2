"""The serve edge's interest spans, in both serve engines.

`tests/test_serve_batch.py`'s scripted churn is driven through the
per-session engine and the batched one (`serve_batch=True`), a few
frames each under a profiler session, after the same frames unprofiled
have compiled every program.  Both engines must open the same three
`nf.interest.*` spans, each with its `cls` and `lane`, and
`nf_interest_builds_total` must move by exactly the number of
`nf.interest.dispatch` spans, lane by lane: the counter is incremented
where the span opens.  The HP diff's lane reads the position lane's
answer of the same flush: it opens `nf.interest.views` and no dispatch,
and `nf_interest_shared_total` counts it.
"""

import glob
from collections import Counter

import jax
import pytest
from test_serve_batch import Driver, build_role

SPANS = ("nf.interest.dispatch", "nf.interest.fetch", "nf.interest.views")
WARM, TRACED = 12, 12  # frames 12-23 hold every lane (HP at 17)


def _builds(role, counter="_interest_builds") -> Counter:
    return Counter({(labels["cls"], labels["lane"]): value
                    for _s, labels, value in getattr(role, counter).samples()})


@pytest.fixture(scope="module", params=[False, True],
                ids=["per_session", "batched"])
def traced(request, tmp_path_factory):
    from jax.profiler import ProfileData

    role, world, sent = build_role(serve_batch=request.param)
    driver = Driver(role, world)
    driver.run(WARM)
    before = _builds(role)
    shared_before = _builds(role, "_interest_shared")
    tmp = tmp_path_factory.mktemp("interest_spans")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        for f in range(WARM, WARM + TRACED):
            driver.frame(f)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(
        tmp / "plugins" / "profile" / "*" / "*.xplane.pb")))[-1]
    events = [(e.name, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:CPU")
              for line in plane.lines for e in line.events
              if e.name.startswith("nf.")]
    delta = _builds(role) - before
    shared = _builds(role, "_interest_shared") - shared_before
    role.shut()
    return {"batched": request.param, "events": events, "delta": delta,
            "shared": shared}


def test_both_engines_open_the_same_interest_spans(traced):
    names = {n for n, _ in traced["events"]}
    assert set(SPANS) <= names, sorted(names)
    for name, args in traced["events"]:
        if name in SPANS:
            assert {"cls", "lane"} <= set(args), (name, args)
    lanes = {(a["cls"], a["lane"]) for n, a in traced["events"]
             if n == "nf.interest.views"}
    # the position lane and the HP diff's property lane, in either engine
    assert ("NPC", "pos") in lanes and ("NPC", "HP") in lanes, lanes
    assert "nf.serve.gather" in names


def test_builds_counter_moves_with_the_dispatch_spans(traced):
    spans = Counter((a["cls"], a["lane"]) for n, a in traced["events"]
                    if n == "nf.interest.dispatch")
    assert sum(spans.values()) > 0
    assert spans == traced["delta"]
    # one fetch a dispatch: the host waits once a build
    fetches = Counter((a["cls"], a["lane"]) for n, a in traced["events"]
                      if n == "nf.interest.fetch")
    assert fetches == spans


def test_the_hp_lane_shares_the_position_answer(traced):
    dispatches = {(a["cls"], a["lane"]) for n, a in traced["events"]
                  if n == "nf.interest.dispatch"}
    assert ("NPC", "pos") in dispatches
    assert ("NPC", "HP") not in dispatches, dispatches
    assert traced["shared"][("NPC", "HP")] >= 1, traced["shared"]
