"""Host phases on the profiler's clock (``Tracer.phase``), the engine's
live tracer switch (``SAGINEngine.set_tracer``) and the cohort engine's
host-to-device byte counter (``CohortEngineStats.h2d_bytes``)."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fl import FLConfig
from repro.fl.cohort_engine import CohortEngine
from repro.fl.federation import FederationConfig
from repro.obs import NULL_TRACER, ObsConfig, Tracer
from repro.scenarios import Scenario
from repro.sim import Region, SAGINEngine

XR2 = Scenario(
    name="_phases_xr2", description="two-region phase test scenario",
    regions=(Region("indiana", 40.0, -86.0), Region("nairobi", -1.3, 36.8)),
    n_devices=4, n_air=1,
    federation=FederationConfig(policy="synchronous", every=1,
                                topology="star", half_life=600.0),
    horizon=6 * 3600.0)

CFG = FLConfig(dataset="mnist", n_rounds=2, n_devices=4, n_air=1,
               h_local=2, train_fraction=0.005, eval_size=64, seed=0,
               execution="batched")

#: the phases one batched region round opens inside ``region.step`` (its
#: launch) and inside ``region.settle`` (the read of its results)
LAUNCH_PHASES = ("region.orchestrate", "cohort.build", "cohort.dispatch",
                 "region.evaluate")
SETTLE_PHASES = ("cohort.wait",)
REGION_PHASES = LAUNCH_PHASES + SETTLE_PHASES


def _trajectory(eng):
    out = []
    for t in eng.trainers:
        r = t.result
        out.append((r.accuracies, r.losses, r.times, r.latencies,
                    [np.asarray(a) for a in
                     jax.tree_util.tree_leaves(t.params)]))
    return out


def _same(a, b):
    for (acc1, l1, t1, lat1, p1), (acc2, l2, t2, lat2, p2) in zip(a, b):
        assert acc1 == acc2 and t1 == t2 and lat1 == lat2
        np.testing.assert_array_equal(l1, l2)
        for x, y in zip(p1, p2):
            np.testing.assert_array_equal(x, y)


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Two rounds of the same engine three ways: the switch off (round 2
    under a CPU profiler capture), on, and flipped on for round 1 only."""
    prof = str(tmp_path_factory.mktemp("prof"))
    off = SAGINEngine(XR2, fl=CFG)
    off.run(1, final_merge=False)
    jax.profiler.start_trace(prof)
    try:
        off.run(1, final_merge=False)
    finally:
        jax.profiler.stop_trace()

    on = SAGINEngine(XR2, fl=CFG)
    tracer = Tracer(ObsConfig())
    assert on.set_tracer(tracer) is NULL_TRACER
    on.run(2, final_merge=False)

    flipped = SAGINEngine(XR2, fl=CFG)
    flipped.set_tracer(Tracer(ObsConfig()))
    flipped.run(1, final_merge=False)
    flipped.set_tracer(None)
    flipped.run(1, final_merge=False)
    return dict(off=off, on=on, flipped=flipped, tracer=tracer, prof=prof)


def test_profiler_capture_nests_wait_inside_region_step(runs):
    """Each region round's launch phases lie inside its ``region.step``
    and its ``cohort.wait`` inside its ``region.settle`` (the engine
    settles a region round after launching the next one)."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{runs['prof']}/**/*.xplane.pb", recursive=True)[0]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        {k: v for k, v in e.stats}) for e in line.events
                       if e.name.startswith("repro.")]
    def named(name):
        return [e for e in events if e[0] == f"repro.{name}"]

    def only_outer(inner, outers):
        for _, s, e, meta in inner:
            outer = [o for o in outers if o[1] <= s and e <= o[2]]
            assert len(outer) == 1
            # the spans of one region round share its region and round
            assert outer[0][3]["region"] == meta["region"]
            assert outer[0][3]["round"] == meta["round"] == 1

    steps, settles = named("region.step"), named("region.settle")
    assert len(steps) == len(settles) == len(XR2.regions)
    assert {m["region"] for *_, m in settles} == {r.name for r in
                                                  XR2.regions}
    for name in LAUNCH_PHASES:
        assert len(named(name)) == len(steps), name
        only_outer(named(name), steps)
    waits = named("cohort.wait")
    assert len(waits) == len(settles)
    only_outer(waits, settles)
    assert not any(st[1] <= s and e <= st[2]
                   for _, s, e, _ in waits for st in steps)
    merges = [e for e in events if e[0] == "repro.engine.merge"]
    assert [m[3]["round"] for m in merges] == [2]


def test_enabled_phases_count_every_region_round(runs):
    snap = runs["tracer"].metrics.snapshot("phase.")
    region_rounds = 2 * len(XR2.regions)
    for name in ("region.step", "region.settle") + REGION_PHASES:
        assert snap[f"phase.{name}.wall_s"]["count"] == region_rounds, name
    assert snap["phase.engine.merge.wall_s"]["count"] == 2
    # a region step's launch phases lie inside it, and the wait inside
    # the settle: each one's self time is the rest
    for outer, phases in (("region.step", LAUNCH_PHASES),
                          ("region.settle", SETTLE_PHASES)):
        wall = snap[f"phase.{outer}.wall_s"]["sum"]
        inner = sum(snap[f"phase.{n}.wall_s"]["sum"] for n in phases)
        assert snap[f"phase.{outer}.self_s"]["sum"] == pytest.approx(
            wall - inner, rel=1e-9, abs=1e-9), outer


def test_disabled_phase_records_nothing(runs):
    assert NULL_TRACER.metrics.snapshot() == {}
    assert runs["off"].tracer is NULL_TRACER
    off = Tracer(ObsConfig(enabled=False))
    with off.phase("cohort.build"):
        pass
    assert off.metrics.snapshot() == {} and off.spans == []


def test_phase_self_time_is_wall_less_children():
    tr = Tracer(ObsConfig())
    with tr.phase("outer"):
        for _ in range(3):
            with tr.phase("inner"):
                sum(range(1000))
    snap = tr.metrics.snapshot("phase.")
    outer = snap["phase.outer.wall_s"]["sum"]
    inner = snap["phase.inner.wall_s"]["sum"]
    assert snap["phase.inner.wall_s"]["count"] == 3
    assert snap["phase.inner.self_s"]["sum"] == inner
    assert snap["phase.outer.self_s"]["sum"] == pytest.approx(outer - inner)


def test_trajectories_bit_identical_with_switch_on_off_flipped(runs):
    base = _trajectory(runs["off"])
    _same(base, _trajectory(runs["on"]))
    _same(base, _trajectory(runs["flipped"]))
    # the flipped engine's tracer went with every component it reached
    eng = runs["flipped"]
    assert eng.tracer is NULL_TRACER
    assert all(t.tracer is NULL_TRACER
               and t.cohort_engine.tracer is NULL_TRACER
               for t in eng.trainers)


def test_h2d_bytes_equals_the_built_cohorts_nbytes():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1200, 6)).astype(np.float32)
    y = rng.integers(0, 3, size=1200).astype(np.int32)
    params = {"w": jnp.zeros((6, 3), jnp.float32)}

    def apply_fn(p, xb):
        return xb @ p["w"]

    engine = CohortEngine(apply_fn, batch_align=8, client_align=4)
    # many narrow clients and one wide: a layout of several buckets
    pools = [np.arange(k * 30, (k + 1) * 30) for k in range(12)]
    pools.append(np.arange(200, 1100))
    expect = 0
    for r in range(2):
        cohort = engine.build(x, y, pools, 2, np.random.default_rng(r),
                              max_batch=8)
        assert len(cohort.buckets) > 1
        weights = np.concatenate([cb.sizes for cb in cohort.buckets])
        expect += (sum(cb.xs.nbytes + cb.ys.nbytes + cb.mask.nbytes
                       for cb in cohort.buckets)
                   + weights.astype(np.float32).nbytes)
        params, _ = engine.round(params, cohort, 0.1, 1260)
        assert engine.stats.h2d_bytes == expect
