"""The engine's one-region-round lookahead (``SAGINEngine._run_fl``
launches a region round before it settles the previous one) and the
cohort engine's deferred loss readback (``ClientLosses``): the same
trajectories, bit for bit, as settling every launch at once."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fl import FLConfig
from repro.fl.cohort_engine import ClientLosses, CohortEngine
from repro.fl.federation import FederationConfig
from repro.fl.rounds import RegionTrainer
from repro.obs import ObsConfig, Tracer
from repro.resilience import FaultPlan, FaultSpec
from repro.scenarios import Scenario
from repro.sim import Region, SAGINEngine

REGIONS = (Region("indiana", 40.0, -86.0), Region("nairobi", -1.3, 36.8))

CFG = FLConfig(dataset="mnist", n_devices=4, n_air=1, h_local=2,
               train_fraction=0.005, eval_size=64, seed=0,
               execution="batched")


def _scenario(name, regions=REGIONS, **kw):
    return Scenario(name=f"_lookahead_{name}",
                    description="lookahead bit-identity scenario",
                    regions=regions, n_devices=4, n_air=1,
                    horizon=12 * 3600.0, **kw)


def _sync(every):
    return FederationConfig(policy="synchronous", every=every,
                            topology="star", half_life=600.0)


CASES = {
    "sync_every_1": (_scenario("sync1", federation=_sync(1)), (2,)),
    "sync_every_2": (_scenario("sync2", federation=_sync(2)), (2, 1)),
    "soft_async": (_scenario(
        "async", federation=FederationConfig(
            policy="soft_async", every=2, topology="ring",
            half_life=600.0)), (3,)),
    "chaos_quarantine": (_scenario(
        "chaos", federation=_sync(2), faults=FaultPlan(faults=(
            FaultSpec("nan_update", round=0, region=0, severity=2.0),
            FaultSpec("trainer_crash", round=1, region=1),
            FaultSpec("sat_loss", round=1, region=0),
            FaultSpec("straggler", round=2, region=1, severity=2.0),
            FaultSpec("isl_partition", round=2, region=0)))), (2, 1)),
    "one_region": (_scenario("one", regions=REGIONS[:1]), (2, 1)),
}


def _run(scenario, segments, tracer=None):
    """The engine over ``segments`` ``run`` calls (``final_merge=False``
    but the last), with the counter the lookahead should read: region
    rounds launched, less one per call and per merge more launches
    followed within it."""
    eng = SAGINEngine(scenario, fl=CFG)
    if tracer is not None:
        eng.set_tracer(tracer)
    inner = eng._policy_merge
    marks = []

    def marked(*args, **kw):
        # a merge sees settled trainers only
        assert all(not t._pending for t in eng.trainers)
        marks.append(len(eng.step_order))
        return inner(*args, **kw)

    eng._policy_merge = marked
    expect, order = 0, []
    for k, n in enumerate(segments):
        marks.clear()
        eng.run(n, final_merge=k == len(segments) - 1)
        order.append(list(eng.step_order))
        launched = len(eng.step_order)
        expect += launched - 1 - sum(m < launched for m in marks)
        assert all(not t._pending for t in eng.trainers)
    return eng, order, expect


def _settled_at_once(monkeypatch):
    """Every ``launch`` settled before it returns; the engine's own
    settles then find nothing to do."""
    launch, settle = RegionTrainer.launch, RegionTrainer.settle

    def launch_settled(self, r):
        rec = launch(self, r)
        settle(self)
        return rec

    monkeypatch.setattr(RegionTrainer, "launch", launch_settled)
    monkeypatch.setattr(RegionTrainer, "settle", lambda self: None)


def _fields(eng):
    out = []
    for t in eng.trainers:
        res = t.result
        out.append({f.name: repr(getattr(res, f.name))
                    for f in dataclasses.fields(res) if f.name != "config"})
    return out


def _param_bytes(eng):
    return [[np.asarray(a).tobytes()
             for a in jax.tree_util.tree_leaves(t.params)]
            for t in eng.trainers]


@pytest.mark.parametrize("case", sorted(CASES))
def test_lookahead_is_bit_identical_to_settling_at_once(case, monkeypatch):
    scenario, segments = CASES[case]
    # the lookahead run is traced too: the tracer only observes
    tracer = Tracer(ObsConfig())
    ahead, order, expect = _run(scenario, segments, tracer=tracer)
    assert ahead.overlapped_region_rounds == expect > 0
    assert tracer.metrics.snapshot("engine.") == {
        "engine.overlapped_region_rounds": expect}
    # every result entry is a Python float (or bool) once run returns:
    # nothing is left pending on the device
    for t in ahead.trainers:
        res = t.result
        n = len(res.times)
        assert n == sum(segments)
        for name in ("times", "accuracies", "losses", "latencies"):
            values = getattr(res, name)
            assert len(values) == n
            assert all(isinstance(v, float) for v in values), name
        assert all(isinstance(v, bool) for v in res.participated)
    # a region round's spans carry its own region and round, though
    # they are emitted after the next launch has set the context
    names = {r.name for r in scenario.regions}
    rounds = [s for s in tracer.spans if s.kind == "round"]
    assert len(rounds) == len(names) * sum(segments)
    assert all(s.name == f"{s.region}/r{s.round}" for s in rounds)
    assert all(s.region in names and s.round >= 0 for s in tracer.spans
               if s.kind in ("offload", "handover"))

    with monkeypatch.context() as m:
        _settled_at_once(m)
        at_once, order_at_once, _ = _run(scenario, segments)
    # nothing was left unsettled at a launch: no overlap to count
    assert at_once.overlapped_region_rounds == 0
    assert order == order_at_once
    assert _fields(ahead) == _fields(at_once)
    assert _param_bytes(ahead) == _param_bytes(at_once)
    assert repr(ahead.merges) == repr(at_once.merges)
    assert [[(r.wall_clock_start, r.realized_latency) for r in tr.records]
            for tr in ahead.traces] == [
        [(r.wall_clock_start, r.realized_latency) for r in tr.records]
        for tr in at_once.traces]
    if scenario.faults is not None:
        assert (ahead.fault_injector.state_dict()
                == at_once.fault_injector.state_dict())


# -- CohortEngine.round's losses ---------------------------------------------
def _mlp_apply(p, x):
    return x.reshape(x.shape[0], -1) @ p["w"] + p["b"]


def _round(monkeypatch, reads, **kw):
    """One multi-bucket round; ``reads`` counts device readbacks of its
    losses (``CohortEngine._scatter_losses`` calls).  Also returns the losses
    read eagerly in canonical order, and the number of clients."""
    rng = np.random.default_rng(1)
    x = rng.normal(size=(1200, 6)).astype(np.float32)
    y = rng.integers(0, 3, size=1200).astype(np.int32)
    params = {"w": jnp.zeros((6, 3), jnp.float32),
              "b": jnp.zeros(3, jnp.float32)}
    pools = [np.arange(k * 30, (k + 1) * 30) for k in range(12)]
    pools.append(np.arange(400, 1100))
    engine = CohortEngine(_mlp_apply, batch_align=8, client_align=4)
    cohort = engine.build(x, y, pools, 2, np.random.default_rng(2),
                          max_batch=8)
    assert len(cohort.buckets) > 1
    scatter = CohortEngine._scatter_losses

    def counted(plans, n_clients, loss_parts):
        reads.append(1)
        return scatter(plans, n_clients, loss_parts)

    monkeypatch.setattr(CohortEngine, "_scatter_losses",
                        staticmethod(counted))
    _, losses = engine.round(params, cohort, 0.1, 1110, **kw)
    # what the round returned before the readback was deferred
    eager = np.zeros(cohort.n_clients)
    for plan, part in zip(cohort.plans, losses._parts):
        eager[list(plan.members)] = np.asarray(part)[:len(plan.members)]
    return losses, [float(v) for v in eager], len(pools)


def test_round_losses_read_the_device_once_on_first_access(monkeypatch):
    reads = []
    losses, eager, n = _round(monkeypatch, reads)
    assert isinstance(losses, ClientLosses)
    assert len(losses) == n and bool(losses)
    assert jax.block_until_ready(losses) is losses
    assert reads == []
    values = list(losses)
    assert reads == [1]
    assert values == eager and all(type(v) is float for v in values)
    assert [losses[i] for i in range(n)] == eager
    assert losses[-1] == eager[-1] and losses[2:5] == eager[2:5]
    np.testing.assert_array_equal(np.asarray(losses), eager)
    assert losses == eager and eager == losses
    assert reads == [1]


@pytest.mark.parametrize("first", ["index", "asarray", "iter"])
def test_round_losses_any_first_access_reads_once(monkeypatch, first):
    reads = []
    losses, eager, _ = _round(monkeypatch, reads)
    if first == "index":
        assert losses[3] == eager[3]
    elif first == "asarray":
        np.testing.assert_array_equal(np.asarray(losses), eager)
    else:
        assert [v for v in losses] == eager
    assert list(losses) == eager and reads == [1]


def test_quarantined_round_losses_leave_out_the_dropped(monkeypatch):
    """A faulted round's losses are deferred too: ``len()`` already
    leaves out the quarantined clients, and the first read drops them."""
    reads = []
    losses, eager, n = _round(monkeypatch, reads, corrupt=(0, 2),
                              quarantine=True)
    assert isinstance(losses, ClientLosses)
    assert len(losses) == n - 2 and reads == []
    assert list(losses) == [v for i, v in enumerate(eager)
                            if i not in (0, 2)]
    assert all(np.isfinite(losses)) and reads == [1]
