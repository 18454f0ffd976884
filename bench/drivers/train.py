"""Training traffic: a closed loop of back-to-back global federated rounds.

One global round is ``SAGINEngine.run(1, final_merge=False)``: every
region steps once (orchestration, cohort build, bucketed local update,
eq.-(13) aggregate, evaluation) and, at the cadence, the cross-region
merge.  It ends on ``block_until_ready`` of every region's model.

Set-up builds the engine from the cell's data, installs the seed's
weights and row order, compiles every bucket layout the cell lists, and
drives the engine through its first ``checked_rounds`` rounds while
recording what they consumed and produced.  The window continues the same
engine.  Once the window has closed and the program's state is freed, the
plain reference replays the compared rounds (``FIRST_COMPARED`` on) and
the gaps decide ``correct``.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from bench.harness import core, inputs
from bench.harness.readings import Readings, Spans, shadowed
from bench.harness.scenario import build_fl_config, build_scenario
from bench.reference import cohort, compare, convnet

UNITS = {"round_s": "s"}

#: The first global round whose losses and models are compared.  The
#: cells' VGG-11 has no normalisation and overshoots in its first round
#: (step losses ~4-9, then 30-45, then ~3 at lr 0.01), so two sound
#: programs that round differently part there by up to half a loss; round
#: 1 is held to its cohorts, its hand-off and finite losses alone.
FIRST_COMPARED = 2


def _host(tree):
    return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))


def _fake_cohort(layout, sample_shape):
    """A zero-mask cohort of the given bucket layout: it drives the
    program's own round through exactly the programs that layout runs,
    and trains no client."""
    from repro.data.pipeline import BucketedCohort, BucketPlan, CohortBatch
    buckets, plans = [], []
    for c, h, b in layout:
        buckets.append(CohortBatch(
            xs=np.zeros((c, h, b) + sample_shape, np.float32),
            ys=np.zeros((c, h, b), np.int32),
            mask=np.zeros((c, h, b), np.float32),
            sizes=np.zeros(c, np.int64)))
        plans.append(BucketPlan(b_bucket=b, c_bucket=c, members=()))
    return BucketedCohort(buckets=buckets, plans=plans,
                          sizes=np.zeros(0, np.int64))


class Recorder:
    """What the checked rounds consume and produce.

    Per region round: its cohort, its clients' losses, the region's model
    before and after, and the trained models of one client per bucket,
    drawn from the seed.  Per merge: every region's clock as the merge
    opens.  Per region: its rows, as set-up left them, and its model after
    the last checked round."""

    def __init__(self, engine, seed: int):
        self.rounds = []      # [{"round", "region", "lr", "losses",
        #                         "start", "params", "clients", "buckets"}]
        self.merge_clocks = {}    # barrier round -> [clock per region]
        self.rows = [(t.ds.x_train, t.ds.y_train) for t in engine.trainers]
        self.global_round = 0     # the global round being driven
        self.final = []           # per region, leaves after the rounds
        self._draw = np.random.default_rng([seed, 1])
        self._open = None

    def wrap(self, region: int, engine):
        inner = engine.round

        def recording(params, cohort, lr, total, **kw):
            start = jax.tree_util.tree_leaves(_host(params))
            n_real = [len(p.members) for p in cohort.plans]
            self._open = dict(n_real=n_real, bucket=0, clients={})
            try:
                new, losses = inner(params, cohort, lr, total, **kw)
            finally:
                opened, self._open = self._open, None
            self.rounds.append(dict(
                round=self.global_round, region=region, lr=float(lr),
                losses=list(losses),
                start=start, clients=opened["clients"],
                params=jax.tree_util.tree_leaves(_host(new)),
                buckets=[(cb.xs, cb.ys, cb.mask, cb.sizes, n)
                         for cb, n in zip(cohort.buckets, n_real)]))
            return new, losses

        engine.round = recording
        return lambda: engine.__dict__.pop("round", None)

    def wrap_local_update(self):
        """Keep one trained client model of each bucket the program's
        local update returns, before the aggregate consumes them."""
        from repro.fl import cohort_engine
        inner = cohort_engine.cohort_local_update

        def keeping(apply_fn, params, xs, ys, mask, lr):
            stacked, losses = inner(apply_fn, params, xs, ys, mask, lr)
            rnd = self._open
            # inside a traced program (the sharded path) there is no
            # model to keep yet
            if rnd is not None and not isinstance(losses, jax.core.Tracer):
                b = rnd["bucket"]
                rnd["bucket"] += 1
                if rnd["n_real"][b] > 0:
                    slot = int(self._draw.integers(rnd["n_real"][b]))
                    rnd["clients"][(b, slot)] = [
                        np.asarray(a) for a in jax.device_get(
                            [leaf[slot] for leaf in
                             jax.tree_util.tree_leaves(stacked)])]
            return stacked, losses

        cohort_engine.cohort_local_update = keeping

        def undo():
            cohort_engine.cohort_local_update = inner
        return undo

    def wrap_merge(self, engine):
        inner = engine._policy_merge

        def clocked(policy, barrier_round, *args, **kw):
            self.merge_clocks[int(barrier_round)] = [
                float(t.wall_clock) for t in engine.trainers]
            return inner(policy, barrier_round, *args, **kw)

        engine._policy_merge = clocked
        return lambda: engine.__dict__.pop("_policy_merge", None)


def set_up(cell, config, seed):
    """The engine with the seed's weights and rows, every listed layout
    compiled, and nothing yet run."""
    from repro.sim import SAGINEngine
    engine = SAGINEngine(build_scenario(cell["scenario"]),
                         fl=build_fl_config(cell, config))
    rng = np.random.default_rng(seed)
    for t in engine.trainers:
        t.ds.x_train, t.ds.y_train = inputs.shuffle_rows(
            rng, t.ds.x_train, t.ds.y_train)
    w0 = inputs.weights(config, seed)
    for t in engine.trainers:
        t.params = inputs.to_program(w0, t.params)
    sample_shape = tuple(config["input_shape"])
    for layout in cell.get("warm_layouts", ()):
        for t in engine.trainers[:1]:
            scratch = jax.tree_util.tree_map(lambda a: a.copy(), t.params)
            jax.block_until_ready(t.cohort_engine.round(
                scratch, _fake_cohort(layout, sample_shape),
                config["lr"], 1))
    return engine


def checked_rounds(engine, n: int, seed: int) -> Recorder:
    rec = Recorder(engine, seed)
    undo = [rec.wrap(i, t.cohort_engine)
            for i, t in enumerate(engine.trainers)]
    undo += [rec.wrap_local_update(), rec.wrap_merge(engine)]
    with shadowed(undo):
        for k in range(1, n + 1):
            rec.global_round = k
            engine.run(1, final_merge=False)
            jax.block_until_ready([t.params for t in engine.trainers])
    rec.final = [jax.tree_util.tree_leaves(_host(t.params))
                 for t in engine.trainers]
    return rec


def _one_round(engine):
    engine.run(1, final_merge=False)
    jax.block_until_ready([t.params for t in engine.trainers])


def _stats(engine):
    real = sum(t.cohort_engine.stats.real_elements for t in engine.trainers)
    layout = sum(t.cohort_engine.stats.layout_elements
                 for t in engine.trainers)
    return real, layout


def _merge_every(cell) -> int:
    fed = cell["scenario"].get("federation") or {}
    return int(fed.get("every") or 1)


def window(engine, seconds: float, every: int, done: int):
    """Rounds until ``seconds`` have passed and a merge period has closed,
    ``done`` rounds having run before: (rounds, elapsed s, each round's
    s).  Ending on a merge keeps the mix of rounds the same in every
    run."""
    t0 = time.perf_counter()
    each = []
    while True:
        t = time.perf_counter()
        _one_round(engine)
        each.append(time.perf_counter() - t)
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds and (done + len(each)) % every == 0:
            return len(each), elapsed, each


def traced_window(engine, n_rounds: int, directory: str, ctx):
    """``n_rounds`` rounds under the profiler, with the benchmark's spans
    around each region's step, its control plane, cohort build and
    bucketed round, and the merge."""
    from bench.harness.trace import TraceData, capture, find_xplane
    spans = Spans()
    layouts = []

    def layout_of(cohort):
        if cohort is not None:
            layouts.append([list(cb.xs.shape[:3]) for cb in cohort.buckets])

    undo = [spans.wrap(engine, "_policy_merge", "merge")]
    for t in engine.trainers:
        undo.append(spans.wrap(t, "step", "region_step"))
        undo.append(spans.wrap(t.orch, "step", "orchestrate"))
        undo.append(spans.wrap(t.cohort_engine, "round", "local_update"))
        undo.append(spans.wrap(t.cohort_engine, "build", "cohort_build",
                               after=layout_of))
    real0, layout0 = _stats(engine)
    merges0 = len(engine.merges)
    with shadowed(undo):
        with capture(directory):
            for i in range(n_rounds):
                with jax.profiler.StepTraceAnnotation("bench.round",
                                                      step_num=i):
                    _one_round(engine)
    real1, layout1 = _stats(engine)
    trace = TraceData.load(find_xplane(directory))
    return Readings(
        kind="train", chips=ctx.chips, config=ctx.config, peaks=ctx.peaks,
        trace=trace, spans=spans.seconds, layouts=layouts,
        counts=dict(rounds=n_rounds,
                    region_rounds=n_rounds * len(engine.trainers),
                    merges=len(engine.merges) - merges0,
                    real_elements=real1 - real0,
                    layout_elements=layout1 - layout0))


# -- the comparison ----------------------------------------------------------
def _compared(rec: Recorder):
    return [r for r in rec.rounds if r["round"] >= FIRST_COMPARED]


def _merges(cell, rec: Recorder):
    """``(barrier round, weights, base)`` for every merge of the cell's
    synchronous federation that closes a compared round: each region
    weighted by its rows and by ``2^(-staleness / half_life)``, the
    staleness being how far its clock lies behind the latest as the merge
    opens; ``base`` is that merge of the regions' recorded start models of
    the round."""
    fed = cell["scenario"]["federation"]
    if fed.get("policy") != "synchronous":
        raise ValueError(f"the reference merges synchronously, not "
                         f"{fed.get('policy')!r}")
    masses = [len(x) for x, _ in rec.rows]
    last = max((r["round"] for r in rec.rounds), default=0)
    out = []
    for k in range(FIRST_COMPARED, last + 1):
        if k % fed["every"]:
            continue
        clocks = rec.merge_clocks.get(k, [0.0] * len(masses))
        stale = [max(clocks) - c for c in clocks]
        w = convnet.merge_weights(masses, stale, fed.get("half_life"))
        starts = [r["start"] for r in _by_region(rec.rounds, k)]
        out.append((k, w, _leaves(convnet.weighted_average(starts, w))))
    return out


def _by_region(rounds, k: int):
    return sorted((r for r in rounds if r["round"] == k),
                  key=lambda r: r["region"])


def replay(config, cell, rec: Recorder, prec="f32", fault=None):
    """The compared rounds (``FIRST_COMPARED`` on) by the plain reference
    (or, with ``prec`` or ``fault``, by a control put in the program's
    place), on the recorded cohorts and the regions' own rows.

    Each region round is replayed from the program's own recorded start
    model, so that a divergence of one round cannot carry into the next.
    Each region's model is averaged with weights pool size / the region's
    rows; each merge that closes a compared round (``_merges``) merges the
    replayed models of that round.  Returns the side's record: ``{"rounds":
    per compared region round {"round", "region", "losses", "start",
    "params", "clients"}, "merges": per such merge (barrier round, base,
    every region's leaves after it)}``."""
    sgd = convnet.LocalSGD(config, prec)
    treedef = jax.tree_util.tree_structure(jax.eval_shape(
        lambda: convnet.init_params(config, jax.random.PRNGKey(0))))
    masses = [len(x) for x, _ in rec.rows]
    rounds = []
    for r in _compared(rec):
        buckets = [(xs, ys, _faulted(mask, fault), sizes, n_real)
                   for xs, ys, mask, sizes, n_real in r["buckets"]]
        params, losses, clients = convnet.region_round(
            sgd, jax.tree_util.tree_unflatten(treedef, r["start"]), buckets,
            r["lr"], masses[r["region"]], sample=set(r["clients"]))
        rounds.append(dict(round=r["round"], region=r["region"],
                           losses=losses, clients=clients, start=r["start"],
                           params=_leaves(params)))
    merges = []
    for k, w, base in _merges(cell, rec):
        merged = _leaves(convnet.weighted_average(
            [r["params"] for r in _by_region(rounds, k)], w))
        merges.append((k, base, [merged] * len(masses)))
    return {"rounds": rounds, "merges": merges}


def _leaves(tree):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(tree)]


def _faulted(mask, fault):
    """``half_batch``: the second half of each step's valid samples left
    out, the mean taken over the rest."""
    if fault != "half_batch":
        return mask
    m = np.array(mask, copy=True)
    keep = np.cumsum(m, axis=-1) <= np.ceil(m.sum(axis=-1, keepdims=True)
                                            / 2)
    return m * keep


def change(p_after, p_before, r_after, r_before) -> float:
    """Worst leaf's gap between the program's and the reference's change
    of a model (``compare.norm_gap``)."""
    return compare.norm_gap(compare.leaf_deltas(p_after, p_before),
                            compare.leaf_deltas(r_after, r_before))


def gaps(program, reference):
    """The compared numbers from two sides' records (``replay``'s form),
    over the compared rounds (``FIRST_COMPARED`` on), each of which both
    sides ran from the program's recorded start:

    * ``loss_gap``: each region round's loss, the mean of its real
      clients' losses (what the program reports for the round);
    * ``update_gap``: each region round's change of the region's model,
      worst leaf;
    * ``client_gap``: the change of each sampled client's trained model
      over its region round, worst leaf (absent where the program's
      local update handed on no client model);
    * ``change_gap``: each merge that closes a compared round, as every
      region's model after it against the same merge of the regions'
      start models of that round, worst leaf."""
    p_rounds, r_rounds = program["rounds"], reference["rounds"]
    found = dict(
        loss_gap=compare.loss_gap(
            [float(np.mean(pr["losses"])) for pr in p_rounds],
            [float(np.mean(rr["losses"])) for rr in r_rounds]),
        update_gap=max(change(pr["params"], pr["start"], rr["params"],
                              rr["start"])
                       for pr, rr in zip(p_rounds, r_rounds)))
    merged = [change(p, base, r, base)
              for (_, base, p_regions), (_, _, r_regions)
              in zip(program["merges"], reference["merges"])
              for p, r in zip(p_regions, r_regions)]
    if merged:
        found["change_gap"] = max(merged)
    clients = [change(pr["clients"][key], pr["start"], rr["clients"][key],
                      rr["start"])
               for pr, rr in zip(p_rounds, r_rounds)
               for key in pr["clients"] if key in rr["clients"]]
    if clients:
        found["client_gap"] = max(clients)
    return found


def cohort_faults(cell, rec: Recorder) -> int:
    """Departures of the checked rounds' cohorts from the regions' rows
    and the batch rule (``bench/reference/cohort.py``)."""
    pop = cell["population"]
    rows = [cohort.Rows(x, y) for x, y in rec.rows]
    return sum(cohort.faults(r["buckets"], rows[r["region"]],
                             pop["h_local"], pop["batch_cap"])
               for r in rec.rounds)


def handoff_faults(cell, rec: Recorder) -> int:
    """Checked region rounds that did not start, bit for bit, from the
    model their region ended its previous round with, where no merge came
    between: a region that lost or kept its model between rounds, which a
    replay from the recorded start would copy."""
    every = _merge_every(cell)
    last, bad = {}, 0
    for r in rec.rounds:
        prev = last.get(r["region"])
        if prev is not None and prev["round"] % every:
            bad += any(not np.array_equal(a, b)
                       for a, b in zip(r["start"], prev["params"]))
        last[r["region"]] = r
    return bad


def program_record(cell, rec: Recorder):
    """The program's side, in ``replay``'s form: its compared region
    rounds, and after each merge that closes one, every region's model
    (the start of its next recorded round, or its model after the last)."""
    merges = []
    for k, _, base in _merges(cell, rec):
        after = _by_region(rec.rounds, k + 1)
        merges.append((k, base,
                       [r["start"] for r in after] if after else rec.final))
    return {"rounds": _compared(rec), "merges": merges}


def run(ctx):
    cell, config = ctx.cell, ctx.config
    engine = set_up(cell, config, ctx.seed)
    rec = checked_rounds(engine, cell["checked_rounds"], ctx.seed)
    finite = all(np.all(np.isfinite(r["losses"])) for r in rec.rounds)
    ctx.set_up_done()

    readings = None
    region_rounds = 0
    failed = 0
    if ctx.trace:
        readings = traced_window(engine, cell["trace_rounds"],
                                 ctx.trace_dir, ctx)
        region_rounds = readings.counts["region_rounds"]
        e2e = {}
    else:
        c0 = ctx.compiles.mark()
        rounds, elapsed, each = window(engine, ctx.seconds,
                                       _merge_every(cell),
                                       cell["checked_rounds"])
        c1 = ctx.compiles.mark()
        core.say(f"# window: {rounds} rounds in {elapsed!r} s; compiles "
                 f"inside it: {c1[1] - c0[1]} ({c1[0] - c0[0]!r} s)")
        core.say(f"# round seconds: {[round(t, 4) for t in each]}")
        region_rounds = rounds * len(engine.trainers)
        e2e = {"round_s": elapsed / rounds}
    for t in engine.trainers:
        tail = t.result.losses[-1] if t.result.losses else float("nan")
        if not np.isfinite(tail):
            failed += 1
    memory = core.memory_peak_bytes(ctx.devices)

    del engine
    gc.collect()
    t0 = time.perf_counter()
    reference = replay(config, cell, rec)
    found = gaps(program_record(cell, rec), reference)
    found["cohort_faults"] = cohort_faults(cell, rec)
    found["handoff_faults"] = handoff_faults(cell, rec)
    core.say(f"# reference: {time.perf_counter() - t0!r} s; every number: "
             f"{found}")
    checks = []
    for k, limit in cell["limits"].items():
        if k in found:
            checks.append(core.check(k, found[k], limit))
        else:
            core.say(f"# {k}: nothing to compare")
    if not finite:
        checks.append(core.check("checked_losses_finite", float("inf"), 0))
    return dict(e2e=e2e, readings=readings, attempted=region_rounds,
                failed=failed, memory=memory, checks=checks)
