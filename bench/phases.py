"""Phase readings of a training cell: the program's own host phases on the
profiler's clock and on the host clock, and what an enabled tracer costs.

    python3 bench/phases.py --workload <cell> --seed <n> \
        [--cost-pairs K --seconds S]

Set-up as a run of ``bench/run.py`` (the engine, the seed's weights, the
warm-up compiles, the checked rounds and their record, which is kept as a
run keeps it), then, where a run's window would be:

1. an unprofiled stretch of ``trace_rounds`` rounds with the engine
   switched to an enabled tracer (``SAGINEngine.set_tracer``), whose phase
   histograms are the host-clock readings;
2. ``--cost-pairs`` pairs of windows of ``--seconds`` each, one with the
   tracer off and one on, their order alternating from pair to pair;
3. the profiled window of ``trace_rounds`` rounds exactly as a ``--trace
   1`` run makes it, keeping the program's ``repro.`` phases from its
   trace and the cohort engines' host-to-device bytes;
4. the cost of one phase on the host: microseconds to enter and leave it
   on the disabled tracer with no profiler running, the same under a
   running ``jax.profiler`` capture, and on an enabled tracer.

The unprofiled rounds come first because a profiled window changes the
rounds after it: on a TPU v5e the cohort build took 16 ms a region round
after one and 59 ms before (``PERF.md``), so only rounds before it read
what a run's window reads.

Standard error gets each tracer-cost window's round seconds, the ``#
idle by phase`` line (device-idle seconds of the profiled window under
each innermost phase, and under none), the coverage of both stretches,
the host-to-device bytes beside those the window's bucket layouts give,
and the phase cost.  The last line of standard output is one JSON
object: every per-layer metric whose reader finds something to read
(``bench/metrics/``), and those figures.  Nothing here decides
``correct``: that is the reference comparison of ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import tempfile
import time
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness import core, phases, runner  # noqa: E402


def h2d_bytes(engine) -> int:
    return sum(t.cohort_engine.stats.h2d_bytes for t in engine.trainers)


def stretch(train, engine, n_rounds: int) -> dict:
    """``n_rounds`` rounds (``train``: the training driver) with the engine
    switched to an enabled tracer: the phase summary, the wall seconds,
    the region rounds and merges."""
    from repro.obs import ObsConfig, Tracer
    tracer = Tracer(ObsConfig())
    merges0 = len(engine.merges)
    prev = engine.set_tracer(tracer)
    try:
        t0 = time.perf_counter()
        for _ in range(n_rounds):
            train._one_round(engine)
        wall = time.perf_counter() - t0
    finally:
        engine.set_tracer(prev)
    summary = phases.phase_summary(tracer.metrics.snapshot("phase."))
    return dict(phases=summary, wall_s=wall,
                region_rounds=n_rounds * len(engine.trainers),
                merges=len(engine.merges) - merges0)


def tracer_cost(train, engine, pairs: int, seconds: float, every: int,
                done: int):
    """``round_s`` of ``pairs`` windows with the tracer off and as many
    with it on, off first in even pairs, and each window's round seconds;
    ``done`` rounds ran before."""
    from repro.obs import ObsConfig, Tracer
    out = {"off": [], "on": [], "off_rounds": [], "on_rounds": []}
    for i in range(pairs):
        for side in (("off", "on") if i % 2 == 0 else ("on", "off")):
            prev = engine.set_tracer(Tracer(ObsConfig()) if side == "on"
                                     else None)
            try:
                rounds, elapsed, each = train.window(engine, seconds, every,
                                                     done)
            finally:
                engine.set_tracer(prev)
            done += rounds
            out[side].append(elapsed / rounds)
            out[f"{side}_rounds"].append(each)
            core.say(f"# pair {i} tracer {side}: round_s "
                     f"{elapsed / rounds!r}, rounds {each!r}")
    return out


def phase_cost(n: int = 20_000) -> dict:
    """Microseconds to enter and leave one phase, the mean of ``n``: on
    the disabled tracer with no profiler running (``disabled_us``), the
    same under a running ``jax.profiler`` capture (``profiled_us``), and
    on an enabled tracer with no profiler (``enabled_us``)."""
    import jax
    from repro.obs import NULL_TRACER, ObsConfig, Tracer

    def each(tracer) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            with tracer.phase("cost.probe"):
                pass
        return 1e6 * (time.perf_counter() - t0) / n

    out = dict(n=n, disabled_us=each(NULL_TRACER),
               enabled_us=each(Tracer(ObsConfig())))
    with tempfile.TemporaryDirectory(prefix="bench-phase-cost-") as tdir:
        jax.profiler.start_trace(tdir)
        try:
            out["profiled_us"] = each(NULL_TRACER)
        finally:
            jax.profiler.stop_trace()
    return out


def measure(ctx, cost_pairs: int = 0) -> dict:
    from bench.harness.trace import find_xplane
    train = core.driver("train")
    cell = ctx.cell
    engine = train.set_up(cell, ctx.config, ctx.seed)
    # held to the end, as a run holds it through its window
    record = train.checked_rounds(engine, cell["checked_rounds"], ctx.seed)
    ctx.set_up_done()
    n = cell["trace_rounds"]
    unprofiled = stretch(train, engine, n)
    cost = None
    if cost_pairs:
        cost = tracer_cost(train, engine, cost_pairs, ctx.seconds,
                           train._merge_every(cell),
                           cell["checked_rounds"] + n)
        core.say(f"# round_s off {cost['off']}, on {cost['on']}")

    b0 = h2d_bytes(engine)
    with tempfile.TemporaryDirectory(prefix="bench-phases-") as tdir:
        base = train.traced_window(engine, n, tdir, ctx)
        program = phases.load_program(find_xplane(tdir))
    counts = dict(base.counts, h2d_bytes=h2d_bytes(engine) - b0)
    fields = {f.name: getattr(base, f.name)
              for f in dataclasses.fields(base)}
    fields.update(trace=phases.ProgramTrace(base.trace, program),
                  counts=counts, phases=unprofiled["phases"])
    r = phases.PhaseReadings(**fields)

    metrics = {}
    for name, mod in core.metric_modules().items():
        value = mod.read(r)
        if value is not None and math.isfinite(value):
            metrics[name] = {"value": value, "unit": mod.UNIT}
    idle = phases.idle_by_phase(r.trace, program)
    idle_s = sum(idle.values())
    by_phase = unprofiled["phases"]
    covered = (by_phase.get("region.step", {}).get("wall_s", 0.0)
               + by_phase.get("engine.merge", {}).get("wall_s", 0.0))
    layout_bytes = phases.layout_h2d_bytes(ctx.config, r.layouts)
    # last: a profiler capture changes the rounds after it (docstring)
    cost_one = phase_cost()
    per_round = sum(h["count"] for h in by_phase.values()) / n
    cost_one["phases_per_round"] = per_round
    out = dict(
        metrics=metrics,
        idle_by_phase=idle,
        idle_s=idle_s,
        idle_under_phase=(1.0 - idle[phases.NONE] / idle_s) if idle_s else
        None,
        window_s=r.window_s, busy_s=r.trace.busy_s(),
        region_rounds=counts["region_rounds"],
        h2d_bytes=counts["h2d_bytes"], layout_h2d_bytes=layout_bytes,
        stretch=dict(unprofiled, covered_s=covered,
                     coverage=covered / unprofiled["wall_s"]),
        program_events=len(program), tracer_cost=cost,
        phase_cost=cost_one,
        checked_region_rounds=len(record.rounds))
    core.say(f"# idle by phase (s): {idle}; under a phase: "
             f"{out['idle_under_phase']!r} of {idle_s!r} s idle")
    core.say(f"# unprofiled stretch: {unprofiled['wall_s']!r} s, phases "
             f"cover {out['stretch']['coverage']!r}")
    core.say(f"# h2d bytes: counter {counts['h2d_bytes']}, layouts "
             f"{layout_bytes}")
    core.say(f"# phase cost (us each): {cost_one!r}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0,
                    help="length of each tracer-cost window")
    ap.add_argument("--cost-pairs", type=int, default=0)
    args = ap.parse_args(argv)
    ns = argparse.Namespace(workload=args.workload, seed=args.seed,
                            seconds=args.seconds, trace=1)
    try:
        ctx = runner.context(ns, core.Clock())
        if ctx.cell["driver"] != "train":
            raise core.BenchError(f"{args.workload} is not a training cell")
        out = measure(ctx, args.cost_pairs)
    except core.BenchError as e:
        core.say(f"bench: {e}")
        return 2
    out.update(workload=args.workload, seed=args.seed, setup_s=ctx.setup_s)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
