"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Set REPRO_BENCH_FULL=1 for the
paper-scale settings (50 devices, full datasets, 30 rounds).

Artifact mode (``--json``) additionally writes machine-readable perf
baselines so every PR's numbers are comparable against the previous
ones:

* ``BENCH_cohort.json`` — rows from ``cohort_scaling``,
  ``obs_overhead`` (the <2% disabled-tracing gate; rows carry
  ``repro.obs`` metrics snapshots) and ``fl_payload_scaling`` when it
  ran: the FL round-engine trajectory.
* ``BENCH_sim.json``    — rows from ``sim_scale`` (and
  ``handover_dynamics`` when it ran): the propagation/engine trajectory.
* ``BENCH_federation.json`` — rows from ``cross_region``: the
  federation-policy sweep (synchronous vs soft_async vs partial
  time-to-target-loss under degraded ISLs) plus the global-vs-
  independent merge comparison.
* ``BENCH_resilience.json`` — rows from ``resilience``: chaos-preset
  completion (gated: finite global model, in-round faults recovered),
  unplanned-handover recovery vs restart-from-scratch (gated:
  recovery wins), and the fault-rate degradation curve.

``--smoke`` shrinks every module to CI sizes (exports
``REPRO_BENCH_SMOKE=1``) and restricts the run to the artifact-feeding
modules, which is what the CI bench-smoke lane executes:

    PYTHONPATH=src python -m benchmarks.run --json --smoke

``--only NAME [NAME ...]`` selects modules explicitly in either mode.
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback

from .common import drain_rows, write_bench_json

# module name -> BENCH artifact it feeds (None: CSV only)
ARTIFACT_OF = {
    "cohort_scaling": "BENCH_cohort.json",
    "fl_payload_scaling": "BENCH_cohort.json",
    "obs_overhead": "BENCH_cohort.json",
    "sim_scale": "BENCH_sim.json",
    "handover_dynamics": "BENCH_sim.json",
    "cross_region": "BENCH_federation.json",
    "resilience": "BENCH_resilience.json",
    "serve": "BENCH_serve.json",
}
SMOKE_MODULES = ("sim_scale", "cohort_scaling", "cross_region",
                 "obs_overhead", "resilience", "serve")


def _modules():
    from . import (cohort_scaling, complexity, convergence_bound,
                   cross_region, fig4_time_to_accuracy,
                   fig5_compute_ablation, fig6_alpha_sweep, fig7_pathloss,
                   fl_payload_scaling, handover_dynamics, kernels_micro,
                   obs_overhead, resilience, roofline_report, serve,
                   sim_scale)
    return [
        ("sim_scale", sim_scale),
        ("cross_region", cross_region),
        ("cohort_scaling", cohort_scaling),
        ("obs_overhead", obs_overhead),
        ("resilience", resilience),
        ("serve", serve),
        ("fig5_compute_ablation", fig5_compute_ablation),
        ("handover_dynamics", handover_dynamics),
        ("fl_payload_scaling", fl_payload_scaling),
        ("complexity", complexity),
        ("convergence_bound", convergence_bound),
        ("kernels_micro", kernels_micro),
        ("fig4_time_to_accuracy", fig4_time_to_accuracy),
        ("fig6_alpha_sweep", fig6_alpha_sweep),
        ("fig7_pathloss", fig7_pathloss),
        ("roofline_report", roofline_report),
    ]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_*.json perf artifacts")
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizes; runs only the artifact modules")
    ap.add_argument("--only", nargs="+", default=None,
                    help="run only these modules")
    ap.add_argument("--out-dir", default=".",
                    help="directory for the BENCH_*.json artifacts")
    args = ap.parse_args()

    modules = _modules()
    known = [name for name, _ in modules]
    selected = args.only or (list(SMOKE_MODULES) if args.smoke else known)
    unknown = sorted(set(selected) - set(known))
    if unknown:
        ap.error(f"unknown modules {unknown}; available: {known}")
    modules = [(n, m) for n, m in modules if n in selected]

    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    # module mains parse sys.argv themselves; hide the driver's flags
    sys.argv = [sys.argv[0]]

    from repro.compat import setup_compile_cache
    print(f"# {setup_compile_cache()}", flush=True)
    print("name,us_per_call,derived")
    failures = []
    rows_by_module = {}
    drain_rows()
    for name, mod in modules:
        ok = True
        try:
            rc = mod.main()
            if rc:
                ok = False
        except Exception:
            ok = False
            print(f"{name},0.0,ERROR", flush=True)
            traceback.print_exc()
        rows = drain_rows()
        if ok:
            rows_by_module[name] = rows
        else:
            # a failed module's partial rows (or below-gate numbers) must
            # not become a committed perf baseline
            failures.append(name)
            print(f"# dropping {len(rows)} row(s) of failed module {name} "
                  f"from artifacts", flush=True)

    if args.json:
        os.makedirs(args.out_dir, exist_ok=True)
        for target in ("BENCH_cohort.json", "BENCH_sim.json",
                       "BENCH_federation.json", "BENCH_resilience.json",
                       "BENCH_serve.json"):
            feeders = [n for n, _ in _modules()
                       if ARTIFACT_OF.get(n) == target]
            ran = [n for n in feeders if n in rows_by_module]
            if not ran:
                # never clobber a committed baseline with an empty doc
                # when the selection excluded every feeding module
                print(f"# skipping {target}: none of {feeders} ran",
                      flush=True)
                continue
            rows = [r for n in ran for r in rows_by_module[n]]
            write_bench_json(os.path.join(args.out_dir, target), rows,
                             smoke=args.smoke)

    if failures:
        print(f"# failed modules: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
