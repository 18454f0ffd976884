"""Readings that the limits of ``correct`` are set from, on the chip.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6 [--controls bf16] [--witnesses default]

For every seed of ``--seeds`` it drives the cell's timed path as a run
does (the training cells' checked rounds; the serving cell's window for
``--seconds``) and prints the compared numbers against the plain
reference: the lower readings.  For a training cell it prints beside them
each ``--witnesses`` precision's numbers against the reference, and the
program's against that witness.  For every seed of ``--control-seeds`` it
prints the same numbers for each control, the reference computed in a
lower precision in the program's place, and for each fault the cell can
have, planted in the reference put in the program's place: the upper
readings.  One JSON line per reading; nothing here is timed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness import core  # noqa: E402


def _ints(text: str):
    return [int(v) for v in text.split(",") if v]


def train_readings(drv, cell, config, seed, controls, faults, witnesses):
    engine, w0 = drv.set_up(cell, config, seed)
    rec = drv.checked_rounds(engine, cell["checked_rounds"], seed)
    program = drv.program_record(rec, engine, w0)
    del engine
    gc.collect()
    n = len(rec.rows)
    reference = drv.replay(config, cell, seed, rec)
    numbers = drv.gaps(program, reference, n)
    numbers["cohort_faults"] = drv.cohort_faults(cell, rec)
    yield "program", numbers
    for prec in witnesses:
        low = drv.replay(config, cell, seed, rec, prec=prec)
        yield f"witness:{prec}", drv.gaps(low, reference, n)
        yield f"program-vs-witness:{prec}", drv.gaps(program, low, n)
    for prec in controls:
        low = drv.replay(config, cell, seed, rec, prec=prec)
        yield f"control:{prec}", drv.gaps(low, reference, n)
    for fault in faults:
        bad = drv.replay(config, cell, seed, rec, fault=fault)
        yield f"fault:{fault}", drv.gaps(bad, reference, n)


def serve_readings(drv, cell, config, seed, controls, faults, seconds):
    engine, gw, backend = drv.set_up(cell, config, seed)
    first = len(gw.completed)
    drv.serve_for(gw, seconds, float(cell["chunk_s"]), 0.0)
    records = drv.answered(gw, backend, first)
    x_rows = [v for v in gw._x]
    del engine, gw, backend
    gc.collect()
    sample = drv.draw(records, seed)
    yield "program", {"served_gap": drv.reference_gap(config, seed, sample,
                                                      x_rows)}
    for prec in controls:
        yield f"control:{prec}", {"served_gap": drv.reference_gap(
            config, seed, sample, x_rows, prec=prec)}
    for fault in faults:
        if fault == "altered_answer":
            n = config["n_classes"]
            bad = [(req, region, (cls + 1) % n)
                   for req, region, cls in sample]
            yield f"fault:{fault}", {"served_gap": drv.reference_gap(
                config, seed, bad, x_rows)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--controls", default="bf16")
    ap.add_argument("--witnesses", default="default",
                    help="precisions read on every seed beside the program")
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)

    cell = core.workload(args.workload)
    config = core.config(cell["config"])
    core.program_path()
    core.setup_jax()
    device = core.require_tpu(cell["chips"])
    drv = core.driver(cell["driver"])
    controls = [c for c in args.controls.split(",") if c]
    witnesses = [c for c in args.witnesses.split(",") if c]
    faults = (["half_batch"] if cell["driver"] == "train"
              else ["altered_answer"])
    jobs = [(s, False) for s in args.seeds]
    jobs += [(s, True) for s in args.control_seeds]
    for seed, upper in jobs:
        t0 = time.perf_counter()
        if cell["driver"] == "train":
            it = train_readings(drv, cell, config, seed,
                                controls if upper else [],
                                faults if upper else [], witnesses)
        else:
            it = serve_readings(drv, cell, config, seed,
                                controls if upper else [],
                                faults if upper else [], args.seconds)
        for kind, numbers in it:
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kind": kind, "numbers": numbers,
                              "device": device["kind"],
                              "t_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
