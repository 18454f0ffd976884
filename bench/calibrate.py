"""Readings that the limits of ``correct`` are set from, on the chip.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6 [--controls bf16] [--witnesses default] \
        [--seed-list bench/seeds.txt] [--checked-rounds N]

For every seed of ``--seeds`` it drives the cell's timed path as a run
does (the training cells' checked rounds; the serving cell's window for
``--seconds``) and prints the compared numbers against the plain
reference: the lower readings.  For a training cell it prints beside them
each ``--witnesses`` precision's numbers against the reference, and the
program's against that witness.  For every seed of ``--control-seeds`` it
prints the same numbers for each control, the reference computed in a
lower precision in the program's place, and for each fault the cell can
have, planted in the reference put in the program's place: the upper
readings.  ``--seed-list`` adds the seeds of a file, one to a line.

For a training cell each reading also prints, per compared global round,
the mean client loss on both sides and the worst loss and update gaps,
and, for the region round whose mean losses lie farthest apart, every
client's loss on both sides; the program's line carries every checked
round's mean loss and a digest of what it recorded, so that two runs of
one seed can be compared bit for bit.  ``--checked-rounds`` drives and compares
another number of rounds than the cell's, to see how the numbers evolve.
One JSON line per reading; nothing here is timed.
"""
from __future__ import annotations

import argparse
import gc
import hashlib
import json
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness import core  # noqa: E402


def _ints(text: str):
    return [int(v) for v in text.split(",") if v]


def _seed_file(path: str):
    with open(path) as f:
        return [int(line.split("#")[0]) for line in f
                if line.split("#")[0].strip()]


def digest(rec) -> str:
    """sha256 of every checked round's losses and resulting region model:
    equal digests mean two runs agreed bit for bit."""
    import numpy as np
    h = hashlib.sha256()
    for r in rec.rounds:
        h.update(np.asarray(r["losses"], np.float64).tobytes())
        for leaf in r["params"]:
            h.update(np.ascontiguousarray(leaf).tobytes())
    return h.hexdigest()


def by_round(drv, side, reference) -> dict:
    """Per compared global round: the mean client loss on both sides, the
    worst region round's loss and update gaps, and the change gap of the
    merge that closes it; and every client's loss on both sides in the
    region round whose means lie farthest apart."""
    import numpy as np
    from bench.reference import compare
    rows = {}
    worst = (-1.0, None, None)
    for s, r in zip(side["rounds"], reference["rounds"]):
        loss = compare.loss_gap([np.mean(s["losses"])], [np.mean(r["losses"])])
        update = drv.change(s["params"], s["start"], r["params"], r["start"])
        row = rows.setdefault(s["round"], {"mean": [], "ref_mean": [],
                                           "loss_gap": 0.0,
                                           "update_gap": 0.0})
        row["mean"].append(float(np.mean(s["losses"])))
        row["ref_mean"].append(float(np.mean(r["losses"])))
        row["loss_gap"] = max(row["loss_gap"], loss)
        row["update_gap"] = max(row["update_gap"], update)
        if loss > worst[0]:
            worst = (loss, s, r)
    for row in rows.values():
        row["mean"] = float(np.mean(row["mean"]))
        row["ref_mean"] = float(np.mean(row["ref_mean"]))
    for (k, base, p_regions), (_, _, r_regions) in zip(side["merges"],
                                                       reference["merges"]):
        rows[k]["change_gap"] = max(drv.change(p, base, r, base)
                                    for p, r in zip(p_regions, r_regions))
    _, s, r = worst
    return {"by_round": rows,
            "worst": {"round": s["round"], "region": s["region"],
                      "clients": [float(v) for v in s["losses"]],
                      "ref_clients": [float(v) for v in r["losses"]]}}


def train_readings(drv, cell, config, seed, controls, faults, witnesses):
    import numpy as np
    engine = drv.set_up(cell, config, seed)
    rec = drv.checked_rounds(engine, cell["checked_rounds"], seed)
    del engine
    gc.collect()
    program = drv.program_record(cell, rec)
    reference = drv.replay(config, cell, rec)
    numbers = drv.gaps(program, reference)
    numbers["cohort_faults"] = drv.cohort_faults(cell, rec)
    numbers["handoff_faults"] = drv.handoff_faults(cell, rec)
    learning = {}
    for r in rec.rounds:
        learning.setdefault(r["round"], []).append(np.mean(r["losses"]))
    yield "program", numbers, {
        "digest": digest(rec),
        "round_mean_loss": {k: float(np.mean(v))
                            for k, v in learning.items()},
        **by_round(drv, program, reference)}
    for prec in witnesses:
        low = drv.replay(config, cell, rec, prec=prec)
        yield (f"witness:{prec}", drv.gaps(low, reference),
               by_round(drv, low, reference))
        yield (f"program-vs-witness:{prec}", drv.gaps(program, low),
               by_round(drv, program, low))
    for prec in controls:
        low = drv.replay(config, cell, rec, prec=prec)
        yield (f"control:{prec}", drv.gaps(low, reference),
               by_round(drv, low, reference))
    for fault in faults:
        bad = drv.replay(config, cell, rec, fault=fault)
        yield (f"fault:{fault}", drv.gaps(bad, reference),
               by_round(drv, bad, reference))


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
                                                      x_rows)}, {}
    for prec in controls:
        yield f"control:{prec}", {"served_gap": drv.reference_gap(
            config, seed, sample, x_rows, prec=prec)}, {}
    for fault in faults:
        if fault == "altered_answer":
            n = config["n_classes"]
            bad = [(req, region, (cls + 1) % n)
                   for req, region, cls in sample]
            yield f"fault:{fault}", {"served_gap": drv.reference_gap(
                config, seed, bad, x_rows)}, {}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--seed-list", default=None,
                    help="a file of seeds, one to a line, read as --seeds")
    ap.add_argument("--checked-rounds", type=int, default=None,
                    help="drive and compare this many rounds, not the "
                         "cell's number")
    ap.add_argument("--controls", default="bf16")
    ap.add_argument("--witnesses", default="default",
                    help="precisions read on every seed beside the program")
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)

    cell = core.workload(args.workload)
    if args.checked_rounds is not None:
        cell["checked_rounds"] = args.checked_rounds
    config = core.config(cell["config"])
    seeds = list(args.seeds)
    if args.seed_list:
        seeds += _seed_file(args.seed_list)
    core.program_path()
    core.setup_jax()
    device = core.require_tpu(cell["chips"])
    drv = core.driver(cell["driver"])
    controls = [c for c in args.controls.split(",") if c]
    witnesses = [c for c in args.witnesses.split(",") if c]
    faults = (["half_batch"] if cell["driver"] == "train"
              else ["altered_answer"])
    jobs = [(s, False) for s in seeds if s not in args.control_seeds]
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
        for kind, numbers, detail in it:
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kind": kind, "numbers": numbers,
                              "device": device["kind"],
                              "t_s": time.perf_counter() - t0, **detail}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
