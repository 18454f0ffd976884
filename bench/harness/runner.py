"""One run of one cell: set-up, window, reference comparison, result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics
and ``setup_s``; with ``--trace 1`` the window runs under the profiler and
the metrics are the per-layer metrics whose readers find something to
read.  Either way ``correct`` is decided by the comparison with the plain
reference, printed number by number beside its limit.
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import shutil
import tempfile
import traceback
from typing import Optional

from bench.harness import core


@dataclasses.dataclass
class Context:
    cell: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    chips: int
    devices: list
    device: dict
    peaks: dict
    clock: core.Clock
    compiles: core.CompileClock
    trace_dir: Optional[str] = None
    setup_s: Optional[float] = None

    def set_up_done(self) -> None:
        """Everything before the window is set-up."""
        self.setup_s = self.clock.now()
        core.say(f"# set-up: {self.setup_s!r} s, compiles "
                 f"{self.compiles.count} ({self.compiles.seconds!r} s)")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced window's profile to this directory")
    return ap.parse_args(argv)


def context(args, clock: core.Clock, require=core.require_tpu) -> Context:
    cell = core.workload(args.workload)
    config = core.config(cell["config"])
    core.program_path()
    import jax
    core.setup_jax()
    device = require(cell["chips"])
    return Context(cell=cell, config=config, seed=args.seed % 2**64,
                   seconds=args.seconds, trace=bool(args.trace),
                   chips=cell["chips"],
                   devices=jax.devices()[:cell["chips"]], device=device,
                   peaks=core.peaks(device["kind"]), clock=clock,
                   compiles=core.CompileClock())


def measure(ctx: Context, keep_trace: Optional[str] = None) -> dict:
    """Drive the cell and build its result line (without the checks)."""
    drv = core.driver(ctx.cell["driver"])
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        ctx.trace_dir = tdir
        out = drv.run(ctx)
        if keep_trace and ctx.trace:
            shutil.copytree(tdir, keep_trace, dirs_exist_ok=True)
    device = dict(ctx.device, memory_peak_bytes=out["memory"])
    result = {"correct": all(c["ok"] for c in out["checks"])
              and out["failed"] == 0,
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": {}, "device": device}
    if ctx.trace:
        r = out["readings"]
        device["busy_s"] = r.trace.busy_s()
        device["window_s"] = r.window_s
        for name, mod in core.metric_modules().items():
            value = mod.read(r)
            if value is not None and math.isfinite(value):
                result["metrics"][name] = {"value": value, "unit": mod.UNIT}
        result["breakdown"] = {"device_ops": r.trace.top_ops(),
                               "idle_gaps": r.trace.idle_gaps()}
    else:
        units = drv.UNITS
        for name, value in out["e2e"].items():
            result["metrics"][name] = {"value": value, "unit": units[name]}
        result["metrics"]["setup_s"] = {"value": ctx.setup_s, "unit": "s"}
    return result, out["checks"]


def main(argv=None, t0: Optional[float] = None,
         require=core.require_tpu) -> int:
    args = parse(argv)
    clock = core.Clock(t0)
    try:
        ctx = context(args, clock, require)
        result, checks = measure(ctx, args.keep_trace)
    except core.BenchError as e:
        core.say(f"bench: {e}")
        return 2
    except Exception:  # noqa: BLE001 - a failed run prints no result
        core.say(traceback.format_exc())
        return 1
    core.emit(result, checks)
    return 0
