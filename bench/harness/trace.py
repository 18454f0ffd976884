"""The profiler trace of a run's window, and its reduction to seconds.

``capture(directory)`` profiles a block with JAX's profiler (no Python
tracer) inside a host annotation ``bench.window``, which bounds the traced
window.  ``TraceData.load`` reads the ``.xplane.pb`` it writes with
``jax.profiler.ProfileData`` alone and keeps three things:

* per chip, the device's op events (``XLA Ops``, named
  ``program/op``) and program events (``XLA Modules``) as
  ``(name, start_s, end_s)``;
* the host annotations the benchmark opened (names starting ``bench.``);
* the window.

Busy time is the union of a chip's op intervals inside the window; idle
gaps are the holes in that union, each named by the innermost benchmark
annotation open at its middle.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW = "bench.window"


@contextlib.contextmanager
def capture(directory: str):
    """Profile the block; the trace goes under ``directory``."""
    import jax
    from jax.profiler import ProfileOptions
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    # level 1 keeps annotations and drops the runtime's own host events,
    # millions of them a second, which the reduction does not read
    opts.host_tracer_level = 1
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def _program(name: str) -> str:
    """``jit_f(123...)`` -> ``jit_f``."""
    return name.split("(", 1)[0]


def _op(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _qualify(ops: List[Interval], modules: List[Interval]) -> List[Interval]:
    """Op events named ``program/op`` by the program running at the op's
    start (``?`` where none is)."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = (_program(mods[i][0]) if i >= 0 and mods[i][2] >= s
                else "?")
        out.append((f"{prog}/{_op(name)}", s, e))
    return out


class TraceData:
    """Device and host intervals of one traced window, in seconds."""

    def __init__(self, ops: Dict[int, List[Interval]],
                 modules: Dict[int, List[Interval]],
                 host: List[Interval], window: Tuple[float, float]):
        self.ops = ops
        self.modules = modules
        self.host = host
        self.window = window

    @classmethod
    def load(cls, path: str) -> "TraceData":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        ops: Dict[int, List[Interval]] = {}
        modules: Dict[int, List[Interval]] = {}
        host: List[Interval] = []
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m is not None and line.name in (OPS_LINE, MODULES_LINE):
                    chip = int(m.group(1))
                    dest = ops if line.name == OPS_LINE else modules
                    dest.setdefault(chip, []).extend(
                        (e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                        for e in line.events)
                elif m is None and plane.name.startswith("/host"):
                    host.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                                for e in line.events
                                if e.name.startswith(HOST_PREFIX))
        ops = {c: _qualify(v, modules.get(c, [])) for c, v in ops.items()}
        windows = [(s, e) for n, s, e in host if n == WINDOW]
        if not windows:
            raise ValueError(f"{path}: no {WINDOW!r} annotation")
        return cls(ops, modules, host, windows[0])

    # -- reductions ----------------------------------------------------------
    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)

    def busy_intervals(self, chip: int) -> List[Tuple[float, float]]:
        return merge_intervals(self.ops.get(chip, ()), *self.window)

    def busy_s(self, chip: Optional[int] = None) -> float:
        """Seconds in which an op ran: on ``chip``, or the mean over the
        chips in the trace."""
        chips = self.chips if chip is None else [chip]
        if not chips:
            return 0.0
        return sum(sum(e - s for s, e in self.busy_intervals(c))
                   for c in chips) / len(chips)

    def module_s(self, patterns: Sequence[str],
                 chip: Optional[int] = None) -> float:
        """Device seconds of the programs whose name matches a pattern
        (``re.search``), clipped to the window; summed on ``chip``, or the
        mean over chips."""
        return self._matched(self.modules, patterns, chip)

    def op_s(self, patterns: Sequence[str],
             chip: Optional[int] = None) -> float:
        """As :meth:`module_s`, over the op events."""
        return self._matched(self.ops, patterns, chip)

    def _matched(self, table, patterns, chip) -> float:
        rx = [re.compile(p) for p in patterns]
        chips = sorted(table) if chip is None else [chip]
        if not chips:
            return 0.0
        lo, hi = self.window
        total = 0.0
        for c in chips:
            for name, s, e in table.get(c, ()):
                if any(r.search(name) for r in rx):
                    total += max(0.0, min(e, hi) - max(s, lo))
        return total / len(chips)

    def top_ops(self, chip: int = 0, top: int = 10) -> List[list]:
        """``[[op, seconds]]`` of the ops that took most device time."""
        lo, hi = self.window
        acc: Dict[str, float] = {}
        for name, s, e in self.ops.get(chip, ()):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                acc[name] = acc.get(name, 0.0) + d
        return [[n, v] for n, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, chip: int = 0, top: int = 10) -> List[list]:
        """``[[host annotation, seconds]]`` of the longest holes in the
        chip's busy time, each named by the innermost benchmark annotation
        open at its middle (``bench.window`` where no other is)."""
        busy = self.busy_intervals(chip)
        lo, hi = self.window
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.host_at(0.5 * (s + e)), e - s] for s, e in gaps[:top]]

    def host_at(self, t: float) -> str:
        """The innermost (shortest) benchmark annotation open at ``t``."""
        open_ = [(e - s, n) for n, s, e in self.host if s <= t <= e]
        return min(open_)[1] if open_ else "(none)"


def merge_intervals(events: Iterable, lo: float, hi: float
                    ) -> List[Tuple[float, float]]:
    """The union of ``(name, start, end)`` intervals clipped to
    ``[lo, hi]``, as sorted disjoint ``(start, end)`` pairs."""
    spans = sorted((max(s, lo), min(e, hi)) for _, s, e in events
                   if e > lo and s < hi)
    out: List[Tuple[float, float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out
