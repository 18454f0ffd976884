"""The program's own host phases, and the readings built on them.

The program opens a ``jax.profiler.TraceAnnotation`` named ``repro.<phase>``
around each phase of a federated round (``repro.obs.Tracer.phase``):
``region.step`` around ``region.orchestrate``, ``cohort.build``,
``cohort.dispatch``, ``cohort.wait`` and ``region.evaluate``, and
``engine.merge``.  In a profiled window they share the trace's clock with
the device's ops; an enabled tracer times them on the host clock too.

* :func:`load_program` reads the phases out of an ``.xplane.pb``;
* :class:`ProgramTrace` is a :class:`TraceData` that carries them as
  ``program``;
* :class:`PhaseReadings` adds ``phases``, the enabled tracer's phase
  histograms over an unprofiled stretch of rounds (:func:`phase_summary`);
* :func:`innermost` and :func:`idle_by_phase` put each second the device
  sat idle under the innermost phase open at that moment.

A program without phases leaves ``program`` and ``phases`` empty, and the
readers that need them return ``None``.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

from bench.harness.readings import Readings
from bench.harness.trace import Interval, TraceData

PREFIX = "repro."
#: what is left of the window where no phase is open
NONE = "(none)"
#: the device programs of the local update and of the eq.-(13) aggregate
#: (the patterns of ``local_update_roofline`` and ``aggregate_roofline``)
ROUND_PROGRAMS = (r"cohort_local_update", r"_cohort_round_impl",
                  r"bucket_step", r"_fedavg_multi_impl")


def load_program(path: str) -> List[Interval]:
    """The ``repro.`` host annotations of a trace, ``(name, start_s,
    end_s)`` in the trace's seconds, sorted by start."""
    from jax.profiler import ProfileData
    out: List[Interval] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                       for e in line.events if e.name.startswith(PREFIX))
    return sorted(out, key=lambda ev: (ev[1], -ev[2]))


class ProgramTrace(TraceData):
    """A :class:`TraceData` and the program's phases in its window."""

    def __init__(self, base: TraceData, program: List[Interval]):
        super().__init__(base.ops, base.modules, base.host, base.window)
        self.program = program


@dataclasses.dataclass
class PhaseReadings(Readings):
    #: per phase (without the prefix): {"count", "wall_s", "self_s"},
    #: summed over an unprofiled stretch of rounds
    phases: Dict[str, dict] = dataclasses.field(default_factory=dict)


def phase_summary(snapshot: dict) -> Dict[str, dict]:
    """``Metrics.snapshot("phase.")`` of an enabled tracer, by phase."""
    out: Dict[str, dict] = {}
    for key, h in snapshot.items():
        m = re.fullmatch(r"phase\.(.+)\.(wall_s|self_s)", key)
        if m is None or not h.get("count"):
            continue
        entry = out.setdefault(m.group(1), {"count": h["count"]})
        entry[m.group(2)] = h["sum"]
    return out


def program_of(r) -> Optional[List[Interval]]:
    return getattr(r.trace, "program", None) or None


def phase_ms(r, name: str) -> Optional[float]:
    """Mean host milliseconds of one run of the phase ``name`` over the
    unprofiled stretch; ``None`` where the run holds none."""
    h = (getattr(r, "phases", None) or {}).get(name)
    if r.kind != "train" or not h:
        return None
    return 1e3 * h["wall_s"] / h["count"]


def innermost(program: Sequence[Interval]) -> List[Interval]:
    """Disjoint ``(phase, start, end)`` segments, each named by the
    innermost phase open over it (phases nest: one host thread opens
    them).  Time under no phase has no segment."""
    segs: List[Interval] = []
    stack: List[Tuple[str, float]] = []
    cursor = 0.0

    def close_until(t: float) -> None:
        nonlocal cursor
        while stack and stack[-1][1] <= t:
            name, end = stack.pop()
            segs.append((name, cursor, end))
            cursor = end

    for name, s, e in sorted(program, key=lambda ev: (ev[1], -ev[2])):
        close_until(s)
        if stack:
            segs.append((stack[-1][0], cursor, s))
        stack.append((name[len(PREFIX):] if name.startswith(PREFIX)
                      else name, e))
        cursor = s
    close_until(float("inf"))
    return [sg for sg in segs if sg[2] > sg[1]]


def idle_intervals(trace: TraceData, chip: int = 0
                   ) -> List[Tuple[float, float]]:
    """The holes in the chip's busy time inside the window."""
    lo, hi = trace.window
    edges = [lo] + [t for iv in trace.busy_intervals(chip) for t in iv] \
        + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_by_phase(trace: TraceData, program: Sequence[Interval],
                  chip: int = 0) -> Dict[str, float]:
    """Idle seconds of the chip in the window, by the innermost phase
    open over them (:data:`NONE` for those under no phase)."""
    idle = idle_intervals(trace, chip)
    segs = innermost(program)
    out: Dict[str, float] = {}
    j = 0
    for s, e in idle:
        while j < len(segs) and segs[j][2] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][1] < e:
            name, ss, se = segs[k]
            d = min(e, se) - max(s, ss)
            if d > 0:
                out[name] = out.get(name, 0.0) + d
            k += 1
    out[NONE] = sum(e - s for s, e in idle) - sum(out.values())
    return out


def device_s_within(trace: TraceData, patterns: Sequence[str],
                    spans: Sequence[Tuple[float, float]]) -> float:
    """Device seconds of the programs matching a pattern that fall inside
    the disjoint ``(start, end)`` host spans, the mean over the chips."""
    rx = [re.compile(p) for p in patterns]
    chips = trace.chips
    if not chips:
        return 0.0
    total = 0.0
    for c in chips:
        for name, s, e in trace.modules.get(c, ()):
            if any(r.search(name) for r in rx):
                total += sum(max(0.0, min(e, he) - max(s, hs))
                             for hs, he in spans)
    return total / len(chips)


def layout_h2d_bytes(config: dict, layouts: Sequence[Sequence]) -> int:
    """Bytes the bucket layouts hand to the device, from the sample shape
    alone: float32 samples, int32 labels and float32 mask per batch
    element, one float32 aggregate weight per client slot."""
    sample = 1
    for d in config["input_shape"]:
        sample *= d
    return sum(c * h * b * (4 * sample + 4 + 4) + 4 * c
               for layout in layouts for c, h, b in layout)
