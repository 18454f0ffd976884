"""What a traced run hands its per-layer metric readers.

A reader (``bench/metrics/<name>.py``) takes a :class:`Readings` and
returns its number, or ``None`` where the run holds nothing for it to
read; the harness then leaves that metric out of the result line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

from bench.harness.trace import TraceData


@dataclasses.dataclass
class Readings:
    kind: str                       # the driver: "train" or "serve"
    chips: int
    config: dict                    # the cell's model configuration
    peaks: dict                     # bench/peaks.json entry of the chip
    trace: Optional[TraceData]      # the traced window's device trace
    counts: Dict[str, float] = dataclasses.field(default_factory=dict)
    spans: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    layouts: List[list] = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.trace.window_s


class Spans:
    """Host spans the benchmark opens around the program's entry points:
    each call is timed by the host clock and marked in the profiler's
    trace as ``bench.<name>``."""

    def __init__(self):
        self.seconds: Dict[str, List[float]] = {}

    def wrap(self, obj, attr: str, name: str, after=None):
        """Shadow ``obj.attr`` with a timed, annotated call of it;
        ``after(result)`` sees each result."""
        import jax
        inner = getattr(obj, attr)
        label = f"bench.{name}"
        store = self.seconds.setdefault(name, [])

        def timed(*args, **kwargs):
            with jax.profiler.TraceAnnotation(label):
                t0 = time.perf_counter()
                out = inner(*args, **kwargs)
                store.append(time.perf_counter() - t0)
            if after is not None:
                after(out)
            return out

        setattr(obj, attr, timed)
        return lambda: obj.__dict__.pop(attr, None)


@contextlib.contextmanager
def shadowed(undo: list):
    """Remove every shadowing wrapper in ``undo`` on the way out."""
    try:
        yield
    finally:
        for fn in undo:
            fn()
