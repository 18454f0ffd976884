"""The numbers that decide ``correct``: gaps between the program's outputs
and the reference's, each a worst case over what the run produced."""
from __future__ import annotations

from typing import Sequence

import numpy as np

#: Leaves whose reference change is under this share of the median leaf's
#: move by round-off alone; they are left out of the change norms.
NOUGHT = 1e-3


def loss_gap(program: Sequence[float], reference: Sequence[float]) -> float:
    """Largest relative gap between paired losses."""
    a = np.asarray(program, np.float64)
    b = np.asarray(reference, np.float64)
    if a.shape != b.shape or a.size == 0:
        raise ValueError(f"loss counts differ: {a.shape} vs {b.shape}")
    if not np.all(np.isfinite(a)):
        return float("inf")
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12)))


def norm_gap(program_leaves, reference_leaves) -> float:
    """Worst leaf's gap between the norms of two changes, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger.  Leaves the reference moves by under ``NOUGHT`` of the median
    leaf are left out."""
    p = np.array([np.linalg.norm(np.asarray(x, np.float64))
                  for x in program_leaves])
    r = np.array([np.linalg.norm(np.asarray(x, np.float64))
                  for x in reference_leaves])
    if p.shape != r.shape or r.size == 0:
        raise ValueError(f"leaf counts differ: {p.shape} vs {r.shape}")
    if not np.all(np.isfinite(p)):
        return float("inf")
    med = float(np.median(r))
    keep = r >= NOUGHT * med
    return float(np.max(np.abs(p - r)[keep]
                        / np.maximum(r[keep], med)))


def leaf_deltas(after, before):
    """``after - before`` leaf by leaf, in float64 on the host."""
    return [np.asarray(a, np.float64) - np.asarray(b, np.float64)
            for a, b in zip(after, before)]


def served_gap(ref_logits, served) -> float:
    """Widest gap by which a served class's reference logit lies below
    the reference's best."""
    z = np.asarray(ref_logits, np.float64)
    s = np.asarray(served, np.int64)
    if len(z) != len(s) or len(s) == 0:
        raise ValueError(f"{len(z)} logit rows for {len(s)} answers")
    if np.any(s < 0) or np.any(s >= z.shape[1]):
        return float("inf")
    return float(np.max(z.max(axis=1) - z[np.arange(len(s)), s]))
