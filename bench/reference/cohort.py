"""What a region round's cohort has to hold, checked against the region's
own rows rather than taken from the program.

Each data-holding node trains ``H`` local steps on samples of its own
pool.  A pool of ``n`` samples draws ``ceil(n / H)`` of them per step,
capped at the batch cap ``B``; a pool of more than ``4 H B`` samples (one
that offloading filled) may draw up to ``n / (4 H)``, at most ``8 B``.
Offloading moves samples between nodes and never makes or drops one, so
the pools of a region round add up to the region's rows.
"""
from __future__ import annotations

import hashlib

import numpy as np


def batch_width(n: int, h: int, cap: int) -> int:
    """Samples per local step of a pool of ``n`` over ``h`` steps."""
    if n <= 0:
        return 0
    eff = min(max(cap, n // (4 * h)), 8 * cap)
    return max(1, min(-(-n // h), eff))


def _digest(row) -> bytes:
    return hashlib.blake2b(np.ascontiguousarray(row).tobytes(),
                           digest_size=16).digest()


class Rows:
    """A region's rows and labels, found by content."""

    def __init__(self, x, y):
        self.n = len(x)
        self._label = {_digest(r): int(v) for r, v in zip(x, y)}

    def label(self, row):
        return self._label.get(_digest(row))


def faults(buckets, rows: Rows, h: int, cap: int) -> int:
    """Departures of one region round's cohort from the rules above.

    ``buckets`` is ``[(xs, ys, mask, sizes, n_real)]``.  Counted: a padding
    slot that holds a size or a sample; a client whose steps are not ``h``,
    or one of whose steps draws another number of samples than its pool
    gives; a sample that is no row of the region, or carries another
    label; pools that do not add up to the region's rows."""
    count = 0
    total = 0
    for xs, ys, mask, sizes, n_real in buckets:
        m = np.asarray(mask) > 0
        count += int(np.count_nonzero(sizes[n_real:]))
        count += int(np.count_nonzero(m[n_real:].any(axis=(1, 2))))
        for c in range(n_real):
            n = int(sizes[c])
            total += n
            if xs.shape[1] != h:
                count += 1
                continue
            want = batch_width(n, h, cap)
            for s in range(h):
                valid = m[c, s]
                count += int(valid.sum() != want)
                for x, y in zip(xs[c, s][valid], ys[c, s][valid]):
                    count += int(rows.label(x) != int(y))
    return count + int(total != rows.n)
