"""Print the structure of a profiler trace: planes, lines, event counts,
the busiest event names and their stats, and the time range of each line.

    python bench/inspect_trace.py <directory holding an .xplane.pb>

For reading one trace by hand before writing a reduction against it.
"""
from __future__ import annotations

import collections
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness.trace import find_xplane  # noqa: E402


def main(argv=None) -> int:
    from jax.profiler import ProfileData
    argv = sys.argv[1:] if argv is None else argv
    path = argv[0] if argv[0].endswith(".pb") else find_xplane(argv[0])
    pd = ProfileData.from_file(path)
    print(f"# {path} ({Path(path).stat().st_size} bytes)")
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            if not events:
                print(f"  LINE {line.name!r}: 0 events")
                continue
            lo = min(e.start_ns for e in events)
            hi = max(e.end_ns for e in events)
            dur = collections.Counter()
            for e in events:
                dur[e.name] += e.duration_ns
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"[{lo * 1e-9:.6f}, {hi * 1e-9:.6f}] s")
            for name, ns in dur.most_common(6):
                print(f"    {ns * 1e-9:.6f} s  {name[:100]}")
            stats = dict(events[0].stats)
            if stats:
                print(f"    stats of the first event: "
                      f"{ {k: str(v)[:60] for k, v in stats.items()} }")
    return 0


if __name__ == "__main__":
    sys.exit(main())
