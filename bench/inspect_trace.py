"""Print the structure of a profiler trace, to look at one by hand.

    python3 bench/inspect_trace.py <trace dir or .xplane.pb> [--top 25]

For every plane and line: its event count and the names that took most
time, with the stats of the longest event of each name.  This is how the
names that ``devtrace.py`` and the metric readers look for were found.
"""
from __future__ import annotations

import argparse
import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData

    import devtrace
    path = args.path
    if os.path.isdir(path):
        path = devtrace.find_xplane(path)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            total: dict[str, float] = defaultdict(float)
            count: dict[str, int] = defaultdict(int)
            longest: dict[str, object] = {}
            lo, hi = float("inf"), float("-inf")
            for e in line.events:
                d = float(e.duration_ns)
                total[e.name] += d
                count[e.name] += 1
                lo, hi = min(lo, e.start_ns), max(hi, e.start_ns + d)
                if e.name not in longest or d > longest[e.name][0]:
                    longest[e.name] = (d, e)
            n = sum(count.values())
            print(f"  LINE {line.name!r} events={n} span_ns=[{lo}, {hi}]")
            for name, t in sorted(total.items(), key=lambda kv: -kv[1])[
                    :args.top]:
                stats = ""
                try:
                    stats = dict(longest[name][1].stats)
                except Exception as e:  # noqa: BLE001 - best effort display
                    stats = f"<stats unavailable: {e}>"
                print(f"    {t / 1e6:12.3f} ms  x{count[name]:<6d} {name!r}"
                      f"  {str(stats)[:300]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
