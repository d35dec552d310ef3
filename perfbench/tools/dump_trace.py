#!/usr/bin/env python3
"""Print what is in an ``.xplane.pb``: planes, lines, event counts, and a few
events of each line with every stat. For looking at a trace by hand before
changing ``harness/trace_reduce.py``.

    python3 perfbench/tools/dump_trace.py <file.xplane.pb> [events per line]
"""

import collections
import sys


def main(argv):
    from jax.profiler import ProfileData

    show = int(argv[1]) if len(argv) > 1 else 4
    for plane in ProfileData.from_file(argv[0]).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            span = (min(e.start_ns for e in events), max(e.start_ns + e.duration_ns for e in events))
            print(f"  LINE {line.name!r}: {len(events)} events, {span[0]:.0f}..{span[1]:.0f} ns")
            names = collections.Counter(e.name.split(".")[0] for e in events)
            print(f"    names: {names.most_common(12)}")
            categories = collections.Counter(
                str(dict(e.stats).get("hlo_category")) for e in events)
            print(f"    hlo_category: {categories.most_common(12)}")
            longest = sorted(events, key=lambda e: -e.duration_ns)[:show]
            for e in events[:show] + longest:
                stats = {k: (str(v)[:160]) for k, v in e.stats}
                print(f"    {e.name!r} start {e.start_ns:.0f} dur {e.duration_ns:.0f} {stats}")


if __name__ == "__main__":
    main(sys.argv[1:])
