#!/usr/bin/env python3
"""Print what a kept trace holds: planes, lines, and one event of each
kind with its stats. Look at a trace by hand before trusting a
reduction of it.

    python3 perf/run.py ... --trace 1 --keep-trace chiprun_out/t
    python3 perf/dump_trace.py chiprun_out/t
"""

import collections
import glob
import sys

from jax.profiler import ProfileData


def main(trace_dir: str) -> None:
    path = sorted(glob.glob(trace_dir + "/*.xplane.pb"))[-1]
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print("PLANE", repr(plane.name), len(lines))
        for line in lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            seen = collections.Counter()
            for e in events:
                kind = e.name.split(".")[0][:40]
                seen[kind] += 1
                if seen[kind] == 1 and len(seen) <= 25:
                    stats = {k: str(v)[:60] for k, v in list(e.stats)[:8]}
                    print("      ", e.name[:100], e.start_ns,
                          e.duration_ns, stats)


if __name__ == "__main__":
    main(sys.argv[1])
