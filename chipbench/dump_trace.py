"""Print the layout of a profiler trace: planes, lines, and the events
that took most time on each line, with one example's stats.

    python3 chipbench/dump_trace.py <trace dir or .xplane.pb> [top]

Look at a trace this way before writing a reducer against it: which
planes are devices, which lines hold operations, how kernels are named.
"""
from __future__ import annotations

import glob
import os
import sys
from collections import defaultdict


def main(argv) -> int:
    from jax.profiler import ProfileData

    path = argv[0]
    top = int(argv[1]) if len(argv) > 1 else 25
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                recursive=True), key=os.path.getmtime)[-1]
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            tot, cnt, ex = defaultdict(float), defaultdict(int), {}
            for ev in evs:
                tot[ev.name] += ev.duration_ns
                cnt[ev.name] += 1
                ex.setdefault(ev.name, ev)
            print(f"  LINE {line.name!r}: {len(evs)} events, "
                  f"first {evs[0].start_ns:.0f} last "
                  f"{evs[-1].start_ns + evs[-1].duration_ns:.0f} ns")
            for name in sorted(tot, key=tot.get, reverse=True)[:top]:
                stats = [(k, str(v)[:240]) for k, v in ex[name].stats][:16]
                print(f"    {tot[name] / 1e6:10.3f} ms x{cnt[name]:<6d} "
                      f"{name[:90]!r} {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
