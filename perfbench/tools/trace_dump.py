"""Print the structure of a profiler trace: planes, lines, event counts, sample events.

    python3 perfbench/tools/trace_dump.py <path to .xplane.pb>
"""

import sys

from jax.profiler import ProfileData


def main(path: str) -> int:
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r} stats={dict(plane.stats)}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r} events={len(events)}")
            for ev in events[:6]:
                stats = dict(ev.stats)
                print(f"    {ev.name!r} start={ev.start_ns} dur={ev.duration_ns} stats={stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
