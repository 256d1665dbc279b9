"""Print the planes, lines and first events of an ``.xplane.pb`` trace.

    python chipbench/tests/dump_trace.py <trace.xplane.pb>
"""
import sys

from jax.profiler import ProfileData

for plane in ProfileData.from_file(sys.argv[1]).planes:
    lines = list(plane.lines)
    print(f"plane {plane.name!r}: {len(lines)} lines")
    for line in lines[:12]:
        events = list(line.events)
        print(f"  line {line.name!r}: {len(events)} events")
        for e in events[:3]:
            print(f"    {e.name!r} start {e.start_ns} dur {e.duration_ns}")
