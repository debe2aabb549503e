"""Look at one trace by hand: planes, lines, and the operations that took
most time.  `python benchmarks/reduce/describe.py <trace.xplane.pb>`"""

import collections
import sys


def describe(path: str, top: int = 25) -> None:
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            total = collections.Counter()
            count = 0
            for e in line.events:
                total[e.name[:160]] += e.duration_ns
                count += 1
            print(f"  LINE {line.name!r}: {count} events")
            if plane.name.startswith("/device:"):
                for name, ns in total.most_common(top):
                    print(f"    {ns / 1e6:10.3f} ms  {name}")


if __name__ == "__main__":
    describe(sys.argv[1])
