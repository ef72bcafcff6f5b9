"""What a trace holds, for a first look by hand: planes, their lines, and on
each line the events that took most time, with their stats.

    python3 benchmark/tests/trace_inventory.py <file.xplane.pb> [events per line]
"""
import sys
from collections import defaultdict

from jax.profiler import ProfileData


def main(path: str, top: int = 12) -> None:
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            total, count, sample = defaultdict(float), defaultdict(int), {}
            first, last = None, 0.0
            for e in line.events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
                sample.setdefault(e.name, e)
                first = e.start_ns if first is None else min(first, e.start_ns)
                last = max(last, e.start_ns + e.duration_ns)
            print(f"  LINE {line.name!r}: {sum(count.values())} events, "
                  f"{len(total)} names, from {first} to {last} ns")
            for name in sorted(total, key=total.get, reverse=True)[:top]:
                stats = {k: str(v)[:70] for k, v in sample[name].stats}
                print(f"    {total[name] / 1e6:12.3f} ms {count[name]:7d}x "
                      f"{name[:70]!r} {stats}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 12)
