"""Look at one trace by hand: planes, lines, event counts, the longest
events.  ``python3 chipbench/tools/trace_dump.py <dir or .xplane.pb>``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import xplane  # noqa: E402


def main() -> None:
    path = sys.argv[1]
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    planes = xplane.load(path)
    for pname, lines in planes.items():
        print(f"plane {pname!r}")
        for lname, evs in lines.items():
            dur = sum(e - s for _, s, e in evs)
            print(f"  line {lname!r}: {len(evs)} events, {dur:.4f}s")
            for n, s, e in sorted(evs, key=lambda x: x[1] - x[2])[:6]:
                print(f"      {e - s:.6f}s  {n[:140]}")
    print(xplane.summarise(planes))


if __name__ == "__main__":
    main()
