#!/usr/bin/env python3
"""Where a tier-1 run's time went, from its junit file:

    python3 tools/tier1_times.py <junit.xml>

The driver runs ``pytest tests/ -n 6 --dist loadfile --junitxml=...``
under a limit of 1,470 s, so a FILE is one worker's: the run is no
shorter than its longest file, nor than the sum of all files over six —
and xdist hands the files out by their NUMBER OF TESTS, largest first
(``--loadscope-reorder``, its default; ties in collection order), each to
the first free worker: a long file of few cases starts last and is the
run's tail.  Prints seconds by file (a module fixture's time is its first test's;
and the file's skipped cases, where it has any: a file whose fixture
skipped in silence shows here), the cases over 45 s, the wall those
sums give in xdist's order and the files that end last in it; exits 1 where a file is over a sixth of the limit — split it
by what it holds before adding tests to it (ROADMAP D12).  Read a run
under six workers' load: a file alone reads much faster and proves
nothing."""

from __future__ import annotations

import collections
import heapq
import sys
import xml.etree.ElementTree as ET

LIMIT_S, WORKERS = 1470.0, 6
FILE_S = LIMIT_S / WORKERS      # 245: one worker's share of the limit
LONG_CASE_S = 45.0


def file_of(classname: str) -> str:
    """``tests.test_x.TestY`` -> ``tests/test_x.py``: the module is the
    first part named like a test file."""
    parts = classname.split(".")
    for i, part in enumerate(parts):
        if part.startswith("test_"):
            return "/".join(parts[:i + 1]) + ".py"
    return "/".join(parts) + ".py"


def schedule(files) -> list:
    """(end, start, cases, file), last to end first: ``files`` (seconds,
    cases, name, skipped) handed out as xdist's ``loadfile`` does."""
    free = [(0.0, worker) for worker in range(WORKERS)]
    ends = []
    for seconds, n, name, _ in sorted(files, key=lambda f: (-f[1], f[2])):
        start, worker = heapq.heappop(free)
        heapq.heappush(free, (start + seconds, worker))
        ends.append((start + seconds, start, n, name))
    return sorted(ends, reverse=True)


def read(path: str) -> dict:
    files = collections.defaultdict(lambda: [0, 0.0, 0])
    cases = []
    for case in ET.parse(path).getroot().iter("testcase"):
        seconds = float(case.get("time", 0.0))
        name = file_of(case.get("classname", ""))
        files[name][0] += 1
        files[name][1] += seconds
        files[name][2] += case.find("skipped") is not None
        if seconds > LONG_CASE_S:
            cases.append((seconds, f"{name}::{case.get('name')}"))
    total = sum(s for _, s, _ in files.values())
    longest = max((s for _, s, _ in files.values()), default=0.0)
    by_seconds = sorted(((s, n, name, skipped)
                         for name, (n, s, skipped) in files.items()),
                        reverse=True)
    ends = schedule(by_seconds)
    return {"files": by_seconds, "cases": sorted(cases, reverse=True),
            "total_s": total, "longest_s": longest,
            "wall_s": max(longest, total / WORKERS),
            "scheduled_s": ends[0][0] if ends else 0.0, "last": ends[:3],
            "over": sorted(name for name, (_, s, _) in files.items()
                           if s > FILE_S)}


def main(argv) -> int:
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    got = read(argv[1])
    wall = got["wall_s"] or 1.0
    print(f"{'seconds':>9} {'cases':>6} {'% wall':>7}  file")
    for seconds, n, name, skipped in got["files"]:
        if seconds >= 1.0 or skipped:
            print(f"{seconds:9.1f} {n:6d} {100 * seconds / wall:7.1f}  {name}"
                  + f"  ({skipped} skipped)" * bool(skipped))
    small = [(s, n) for s, n, _, skipped in got["files"]
             if s < 1.0 and not skipped]
    print(f"{sum(s for s, _ in small):9.1f} {sum(n for _, n in small):6d}"
          f" {'':7}  ({len(small)} files under 1 s)")
    for seconds, case in got["cases"]:
        print(f"case over {LONG_CASE_S:.0f} s: {seconds:7.1f}  {case}")
    print(f"total {got['total_s']:.1f} s in {len(got['files'])} files; "
          f"over {WORKERS} workers {got['total_s'] / WORKERS:.1f} s; "
          f"longest file {got['longest_s']:.1f} s; "
          f"the wall is at least {got['wall_s']:.1f} s, "
          f"{got['scheduled_s']:.1f} s in xdist's order "
          f"of {LIMIT_S:.0f}")
    for end, start, n, name in got["last"]:
        print(f"ends last in that order: {name} ({n} cases) "
              f"{start:.0f} -> {end:.0f} s")
    for name in got["over"]:
        print(f"OVER {FILE_S:.0f} s (a sixth of the limit): {name}")
    return 1 if got["over"] else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
