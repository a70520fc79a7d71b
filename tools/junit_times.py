#!/usr/bin/env python
"""What a run of the tests cost, from its junitxml: the wall, the sum of the
cases' own times (CPU-seconds over the workers), and that sum a file.

    python tools/junit_times.py t1.xml            # the per-file table
    python tools/junit_times.py --total t1.xml    # one line
"""
import collections
import sys
import xml.etree.ElementTree as ET


def main(argv):
    total_only = "--total" in argv
    path = [a for a in argv if a != "--total"][0]
    suite = ET.parse(path).getroot()
    suite = suite if suite.tag == "testsuite" else suite.find("testsuite")
    files = collections.defaultdict(lambda: [0, 0.0])
    for case in suite.iter("testcase"):
        name = case.get("classname", "").split(".Test")[0].replace(".", "/") + ".py"
        files[name][0] += 1
        files[name][1] += float(case.get("time", 0))
    cases = sum(n for n, _ in files.values())
    seconds = sum(t for _, t in files.values())
    print(f"WALL_S={float(suite.get('time', 0)):.0f} CASES={cases} CASE_TIMES_SUM_S={seconds:.0f}")
    if not total_only:
        for name, (n, t) in sorted(files.items(), key=lambda item: -item[1][1]):
            print(f"{t:8.1f} s {n:5d}  {name}")


if __name__ == "__main__":
    main(sys.argv[1:])
