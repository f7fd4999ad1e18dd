"""Seconds a test file takes on one loaded worker, from a tier-1 run's
junit file, into ``tests/data/tier1_file_seconds.json``: the table by which
``tests/conftest.py`` hands the heaviest files out first.

    python scripts/tier1_seconds.py /tmp/_t1.xml            # write the table
    python scripts/tier1_seconds.py /tmp/_t1.xml --over 30  # print, write nothing

Take it from a run that started with an empty compile cache
(``JAX_COMPILATION_CACHE_DIR=<an empty directory>``): that is the order a
changed tree runs in.  A file the table does not have goes first."""
import argparse
import collections
import json
import os
import xml.etree.ElementTree as ET

TABLE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "tests", "data", "tier1_file_seconds.json")


def file_seconds(junit_xml):
    seconds, tests = collections.Counter(), collections.Counter()
    for case in ET.parse(junit_xml).getroot().iter("testcase"):
        name = case.get("classname").replace(".", "/") + ".py"
        seconds[name] += float(case.get("time"))
        tests[name] += 1
    return seconds, tests


def main():
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("junit_xml")
    parser.add_argument("--over", type=float, default=None, metavar="SECONDS",
                        help="print the files over this many seconds "
                             "instead of writing the table")
    args = parser.parse_args()
    seconds, tests = file_seconds(args.junit_xml)
    if args.over is not None:
        print(f"{sum(seconds.values()):.0f} s in {sum(tests.values())} tests "
              f"of {len(seconds)} files")
        for name, s in seconds.most_common():
            if s >= args.over:
                print(f"{s:7.0f} s {tests[name]:4d} tests  {name}")
        return
    with open(TABLE, "w") as f:
        json.dump({name: round(s) for name, s in sorted(seconds.items())},
                  f, indent=0)
        f.write("\n")
    print(f"{len(seconds)} files -> {TABLE}")


if __name__ == "__main__":
    main()
