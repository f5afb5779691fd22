"""The seconds of a tier-1 run by file, from the junit XML the driver's command writes, kept in the tree.

    python scripts/suite_seconds.py [<xml>] [--machine "<who ran it>"]   writes tests/SECONDS.md
    python scripts/suite_seconds.py [<xml>] --cases 30 [--match <words>]  prints the longest cases, writes nothing
    python scripts/suite_seconds.py <xml> --against <another run's xml>    prints what moved, by file and by test, writes nothing

``tests/test_suite_budget.py`` holds the table's shape and its rules (a row for every test file, no file
over 240 s but those it names, the sum under a constant) and times nothing itself: a PR that adds seconds
runs the suite and renews the table. The table is a record of ONE machine and says which."""

import argparse
import collections
import datetime
import os
import platform
import re
import xml.etree.ElementTree as ET

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TABLE = os.path.join(REPO, "tests", "SECONDS.md")
ROW = re.compile(r"^\| `([^`]+)` \| (\d+) \| ([\d.]+) \|$", re.M)


def cases(xml):
    """(file under ``tests/``, test name, seconds) of every case; a case's seconds hold its set-up and tear-down."""
    for case in ET.parse(xml).getroot().iter("testcase"):
        module = case.get("classname").split(".")
        module = module[1:] if module[0] == "tests" else module
        yield "/".join(module) + ".py", case.get("name"), float(case.get("time"))


def by_file(xml):
    files = collections.defaultdict(lambda: [0, 0.0])
    for path, _, seconds in cases(xml):
        files[path][0] += 1
        files[path][1] += seconds
    return dict(files)


def moved(xml, other, by, most=25):
    """Lines of ``other``'s seconds -> ``xml``'s, summed by ``by(file, test name)``, the largest moves first."""
    sums = collections.defaultdict(lambda: [0.0, 0.0])
    for side, path in enumerate((other, xml)):
        for file, name, seconds in cases(path):
            sums[by(file, name)][side] += seconds
    rows = sorted(sums.items(), key=lambda kv: -abs(kv[1][1] - kv[1][0]))[:most]
    return [f"{was:8.1f} -> {now:8.1f}  {now - was:+8.1f}  {key}" for key, (was, now) in rows]


def read_table(path=TABLE):
    """{file: (cases, seconds)} and the sum the table states, as ``write_table`` wrote them."""
    with open(path, encoding="utf-8") as f:
        text = f.read()
    rows = {name: (int(n), float(s)) for name, n, s in ROW.findall(text)}
    return rows, float(re.search(r"^sum: ([\d.]+) s", text, re.M).group(1))


def write_table(files, said, machine, path=TABLE):
    rows = sorted(files.items(), key=lambda kv: -kv[1][1])
    lines = ["# Tier-1's seconds by file", "",
             "Written by `scripts/suite_seconds.py` from the junit XML of one whole run of the driver's command",
             "(`-n 6 --dist loadfile`); held by `tests/test_suite_budget.py`. A case's seconds hold its fixtures',",
             "so a module's engine is in the case that first asks for it.", "",
             f"machine: {machine}", f"date: {datetime.date.today().isoformat()}",
             f"run: {said}", f"sum: {sum(s for _, s in files.values()):.1f} s over {len(files)} files, {sum(n for n, _ in files.values())} cases", "",
             "| file | cases | seconds |", "|---|---|---|"]
    lines += [f"| `{name}` | {n} | {s:.1f} |" for name, (n, s) in rows]
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("xml", nargs="?", default="/tmp/_t1.xml")
    ap.add_argument("--machine", default=f"{platform.node()}, {os.cpu_count()} cores")
    ap.add_argument("--cases", type=int, default=0, help="print the N longest cases and write nothing")
    ap.add_argument("--match", default="", help="with --cases: only the cases whose file::name holds this")
    ap.add_argument("--against", help="another run's junit XML: print what moved from it to this one and write nothing")
    args = ap.parse_args()
    if args.against:
        total = [sum(c[2] for c in cases(path)) for path in (args.against, args.xml)]
        print(f"sum {total[0]:.1f} -> {total[1]:.1f} s ({100 * (total[1] / total[0] - 1):+.1f}%)\nby file:")
        print("\n".join(moved(args.xml, args.against, lambda file, name: file)))
        print("by test (a test's cases in every file that collects it):")
        print("\n".join(moved(args.xml, args.against, lambda file, name: name.split("[")[0])))
        return
    if args.cases:
        picked = sorted((c for c in cases(args.xml) if args.match in f"{c[0]}::{c[1]}"), key=lambda c: -c[2])
        print(f"{len(picked)} cases, {sum(c[2] for c in picked):.1f} s")
        for path, name, seconds in picked[:args.cases]:
            print(f"{seconds:8.1f}  {path}::{name}")
        return
    suite = next(ET.parse(args.xml).getroot().iter("testsuite"))
    said = ", ".join(f"{k} {suite.get(k)}" for k in ("tests", "failures", "errors", "skipped")) + f", wall {float(suite.get('time')):.0f} s"
    files = by_file(args.xml)
    write_table(files, said, args.machine)
    print(f"{TABLE}: {len(files)} files, {sum(s for _, s in files.values()):.1f} s; {said}")


if __name__ == "__main__":
    main()
