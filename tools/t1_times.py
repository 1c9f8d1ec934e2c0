"""Where tier-1's time goes: `python tools/t1_times.py <junit.xml> [<junit.xml>]`.

Reads what `pytest --junitxml` wrote: seconds of test time by file and by
function (a function's cases summed), the sum, the sum over the six workers
the driver runs, the dearest file (one worker's under `--dist loadfile`)
and the cases over 20 s. Given two runs it prints them side by side."""

import sys
import xml.etree.ElementTree as ET
from collections import Counter

WORKERS, DEAR_S, ROWS = 6, 20.0, 20


def read(path):
    """(seconds by file, by function, by case) of one run."""
    files, funcs, cases = Counter(), Counter(), Counter()
    for case in ET.parse(path).getroot().iter("testcase"):
        file = case.get("classname", "").split(".")[1] + ".py"
        name, seconds = case.get("name"), float(case.get("time", 0))
        files[file] += seconds
        funcs[f"{file}::{name.split('[')[0]}"] += seconds
        cases[f"{file}::{name}"] += seconds
    return files, funcs, cases


def table(title, runs, rows=ROWS):
    """The `rows` dearest keys of the first run, every run beside it."""
    print(f"\n{title}")
    for key, _ in runs[0].most_common(rows):
        print("  " + "".join(f"{r[key]:9.1f}" for r in runs) + f"  {key}")


def main(paths):
    runs = [read(p) for p in paths]
    for path, (files, _, cases) in zip(paths, runs):
        total = sum(files.values())
        dearest, seconds = files.most_common(1)[0]
        over = [s for s in cases.values() if s >= DEAR_S]
        print(f"{path}: {len(cases)} cases in {len(files)} files, "
              f"sum {total:.0f} s, sum/{WORKERS} {total / WORKERS:.0f} s, "
              f"dearest file {dearest} {seconds:.0f} s, "
              f"{len(over)} cases of {DEAR_S:g} s or more "
              f"({sum(over):.0f} s)")
    table("seconds by file", [r[0] for r in runs])
    table("seconds by function (cases summed)", [r[1] for r in runs])
    table(f"cases of {DEAR_S:g} s or more", [
        Counter({k: s for k, s in r[2].items() if s >= DEAR_S})
        for r in runs], rows=10 ** 6)


if __name__ == "__main__":
    if len(sys.argv) not in (2, 3):
        sys.exit(__doc__)
    main(sys.argv[1:])
