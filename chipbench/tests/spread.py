"""
Read the result lines of chip_sets.sh and print, per metric, each set's
median and spread (distance between the quartiles over the median) as the
driver takes them: two sets of 6 runs after the compiling run 0.

    python3 chipbench/tests/spread.py chiprun_out/sets/<cell> [runs_per_set]
"""

import json
import pathlib
import statistics
import sys


def spread(values):
    q = statistics.quantiles(values, n=4, method="inclusive")
    return (q[2] - q[0]) / statistics.median(values)


def at_marks(checks, per_set):
    """The same study at shorter windows: the harness marks its progress
    every 5 s, so one set of long runs says what a shorter `run_seconds`
    would have spread by (rates from the window's start to each mark)."""
    for k in range(min(len(c["marks"]) for c in checks)):
        row = []
        for chunk in (checks[i:i + per_set]
                      for i in range(0, len(checks), per_set)):
            if len(chunk) < 3:
                continue
            steps = [c["marks"][k][1] / c["marks"][k][0] for c in chunk]
            sim = [(c["marks"][k][2] - c["sim_before"]) / c["marks"][k][0]
                   for c in chunk]
            row.append(f"steps/s {statistics.median(steps):.5g} "
                       f"spread {100 * spread(steps):.3f}%, sim/s "
                       f"{statistics.median(sim):.5g} "
                       f"spread {100 * spread(sim):.3f}%")
        print(f"  first {checks[0]['marks'][k][0]:.0f} s: " + " | ".join(row))


def main(directory, per_set=6):
    logs = sorted(pathlib.Path(directory).glob("run*.log"),
                  key=lambda p: int(p.stem[3:]))
    lines, checks = [], []
    for log in logs:
        text = log.read_text().strip().splitlines()
        if not text or not text[-1].startswith("{"):
            print(f"{log.name}: no result line")
            continue
        lines.append((int(log.stem[3:]), json.loads(text[-1])))
        if lines[-1][0] > 0:
            checks.append(json.loads(next(
                t for t in text if t.startswith("chipbench checks: ")
            ).split(": ", 1)[1]))
    lines = [(i, line) for i, line in lines if i > 0]      # run 0 compiles
    print(f"{directory}: {len(lines)} runs after run 0; correct: "
          f"{[line['correct'] for _, line in lines]}")
    sets = [lines[k:k + per_set] for k in range(0, len(lines), per_set)]
    for name in lines[0][1]["metrics"] if lines else []:
        row = []
        for chunk in sets:
            values = [line["metrics"][name]["value"] for _, line in chunk]
            if len(values) >= 3:
                row.append((statistics.median(values), spread(values),
                            min(values), max(values)))
        print(name, " | ".join(
            f"median {m:.6g} spread {100 * s:.3f}% [{lo:.6g}, {hi:.6g}]"
            for m, s, lo, hi in row))
        if len(row) == 2:
            print(f"  second median / first: {row[1][0] / row[0][0]:.5f}; "
                  f"wider spread {100 * max(row[0][1], row[1][1]):.3f}%")


    if checks and all(c["marks"] for c in checks):
        at_marks(checks, per_set)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 6)
