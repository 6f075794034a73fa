"""The spread of two sets of runs, as the contract measures it: for each
end-to-end metric the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median, in each set,
and how far the second set's median lies from the first's.

    python3 benchmark/tests/spread.py chiprun_out/<setA> chiprun_out/<setB>
"""

import glob
import json
import statistics
import sys


def values(directory):
    out = {}
    for path in sorted(glob.glob(directory + "/*.t0.out")):
        with open(path) as f:
            lines = f.read().strip().splitlines()
        if not lines:
            continue
        r = json.loads(lines[-1])
        if not r["correct"]:
            print("NOT CORRECT:", path)
        for k, v in r["metrics"].items():
            out.setdefault(k, []).append(v["value"])
    return out


def spread(xs):
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / statistics.median(xs)


sets = [values(d) for d in sys.argv[1:]]
for name in sets[0]:
    row = [f"{name:16s}"]
    for s in sets:
        xs = s[name]
        row.append(f"n={len(xs)} median {statistics.median(xs):9.3f} "
                   f"spread {100 * spread(xs):5.2f}%  "
                   f"[{min(xs):.2f} .. {max(xs):.2f}]")
    if len(sets) == 2:
        a, b = (statistics.median(s[name]) for s in sets)
        row.append(f"B/A {100 * (b / a - 1):+.2f}%")
        # leaving out the first run of each set, as the driver does for setup_s
        a1, b1 = (statistics.median(s[name][1:]) for s in sets)
        row.append(f"(without first runs {100 * (b1 / a1 - 1):+.2f}%)")
    print("  ".join(row))
