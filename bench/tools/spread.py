"""Spread of each end-to-end metric over the runs of a set.

    python3 bench/tools/spread.py <set1 result files...> -- <set2 result files...>

Each file is one run's stdout; its last line is the result. Prints, per set
and metric, the median and the interquartile range as a share of the median
(``statistics.quantiles(values, n=4)``), the share that the bound rule of
five times the widest spread gives, and whether every run was correct.
"""
import json
import statistics
import sys


def last_json(path):
    with open(path) as f:
        lines = [ln for ln in f if ln.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def iqr_share(vals):
    q1, _, q3 = statistics.quantiles(vals, n=4)
    return (q3 - q1) / statistics.median(vals)


def main(argv):
    sets, cur = [], []
    for a in argv:
        if a == "--":
            sets.append(cur)
            cur = []
        else:
            cur.append(a)
    sets.append(cur)
    widest = {}
    for i, files in enumerate(sets, 1):
        res = [r for r in map(last_json, files) if r is not None]
        print(f"set {i}: {len(res)} results of {len(files)} runs, correct "
              f"{sum(bool(r['correct']) for r in res)}, check "
              f"{[{k: v['value'] for k, v in r['check'].items()} for r in res]}")
        names = sorted({k for r in res for k in r["metrics"]})
        for n in names:
            vals = [r["metrics"][n]["value"] for r in res if n in r["metrics"]]
            med = statistics.median(vals)
            sp = iqr_share(vals)
            # the spread with the run farthest from the median left out
            trim = sorted(vals, key=lambda v: abs(v - med))[:-1]
            widest[n] = max(widest.get(n, 0.0), sp)
            print(f"  {n}: median {med:.6g}, spread {sp:.4f}, trimmed {iqr_share(trim):.4f}, "
                  f"values {[round(v, 4) for v in vals]}")
    for n, sp in widest.items():
        print(f"widest {n}: {sp:.4f} -> bound {max(0.01, 5 * sp):.4f}")


if __name__ == "__main__":
    main(sys.argv[1:])
