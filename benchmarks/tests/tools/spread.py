"""Spreads of sets of runs, by the rule the bounds are set from.

    python3 benchmarks/tests/tools/spread.py chiprun_out/<set A> chiprun_out/<set B> ...

Each directory holds one ``<seed>.out`` a run (``runs.sh`` writes them).
For each metric: every set's median and its spread (the distance
between the first and third quartile as ``statistics.quantiles(n=4)``
gives them, over the median), the wider spread, five times it, and how
far the second set's median lies from the first's.  Also the numbers
compared in every run, by seed.
"""
import glob
import json
import os
import statistics
import sys


def load(d):
    runs = {}
    for p in sorted(glob.glob(os.path.join(d, "*.out"))):
        lines = open(p).read().strip().splitlines()
        if lines and lines[-1].startswith("{"):
            runs[os.path.basename(p)[:-4]] = json.loads(lines[-1])
    return runs


def main(dirs):
    sets = [load(d) for d in dirs]
    names = sorted({m for s in sets for r in s.values() for m in r["metrics"]})
    for m in names:
        rows = []
        for d, s in zip(dirs, sets):
            vals = [r["metrics"][m]["value"] for r in s.values()
                    if m in r["metrics"]]
            if len(vals) < 2:
                rows.append((d, vals, None, None))
                continue
            q = statistics.quantiles(vals, n=4)
            med = statistics.median(vals)
            rows.append((d, vals, med, (q[2] - q[0]) / med))
        print(m)
        for d, vals, med, spread in rows:
            if med is None:
                print(f"  {d}: {vals}")
                continue
            print(f"  {d}: n={len(vals)} median {med:.5g} spread "
                  f"{100 * spread:.3f}%  min {min(vals):.5g} max {max(vals):.5g}")
        spreads = [r[3] for r in rows if r[3] is not None]
        meds = [r[2] for r in rows if r[2] is not None]
        if spreads:
            line = f"  widest spread {100 * max(spreads):.3f}% -> x5 = {500 * max(spreads):.2f}%"
            if len(meds) >= 2:
                line += f"; second median {100 * (meds[1] / meds[0] - 1):+.3f}% from the first"
            print(line)
    print("compared, by run:")
    for d, s in zip(dirs, sets):
        for seed, r in s.items():
            c = {k: v["value"] for k, v in r["compared"].items()}
            print(f"  {d} {seed}: correct={r['correct']} failed={r['failed']}/"
                  f"{r['attempted']} widest {c['logit_gap_std']:.4f} mean "
                  f"{c.get('mean_gap_std', float('nan')):.2e} off "
                  f"{c['tokens_off_best']}/{c['tokens_compared']} "
                  f"peak {r['device']['memory_peak_bytes'] / 1e9:.2f} GB")


if __name__ == "__main__":
    main(sys.argv[1:])
