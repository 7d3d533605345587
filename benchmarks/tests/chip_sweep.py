"""The knee of an open-loop cell, found once by a sweep on the chip.

    chiprun -- python3 benchmarks/tests/chip_sweep.py <cell> <seconds> <seed>[,<seed>...] <rate> [<rate> ...]

One process a seed and rate: the cell as it stands with ``rate_rps`` replaced,
a window of ``seconds``.  Prints, a rate, what a user would feel and
whether a backlog grew (the median wait for the first token in each
third of the window, the streams still open at the close),
and appends the line to ``chiprun_out/sweep.jsonl``.  The rate a cell
then runs at is written into ``cells/<cell>.json`` as a number.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "tools"))
from procs import ROOT, each_in_a_process, record  # noqa: E402


def p50(xs: list):
    xs = sorted(xs)
    return xs[len(xs) // 2] if xs else None


def one(cell_name: str, seconds: float, seed: int, rate: float) -> int:
    sys.path.insert(0, ROOT)
    from benchmarks import run as bench_run
    from benchmarks.harness import compare, spec

    cell = spec.Cell(cell_name)
    cell.traffic["rate_rps"] = rate
    win = bench_run.run_window(cell, seed, seconds, trace=False)
    res = win.judged(compare.compare(win.reference(), win.sample))
    run = win.run
    due = run.due_in_window()
    thirds = [[(r.stamps[0] - r.due) * 1e3 for r in due if r.stamps
               and k <= 3 * (r.due - run.t0) / (run.t1 - run.t0) < k + 1]
              for k in range(3)]
    record("sweep", {
        "cell": cell_name, "seed": seed, "seconds": seconds,
        "rate_rps": rate, "correct": res["correct"],
        "attempted": res["attempted"], "failed": res["failed"],
        "tokens_s": run.tokens_between(run.t0, run.t1) / (run.t1 - run.t0),
        "ttft_p50_ms_by_thirds": [p50(t) for t in thirds],
        "ttft_ms_max": max((x for t in thirds for x in t), default=None),
        "open_at_close": sum(1 for r in due if r.closed is None
                             or r.closed > run.t1),
        "metrics": {k: v["value"] for k, v in res["metrics"].items()}})
    return 0


def main(argv) -> int:
    if argv[0] == "--one":
        return one(argv[1], float(argv[2]), int(argv[3]), float(argv[4]))
    rc = 0
    for seed in argv[2].split(","):
        rc = each_in_a_process(__file__, [*argv[:2], seed], argv[3:]) or rc
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
