"""The control on the chip, at a cell's own size.

    chiprun -- python3 benchmarks/tests/chip_control.py <cell> <seconds> <seed> [<seed> ...]

One process a seed (a chip belongs to one process, and a process's
weights are its seed's): a short window of the cell at its own load,
then the run's own comparison twice over the same sample of what was
served: of the served tokens (``correct`` has to read true), and with
the control (the configuration's reference in int8) put in the
program's place (``control_correct`` has to read false).  Prints both
with every number compared, appends them to ``chiprun_out/control.jsonl``
and exits non-zero where either reads otherwise.  The benchmark's own
runs never run the control.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "tools"))
from procs import ROOT, each_in_a_process, record  # noqa: E402


def one(cell_name: str, seconds: float, seed: int) -> int:
    sys.path.insert(0, ROOT)
    from benchmarks import run as bench_run
    from benchmarks.harness import compare, spec

    win = bench_run.run_window(spec.Cell(cell_name), seed, seconds,
                               trace=False)
    ref = win.reference()
    served = win.judged(compare.compare(ref, win.sample))
    control = win.judged(compare.compare(ref, win.sample,
                                         tokens_of=win.reference(int8=True)))
    record("control", {
        "cell": cell_name, "seed": seed, "seconds": seconds,
        "correct": served["correct"], "control_correct": control["correct"],
        "compared": served["compared"],
        "control_compared": control["compared"],
        "metrics": {k: v["value"] for k, v in served["metrics"].items()}})
    return 0 if served["correct"] and not control["correct"] else 1


def main(argv) -> int:
    if argv[0] == "--one":
        return one(argv[1], float(argv[2]), int(argv[3]))
    return each_in_a_process(__file__, argv[:2], argv[2:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
