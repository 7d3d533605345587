"""``setup_s`` of a cell from a checkout, without the window's drain or
the reference: what the runner counts as set-up (process start to the
window's opening: the TPU, the weights, the server, the pass over the
strata, the mix's warm-up) and nothing behind it.

    python3 benchmarks/tests/chip_setup.py <checkout> <cell> \
        <seed> [<seed> ...]

One process a seed (a chip belongs to one process); each prints one
JSON line and appends it to ``chiprun_out/setup.jsonl``.  ``checkout``
is the tree whose program and benchmark are timed (a parent unpacked
from ``git archive`` into a git-ignored directory, or the checkout
itself): time both sides in one call, under one
``JAX_COMPILATION_CACHE_DIR``, after a first run that fills it.  The
window is 5 s long and nothing waits for the streams it leaves open.
"""
import time

T0 = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "tools"))
from procs import each_in_a_process, record  # noqa: E402


def one(tree: str, cell_name: str, seed: int) -> int:
    sys.path.insert(0, tree)
    from benchmarks import run as bench_run
    from benchmarks.harness import spec

    bench_run.DRAIN_S = 0.0
    win = bench_run.run_window(spec.Cell(cell_name, root=tree), seed, 5.0,
                               trace=False, t_start=T0)
    record("setup", {"tree": tree, "cell": cell_name, "seed": seed,
                     "setup_s": win.run.setup_s})
    return 0


def main(argv) -> int:
    if argv[0] == "--one":
        return one(argv[1], argv[2], int(argv[3]))
    return each_in_a_process(__file__, argv[:2], argv[2:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
