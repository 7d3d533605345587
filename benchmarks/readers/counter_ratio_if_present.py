"""``counter_ratio``, for counters that a program may not have yet.

The spec is ``counter_ratio``'s and so is the arithmetic (this calls
it).  Where a path leads to no counter, because the program under the
benchmark is older than the counter, the metric is left out of the
line instead of failing the run."""

from benchmarks.harness import spec as _spec


def read(run, spec):
    try:
        return _spec.load_module("readers", "counter_ratio").read(run, spec)
    except KeyError:
        return None
