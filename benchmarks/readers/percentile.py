"""A percentile of one of the run's series (``itl_ms``, ``ttft_ms``,
``ttft_first_turn_ms``, ``late_ms``) over the whole window.
spec: ``{"series": ..., "q": 50}``."""
from benchmarks.harness.series import percentile


def read(run, spec):
    values = run.series(spec["series"])
    if not values:
        return None
    return percentile(values, float(spec["q"]))
