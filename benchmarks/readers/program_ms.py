"""A percentile of the device duration of one compiled program's
executions in the traced window.  spec: ``{"programs": [...], "q": 50}``."""
from benchmarks.harness.series import percentile


def read(run, spec):
    durs = run.program_durations(spec["programs"])
    if not durs:
        return None
    return percentile(durs, float(spec["q"])) * 1e3
