"""The mean of one of the run's series over the whole window.
spec: ``{"series": ...}``."""


def read(run, spec):
    values = run.series(spec["series"])
    if not values:
        return None
    return sum(values) / len(values)
