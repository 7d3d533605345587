"""1 - (union of the device operations' intervals) / traced window."""


def read(run, spec):
    if run.trace is None:
        return None
    red = run.trace["reduced"]
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
