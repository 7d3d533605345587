"""Share of the device's busy time that the named programs took in the
traced window.  spec: ``{"programs": [...]}``."""


def read(run, spec):
    secs = sum(run.program_durations(spec["programs"]))
    if not secs:
        return None
    return 100.0 * secs / run.trace["reduced"]["busy_s"]
