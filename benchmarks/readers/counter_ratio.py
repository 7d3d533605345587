"""A ratio of the program's counters over the window.

spec: ``{"num": [path, ...], "den": [path, ...] | absent, "scale": 1,
"gauge": false}``.  A path is a list of keys into
``{"engine": engine.telemetry(), "kv": kv_stats()}``; one that ends at
a dict of counts stands for their sum.  Numerator and
denominator are sums of their paths' growth over the window (with
``gauge`` the value at the close, not the growth).  ``"den":
"requests"`` divides by the requests due in the window.  No
denominator: the sum itself.  A denominator of 0 gives nothing."""


def read(run, spec):
    def value(snap, path):
        v = run.counter(snap, path)
        return sum(v.values()) if isinstance(v, dict) else v

    def total(paths):
        if spec.get("gauge"):
            return sum(value(run.c1, p) for p in paths)
        return sum(value(run.c1, p) - value(run.c0, p) for p in paths)

    num = total(spec["num"])
    den = spec.get("den")
    if den is None:
        return float(num) * spec.get("scale", 1)
    den = len(run.due_in_window()) if den == "requests" else total(den)
    if not den:
        return None
    return num / den * spec.get("scale", 1)
