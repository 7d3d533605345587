"""Share of the batch's slots that produced a token: tokens received
in the window over the steps the batcher ran in it times its slots.
spec: ``{"steps": path}``."""


def read(run, spec):
    steps = run.delta(spec["steps"])
    if not steps:
        return None
    slots = run.cfg["service"]["decode_slots"]
    return 100.0 * run.tokens_between(run.t0, run.t1) / (steps * slots)
