"""Tokens received inside the window over the window's length on the
clock.  spec: ``{}``."""


def read(run, spec):
    return run.tokens_between(run.t0, run.t1) / (run.t1 - run.t0)
