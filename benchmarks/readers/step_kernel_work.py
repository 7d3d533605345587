"""One named kernel of the decode step against what the model needs of
it, over the traced window.

spec: ``{"programs": ["jit_step"], "kernel": "<name>", "work":
"<function of the configuration's model module>"}``.

Device time: the trace's operations whose name starts with ``kernel``
inside the named programs (``readers/kernel_work.py``'s
``kernel_time``), a step.  Work: ``work(cfg, lives, steps, counters)``
of the model module for the tokens received in the traced window (as
``decode_work`` takes them), a step; ``counters`` is the growth of
``kv_stats()["moe"]`` over the whole window scaled to the traced steps,
or None where the program keeps no such counts.  The value is the share
of the roofline: the least time the chip could take (the larger of
FLOPs / peak and bytes / bandwidth) over the kernel's device time.

Nothing, never 0, where there is no trace, the model module has no such
count, the kernel is not among the operations the runner kept (it keeps
the ten longest), or its calls do not add up to the step's executions
times the model module's ``kernel_calls``.
"""
from benchmarks.harness import spec as _spec
from benchmarks.harness.peaks import least_seconds


def moe_counters(run, steps: int):
    """The expert layers' counts over the window, scaled to ``steps``
    steps; None where the program has none."""
    try:
        c0, c1 = run.c0["kv"]["moe"], run.c1["kv"]["moe"]
    except (KeyError, TypeError):
        return None
    n = c1["steps"] - c0["steps"]
    if n <= 0:
        return None
    return {k: (c1[k] - c0[k]) * steps / n
            for k in ("rows", "local_pairs", "experts_touched")}


def read(run, spec):
    work = getattr(run.model, spec["work"], None)
    tr = run.trace
    if tr is None or run.peaks is None or work is None:
        return None
    execs = len(run.program_durations(spec["programs"]))
    secs, calls = _spec.load_module("readers", "kernel_work").kernel_time(
        run, spec)
    steps = tr["steps_b"] - tr["steps_a"]
    lives = run.decoded_between(tr["ta"], tr["tb"])
    if not execs or not secs or not steps or not lives:
        return None
    per_exec = run.model.kernel_calls(run.cfg, spec["kernel"])
    if abs(calls - per_exec * execs) > per_exec:
        return None         # part of its calls fell off the list
    flops, nbytes = work(run.cfg, lives, steps, moe_counters(run, steps))
    least, _bound = least_seconds(flops / steps, nbytes / steps, run.peaks)
    return 100.0 * least / (secs / execs)
