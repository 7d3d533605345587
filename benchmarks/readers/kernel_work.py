"""One named kernel of the filling programs against what the model
needs of it, over the traced window.

spec: ``{"programs": [...], "kernel": "<name>", "work": "<function of
the configuration's model module>"}``.

Device time: the trace's operations whose name starts with ``kernel``
inside the named programs (a Pallas kernel runs under its own name;
``harness/xplane.py`` adds every layer's calls up under it).  Work:
``work(cfg, ctx_lens)`` of the model module for the contexts of the
requests whose first token arrived in the traced window (as
``prefill_work`` takes them).  The value is the share of the roofline:
the least time the chip could take (the larger of FLOPs / peak and
bytes / bandwidth) over that device time.

Nothing, never 0, where there is no trace, the model module has no
such count, or the kernel's calls do not add up to the programs'
executions times the model module's ``kernel_calls``.  THE RUNNER KEEPS
ONLY THE TEN LONGEST OPERATIONS of a trace (``run.py`` reduces it and
deletes the file before any reader runs), and a kernel that takes 1%
of the device's time is not always among them: so no entry of
``BENCHMARK.json`` names this reader yet (PERF.md, Open questions).
``tests/chip_ops.py`` reduces a trace whole and reads through it.
"""
import re

from benchmarks.harness.peaks import least_seconds


def kernel_time(run, spec):
    """``(seconds, calls)`` of the kernel inside the named programs."""
    secs, calls = 0.0, 0
    for key, s in run.trace["reduced"]["device_ops"]:
        m = re.match(r"(\S+): (\S+).* x(\d+)$", key)
        if m and m.group(1) in spec["programs"] \
                and m.group(2).startswith(spec["kernel"]):
            secs += s
            calls += int(m.group(3))
    return secs, calls


def read(run, spec):
    work = getattr(run.model, spec["work"], None)
    tr = run.trace
    if tr is None or run.peaks is None or work is None:
        return None
    execs = len(run.program_durations(spec["programs"]))
    secs, calls = kernel_time(run, spec)
    ctx = [len(r.prompt) - 1 for r in run.admitted_between(tr["ta"], tr["tb"])]
    if not execs or not secs or not ctx:
        return None
    per_exec = run.model.kernel_calls(run.cfg, spec["kernel"])
    if abs(calls - per_exec * execs) > per_exec:
        return None         # part of its calls fell off the list (an
        #                     execution cut by the trace's edge may)
    least, _ = least_seconds(*work(run.cfg, ctx), run.peaks)
    return 100.0 * least / secs
