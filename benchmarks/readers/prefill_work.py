"""The programs that fill a context (prefill, catch-up slices, the
insert into the pages) against what the model needs for the contexts
filled in the traced window.

spec: ``{"programs": [...], "kind": "mfu" | "roofline"}``.

A request whose first token arrived inside the traced window had its
context filled just before.  What the model NEEDS for it (counted by
the configuration's model module, ``fill_work``): the positions that no
earlier turn of its session shares (a first turn: all of its context; a
later turn: what follows the shared document), each attending over
everything before it.  Device time: every execution of the named
programs in the trace.
"""
from benchmarks.harness.peaks import least_seconds


def read(run, spec):
    tr = run.trace
    if tr is None or run.peaks is None:
        return None
    secs = sum(run.program_durations(spec["programs"]))
    reqs = run.admitted_between(tr["ta"], tr["tb"])
    if not secs or not reqs:
        return None
    flops = least = 0.0
    for r in reqs:
        ctx = len(r.prompt) - 1
        start = min(r.shared, ctx) if r.turn > 0 else 0
        f, b = run.model.fill_work(run.cfg, start, ctx - start)
        flops += f
        least += least_seconds(f, b, run.peaks)[0]
    if spec["kind"] == "mfu":
        return 100.0 * flops / secs / run.peaks["bf16_flops"]
    return 100.0 * least / secs
