"""Milliseconds the batcher's loop spent in some of its phases, for
each step, request or other count of the window.

spec: ``{"phases": [name, ...]}`` or ``{"all_but": [name, ...]}`` (every
phase the program reports but these), and ``"per"``: a path into the
program's counters (``["kv", "steps"]``) or ``"requests"`` (the
requests due in the window).  The phases are the members of
``kv_stats()["phase_ns"]``: nanoseconds of the batcher thread's loop,
by the leaf of the loop they were spent in; they partition it.

Returns (growth of the named phases over the window) / (growth of
``per``), in ms.  A program without the table or without a named phase,
or a window in which either stood still, gives nothing."""

PHASE_NS = ["kv", "phase_ns"]


def read(run, spec):
    try:
        ns0 = run.counter(run.c0, PHASE_NS)
        ns1 = run.counter(run.c1, PHASE_NS)
        names = spec["phases"] if "phases" in spec \
            else [p for p in ns1 if p not in spec["all_but"]]
        ns = sum(ns1[p] - ns0[p] for p in names)
        per = spec["per"]
        den = len(run.due_in_window()) if per == "requests" \
            else run.delta(per)
    except KeyError:
        return None
    if not ns or not den:
        return None
    return ns / den / 1e6
