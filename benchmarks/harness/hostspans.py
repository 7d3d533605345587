"""Device idle gaps, put down to what the host was doing.

The batcher annotates the leaves of its loop on the profiler's clock
(``lm/<phase>``, ``brpc_tpu/models/lm_telemetry.py``), so a trace taken
with the host tracer on holds, beside each TPU plane's operations, the
batcher thread's phases on the same clock.  Every stretch in which no
operation ran on the device (``xplane.py``'s idle gaps, named
``jit__argmax -> jit_step`` by the programs on either side) is split
here over the phases that overlap it, and two remainders that are no
phase's fault:

- ``d2h_return``: the head of a gap that begins inside a ``device_wait``:
  the device's last operation has ended and the wait has not yet
  returned (the result's way back to the host);
- ``launch``: the tail of a gap that ends with the first operation of
  a step or a prefill, from where the phase that enqueued it
  (``step_dispatch``, ``prefill_dispatch``) had returned: the program's
  way to the device;

and ``unattributed``: gap time under no annotation at all.  Busy time
plus the gaps is the window, as in ``xplane.py``.

**The two clocks.**  The profiler puts the device's events some
milliseconds EARLY on the host's clock (v5e, JAX 0.9: 1.7 ms; a step
"began" before the host had enqueued it).  The host plane also holds
the runtime's ``DoEnqueueProgram`` events, and they and the device's
``XLA Modules`` events carry the execution's ``run_id``: a program
cannot begin before it is enqueued, so the device's events are moved
later by the largest (enqueue - begin) over the trace's executions
(``clock_lead``).  The fastest launch of the trace then reads 0:
``launch`` is what a launch took beyond the fastest seen, and
``d2h_return`` holds the completion's way to the host as well as the
copy (in one trace read by hand the true lead lay within 0.6 ms above
that bound).  Where no execution can be matched the lead is taken as 0
and ``aligned`` is false: ``d2h_return`` and ``launch`` then mean
nothing, their sum and the phases' shares of the rest still do.

The benchmark's own traced run keeps the host tracer off; this reads
the traces of ``tests/chip_gaps.py``.
"""

from __future__ import annotations

import bisect

from benchmarks.harness import xplane

HOST_PLANE = "/host:CPU"
ENQUEUE_EVENT, RUN_ID = "DoEnqueueProgram", "run_id"
PHASE_PREFIX = "lm/"
WAIT = "device_wait"
D2H, LAUNCH, NOBODY = "d2h_return", "launch", "unattributed"
# the phase that enqueues the program a gap ends with
DISPATCH_OF = {"jit_step": "step_dispatch", "jit_prefill": "prefill_dispatch"}


def idle_gaps(mods: list, ops: list):
    """``(gaps, busy_ns, window_ns)``: the stretches with nothing
    running as ``(start_ns, end_ns, program before, program after)``.
    ``mods`` and ``ops`` are sorted ``(start_ns, end_ns, name)``."""
    starts = [m[0] for m in mods]

    def program_at(t_ns) -> str:
        i = bisect.bisect_right(starts, t_ns) - 1
        if i >= 0 and t_ns <= mods[i][1]:
            return mods[i][2]
        return "outside_any_program"

    gaps = []
    busy_ns = 0
    cur_s = cur_e = cur_prog = None
    for s, e, _name in ops:
        prog = program_at(s)
        if cur_e is None:
            cur_s, cur_e, cur_prog = s, e, prog
        elif s > cur_e:
            busy_ns += cur_e - cur_s
            gaps.append((cur_e, s, cur_prog, prog))
            cur_s, cur_e, cur_prog = s, e, prog
        elif e > cur_e:
            cur_e, cur_prog = e, prog
    if cur_e is not None:
        busy_ns += cur_e - cur_s
    window_ns = (max(o[1] for o in ops) - ops[0][0]) if ops else 0
    return gaps, busy_ns, window_ns


def split_gap(g0: int, g1: int, nxt: str, phases: list, starts: list) -> dict:
    """One gap's nanoseconds by name.  ``phases`` are the batcher's
    annotations, sorted ``(start_ns, end_ns, phase)`` and not
    overlapping; ``starts`` their start times."""
    out: dict = {}

    def give(name, ns):
        if ns > 0:
            out[name] = out.get(name, 0) + ns

    lo, hi = g0, g1
    # head: the device is done, the wait has not returned
    i = bisect.bisect_right(starts, g0) - 1
    if i >= 0 and phases[i][2] == WAIT and phases[i][1] > g0:
        lo = min(phases[i][1], g1)
        give(D2H, lo - g0)
    # tail: the program is enqueued, the device has not begun it
    disp = DISPATCH_OF.get(nxt)
    if disp is not None:
        j = bisect.bisect_right(starts, g1) - 1
        while j >= 0 and phases[j][2] != disp:
            j -= 1
        if j >= 0 and phases[j][1] < g1:
            hi = max(phases[j][1], lo)
            give(LAUNCH, g1 - hi)
    # the middle: whatever phase the loop was in
    covered = 0
    k = max(bisect.bisect_right(starts, lo) - 1, 0)
    while k < len(phases) and phases[k][0] < hi:
        ns = min(phases[k][1], hi) - max(phases[k][0], lo)
        if ns > 0:
            give(phases[k][2], ns)
            covered += ns
        k += 1
    give(NOBODY, (hi - lo) - covered)
    return out


def split_gaps(mods: list, ops: list, phases: list) -> dict:
    """One device's gaps by kind (``before -> after``), each split by
    name; seconds."""
    gaps, busy_ns, window_ns = idle_gaps(mods, ops)
    starts = [p[0] for p in phases]
    kinds: dict = {}
    for g0, g1, prev, nxt in gaps:
        kind = kinds.setdefault(f"{prev} -> {nxt}",
                                {"n": 0, "total_s": 0.0, "by": {}})
        kind["n"] += 1
        kind["total_s"] += (g1 - g0) / 1e9
        for name, ns in split_gap(g0, g1, nxt, phases, starts).items():
            kind["by"][name] = kind["by"].get(name, 0.0) + ns / 1e9
    return {"busy_s": busy_ns / 1e9, "window_s": window_ns / 1e9,
            "gaps": kinds}


def batcher_phases(data) -> list:
    """The ``lm/<phase>`` events of the host plane, sorted, the prefix
    taken off.  They come from one thread, the batcher's."""
    out = []
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            out.extend((e.start_ns, e.start_ns + e.duration_ns,
                        e.name[len(PHASE_PREFIX):])
                       for e in line.events
                       if e.name.startswith(PHASE_PREFIX))
    out.sort()
    return out


def clock_lead(enqueued: dict, began: dict):
    """By how many ns the device's events lead the host's clock, at
    the least: the largest (host's enqueue - device's begin) over the
    executions both sides name (``run_id -> ns``); None where they
    share none."""
    both = enqueued.keys() & began.keys()
    return max(enqueued[r] - began[r] for r in both) if both else None


def _by_run_id(lines, event_name=None) -> dict:
    out = {}
    for line in lines:
        for e in line.events:
            if event_name is None or e.name == event_name:
                rid = dict(e.stats).get(RUN_ID)
                if rid is not None:
                    out[rid] = e.start_ns
    return out


def read_trace(path: str) -> dict:
    """``split_gaps`` of the first TPU plane in ``path`` that ran
    anything, its events moved onto the host's clock; with the count
    of annotations found and the lead that was taken off."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    phases = batcher_phases(data)
    enqueued = _by_run_id((ln for pl in data.planes if pl.name == HOST_PLANE
                           for ln in pl.lines), ENQUEUE_EVENT)
    for plane in data.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        mods, ops = xplane._plane_events(plane)
        if not ops:
            continue
        lead = clock_lead(enqueued, _by_run_id(
            ln for ln in plane.lines if ln.name == xplane.MODULE_LINE))
        shift = lead or 0
        mods = [(s + shift, e + shift, n) for s, e, n in mods]
        ops = [(s + shift, e + shift, n) for s, e, n in ops]
        return {**split_gaps(mods, ops, phases), "annotations": len(phases),
                "aligned": lead is not None, "clock_lead_ms": shift / 1e6}
    raise RuntimeError(f"no operation ran on a device in {path}")


def table(split: dict, top: int = 4) -> str:
    """The ``top`` kinds of gap by time, each with its shares."""
    lines = []
    kinds = sorted(split["gaps"].items(), key=lambda kv: -kv[1]["total_s"])
    for kind, g in kinds[:top]:
        tot = g["total_s"]
        lines.append(f"{kind} x{g['n']}: {tot:.4f} s, "
                     f"{tot / g['n'] * 1e3:.3f} ms each")
        for name, sec in sorted(g["by"].items(), key=lambda kv: -kv[1]):
            lines.append(f"    {name:<18}{sec / g['n'] * 1e3:8.3f} ms each"
                         f"{100 * sec / tot:7.1f}%")
    return "\n".join(lines)
