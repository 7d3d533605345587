"""From the profiler's ``.xplane.pb`` to what the readers use.

A TPU's plane (``/device:TPU:<n>``) holds a line ``XLA Modules`` with
one event for every execution of a compiled program (``jit_step(<id>)``)
and a line ``XLA Ops`` with one event for every operation that ran.
From those:

- ``busy_s``: the union of the operations' intervals; ``window_s``:
  from the first operation's start to the last one's end; both
  averaged over the device planes;
- ``programs``: every execution's duration, by program name;
- ``device_ops``: operation time summed by (program, operation and
  result shape), with the count;
- ``idle_gaps``: every stretch in which no operation ran, summed by the
  programs on either side of it (``jit__argmax -> jit_step``).

Only JAX reads the file (``jax.profiler.ProfileData``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"the profiler left no .xplane.pb in {trace_dir}")
    return found[-1]


def program_name(event_name: str) -> str:
    """``jit_step(1444516...)`` -> ``jit_step``."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[8,4096]{...} fusion(...)`` -> ``fusion f32[8,4096]``:
    the operation without its serial number and its result's shape, so
    that the same operation of every layer adds up under one name."""
    m = re.match(r"%?([\w.\-]+)\s*=\s*\(?([\w]+\[[\d,]*\])?", event_name)
    if not m:
        return event_name[:48]
    base = re.sub(r"\.\d+$", "", m.group(1))
    return (base + (" " + m.group(2) if m.group(2) else ""))[:64]


def _plane_events(plane):
    mods, ops = [], []
    for line in plane.lines:
        if line.name == MODULE_LINE:
            mods = [(e.start_ns, e.start_ns + e.duration_ns,
                     program_name(e.name)) for e in line.events]
        elif line.name == OP_LINE:
            ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in line.events]
    mods.sort()
    ops.sort()
    return mods, ops


def reduce_plane(mods: list, ops: list) -> dict:
    """One device's reduction; times in seconds.  ``mods`` and ``ops``
    are sorted ``(start_ns, end_ns, name)``."""
    starts = [m[0] for m in mods]

    def program_at(t_ns) -> str:
        i = bisect.bisect_right(starts, t_ns) - 1
        if i >= 0 and t_ns <= mods[i][1]:
            return mods[i][2]
        return "outside_any_program"

    programs: dict = {}
    for s, e, name in mods:
        programs.setdefault(name, []).append((e - s) / 1e9)
    by_op: dict = {}
    gaps: dict = {}
    busy_ns = 0
    cur_s = cur_e = None
    cur_prog = None
    for s, e, name in ops:
        prog = program_at(s)
        key = f"{prog}: {op_name(name)}"
        acc = by_op.setdefault(key, [0.0, 0])
        acc[0] += (e - s) / 1e9
        acc[1] += 1
        if cur_e is None:
            cur_s, cur_e, cur_prog = s, e, prog
            continue
        if s > cur_e:                       # a stretch with nothing running
            busy_ns += cur_e - cur_s
            gkey = f"{cur_prog} -> {prog}"
            g = gaps.setdefault(gkey, [0.0, 0])
            g[0] += (s - cur_e) / 1e9
            g[1] += 1
            cur_s, cur_e, cur_prog = s, e, prog
        elif e > cur_e:
            cur_e, cur_prog = e, prog
    if cur_e is not None:
        busy_ns += cur_e - cur_s
    window_ns = (max(o[1] for o in ops) - ops[0][0]) if ops else 0
    return {"busy_s": busy_ns / 1e9, "window_s": window_ns / 1e9,
            "programs": programs, "by_op": by_op, "gaps": gaps}


def reduce_trace(path: str, top: int = 10) -> dict:
    """The reduction of every TPU plane in ``path``.  Raises where the
    trace holds no device operation."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = [reduce_plane(*_plane_events(p)) for p in data.planes
              if DEVICE_PLANE.match(p.name)]
    planes = [p for p in planes if p["busy_s"] > 0]
    if not planes:
        raise RuntimeError(f"no operation ran on a device in {path}")
    n = len(planes)
    programs: dict = {}
    by_op: dict = {}
    gaps: dict = {}
    for p in planes:
        for k, v in p["programs"].items():
            programs.setdefault(k, []).extend(v)
        for k, (sec, cnt) in p["by_op"].items():
            acc = by_op.setdefault(k, [0.0, 0])
            acc[0] += sec / n
            acc[1] += cnt
        for k, (sec, cnt) in p["gaps"].items():
            g = gaps.setdefault(k, [0.0, 0])
            g[0] += sec / n
            g[1] += cnt
    return {
        "busy_s": sum(p["busy_s"] for p in planes) / n,
        "window_s": sum(p["window_s"] for p in planes) / n,
        "devices": n,
        "programs": programs,
        "device_ops": sorted(([f"{k} x{c}", s] for k, (s, c) in by_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([f"{k} x{c}", s] for k, (s, c) in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
    }
