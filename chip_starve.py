"""The device's idle time between programs against the batcher's own
account of it, on the chip.

    python3 chip_starve.py trace <cell> <seconds> <seed> [<seed> ...]  # one chip
    python3 chip_starve.py host  <cell> <seconds> <seed> [<seed> ...]  # one chip
    python3 chip_starve.py plain <cell> <seconds> <seed> [<seed> ...]  # one chip

One process a seed (a chip belongs to one process), each the cell's
window as ``benchmarks/run.py`` runs it, judged by the cell's
reference, with the batcher's readiness probe timed
(``PhaseClock._ready``):

- ``trace``: the window traced as ``--trace 1`` traces it (the host
  tracer off), the trace kept, ``kv_stats()`` read where the trace
  starts and stops.  The records (``round_log()``) go onto the trace's
  clock by the ``clock`` pair of ``kv_stats()["rounds"]`` and the
  session's ``profile_start_time``; every gap between program
  executions of the ``XLA Modules`` line is printed with the host's
  stretch of the pass open at its start (from the records: pass top ->
  step queued -> wait -> tokens landed -> delivered); then, over the
  steps queued in the traced span, the program's dry + starved time
  (the records') and the bracket (the table's growth) against the
  trace's idle time between programs;
- ``host``: the same with the host tracer on: the ``lm_round``
  annotations against the records' ``pass_ns`` (the clock pair's
  residual), the device's lead from the runtime's enqueue events, and
  each gap with the ``lm/<phase>`` open at its start;
- ``plain``: an untraced window.

Every run prints the whole window's ``starved_share`` / ``dry_share``
(the metric files' arithmetic) and appends a line to
``chiprun_out/starve.jsonl``; exits non-zero where a run is not
``correct``.
"""
import bisect
import json
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "tests", "tools"))
from procs import each_in_a_process, record  # noqa: E402

TABLES = ("dry_ns", "starved_ns", "starved_bracket_ns")
TOP_GAPS = 40
# a gap this short is the runtime's between two programs queued back to
# back (a step and its argmax: 2-7 us on the v5e), which no host
# account holds: the launch floor
LAUNCH_NS = 20_000


def grew(c0: dict, c1: dict, *path):
    for key in path:
        c0, c1 = c0[key], c1[key]
    if isinstance(c1, dict):
        return sum(c1.values()) - sum(c0.values())
    return c1 - c0


def shares(c0: dict, c1: dict) -> dict:
    """100 x each idle table's growth over ``loop_ns``'s: what
    ``batcher.<t>_dry_share`` / ``_starved_share`` read."""
    loop = grew(c0, c1, "loop_ns")
    if not loop or "starved_ns" not in c1["rounds"]:
        return {}
    return {k.replace("_ns", "_share"): 100 * grew(c0, c1, "rounds", k) / loop
            for k in TABLES}


def by_phase(c0: dict, c1: dict, table: str) -> dict:
    return {p: round((c1["rounds"][table][p] - c0["rounds"][table][p]) / 1e6,
                     3) for p in c1["rounds"][table]
            if c1["rounds"][table][p] != c0["rounds"][table][p]}


def profile_start(data) -> int:
    """The session's start on the profiler's clock: a trace's events
    count from it."""
    for plane in data.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            return int(stats["profile_start_time"])
    return 0


def module_events(data) -> list:
    """``(start_ns, end_ns, program)`` of the ``XLA Modules`` line of the
    first TPU plane that ran any, sorted."""
    from benchmarks.harness import xplane

    for plane in data.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == xplane.MODULE_LINE and line.events:
                return sorted((e.start_ns, e.start_ns + e.duration_ns,
                               xplane.program_name(e.name))
                              for e in line.events)
    return []


def program_gaps(mods: list) -> list:
    """The stretches in which no program executed: ``(start, end,
    program before, program after)``."""
    out, end, prev = [], None, None
    for s, e, name in mods:
        if end is not None and s > end:
            out.append((end, s, prev, name))
        if end is None or e > end:
            end, prev = e, name
    return out


def idle_within(gaps: list, lo: int, hi: int, shorter: int = 0) -> int:
    """The idle time of ``gaps`` inside ``[lo, hi]``; with ``shorter``,
    of the gaps shorter than that alone."""
    return sum(max(0, min(g1, hi) - max(g0, lo)) for g0, g1, _a, _b in gaps
               if not shorter or g1 - g0 < shorter)


def host_marks(records: list) -> list:
    """Where each stretch of a pass begins, on the host's clock, from
    the records: ``(ns, stretch)``, sorted."""
    marks = []
    for x in records:
        marks.append((x["pass_ns"], "pass top: sched, admission, "
                      "step_dispatch"))
        marks.append((x["dispatch_ns"], "step queued: its inputs' tail"))
        if x["land_ns"]:
            marks.append((x["land_ns"] - x["wait_ns"], "device_wait"))
            marks.append((x["land_ns"], "landed: token_walk, stream_emit, "
                          "evict"))
        if x["done_ns"]:
            marks.append((x["done_ns"], "delivered: to the next pass top"))
    marks.sort()
    return marks


def stretch_at(marks: list, t: int) -> str:
    i = bisect.bisect_right([m[0] for m in marks], t) - 1
    return marks[i][1] if i >= 0 else "before the records"


def restart_lead(gaps: list, records: list, shift: int):
    """By how much the device's events read early on the host's clock,
    from the gaps a step ended after a long stretch: such a step began
    when it was enqueued, between its pass's top and ``dispatch_ns``.
    ``(low, high)`` medians over those steps, ns; None where none."""
    starts = sorted((x["pass_ns"] + shift, x["dispatch_ns"] + shift)
                    for x in records)
    keys = [s[0] for s in starts]
    lo, hi = [], []
    for g0, g1, _a, nxt in gaps:
        if nxt != "jit_step" or g1 - g0 < 500_000:
            continue
        i = bisect.bisect_right(keys, g1 + 3_000_000) - 1
        if i < 0 or starts[i][1] < g1 - 3_000_000:
            continue
        lo.append(starts[i][0] - g1)
        hi.append(starts[i][1] - g1)
    if not lo:
        return None
    return statistics.median(lo), statistics.median(hi)


def gap_table(gaps: list, label, lo: int, hi: int) -> dict:
    """The gaps inside ``[lo, hi]`` by (programs on either side, what
    the host was doing at its start): n and ms; and the longest."""
    kinds: dict = {}
    inside = [g for g in gaps if g[0] >= lo and g[1] <= hi]
    for g0, g1, a, b in inside:
        k = f"{a} -> {b} | {label(g0)}"
        acc = kinds.setdefault(k, [0, 0.0])
        acc[0] += 1
        acc[1] += (g1 - g0) / 1e6
    longest = sorted(inside, key=lambda g: g[0] - g[1])[:TOP_GAPS]
    return {"by_kind": {k: [n, round(ms, 3)] for k, (n, ms) in sorted(
                kinds.items(), key=lambda kv: -kv[1][1])},
            "longest": [[round((g0 - lo) / 1e6, 3), round((g1 - g0) / 1e6,
                                                          3),
                         f"{a} -> {b}", label(g0)]
                        for g0, g1, a, b in sorted(longest)]}


def sandwich(log: list, a: dict, b: dict, gaps: list, shift: int,
             lead: int) -> dict:
    """Over the steps queued between the two snapshots: the records'
    dry + starved time (what each step's queueing ended) and the
    bracket table's growth, against the trace's idle time between
    programs from the first of those steps' queueing to the last's;
    the upper side also without the launch floor (``LAUNCH_NS``)."""
    recs = sorted((x for x in log
                   if a["rounds"]["clock"]["mono_ns"] <= x["dispatch_ns"]
                   <= b["rounds"]["clock"]["mono_ns"]),
                  key=lambda x: x["ordinal"])
    if len(recs) < 2:
        return {"steps": len(recs)}
    t0, t1 = recs[0]["dispatch_ns"], recs[-1]["dispatch_ns"]
    ended = recs[1:]
    dry = sum(x["dry_ns"] for x in ended)
    starved = sum(x["starved_ns"] for x in ended)
    bracket = grew(a, b, "rounds", "starved_bracket_ns")
    lo, hi = t0 + shift - lead, t1 + shift - lead
    idle = idle_within(gaps, lo, hi)
    launch = idle_within(gaps, lo, hi, LAUNCH_NS)
    lower, upper = dry + starved, dry + starved + bracket
    return {"steps": len(recs), "span_ms": (t1 - t0) / 1e6,
            "dry_ms": dry / 1e6, "starved_ms": starved / 1e6,
            "bracket_ms": bracket / 1e6, "trace_idle_ms": idle / 1e6,
            "launch_ms": launch / 1e6, "lower_holds": lower <= idle,
            "upper_holds": idle <= upper,
            "upper_holds_past_launch": idle - launch <= upper}


def segments(log: list, cuts: list) -> dict:
    """The window cut where the trace starts and stops and where the
    profiler is done writing it: each stretch's steps queued and the
    dry and starved time their queueing ended, as shares of it."""
    out = {}
    for (name, lo), (_n, hi) in zip(cuts, cuts[1:]):
        recs = [x for x in log if lo <= x["dispatch_ns"] < hi]
        span = max(hi - lo, 1)
        out[name] = {"ms": span / 1e6, "steps": len(recs),
                     "dry_share": 100 * sum(x["dry_ns"] for x in recs) / span,
                     "starved_share": 100 * sum(x["starved_ns"] for x in recs)
                     / span,
                     "late": sum(x["seen_ns"] <= x["land_ns"] - x["wait_ns"]
                                 for x in recs if x["land_ns"])}
    return out


def residual(data, log: list, shift: int) -> dict:
    """The host tracer's ``lm_round`` starts against the records'
    ``pass_ns`` put on the trace by the clock pair."""
    from benchmarks.harness import hostspans

    starts = {}
    for plane in data.planes:
        if plane.name != hostspans.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "lm_round":
                    starts[int(dict(e.stats)["step_num"])] = e.start_ns
    res = sorted(starts[x["ordinal"]] - (x["pass_ns"] + shift)
                 for x in log if x["ordinal"] in starts)
    if not res:
        return {"records": 0}
    return {"records": len(res), "median_us": statistics.median(res) / 1e3,
            "p10_us": res[len(res) // 10] / 1e3,
            "p90_us": res[9 * len(res) // 10] / 1e3}


class Probe:
    """``PhaseClock._ready`` timed: every call's nanoseconds, and where
    the window's first and last counter snapshots fell among them."""

    def __init__(self):
        from benchmarks.harness import serving
        from brpc_tpu.models import lm_telemetry as lmt

        self.ns = []
        self.marks = []
        real = lmt.PhaseClock._ready
        counters = serving.Served.counters

        def marked(served, _c=counters, _marks=self.marks, _ns=self.ns):
            _marks.append(len(_ns))
            return _c(served)

        serving.Served.counters = marked

        def timed(ref, _now=time.perf_counter_ns, _real=real,
                  _ns=self.ns):
            t = _now()
            r = _real(ref)
            _ns.append(_now() - t)
            return r

        lmt.PhaseClock._ready = staticmethod(timed)

    def summary(self, steps: int) -> dict:
        """The calls between the window's first and last snapshots."""
        if len(self.marks) < 2:
            return {"calls": 0}
        ns = sorted(self.ns[self.marks[0]:self.marks[-1]])
        if not ns:
            return {"calls": 0}
        return {"calls": len(ns), "median_ns": ns[len(ns) // 2],
                "p90_ns": ns[9 * len(ns) // 10],
                "calls_per_step": len(ns) / max(steps, 1),
                "us_per_step": sum(ns) / 1e3 / max(steps, 1)}


def one(mode: str, cell_name: str, seconds: float, seed: int) -> int:
    sys.path.insert(0, ROOT)
    import jax

    from benchmarks import run as bench_run
    from benchmarks.harness import compare, hostspans, spec, xplane

    probe = Probe()
    snaps, found = {}, {}

    def traced_window(served, t0, seconds, mix):
        """``run.traced_window``, the host tracer as the mode says,
        ``kv_stats()`` and the step log read at both ends."""
        at = float(mix.get("trace_at_s", 2.0))
        length = min(float(mix.get("trace_s", 4.0)),
                     max(seconds - at - 1.0, 0.5))
        shutil.rmtree(bench_run.TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1 if mode == "host" else 0
        now = bench_run.now
        snaps["begin"] = time.monotonic_ns()
        time.sleep(max(0.0, t0 + at - now()))
        jax.profiler.start_trace(bench_run.TRACE_DIR, profiler_options=opts)
        snaps["a"] = served.counters()["kv"]
        steps_a, ta = served.steps_run(), now()
        time.sleep(length)
        steps_b, tb = served.steps_run(), now()
        snaps["b"] = served.counters()["kv"]
        jax.profiler.stop_trace()
        snaps["stopped"] = time.monotonic_ns()
        snaps["log"] = served.svc.batcher().round_log(
            since=snaps["a"]["steps"] - 2)
        time.sleep(max(0.0, t0 + seconds - now()))
        snaps["end"] = time.monotonic_ns()
        snaps["window_log"] = served.svc.batcher().round_log()
        return {"ta": ta, "tb": tb, "steps_a": steps_a, "steps_b": steps_b,
                "path": xplane.find_xplane(bench_run.TRACE_DIR)}

    reduce_trace = xplane.reduce_trace

    def keep_reading(path, **kw):
        found["data"] = jax.profiler.ProfileData.from_file(path)
        return reduce_trace(path, **kw)

    if mode != "plain":
        bench_run.traced_window = traced_window
        xplane.reduce_trace = keep_reading
    win = bench_run.run_window(spec.Cell(cell_name), seed, seconds,
                               trace=mode != "plain")
    res = win.judged(compare.compare(win.reference(), win.sample))
    run = win.run
    c0, c1 = run.c0["kv"], run.c1["kv"]
    line = {"cell": cell_name, "mode": mode, "seed": seed,
            "seconds": seconds, "correct": res["correct"],
            "failed": res["failed"],
            "window": {"steps": grew(c0, c1, "steps"), **shares(c0, c1),
                       "starved_n": grew(c0, c1, "rounds", "starved", "n")
                       if "starved" in c1["rounds"] else None,
                       "late_n": grew(c0, c1, "rounds", "late", "n")},
            "probe": probe.summary(grew(c0, c1, "steps")),
            "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
    if mode != "plain" and "starved_ns" in snaps["b"]["rounds"]:
        a, b, log, data = snaps["a"], snaps["b"], snaps["log"], found["data"]
        red = run.trace["reduced"]
        clock = b["rounds"]["clock"]
        shift = clock["trace_ns"] - clock["mono_ns"] - profile_start(data)
        mods = module_events(data)
        gaps = program_gaps(mods)
        if mode == "host":
            phases = hostspans.batcher_phases(data)
            starts = [p[0] for p in phases]
            enq = hostspans._by_run_id(
                (ln for pl in data.planes if pl.name == hostspans.HOST_PLANE
                 for ln in pl.lines), hostspans.ENQUEUE_EVENT)
            began = {}
            for pl in data.planes:
                if xplane.DEVICE_PLANE.match(pl.name):
                    began.update(hostspans._by_run_id(
                        ln for ln in pl.lines
                        if ln.name == xplane.MODULE_LINE))
            lead = hostspans.clock_lead(enq, began) or 0

            def label(g0):
                i = bisect.bisect_right(starts, g0 + lead) - 1
                return phases[i][2] if i >= 0 and \
                    phases[i][1] >= g0 + lead else "no phase"

            line["residual"] = residual(data, log, shift)
            line["lead_ms"] = {"enqueue": lead / 1e6}
        else:
            est = restart_lead(gaps, log, shift)
            lead = int((est[0] + est[1]) / 2) if est else 0
            marks = host_marks(log)
            keys = [m[0] for m in marks]

            def label(g0):
                i = bisect.bisect_right(keys, g0 + lead - shift) - 1
                return marks[i][1] if i >= 0 else "before the records"

            line["lead_ms"] = {"restarts": [round(x / 1e6, 3) for x in est]
                               if est else None}
        lo = a["rounds"]["clock"]["mono_ns"] + shift - lead
        hi = b["rounds"]["clock"]["mono_ns"] + shift - lead
        cuts = [("before the trace", snaps["begin"]),
                ("traced", a["rounds"]["clock"]["mono_ns"]),
                ("stopping the profiler", b["rounds"]["clock"]["mono_ns"]),
                ("after", snaps["stopped"]), ("end", snaps["end"])]
        line["segments"] = segments(snaps["window_log"], cuts)
        line["traced"] = {
            "steps": grew(a, b, "steps"),
            "idle_share": 100 * (1 - red["busy_s"] / red["window_s"]),
            "program_idle_share": 100 * idle_within(gaps, lo, hi)
            / max(hi - lo, 1),
            "sandwich": sandwich(log, a, b, gaps, shift, lead),
            **{t: by_phase(a, b, t) for t in TABLES},
            "gaps": gap_table(gaps, label, lo, hi)}
    record("starve", line)
    print(json.dumps(res), flush=True)
    return 0 if res["correct"] else 1


def main(argv) -> int:
    if argv[0] == "--one":
        return one(argv[1], argv[2], float(argv[3]), int(argv[4]))
    mode, cell, seconds, seeds = argv[0], argv[1], argv[2], argv[3:]
    if mode not in ("trace", "host", "plain") or not seeds:
        raise SystemExit(__doc__)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    return each_in_a_process(__file__, [mode, cell, seconds], seeds)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
