"""The batcher's step log against the device trace, on the chip.

    chiprun -- python3 benchmarks/tests/chip_rounds.py [--trace-s S] [--telemetry 0] <cell> <seconds> <seed> [<seed> ...]

One process a seed: the cell's window as ``run.py --trace 1`` runs it,
but (a) the profile is ``--trace-s`` seconds long (default: the mix's
own), taken with the host tracer at 1 so that the trace holds the
batcher's ``lm_round`` annotations beside the device's programs, (b)
``kv_stats()`` is read where the trace starts and stops, and (c) the
trace is reduced whole.  Over the traced span it then sets the
program's own numbers (``benchmarks/ROUNDS.md``) beside the trace's:

- ``fill``: ``join_stall_ms`` x the steps that had filling programs in
  front of them (class ``fill``, and the restarts that carried some)
  against the device time of the filling programs (every program but
  ``jit_step``, ``jit__argmax`` and the token poke);
- ``ride``: ``ride_stall_ms`` against the mean device time of the
  ``jit_step`` executions with a slice on board over the plain ones',
  split by the ring: an execution's ``run_id`` finds its enqueue on the
  host's clock, that the ``lm_round`` it fell in, whose ``step_num`` is
  the record's ordinal (the recipe for a ``benchmark`` PR);
- ``dry``: the dry table's growth over ``loop_ns``'s against the trace's
  idle share;
- the identities of the whole window: the classes' ``n`` against the
  steps, the first token's four stages against ``ttft_ns``, dry time
  against the loop and ``idle_wait``.

Prints them and the run's own result line, appends a line to
``chiprun_out/rounds.jsonl``; exits non-zero where a run is not
``correct``.  ``--telemetry 0`` is the control for the instrument's
cost: an untraced window with ``lm_telemetry`` off, the result line
only.
"""
import bisect
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "tools"))
from procs import ROOT, each_in_a_process, record  # noqa: E402

NOT_FILLING = ("jit_step", "jit__argmax", "jit__settok")
CLASSES = ("restart", "fill", "ride", "plain")


def grew(c0: dict, c1: dict, *path):
    for key in path:
        c0, c1 = c0[key], c1[key]
    if isinstance(c1, dict):
        return sum(c1.values()) - sum(c0.values())
    return c1 - c0


class Span:
    """Two snapshots of ``kv_stats()`` as a reader takes a run's."""

    def __init__(self, c0: dict, c1: dict):
        self.c0, self.c1 = {"kv": c0}, {"kv": c1}

    def counter(self, snap: dict, path: list):
        for key in path:
            snap = snap[key]
        return snap


def excess_ms(c0: dict, c1: dict, cls: str):
    """``readers/round_excess.py`` between two snapshots."""
    from benchmarks.harness import spec

    return spec.load_module("readers", "round_excess").read(
        Span(c0, c1), {"class": cls})


def identities(c0: dict, c1: dict) -> dict:
    first = {k: grew(c0, c1, "first", k) for k in c1["first"]}
    stages = first["queue_ns"] + first["admit_ns"] + first["device_ns"] \
        + first["emit_ns"]
    return {
        "steps": grew(c0, c1, "steps"),
        "class_n": {cls: grew(c0, c1, "rounds", cls, "n")
                    for cls in CLASSES},
        "first": first,
        "stages_over_ttft": stages / first["ttft_ns"]
        if first["ttft_ns"] else None,
        # with the queue's own counter (the sessions TAKEN in the window,
        # not the ones that got their first token in it)
        "stages_with_queue_counter_over_ttft":
            (stages - first["queue_ns"] + grew(c0, c1, "queue", "wait_ns"))
            / first["ttft_ns"] if first["ttft_ns"] else None,
        "dry_ns": grew(c0, c1, "rounds", "dry_ns"),
        "loop_ns": grew(c0, c1, "loop_ns"),
        "dry_idle_wait_ns": grew(c0, c1, "rounds", "dry_ns", "idle_wait"),
        "phase_idle_wait_ns": grew(c0, c1, "phase_ns", "idle_wait"),
        "late_n": grew(c0, c1, "rounds", "late", "n"),
        "max_gap_ms": c1["rounds"]["max_gap_ns"] / 1e6,
    }


def steps_by_ordinal(data) -> dict:
    """``ordinal -> device seconds`` of the ``jit_step`` executions of
    the first TPU plane that ran any: a device event's ``run_id`` finds
    the runtime's enqueue on the host's clock, that the ``lm_round`` it
    lies in, and the round's ``step_num`` is the ordinal."""
    from benchmarks.harness import hostspans, xplane

    host = [ln for pl in data.planes if pl.name == hostspans.HOST_PLANE
            for ln in pl.lines]
    enqueued = hostspans._by_run_id(host, hostspans.ENQUEUE_EVENT)
    rounds = sorted((e.start_ns, e.start_ns + e.duration_ns,
                     int(dict(e.stats)["step_num"]))
                    for ln in host for e in ln.events
                    if e.name == "lm_round")
    starts = [r[0] for r in rounds]
    out = {}
    for plane in data.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != xplane.MODULE_LINE:
                continue
            for e in line.events:
                if xplane.program_name(e.name) != "jit_step":
                    continue
                t = enqueued.get(dict(e.stats).get(hostspans.RUN_ID))
                if t is None:
                    continue
                i = bisect.bisect_right(starts, t) - 1
                if i >= 0 and t <= rounds[i][1]:
                    out[rounds[i][2]] = e.duration_ns / 1e9
        if out:
            break
    return out


def one(trace_s: float, cell_name: str, seconds: float, seed: int) -> int:
    sys.path.insert(0, ROOT)
    import jax

    from benchmarks import run as bench_run
    from benchmarks.harness import compare, spec, xplane

    snaps, found = {}, {}

    def traced_window(served, t0, seconds, mix):
        """``run.traced_window`` with the host tracer at 1, its own
        length, and the program's counters read at both ends."""
        at = float(mix.get("trace_at_s", 2.0))
        length = min(trace_s or float(mix.get("trace_s", 4.0)),
                     max(seconds - at - 1.0, 0.5))
        shutil.rmtree(bench_run.TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        now = bench_run.now
        time.sleep(max(0.0, t0 + at - now()))
        jax.profiler.start_trace(bench_run.TRACE_DIR, profiler_options=opts)
        snaps["a"] = served.counters()["kv"]
        steps_a, ta = served.steps_run(), now()
        time.sleep(length)
        steps_b, tb = served.steps_run(), now()
        snaps["b"] = served.counters()["kv"]
        jax.profiler.stop_trace()
        snaps["log"] = served.svc.batcher().round_log(
            since=snaps["a"]["steps"] - 2)
        time.sleep(max(0.0, t0 + seconds - now()))
        return {"ta": ta, "tb": tb, "steps_a": steps_a, "steps_b": steps_b,
                "path": xplane.find_xplane(bench_run.TRACE_DIR)}

    reduce_trace = xplane.reduce_trace

    def reduce_whole(path, **_kw):
        found["steps"] = steps_by_ordinal(
            jax.profiler.ProfileData.from_file(path))
        return reduce_trace(path, top=1 << 30)

    bench_run.traced_window = traced_window
    xplane.reduce_trace = reduce_whole
    win = bench_run.run_window(spec.Cell(cell_name), seed, seconds,
                               trace=True)
    res = win.judged(compare.compare(win.reference(), win.sample))
    run, red = win.run, win.run.trace["reduced"]
    a, b = snaps["a"], snaps["b"]
    if "rounds" not in b:
        print("this program keeps no step log: nothing to compare")
        print(json.dumps(res))
        return 0 if res["correct"] else 1

    programs = {name: [len(d), sum(d)] for name, d in red["programs"].items()}
    filling_s = sum(s for name, (_n, s) in programs.items()
                    if name not in NOT_FILLING)
    stall = excess_ms(a, b, "fill")
    by_ord = {x["ordinal"]: x for x in snaps["log"]}
    # the traced span's steps with filling programs in front of them
    traced = [x for x in snaps["log"]
              if a["steps"] <= x["ordinal"] < b["steps"]]
    filled = [x for x in traced if x["fill_programs"]]
    dur = {"ride": [], "plain": [], "fill": [], "restart": []}
    for ordinal, secs in found["steps"].items():
        rec = by_ord.get(ordinal)
        if rec is not None:
            dur[rec["cls"]].append(secs)

    def mean_ms(xs):
        return 1e3 * sum(xs) / len(xs) if xs else None

    ride_trace = mean_ms(dur["ride"]) - mean_ms(dur["plain"]) \
        if dur["ride"] and dur["plain"] else None
    idle_share = 100 * (1 - red["busy_s"] / red["window_s"])
    dry_share = 100 * grew(a, b, "rounds", "dry_ns") / grew(a, b, "loop_ns")
    line = {
        "cell": cell_name, "seed": seed, "seconds": seconds,
        "trace_s": run.trace["tb"] - run.trace["ta"],
        "correct": res["correct"], "failed": res["failed"],
        "traced": {
            "steps": grew(a, b, "steps"),
            "class_n": {cls: grew(a, b, "rounds", cls, "n")
                        for cls in CLASSES},
            "filled_steps": len(filled),
            "filled_steps_joins": sum(x["joins"] for x in filled),
            "filled_steps_rows": sum(x["fill_rows"] for x in filled),
            "join_stall_ms": stall,
            "stall_x_filled_steps_ms": stall * len(filled)
            if stall is not None else None,
            "filling_programs_ms": 1e3 * filling_s,
            "ride_stall_ms": excess_ms(a, b, "ride"),
            "riding_over_plain_step_ms": ride_trace,
            "step_ms_by_class": {k: mean_ms(v) for k, v in dur.items()},
            "steps_matched": sum(len(v) for v in dur.values()),
            # what the traced steps attended, by the batcher's own count
            "pages_per_step": sum(x["pages"] for x in traced)
            / max(len(traced), 1),
            "touched_per_step": sum(x["touched"] for x in traced)
            / max(len(traced), 1),
            "rows_per_step": sum(x["rows"] for x in traced)
            / max(len(traced), 1),
            "jit_step_executions": programs.get("jit_step", [0])[0],
            "dry_share": dry_share, "idle_share": idle_share,
            "dry_by_phase_ms": {p: round(grew(a, b, "rounds", "dry_ns", p)
                                         / 1e6, 3)
                                for p in b["rounds"]["dry_ns"]
                                if grew(a, b, "rounds", "dry_ns", p)},
            "programs": programs,
        },
        "window": identities(run.c0["kv"], run.c1["kv"]),
        "metrics": {k: v["value"] for k, v in res["metrics"].items()
                    if k.startswith(("batcher.", "device."))},
    }
    record("rounds", line)
    print(json.dumps(res), flush=True)
    return 0 if res["correct"] else 1


def untelemetered(cell_name: str, seconds: float, seed: int) -> int:
    """The cost's control: ``run.py --trace 0`` with the gate off."""
    sys.path.insert(0, ROOT)
    from benchmarks import run as bench_run
    from benchmarks.harness import spec
    from brpc_tpu.butil.flags import set_flag
    from brpc_tpu.models import lm_telemetry    # defines the flag

    assert set_flag("lm_telemetry", "false")
    assert not lm_telemetry.telemetry_enabled()
    res = bench_run.run_cell(spec.Cell(cell_name), seed, seconds, False)
    print(json.dumps(res), flush=True)
    return 0 if res["correct"] else 1


def main(argv) -> int:
    if argv[0] == "--one":
        if argv[2] == "0":
            return untelemetered(argv[3], float(argv[4]), int(argv[5]))
        return one(float(argv[1]), argv[3], float(argv[4]), int(argv[5]))
    trace_s, telemetry = "0", "1"
    while argv[0].startswith("--"):
        if argv[0] == "--trace-s":
            trace_s, argv = argv[1], argv[2:]
        elif argv[0] == "--telemetry":
            telemetry, argv = argv[1], argv[2:]
        else:
            raise SystemExit(f"unknown option {argv[0]}")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    return each_in_a_process(__file__, [trace_s, telemetry, *argv[:2]],
                             argv[2:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
