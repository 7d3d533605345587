"""Device idle gaps by what the host was doing, on the chip.

    chiprun -- python3 benchmarks/tests/chip_gaps.py [--host-tracer 0|1] [--keep-trace] <cell> <seconds> <seed> [<seed> ...]

One process a seed: the cell's window as ``run.py --trace 1`` runs it,
but profiled with the HOST tracer at 1 (the Python tracer stays off),
so that the trace holds the batcher's ``lm/<phase>`` annotations beside
the device's operations.  ``harness/hostspans.py`` then splits every
idle gap over those phases, ``d2h_return``, ``launch`` and
``unattributed``.  Prints that table, and what the tracer costs: the
gap between tokens (p50) and the tokens per second inside the traced
part of the window against the rest of it.  ``--host-tracer 0`` is the
control: the benchmark's own tracing, no table.  ``--keep-trace``
copies each run's ``.xplane.pb`` to ``chiprun_out/`` (some MB), to be
looked at by hand.  Appends a line to ``chiprun_out/gaps.jsonl``; exits
non-zero where a run is not ``correct``.  The benchmark's own runs never trace the host.
"""
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "tools"))
from procs import ROOT, each_in_a_process, record  # noqa: E402


def one(level: int, keep: bool, cell_name: str, seconds: float,
        seed: int) -> int:
    sys.path.insert(0, ROOT)
    from benchmarks import run as bench_run
    from benchmarks.harness import compare, hostspans, spec, xplane
    from benchmarks.harness.series import percentile

    def traced_window(served, t0, seconds, mix):
        """``run.traced_window`` with the host tracer at ``level``."""
        import jax

        at = float(mix.get("trace_at_s", 2.0))
        length = min(float(mix.get("trace_s", 4.0)),
                     max(seconds - at - 1.0, 0.5))
        shutil.rmtree(bench_run.TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = level
        now = bench_run.now
        time.sleep(max(0.0, t0 + at - now()))
        jax.profiler.start_trace(bench_run.TRACE_DIR, profiler_options=opts)
        steps_a, ta = served.steps_run(), now()
        time.sleep(length)
        steps_b, tb = served.steps_run(), now()
        jax.profiler.stop_trace()
        time.sleep(max(0.0, t0 + seconds - now()))
        return {"ta": ta, "tb": tb, "steps_a": steps_a, "steps_b": steps_b,
                "path": xplane.find_xplane(bench_run.TRACE_DIR)}

    found = {}
    reduce_trace = xplane.reduce_trace

    def reduce_and_split(path, **kw):
        # the runner deletes the trace after its reduction: split it here
        found["split"] = hostspans.read_trace(path)
        if keep:
            shutil.copy(path, os.path.join(
                ROOT, "chiprun_out", f"gaps.{cell_name}.{seed}.xplane.pb"))
        return reduce_trace(path, **kw)

    bench_run.traced_window = traced_window
    xplane.reduce_trace = reduce_and_split
    win = bench_run.run_window(spec.Cell(cell_name), seed, seconds,
                               trace=True)
    res = win.judged(compare.compare(win.reference(), win.sample))
    run, split = win.run, found["split"]
    ta, tb = run.trace["ta"], run.trace["tb"]

    def part(inside: bool) -> dict:
        """The gaps between tokens that ended inside the traced part of
        the window (or outside it), and the tokens a second there."""
        gaps = [(r.stamps[i] - r.stamps[i - 1]) * 1e3
                for r in run.requests for i in range(1, len(r.stamps))
                if run.t0 <= r.stamps[i] <= run.t1
                and (ta <= r.stamps[i] <= tb) == inside]
        secs = (tb - ta) if inside else (run.t1 - run.t0) - (tb - ta)
        toks = run.tokens_between(ta, tb) if inside else \
            run.tokens_between(run.t0, run.t1) - run.tokens_between(ta, tb)
        return {"itl_p50_ms": percentile(gaps, 50),
                "itl_mean_ms": sum(gaps) / len(gaps),
                "out_tok_s": toks / secs, "seconds": secs}

    if level:
        print(f"device events moved {split['clock_lead_ms']:.3f} ms later "
              f"(aligned: {split['aligned']})\n" + hostspans.table(split),
              flush=True)
    record("gaps", {
        "cell": cell_name, "seed": seed, "seconds": seconds,
        "host_tracer": level, "correct": res["correct"],
        "failed": res["failed"], "annotations": split["annotations"],
        "aligned": split["aligned"], "clock_lead_ms": split["clock_lead_ms"],
        "busy_s": split["busy_s"], "window_s": split["window_s"],
        "traced": part(True), "untraced": part(False),
        "gaps": dict(sorted(split["gaps"].items(),
                            key=lambda kv: -kv[1]["total_s"])[:6]),
        "metrics": {k: v["value"] for k, v in res["metrics"].items()}})
    return 0 if res["correct"] else 1


def main(argv) -> int:
    if argv[0] == "--one":
        return one(int(argv[1]), argv[2] == "keep", argv[3], float(argv[4]),
                   int(argv[5]))
    level, keep = "1", "drop"
    while argv[0].startswith("--"):
        if argv[0] == "--host-tracer":
            level, argv = argv[1], argv[2:]
        elif argv[0] == "--keep-trace":
            keep, argv = "keep", argv[1:]
        else:
            raise SystemExit(f"unknown option {argv[0]}")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    return each_in_a_process(__file__, [level, keep, *argv[:2]], argv[2:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
