"""What the process was doing when the batcher's loop stood still.

    chiprun -- python3 benchmarks/tests/chip_stalls.py <cell> <seconds> <seed>

One long untraced window of the cell through the runner's own
``run_window`` with a watchdog beside it: every 20 ms it reads the
batcher's phase counters (``lm_telemetry.phase_counters``); when no
phase has been entered for 300 ms while sessions are live it writes
every thread's stack (``faulthandler``) to ``chiprun_out/stalls.<cell>.txt``
with the time since the last progress, and again when the loop moves
on.  Garbage collections of 50 ms and more are logged from
``gc.callbacks``.  A run of 45 s holds a stall of 0.8-2.5 s about one
time in eight (PERF.md, Findings, PR 27): this is how to look at one.
"""
import faulthandler
import gc
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def watch(out, stop):
    from brpc_tpu.models import lm_telemetry as lmt

    last, t_last, dumped = None, time.monotonic(), False
    while not stop.is_set():
        time.sleep(0.02)
        now = time.monotonic()
        cur = sum(lmt.phase_counters().values())
        if cur != last:
            if dumped:
                out.write(f"== moved on after {now - t_last:.3f}s\n")
                out.flush()
            last, t_last, dumped = cur, now, False
        elif not dumped and now - t_last > 0.3 and lmt.live_sessions():
            out.write(f"== no phase entered for {now - t_last:.3f}s, "
                      f"{len(lmt.live_sessions())} live sessions\n")
            out.flush()
            faulthandler.dump_traceback(file=out, all_threads=True)
            out.flush()
            dumped = True


def main(argv) -> int:
    from benchmarks import run as bench_run
    from benchmarks.harness import spec

    name, seconds, seed = argv[0], float(argv[1]), int(argv[2])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", f"stalls.{name}.txt"), "a")
    t_gc = {}

    def on_gc(phase, info):
        if phase == "start":
            t_gc["t"] = time.monotonic()
        elif time.monotonic() - t_gc.get("t", 0) > 0.05:
            out.write(f"== gc generation {info['generation']} took "
                      f"{time.monotonic() - t_gc['t']:.3f}s\n")
            out.flush()

    gc.callbacks.append(on_gc)
    stop = threading.Event()
    threading.Thread(target=watch, args=(out, stop), daemon=True).start()
    win = bench_run.run_window(spec.Cell(name), seed, seconds, trace=False)
    stop.set()
    gaps = sorted(win.run.series("itl_ms"))
    print(f"{name}: {len(gaps)} gaps, p50 {gaps[len(gaps) // 2]:.3f} ms, "
          f"max {gaps[-1]:.1f} ms, {sum(g > 500 for g in gaps)} over 500 ms; "
          f"see chiprun_out/stalls.{name}.txt")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
