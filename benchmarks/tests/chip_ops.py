"""Every device operation of a traced window, not only the ten longest.

    chiprun -- python3 benchmarks/tests/chip_ops.py <cell> <seconds> <seed>

One traced run of the cell through the runner's own ``run_window``,
with the trace reduced whole (``xplane.reduce_trace(top=all)``): prints,
a compiled program, its executions and its operations by device time
(name, calls, seconds, share of the program), and writes the table to
``chiprun_out/ops.<cell>.json``.  The result's line keeps ten
(``breakdown.device_ops``); this is how PERF.md's "where the time goes"
reads a kernel that is not among them; the metric files of
``readers/kernel_work.py`` are read from the same whole reduction.
"""
import functools
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    from benchmarks import run as bench_run
    from benchmarks.harness import spec, xplane

    name, seconds, seed = argv[0], float(argv[1]), int(argv[2])
    bench_run.xplane.reduce_trace = functools.partial(xplane.reduce_trace,
                                                      top=1 << 30)
    win = bench_run.run_window(spec.Cell(name), seed, seconds, trace=True)
    red = win.run.trace["reduced"]
    by_prog: dict = {}
    for key, secs in red["device_ops"]:
        m = re.match(r"(\S+): (.*) x(\d+)$", key)
        by_prog.setdefault(m.group(1), []).append(
            [m.group(2), int(m.group(3)), secs])
    table = {"cell": name, "seed": seed, "busy_s": red["busy_s"],
             "window_s": red["window_s"], "programs": {}}
    for prog, ops in sorted(by_prog.items(),
                            key=lambda kv: -sum(o[2] for o in kv[1])):
        total = sum(o[2] for o in ops)
        runs = red["programs"].get(prog, [])
        print(f"{prog}: {len(runs)} executions, {total:.4f} s of "
              f"{red['busy_s']:.4f} s busy")
        for op, calls, secs in ops[:40]:
            print(f"  {secs:9.5f} s {100 * secs / total:5.1f}%  x{calls:<6d} "
                  f"{1e6 * secs / calls:8.1f} us  {op}")
        table["programs"][prog] = {"executions": len(runs), "seconds": total,
                                   "ops": ops}
    # metric files that no entry of BENCHMARK.json names yet, read from
    # the whole reduction (readers/kernel_work.py says why)
    for path in sorted(glob.glob(os.path.join(spec.BENCH_DIR, "metrics",
                                              "kernel.*.json"))):
        m = spec.load_json(path)
        if m["reader"] == "kernel_work":
            value = spec.load_module("readers", "kernel_work").read(win.run, m)
            name = os.path.basename(path)[:-5]
            table[name] = value
            print(f"{name}: {value}")
    print(json.dumps({"metrics": {k: v["value"] for k, v in
                                  win.result["metrics"].items()}}))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"ops.{name}.json"), "w") as f:
        json.dump(table, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
