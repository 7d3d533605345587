"""Record the small device trace that the trace-reduction test reads.

Run once on the chip (``chiprun -- python3 benchmarks/tests/record_trace.py``):
two named jitted programs with idle gaps between them, traced for a
fraction of a second.  The ``.xplane.pb`` lands in ``chiprun_out/`` and
is copied to ``benchmarks/tests/data/`` by hand; the test then checks the
reduction against numbers worked out from this script's own layout.
"""
import glob
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("no TPU: nothing recorded", file=sys.stderr)
        return 1

    def step(x, w):
        return jnp.tanh(x @ w)

    def prefill(x, w):
        return jax.nn.softmax((x @ w) @ w.T, axis=-1)

    step_j = jax.jit(step)
    prefill_j = jax.jit(prefill)
    x = jnp.ones((256, 1024), jnp.float32)
    w = jnp.ones((1024, 1024), jnp.float32) * 0.01
    step_j(x, w).block_until_ready()
    prefill_j(x, w).block_until_ready()
    out = os.path.join("chiprun_out", "toy_trace")
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(out)
    for i in range(6):
        y = step_j(x, w)
        y = step_j(y, w)
        y.block_until_ready()
        time.sleep(0.01)                  # an idle gap: step -> prefill
        prefill_j(x, w).block_until_ready()
    jax.profiler.stop_trace()
    pbs = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))
    print("trace files:", pbs, [os.path.getsize(p) for p in pbs])
    pd = jax.profiler.ProfileData.from_file(pbs[0])
    summary = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"line": line.name, "n": len(evs),
                          "first": [(e.name[:80], e.start_ns, e.duration_ns,
                                     {k: str(v)[:60] for k, v in list(e.stats)[:6]})
                                    for e in evs[:4]]})
        summary.append({"plane": plane.name, "lines": lines})
    with open(os.path.join("chiprun_out", "toy_trace_summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    for p in summary:
        print(p["plane"], [(l["line"], l["n"]) for l in p["lines"]])
    shutil.copy(pbs[0], os.path.join("chiprun_out", "toy.xplane.pb"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
