"""On the chip: the held experts' grouped product, ``ops/expert_gmm.py``
against ``jax.lax.ragged_dot``, at ``kimi-k2.7-code``'s widths (12 held
experts of 7,168 -> 2 x 2,048 -> 7,168, bfloat16).

    chiprun -- python3 chip_gmm.py [tiles]

- the step's shapes: ~16 live rows on 9 of 12 experts in buffers of 512,
  128 and 32 rows (does the time follow the buffer, the rows or the
  experts touched?), then 3 and 12 experts touched, then the worst case
  (every row of the buffer live);
- the prefill's shapes: buffers of 1,024-8,192 rows (128-1,024 tokens
  x 8 choices), a 32nd of them live (12 of 384 experts are held here);
- with ``tiles``: the kernel over row tiles and weight-block sizes.

A time is the device's own, from a profiler trace of 20 calls
(``benchmarks/harness/xplane.py``), beside the host's clock over the
same calls.  Every kernel result is checked against ``ragged_dot`` on
the live rows.  One JSON line a measurement, appended to
``chiprun_out/gmm.jsonl``.  Exits non-zero without a TPU.
"""
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.harness import xplane  # noqa: E402
from brpc_tpu.ops import expert_gmm as G  # noqa: E402

HELD = 12
SHAPES = {"w1": (7168, 4096), "w2": (2048, 7168)}
HBM_GBS = 819.0
CALLS = 20
# 16 rows on 9 experts: a decode step's layer (64 rows x 8 / 384 x 12)
STEP_SIZES = [2, 0, 1, 3, 2, 0, 1, 2, 0, 3, 1, 1]


def spread(live: int, touched: int, seed: int = 0):
    """``live`` rows over the first ``touched`` experts, at least one
    each."""
    r = np.random.default_rng(seed)
    s = np.zeros(HELD, np.int64)
    s[:touched] = 1 + r.multinomial(live - touched,
                                    np.ones(touched) / touched)
    return [int(v) for v in s]


def device_seconds(fns, args):
    """Every ``fns[name](*args[name])`` CALLS times under one trace ->
    ``{name: (device seconds a call, host seconds a call)}``."""
    for name, fn in fns.items():
        jax.block_until_ready(fn(*args[name]))
    path = tempfile.mkdtemp(prefix="gmm_trace_")
    host = {}
    jax.profiler.start_trace(path)
    for name, fn in fns.items():
        t0 = time.perf_counter()
        for _ in range(CALLS):
            out = fn(*args[name])
        jax.block_until_ready(out)
        host[name] = (time.perf_counter() - t0) / CALLS
    jax.profiler.stop_trace()
    red = xplane.reduce_trace(xplane.find_xplane(path), top=10_000)
    shutil.rmtree(path, ignore_errors=True)
    return {name: (sum(red["programs"].get("jit_" + name, [0.0])) / CALLS,
                   host[name]) for name in fns}, red


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: {dev.platform}", file=sys.stderr)
        return 1
    tiles = len(sys.argv) > 1 and sys.argv[1] == "tiles"
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out", "gmm.jsonl"), "a")

    def record(**kw):
        line = json.dumps({"device": dev.device_kind, **kw})
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    r = np.random.default_rng(7)
    weights = {k: jnp.asarray(r.normal(size=(HELD,) + s).astype(np.float32)
                              / np.sqrt(s[0]), jnp.bfloat16)
               for k, s in SHAPES.items()}
    cases = [("step", m, STEP_SIZES) for m in (512, 128, 32)]
    cases += [("step_3_touched", 512, spread(16, 3)),
              ("step_12_touched", 512, spread(16, 12)),
              ("step_all_live", 512, spread(512, 12)),
              ("step_no_rows", 512, [0] * HELD)]
    cases += [("prefill", m, spread(m // 32, 12, m))
              for m in (1024, 2048, 4096, 8192)]
    variants = {"ragged_dot": None, "expert_gmm": (G._ROW_TILE,
                                                   G._BLOCK_BYTES)}
    if tiles:
        for tm in (16, 32, 64, 128, 256):
            for mb in (2, 4, 7):
                if (tm, mb << 20) != variants["expert_gmm"]:
                    variants[f"expert_gmm_tm{tm}_mb{mb}"] = (tm, mb << 20)

    fns, args, meta = {}, {}, {}
    for case, m, sizes in cases:
        for wname, (k, n) in SHAPES.items():
            xs = jnp.asarray(r.normal(size=(m, k)).astype(np.float32),
                             jnp.bfloat16)
            sz = jnp.asarray(sizes, jnp.int32)
            for vname, v in variants.items():
                # a serial number among the results: two programs that
                # differed by their names alone would share one executable
                if v is None:
                    def fn(xs, w, sz, i=len(fns)):
                        return jax.lax.ragged_dot(
                            xs, w, sz,
                            preferred_element_type=jnp.float32), jnp.int32(i)
                else:
                    def fn(xs, w, sz, v=v, i=len(fns)):
                        return G.expert_gmm(
                            xs, w, sz, tm=v[0],
                            block_bytes=v[1]), jnp.int32(i)
                name = f"{vname}_{case}_{m}_{wname}"
                fn.__name__ = name
                fns[name] = jax.jit(fn)
                args[name] = (xs, weights[wname], sz)
                meta[name] = dict(case=case, rows=m, product=wname,
                                  variant=vname, live=int(sum(sizes)),
                                  touched=int(np.count_nonzero(sizes)))
    secs, red = device_seconds(fns, args)
    worst = 0.0
    for name, (dev_s, host_s) in secs.items():
        md = meta[name]
        k, n = SHAPES[md["product"]]
        nbytes = md["touched"] * k * n * 2
        kernel = [t for key, t in red["device_ops"]
                  if key.startswith(f"jit_{name}: expert_gmm")]
        rec = dict(md, device_ms=dev_s * 1e3, host_ms=host_s * 1e3,
                   kernel_ms=sum(kernel) * 1e3 / CALLS if kernel else None,
                   hbm_share=(100.0 * nbytes / HBM_GBS / 1e9 / dev_s
                              if dev_s else None))
        if md["variant"] != "ragged_dot" and md["live"]:
            xs, w, sz = args[name]
            ref = f"ragged_dot_{md['case']}_{md['rows']}_{md['product']}"
            want = np.asarray(fns[ref](xs, w, sz)[0])[:md["live"]]
            got = np.asarray(fns[name](xs, w, sz)[0])[:md["live"]]
            rec["max_err"] = float(np.abs(got - want).max())
            worst = max(worst, rec["max_err"])
        record(**rec)
    # what XLA's own product is made of, by operation
    for key, s in red["device_ops"]:
        if key.startswith("jit_ragged_dot_step_512") \
                or key.startswith("jit_ragged_dot_step_32_"):
            record(op=key, ms_a_call=s * 1e3 / CALLS)
    record(check="expert_gmm against ragged_dot, live rows",
           max_err=worst, ok=worst < 1e-3)
    return 0 if worst < 1e-3 else 1


if __name__ == "__main__":
    sys.exit(main())
