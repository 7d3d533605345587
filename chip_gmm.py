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

    chiprun -- python3 chip_gmm.py combine [tiles]

- the combine behind the products (``ops/expert_combine.py``) alone, at
  the four shapes the cells send, (tokens, buffer rows, dim, routed,
  held): ``kimi-k2.7-code``'s step (64, 512, 7168, 384, 12) and 1,024-row
  bucket (1024, 8192, 7168, 384, 12), ``kimi-linear-48b``'s step (128,
  1024, 2304, 256, 32), ``command-a-plus``'s step (16, 128, 4096, 128,
  16): the scatter-add of the whole masked, weighed buffer that stood in
  ``serve`` until PR 38, the plain form from the token's side and the
  kernel ``expert_combine``; microseconds a call and GB/s of the
  LIVE rows' bytes.  With ``tiles``: the kernel over output-block and
  row-tile sizes.

A time is the device's own, from a profiler trace of 20 calls
(``benchmarks/harness/xplane.py``), beside the host's clock over the
same calls.  Every kernel result is checked against ``ragged_dot`` on
the live rows (the combine's forms against the scatter-add).  One JSON
line a measurement, appended to ``chiprun_out/gmm.jsonl``.  Exits
non-zero without a TPU.
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
from brpc_tpu.ops import expert_combine as C  # noqa: E402
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


# (tokens, buffer rows, dim, routed, held) as the cells send them
COMBINE_SHAPES = {
    "kimi_k2_step": (64, 512, 7168, 384, 12),
    "kimi_linear_step": (128, 1024, 2304, 256, 32),
    "command_a_step": (16, 128, 4096, 128, 16),
    "kimi_k2_bucket": (1024, 8192, 7168, 384, 12),
}
TOP_K = 8


def scatter_add(ys, order, w, n_live):
    """``serve``'s combine until PR 38: the whole buffer masked,
    weighed and scatter-added."""
    k = w.shape[1]
    ys = jnp.where((jnp.arange(ys.shape[0]) < n_live)[:, None], ys, 0.0) \
        * w.reshape(-1)[order][:, None]
    return jnp.zeros((w.shape[0], ys.shape[1]), jnp.float32).at[
        order // k].add(ys)


def combine_main(record, tiles: bool) -> int:
    r = np.random.default_rng(38)
    variants = {
        "scatter_add": scatter_add,
        "plain": C.plain,
        "expert_combine": C.expert_combine,
    }
    if tiles:
        for ob in (1, 2, 4, 8):
            for tb in (256, 1024, 4096):
                if (ob << 20, tb << 10) != (C._OUT_BYTES, C._TILE_BYTES):
                    variants[f"expert_combine_out{ob}m_tile{tb}k"] = (
                        lambda ys, order, w, n, ob=ob, tb=tb:
                        C.expert_combine(ys, order, w, n,
                                         out_bytes=ob << 20,
                                         tile_bytes=tb << 10))
    fns, args, meta = {}, {}, {}
    for case, (t, m, dim, routed, held) in COMBINE_SHAPES.items():
        ids = np.stack([r.permutation(routed)[:TOP_K] for _ in range(t)])
        local = ids < held
        key = np.where(local, ids, held).reshape(-1)
        full = np.argsort(key, kind="stable").astype(np.int32)
        live = int(local.sum())
        ys = r.normal(size=(m, dim)).astype(np.float32)
        ys[live:] = np.nan
        w = r.uniform(0.1, 1.0, (t, TOP_K)).astype(np.float32)
        for vname, v in variants.items():
            def fn(ys, order, w, n, v=v, i=len(fns)):
                return v(ys, order, w, n), jnp.int32(i)
            name = f"{vname}_{case}"
            fn.__name__ = name
            fns[name] = jax.jit(fn)
            args[name] = (jnp.asarray(ys), jnp.asarray(full[:m]),
                          jnp.asarray(w), jnp.int32(live))
            meta[name] = dict(case=case, variant=vname, tokens=t, rows=m,
                              dim=dim, live=live,
                              live_share=100.0 * live / m)
    secs, red = device_seconds(fns, args)
    worst = 0.0
    for name, (dev_s, host_s) in secs.items():
        md = meta[name]
        kernel = [s for key, s in red["device_ops"]
                  if key.startswith(f"jit_{name}: expert_combine")]
        rec = dict(md, device_us=dev_s * 1e6, host_us=host_s * 1e6,
                   kernel_us=sum(kernel) * 1e6 / CALLS if kernel else None,
                   live_gbs=(md["live"] * md["dim"] * 4 / dev_s / 1e9
                             if dev_s else None),
                   buffer_gbs=(md["rows"] * md["dim"] * 4 / dev_s / 1e9
                               if dev_s else None))
        if md["variant"] != "scatter_add":
            want = np.asarray(fns[f"scatter_add_{md['case']}"](
                *args[f"scatter_add_{md['case']}"])[0])
            got = np.asarray(fns[name](*args[name])[0])
            rec["max_err"] = float(np.abs(got - want).max())
            worst = max(worst, rec["max_err"])
        record(**rec)
    # what each plain form is made of, by operation, at the step's shape
    for key, s in red["device_ops"]:
        if "_kimi_k2_step:" in key and "expert_combine_out" not in key:
            record(op=key, us_a_call=s * 1e6 / CALLS)
    ok = worst < 1e-5
    record(check="the combine's forms against the scatter-add",
           max_err=worst, ok=ok)
    return 0 if ok else 1


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: {dev.platform}", file=sys.stderr)
        return 1
    tiles = sys.argv[-1] == "tiles"
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out", "gmm.jsonl"), "a")

    def record(**kw):
        line = json.dumps({"device": dev.device_kind, **kw})
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    if "combine" in sys.argv[1:]:
        return combine_main(record, tiles)
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
