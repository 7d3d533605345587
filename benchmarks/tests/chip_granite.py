"""On the chip, at ``granite-4.0-h-small``'s published widths (run on a
machine with a TPU, from the checkout's root):

    python3 benchmarks/tests/chip_granite.py numerics [seed] [layers]
    python3 benchmarks/tests/chip_granite.py metrics <seconds> <seed>

``numerics``: the SSD update kernel against its plain form
(``ops/ssd.py``: ``ssd_step`` at 64 slots x 128 heads of 64 x 128 with
64, 32, 1 and no slot active, its time beside the bytes it has to move
and their share of the HBM's 819 GB/s; the chunked prompt form against
the scan over positions at 128 and 1,024 positions; both under the
profiler, by the names a device trace shows them under), and, where
``layers`` is given, a cut of that many layers through prefill, insert
and paged steps against the plain reference and its int8 control.  One
JSON line a check, appended to ``chiprun_out/granite_numerics.jsonl``.

``metrics``: one traced run of ``granite-4.0-h-small.codegen`` through
the runner's own ``run_window`` with the trace reduced whole, judged as
the runner judges it; prints the result's line, the step by operation
(and every operation of the step whose result is a whole SSD state
pool, ``f32[64,128,64,128]``: ``ssd_step`` alone should be), the metric
file that waits for room in ``per_layer``
(``kernel.codegen_ssd_step_roofline``) and ``kv_stats()["mamba2"]``
over the window; writes ``chiprun_out/granite_metrics.json``.
"""
import functools
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "tools"))
from procs import ROOT, record  # noqa: E402

sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.harness import spec  # noqa: E402

CELL = "granite-4.0-h-small.codegen"
WAITING = ("kernel.codegen_ssd_step_roofline",)
HBM_GBS = 819.0
SLOTS, HEADS, P, N = 64, 128, 64, 128


def timed(fn, *args, n=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def _inputs(seed, batch, n_pos):
    ks = jax.random.split(jax.random.key(seed), 7)
    return (jax.random.normal(ks[0], (batch, n_pos, HEADS, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (batch, n_pos, HEADS))
                            - 2.0),
            -jax.random.uniform(ks[2], (HEADS,), minval=1.0, maxval=16.0),
            jax.random.normal(ks[3], (batch, n_pos, 1, N)),
            jax.random.normal(ks[4], (batch, n_pos, 1, N)),
            jnp.ones((HEADS,)),
            jax.random.normal(ks[6], (batch, HEADS, P, N)))


def step_kernel(seed: int) -> None:
    """64 slots x 128 heads of 64 x 128: the pool of one layer."""
    from brpc_tpu.ops import ssd

    x, dt, a, b, c, d, s0 = _inputs(seed, SLOTS, 1)
    args = (x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], d)
    step = jax.jit(lambda s, act: ssd.ssd_step(*args, s, act),
                   donate_argnums=(0,))
    plain = jax.jit(ssd.sequential)
    for n_active in (64, 32, 1, 0):
        active = jnp.arange(SLOTS) % (SLOTS // max(n_active, 1)) == 0 \
            if n_active else jnp.zeros((SLOTS,), bool)
        assert int(active.sum()) == n_active
        with jax.default_matmul_precision("highest"):
            want_y, want_s = plain(x, dt, a, b, c, d, s0,
                                   active.astype(jnp.int32))
        y, s = step(s0 + 0.0, active)
        err_s = float(jnp.abs(s - want_s).max())
        err_y = float(jnp.abs(y - jnp.where(active[:, None, None],
                                            want_y[:, 0], 0.0)).max())
        idle_same = bool(jnp.all(jnp.where(active[:, None, None, None],
                                           True, s == s0)))
        del y, want_y, want_s
        pool = s
        jax.block_until_ready(pool)
        t0 = time.perf_counter()
        for _ in range(20):
            _y, pool = step(pool, active)
        jax.block_until_ready(pool)
        secs = (time.perf_counter() - t0) / 20
        moved = 2.0 * n_active * HEADS * P * N * 4
        record("granite_numerics", {
            "check": "ssd_step", "seed": seed, "active": n_active,
            "max_abs_err_state": err_s, "max_abs_err_y": err_y,
            "idle_slots_bit_equal": idle_same, "seconds": secs,
            "state_gb_s": moved / secs / 1e9,
            "share_of_hbm": 100.0 * moved / secs / 1e9 / HBM_GBS})
        del pool, s


def chunk_form(seed: int) -> None:
    """The prompt's chunked form (chunks of 256) against the scan over
    positions, both from a state that is not zero."""
    from brpc_tpu.ops import ssd

    for n_pos in (128, 1024):
        x, dt, a, b, c, d, s0 = _inputs(seed + n_pos, 1, n_pos)
        lens = jnp.asarray([n_pos], jnp.int32)
        with jax.default_matmul_precision("highest"):
            want_y, want_s = jax.jit(ssd.sequential)(x, dt, a, b, c, d, s0,
                                                     lens)
        chunked = jax.jit(functools.partial(ssd.chunk_scan, chunk=256))
        y, s = chunked(x, dt, a, b, c, d, s0)
        record("granite_numerics", {
            "check": "chunk_scan", "seed": seed, "positions": n_pos,
            "max_abs_err_state": float(jnp.abs(s - want_s).max()),
            "max_abs_state": float(jnp.abs(want_s).max()),
            "max_abs_err_y": float(jnp.abs(y - want_y).max()),
            "max_abs_y": float(jnp.abs(want_y).max()),
            "seconds": timed(chunked, x, dt, a, b, c, d, s0, n=5)})


def kernels_in_a_trace(seed: int) -> None:
    """The kernel and the chunked form under the profiler: the names a
    device trace shows them under, and the device's own time a call."""
    import shutil
    import tempfile

    from benchmarks.harness import xplane
    from brpc_tpu.ops import ssd

    x, dt, a, b, c, d, s0 = _inputs(seed, 1, 1024)
    chunked = jax.jit(functools.partial(ssd.chunk_scan, chunk=256))
    xs, dts, _a, bs, cs, _d, pool = _inputs(seed + 1, SLOTS, 1)
    args = (xs[:, 0], dts[:, 0], a, bs[:, 0], cs[:, 0], d)
    active = jnp.ones((SLOTS,), bool)
    step = jax.jit(lambda s: ssd.ssd_step(*args, s, active),
                   donate_argnums=(0,))
    jax.block_until_ready(chunked(x, dt, a, b, c, d, s0))
    _y, pool = step(pool)
    jax.block_until_ready(pool)
    path = tempfile.mkdtemp(prefix="ssd_trace_")
    jax.profiler.start_trace(path)
    for _ in range(5):
        out = chunked(x, dt, a, b, c, d, s0)
        _y, pool = step(pool)
    jax.block_until_ready((out, pool))
    jax.profiler.stop_trace()
    red = xplane.reduce_trace(xplane.find_xplane(path), top=10_000)
    shutil.rmtree(path, ignore_errors=True)
    ops = [(key, secs) for key, secs in red["device_ops"]
           if ": ssd_step" in key]
    record("granite_numerics", {
        "check": "named_in_a_device_trace", "kernel": "ssd_step",
        "operations": [key for key, _ in ops],
        "device_seconds_a_call": sum(t for _, t in ops) / 5,
        "programs": {p: [len(v), sum(v)]
                     for p, v in red["programs"].items()}})


def layers_cut(seed: int, layers: int) -> None:
    from brpc_tpu.models import transformer_lm as T

    cfg = dict(spec.load_json(os.path.join(
        spec.BENCH_DIR, "configs", "granite-4.0-h-small.json")))
    m = spec.load_module("models", cfg["model"])
    cfg["num_hidden_layers"] = layers
    params = m.make_params(cfg, seed)
    lm = T.LMConfig(remat=False, **m.lm_kwargs(cfg))
    page, slots = 16, 8
    prefill, step = T.make_paged_batch_decode(lm, page)
    insert = T.make_paged_io(lm, page)[2]
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg["vocab_size"], (301,), dtype=np.int32)
    served = rng.integers(0, cfg["vocab_size"], (24,), dtype=np.int32)
    ctx = prompt[:-1]
    ids = np.zeros((512,), np.int32)
    ids[:len(ctx)] = ctx
    cache1, _ = jax.jit(prefill)(params, ids[None], jnp.int32(len(ctx)))
    cache = T.empty_paged_cache(lm, 257, slots, page)
    bt = np.zeros((slots, lm.max_seq // page), np.int32)
    bt[3, :64] = 1 + np.arange(64)
    cache = jax.jit(insert)(cache, jnp.asarray(bt[3]), cache1, jnp.int32(3))
    cache["len"] = cache["len"].at[3].set(len(ctx))
    active = np.zeros((slots,), bool)
    active[3] = True
    stepj = jax.jit(step, donate_argnums=(1,))
    got = []
    for tok in np.concatenate([prompt[-1:], served[:-1]]):
        toks = np.zeros((slots,), np.int32)
        toks[3] = tok
        cache, logits, _counts = stepj(params, cache, jnp.asarray(bt),
                                       jnp.asarray(toks), jnp.asarray(active))
        got.append(np.asarray(logits[3]))
    got = np.stack(got)
    del cache
    want = m.Reference(cfg, params).served_logits(prompt, served)
    ctl = m.Reference(cfg, params, int8=True).served_logits(prompt, served)
    std = want.std(axis=-1)

    def gaps(x):
        g = np.abs(x - want).max(axis=-1) / std
        return float(g.max()), float(g.mean())

    record("granite_numerics", {
        "check": "layers", "layers": layers, "seed": seed,
        "served_gap_std_max": gaps(got)[0],
        "served_gap_std_mean": gaps(got)[1],
        "int8_gap_std_max": gaps(ctl)[0],
        "int8_gap_std_mean": gaps(ctl)[1]})


def metrics(seconds: float, seed: int) -> int:
    from benchmarks import run as bench_run
    from benchmarks.harness import compare, xplane

    bench_run.xplane.reduce_trace = functools.partial(xplane.reduce_trace,
                                                      top=1 << 30)
    win = bench_run.run_window(spec.Cell(CELL), seed, seconds, trace=True)
    run, red = win.run, win.run.trace["reduced"]
    waiting = {}
    for name in WAITING:
        m = spec.load_json(os.path.join(spec.BENCH_DIR, "metrics",
                                        name + ".json"))
        waiting[name] = spec.load_module("readers", m["reader"]).read(run, m)
    step_ops = []
    for key, secs in red["device_ops"]:
        m = re.match(r"(\S+): (.*) x(\d+)$", key)
        if m.group(1) == "jit_step":
            step_ops.append([m.group(2), int(m.group(3)), secs])
    total = sum(o[2] for o in step_ops)
    execs = len(red["programs"].get("jit_step", []))
    print(f"jit_step: {execs} executions, {total:.4f} s of "
          f"{red['busy_s']:.4f} s busy in {red['window_s']:.4f} s")
    for op, calls, secs in step_ops[:30]:
        print(f"  {secs:9.5f} s {100 * secs / total:5.1f}%  x{calls:<6d} "
              f"{1e6 * secs / calls:8.1f} us  {op}")
    pool = f"f32[{SLOTS},{HEADS},{P},{N}]"
    on_pool = [o for o in step_ops if pool in o[0]]
    ssd_secs = sum(o[2] for o in step_ops if o[0].startswith("ssd_step"))
    line = win.judged(compare.compare(win.reference(), win.sample))
    line["breakdown"] = {"idle_gaps": line["breakdown"]["idle_gaps"]}
    grew = ("steps", "slot_steps", "fills", "fill_rows", "chunks")
    out = {"seed": seed, "seconds": seconds, "line": line,
           "waiting": waiting, "step_ops": step_ops[:60],
           "ops_on_the_state_pool": on_pool,
           "step_executions": execs, "step_seconds": total,
           "ssd_step_share_of_step": 100.0 * ssd_secs / total
           if total else None,
           "mamba2": {k: run.c1["kv"]["mamba2"][k]
                      - run.c0["kv"]["mamba2"].get(k, 0) if k in grew
                      else run.c1["kv"]["mamba2"][k]
                      for k in run.c1["kv"]["mamba2"]},
           "state": run.c1["kv"]["state"],
           "programs": {p: [len(d), sum(d)]
                        for p, d in red["programs"].items()}}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "granite_metrics.json"),
              "w") as f:
        json.dump(out, f)
    for k in ("waiting", "ssd_step_share_of_step", "ops_on_the_state_pool",
              "mamba2", "programs"):
        print(json.dumps({k: out[k]}))
    print(json.dumps(line), flush=True)
    return 0 if line["correct"] else 1


def main(argv) -> int:
    if jax.default_backend() != "tpu":
        raise SystemExit("this needs the chip")
    if argv and argv[0] == "metrics":
        return metrics(float(argv[1]), int(argv[2]))
    seed = int(argv[1]) if len(argv) > 1 else 1
    step_kernel(seed)
    chunk_form(seed)
    kernels_in_a_trace(seed)
    if len(argv) > 2:
        layers_cut(seed, int(argv[2]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
