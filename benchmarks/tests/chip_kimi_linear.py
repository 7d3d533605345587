"""On the chip, at ``kimi-linear-48b``'s published widths.

    chiprun -- python3 benchmarks/tests/chip_kimi_linear.py numerics [seed] [kernels]
    chiprun -- python3 benchmarks/tests/chip_kimi_linear.py metrics <seconds> <seed>

``numerics``: the delta rule's two kernels against the plain scan
(``ops/delta_rule.py``: ``kda_step`` at 128 slots with 128, 64, 1 and
no slot active, its time beside the bytes it has to move; ``kda_scan``
over buckets of 128 and 1,024 positions; both under the profiler, by
the names a device trace shows them under), and, unless ``kernels`` is
given, a four-layer cut (the
dense layer, two more KDA layers and a latent one) through prefill,
insert and paged steps against the plain reference and its int8
control.  One JSON line a check, appended to
``chiprun_out/kimi_linear_numerics.jsonl``.

``metrics``: one traced run of ``kimi-linear-48b.codegen`` through the
runner's own ``run_window`` with the trace reduced whole, judged as the
runner judges it; prints the result's line, the step by operation, and
the two metric files that wait for room in ``per_layer``
(``kernel.codegen_kda_step_roofline``, ``kv.codegen_state_slots_share``)
read from the same run; writes ``chiprun_out/kimi_linear_metrics.json``.
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

CELL = "kimi-linear-48b.codegen"
WAITING = ("kernel.codegen_kda_step_roofline", "kv.codegen_state_slots_share")
HBM_GBS = 819.0


def timed(fn, *args, n=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def _inputs(seed, batch, n_pos, heads=32, d=128):
    ks = jax.random.split(jax.random.key(seed), 6)
    shape = (batch, n_pos, heads, d)

    def unit(t):
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    return (unit(jax.random.normal(ks[0], shape)) * d ** -0.5,
            unit(jax.random.normal(ks[1], shape)),
            jax.random.normal(ks[2], shape),
            jnp.exp(-3.0 * jax.random.uniform(ks[3], shape)),
            jax.nn.sigmoid(jax.random.normal(ks[4], shape[:-1])),
            jax.random.normal(ks[5], (batch, heads, d, d)))


def step_kernel(seed: int) -> None:
    """128 slots x 32 heads of 128 x 128: the pool of one layer."""
    from brpc_tpu.ops import delta_rule

    q, k, v, a, b, s0 = _inputs(seed, 128, 1)
    args = tuple(x[:, 0] for x in (q, k, v, a, b))
    step = jax.jit(lambda s, act: delta_rule.kda_step(*args, s, act),
                   donate_argnums=(0,))
    for n_active in (128, 64, 1, 0):
        active = jnp.arange(128) % (128 // max(n_active, 1)) == 0 \
            if n_active else jnp.zeros((128,), bool)
        assert int(active.sum()) == n_active
        want_y, want_s = delta_rule.sequential(
            q, k, v, a, b, s0, active.astype(jnp.int32))
        y, s = step(s0 + 0.0, active)
        err_s = float(jnp.abs(s - want_s).max())
        err_y = float(jnp.abs(y - jnp.where(active[:, None, None],
                                            want_y[:, 0], 0.0)).max())
        idle_same = bool(jnp.all(jnp.where(active[:, None, None, None],
                                           True, s == s0)))
        del y, want_y, want_s
        # timing: the pool goes round through the donated argument
        pool = s
        jax.block_until_ready(pool)
        t0 = time.perf_counter()
        for _ in range(20):
            _y, pool = step(pool, active)
        jax.block_until_ready(pool)
        secs = (time.perf_counter() - t0) / 20
        moved = 2.0 * n_active * 32 * 128 * 128 * 4
        record("kimi_linear_numerics", {
            "check": "kda_step", "seed": seed, "active": n_active,
            "max_abs_err_state": err_s, "max_abs_err_y": err_y,
            "idle_slots_bit_equal": idle_same, "seconds": secs,
            "state_gb_s": moved / secs / 1e9,
            "share_of_hbm": 100.0 * moved / secs / 1e9 / HBM_GBS})
        del pool, s


def scan_kernel(seed: int) -> None:
    from brpc_tpu.ops import delta_rule

    for n_pos, n_live in ((128, 100), (1024, 1000)):
        q, k, v, a, b, s0 = _inputs(seed + n_pos, 1, n_pos)
        lens = jnp.asarray([n_live], jnp.int32)
        want_y, want_s = jax.jit(delta_rule.sequential)(q, k, v, a, b, s0,
                                                        lens)
        y, s = delta_rule.kda_scan(q, k, v, a, b, s0, lens)
        record("kimi_linear_numerics", {
            "check": "kda_scan", "seed": seed, "positions": n_pos,
            "live": n_live,
            "max_abs_err_state": float(jnp.abs(s - want_s).max()),
            "max_abs_err_y": float(jnp.abs(y[:, :n_live]
                                           - want_y[:, :n_live]).max()),
            "seconds": timed(delta_rule.kda_scan, q, k, v, a, b, s0, lens,
                             n=5),
            "sequential_seconds": timed(jax.jit(delta_rule.sequential),
                                        q, k, v, a, b, s0, lens, n=2)})


def kernels_in_a_trace(seed: int) -> None:
    """Both kernels under the profiler: the names a device trace shows
    them under, and the device's own time a call."""
    import shutil
    import tempfile

    from benchmarks.harness import xplane
    from brpc_tpu.ops import delta_rule

    q, k, v, a, b, s0 = _inputs(seed, 1, 1024)
    lens = jnp.asarray([1000], jnp.int32)
    scan = jax.jit(lambda *x: delta_rule.kda_scan(*x))
    qs, ks, vs, as_, bs, pool = _inputs(seed + 1, 128, 1)
    args = tuple(x[:, 0] for x in (qs, ks, vs, as_, bs))
    active = jnp.ones((128,), bool)
    step = jax.jit(lambda s: delta_rule.kda_step(*args, s, active),
                   donate_argnums=(0,))
    jax.block_until_ready(scan(q, k, v, a, b, s0, lens))
    _y, pool = step(pool)
    jax.block_until_ready(pool)
    path = tempfile.mkdtemp(prefix="kda_trace_")
    jax.profiler.start_trace(path)
    for _ in range(5):
        out = scan(q, k, v, a, b, s0, lens)
        _y, pool = step(pool)
    jax.block_until_ready((out, pool))
    jax.profiler.stop_trace()
    red = xplane.reduce_trace(xplane.find_xplane(path), top=10_000)
    shutil.rmtree(path, ignore_errors=True)
    for name in ("kda_scan", "kda_step"):
        ops = [(key, secs) for key, secs in red["device_ops"]
               if f": {name}" in key]
        record("kimi_linear_numerics", {
            "check": "named_in_a_device_trace", "kernel": name,
            "operations": [key for key, _ in ops],
            "device_seconds_a_call": sum(t for _, t in ops) / 5})


def four_layers(seed: int) -> None:
    from brpc_tpu.models import transformer_lm as T

    cfg = dict(spec.load_json(os.path.join(
        spec.BENCH_DIR, "configs", "kimi-linear-48b.json")))
    m = spec.load_module("models", cfg["model"])
    cfg["num_hidden_layers"] = 4
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

    record("kimi_linear_numerics", {
        "check": "four_layers", "seed": seed,
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
    kda = sum(o[2] for o in step_ops if o[0].startswith("kda_step"))
    line = win.judged(compare.compare(win.reference(), win.sample))
    line["breakdown"] = {"idle_gaps": line["breakdown"]["idle_gaps"]}
    out = {"seed": seed, "seconds": seconds, "line": line,
           "waiting": waiting, "step_ops": step_ops[:60],
           "step_executions": execs, "step_seconds": total,
           "kda_step_share_of_step": 100.0 * kda / total if total else None,
           "kda": {k: run.c1["kv"]["kda"][k] - run.c0["kv"]["kda"].get(k, 0)
                   if k in ("steps", "slot_steps", "fills", "fill_rows")
                   else run.c1["kv"]["kda"][k]
                   for k in run.c1["kv"]["kda"]},
           "state": run.c1["kv"]["state"],
           "programs": {p: [len(d), sum(d)]
                        for p, d in red["programs"].items()}}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "kimi_linear_metrics.json"), "w") as f:
        json.dump(out, f)
    for k in ("waiting", "kda_step_share_of_step", "kda", "programs"):
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
    scan_kernel(seed)
    kernels_in_a_trace(seed)
    if "kernels" not in argv:
        four_layers(seed)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
