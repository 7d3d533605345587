"""On the chip, at ``ouro-2.6b``'s published widths.

    chiprun -- python3 benchmarks/tests/chip_ouro.py numerics [seed] [layers]
    chiprun -- python3 benchmarks/tests/chip_ouro.py metrics <seconds> <seed>

``numerics``: a cut of ``layers`` layers (12 where none is given) run
all four passes, at the cell's widths, page pool and slots: a prompt of
601 tokens filled through the pages in spans, then 24 paged steps in
slot 3 of 4, the logits against the plain reference and its int8
control; then the step and the fill ALONE, timed, the passes as the one
loop the program has and, beside it, unrolled (``lax.fori_loop``
swapped for a Python loop while the program is traced: 4 x ``layers``
bodies in the compiled text), with what the loop's form costs or saves
a step and each program's compile time.  One JSON line a check,
appended to ``chiprun_out/ouro_numerics.jsonl``.

``metrics``: one traced run of ``ouro-2.6b.batch`` through the runner's
own ``run_window`` with the trace reduced whole, judged as the runner
judges it; prints the result's line, the step and the fill by
operation, ``kv_stats()["loop"]`` over the window, and the two metric
files that wait for room in ``per_layer``
(``kernel.batch_paged_decode_roofline``,
``model.batch_fill_device_share``) read from the same run; writes
``chiprun_out/ouro_metrics.json``.
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

CELL = "ouro-2.6b.batch"
WAITING = ("kernel.batch_paged_decode_roofline",
           "model.batch_fill_device_share")
SLOT = 3


def _cut(layers: int):
    cfg = dict(spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                           "ouro-2.6b.json")))
    cfg["num_hidden_layers"] = layers
    return cfg, spec.load_module("models", cfg["model"])


def _programs(lm, page: int, unrolled: bool):
    """``(step, fill)`` jitted with the pools donated; ``unrolled``:
    traced with the loop of passes as a Python loop."""
    from brpc_tpu.models import transformer_lm as T

    step = T.make_paged_batch_decode(lm, page)[1]
    fill = T.make_paged_span_fill(lm, page)
    if unrolled:
        def python_loop(fn):
            @functools.wraps(fn)
            def traced(*a):
                real = jax.lax.fori_loop

                def unroll(lo, hi, body, init):
                    # the loop of passes alone (``_looped``'s body): a
                    # kernel's own loops stay what they are
                    if getattr(body, "__name__", "") != "one_pass":
                        return real(lo, hi, body, init)
                    return functools.reduce(lambda c, t: body(t, c),
                                            range(lo, hi), init)

                jax.lax.fori_loop = unroll
                try:
                    return fn(*a)
                finally:
                    jax.lax.fori_loop = real
            return traced
        step, fill = python_loop(step), python_loop(fill)
    return (jax.jit(step, donate_argnums=(1,)),
            jax.jit(fill, donate_argnums=(1,)))


def numerics(seed: int, layers: int) -> None:
    from brpc_tpu.models import transformer_lm as T

    cfg, m = _cut(layers)
    svc = cfg["service"]
    page, slots, pages = svc["page"], svc["decode_slots"], svc["kv_pages"]
    params = m.make_params(cfg, seed)
    lm = T.LMConfig(remat=False, **m.lm_kwargs(cfg))
    w, pps = lm.fill_span, lm.max_seq // page
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg["vocab_size"], (601,), dtype=np.int32)
    served = rng.integers(0, cfg["vocab_size"], (24,), dtype=np.int32)
    ctx = prompt[:-1]
    bt = np.zeros((slots, pps), np.int32)
    bt[SLOT, :40] = 7 + np.arange(40)
    active = np.zeros((slots,), bool)
    active[SLOT] = True
    results = {}
    for form in ("loop", "unrolled"):
        step, fill = _programs(lm, page, form == "unrolled")
        cache = T.empty_paged_cache(lm, pages, slots, page)
        t0 = time.perf_counter()
        for start in range(0, len(ctx), w):
            n = min(w, len(ctx) - start)
            ids = np.zeros((w,), np.int32)
            ids[:n] = ctx[start:start + n]
            cache = fill(params, cache, jnp.asarray(bt[SLOT]),
                         np.int32(SLOT), np.int32(start), np.int32(n), ids)
        jax.block_until_ready(cache)
        first_fill_s = time.perf_counter() - t0
        got = []
        t0 = time.perf_counter()
        for i, tok in enumerate(np.concatenate([prompt[-1:], served[:-1]])):
            toks = np.zeros((slots,), np.int32)
            toks[SLOT] = tok
            cache, logits = step(params, cache, jnp.asarray(bt),
                                 jnp.asarray(toks), jnp.asarray(active))
            got.append(np.asarray(logits[SLOT]))
            if i == 0:
                first_step_s = time.perf_counter() - t0
        # the two programs alone, timed: the pools go round through the
        # donated argument; the step at every slot's 624 positions
        bt_all = np.tile(bt[SLOT], (slots, 1))
        cache["len"] = jnp.full((slots,), 624, jnp.int32)
        toks = jnp.zeros((slots,), jnp.int32)
        on = jnp.ones((slots,), bool)
        t0 = time.perf_counter()
        for _ in range(20):
            cache, logits = step(params, cache, jnp.asarray(bt_all), toks, on)
            cache["len"] = jnp.full((slots,), 624, jnp.int32)
        jax.block_until_ready(logits)
        step_s = (time.perf_counter() - t0) / 20
        ids = np.zeros((w,), np.int32)
        t0 = time.perf_counter()
        for _ in range(5):
            cache = fill(params, cache, jnp.asarray(bt[SLOT]),
                         np.int32(SLOT), np.int32(256), np.int32(w), ids)
        jax.block_until_ready(cache)
        fill_s = (time.perf_counter() - t0) / 5
        del cache
        results[form] = (np.stack(got), step_s, fill_s, first_fill_s,
                         first_step_s)
    want = m.Reference(cfg, params).served_logits(prompt, served)
    ctl = m.Reference(cfg, params, int8=True).served_logits(prompt, served)
    std = want.std(axis=-1)

    def gaps(x):
        g = np.abs(x - want).max(axis=-1) / std
        return float(g.max()), float(g.mean())

    def below_best(tokens):
        rows = np.arange(len(tokens))
        return float(((want.max(axis=-1) - want[rows, tokens]) / std).mean())

    _flops, nbytes = m.step_work(cfg, [625] * slots, 1)
    f_fill, _b = m.fill_work(cfg, 256, w)
    for form, (got, step_s, fill_s, first_fill_s, first_step_s) \
            in results.items():
        record("ouro_numerics", {
            "check": "cut_of_layers", "form": form, "seed": seed,
            "layers": layers, "passes": lm.passes,
            "served_gap_std_max": gaps(got)[0],
            "served_gap_std_mean": gaps(got)[1],
            "served_below_best_mean": below_best(got.argmax(axis=-1)),
            "int8_gap_std_max": gaps(ctl)[0],
            "int8_gap_std_mean": gaps(ctl)[1],
            "int8_below_best_mean": below_best(ctl.argmax(axis=-1)),
            "step_seconds": step_s, "step_gb_s": nbytes / step_s / 1e9,
            "fill_seconds": fill_s, "fill_tflop_s": f_fill / fill_s / 1e12,
            "first_fill_seconds_with_compile": first_fill_s,
            "first_step_seconds_with_compile": first_step_s})
    loop, flat = results["loop"], results["unrolled"]
    record("ouro_numerics", {
        "check": "loop_against_unrolled", "layers": layers,
        "step_loop_over_unrolled": loop[1] / flat[1],
        "fill_loop_over_unrolled": loop[2] / flat[2],
        "same_logits_max_abs": float(np.abs(loop[0] - flat[0]).max())})


def _by_operation(red, program: str, top: int = 25):
    ops = []
    for key, secs in red["device_ops"]:
        m = re.match(r"(\S+): (.*) x(\d+)$", key)
        if m.group(1) == program:
            ops.append([m.group(2), int(m.group(3)), secs])
    total = sum(o[2] for o in ops)
    execs = len(red["programs"].get(program, []))
    print(f"{program}: {execs} executions, {total:.4f} s of "
          f"{red['busy_s']:.4f} s busy in {red['window_s']:.4f} s")
    for op, calls, secs in ops[:top]:
        print(f"  {secs:9.5f} s {100 * secs / total:5.1f}%  x{calls:<6d} "
              f"{1e6 * secs / calls:8.1f} us  {op}")
    return ops, execs, total


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
    step_ops, execs, total = _by_operation(red, "jit_step")
    fill_ops, fills, fill_total = _by_operation(red, "jit_fill", top=15)
    line = win.judged(compare.compare(win.reference(), win.sample))
    line["breakdown"] = {"idle_gaps": line["breakdown"]["idle_gaps"]}
    c0, c1 = run.c0["kv"]["loop"], run.c1["kv"]["loop"]
    loop = {k: c1[k] - c0[k] if k in ("steps", "layer_passes", "fills",
                                      "fill_rows", "fill_spans") else c1[k]
            for k in c1}
    out = {"seed": seed, "seconds": seconds, "line": line,
           "waiting": waiting, "step_ops": step_ops[:60],
           "fill_ops": fill_ops[:40], "step_executions": execs,
           "step_seconds": total, "fill_executions": fills,
           "fill_seconds": fill_total, "loop": loop,
           "layer_passes_a_step": loop["layer_passes"] / max(loop["steps"],
                                                             1),
           "alloc": run.c1["kv"]["alloc"],
           "programs": {p: [len(d), sum(d)]
                        for p, d in red["programs"].items()}}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "ouro_metrics.json"),
              "w") as f:
        json.dump(out, f)
    for k in ("waiting", "loop", "layer_passes_a_step", "alloc", "programs"):
        print(json.dumps({k: out[k]}))
    print(json.dumps(line), flush=True)
    return 0 if line["correct"] else 1


def main(argv) -> int:
    if jax.default_backend() != "tpu":
        raise SystemExit("this needs the chip")
    if argv and argv[0] == "metrics":
        return metrics(float(argv[1]), int(argv[2]))
    numerics(int(argv[1]) if len(argv) > 1 else 1,
             int(argv[2]) if len(argv) > 2 else 12)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
