"""On the chip, at ``nemotron-3-nano-30b``'s published widths (run on a
machine with a TPU, from the checkout's root):

    python3 benchmarks/tests/chip_nemotron.py numerics [seed]
    python3 benchmarks/tests/chip_nemotron.py metrics <seconds> <seed>

``numerics``: the SSD update kernel in EIGHT groups against its plain
form (``ops/ssd.py``: ``ssd_step`` at 128 slots x 64 heads of 64 x 128,
8 heads a group, with 128, 64, 1 and no slot active: its error, its
time and the share of the HBM's 819 GB/s its state moves at); the
chunked prompt form at 8 groups against the scan over positions at 128
and 1,024 positions; the grouped expert product at the expert width of
1,856 (14.5 lane tiles: ``expert_gmm``'s whole-width blocks) against a
plain product a group, up and down at a step's 768 rows over the 64
held experts, its time beside the bytes of the touched experts'
weights.  One JSON line a check, appended to
``chiprun_out/nemotron_numerics.jsonl``.

``metrics``: one traced run of ``nemotron-3-nano-30b.codegen`` through
the runner's own ``run_window`` with the trace reduced whole, judged as
the runner judges it; prints the result's line, the step by operation,
the shares of ``ssd_step`` and ``expert_gmm`` in the step, the metric
file that waits for room in ``per_layer``
(``kernel.codegen_ssd_step_roofline``), ``kv_stats()`` ``mamba2`` /
``moe`` / ``schedule`` over the window, and the share of served tokens
that repeat the one before; writes ``chiprun_out/nemotron_metrics.json``.
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

CELL = "nemotron-3-nano-30b.codegen"
WAITING = ("kernel.codegen_ssd_step_roofline",)
HBM_GBS = 819.0
SLOTS, HEADS, P, N, GROUPS = 128, 64, 64, 128, 8
DIM, WIDTH, HELD, TOP_K, ROUTED = 2688, 1856, 64, 6, 128


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
            jax.random.normal(ks[3], (batch, n_pos, GROUPS, N)),
            jax.random.normal(ks[4], (batch, n_pos, GROUPS, N)),
            jnp.ones((HEADS,)),
            jax.random.normal(ks[6], (batch, HEADS, P, N)))


def step_kernel(seed: int) -> None:
    """128 slots x 64 heads of 64 x 128 in 8 groups: one layer's pool,
    in the pool's layout (``ssd.to_pool``)."""
    from brpc_tpu.ops import ssd

    x, dt, a, b, c, d, s0 = _inputs(seed, SLOTS, 1)
    args = (x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], d)
    pool0 = ssd.to_pool(s0)
    step = jax.jit(lambda s, act: ssd.ssd_step(*args, s, act),
                   donate_argnums=(0,))
    plain = jax.jit(ssd.sequential)
    for n_active in (SLOTS, SLOTS // 2, 1, 0):
        active = jnp.arange(SLOTS) % (SLOTS // max(n_active, 1)) == 0 \
            if n_active else jnp.zeros((SLOTS,), bool)
        assert int(active.sum()) == n_active
        with jax.default_matmul_precision("highest"):
            want_y, want_s = plain(x, dt, a, b, c, d, s0,
                                   active.astype(jnp.int32))
        y, pool = step(pool0 + 0.0, active)
        got_s = ssd.from_pool(pool, HEADS)
        err_s = float(jnp.abs(got_s - want_s).max())
        err_y = float(jnp.abs(y - jnp.where(active[:, None, None],
                                            want_y[:, 0], 0.0)).max())
        idle_same = bool(jnp.all(jnp.where(active[:, None, None, None],
                                           True, pool == pool0)))
        del y, want_y, want_s, got_s
        jax.block_until_ready(pool)
        t0 = time.perf_counter()
        for _ in range(20):
            _y, pool = step(pool, active)
        jax.block_until_ready(pool)
        secs = (time.perf_counter() - t0) / 20
        moved = 2.0 * n_active * HEADS * P * N * 4
        record("nemotron_numerics", {
            "check": "ssd_step", "groups": GROUPS, "seed": seed,
            "active": n_active, "max_abs_err_state": err_s,
            "max_abs_err_y": err_y, "idle_slots_bit_equal": idle_same,
            "seconds": secs, "state_gb_s": moved / secs / 1e9,
            "share_of_hbm": 100.0 * moved / secs / 1e9 / HBM_GBS})
        del pool


def chunk_form(seed: int) -> None:
    """The prompt's chunked form (chunks of 128) at 8 groups against the
    scan over positions, both from a state that is not zero."""
    from brpc_tpu.ops import ssd

    for n_pos in (128, 1024):
        x, dt, a, b, c, d, s0 = _inputs(seed + n_pos, 1, n_pos)
        lens = jnp.asarray([n_pos], jnp.int32)
        with jax.default_matmul_precision("highest"):
            want_y, want_s = jax.jit(ssd.sequential)(x, dt, a, b, c, d, s0,
                                                     lens)
        chunked = jax.jit(functools.partial(ssd.chunk_scan, chunk=128))
        y, s = chunked(x, dt, a, b, c, d, s0)
        record("nemotron_numerics", {
            "check": "chunk_scan", "groups": GROUPS, "seed": seed,
            "positions": n_pos,
            "max_abs_err_state": float(jnp.abs(s - want_s).max()),
            "max_abs_state": float(jnp.abs(want_s).max()),
            "max_abs_err_y": float(jnp.abs(y - want_y).max()),
            "max_abs_y": float(jnp.abs(want_y).max()),
            "seconds": timed(chunked, x, dt, a, b, c, d, s0, n=5)})


def expert_products(seed: int) -> None:
    """``expert_gmm`` at the cell's widths, a step's rows (128 tokens x
    6 choices, the held experts' share of them sorted to the front),
    up (the weights as served: ``(64, 1856, 2688)``, read transposed)
    and down ``(64, 1856, 2688)`` in bfloat16, against a plain product
    a group; its time beside the touched experts' weights."""
    from brpc_tpu.ops import expert_gmm as eg

    rng = np.random.default_rng(seed)
    ids = np.stack([rng.choice(ROUTED, TOP_K, replace=False)
                    for _ in range(SLOTS)])
    held = np.sort(ids[ids < HELD])
    sizes = np.bincount(held, minlength=HELD).astype(np.int32)
    m = SLOTS * TOP_K
    ks = jax.random.split(jax.random.key(seed), 4)
    for name, (k, n, tr) in (("up", (DIM, WIDTH, True)),
                             ("down", (WIDTH, DIM, False))):
        xs = (jax.random.normal(ks[0], (m, k)) / 4).astype(jnp.bfloat16)
        w = (jax.random.normal(ks[1], (HELD, n, k) if tr else (HELD, k, n))
             / np.sqrt(k)).astype(jnp.bfloat16)
        call = jax.jit(functools.partial(eg.expert_gmm, transposed=tr))
        got = np.asarray(call(xs, w, jnp.asarray(sizes)))
        ends = np.cumsum(sizes)
        with jax.default_matmul_precision("highest"):
            want = np.concatenate([np.asarray(
                xs[e - s:e].astype(jnp.float32)
                @ (w[g].T if tr else w[g]).astype(jnp.float32))
                for g, (s, e) in enumerate(zip(sizes, ends))])
        touched = int((sizes > 0).sum())
        secs = timed(call, xs, w, jnp.asarray(sizes))
        nbytes = touched * k * n * 2
        record("nemotron_numerics", {
            "check": "expert_gmm", "product": name, "seed": seed,
            "transposed": tr,
            "blocks": list(eg._blocks(k, n, 2, eg._BLOCK_BYTES)),
            "rows": int(sizes.sum()), "touched": touched,
            "max_abs_err": float(np.abs(got[:len(want)] - want).max()),
            "max_abs": float(np.abs(want).max()), "seconds": secs,
            "weights_gb_s": nbytes / secs / 1e9,
            "share_of_hbm": 100.0 * nbytes / secs / 1e9 / HBM_GBS})


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

    def share(kernel):
        secs = sum(o[2] for o in step_ops if o[0].startswith(kernel))
        return 100.0 * secs / total if total else None

    finished = [r for r in run.requests if r.reason == "finished"
                and len(r.tokens) > 1]
    repeats = [int(np.sum(np.diff(np.asarray(r.tokens)) == 0))
               for r in finished]
    line = win.judged(compare.compare(win.reference(), win.sample))
    line["breakdown"] = {"idle_gaps": line["breakdown"]["idle_gaps"]}
    kv0, kv1 = run.c0["kv"], run.c1["kv"]

    def grown(key, counts):
        return {k: v - kv0[key][k] if k in counts else v
                for k, v in kv1[key].items()}

    out = {"seed": seed, "seconds": seconds, "line": line,
           "waiting": waiting, "step_ops": step_ops[:60],
           "step_executions": execs, "step_seconds": total,
           "share_of_step": {k: share(k) for k in (
               "ssd_step", "expert_gmm", "expert_combine",
               "paged_decode_attention")},
           "mamba2": grown("mamba2", ("steps", "slot_steps", "fills",
                                      "fill_rows", "chunks")),
           "moe": grown("moe", ("steps", "rows", "local_pairs",
                                "experts_touched")),
           "schedule": kv1["schedule"], "state": kv1["state"],
           "served_repeat_share": 100.0 * sum(repeats)
           / max(sum(len(r.tokens) - 1 for r in finished), 1),
           "programs": {p: [len(d), sum(d)]
                        for p, d in red["programs"].items()}}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "nemotron_metrics.json"),
              "w") as f:
        json.dump(out, f)
    for k in ("waiting", "share_of_step", "mamba2", "moe", "schedule",
              "served_repeat_share", "programs"):
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
    expert_products(seed)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
