"""On the chip, at ``command-a-plus``'s published widths: the decode
kernel of a window schedule against its plain form (a window layer's
walk from the window's first page, a global layer's whole walk), and a
span's flash attention against its plain form (a window layer's span
from deep in a context, a global layer's first span), each with its
device time from the host's clock and what that is of the pages' bytes.

    chiprun -- python3 benchmarks/tests/chip_command_a.py [seed]

Prints one JSON line a check and appends them to
``chiprun_out/command_a_numerics.jsonl``; exits non-zero where a kernel
lies further from its plain form than bfloat16 operands explain.
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "tools"))
from procs import ROOT, record  # noqa: E402

sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from brpc_tpu.ops import paged_attention, span_attention  # noqa: E402

HEADS, KVH, HD, PAGE, WINDOW, PPS = 128, 8, 128, 16, 4096, 816
SPAN, LIVE_PAGES, POOL = 1024, 600, 9601
if os.environ.get("CHIP_COMMAND_A_TOY"):      # a rehearsal off the chip
    HEADS, KVH, HD, WINDOW, PPS = 8, 2, 16, 40, 16
    SPAN, LIVE_PAGES, POOL = 32, 12, 65
TOL = 0.03          # of a unit-variance value; bf16 operands read ~5e-3


def timed(fn, *args, n=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def pools(r, pages):
    shape = (pages, PAGE * KVH, HD)
    return (jnp.asarray(r.normal(size=shape).astype(np.float32)),
            jnp.asarray(r.normal(size=shape).astype(np.float32)))


def plain_decode(q, pk, pv, bt, pos, window: int):
    """``paged_attention.reference`` without its copy of every
    key/value head for each query head of the group (13 GB here)."""
    b, s = q.shape[0], bt.shape[1] * PAGE
    k, v = (p[bt].reshape(b, s, KVH, HD) for p in (pk, pv))
    sc = jnp.einsum("bhgd,bkhd->bhgk", q.reshape(b, KVH, HEADS // KVH, HD),
                    k) / HD ** 0.5
    j = jnp.arange(s)[None, :]
    live = j <= pos[:, None]
    if window:
        live = live & (j > pos[:, None] - window)
    p = jax.nn.softmax(jnp.where(live[:, None, None], sc, -1e30), axis=-1)
    return jnp.einsum("bhgk,bkhd->bhgd", p, v).reshape(b, HEADS, HD)


def decode_kernel(seed: int, window: int) -> bool:
    """16 slots at contexts of 3,000-9,600 (and the edges)."""
    r = np.random.default_rng(seed)
    slots, pages, n_live = 16, POOL, LIVE_PAGES
    top = n_live * PAGE
    q = jnp.asarray(r.normal(size=(slots, HEADS, HD)).astype(np.float32))
    pk, pv = pools(r, pages)
    bt = jnp.asarray((1 + r.integers(0, pages - 1, (slots * n_live,)))
                     .reshape(slots, n_live).astype(np.int32))
    bt = jnp.pad(bt, ((0, 0), (0, PPS - n_live)))
    pos = jnp.asarray(np.concatenate(
        [[0, 15, 16, min(WINDOW, top) - 1, min(WINDOW, top - 1), top - 1],
         r.integers(top // 3, top, (slots - 6,))]).astype(np.int32))
    got = paged_attention.window_decode_attention(q, pk, pv, bt, pos, PAGE,
                                                  window)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(plain_decode, static_argnums=(5,))(
            q, pk, pv, bt, pos, window)
    err = float(jnp.abs(got - want).max())
    secs = timed(lambda: paged_attention.window_decode_attention(
        q, pk, pv, bt, pos, PAGE, window))
    live = np.asarray(pos) + 1
    rows = int((np.minimum(live, window) if window else live).sum())
    nbytes = rows * 2 * KVH * HD * 4
    record("command_a_numerics", {
        "check": "window_decode_attention" if window
        else "paged_decode_attention (grouped, bf16)", "window": window,
        "max_abs_err": err, "ms": secs * 1e3, "rows_read": rows,
        "gb_s": nbytes / secs / 1e9, "finite": bool(jnp.isfinite(got).all())})
    return err < TOL


def span_kernel(seed: int, window: int, start: int) -> bool:
    """One span of 1,024 rows at ``start``."""
    r = np.random.default_rng(seed + 1)
    w, pages = SPAN, POOL
    q = jnp.asarray(r.normal(size=(w, HEADS, HD)).astype(np.float32))
    pk, pv = pools(r, pages)
    row = np.zeros((PPS,), np.int32)
    n_live = (start + w) // PAGE
    row[:n_live] = 1 + r.integers(0, pages - 1, (n_live,))
    if window:
        reach = (window + w) // PAGE + 2
        p0 = min(max((start - window + 1) // PAGE, 0), PPS - reach)
        ids = jnp.asarray(row[p0:p0 + reach])
    else:
        p0, ids = 0, jnp.asarray(row)
    args = (q, pk, pv, ids, jnp.int32(start), jnp.int32(p0 * PAGE))
    fn = jax.jit(lambda *a: span_attention.span_flash_attention(
        *a, PAGE, window))
    got = fn(*args)
    # the plain form a block of rows at a time (the whole span's
    # scores over 13,056 keys are 6.8 GB): the first and the last
    plain = jax.jit(lambda q, pk, pv, ids, q0, k0: span_attention.reference(
        q, pk, pv, ids, q0, k0, PAGE, window))
    err, blk = 0.0, min(128, w)
    with jax.default_matmul_precision("highest"):
        for lo in (0, w - blk):
            want = plain(q[lo:lo + blk], pk, pv, ids,
                         jnp.int32(start + lo), args[5])
            err = max(err, float(jnp.abs(got[lo:lo + blk] - want).max()))
    secs = timed(fn, *args, n=5)
    keys = (np.minimum(start + np.arange(w) + 1, window) if window
            else start + np.arange(w) + 1).sum()
    record("command_a_numerics", {
        "check": "span_flash_attention", "window": window, "start": start,
        "max_abs_err": err, "ms": secs * 1e3,
        "tflops": 4.0 * HEADS * HD * float(keys) / secs / 1e12,
        "finite": bool(jnp.isfinite(got).all())})
    return err < TOL


def main(argv) -> int:
    seed = int(argv[0]) if argv else 7
    deep = (PPS * PAGE - SPAN) // SPAN * SPAN
    ok = [decode_kernel(seed, WINDOW), decode_kernel(seed, 0),
          span_kernel(seed, WINDOW, deep), span_kernel(seed, WINDOW, 0),
          span_kernel(seed, 0, 0), span_kernel(seed, 0, deep)]
    return 0 if all(ok) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
