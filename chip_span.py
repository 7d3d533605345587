"""On the chip: what a span of a prompt's fill does to its session's
pages, alone, at the widths of the two cells that fill through the
pages: ``ops/span_attention.py``'s ``span_flash_attention`` against
``reference``, and ``write`` (the kernel's copies, and XLA's merge)
against a scatter of separate rows.

    chiprun -- python3 chip_span.py [seed] [another span_attention.py]
    chiprun -- python3 chip_span.py fills <cell> <seconds> <seed>

- ``ouro-2.6b.batch``: 16 whole heads of 128, spans of 256 rows from
  position 0, 256, 512 and 768 (the batch mix's), a table of 128
  pages of 16 tokens in a pool of 784: the keys read in place, at key
  blocks of 128, 256 (the tree's) and 512;
- ``command-a-plus.longdoc``: 128 query heads on 8 key/value heads,
  spans of 1,024 rows from 0, 4,096 and 11,264: the global layer over
  a table of 816 pages in a pool of 9,601, a window layer (4,096) over
  the 322 entries its reach takes in a pool of 4,193: the keys
  gathered once.

With the path of another ``span_attention.py`` (the parent commit's,
an experiment's), its ``span_flash_attention`` is timed beside the
tree's on the same operands as ``parent`` (two programs of one
compiled text run under the first one's name: read the sum).  A time
is the device's own, from a profiler trace of ``CALLS`` calls
(``benchmarks/harness/xplane``); the pools are donated and handed on,
as a fill's loop carries them.  One JSON line a measurement, appended
to ``chiprun_out/span.jsonl``.  Exits non-zero without a TPU, or where
the kernel is further than 6e-2 from the reference.

``fills``: one untraced window of a cell through the runner's own
``run_window``, judged as the runner judges it; prints
``kv_stats()["fill"]`` over the window, which the benchmark's own chip
scripts do not, and the result's line.
"""
import importlib.util
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.harness import xplane  # noqa: E402
from brpc_tpu.ops import span_attention as S  # noqa: E402

PAGE, CALLS, TOLERANCE = 16, 20, 6e-2
# name: (span, heads, kv_heads, hd, pool pages, table, window, whole
# heads, the spans' first positions)
SHAPES = {
    "ouro": (256, 16, 16, 128, 784, 128, 0, True, (0, 256, 512, 768)),
    "longdoc_global": (1024, 128, 8, 128, 9601, 816, 0, False,
                       (0, 4096, 11264)),
    "longdoc_window": (1024, 128, 8, 128, 4193, 322, 4096, False,
                       (0, 4096, 11264)),
}


def device_seconds(runs):
    """``runs``: ``{name: (jitted fn, args, carried)}``; where
    ``carried``, the first argument is donated and the result handed
    on in its place -> device seconds a call by name."""
    def call(name, first):
        fn, args, carried = runs[name]
        return fn(first, *args[1:]) if carried else fn(*args)

    last = {name: jax.block_until_ready(call(name, runs[name][1][0]))
            for name in runs}
    path = tempfile.mkdtemp(prefix="span_trace_")
    jax.profiler.start_trace(path)
    for name in runs:
        c = last[name]
        for _ in range(CALLS):
            c = call(name, c)
        jax.block_until_ready(c)
    jax.profiler.stop_trace()
    red = xplane.reduce_trace(xplane.find_xplane(path), top=10_000)
    shutil.rmtree(path, ignore_errors=True)
    return {name: sum(red["programs"].get("jit_" + name, [0.0])) / CALLS
            for name in runs}


def named(name, fn, **jit_kw):
    """``fn`` jitted as the program ``jit_<name>`` of a trace."""
    fn.__name__ = name
    return jax.jit(fn, **jit_kw)


def rows_scatter(pool, rows, page_idx, row):
    """A fill's write until PR 41: a row at a time."""
    if pool.ndim == 4:
        return pool.at[page_idx, row].set(rows)
    kvh = rows.shape[1]
    return pool.at[page_idx[:, None],
                   row[:, None] * kvh + jnp.arange(kvh)[None, :]].set(rows)


def fills(cell: str, seconds: float, seed: int) -> int:
    from benchmarks import run as bench_run
    from benchmarks.harness import compare, spec

    win = bench_run.run_window(spec.Cell(cell), seed, seconds, trace=False)
    c0, c1 = (c["kv"]["fill"] for c in (win.run.c0, win.run.c1))
    grew = {k: c1[k] - c0[k] for k in c1}
    grew["attended_share"] = grew["pages_attended"] / max(
        grew["pages_table"], 1)
    print(json.dumps({"cell": cell, "seed": seed, "fill": grew}))
    line = win.judged(compare.compare(win.reference(), win.sample))
    print(json.dumps(line), flush=True)
    return 0 if line["correct"] else 1


def main() -> int:
    if sys.argv[1:2] == ["fills"]:
        return fills(sys.argv[2], float(sys.argv[3]), int(sys.argv[4]))
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 41
    parent = None
    if len(sys.argv) > 2:
        spec = importlib.util.spec_from_file_location(
            "brpc_tpu.ops.parent_span_attention", sys.argv[2])
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("chip_span.py needs a TPU, found", dev.platform)
        return 2
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "span.jsonl"), "a")
    bad = 0

    def record(**kw):
        line = json.dumps({"device": dev.device_kind, "seed": seed, **kw})
        print(line, flush=True)
        out.write(line + "\n")

    for shape, (w, heads, kvh, hd, pages, table, window, whole,
                starts) in SHAPES.items():
        r = np.random.default_rng(seed)
        dims = (pages, PAGE, kvh, hd) if whole else (pages, PAGE * kvh, hd)
        key = jax.random.PRNGKey(seed)
        pk, pv = (jax.random.normal(k, dims, jnp.float32)
                  for k in jax.random.split(key))
        q = jnp.asarray(r.normal(size=(w, heads, hd)), jnp.float32)
        new = jnp.asarray(r.normal(size=(w, kvh, hd)), jnp.float32)
        ids = jnp.asarray(1 + r.permutation(pages - 1)[:table], jnp.int32)
        for start in starts:
            k0 = 0
            if window:
                k0 = PAGE * min(max(0, (start - window + 1) // PAGE),
                                (13056 // PAGE) - table)
            args = (q, pk, pv, ids, jnp.int32(start), jnp.int32(k0))

            def attend(name, mod, **kw):
                return named(name, lambda q, pk, pv, ids, q0, k0:
                             mod.span_flash_attention(
                                 q, pk, pv, ids, q0, k0, PAGE, window, **kw))

            runs = {"kernel": attend("kernel", S)}
            if shape == "ouro":
                runs.update({f"kernel_bk{bk}": attend(f"kernel_bk{bk}", S,
                                                      block_k=bk)
                             for bk in (128, 512)})
            if parent is not None:
                runs["parent"] = attend("parent", parent)
            runs = {name: (fn, args, False) for name, fn in runs.items()}
            got = np.asarray(S.span_flash_attention(*args, PAGE, window))
            # (the plain form a quarter of the rows at a time: its
            # scores over longdoc's 13,056 keys are 6.8 GB whole)
            want = np.concatenate([np.asarray(S.reference(
                q[i:i + 256], pk, pv, ids, start + i, k0, PAGE, window))
                for i in range(0, w, 256)])
            err = float(np.abs(got - want).max())
            bad += err > TOLERANCE
            secs = device_seconds(runs)
            reach = start + w - k0
            for name, s in secs.items():
                record(shape=shape, what=name, start=start,
                       keys_reached=reach, device_us=s * 1e6,
                       max_err=err if name == "kernel" else None)
        # the span's rows into its pages: whole pages, and row by row
        n = w - PAGE // 2                       # a partial last page
        mine = ids[:w // PAGE]
        j = jnp.arange(w)
        page_idx = jnp.where(j < n, ids[j // PAGE], 0)

        runs = {
            "write_pages": (named(
                "write_pages", lambda pool, new, mine: S.write(
                    pool, new, mine, n, PAGE), donate_argnums=0),
                (pk, new, mine), True),
            "write_plain": (named(
                "write_plain", lambda pool, new, mine: S.write_plain(
                    pool, new, mine, n, PAGE), donate_argnums=0),
                (jnp.copy(pk), new, mine), True),
            "write_rows": (named(
                "write_rows", lambda pool, new, page_idx: rows_scatter(
                    pool, new, page_idx, j % PAGE), donate_argnums=0),
                (pv, new, page_idx), True)}
        for name, s in device_seconds(runs).items():
            record(shape=shape, what=name, rows=w, device_us=s * 1e6)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
