"""On the chip: the window schedule's decode kernel alone,
``ops/paged_attention.py window_decode_attention`` against ``reference``,
at the widths of the two cells that run it (pages of 16 tokens of
float32, an 816-wide table, a window of 4,096):

- ``smallthinker`` (``smallthinker-21b.longdoc``): 32 slots, 28 query
  heads on 4 key/value heads of 128 (a page a pool is 32 KB), the window
  layers' pool of 8,321 pages and the global layers' of 15,361;
- ``command-a`` (``command-a-plus.longdoc``): 16 slots, 128 on 8 (64 KB),
  pools of 4,193 and 9,601.

    chiprun -- python3 chip_window.py [seed] [another paged_attention.py]

Each shape under ``window`` 4,096 (``window_decode_attention`` in the
trace) and 0 (``paged_decode_attention``), live lengths as the cells
have them (uniform 2,048-13,056 a slot):

- the kernel as the tree has it, then with rings of 2 to 8 buffers,
  with waves of 256 KB and 1 MB a pool, and with Mosaic's bounds checks
  off (``nobc``: what the two checks a copy cost);
- two ablations of the SAME kernel, made while it is traced and never
  in the tree: ``copies`` (every product answers zeros and ``exp`` is
  the identity: what the page copies reach by themselves) and
  ``products`` (no copy is started or waited for: the wave's work on a
  resident buffer);
- with the path of another ``paged_attention.py`` (the parent
  commit's), its kernel whole and under the same two ablations, as
  ``parent``, ``parent_copies``, ``parent_products``.

A time is the device's own, from a profiler trace of 20 calls
(``chip_gmm.py device_seconds``).  GB/s are over the live pages as they
lie (every page from the window's first to the last live one, keys and
values) and over the rows the model needs (what
``kernel.longdoc_window_decode_roofline`` counts).  One JSON line a
measurement, appended to ``chiprun_out/window.jsonl``.  Exits non-zero
without a TPU, or where a kernel is further than 3e-2 from the
reference (bfloat16 operands read ~5e-3 of a unit-variance value).
"""
import contextlib
import importlib.util
import json
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from brpc_tpu.ops import paged_attention as pa  # noqa: E402
from chip_gmm import CALLS, device_seconds  # noqa: E402
from chip_mla import ABLATIONS  # noqa: E402

PAGE, HD, TABLE, WINDOW = 16, 128, 816, 4096
# name: (slots, heads, kv_heads, window pool's pages, global pool's)
SHAPES = {"smallthinker": (32, 28, 4, 8321, 15361),
          "command-a": (16, 128, 8, 4193, 9601)}
LIVE = (2048, 13056)
HBM_GBS = 819.0
TOLERANCE = 3e-2


def inputs(seed: int, shape: str, window: int):
    """Queries, two pools of random rows, a table of random pages and
    the slots' last positions -> those, the pages a call fetches and
    the rows it attends."""
    slots, heads, kvh, win_pages, all_pages = SHAPES[shape]
    pages = win_pages if window else all_pages
    r = np.random.default_rng(seed)
    q = r.normal(size=(slots, heads, HD)).astype(np.float32)
    pk, pv = (jnp.asarray(r.normal(size=(pages, PAGE * kvh, HD))
                          .astype(np.float32)) for _ in range(2))
    bt = (1 + r.integers(0, pages - 1, (slots, TABLE))).astype(np.int32)
    live = r.integers(*LIVE, (slots,))
    pos = live - 1
    first = np.maximum(pos - window + 1, 0) // PAGE if window else 0 * pos
    fetched = int((pos // PAGE + 1 - first).sum())
    rows = int((np.minimum(live, window) if window else live).sum())
    args = (jnp.asarray(q), pk, pv, jnp.asarray(bt),
            jnp.asarray(pos.astype(np.int32)))
    return args, fetched, rows


def plain(kvh: int, window: int):
    """``paged_attention.reference`` without its copy of every
    key/value head for each query head of the group (GBs at these
    widths; ``benchmarks/tests/chip_smallthinker.py plain_decode``,
    which is no module a root script can import)."""
    def fn(q, pk, pv, bt, pos):
        b, heads, n = q.shape[0], q.shape[1], bt.shape[1] * PAGE
        k, v = (p[bt].reshape(b, n, kvh, HD) for p in (pk, pv))
        sc = jnp.einsum("bhgd,bkhd->bhgk",
                        q.reshape(b, kvh, heads // kvh, HD), k) / HD ** 0.5
        j = jnp.arange(n)[None, :]
        live = j <= pos[:, None]
        if window:
            live = live & (j > pos[:, None] - window)
        p = jax.nn.softmax(jnp.where(live[:, None, None], sc, -1e30),
                           axis=-1)
        return jnp.einsum("bhgk,bkhd->bhgd", p, v).reshape(b, heads, HD)
    return jax.jit(fn)


_PARAMS = pltpu.CompilerParams


def _no_bounds_checks(**kw):
    return _PARAMS(disable_bounds_checks=True, **kw)


def kernel(mod, window: int, ring=None, wave_kb=None, ablation=None,
           nobc=False):
    """``mod``'s kernel with its two constants set, and one ablation
    patched in, for the time it is traced."""
    patches = list(ABLATIONS[ablation])
    if ring:
        patches.append((mod, "_WINDOW_RING", ring))
    if wave_kb:
        patches.append((mod, "_WAVE_BYTES", wave_kb << 10))
    if nobc:
        patches.append((pltpu, "CompilerParams", _no_bounds_checks))

    def fn(*args):
        with contextlib.ExitStack() as stack:
            for p in patches:
                stack.enter_context(mock.patch.object(*p))
            return mod._window_call.__wrapped__(
                *args, page=PAGE, window=window,
                interpret=pa._resolve_interpret(None))
    return fn


def variants(window: int, parent) -> dict:
    out = {"tree": dict(mod=pa), "copies": dict(mod=pa, ablation="copies"),
           "products": dict(mod=pa, ablation="products"),
           "nobc": dict(mod=pa, nobc=True)}
    if window:
        out.update({f"ring_{n}": dict(mod=pa, ring=n)
                    for n in (2, 3, 4, 5, 6, 8) if n != pa._WINDOW_RING})
        out.update({f"wave_{kb}k": dict(mod=pa, wave_kb=kb)
                    for kb in (256, 1024)})
    if parent is not None:
        out.update({"parent": dict(mod=parent),
                    "parent_copies": dict(mod=parent, ablation="copies"),
                    "parent_products": dict(mod=parent,
                                            ablation="products")})
    return out


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: {dev.platform}", file=sys.stderr)
        return 1
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 1
    parent = None
    if len(sys.argv) > 2:
        spec = importlib.util.spec_from_file_location(
            "brpc_tpu.ops.parent_paged_attention", sys.argv[2])
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out", "window.jsonl"), "a")

    def record(**kw):
        line = json.dumps({"device": dev.device_kind, "seed": seed, **kw})
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    worst = 0.0
    for shape, (_slots, _heads, kvh, _w, _a) in SHAPES.items():
        for window in (WINDOW, 0):
            args, fetched, rows = inputs(seed, shape, window)
            with jax.default_matmul_precision("highest"):
                want = np.asarray(plain(kvh, window)(*args))
            vs = variants(window, parent)
            fns = {}
            for i, (name, kw) in enumerate(vs.items()):
                # a serial number among the results: two programs that
                # differed by their names alone would share one
                # executable
                def fn(*a, k=kernel(window=window, **kw), i=i):
                    return k(*a), jnp.int32(i)
                fn.__name__ = name
                fns[name] = jax.jit(fn)
            _secs, red = device_seconds(fns, {name: args for name in fns})
            op = "window_decode_attention" if window \
                else "paged_decode_attention"
            page_bytes = 2 * PAGE * kvh * HD * 4
            for name in fns:
                secs = sum(t for key, t in red["device_ops"]
                           if key.startswith(f"jit_{name}: {op}")) / CALLS
                model = rows * 2 * kvh * HD * 4 / secs / 1e9
                rec = dict(shape=shape, window=window, variant=name,
                           operation=op, pages_fetched=fetched,
                           rows_attended=rows, kernel_ms=secs * 1e3,
                           gbs_as_they_lie=fetched * page_bytes / secs / 1e9,
                           gbs_model=model,
                           roofline_share=100.0 * model / HBM_GBS)
                if not vs[name].get("ablation"):
                    got = np.asarray(fns[name](*args)[0])
                    rec["max_err"] = float(np.abs(got - want).max())
                    rec["out_std"] = float(want.std())
                    worst = max(worst, rec["max_err"])
                record(**rec)
    record(check="window_decode_attention against the plain gather",
           max_err=worst, ok=worst < TOLERANCE)
    return 0 if worst < TOLERANCE else 1


if __name__ == "__main__":
    sys.exit(main())
