"""On the chip: the grouped layout's decode kernel alone,
``ops/paged_attention.py _window_kernel`` against ``reference``, at the
widths of the cells that run it (pages of 16 tokens of float32):

- ``smallthinker`` (``smallthinker-21b.longdoc``): 32 slots, 28 query
  heads on 4 key/value heads of 128 (a page a pool is 32 KB), the window
  layers' pool of 8,321 pages and the global layers' of 15,361;
- ``command-a`` (``command-a-plus.longdoc``): 16 slots, 128 on 8 (64 KB),
  pools of 4,193 and 9,601;
- ``granite`` (``granite-4.0-h-small.codegen``): 64 slots, 32 on 8
  (64 KB), a pool of 8,193 pages, the softmax's scale 1/128;
- ``jamba`` (``jamba2-3b.chat``): 8 slots, 20 on 1 (8 KB), 1,025 pages;
- ``nemotron`` (``nemotron-3-nano-30b.codegen``): 128 slots, 32 on 2
  (16 KB), a pool of 16,385 pages.

    chiprun -- python3 chip_window.py [seed] [another paged_attention.py]
                                      [shape,shape,...]

The first two shapes (an 816-wide table, live lengths uniform
2,048-13,056 a slot) run the bfloat16 form, under ``window`` 4,096
(``window_decode_attention`` in the trace) and 0
(``paged_decode_attention``); the last three (a 128-wide table, live
lengths uniform 128-2,047) run the float32-accurate form at window 0
(``paged_decode_attention_grouped``):

- the kernel as the tree has it, then with rings of 2 to 8 buffers,
  with waves of 256 KB and 1 MB a pool, and with Mosaic's bounds checks
  off (``nobc``: what the two checks a copy cost);
- two ablations of the SAME kernel, made while it is traced and never
  in the tree: ``copies`` (every product answers zeros and ``exp`` is
  the identity: what the page copies reach by themselves) and
  ``products`` (no copy is started or waited for: the wave's work on a
  resident buffer);
- at the float32 shapes, the same walk in its bfloat16 form (``bf16``);
- with the path of another ``paged_attention.py`` (the parent
  commit's), its kernel whole and under the same two ablations, as
  ``parent``, ``parent_copies``, ``parent_products``.

A time is the device's own, from a profiler trace of 20 calls
(``chip_gmm.py device_seconds``).  GB/s are over the live pages as they
lie (every page from the window's first to the last live one, keys and
values) and over the rows the model needs (what
``kernel.longdoc_window_decode_roofline`` counts).  One JSON line a
measurement, appended to ``chiprun_out/window.jsonl``.  Exits non-zero
without a TPU, or where a kernel is further from the reference (float32
at ``highest``) than its form's gate: 3e-2 for bfloat16 operands (they
read ~5e-3 of a unit-variance value), 1e-4 for the float32-accurate
form.

    python3 chip_window.py bundles [another paged_attention.py]

No chip: compiles the float32-accurate form, the bfloat16 form and the
other file's grouped kernel at each float32-accurate shape for a
described v5e with the compiler's instruction listing on (a child
process a form and shape, which the compiler ends with an abort AFTER it
has written the listing), and prints each one's bundles, in all and in
the loop over a wave, with the MXU's weight pushes and products there.
"""
import contextlib
import glob
import importlib.util
import json
import os
import re
import subprocess
import sys
import tempfile
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

PAGE, HD, WINDOW = 16, 128, 4096
# name: (slots, heads, kv_heads, window pool's pages, global pool's)
SHAPES = {"smallthinker": (32, 28, 4, 8321, 15361),
          "command-a": (16, 128, 8, 4193, 9601),
          "granite": (64, 32, 8, None, 8193),
          "jamba": (8, 20, 1, None, 1025),
          "nemotron": (128, 32, 2, None, 16385)}
# the float32-accurate shapes: the softmax's scale (None: hd^-0.5)
EXACT = {"granite": 0.0078125, "jamba": None, "nemotron": None}
# (table width, live lengths) by form
TABLES = {False: (816, (2048, 13056)), True: (128, (128, 2048))}
HBM_GBS = 819.0
TOLERANCE = {False: 3e-2, True: 1e-4}


def inputs(seed: int, shape: str, window: int):
    """Queries, two pools of random rows, a table of random pages and
    the slots' last positions -> those, the pages a call fetches and
    the rows it attends."""
    slots, heads, kvh, win_pages, all_pages = SHAPES[shape]
    table, lengths = TABLES[shape in EXACT]
    pages = win_pages if window else all_pages
    r = np.random.default_rng(seed)
    q = r.normal(size=(slots, heads, HD)).astype(np.float32)
    pk, pv = (jnp.asarray(r.normal(size=(pages, PAGE * kvh, HD))
                          .astype(np.float32)) for _ in range(2))
    bt = (1 + r.integers(0, pages - 1, (slots, table))).astype(np.int32)
    live = r.integers(*lengths, (slots,))
    pos = live - 1
    first = np.maximum(pos - window + 1, 0) // PAGE if window else 0 * pos
    fetched = int((pos // PAGE + 1 - first).sum())
    rows = int((np.minimum(live, window) if window else live).sum())
    args = (jnp.asarray(q), pk, pv, jnp.asarray(bt),
            jnp.asarray(pos.astype(np.int32)))
    return args, fetched, rows


def plain(kvh: int, window: int, scale=None):
    """``paged_attention.reference`` without its copy of every
    key/value head for each query head of the group (GBs at these
    widths; ``benchmarks/tests/chip_smallthinker.py plain_decode``,
    which is no module a root script can import)."""
    scale = HD ** -0.5 if scale is None else scale

    def fn(q, pk, pv, bt, pos):
        b, heads, n = q.shape[0], q.shape[1], bt.shape[1] * PAGE
        k, v = (p[bt].reshape(b, n, kvh, HD) for p in (pk, pv))
        sc = jnp.einsum("bhgd,bkhd->bhgk",
                        q.reshape(b, kvh, heads // kvh, HD), k) * scale
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
           nobc=False, exact=False, scale=None, interpret=None):
    """``mod``'s kernel with its two constants set, and one ablation
    patched in, for the time it is traced.  ``exact``: the grouped
    heads' float32 form (a parent's ``_grouped_kernel`` where ``mod``
    has one)."""
    patches = list(ABLATIONS[ablation])
    interpret = pa._resolve_interpret(interpret)
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
            if exact and hasattr(mod, "_grouped_call"):
                return mod._grouped_call.__wrapped__(
                    *args, page=PAGE, scale=scale, interpret=interpret)
            if exact:
                return mod._window_call.__wrapped__(
                    *args, page=PAGE, window=0, scale=scale, exact=True,
                    interpret=interpret)
            return mod._window_call.__wrapped__(
                *args, page=PAGE, window=window, interpret=interpret,
                **({"scale": scale} if scale else {}))
    return fn


def variants(window: int, parent, exact: bool, scale) -> dict:
    mine = dict(mod=pa, exact=exact, scale=scale)
    out = {"tree": mine, "copies": dict(mine, ablation="copies"),
           "products": dict(mine, ablation="products"),
           "nobc": dict(mine, nobc=True)}
    if exact:
        out["bf16"] = dict(mod=pa, scale=scale)
    if window:
        out.update({f"ring_{n}": dict(mod=pa, ring=n)
                    for n in (2, 3, 4, 5, 6, 8) if n != pa._WINDOW_RING})
        out.update({f"wave_{kb}k": dict(mod=pa, wave_kb=kb)
                    for kb in (256, 1024)})
    if parent is not None and exact:
        mine = dict(mod=parent, exact=True, scale=scale)
        out.update({"parent": mine,
                    "parent_copies": dict(mine, ablation="copies"),
                    "parent_products": dict(mine, ablation="products")})
    elif parent is not None:
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
    shapes = sys.argv[3].split(",") if len(sys.argv) > 3 else list(SHAPES)
    if len(sys.argv) > 2 and sys.argv[2]:
        parent = _load(sys.argv[2])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out", "window.jsonl"), "a")

    def record(**kw):
        line = json.dumps({"device": dev.device_kind, "seed": seed, **kw})
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    worst = {False: 0.0, True: 0.0}
    for shape in shapes:
        kvh = SHAPES[shape][2]
        exact = shape in EXACT
        scale = EXACT.get(shape)
        for window in (0,) if exact else (WINDOW, 0):
            args, fetched, rows = inputs(seed, shape, window)
            with jax.default_matmul_precision("highest"):
                want = np.asarray(plain(kvh, window, scale)(*args))
            vs = variants(window, parent, exact, scale)
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
                    form = bool(vs[name].get("exact"))
                    rec["form"] = "float32" if form else "bfloat16"
                    worst[form] = max(worst[form], rec["max_err"])
                record(**rec)
    ok = all(worst[f] < TOLERANCE[f] for f in worst)
    for form, err in worst.items():
        record(check="the grouped decode kernel against the plain gather",
               form="float32" if form else "bfloat16", max_err=err,
               gate=TOLERANCE[form], ok=err < TOLERANCE[form])
    return 0 if ok else 1


# -- the instruction bundles, for a described v5e -----------------------------

def _load(path):
    spec = importlib.util.spec_from_file_location(
        "brpc_tpu.ops.parent_paged_attention", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bundle_variants(path: str, shape: str) -> dict:
    """The forms whose bundles :func:`bundles` counts, at ``shape``."""
    scale = EXACT[shape]
    mine = dict(mod=pa, exact=True, scale=scale)
    out = {"tree": mine, "bf16": dict(mod=pa, scale=scale)}
    if path:
        out["parent"] = dict(mine, mod=_load(path))
    return out


def compile_for_v5e(which: str, path: str, shape: str) -> None:
    """Compile one form at ``shape`` for a described v5e (a child of
    :func:`bundles`, with the listing's flags in its environment)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    slots, heads, kvh, _w, pages = SHAPES[shape]
    table = TABLES[True][0]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pool = arg((pages, PAGE * kvh, HD), jnp.float32)
    fn = kernel(window=0, interpret=False,
                **bundle_variants(path, shape)[which])
    jax.jit(fn).lower(arg((slots, heads, HD), jnp.float32), pool, pool,
                      arg((slots, table), jnp.int32),
                      arg((slots,), jnp.int32)).compile()


def count_bundles(dump: str) -> dict:
    """The kernel's final bundles: in all, and in the loop over a wave
    (its header to the first branch after it, the back edge), with the
    weight pushes and products the MXU is handed there."""
    paths = listings(dump)
    bundles = []
    for line in open(paths[0]):
        m = re.match(r"\s*(0x[0-9a-f]+|\d+)\s+(LH|LB|LE|PB|PF|CT)?:?\s*(>*)"
                     r"\s*\{", line)
        if m:
            bundles.append((int(m.group(1), 0), m.group(2),
                            len(m.group(3)), line))
    head = min(at for at, mark, depth, _l in bundles
               if mark == "LB" and depth == 2)
    back = min(at for at, _m, _d, line in bundles
               if at > head and "sbr.rel" in line)
    wave = [line for at, _m, _d, line in bundles if head <= at <= back]
    return {"bundles": len(bundles), "wave": back - head + 1,
            "weight_pushes": sum(line.count("vmatpush") for line in wave),
            "products": sum(line.count("vmatmul") for line in wave)}


def listings(dump: str) -> list:
    """The kernel's instruction listings a compile wrote into ``dump``."""
    return [p for p in glob.glob(os.path.join(
        dump, "*paged_decode_attention*final_bundles.txt"))
        if "schedule-analysis" not in p]


def bundles() -> int:
    path = sys.argv[2] if len(sys.argv) > 2 else ""
    for shape, which in ((s, w) for s in EXACT
                         for w in bundle_variants(path, s)):
        with tempfile.TemporaryDirectory(prefix="window_bundles_") as dump:
            # the listing's flags before any the environment gives, so
            # the listing is that of the program those flags compile
            flags = (f"--xla_jf_dump_to={dump} --xla_jf_dump_llo_text=true "
                     + os.environ.get("LIBTPU_INIT_ARGS", "")).strip()
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       TPU_LOG_DIR="disabled", LIBTPU_INIT_ARGS=flags)
            child = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "_compile",
                 which, path, shape], env=env, stdin=subprocess.DEVNULL,
                stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
                text=True)
            # the compiler aborts AFTER writing the listing, so a
            # non-zero exit alone says nothing: no listing does
            if not listings(dump):
                sys.stderr.write(child.stderr[-4000:])
                raise SystemExit(f"{which} at {shape}: the compile wrote "
                                 f"no listing "
                                 f"(exit code {child.returncode})")
            print(json.dumps({"kernel": which, "shape": shape,
                              "target": "v5e (described)",
                              **count_bundles(dump)}), flush=True)
    return 0


if __name__ == "__main__":
    if sys.argv[1:2] == ["bundles"]:
        sys.exit(bundles())
    if sys.argv[1:2] == ["_compile"]:
        sys.exit(compile_for_v5e(*sys.argv[2:5]))
    sys.exit(main())
