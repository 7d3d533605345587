"""On the chip: the latent decode kernel alone, ``ops/paged_attention.py
mla_decode_attention`` against ``mla_reference``, at
``kimi-k2.7-code.codegen``'s widths (64 slots, 64 heads, ``kv_lora`` 512,
rope 64, pages of 16 rows, a 128-wide table, a pool of 7,169 pages of
float32 rows padded to 640 lanes).

    chiprun -- python3 chip_mla.py [seed] [kimi-k2 | kimi-linear]

(``kimi-linear``: ``kimi-linear-48b.codegen``'s, 128 slots, 32 heads, a
pool of 16,385 pages, no YaRN in the scale.)

- live lengths as the cell has them (uniform 256-2,047 a slot) and all
  slots at 1,024 (no partial wave);
- the kernel as the tree has it, then with waves of 512 rows and with
  rings of 2, 3 and 6 buffers (where the tree's kernel has a ring);
- two ablations of the SAME kernel, made while it is traced and never
  in the tree: ``copies`` (every product answers zeros and ``exp`` is
  the identity: what the page copies reach by themselves) and
  ``products`` (no copy is started or waited for: the wave's work on a
  resident buffer).

A time is the device's own, from a profiler trace of 20 calls
(``chip_gmm.py device_seconds``).  GB/s are over the live rows, as
they lie in the pool (2,560 B a row) and as the model needs them
(2,304 B: what ``kernel.codegen_mla_decode_roofline`` counts, so its
ceiling is 90%).  One JSON line a measurement, appended to
``chiprun_out/mla.jsonl``.  Exits non-zero without a TPU, or where the
kernel is further than 6e-2 from the reference.
"""
import contextlib
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

SLOTS, HEADS, KL, ROPE, PAGE, TABLE, PAGES, ROW = 64, 64, 512, 64, 16, 128, \
    7169, 640
SCALE = 192 ** -0.5 * 1.4159 ** 2       # the configuration's, with yarn
# the other latent configuration's: (slots, heads, pages, scale)
SHAPES = {"kimi-k2": (SLOTS, HEADS, PAGES, SCALE),
          "kimi-linear": (128, 32, 16385, 192 ** -0.5)}
HBM_GBS = 819.0
TOLERANCE = 6e-2


def inputs(seed: int, lengths: str):
    """Queries, a pool of random rows (padding lanes zero), a table of
    distinct pages and the slots' last positions -> those and the live
    rows a call."""
    r = np.random.default_rng(seed)
    ql = r.normal(size=(SLOTS, HEADS, KL)).astype(np.float32)
    qr = r.normal(size=(SLOTS, HEADS, ROPE)).astype(np.float32)
    pc = np.zeros((PAGES, PAGE, ROW), np.float32)
    pc[..., :KL + ROPE] = r.normal(size=(PAGES, PAGE, KL + ROPE))
    held = (PAGES - 1) // SLOTS
    bt = (1 + r.permutation(PAGES - 1)[:SLOTS * held]).reshape(SLOTS, held)
    bt = np.pad(bt, ((0, 0), (0, TABLE - held))).astype(np.int32)
    live = r.integers(256, 2048, (SLOTS,)) if lengths == "uniform" \
        else np.full((SLOTS,), int(lengths))
    live = np.minimum(live, held * PAGE)
    args = tuple(jnp.asarray(a) for a in
                 (ql, qr, pc, bt, (live - 1).astype(np.int32)))
    return args, int(live.sum())


class _NoCopy:
    """What ``make_async_copy`` answers in the ``products`` ablation."""

    def start(self):
        pass

    def wait(self):
        pass


def _zeros_dot(a, b, dimension_numbers=None, **_kw):
    """A product's shape with none of its work."""
    if dimension_numbers is None:
        return jnp.zeros((a.shape[0], b.shape[1]), jnp.float32)
    (ca, cb), _batch = dimension_numbers
    return jnp.zeros(
        tuple(d for i, d in enumerate(a.shape) if i not in ca)
        + tuple(d for i, d in enumerate(b.shape) if i not in cb),
        jnp.float32)


ABLATIONS = {
    None: (),
    "copies": ((jax.lax, "dot_general", _zeros_dot),
               (jnp, "dot", _zeros_dot), (jnp, "exp", lambda x: x)),
    "products": ((pltpu, "make_async_copy", lambda *_a: _NoCopy()),),
}


def kernel(wave_tokens=None, ring=None, ablation=None):
    """The tree's kernel with its two constants set, and one ablation
    patched in, for the time it is traced."""
    patches = list(ABLATIONS[ablation])
    if wave_tokens:
        patches.append((pa, "_LATENT_WAVE_TOKENS", wave_tokens))
    if ring:
        patches.append((pa, "_LATENT_RING", ring))

    def fn(*args):
        with contextlib.ExitStack() as stack:
            for p in patches:
                stack.enter_context(mock.patch.object(*p))
            return pa._latent_call.__wrapped__(
                *args, scale=SCALE, interpret=pa._resolve_interpret(None))
    return fn


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: {dev.platform}", file=sys.stderr)
        return 1
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 1
    global SLOTS, HEADS, PAGES, SCALE
    shape = sys.argv[2] if len(sys.argv) > 2 else "kimi-k2"
    SLOTS, HEADS, PAGES, SCALE = SHAPES[shape]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out", "mla.jsonl"), "a")

    def record(**kw):
        line = json.dumps({"device": dev.device_kind, "seed": seed,
                           "shape": shape, **kw})
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    variants = {"tree": {}, "waves_512": dict(wave_tokens=512),
                "copies": dict(ablation="copies"),
                "products": dict(ablation="products")}
    if hasattr(pa, "_LATENT_RING"):
        variants.update({f"ring_{n}": dict(ring=n) for n in (2, 3, 6)})
    worst = 0.0
    for lengths in ("uniform", "1024"):
        args, live = inputs(seed, lengths)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(pa.mla_reference(*args, SCALE))
        fns = {}
        for i, (name, kw) in enumerate(variants.items()):
            # a serial number among the results: two programs that
            # differed by their names alone would share one executable
            def fn(*a, k=kernel(**kw), i=i):
                return k(*a), jnp.int32(i)
            fn.__name__ = name
            fns[name] = jax.jit(fn)
        _secs, red = device_seconds(fns, {name: args for name in fns})
        for name in fns:
            secs = sum(t for key, t in red["device_ops"] if key.startswith(
                f"jit_{name}: mla_decode_attention")) / CALLS
            model = live * (KL + ROPE) * 4 / secs / 1e9
            rec = dict(variant=name, lengths=lengths, live_rows=live,
                       kernel_ms=secs * 1e3,
                       gbs_as_they_lie=live * ROW * 4 / secs / 1e9,
                       gbs_model=model,
                       roofline_share=100.0 * model / HBM_GBS)
            if not variants[name].get("ablation"):
                got = np.asarray(fns[name](*args)[0])
                rec["max_err"] = float(np.abs(got - want).max())
                rec["out_std"] = float(want.std())
                worst = max(worst, rec["max_err"])
            record(**rec)
    record(check="mla_decode_attention against mla_reference",
           max_err=worst, ok=worst < TOLERANCE)
    return 0 if worst < TOLERANCE else 1


if __name__ == "__main__":
    sys.exit(main())
