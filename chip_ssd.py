"""On the chip: the Mamba-2 decode update ``ops/ssd.py ssd_step`` alone,
at ``granite-4.0-h-small``'s widths (64 slots, 128 heads of 64, a state
of 128, one group: 4 MB of float32 a slot).

    python3 chip_ssd.py [seed] [another ssd.py]   # one v5e chip

At 64, 32, 1 and 0 active slots:

- ``tree``: the kernel as the tree has it (the pool laid ``(64, 128,
  128)`` a slot: ``N`` on the sublanes, ``(head, p)`` on the lanes);
- ``copies``: the same grid, blocks, aliasing and active order with a
  body that only copies the block (what the pipeline's copies reach by
  themselves: the kernel's ceiling), patched in while it is traced;
- with the path of another ``ssd.py`` (the parent commit's, whose pool
  lies ``(heads, P, N)`` a slot), its kernel as ``parent``.

A time is the device's own, from a profiler trace of 20 calls
(``chip_gmm.py device_seconds``: the kernel's operation alone); GB/s
are over the active slots' state, read once and written once.  Each
kernel's largest error against ``ssd.sequential`` (float32 at
``highest``) on the state and on ``y``, both in the equation's layout,
and whether the idle slots' state came back bit for bit.  One JSON line
a measurement, appended to ``chiprun_out/ssd.jsonl``.  Exits non-zero
without a TPU, or where a kernel is further than 1e-4 from the plain
scan.

    python3 chip_ssd.py bundles [another ssd.py]

No chip: compiles each kernel for a described v5e with the compiler's
instruction listing on (a child process a kernel, which the compiler
ends with an abort AFTER it has written the listing) and prints the
kernel's bundles, in all and in the active slot's branch (~1 ns a
bundle).
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

from brpc_tpu.ops import ssd  # noqa: E402

SLOTS, HEADS, P, N, GROUPS = 64, 128, 64, 128, 1
ACTIVE = (64, 32, 1, 0)
HBM_GBS = 819.0
TOLERANCE = 1e-4


def _copies_kernel(order_ref, n_ref, *refs, **_):
    """The step's pipeline with a body that moves the block and nothing
    else: ``s_in`` back out, ``y`` zero."""
    s_in, s_ref, y_ref = refs[-3:]
    s_ref[...] = s_in[...]
    y_ref[...] = jnp.zeros_like(y_ref)


def load(path):
    """Another ``ssd.py`` as a module beside the tree's (its relative
    imports resolve in ``brpc_tpu.ops``)."""
    spec = importlib.util.spec_from_file_location(
        "brpc_tpu.ops.other_ssd", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def equation_layout(mod) -> bool:
    """Whether ``mod``'s pool is the equation's ``(heads, P, N)`` (a
    tree before the pool took its own layout)."""
    return not hasattr(mod, "to_pool")


def call(mod, copies=False):
    """``mod``'s kernel, compiled (the copies-alone body patched in for
    the time it is traced)."""
    def fn(*args):
        with contextlib.ExitStack() as stack:
            if copies:
                stack.enter_context(mock.patch.object(
                    mod, "_step_kernel", _copies_kernel))
            return mod._step_call.__wrapped__(*args, interpret=False)
    return fn


def inputs(seed: int):
    ks = jax.random.split(jax.random.key(seed), 7)
    return (jax.random.normal(ks[0], (SLOTS, HEADS, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (SLOTS, HEADS)) - 2.0),
            -jax.random.uniform(ks[2], (HEADS,), minval=1.0, maxval=16.0),
            jax.random.normal(ks[3], (SLOTS, GROUPS, N)),
            jax.random.normal(ks[4], (SLOTS, GROUPS, N)),
            jax.random.normal(ks[5], (HEADS,)),
            jax.random.normal(ks[6], (SLOTS, HEADS, P, N)))


def active_slots(n: int):
    if not n:
        return jnp.zeros((SLOTS,), bool)
    return jnp.arange(SLOTS) % (SLOTS // n) == 0


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: {dev.platform}", file=sys.stderr)
        return 1
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 1
    mods = {"tree": ssd}
    if len(sys.argv) > 2:
        mods["parent"] = load(sys.argv[2])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out", "ssd.jsonl"), "a")

    def record(**kw):
        line = json.dumps({"device": dev.device_kind, "seed": seed, **kw})
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    from chip_gmm import CALLS, device_seconds

    x, dt, a, b, c, d, s0 = inputs(seed)
    args = (x, dt, a, b, c, d)
    pools = {name: s0 if equation_layout(mod) else ssd.to_pool(s0)
             for name, mod in mods.items()}
    variants = {name: (mod, False) for name, mod in mods.items()}
    variants["copies"] = (ssd, True)
    worst = 0.0
    for n_act in ACTIVE:
        active = active_slots(n_act)
        assert int(active.sum()) == n_act
        with jax.default_matmul_precision("highest"):
            want_y, want_s = jax.jit(ssd.sequential)(
                x[:, None], dt[:, None], a, b[:, None], c[:, None], d, s0,
                active.astype(jnp.int32))
        want_y = np.asarray(jnp.where(active[:, None, None], want_y[:, 0],
                                      0.0))
        want_s = np.asarray(want_s)
        fns, operands = {}, {}
        for i, (name, (mod, copies)) in enumerate(variants.items()):
            # a serial number among the results: two programs that
            # differed by their names alone would share one executable
            def fn(*t, k=call(mod, copies), i=i):
                return k(*t), jnp.int32(i)
            fn.__name__ = name
            fns[name] = jax.jit(fn)
            pool = pools["tree" if copies else name]
            operands[name] = args + (pool, active)
        _secs, red = device_seconds(fns, operands)
        moved = 2.0 * n_act * HEADS * P * N * 4
        for name, (mod, copies) in variants.items():
            secs = sum(t for key, t in red["device_ops"]
                       if key.startswith(f"jit_{name}: ssd_step")) / CALLS
            rec = dict(variant=name, active=n_act, kernel_ms=secs * 1e3,
                       state_gb_s=moved / secs / 1e9 if secs else None,
                       share_of_hbm=100.0 * moved / secs / 1e9 / HBM_GBS
                       if secs else None,
                       pool=list(operands[name][6].shape))
            if not copies:
                (y, s), _i = fns[name](*operands[name])
                s = s if equation_layout(mod) else ssd.from_pool(s, HEADS)
                s = np.asarray(s)
                idle = ~np.asarray(active)
                rec.update(
                    max_err_state=float(np.abs(s - want_s).max()),
                    max_err_y=float(np.abs(np.asarray(y) - want_y).max()),
                    max_abs_state=float(np.abs(want_s).max()),
                    idle_slots_bit_equal=s[idle].tobytes()
                    == np.asarray(s0)[idle].tobytes())
                worst = max(worst, rec["max_err_state"], rec["max_err_y"])
            record(**rec)
    record(check="ssd_step against ssd.sequential", max_err=worst,
           ok=worst < TOLERANCE)
    return 0 if worst < TOLERANCE else 1


# -- the instruction bundles, for a described v5e -----------------------------

def compile_for_v5e(which: str, path: str) -> None:
    """Compile one kernel at the cell's shapes for a described v5e (a
    child of :func:`bundles`, with the listing's flags in its
    environment)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    mod = ssd if which != "parent" else load(path)
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    pool = (SLOTS, HEADS, P, N) if equation_layout(mod) \
        else (SLOTS,) + ssd.pool_shape(HEADS, P, N)
    shapes = [(SLOTS, HEADS, P), (SLOTS, HEADS), (HEADS,), (SLOTS, GROUPS, N),
              (SLOTS, GROUPS, N), (HEADS,), pool]
    args = [jax.ShapeDtypeStruct(t, jnp.float32, sharding=one)
            for t in shapes]
    args.append(jax.ShapeDtypeStruct((SLOTS,), jnp.bool_, sharding=one))
    jax.jit(call(mod, copies=which == "copies"),
            donate_argnums=(6,)).lower(*args).compile()


def count_bundles(dump: str) -> dict:
    """The kernel's final bundles: in all, in the grid's loop (its
    header to its back edge, both branches of a body), and in the
    active slot's branch (from the bundle that branches past
    ``pl.when(i < n)``'s body to the first fall-through after it; none
    in a body without that branch)."""
    paths = [p for p in glob.glob(os.path.join(dump, "*ssd_step*"
                                               "final_bundles.txt"))
             if "schedule-analysis" not in p]
    bundles = []
    for line in open(paths[0]):
        m = re.match(r"\s*(0x[0-9a-f]+|\d+)\s+(LH|LB|LE|PB|PF|CT)?:?\s*>?"
                     r"\s*\{", line)
        if m:
            bundles.append((int(m.group(1), 0), m.group(2), "sbr" in line))
    branches = [at for at, _mark, br in bundles if br]
    fall = [at for at, mark, _br in bundles if mark == "PF"]
    loop = [at for at, mark, _br in bundles if mark == "LB"]
    active = fall[0] - branches[1] if fall and fall[0] > branches[1] \
        else None
    return {"bundles": len(bundles), "loop": branches[-1] - loop[0] + 1,
            "active_branch": active}


def bundles() -> int:
    path = sys.argv[2] if len(sys.argv) > 2 else ""
    for which in ("tree", "copies") + (("parent",) if path else ()):
        with tempfile.TemporaryDirectory(prefix="ssd_bundles_") as dump:
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       TPU_LOG_DIR="disabled",
                       LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} "
                       "--xla_jf_dump_llo_text=true")
            subprocess.run([sys.executable, os.path.abspath(__file__),
                            "_compile", which, path], env=env,
                           stdout=subprocess.DEVNULL,
                           stderr=subprocess.DEVNULL)
            print(json.dumps({"kernel": which, "target": "v5e (described)",
                              **count_bundles(dump)}), flush=True)
    return 0


if __name__ == "__main__":
    if sys.argv[1:2] == ["bundles"]:
        sys.exit(bundles())
    if sys.argv[1:2] == ["_compile"]:
        sys.exit(compile_for_v5e(sys.argv[2], sys.argv[3]))
    sys.exit(main())
