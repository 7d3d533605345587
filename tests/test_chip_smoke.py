"""chip_smoke.py on the CPU: what can be pinned without a chip.

- the script refuses to run without an accelerator (non-zero exit, the
  platform named, no result line);
- its serving and kernel stages pass at toy widths when called
  directly (the same code the chip runs at full width);
- no serving program's lowered module holds the weights (they are jit
  ARGUMENTS — a regression to closure constants would embed a copy per
  program);
- the compile cache lands where ``JAX_COMPILATION_CACHE_DIR`` says, else
  in the one fixed in-checkout directory;
- ``bench.py``'s parent never initialises a JAX backend, and a device
  section without a chip makes its exit code non-zero.
"""

import ast
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import require_native

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TOY_LM = dict(vocab=256, dim=64, heads=4, depth=2, max_seq=128, mlp_mult=4)
# 7 streams over 4 slots; 5 re-sends 2 (full prefix hit), 6 re-sends 3
# (partial hit -> chunk-slice catch-up); request 4 fills the top bucket
TOY_REQUESTS = ((6, 8, None), (12, 5, None), (33, 9, None), (41, 6, None),
                (70, 7, None), (33, 6, 2), (41, 4, 3))


def _run(args, env_extra=None, cwd=ROOT, timeout=300):
    env = dict(os.environ)
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_smoke_refuses_cpu():
    r = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "platform=cpu" in r.stdout
    assert '"ok"' not in r.stdout


def test_smoke_stages_at_toy_width():
    require_native()
    import jax.numpy as jnp

    import chip_smoke as cs
    from brpc_tpu.utils.compile_cache import CompileMeter

    cs.stage_serving(TOY_LM, TOY_REQUESTS, (12, 8), slots=4, page=16,
                     meter=CompileMeter(), expect_flash=False,
                     timeout_s=240)
    cs.stage_kernels(
        flash_fwd=[((1, 64, 2, 16), jnp.float32),
                   ((1, 96, 2, 16), jnp.bfloat16)],
        flash_bwd_shape=(1, 64, 2, 16), train_kw=TOY_LM, train_batch=2,
        train_accum=2, train_seq=32, checksum_bytes=1 << 12)


def test_smoke_stage_failure_raises():
    """A stage that finds a wrong answer raises (non-zero exit), it
    does not print a line and carry on."""
    import chip_smoke as cs
    from brpc_tpu.models.transformer_lm import LMConfig, init_params
    import jax

    cfg = LMConfig(remat=False, **TOY_LM)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.arange(8, dtype=np.int32)
    wrong = np.zeros((4,), np.int32)      # not what the model emits
    with pytest.raises(RuntimeError, match="trails the reference argmax"):
        cs.check_against_reference(cfg, params, [(prompt, wrong)])


def test_serving_programs_do_not_embed_weights():
    """Batcher step, chunk slice, paged prefill, scan generator: each
    lowers to a module far smaller than the weights it runs."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models.lm_service import ContinuousBatcher
    from brpc_tpu.models.transformer_lm import (LMConfig, init_params,
                                                make_scan_generator)
    from brpc_tpu.ops.quant import quantized_nbytes

    # wide enough that an embedded copy (2 hex chars per byte) dwarfs
    # the program text
    cfg = LMConfig(vocab=512, dim=256, heads=4, depth=2, max_seq=64,
                   remat=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    nbytes = quantized_nbytes(params)
    b = ContinuousBatcher(cfg, params, slots=2, page=16)
    b._ensure_engine()

    def size(prog, *args):
        return len(prog.func.lower(*prog.args, *args).as_text())

    bt_row = jnp.zeros((cfg.max_seq // 16,), jnp.int32)
    i32 = jnp.int32
    sizes = {
        "step": size(b._step, b._cache, b._bt, b._tokens, b._active),
        "chunk": size(b._chunk_j, b._cache, bt_row, i32(0), i32(0), i32(1),
                      jnp.zeros((b._chunk_w,), jnp.int32)),
        "prefill": size(b._prefill, jnp.zeros((1, 16), jnp.int32), i32(16)),
    }
    gen = make_scan_generator(cfg, params)
    sizes["scan_generator"] = len(gen.program.lower(
        params, jnp.zeros((1, 8), jnp.int32), 4, False, jnp.float32(0.0),
        jax.random.PRNGKey(0)).as_text())
    for name, n in sizes.items():
        assert n < nbytes / 4, (name, n, nbytes)


_CACHE_PROBE = """
import sys
sys.path.insert(0, {root!r})
from brpc_tpu.utils.compile_cache import enable_compile_cache
import jax
got = enable_compile_cache()
assert got == jax.config.jax_compilation_cache_dir
print(got)
"""


def test_compile_cache_follows_env(tmp_path):
    from brpc_tpu.utils.compile_cache import DEFAULT_CACHE_DIR

    def checkout_listing():
        return (sorted(os.listdir(ROOT)),
                os.path.isdir(DEFAULT_CACHE_DIR)
                and sorted(os.listdir(DEFAULT_CACHE_DIR)))

    before = checkout_listing()
    want = str(tmp_path / "cache")
    r = _run(["-c", _CACHE_PROBE.format(root=ROOT)],
             {"JAX_COMPILATION_CACHE_DIR": want}, cwd=str(tmp_path))
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == want
    assert checkout_listing() == before     # nothing made in the checkout


def test_compile_cache_default_is_one_fixed_path(tmp_path):
    from brpc_tpu.utils.compile_cache import DEFAULT_CACHE_DIR

    assert DEFAULT_CACHE_DIR == os.path.join(ROOT, ".jax_compile_cache")
    env = {"JAX_COMPILATION_CACHE_DIR": ""}
    outs = [_run(["-c", _CACHE_PROBE.format(root=ROOT)], env, cwd=cwd)
            for cwd in (ROOT, str(tmp_path))]
    for r in outs:
        assert r.returncode == 0, r.stderr
        assert r.stdout.strip() == DEFAULT_CACHE_DIR
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()


_BENCH_PROBE = """
import json, sys
sys.path.insert(0, {root!r})
import bench

def host_section(extra):
    # what host sections do: start a native server, make real calls
    srv = bench._start_server(native=True)
    try:
        from brpc_tpu.client import Channel
        ch = Channel()
        ch.init(str(srv.listen_endpoint))
        assert ch.call("Bench.Echo", b"x") == b"ok"
    finally:
        srv.stop()
    extra["host_ran"] = 1

def touches_jax(extra):
    import jax
    jax.devices()

bench.SECTIONS = {sections}
if __name__ == "__main__":
    rc = bench.main()
    print("RC", rc, bench._parent_touched_jax())
"""


def _bench_probe(sections: str):
    r = _run(["-c", _BENCH_PROBE.format(root=ROOT, sections=sections)],
             {"JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    lines = r.stdout.strip().splitlines()
    return json.loads(lines[-2]), lines[-1].split()


def test_bench_parent_never_initialises_jax():
    """A device section runs in a child (and, chip-less, fails the run
    by name); host sections run in the parent, which ends with no JAX
    backend."""
    require_native()
    out, (_tag, rc, touched) = _bench_probe(
        '(("mfu", bench.bench_device_mfu, True), '
        '("host", host_section, False))')
    assert out["extra"]["host_ran"] == 1
    assert out["device"]["platform"] == "cpu"
    assert out["failed"] == ["mfu_error"]
    assert "no accelerator" in out["extra"]["mfu_error"]
    assert rc == "1" and touched == "False"


def test_bench_parent_touching_jax_is_an_error():
    out, (_tag, rc, touched) = _bench_probe(
        '(("host", touches_jax, False),)')
    assert out["failed"] == ["parent_error"]
    assert rc == "1" and touched == "True"


def test_bench_device_sections_all_run_in_children():
    """Exactly the bench functions that import JAX or build a model
    are marked holds_device; nothing main() runs in the parent does."""
    import bench

    tree = ast.parse(open(os.path.join(ROOT, "bench.py")).read())
    device_fns = set()
    for fn in tree.body:
        if not (isinstance(fn, ast.FunctionDef)
                and fn.name.startswith("bench_")):
            continue
        for node in ast.walk(fn):
            mods = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                mods = [node.module or ""]
            if any(m == "jax" or m.startswith(("jax.", "brpc_tpu.models",
                                               "brpc_tpu.kv",
                                               "brpc_tpu.ops",
                                               "brpc_tpu.parallel"))
                   for m in mods):
                device_fns.add(fn.name)
    assert device_fns == {fn.__name__ for _name, fn, holds_device
                          in bench.SECTIONS if holds_device}


def test_peaks_table_rejects_unknown_device():
    import bench

    assert bench.device_peaks("TPU v5 lite") == {"bf16_tflops": 197.0,
                                                 "hbm_gbs": 819.0}
    with pytest.raises(RuntimeError, match="no published peaks"):
        bench.device_peaks("TPU v9 imaginary")
