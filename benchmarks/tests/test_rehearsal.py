"""The runner's own functions, end to end on the CPU with the toy
configuration: server up, traffic sent, counts read, the result's line
well-formed, device metrics absent.  Later PRs run this to check a new
mix's or metric's files before spending chip time: the toy root takes
every metric of ``BENCHMARK.json`` and every toy mix under
``tests/toy/traffic/``.

Also here: the comparison that decides ``correct`` is shown to fail,
once with the timed path broken underneath (a token altered where it is
produced), once for the control (the reference in int8, put in the
program's place and judged by the run's own ``compare`` and ``judge``).
"""
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import compare, spec

TOY_CELLS = ["toy.batch", "toy.docqa", "toy.chat"]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


def native_or_skip():
    from brpc_tpu import native
    if native.load() is None:
        pytest.skip("the native engine does not build here")


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", TOY_CELLS)
def test_rehearsal(toy_root, name, trace):
    native_or_skip()
    cell = spec.Cell(name, root=toy_root)
    res = bench_run.run_cell(cell, seed=2 ** 31 + 17, seconds=2.0,
                             trace=bool(trace), require_tpu=False)
    line = json.loads(json.dumps(res))              # it is one JSON object
    assert list(line)[:5] == KEYS[:5] and list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["memory_peak_bytes"] is None
    assert "busy_s" not in line["device"] and "breakdown" not in line
    want = cell.per_layer if trace else cell.end_to_end
    declared = {m["name"]: m for m in want}
    assert set(line["metrics"]) <= set(declared)
    for n, m in line["metrics"].items():
        assert m["unit"] == declared[n]["unit"]
        assert isinstance(m["value"], float)
    # no CPU number under a device metric's name
    for n, m in declared.items():
        if m["source"] == "device_trace" or n.endswith("hbm_peak_gb"):
            assert n not in line["metrics"]
        else:
            assert n in line["metrics"], n
    if not trace:
        assert "setup_s" in line["metrics"]
    for n, c in line["compared"].items():
        assert "value" in c


def test_altered_token_is_not_correct(toy_root, monkeypatch):
    """The timed path broken underneath: every 5th token that the
    batcher's step produces is altered before it is streamed."""
    native_or_skip()
    import jax.numpy as jnp

    real = jnp.argmax
    calls = {"n": 0}

    def off_by_one(x, axis=None, **kw):
        out = real(x, axis=axis, **kw)
        calls["n"] += 1
        if axis == -1 and x.ndim == 2 and calls["n"] % 5 == 0:
            out = (out + 1) % x.shape[-1]
        return out

    monkeypatch.setattr(jnp, "argmax", off_by_one)
    cell = spec.Cell("toy.batch", root=toy_root)
    res = bench_run.run_cell(cell, seed=5, seconds=1.5, trace=False,
                             require_tpu=False)
    assert res["correct"] is False
    c = res["compared"]["logit_gap_std"]
    assert c["value"] > 10 * c["limit"]


def test_short_stream_is_not_correct(toy_root, monkeypatch):
    """An answer that never comes in full: the service closes every
    stream one token early."""
    native_or_skip()
    from brpc_tpu.models import lm_service

    real_join = lm_service.ContinuousBatcher.join

    def join(self, stream, prompt, max_new, **kw):
        return real_join(self, stream, prompt, max(1, max_new - 1), **kw)

    monkeypatch.setattr(lm_service.ContinuousBatcher, "join", join)
    cell = spec.Cell("toy.batch", root=toy_root)
    res = bench_run.run_cell(cell, seed=6, seconds=1.0, trace=False,
                             require_tpu=False)
    assert res["correct"] is False and res["failed"] > 0
    assert res["compared"]["streams_unfinished"]["value"] > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_not_correct(seed):
    """The control at a size a test can hold, through the comparison a
    run makes.  It does not decode: at each of 448 positions of seeded
    prompts and tokens the int8 reference puts some token first; those
    tokens, compared and judged as served tokens are, come out NOT
    correct, while the float32 reference's own first tokens come out
    correct with every gap 0."""
    cfg = spec.load_json(spec.BENCH_DIR + "/tests/toy/config.json")
    model = spec.load_module("models", cfg["model"])
    params = model.make_params(cfg, seed)
    ref = model.Reference(cfg, params)
    ctl = model.Reference(cfg, params, int8=True)
    rng = np.random.default_rng(seed)

    class Served:
        turn = 0

    sample = []
    for _ in range(4):
        r = Served()
        r.prompt = rng.integers(0, cfg["vocab_size"], (8,), dtype=np.int32)
        r.tokens = list(rng.integers(0, cfg["vocab_size"], (112,)))
        sample.append(r)
    own = compare.compare(ref, sample, tokens_of=ref)
    ok, compared = compare.judge(own, cfg["correct"], 0, 0)
    assert ok is True and own["tokens_compared"] == 448
    assert compared["logit_gap_std"]["value"] == 0
    got = compare.compare(ref, sample, tokens_of=ctl)
    ok, compared = compare.judge(got, cfg["correct"], 0, 0)
    assert ok is False
    c = compared["logit_gap_std"]
    assert c["value"] > c["limit"] and got["mean_gap_std"] > 0


def test_no_tpu_no_result():
    """The measuring path raises without a TPU and prints no result."""
    r = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "neox-6b9.batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "needs 1 TPU chip" in r.stderr
    assert '"correct"' not in r.stdout


def test_runner_names_no_cell():
    """No branch on a cell's, a configuration's or a mix's name."""
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    names = {w["name"] for w in bench["workloads"]} \
        | {w["traffic"] for w in bench["workloads"]} \
        | {c["name"] for c in bench["configs"]}
    code = [os.path.join(spec.BENCH_DIR, "run.py")] \
        + glob.glob(os.path.join(spec.BENCH_DIR, "harness", "*.py")) \
        + glob.glob(os.path.join(spec.BENCH_DIR, "readers", "*.py")) \
        + glob.glob(os.path.join(spec.BENCH_DIR, "models", "*.py"))
    for path in code:
        src = open(path).read()
        for n in names:
            assert f'"{n}"' not in src and f"'{n}'" not in src, (path, n)
