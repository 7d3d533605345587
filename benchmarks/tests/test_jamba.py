"""``models/jamba.py``: its counts against hand arithmetic, a toy
configuration of the same structure rehearsed through the runner on the
CPU, and its plain reference against the program at toy size.
"""
import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import compare, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "jamba2-3b.chat"


def load(name):
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, name))
    return cfg, spec.load_module("models", cfg["model"])


def test_counts_against_hand_arithmetic():
    cfg, m = load("configs/jamba2-3b.json")
    assert m.n_layers(cfg) == (2, 26)
    assert [i for i in range(28) if m.is_attention(cfg, i)] == [7, 21]
    # Mamba mixer: W_in 2560 x 10240, W_x 5120 x 192, W_dt 160 x 5120 and
    # b_dt, W_out 5120 x 2560, conv 4 x 5120 and its bias, A_log 16 x
    # 5120, D, the three inner norms (160 + 16 + 16)
    mixer = (26_214_400 + 983_040 + 819_200 + 5_120 + 13_107_200
             + 20_480 + 5_120 + 81_920 + 5_120 + 192)
    assert m.mamba_mixer_params(cfg) == mixer == 41_241_792
    # attention mixer: W_q 2560 x 2560, W_k and W_v 2560 x 128, W_o
    assert m.attention_mixer_params(cfg) == 6_553_600 + 2 * 327_680 \
        + 6_553_600
    assert m.mlp_params(cfg) == 3 * 2560 * 8192 == 62_914_560
    layer_norms = 2 * 2560
    total = (26 * (mixer + 62_914_560 + layer_norms)
             + 2 * (13_762_560 + 62_914_560 + layer_norms)
             + 65536 * 2560 + 2560)
    assert m.total_params(cfg) == total == 3_029_337_472
    # 12.12 GB at the 4 bytes a parameter the program stores
    assert round(4 * total / 1e9, 2) == 12.12
    # what a sequence carries: h (5120 x 16) and 3 inputs of the conv
    assert m.state_bytes(cfg) == 389_120

    # one step at 8 live slots of 300 positions: weights once at 2
    # bytes, 8 x 26 states read and written, 8 x 301 positions of 2
    # layers x 1 head x 128 x (k, v) x 4 bytes
    flops, nbytes = m.step_work(cfg, [300] * 8, 1)
    weights = 2 * (total - 2560 - 28 * layer_norms
                   - 26 * (5_120 + 20_480 + 5_120 + 81_920 + 5_120 + 192))
    assert m.weight_bytes(cfg) == weights == 2 * m.matmul_params(cfg)
    assert nbytes == weights + 2 * 26 * 389_120 * 8 + 8 * 301 * 2048
    per_token = (2 * (m.matmul_params(cfg))          # every matrix
                 + 26 * (9 * 5120 * 16 + 2 * 4 * 5120)   # scan, conv
                 + 4 * 2 * 2560 * 300)               # q.k and p.v
    assert flops == 8 * per_token
    # filling 512 positions: no unembedding, the states written once
    f_flops, f_bytes = m.fill_work(cfg, 0, 512)
    assert f_bytes == weights - 2 * 2560 * 65536 + 512 * 2048 \
        + 26 * 389_120
    assert f_flops == 512 * (per_token - 2 * 2560 * 65536
                             - 4 * 2 * 2560 * 300) \
        + 4 * 2 * 2560 * (512 * 513 / 2)
    # the sequence scan's own: 26 calls an execution
    assert m.kernel_calls(cfg, "ssm_scan") == 26
    c_flops, c_bytes = m.ssm_scan_work(cfg, [100, 400])
    assert c_flops == 26 * 9 * 5120 * 16 * 500
    assert c_bytes == 26 * (500 * 4 * (3 * 5120 + 32) + 2 * 2 * 327_680)


@pytest.fixture()
def toy_jamba_root(tmp_path):
    """A checkout in little: the real ``BENCHMARK.json`` cut to the
    cell ``toy.chat`` on the toy configuration of this directory, with
    every metric that the real cell reports; the real metric files and
    readers."""
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    base = tmp_path / bench["paths"][0]
    os.makedirs(base / "traffic")
    shutil.copy(os.path.join(HERE, "toy", "traffic", "chat.json"),
                base / "traffic" / "chat.json")
    shutil.copy(os.path.join(HERE, "toy_jamba", "config.json"),
                base / "toy.json")
    bench["configs"] = [{"name": "toy",
                         "file": f"{bench['paths'][0]}/toy.json"}]
    bench["workloads"] = [{"name": "toy.chat", "config": "toy",
                           "traffic": "chat", "chips": 1}]
    for key in ("end_to_end", "per_layer"):
        bench[key] = [{**m, "workloads": ["toy.chat"]} for m in bench[key]
                      if CELL in m.get("workloads", [CELL])]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(toy_jamba_root, trace):
    from brpc_tpu import native
    if native.load() is None:
        pytest.skip("the native engine does not build here")
    cell = spec.Cell("toy.chat", root=toy_jamba_root)
    res = bench_run.run_cell(cell, seed=2 ** 31 + 27, seconds=2.0,
                             trace=bool(trace), require_tpu=False)
    line = json.loads(json.dumps(res))
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    declared = {m["name"]: m for m in
                (cell.per_layer if trace else cell.end_to_end)}
    assert set(line["metrics"]) <= set(declared)
    if not trace:
        assert set(line["metrics"]) == {"itl_p50_ms", "setup_s"}
        return
    # no trace on the CPU: the device metrics' readers find nothing
    # to read and return nothing; the state pool's counter is read
    for n, m in declared.items():
        if m["source"] == "device_trace" or n.endswith("hbm_peak_gb"):
            assert n not in line["metrics"], n
        else:
            assert n in line["metrics"], n
    assert "kv.chat_state_slots_share" in declared
    share = line["metrics"]["kv.chat_state_slots_share"]["value"]
    occupancy = line["metrics"]["batcher.chat_occupancy"]["value"]
    assert 0 < share <= 100 and abs(share - occupancy) < 25


def test_kernel_reader_reads_a_reduced_trace():
    """``readers/kernel_work.py`` on a made-up reduction: the kernel's
    time inside the named program only; nothing where its calls do not
    add up, the model module has no such count, or there is no trace."""
    cfg, m = load("configs/jamba2-3b.json")
    reader = spec.load_module("readers", "kernel_work")
    scan = spec.load_json(os.path.join(
        spec.BENCH_DIR, "metrics", "kernel.chat_ssm_scan_roofline.json"))

    class Req:
        def __init__(self, n):
            self.prompt = np.zeros((n,), np.int32)

    class Run:
        model, peaks = m, {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
        trace = {"ta": 0.0, "tb": 4.0, "reduced": {"device_ops": [
            ["jit_step: ssm_scan f32[1,16,40,128] x26", 9.0],
            ["jit_prefill: ssm_scan f32[1,16,40,128] x52", 0.0104],
            ["jit_prefill: fusion f32[1,512,10240] x52", 0.3]]}}

        def program_durations(self, names):
            return [0.03] * 2

        def admitted_between(self, a, b):
            return [Req(101), Req(401)]

    run = Run()
    run.cfg = cfg
    _flops, nbytes = m.ssm_scan_work(cfg, [100, 400])
    want = 100.0 * (nbytes / 819e9) / 0.0104
    assert reader.read(run, scan) == pytest.approx(want) and 0 < want < 100
    run.trace["reduced"]["device_ops"][1][0] = \
        "jit_prefill: ssm_scan f32[1,16,40,128] x20"
    assert reader.read(run, scan) is None           # calls fell off the list
    assert reader.read(run, {**scan, "work": "no_such_count"}) is None
    run.trace = None
    assert reader.read(run, scan) is None


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_reference_against_the_program_and_the_control(seed):
    """Logits, not tokens: the program's prefill and paged steps
    through its slot state against the plain reference's full forward,
    at toy size on seeded weights; then the int8 control through the
    run's own comparison, which has to come out not correct."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models import transformer_lm as T

    cfg, m = load("tests/toy_jamba/config.json")
    params = m.make_params(cfg, seed)
    lm = T.LMConfig(remat=False, **m.lm_kwargs(cfg))
    page, slots = cfg["service"]["page"], 2
    prefill, step = T.make_paged_batch_decode(lm, page)
    insert = T.make_paged_io(lm, page)[2]
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, 256, (21,), dtype=np.int32)
    served = rng.integers(0, 256, (12,), dtype=np.int32)
    ctx = prompt[:-1]
    ids = np.zeros((32,), np.int32)
    ids[:len(ctx)] = ctx
    cache1, _ = jax.jit(prefill)(params, ids[None], jnp.int32(len(ctx)))
    cache = T.empty_paged_cache(lm, 33, slots, page)
    bt = np.zeros((slots, lm.max_seq // page), np.int32)
    bt[1] = 1 + np.arange(bt.shape[1])
    cache = jax.jit(insert)(cache, jnp.asarray(bt[1]), cache1, jnp.int32(1))
    cache["len"] = cache["len"].at[1].set(len(ctx))
    fed = np.concatenate([prompt[-1:], served[:-1]])
    got = []
    stepj = jax.jit(step)
    for t in fed:
        cache, logits = stepj(params, cache, jnp.asarray(bt),
                              jnp.asarray([0, t], jnp.int32),
                              jnp.asarray([False, True]))
        got.append(np.asarray(logits[1]))
    got = np.stack(got)
    ref = m.Reference(cfg, params)
    want = ref.served_logits(prompt, served)
    # the program multiplies bf16 operands into a bf16 result and the
    # reference float32: readings at this size 0.03-0.08 of a
    # position's logit standard deviation; a wrong formula (the
    # convolution's taps reversed, the state at the bucket's end) reads
    # 0.5 and more
    gap = np.abs(got - want).max(axis=-1) / want.std(axis=-1)
    assert gap.max() < 0.25, gap

    class Served:
        turn = 0

    sample = []
    for _ in range(4):
        r = Served()
        r.prompt = rng.integers(0, 256, (8,), dtype=np.int32)
        r.tokens = list(rng.integers(0, 256, (112,)))
        sample.append(r)
    own = compare.compare(ref, sample, tokens_of=ref)
    assert compare.judge(own, cfg["correct"], 0, 0)[0] is True
    ctl = compare.compare(ref, sample,
                          tokens_of=m.Reference(cfg, params, int8=True))
    ok, compared = compare.judge(ctl, cfg["correct"], 0, 0)
    assert ok is False, compared
