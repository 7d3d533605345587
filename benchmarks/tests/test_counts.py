"""The count functions of ``models/neox.py`` against values worked out
by hand from the two configurations' shapes."""
import os

import pytest

from benchmarks.harness import spec
from benchmarks.harness.peaks import device_peaks, least_seconds

counts = spec.load_module("models", "neox")

CFG = os.path.join(spec.BENCH_DIR, "configs")
V5E = device_peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def c6b9():
    return spec.load_json(os.path.join(CFG, "neox-6b9.json"))


@pytest.fixture(scope="module")
def c1b4():
    return spec.load_json(os.path.join(CFG, "neox-1b4.json"))


def test_params_6b9(c6b9):
    # a layer: 3*4096^2 + 4096^2 + 2*4096*16384 = 12 * 4096^2 = 201,326,592
    assert counts.layer_matmul_params(c6b9) == 201_326_592
    # 8 layers + unembed 4096*50432 = 206,569,472
    assert counts.matmul_params(c6b9) == 8 * 201_326_592 + 206_569_472
    # + embed 206,569,472 + norms 8*2*4096: 2,023,817,216 -> 8.10 GB of float32
    assert counts.total_params(c6b9) == 2_023_817_216
    assert counts.weight_bytes(c6b9) == 2 * 1_817_182_208


def test_params_1b4(c1b4):
    assert counts.layer_matmul_params(c1b4) == 12 * 2048 ** 2 == 50_331_648
    assert counts.matmul_params(c1b4) == 24 * 50_331_648 + 2048 * 50304
    # 1,207,959,552 + 103,022,592 + 103,022,592 + 98,304 -> 5.66 GB of float32
    assert counts.total_params(c1b4) == 1_414_103_040


def test_token_flops(c6b9, c1b4):
    # 2 FLOPs a multiply-add over the 1,817,182,208 matmul parameters, and
    # q.k + p.v over 1,000 live positions: 4 * 8 * 4096 * 1000
    assert counts.token_flops(c6b9, 1000) == 2 * 1_817_182_208 + 131_072_000
    assert counts.token_flops(c6b9, 1000, unembed=False) == \
        2 * 8 * 201_326_592 + 131_072_000
    assert counts.token_flops(c1b4, 0) == 2 * (24 * 50_331_648 + 103_022_592)


def test_span_flops(c1b4):
    # 3 positions from 10: lives 11 + 12 + 13 = 36
    want = 2 * 24 * 50_331_648 * 3 + 4 * 24 * 2048 * 36
    assert counts.span_flops(c1b4, 10, 3) == want
    assert counts.span_flops(c1b4, 10, 0) == 0.0
    # prefill from 0 is the sum of its tokens without their unembeds
    assert counts.span_flops(c1b4, 0, 5) == sum(
        counts.token_flops(c1b4, n, unembed=False) for n in range(1, 6))


def test_kv_bytes(c6b9, c1b4):
    # a position: k and v, 8 layers, 4096 wide, float32 = 262,144 bytes
    assert counts.kv_bytes(c6b9, 1) == 262_144
    assert counts.kv_bytes(c1b4, 1) == 2 * 24 * 2048 * 4 == 393_216
    # the whole pool, 1,025 pages of 16: 4.30 GB and 6.45 GB
    assert counts.kv_bytes(c6b9, 1025 * 16) == 4_299_161_600
    assert counts.kv_bytes(c1b4, 1025 * 16) == 6_448_742_400


def test_step_work(c6b9):
    # 8 slots at 600 live positions: bytes 3,634,364,416 + 262,144 * 4,808
    # = 4,894,752,768 -> 5.976 ms at 819 GB/s; FLOPs 8 * (3,634,364,416 +
    # 78,643,200) = 29.7 GFLOP -> 0.151 ms at 197 TFLOP/s: bytes bind
    flops, nbytes = counts.step_work(c6b9, [600] * 8)
    assert nbytes == 3_634_364_416 + 262_144 * 4808
    assert flops == 8 * (3_634_364_416 + 78_643_200)
    t, bound = least_seconds(flops, nbytes, V5E)
    assert bound == "bytes"
    assert t == pytest.approx(4_894_752_768 / 819e9)
    # the same 8 tokens over two steps: the weights twice
    assert counts.step_work(c6b9, [600] * 8, steps=2) == \
        (flops, nbytes + 3_634_364_416)


def test_fill_work(c1b4):
    # 2,047 positions from 0 on the 1.4B: FLOPs bind
    flops, nbytes = counts.fill_work(c1b4, 0, 2047)
    assert flops == counts.span_flops(c1b4, 0, 2047)
    assert nbytes == 2 * 24 * 50_331_648 + 393_216 * 2047
    t, bound = least_seconds(flops, nbytes, V5E)
    assert bound == "flops" and t == pytest.approx(flops / 197e12)
    # a 20-position catch-up after 1,024 shared ones: the weights bind
    flops, nbytes = counts.fill_work(c1b4, 1024, 20)
    t, bound = least_seconds(flops, nbytes, V5E)
    assert bound == "bytes" and t == pytest.approx(nbytes / 819e9)


def test_unknown_device_raises():
    with pytest.raises(RuntimeError, match="no published peaks"):
        device_peaks("TPU v9")
