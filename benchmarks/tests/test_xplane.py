"""The trace reduction on a small trace recorded on a v5e by
``record_trace.py``: six rounds of (step, step, sleep 10 ms, prefill)."""
import os

import pytest

from benchmarks.harness import xplane

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "toy.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return xplane.reduce_trace(TRACE)


def test_programs(red):
    assert sorted(red["programs"]) == ["jit_prefill", "jit_step"]
    assert len(red["programs"]["jit_step"]) == 12
    assert len(red["programs"]["jit_prefill"]) == 6
    # a 256x1024x1024 float32 matmul + tanh: 5.7 us each on this chip
    for d in red["programs"]["jit_step"]:
        assert 4e-6 < d < 8e-6


def test_busy_and_window(red):
    assert red["devices"] == 1
    # busy is the operations' union: no more than the programs' time
    total = sum(sum(v) for v in red["programs"].values())
    assert 0.9 * total < red["busy_s"] <= total
    assert red["busy_s"] == pytest.approx(127.586e-6, rel=1e-3)
    # six sleeps of 10 ms and more lie inside the window
    assert 0.06 < red["window_s"] < 0.2
    assert red["busy_s"] < 0.01 * red["window_s"]


def test_gaps_are_named_by_their_neighbours(red):
    gaps = dict((k, v) for k, v in red["idle_gaps"])
    # the sleep sits between the second step and the prefill, six times
    assert gaps["jit_step -> jit_prefill x6"] > 0.06
    assert gaps["jit_prefill -> jit_step x5"] < 0.01
    # busy + gaps = window
    assert red["busy_s"] + sum(gaps.values()) == pytest.approx(
        red["window_s"], rel=1e-6)


def test_top_ops(red):
    name, secs = red["device_ops"][0]
    assert name == "jit_step: convolution_tanh_fusion f32[256,1024] x12"
    assert secs == pytest.approx(sum(red["programs"]["jit_step"]), rel=0.02)
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


def test_reduce_plane_by_hand():
    mods = [(0, 100, "jit_a"), (200, 260, "jit_b")]
    ops = [(0, 40, "%x = f32[2]{0} add(...)"), (30, 100, "%y = f32[2]{0} mul(...)"),
           (200, 250, "%fusion.1 = (f32[4]{0}, f32[4]{0}) fusion(...)"),
           (255, 260, "%z = f32[2]{0} add(...)")]
    r = xplane.reduce_plane(mods, ops)
    assert r["busy_s"] == pytest.approx(155e-9)      # 100 + 50 + 5
    assert r["window_s"] == pytest.approx(260e-9)
    assert r["gaps"] == {"jit_a -> jit_b": [pytest.approx(100e-9), 1],
                         "jit_b -> jit_b": [pytest.approx(5e-9), 1]}
    assert r["programs"] == {"jit_a": [pytest.approx(100e-9)],
                             "jit_b": [pytest.approx(60e-9)]}
    assert r["by_op"]["jit_b: fusion f32[4]"] == [pytest.approx(50e-9), 1]
    assert r["by_op"]["jit_a: x f32[2]"] == [pytest.approx(40e-9), 1]


def test_empty_trace_raises(tmp_path):
    with pytest.raises(RuntimeError, match="left no .xplane.pb"):
        xplane.find_xplane(str(tmp_path))
