"""``models/kimi_linear.py``: a toy configuration of the same structure
rehearsed through the runner on the CPU both ways (as served, and with
the int8 control in the program's place), the new count functions
against hand arithmetic at the toy's shapes, and the two metric files
that wait for room in ``per_layer`` on a made-up reduction.  (The
cell's own counts against hand arithmetic, the reference against the
program's logits, the shares that add up: tier-1,
``tests/test_linear_experts.py``.)
"""
import json
import os
import shutil

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import compare, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "kimi-linear-48b.codegen"


@pytest.fixture()
def toy_root(tmp_path):
    """A checkout in little: the real ``BENCHMARK.json`` cut to the
    cell ``toy.codegen`` on the toy configuration of this directory,
    with every metric that the real cell reports; the real metric files
    and readers."""
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    base = tmp_path / bench["paths"][0]
    os.makedirs(base / "traffic")
    shutil.copy(os.path.join(HERE, "toy", "traffic", "codegen.json"),
                base / "traffic" / "codegen.json")
    shutil.copy(os.path.join(HERE, "toy_kimi_linear", "config.json"),
                base / "toy.json")
    bench["configs"] = [{"name": "toy",
                         "file": f"{bench['paths'][0]}/toy.json"}]
    bench["workloads"] = [{"name": "toy.codegen", "config": "toy",
                           "traffic": "codegen", "chips": 1}]
    for key in ("end_to_end", "per_layer"):
        bench[key] = [{**m, "workloads": ["toy.codegen"]} for m in bench[key]
                      if CELL in m.get("workloads", [CELL])]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_the_cell_takes_the_codegen_metrics():
    """The cell's file overrides the mix's ``clients`` alone; it
    reports ``itl_p50_ms``, ``setup_s`` and the 23 ``*.codegen_*``
    per-layer metrics; its configuration holds every key of the
    source's but the three it cuts."""
    cell = spec.Cell(CELL)
    assert cell.traffic["clients"] == 128 == cell.config["service"][
        "decode_slots"]
    assert cell.traffic["session"]["output_len"]["values"][0] == 1024
    assert [m["name"] for m in cell.end_to_end] == ["itl_p50_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert len(names) == 23 and all(".codegen_" in n for n in names)
    assert all("reader" in m["spec"] for m in cell.per_layer)
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in bench["configs"]}["kimi-linear-48b"]
    assert sorted(entry["reduced"]) == sorted(cell.config["reduced"]) \
        == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert len(bench["per_layer"]) == 128


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(toy_root, trace, monkeypatch):
    from brpc_tpu import native
    from brpc_tpu.ops import quant
    if native.load() is None:
        pytest.skip("the native engine does not build here")
    # the program's matmuls in float32, as tests/test_kimi.py has them
    # and for its reason: at widths this small a bf16 rounding flips a
    # router's choice in one request of a few, which reads like the
    # control; what is rehearsed is the runner and the comparison
    monkeypatch.setattr(quant, "qmatmul", lambda x, w: x @ w)
    monkeypatch.setattr(quant, "mxu_operand", lambda x: x)
    cell = spec.Cell("toy.codegen", root=toy_root)
    win = bench_run.run_window(cell, seed=6, seconds=2.0,
                               trace=bool(trace), require_tpu=False)
    ref = win.reference()
    line = json.loads(json.dumps(win.judged(compare.compare(ref,
                                                            win.sample))))
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    declared = {m["name"]: m for m in
                (cell.per_layer if trace else cell.end_to_end)}
    assert set(line["metrics"]) <= set(declared)
    kda = win.run.c1["kv"]["kda"]
    assert kda["layers"] == 4 and kda["fills"] > 0
    assert 0 < kda["slot_steps"] <= 4 * kda["steps"]
    assert set(win.run.c1["kv"]["state"]["kinds"]) == {"kda"}
    if not trace:
        assert set(line["metrics"]) == {"itl_p50_ms", "setup_s"}
        # the int8 control in the program's place, same sample
        ctl = win.judged(compare.compare(
            ref, win.sample, tokens_of=win.reference(int8=True)))
        assert ctl["correct"] is False, ctl["compared"]
        return
    for n, m in declared.items():
        if m["source"] == "device_trace" or n.endswith("hbm_peak_gb"):
            assert n not in line["metrics"], n
        else:
            assert n in line["metrics"], n
    # the metric file that waits for an entry reads the state pool
    share = spec.load_module("readers", "counter_ratio_if_present").read(
        win.run, spec.load_json(os.path.join(
            spec.BENCH_DIR, "metrics", "kv.codegen_state_slots_share.json")))
    assert 0 < share <= 100


def test_counts_at_the_toys_shapes():
    """The count functions on shapes small enough to add by hand: 5
    layers (KDA at 1, 2, 3, 5; latent at 4), 4 heads of 16, hidden 64,
    4 of 64 experts held."""
    cfg = spec.load_json(os.path.join(HERE, "toy_kimi_linear",
                                      "config.json"))
    m = spec.load_module("models", cfg["model"])
    assert m.mixers(cfg) == ("kda", "kda", "kda", "mla", "kda")
    kda = 4 * 64 * 64 + 2 * (64 * 16 + 16 * 64) + 64 * 4 + 4 * 3 * 64 \
        + 4 + 64 + 64 + 16
    assert m.kda_params(cfg) == kda == 21_652
    mla = 64 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 64 * 64
    assert m.mla_params(cfg) == mla == 16_896
    assert m.kda_state_values(cfg) == 4 * 16 * (16 + 9) == 1_600
    # one session's state in the four KDA layers, float32
    assert m.kda_state_bytes(cfg, 1) == 4 * 1_600 * 4
    update = 7 * 4 * 16 * 16 + 2 * 4 * 3 * 64
    assert m.kda_update_flops(cfg) == update == 8_704
    lives = [10, 20, 30]
    assert m.kda_step_work(cfg, lives, 2) == (3 * 4 * update,
                                              2 * 3 * 4 * 1_600 * 4)
    assert m.mla_decode_work(cfg, lives, 2) == (
        2 * 4 * (40 + 32) * 60, 40 * 4 * 60)
    expert = 3 * 64 * 32
    every_step = 4 * kda + mla + 3 * 64 * 160 \
        + 4 * (64 * 64 + expert) + 64 * 256
    assert m.dense_step_params(cfg) == every_step
    counted = {"experts_touched": 7, "local_pairs": 9}
    flops, nbytes = m.step_work(cfg, lives, 2, counted)
    assert nbytes == 2 * (2 * every_step + 7 * expert) + 40 * 4 * 63 \
        + 2 * 3 * 4 * 1_600 * 4
    per_row = 2 * (every_step - 32 * 4 * 32) + 2 * 4 * 32 * 32 + 4 * update
    assert flops == 3 * per_row + 2 * 4 * 72 * 60 + 2 * expert * 9
    assert m.kernel_calls(cfg, "kda_step") == 4
    assert m.kernel_calls(cfg, "mla_decode_attention") == 1
    assert m.kernel_calls(cfg, "expert_gmm") == 8
    # a fill writes the state once, and reads it where it continues
    _f0, b0 = m.fill_work(cfg, 0, 8)
    _f1, b1 = m.fill_work(cfg, 8, 8)
    assert b1 - b0 == 4 * 1_600 * 4 + 40 * 4 * 8


def test_the_kda_step_metric_reads_a_reduced_trace():
    """``metrics/kernel.codegen_kda_step_roofline.json`` through
    ``readers/step_kernel_work.py`` on a made-up reduction at the
    cell's shapes: ten calls a step, the state once in and once out."""
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      "kimi-linear-48b.json"))
    m = spec.load_module("models", cfg["model"])
    reader = spec.load_module("readers", "step_kernel_work")
    metric = spec.load_json(os.path.join(
        spec.BENCH_DIR, "metrics", "kernel.codegen_kda_step_roofline.json"))

    class Run:
        model, peaks = m, {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
        c0 = c1 = {"kv": {}}
        trace = {"ta": 0.0, "tb": 4.0, "steps_a": 100, "steps_b": 150,
                 "reduced": {"device_ops": [
                     ["jit_step: kda_step f32[128,32,128,128] x500", 0.400],
                     ["jit_prefill: kda_scan f32[1,32,128,128] x80", 0.2]]}}

        def program_durations(self, names):
            return [0.020] * 50

        def decoded_between(self, a, b):
            return [1000] * 6400                   # 50 steps of 128 rows

    run = Run()
    run.cfg = cfg
    _flops, nbytes = m.kda_step_work(cfg, [1000] * 6400, 50)
    assert nbytes == 2 * 6400 * 10 * 2_244_608
    want = 100.0 * (nbytes / 50 / 819e9) / (0.400 / 50)
    assert reader.read(run, metric) == pytest.approx(want) and 80 < want < 100
    run.trace["reduced"]["device_ops"][0][0] = \
        "jit_step: kda_step f32[128,32,128,128] x400"
    assert reader.read(run, metric) is None        # calls fell off the list
