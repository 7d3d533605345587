"""``models/granite.py``: a toy configuration of the same structure
rehearsed through the runner on the CPU both ways (as served, and with
the int8 control in the program's place), the count functions against
hand arithmetic at the toy's shapes, and the metric file that waits for
room in ``per_layer`` on a made-up reduction.  (The cell's own counts
against hand arithmetic, the reference against the program's logits,
the shares that add up: tier-1, ``tests/test_granite_hybrid.py``.)
"""
import json
import os
import shutil

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import compare, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "granite-4.0-h-small.codegen"


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
    shutil.copy(os.path.join(HERE, "toy_granite", "config.json"),
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
    """The cell runs codegen's mix as it stands (64 callers on the 64
    slots); it reports ``itl_p50_ms``, ``setup_s`` and the 22
    ``*.codegen_*`` per-layer metrics a block without latent attention
    can; its configuration holds every key of the source's but the
    three it cuts."""
    cell = spec.Cell(CELL)
    assert cell.traffic["clients"] == 64 == cell.config["service"][
        "decode_slots"]
    assert [m["name"] for m in cell.end_to_end] == ["itl_p50_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert len(names) == 22 and all(".codegen_" in n for n in names)
    assert "kernel.codegen_mla_decode_roofline" not in names
    assert all("reader" in m["spec"] for m in cell.per_layer)
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in bench["configs"]}["granite-4.0-h-small"]
    assert sorted(entry["reduced"]) == sorted(cell.config["reduced"]) \
        == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert len(bench["per_layer"]) == 128
    # the layer types are the source's whole list; the first ten are read
    assert len(cell.config["layer_types"]) == 40


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
    mamba2 = win.run.c1["kv"]["mamba2"]
    assert mamba2["layers"] == 4 and mamba2["fills"] > 0
    assert mamba2["chunks"] >= mamba2["fills"]
    assert 0 < mamba2["slot_steps"] <= 4 * mamba2["steps"]
    assert set(win.run.c1["kv"]["state"]["kinds"]) == {"mamba2"}
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


def test_counts_at_the_toys_shapes():
    """The count functions on shapes small enough to add by hand: 5
    layers (Mamba-2 at 0, 1, 3, 4; attention at 2), 8 heads of 16 over a
    state of 16 in 2 groups, hidden 64, 8 of 16 experts held."""
    cfg = spec.load_json(os.path.join(HERE, "toy_granite", "config.json"))
    m = spec.load_module("models", cfg["model"])
    assert m.mixers(cfg) == ("mamba2", "mamba2", "attn", "mamba2", "mamba2")
    conv = 128 + 2 * 2 * 16
    mixer = 64 * (128 + conv + 8) + 5 * conv + 3 * 8 + 128 + 128 * 64
    assert m.mamba2_params(cfg) == mixer == 30_296
    assert m.attn_params(cfg) == 64 * 8 * 16 + 64 * 64 == 12_288
    assert m.state_values(cfg) == 8 * 16 * 16 + 3 * conv == 2_624
    assert m.state_bytes(cfg, 1) == 4 * 2_624 * 4
    ssd = 5 * 8 * 16 * 16
    assert m.ssd_update_flops(cfg) == ssd + 2 * 4 * conv
    lives = [10, 20, 30]
    assert m.ssd_step_work(cfg, lives, 2) == (3 * 4 * ssd,
                                              2 * 3 * 4 * 2_048 * 4)
    expert = 3 * 64 * 32
    layer = 2 * 64 + 64 * 16 + 3 * 64 * 64
    every_step = 4 * mixer + 12_288 + 5 * layer + 64 * 256 + 64
    assert m.dense_step_params(cfg) == every_step
    counted = {"experts_touched": 7, "local_pairs": 9}
    flops, nbytes = m.step_work(cfg, lives, 2, counted)
    kv = 2 * 2 * 16 * 4
    assert nbytes == 2 * (2 * every_step + 7 * expert) + kv * 63 \
        + 2 * 3 * 4 * 2_624 * 4
    per_row = 2 * (every_step - 64) + 4 * (ssd + 2 * 4 * conv)
    assert flops == 3 * per_row + 4 * 64 * 60 + 2 * expert * 9
    assert [m.kernel_calls(cfg, k) for k in (
        "ssd_step", "paged_decode_attention", "expert_gmm")] == [4, 1, 10]
    # a fill writes the state once, and reads it where it continues
    _f0, b0 = m.fill_work(cfg, 0, 8)
    _f1, b1 = m.fill_work(cfg, 8, 8)
    assert b1 - b0 == 4 * 2_624 * 4 + kv * 8
    # the chunked form: 40 positions are three chunks of 16, the last
    # partial and computed whole
    chunk = 2 * 16 * 16 * 2 * 16 + 2 * 8 * 16 * 16 * 16 \
        + 4 * 16 * 8 * 16 * 16
    assert m.ssd_chunk_flops(cfg, 40) == 3 * chunk + 2 * 4 * conv * 40


def test_the_ssd_step_metric_reads_a_reduced_trace():
    """``metrics/kernel.codegen_ssd_step_roofline.json`` through
    ``readers/step_kernel_work.py`` on a made-up reduction at the
    cell's shapes: nine calls a step, the state once in and once out."""
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      "granite-4.0-h-small.json"))
    m = spec.load_module("models", cfg["model"])
    reader = spec.load_module("readers", "step_kernel_work")
    metric = spec.load_json(os.path.join(
        spec.BENCH_DIR, "metrics", "kernel.codegen_ssd_step_roofline.json"))

    class Run:
        model, peaks = m, {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
        c0 = c1 = {"kv": {}}
        trace = {"ta": 0.0, "tb": 4.0, "steps_a": 100, "steps_b": 150,
                 "reduced": {"device_ops": [
                     ["jit_step: ssd_step f32[64,128,64,128] x450", 0.350],
                     ["jit_prefill: fusion f32[1,1024,8192] x80", 0.2]]}}

        def program_durations(self, names):
            return [0.020] * 50

        def decoded_between(self, a, b):
            return [1000] * 3200                   # 50 steps of 64 rows

    run = Run()
    run.cfg = cfg
    _flops, nbytes = m.ssd_step_work(cfg, [1000] * 3200, 50)
    assert nbytes == 2 * 3200 * 9 * 4_194_304
    want = 100.0 * (nbytes / 50 / 819e9) / (0.350 / 50)
    assert reader.read(run, metric) == pytest.approx(want) and 80 < want < 100
    run.trace["reduced"]["device_ops"][0][0] = \
        "jit_step: ssd_step f32[64,128,64,128] x350"
    assert reader.read(run, metric) is None        # calls fell off the list
