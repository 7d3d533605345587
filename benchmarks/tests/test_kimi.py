"""``models/kimi_k2.py``: a toy configuration of the same structure
rehearsed through the runner on the CPU both ways (as served, and with
the int8 control in the program's place), and the step kernels' reader
on a made-up reduction.  (Its counts against hand arithmetic, the
reference against the program's logits, the shares that add up: tier-1,
``tests/test_latent_experts.py``.)
"""
import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import compare, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "kimi-k2.7-code.codegen"


@pytest.fixture()
def toy_kimi_root(tmp_path):
    """A checkout in little: the real ``BENCHMARK.json`` cut to the
    cell ``toy.codegen`` on the toy configuration of this directory,
    with every metric that the real cell reports; the real metric files
    and readers."""
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    base = tmp_path / bench["paths"][0]
    os.makedirs(base / "traffic")
    shutil.copy(os.path.join(HERE, "toy", "traffic", "codegen.json"),
                base / "traffic" / "codegen.json")
    shutil.copy(os.path.join(HERE, "toy_kimi", "config.json"),
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


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(toy_kimi_root, trace, monkeypatch):
    from brpc_tpu import native
    from brpc_tpu.ops import quant
    if native.load() is None:
        pytest.skip("the native engine does not build here")
    # the program's matmuls in float32: at widths this small a bf16
    # rounding flips a router's choice onto or off a held expert in
    # one request of a few, which reads like the control (the toy
    # configuration's ``correct.set_from``); what is rehearsed here is
    # the runner and the comparison both ways, not the rounding
    monkeypatch.setattr(quant, "qmatmul", lambda x, w: x @ w)
    monkeypatch.setattr(quant, "mxu_operand", lambda x: x)
    cell = spec.Cell("toy.codegen", root=toy_kimi_root)
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
    if not trace:
        assert set(line["metrics"]) == {"itl_p50_ms", "setup_s"}
        # the int8 control in the program's place, same sample
        ctl = win.judged(compare.compare(
            ref, win.sample, tokens_of=win.reference(int8=True)))
        assert ctl["correct"] is False, ctl["compared"]
        return
    # no trace on the CPU: the device metrics' readers find nothing to
    # read and return nothing; the routing counts are read
    for n, m in declared.items():
        if m["source"] == "device_trace" or n.endswith("hbm_peak_gb"):
            assert n not in line["metrics"], n
        else:
            assert n in line["metrics"], n
    touched = line["metrics"]["moe.codegen_touched_share"]["value"]
    local = line["metrics"]["moe.codegen_local_pair_share"]["value"]
    # 4 of 64 held: a sixteenth of the pairs fall here (6.25%)
    assert 0 < touched <= 100 and 2 < local < 12


def test_step_kernel_reader_reads_a_reduced_trace():
    """``readers/step_kernel_work.py`` on a made-up reduction: the
    kernel's time inside the step only, against the model's count for
    the traced tokens; nothing, never 0, where the kernel is not among
    the operations kept, its calls do not add up, the model module has
    no such count, or there is no trace."""
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      "kimi-k2.7-code.json"))
    m = spec.load_module("models", cfg["model"])
    reader = spec.load_module("readers", "step_kernel_work")
    metric = spec.load_json(os.path.join(
        spec.BENCH_DIR, "metrics", "kernel.codegen_mla_decode_roofline.json"))
    moe0 = {"steps": 10, "rows": 640, "local_pairs": 1100,
            "experts_touched": 600}
    moe1 = {"steps": 110, "rows": 7040, "local_pairs": 12300,
            "experts_touched": 6850}

    class Run:
        model, peaks = m, {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
        c0, c1 = {"kv": {"moe": moe0}}, {"kv": {"moe": moe1}}
        trace = {"ta": 0.0, "tb": 4.0, "steps_a": 100, "steps_b": 150,
                 "reduced": {"device_ops": [
                     ["jit_step: mla_decode_attention f32[64,64,512] x400",
                      0.110],
                     ["jit_prefill: mla_decode_attention f32[1] x8", 9.0],
                     ["jit_step: ragged-dot f32[512,4096] x350", 0.5]]}}

        def program_durations(self, names):
            return [0.025] * 50

        def decoded_between(self, a, b):
            return [1000] * 3200                    # 50 steps of 64 rows

    run = Run()
    run.cfg = cfg
    _flops, nbytes = m.mla_decode_work(cfg, [1000] * 3200, 50)
    want = 100.0 * (nbytes / 50 / 819e9) / (0.110 / 50)
    assert reader.read(run, metric) == pytest.approx(want) and 0 < want < 100
    # the counters reach a work function that wants them, scaled to the
    # traced steps
    assert reader.moe_counters(run, 50) == {
        "rows": 3200.0, "local_pairs": 5600.0, "experts_touched": 3125.0}
    run.c0 = run.c1 = {"kv": {}}
    assert reader.moe_counters(run, 50) is None
    assert reader.read(run, metric) == pytest.approx(want)
    ops = run.trace["reduced"]["device_ops"]
    ops[0][0] = "jit_step: mla_decode_attention f32[64,64,512] x300"
    assert reader.read(run, metric) is None         # calls fell off the list
    del ops[0]
    assert reader.read(run, metric) is None         # not among the ten
    assert reader.read(run, {**metric, "work": "no_such_count"}) is None
    run.trace = None
    assert reader.read(run, metric) is None
    share = spec.load_module("readers", "moe_share")
    assert share.read(run, {"num": "local_pairs",
                            "den": ["rows", "top_k", "layers"]}) is None
