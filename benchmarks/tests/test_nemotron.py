"""``models/nemotron.py``: a toy configuration of the same structure
rehearsed through the runner on the CPU both ways (as served, and with
the int8 control in the program's place), and the count functions
against hand arithmetic at the toy's shapes.  (The cell's own counts
against hand arithmetic, the reference against the program's logits,
the shares that add up, the wrong forms that fail: tier-1,
``tests/test_nemotron_hybrid.py``.)
"""
import json
import os
import shutil

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import compare, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "nemotron-3-nano-30b.codegen"


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
    shutil.copy(os.path.join(HERE, "toy_nemotron", "config.json"),
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
    """The cell runs codegen's mix with 128 callers on the 128 slots; it
    reports ``itl_p50_ms``, ``setup_s`` and the 22 ``*.codegen_*``
    per-layer metrics a block without latent attention can; its
    configuration holds every key of the catalog's but the three it
    cuts, each with its published value beside it."""
    cell = spec.Cell(CELL)
    assert cell.traffic["clients"] == 128 == cell.config["service"][
        "decode_slots"]
    assert [m["name"] for m in cell.end_to_end] == ["itl_p50_ms", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert len(names) == 22 and all(".codegen_" in n for n in names)
    assert "kernel.codegen_mla_decode_roofline" not in names
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in bench["configs"]}["nemotron-3-nano-30b"]
    assert sorted(entry["reduced"]) == sorted(cell.config["reduced"]) \
        == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (cell.config["num_hidden_layers_published"],
            cell.config["n_routed_experts_published"],
            cell.config["vocab_size_published"]) == (52, 128, 131072)
    assert len(bench["per_layer"]) == 128
    assert len(cell.config["hybrid_override_pattern"]) == 52


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(toy_root, trace, monkeypatch):
    from brpc_tpu import native
    from brpc_tpu.ops import quant
    if native.load() is None:
        pytest.skip("the native engine does not build here")
    # the program's matmuls in float32, as tests/test_granite.py has them
    # and for its reason: what is rehearsed is the runner and the
    # comparison
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
    kv = win.run.c1["kv"]
    assert kv["schedule"] == {"mixer_only": {"mamba2": 3, "attn": 1},
                              "ffn_only": {"experts": 2}, "both": {}}
    assert kv["mamba2"]["layers"] == 3 and kv["mamba2"]["groups"] == 4
    assert kv["moe"]["layers"] == 2 and kv["moe"]["act"] == "relu2"
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
    """The count functions on shapes small enough to add by hand: 6
    layers MEM*EM, 8 heads of 16 over a state of 16 in 4 groups, hidden
    64, 8 of 16 experts of 40 held, a shared expert of 48."""
    cfg = spec.load_json(os.path.join(HERE, "toy_nemotron", "config.json"))
    m = spec.load_module("models", cfg["model"])
    assert m.n_parts(cfg) == (3, 2, 1)
    conv = 128 + 2 * 4 * 16
    mixer = 64 * (128 + conv + 8) + 5 * conv + 3 * 8 + 128 + 128 * 64 + 64
    assert m.mamba2_params(cfg) == mixer
    attn = 64 * (8 + 4) * 16 + 8 * 16 * 64 + 64
    assert m.attn_params(cfg) == attn
    expert, layer = 2 * 64 * 40, 65 * 16 + 2 * 64 * 48 + 64
    assert m.expert_params(cfg) == expert
    assert m.expert_layer_params(cfg) == layer
    total = 3 * mixer + attn + 2 * (layer + 8 * expert) + 2 * 64 * 256 + 64
    assert m.total_params(cfg) == total
    every_step = total - 2 * 8 * expert - 64 * 256
    assert m.dense_step_params(cfg) == every_step
    lives = [10, 20, 30]
    counted = {"experts_touched": 7, "local_pairs": 9}
    flops, nbytes = m.step_work(cfg, lives, 2, counted)
    kv = 2 * 2 * 16 * 4
    state = 8 * 16 * 16 + 3 * conv
    assert nbytes == 2 * (2 * every_step + 7 * expert) + kv * 63 \
        + 2 * 3 * 3 * state * 4
    ssd = 5 * 8 * 16 * 16
    per_row = 2 * every_step + 3 * (ssd + 2 * 4 * conv)
    assert flops == 3 * per_row + 4 * 8 * 16 * 60 + 2 * expert * 9
    assert [m.kernel_calls(cfg, k) for k in (
        "ssd_step", "paged_decode_attention", "expert_gmm")] == [3, 1, 4]
    assert m.expert_work(cfg, lives, 2, counted) == (2 * expert * 9,
                                                     2 * 7 * expert)
    _f0, b0 = m.fill_work(cfg, 0, 8)
    _f1, b1 = m.fill_work(cfg, 8, 8)
    assert b1 - b0 == 3 * state * 4 + kv * 8
