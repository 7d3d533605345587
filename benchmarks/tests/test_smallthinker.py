"""``models/smallthinker.py``: the reference against itself in shares,
its counts against hand arithmetic, the configuration file against the
catalog's row, and a toy configuration of the same structure (14 query
heads on 2: groups of seven) rehearsed through the runner on the CPU
both ways (as served, and with the int8 control in the program's
place) with every metric file of the cell read.  (The reference against
the program's logits across the window's edge, the wrong variants, the
kernels at a group of seven: tier-1,
``tests/test_early_routed_experts.py``.)
"""
import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import compare, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "smallthinker-21b.longdoc"


def _real():
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      "smallthinker-21b.json"))
    return cfg, spec.load_module("models", cfg["model"])


def _toy():
    cfg = spec.load_json(os.path.join(HERE, "toy_smallthinker",
                                      "config.json"))
    return cfg, spec.load_module("models", cfg["model"])


@pytest.fixture()
def toy_root(tmp_path):
    """A checkout in little: the real ``BENCHMARK.json`` cut to the
    cell ``toy.longdoc`` on the toy configuration of this directory,
    with every metric that the real cell reports; the real metric files
    and readers."""
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    base = tmp_path / bench["paths"][0]
    os.makedirs(base / "traffic")
    shutil.copy(os.path.join(HERE, "toy", "traffic", "longdoc.json"),
                base / "traffic" / "longdoc.json")
    shutil.copy(os.path.join(HERE, "toy_smallthinker", "config.json"),
                base / "toy.json")
    bench["configs"] = [{"name": "toy",
                         "file": f"{bench['paths'][0]}/toy.json"}]
    bench["workloads"] = [{"name": "toy.longdoc", "config": "toy",
                           "traffic": "longdoc", "chips": 1}]
    for key in ("end_to_end", "per_layer"):
        bench[key] = [{**m, "workloads": ["toy.longdoc"]} for m in bench[key]
                      if CELL in m.get("workloads", [CELL])]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(toy_root, trace, monkeypatch):
    from brpc_tpu import native
    from brpc_tpu.ops import quant
    if native.load() is None:
        pytest.skip("the native engine does not build here")
    # the program's matmuls in float32, as tests/test_command_a.py and
    # for its reason: what is rehearsed is the runner and the comparison
    # both ways, not a router's choice flipped by a bf16 rounding
    monkeypatch.setattr(quant, "qmatmul", lambda x, w: x @ w)
    monkeypatch.setattr(quant, "mxu_operand", lambda x: x)
    cell = spec.Cell("toy.longdoc", root=toy_root)
    win = bench_run.run_window(cell, seed=(1 << 31) + 6, seconds=2.0,
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
        ctl = win.judged(compare.compare(
            ref, win.sample, tokens_of=win.reference(int8=True)))
        assert ctl["correct"] is False, ctl["compared"]
        return
    # no trace on the CPU: the device metrics' readers find nothing to
    # read and return nothing; every counter of the cell is read
    # (all of command-a-plus.longdoc's but its prefill device share: this
    # cell's joins come in bursts that miss the traced 4 s)
    assert len(declared) == 24
    for n, m in declared.items():
        if m["source"] == "device_trace" or n.endswith("hbm_peak_gb"):
            assert n not in line["metrics"], n
        else:
            assert n in line["metrics"], n
    value = lambda n: line["metrics"][n]["value"]       # noqa: E731
    # every expert is held: every pair falls here
    assert 0 < value("moe.longdoc_touched_share") <= 100
    assert value("moe.longdoc_local_pair_share") == pytest.approx(100.0)
    # contexts of up to 210 under a window of 40: pages were given back
    assert 10 < value("kv.longdoc_window_held_share") < 100
    assert 99.5 < value("batcher.longdoc_accounted_share") < 100.5
    moe = win.run.c1["kv"]["moe"]
    assert (moe["scoring"], moe["router_at"]) == ("softmax", "layer_input")


def test_the_reference_in_shares_adds_up_to_the_uncut_layer():
    """The four shares of the toy's expert layer (4 of its 16 experts
    each), routed ONCE on the layer's input, are the layer with all 16
    held; and the choice is the softmax's: renormalised over the chosen,
    it is the softmax over the chosen logits alone."""
    import jax
    import jax.numpy as jnp

    cfg, m = _toy()
    e, d = cfg["moe_ffn_hidden_size"], cfg["hidden_size"]
    routed, per = cfg["moe_num_primary_experts"], 4
    ks = jax.random.split(jax.random.key(5), 5)
    n = lambda k, s, f: jax.random.normal(k, s, jnp.float32) / f ** 0.5  # noqa: E731,E501
    mp = {"router": n(ks[0], (d, routed), d),
          "w1": n(ks[1], (routed, d, 2 * e), d),
          "w2": n(ks[2], (routed, e, d), e)}
    x = 3.0 * jax.random.normal(ks[3], (24, d), jnp.float32)
    t = jax.random.normal(ks[4], (24, d), jnp.float32)
    ids, w = m.route(x, mp["router"], cfg)
    assert ids.shape == (24, cfg["moe_num_active_primary_experts"])
    np.testing.assert_allclose(w.sum(axis=-1), 1.0, atol=1e-6)
    logits = jnp.take_along_axis(x @ mp["router"], ids, axis=-1)
    np.testing.assert_allclose(w, jax.nn.softmax(logits, axis=-1), atol=1e-6)
    whole = m._experts(t, ids, w, mp, cfg, False)
    parts = sum(
        m._experts(t, ids, w, {**mp, "w1": mp["w1"][lo:lo + per],
                               "w2": mp["w2"][lo:lo + per]}, cfg, False,
                   held=(lo, lo + per))
        for lo in range(0, routed, per))
    np.testing.assert_allclose(parts, whole, atol=2e-5)


def test_work_against_hand_counts():
    cfg, m = _real()
    assert m.n_layers(cfg) == (6, 2)
    assert m.attn_params(cfg) == 20_971_520
    assert m.router_params(cfg) == 163_840
    assert m.expert_params(cfg) == 5_898_240
    assert m.layer_params(cfg) == 398_622_720
    assert m.table_params(cfg) == 388_956_160
    assert m.total_params(cfg) == 3_966_894_080
    assert m.kv_token_layer_bytes(cfg) == 4096
    dense = 8 * (20_971_520 + 163_840) + 388_956_160
    # one step, 32 rows: 26 past the window, six inside it
    lives = [13056, 9000, 5000, 4097, 4096, 100, 2049, 3000] + [7000] * 24
    win = 4096 * 29 + 100 + 2049 + 3000         # min(live, 4096)
    full = sum(lives)
    touched = 8 * 64 * (1 - (58 / 64) ** 32)
    assert touched / (8 * 64) == pytest.approx(0.957, abs=5e-4)
    flops, nbytes = m.step_work(cfg, lives, 1)
    assert flops == pytest.approx(
        2 * 32 * dense + 4 * 28 * 128 * (6 * win + 2 * full)
        + 2 * 5_898_240 * 8 * 32 * 6)
    assert nbytes == pytest.approx(
        2 * (dense + touched * 5_898_240)
        + 4096 * (6 * win + 2 * full + 32 * 8))
    # the program's own counts take the expectation's place
    counts = {"experts_touched": 480.0, "local_pairs": 1500.0, "rows": 32.0}
    f2, b2 = m.step_work(cfg, lives, 1, counts)
    assert b2 == pytest.approx(nbytes + 2 * (480 - touched) * 5_898_240)
    assert f2 == pytest.approx(flops + 2 * 5_898_240 * (1500 - 1536))
    # the kernels' shares of it
    assert m.window_attn_work(cfg, lives, 1) == (
        pytest.approx(6 * 4 * 28 * 128 * win), pytest.approx(6 * 4096 * win))
    assert m.expert_work(cfg, lives, 1, counts) == (
        pytest.approx(2 * 5_898_240 * 1500),
        pytest.approx(2 * 480 * 5_898_240))
    assert [m.kernel_calls(cfg, k) for k in (
        "expert_gmm", "window_decode_attention",
        "paged_decode_attention")] == [16, 6, 2]
    # a fill of 6,000 rows from 0: a window layer's row p attends
    # min(p + 1, 4096), a global layer's p + 1; every expert is touched
    n = 6000
    a_win = 4096 * 4097 // 2 + (n - 4096) * 4096
    a_full = n * (n + 1) // 2
    flops, nbytes = m.fill_work(cfg, 0, n)
    layers = 8 * (20_971_520 + 163_840)
    assert flops == pytest.approx(
        2 * n * layers + 4 * 28 * 128 * (6 * a_win + 2 * a_full)
        + 2 * 5_898_240 * 8 * n * 6)
    assert nbytes == pytest.approx(
        2 * (layers + 8 * 64 * 5_898_240) + 4096 * 8 * n, rel=1e-9)
    assert m.fill_work(cfg, 100, 0) == (0.0, 0.0)
    # a span from 8,192: the window layers read the window behind it
    _f, b = m.fill_work(cfg, 8192, 1024)
    assert b == pytest.approx(
        2 * (layers + 8 * 64 * 5_898_240)
        + 4096 * (6 * (4096 + 1024) + 2 * (8192 + 1024)), rel=1e-9)


def test_the_file_keeps_the_source_and_states_the_cut():
    cfg, m = _real()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "SmallThinker-21BA3B-Instruct")
    reduced = {"num_hidden_layers": 8}
    for k, v in row["config"].items():
        assert cfg[k] == reduced.get(k, v), k
    assert cfg["source"] == row["source_url"]
    assert cfg["num_hidden_layers_published"] == 52
    assert sorted(cfg["reduced"]) == sorted(reduced)
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "smallthinker-21b")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == row["source_url"]
    for key in ("deployment", "assumed", "what_this_is"):
        assert cfg[key], key
    for item in ("router_input", "routing_order", "window_edge",
                 "rotary_pairing", "attention_bias", "secondary_experts",
                 "kv_cache_bytes", "service.fill_span"):
        assert item in cfg["assumed"], item
    assert m.total_params(cfg) * 2 == pytest.approx(7.934e9, rel=1e-3)
    # two whole periods, every kind of layer in its published ratio
    assert m.layer_windows(cfg) == [False, True, True, True] * 2
    # the traffic fits the service: the longest context is max_seq
    mix = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                      "longdoc.json"))["session"]
    ends = [p + o for p, o in zip(mix["prompt_len"]["values"],
                                  mix["output_len"]["values"])]
    svc = cfg["service"]
    assert max(ends) == svc["max_seq"] == 13056 \
        <= cfg["max_position_embeddings"]
    assert max(mix["output_len"]["values"]) <= svc["max_new_cap"]
    # four of each pair live at once fit the global class's pages, and
    # the cell sends as many callers as there are slots
    own = spec.load_json(os.path.join(spec.BENCH_DIR, "cells",
                                      CELL + ".json"))
    assert own["clients"] == svc["decode_slots"] == 32
    assert 4 * sum(-(-e // svc["page"]) for e in ends) < svc["kv_pages"]
    # the pools' bytes, as the file's deployment states them
    assert svc["kv_pages"] * 16 * 4096 * 2 / 1e9 == pytest.approx(2.013,
                                                                 abs=1e-3)
    assert svc["window_pages"] * 16 * 4096 * 6 / 1e9 == pytest.approx(
        3.272, abs=1e-3)
