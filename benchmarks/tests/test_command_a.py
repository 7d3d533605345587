"""``models/command_a.py``: the reference against itself in shares, its
counts against hand arithmetic, and a toy configuration of the same
structure rehearsed through the runner on the CPU both ways (as served,
and with the int8 control in the program's place) with every metric
file of the cell read.  (The reference against the program's logits
across the window's edge, the two page classes, the kernels: tier-1,
``tests/test_window_experts.py``.)
"""
import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import compare, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "command-a-plus.longdoc"


def _real():
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      "command-a-plus.json"))
    return cfg, spec.load_module("models", cfg["model"])


def _toy():
    cfg = spec.load_json(os.path.join(HERE, "toy_command_a", "config.json"))
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
    shutil.copy(os.path.join(HERE, "toy_command_a", "config.json"),
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
    # the program's matmuls in float32, as tests/test_kimi.py and for
    # its reason: what is rehearsed is the runner and the comparison
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
    assert len(declared) == 24
    for n, m in declared.items():
        if m["source"] == "device_trace" or n.endswith("hbm_peak_gb"):
            assert n not in line["metrics"], n
        else:
            assert n in line["metrics"], n
    value = lambda n: line["metrics"][n]["value"]       # noqa: E731
    # 8 of 16 held: half of the pairs fall here
    assert 0 < value("moe.longdoc_touched_share") <= 100
    assert 40 < value("moe.longdoc_local_pair_share") < 60
    # contexts of up to 210 under a window of 40: pages were given back
    assert 10 < value("kv.longdoc_window_held_share") < 100
    assert 99.5 < value("batcher.longdoc_accounted_share") < 100.5


def test_the_reference_in_shares_adds_up_to_the_uncut_layer():
    """All eight shares of the toy's expert layer (2 of its 16 experts
    each) and the shared experts once are the layer with all 16 held."""
    import jax
    import jax.numpy as jnp

    cfg, m = _toy()
    e, d = cfg["intermediate_size"], cfg["hidden_size"]
    routed, per = cfg["num_experts_published"], 2
    ks = jax.random.split(jax.random.key(5), 6)
    n = lambda k, s, f: jax.random.normal(k, s, jnp.float32) / f ** 0.5  # noqa: E731,E501
    sh = cfg["num_shared_experts"]
    mp = {"router": n(ks[0], (d, routed), d),
          "w1": n(ks[1], (routed, d, 2 * e), d),
          "w2": n(ks[2], (routed, e, d), e),
          "ws1": n(ks[3], (d, 2 * sh * e), d),
          "ws2": n(ks[4], (sh * e, d), e)}
    t = jax.random.normal(ks[5], (24, d), jnp.float32)
    whole = m._experts(t, mp, cfg, False, held=(0, routed))
    only_shared = m._experts(t, {**mp, "w1": mp["w1"][:0],
                                 "w2": mp["w2"][:0]}, cfg, False,
                             held=(0, 0))
    parts = sum(
        m._experts(t, {**mp, "w1": mp["w1"][lo:lo + per],
                       "w2": mp["w2"][lo:lo + per]}, cfg, False,
                   held=(lo, lo + per)) - only_shared
        for lo in range(0, routed, per))
    np.testing.assert_allclose(parts + only_shared, whole, atol=2e-5)
    # and the weights a token gives its chosen experts add up to one
    ids, w = m.route(t, mp["router"], cfg)
    assert ids.shape == (24, cfg["num_experts_per_tok"])
    np.testing.assert_allclose(w.sum(axis=-1), 1.0, atol=1e-6)


def test_work_against_hand_counts():
    cfg, m = _real()
    assert m.n_layers(cfg) == (3, 1)
    assert m.attn_params(cfg) == 142_606_336
    assert m.layer_dense_params(cfg) == 344_457_216
    assert m.kv_token_layer_bytes(cfg) == 8192
    dense = 4 * 344_457_216 + 32768 * 4096
    # one step, 16 rows: ten past the window, six inside it
    lives = [13056, 9000, 5000, 4097, 4096, 100] + [6000] * 10
    win = 4096 * 14 + 4096 + 100                # min(live, 4096)
    full = sum(lives)
    touched = 4 * 16 * (1 - (15 / 16) ** 16)
    flops, nbytes = m.step_work(cfg, lives, 1)
    assert flops == pytest.approx(
        2 * 16 * dense + 4 * 128 * 128 * (3 * win + full)
        + 2 * 50_331_648 * 4 * 16 * 8 * 16 / 128)
    assert nbytes == pytest.approx(
        2 * (dense + touched * 50_331_648)
        + 8192 * (3 * win + full + 16 * 4))
    # the program's own counts take the expectation's place
    counts = {"experts_touched": 40.0, "local_pairs": 70.0, "rows": 16.0}
    f2, b2 = m.step_work(cfg, lives, 1, counts)
    assert b2 == pytest.approx(nbytes + 2 * (40 - touched) * 50_331_648)
    assert f2 == pytest.approx(flops + 2 * 50_331_648 * (70 - 64))
    # the kernels' shares of it
    assert m.window_attn_work(cfg, lives, 1) == (
        pytest.approx(3 * 4 * 128 * 128 * win), pytest.approx(3 * 8192 * win))
    assert m.expert_work(cfg, lives, 1, counts) == (
        pytest.approx(2 * 50_331_648 * 70), pytest.approx(2 * 40 * 50_331_648))
    assert [m.kernel_calls(cfg, k) for k in (
        "expert_gmm", "window_decode_attention",
        "paged_decode_attention")] == [8, 3, 1]
    # a fill of 6,000 rows from 0: a window layer's row p attends
    # min(p + 1, 4096), the full layer's p + 1
    n = 6000
    a_win = 4096 * 4097 // 2 + (n - 4096) * 4096
    a_full = n * (n + 1) // 2
    flops, nbytes = m.fill_work(cfg, 0, n)
    t_fill = 4 * 16 * (1 - (15 / 16) ** n)
    assert flops == pytest.approx(
        2 * n * 4 * 344_457_216 + 4 * 128 * 128 * (3 * a_win + a_full)
        + 2 * 50_331_648 * 4 * n)
    assert nbytes == pytest.approx(
        2 * (4 * 344_457_216 + t_fill * 50_331_648) + 8192 * 4 * n)
    assert m.fill_work(cfg, 100, 0) == (0.0, 0.0)
    # a span from 8,192: the window layers read the window behind it
    _f, b = m.fill_work(cfg, 8192, 1024)
    assert b == pytest.approx(
        2 * (4 * 344_457_216 + 4 * 16 * 50_331_648)
        + 8192 * (3 * (4096 + 1024) + 8192 + 1024), rel=1e-9)


def test_the_file_keeps_the_source_and_states_the_cut():
    cfg, m = _real()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "command-a-plus-05-2026")
    reduced = {"num_experts": 16, "vocab_size": 32768,
               "num_hidden_layers": 4}
    for k, v in row["config"].items():
        assert cfg[k] == reduced.get(k, v), k
    assert cfg["source"] == row["source_url"]
    assert (cfg["num_experts_published"], cfg["vocab_size_published"],
            cfg["num_hidden_layers_published"]) == (128, 262144, 32)
    assert sorted(cfg["reduced"]) == sorted(reduced)
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "command-a-plus")
    assert sorted(entry["reduced"]) == sorted(reduced)
    assert m.total_params(cfg) * 2 == pytest.approx(9.47e9, rel=1e-3)
    # the traffic fits the service: the longest context is max_seq
    mix = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                      "longdoc.json"))["session"]
    ends = [p + o for p, o in zip(mix["prompt_len"]["values"],
                                  mix["output_len"]["values"])]
    svc = cfg["service"]
    assert max(ends) == svc["max_seq"] == 13056
    assert max(mix["output_len"]["values"]) <= svc["max_new_cap"]
    # two of each pair live at once fit the full class's pages
    assert 2 * sum(-(-e // svc["page"]) for e in ends) < svc["kv_pages"]
