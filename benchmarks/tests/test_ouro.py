"""``models/ouro.py``: a toy configuration of the same structure
rehearsed through the runner on the CPU both ways (as served, and with
the int8 control in the program's place) under the toy ``batch`` mix,
the count functions against hand arithmetic at the toy's shapes, the
reference's layer against an independent einsum form, the two metric
files that wait for room in ``per_layer`` on a made-up reduction, and
the step-count model of the closed loop: what the cell's callers
reserve at once fits the configuration's pages.  (The cell's own counts
against hand arithmetic, the reference against the program's logits,
the exit rule: tier-1, ``tests/test_looped_lm.py``.)
"""
import heapq
import json
import os
import random
import shutil

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import compare, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "ouro-2.6b.batch"


def _real():
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      "ouro-2.6b.json"))
    return cfg, spec.load_module("models", cfg["model"])


def _toy():
    cfg = spec.load_json(os.path.join(HERE, "toy_ouro", "config.json"))
    return cfg, spec.load_module("models", cfg["model"])


@pytest.fixture()
def toy_root(tmp_path):
    """A checkout in little: the real ``BENCHMARK.json`` cut to the
    cell ``toy.batch`` on the toy configuration of this directory, with
    every metric that the real cell reports; the real metric files and
    readers."""
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    base = tmp_path / bench["paths"][0]
    os.makedirs(base / "traffic")
    shutil.copy(os.path.join(HERE, "toy", "traffic", "batch.json"),
                base / "traffic" / "batch.json")
    shutil.copy(os.path.join(HERE, "toy_ouro", "config.json"),
                base / "toy.json")
    bench["configs"] = [{"name": "toy",
                         "file": f"{bench['paths'][0]}/toy.json"}]
    bench["workloads"] = [{"name": "toy.batch", "config": "toy",
                           "traffic": "batch", "chips": 1}]
    for key in ("end_to_end", "per_layer"):
        bench[key] = [{**m, "workloads": ["toy.batch"]} for m in bench[key]
                      if CELL in m.get("workloads", [CELL])]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_the_cell_takes_the_batch_metrics():
    """The cell's file overrides the mix's ``clients`` alone; it
    reports ``out_tok_s``, ``setup_s`` and the ``*.batch_*`` per-layer
    metrics but the share of programs it never runs; ``per_layer``
    stays full."""
    cell = spec.Cell(CELL)
    assert cell.traffic["clients"] == 4 == cell.config["service"][
        "decode_slots"]
    assert cell.traffic["session"]["prompt_len"]["values"][1] == 1024
    assert [m["name"] for m in cell.end_to_end] == ["out_tok_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert len(names) == 21 and all(".batch_" in n for n in names)
    assert "model.batch_prefill_device_share" not in names
    assert all("reader" in m["spec"] for m in cell.per_layer)
    bench = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in bench["configs"]}["ouro-2.6b"]
    assert entry["reduced"] == [] and cell.config["reduced"] == {}
    assert len(bench["per_layer"]) == 128


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(toy_root, trace, monkeypatch):
    from brpc_tpu import native
    from brpc_tpu.ops import quant
    if native.load() is None:
        pytest.skip("the native engine does not build here")
    # the program's matmuls in float32, as tests/test_command_a.py has
    # them: at these widths and ~80 compared tokens bf16 operands do
    # not separate from the control (the toy configuration's
    # ``set_from``); what is rehearsed is the runner and the
    # comparison both ways
    monkeypatch.setattr(quant, "qmatmul", lambda x, w: x @ w)
    monkeypatch.setattr(quant, "mxu_operand", lambda x: x)
    cell = spec.Cell("toy.batch", root=toy_root)
    win = bench_run.run_window(cell, seed=(1 << 31) + 39, seconds=2.0,
                               trace=bool(trace), require_tpu=False)
    ref = win.reference()
    line = json.loads(json.dumps(win.judged(compare.compare(ref,
                                                            win.sample))))
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    declared = {m["name"]: m for m in
                (cell.per_layer if trace else cell.end_to_end)}
    assert set(line["metrics"]) <= set(declared)
    loop = win.run.c1["kv"]["loop"]
    assert (loop["passes"], loop["layers"]) == (4, 3)
    assert loop["layer_passes"] == 12 * loop["steps"] > 0
    assert loop["fills"] > 0 and loop["fill_spans"] >= loop["fills"]
    assert loop["token_bytes"] == 4 * 3 * 2 * 4 * 16 * 4
    assert win.run.c1["kv"]["alloc"]["peak_in_use"] <= 64
    if not trace:
        assert set(line["metrics"]) == {"out_tok_s", "setup_s"}
        # the int8 control in the program's place, same sample
        ctl = win.judged(compare.compare(
            ref, win.sample, tokens_of=win.reference(int8=True)))
        assert ctl["correct"] is False, ctl["compared"]
        return
    # no trace on the CPU: the device metrics' readers find nothing to
    # read and return nothing; every counter of the cell is read
    assert len(declared) == 21
    for n, m in declared.items():
        if m["source"] == "device_trace" or n.endswith("hbm_peak_gb"):
            assert n not in line["metrics"], n
        else:
            assert n in line["metrics"], n
    value = lambda n: line["metrics"][n]["value"]       # noqa: E731
    assert 99.5 < value("batcher.batch_accounted_share") < 100.5
    assert 0 < value("kv.batch_pages_peak_share") <= 100


def test_the_layer_is_the_equations_einsum_form():
    """``models/ouro.py``'s layer against the block's equations written
    once more, independently: attention as two einsums over a mask in
    float64 numpy, the norms by hand."""
    import jax
    import jax.numpy as jnp

    cfg, m = _toy()
    d, f, h, hd = 64, 96, 4, 16
    rng = np.random.default_rng(3)
    bp = {"wqkv": rng.standard_normal((d, 3 * d)) / 8,
          "wo": rng.standard_normal((d, d)) / 8,
          "w1": rng.standard_normal((d, 2 * f)) / 8,
          "w2": rng.standard_normal((f, d)) / 10,
          **{g: 1 + 0.1 * rng.standard_normal(d)
             for g in ("ln1", "pn1", "ln2", "pn2")}}
    x = rng.standard_normal((9, d))

    def rms(t, g):
        return t * g / np.sqrt((t * t).mean(-1, keepdims=True) + 1e-6)

    q, k, v = np.split(rms(x, bp["ln1"]) @ bp["wqkv"], 3, axis=-1)
    ang = np.arange(9)[:, None] * 1e6 ** (-np.arange(hd // 2) / (hd // 2))

    def rot(t):
        t = t.reshape(9, h, hd)
        a, b = t[..., :hd // 2], t[..., hd // 2:]
        c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
        return np.concatenate([a * c - b * s, a * s + b * c], -1)

    sc = np.einsum("qhd,khd->hqk", rot(q), rot(k)) / 4.0
    sc = np.where(np.tril(np.ones((9, 9), bool)), sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    o = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True),
                  v.reshape(9, h, hd)).reshape(9, d)
    hh = x + rms(o @ bp["wo"], bp["pn1"])
    g, u = np.split(rms(hh, bp["ln2"]) @ bp["w1"], 2, axis=-1)
    want = hh + rms((g / (1 + np.exp(-g)) * u) @ bp["w2"], bp["pn2"])
    with jax.default_matmul_precision("highest"):
        got = m._layer(jnp.asarray(x, jnp.float32),
                       {n: jnp.asarray(a, jnp.float32)
                        for n, a in bp.items()}, cfg, False)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_counts_at_the_toys_shapes():
    """The count functions on shapes small enough to add by hand: 3
    layers run 4 times, 4 heads of 16, hidden 64, feed-forward 96,
    vocabulary 2,048."""
    cfg, m = _toy()
    layer = 64 * 192 + 64 * 64 + 64 * 192 + 96 * 64
    assert m.layer_matmul_params(cfg) == layer == 34_816
    assert m.total_params(cfg) == 3 * (layer + 256) + 2 * 2048 * 64 + 129
    assert m.layer_bodies(cfg) == 12
    token = 12 * 2 * 4 * 16 * 4
    assert m.token_kv_bytes(cfg) == token == 6_144
    lives = [10, 20, 30]
    flops, nbytes = m.step_work(cfg, lives, 2)
    assert nbytes == 2 * 2 * (4 * 3 * layer + 2048 * 64) + token * (60 + 3)
    assert flops == 2 * 3 * (4 * 3 * layer + 2048 * 64) + 12 * 4 * 64 * 60
    assert m.paged_attn_work(cfg, lives, 2) == (12 * 4 * 64 * 60, token * 60)
    assert m.kernel_calls(cfg, "paged_decode_attention") == 12
    f_fill, b_fill = m.fill_work(cfg, 8, 4)
    assert f_fill == 2 * 4 * 12 * layer + 12 * 4 * 64 * (4 * 8 + 10)
    assert b_fill == 2 * 12 * layer + token * 12


def test_the_file_keeps_the_source_and_cuts_nothing():
    cfg, m = _real()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Ouro-2.6B")
    for k, v in row["config"].items():
        assert cfg[k] == v, k
    assert cfg["source"] == row["source_url"] and cfg["reduced"] == {}
    assert m.total_params(cfg) * 2 == pytest.approx(5.336e9, rel=1e-3)
    svc = cfg["service"]
    mix = spec.Cell(CELL).traffic["session"]
    ends = [p + o for p, o in zip(mix["prompt_len"]["values"],
                                  mix["output_len"]["values"])]
    assert max(ends) <= svc["max_seq"]
    assert max(mix["output_len"]["values"]) <= svc["max_new_cap"]
    assert all(p % 128 == 0 for p in mix["prompt_len"]["values"])


# -- the closed loop's reservations, as a step-count model ---------------------

def most_pages(mix: dict, clients: int, page: int, sessions: int = 400,
               jitter: int = 0, seed: int = 0) -> int:
    """The most pages reserved at once: every live session gains a
    token a step (a fill stalls all alike and changes no order), a
    session reserves ``ceil((prompt - 1 + output) / page)`` pages at
    admission and holds them to its end, a caller starts the cycle's
    next pair the step after its last closed; callers start three steps
    apart.  ``jitter``: up to so many steps more, drawn a session."""
    rng = random.Random(seed)
    s = mix["session"]
    prompts, outputs = s["prompt_len"]["values"], s["output_len"]["values"]
    free = [(3 * c, c) for c in range(clients)]
    heapq.heapify(free)
    held, most = {}, 0
    for n in range(sessions):
        t, c = heapq.heappop(free)
        p, o = prompts[n % len(prompts)], outputs[n % len(outputs)]
        held[c] = -(-(p - 1 + o) // page)
        most = max(most, sum(held.values()))
        heapq.heappush(free, (t + o + 1 + rng.randint(0, jitter), c))
    return most


def test_what_the_callers_reserve_fits_the_pool():
    """``_admit`` takes a session's pages at admission and REFUSES it
    where they are not there (``kv_pool_exhausted``: a failed stream,
    the run not ``correct``).  Which sessions are in flight together
    follows the cycle's order: four callers reserve 190 pages at the
    most, under the 195 usable; five would not fit."""
    cell = spec.Cell(CELL)
    svc = cell.config["service"]
    usable = svc["kv_pages"] - 1                 # page 0: the garbage page
    assert usable == 195

    def most(clients):
        return max(most_pages(cell.traffic, clients, svc["page"],
                              jitter=j, seed=s)
                   for j in (0, 4, 16) for s in range(30))

    assert most(cell.traffic["clients"]) == 190 <= usable
    assert (most(3), most(5)) == (158, 244)
    # the worst any four of the cycle's eight could reserve: the order
    # never produces it
    s = cell.traffic["session"]
    each = sorted(-(-(p - 1 + o) // 16) for p, o in zip(
        s["prompt_len"]["values"], s["output_len"]["values"]))
    assert each == [16, 23, 32, 40, 47, 55, 63, 70] and sum(each[-4:]) == 235


# -- the two metric files that wait for room in ``per_layer`` ------------------

class _Run:
    peaks = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    c0 = c1 = {"kv": {}}

    def __init__(self, ops, busy_s=4.0):
        self.cfg, self.model = _real()
        self.trace = {"ta": 0.0, "tb": 4.0, "steps_a": 100, "steps_b": 200,
                      "reduced": {"device_ops": ops, "busy_s": busy_s}}

    def program_durations(self, names):
        return {"jit_step": [0.036] * 100, "jit_fill": [0.07] * 8}.get(
            names[0], [])

    def decoded_between(self, a, b):
        return [600] * 400                        # 100 steps of 4 rows


def test_the_decode_kernels_metric_reads_a_reduced_trace():
    """``metrics/kernel.batch_paged_decode_roofline.json`` through
    ``readers/step_kernel_work.py``: 192 calls a step, each live
    token's keys and values once a (pass, layer)."""
    reader = spec.load_module("readers", "step_kernel_work")
    metric = spec.load_json(os.path.join(
        spec.BENCH_DIR, "metrics", "kernel.batch_paged_decode_roofline.json"))
    run = _Run([["jit_step: paged_decode_attention f32[4,16,128] x19200",
                 1.05]])
    nbytes = 3_145_728 * 2400
    want = 100.0 * (nbytes / 819e9) / (1.05 / 100)
    assert reader.read(run, metric) == pytest.approx(want) and 80 < want < 100
    run = _Run([["jit_step: paged_decode_attention f32[4,16,128] x4800",
                 0.3]])
    assert reader.read(run, metric) is None       # a pass's calls only
    # a program without the kernel (the parent's, another block's)
    assert reader.read(_Run([["jit_step: fusion x100", 1.0]]), metric) is None


def test_the_fill_share_metric_reads_the_fill_program():
    reader = spec.load_module("readers", "program_share")
    metric = spec.load_json(os.path.join(
        spec.BENCH_DIR, "metrics", "model.batch_fill_device_share.json"))
    assert reader.read(_Run([]), metric) == pytest.approx(100 * 0.56 / 4.0)

    class NoFill(_Run):
        def program_durations(self, names):
            return []

    assert reader.read(NoFill([]), metric) is None
