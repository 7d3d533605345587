"""A looped schedule (``LMConfig.passes``): the whole stack of layers run
several times a token with the weights shared, a norm behind each
branch (``post_norms``), the final norm closing every pass, and keys and
values a (pass, layer): every pass with pages of its own, prompts
filled through the pages in spans.  At toy widths with the structure of
the benchmark's ``ouro-2.6b``: 3 layers run 4 times, 4 heads of 16, a
gated feed-forward of 96, a vocabulary of 2,048.

The yardstick is ``benchmarks/models/ouro.py``'s ``Reference``: the
whole sequence at once, the passes as many full forwards, the exit rule
evaluated; it imports nothing of the program.
"""

import json
import os
import struct
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.models import transformer_lm as T
from brpc_tpu.ops import quant, span_attention
from brpc_tpu.streaming import StreamOptions

PAGE = 16
PAGES = 33                       # logical pages of the tests' pools


def _bench(name="tests/toy_ouro/config.json"):
    from benchmarks.harness import spec
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, name))
    return cfg, spec.load_module("models", cfg["model"])


def _model(passes=4):
    """``(file, module, LMConfig, params)`` of the toy configuration
    with ``passes`` passes, weights float32 (the benchmark's are
    bfloat16: widened once, so that float32 arithmetic is exact on both
    sides)."""
    cfg, m = _bench()
    cfg = {**cfg, "total_ut_steps": passes}
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    m.make_params(cfg, 3))
    return cfg, m, T.LMConfig(remat=False, **m.lm_kwargs(cfg)), params


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture
def f32_matmuls(monkeypatch):
    """Every matmul of the serving path in float32: the paged path and
    the reference then differ by summation order alone."""
    monkeypatch.setattr(quant, "qmatmul", lambda x, w: x @ w)
    monkeypatch.setattr(quant, "mxu_operand", lambda x: x)


def _fill_program(lm):
    """The span fill of ``lm``; of a schedule of ONE pass (which the
    engine fills through ``make_prefill`` + ``insert``) the same loop
    over as many spans, built from the looped form's own parts, so that
    one yardstick holds 1, 2 and 4 passes."""
    if lm.passes > 1:
        return T.make_paged_span_fill(lm, PAGE)
    return T._looped_span_fill(lm, PAGE)


class _Paged:
    """One session in slot 1 of 2: the prompt in spans of ``span`` rows
    (the configuration's where none is given), then steps."""

    def __init__(self, lm, params, ctx):
        self.lm, self.params = lm, params
        self.pps = lm.max_seq // PAGE
        _prefill, step = T.make_paged_batch_decode(lm, PAGE)
        fill = jax.jit(_fill_program(lm))
        self._step = jax.jit(step)
        self.cache = T.empty_paged_cache(lm, PAGES, 2, PAGE)
        self.bt = np.zeros((2, self.pps), np.int32)
        self.bt[1] = 1 + np.arange(self.pps)
        w = lm.fill_span
        self.spans = 0
        for start in range(0, len(ctx), w):
            n = min(w, len(ctx) - start)
            ids = np.zeros((w,), np.int32)
            ids[:n] = ctx[start:start + n]
            self.cache = fill(params, self.cache, jnp.asarray(self.bt[1]),
                              np.int32(1), np.int32(start), np.int32(n), ids)
            self.spans += 1

    def feed(self, tok):
        self.cache, logits = self._step(
            self.params, self.cache, jnp.asarray(self.bt),
            jnp.asarray([0, tok], jnp.int32), jnp.asarray([False, True]))
        return np.asarray(logits[1])


def _gaps(model, n_ctx, n_new=8, span=None, spoil=None, seed=0):
    """The paged path's logits against the reference's at every served
    position, in units of the position's logit standard deviation."""
    cfg, m, _lm, params = model
    lm = T.LMConfig(**{"remat": False, **m.lm_kwargs(cfg),
                       **({"fill_span": span} if span else {})})
    rng = np.random.default_rng(seed + n_ctx)
    prompt = rng.integers(0, cfg["vocab_size"], (n_ctx + 1,), dtype=np.int32)
    served = rng.integers(0, cfg["vocab_size"], (n_new,), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        run = _Paged(lm, params, prompt[:-1])
        got = []
        for tok in np.concatenate([prompt[-1:], served[:-1]]):
            if spoil is not None:
                spoil(run)
            got.append(run.feed(tok))
    ref = m.Reference(cfg, params)
    want = ref.served_logits(prompt, served)
    return (np.abs(np.stack(got) - want).max(axis=-1) / want.std(axis=-1),
            run, ref)


# -- (a) the paged step after a span fill is the reference's full forward ------

# float32 on both sides: readings of 1e-6 to 1e-5 at this size.  The
# span is 32 and the page 16: 0 fills nothing, 31, 32, 33 end a span
# with 31, 32 and 1 rows, 70 takes three spans
@pytest.mark.parametrize("passes", [1, 2, 4])
@pytest.mark.parametrize("n_ctx", [0, 31, 33, 70])
def test_span_fill_then_paged_steps_match_the_reference(f32_matmuls, passes,
                                                        n_ctx):
    gaps, run, ref = _gaps(_model(passes), n_ctx)
    assert gaps.max() < 1e-4
    assert run.spans == -(-n_ctx // 32)
    # the exit rule at threshold 1: the last pass, for every row
    assert (ref.exits == passes - 1).all()


def _without(model, what):
    """``model`` with part of the block's mathematics left out of the
    PROGRAM (the reference keeps all of it)."""
    cfg, m, _lm, params = model

    class Cut:
        @staticmethod
        def lm_kwargs(c):
            return {**m.lm_kwargs(c), **what}

        Reference = m.Reference

    return cfg, Cut, None, params


@pytest.mark.parametrize("what", [
    {"passes": 3}, {"post_norms": False}, {"final_norm": False}],
    ids=["a_pass", "the_post_norms", "the_norm_that_closes_a_pass"])
def test_leaving_part_of_the_block_out_fails_the_tolerance(model,
                                                           f32_matmuls,
                                                           what):
    gaps, _run, _ref = _gaps(_without(model, what), 33)
    assert gaps.max() > 1e-2


def test_one_span_and_three_write_the_same_pages(model, f32_matmuls):
    """A prompt of 70 filled as one span of 96 rows and as three of 32:
    the same logits behind it, and the same rows in every pass's
    pages."""
    one, run1, _ = _gaps(model, 70, span=96)
    three, run3, _ = _gaps(model, 70, span=32)
    assert (run1.spans, run3.spans) == (1, 3)
    assert max(one.max(), three.max()) < 1e-4
    for t in range(4):
        rows = slice(t * PAGES + 1, t * PAGES + 1 + 5)     # 78 positions
        np.testing.assert_allclose(
            np.asarray(run1.cache["pk1"][rows]).reshape(80, -1)[:78],
            np.asarray(run3.cache["pk1"][rows]).reshape(80, -1)[:78],
            atol=2e-5)


def test_served_precision_stays_near_the_reference(model):
    """bf16 operands as served: far from float32's agreement, near
    enough that greedy tokens rarely part (the cell's own limits are
    read on the chip: benchmarks/OURO.md)."""
    gaps, _run, _ref = _gaps(model, 33)
    assert 1e-4 < gaps.max() < 0.3


# -- (b) a pass's pages are its own --------------------------------------------

def test_a_pass_attends_its_own_pages_only(model, f32_matmuls, monkeypatch):
    """Poison pass 0's pages of layer 1: of the step's twelve
    attentions those before pass 0's of layer 1 stay and it changes;
    and every attention of pass ``t`` was handed the block table
    shifted by ``t`` pools' worth of pages.  (The step run eagerly with
    the loop of passes as a Python loop, so that each attention's
    operands can be looked at.)"""
    import functools

    from brpc_tpu.ops import paged_attention
    cfg, m, lm, params = model
    rng = np.random.default_rng(5)
    ctx = rng.integers(0, cfg["vocab_size"], (40,), dtype=np.int32)
    seen = []
    plain = paged_attention.attention

    def spy(q, pk, pv, bt, pos, page=None):
        out = plain(q, pk, pv, bt, pos, page)
        seen.append((np.asarray(bt), np.asarray(out)))
        return out

    def attended(poison):
        run = _Paged(lm, params, ctx)
        if poison:
            for name in ("pk1", "pv1"):
                # pass 0's first two pages of the session (the step
                # writes position 40, in its third)
                run.cache[name] = run.cache[name].at[1:3].add(1.0)
        with monkeypatch.context() as mp:
            mp.setattr(paged_attention, "attention", spy)
            mp.setattr(jax.lax, "fori_loop", lambda lo, hi, body, init:
                       functools.reduce(lambda c, t: body(t, c),
                                        range(lo, hi), init))
            del seen[:]
            T.make_paged_batch_decode(lm, PAGE)[1](
                params, run.cache, jnp.asarray(run.bt),
                jnp.asarray([0, 7], jnp.int32), jnp.asarray([False, True]))
        return list(seen)

    clean, dirty = attended(False), attended(True)
    assert len(clean) == len(dirty) == 4 * 3
    for n, ((bt_c, out_c), (_bt, out_d)) in enumerate(zip(clean, dirty)):
        t, layer = divmod(n, 3)
        assert (bt_c[1] == 1 + np.arange(lm.max_seq // PAGE)
                + t * PAGES).all()
        assert (bt_c[0] == t * PAGES).all()    # the pass's garbage page
        same = np.allclose(out_c[1], out_d[1], atol=1e-6)
        # (what pass 0's layer 1 gave feeds everything behind it: only
        # the attentions BEFORE it are held to be the same)
        assert same == ((t, layer) < (0, 1)), (t, layer)


def test_every_pass_reads_pages_of_its_own(model, f32_matmuls):
    """On the logits: whichever pass's pages are poisoned (the first
    two of the session's, in every layer), the step's logits move:
    every pass reads pages of its own."""
    cfg, m, lm, params = model
    rng = np.random.default_rng(6)
    ctx = rng.integers(0, cfg["vocab_size"], (40,), dtype=np.int32)

    def logits(poison_pass=None):
        run = _Paged(lm, params, ctx)
        if poison_pass is not None:
            lo = poison_pass * PAGES + 1
            for i in range(3):
                for kv in "kv":
                    name = f"p{kv}{i}"
                    run.cache[name] = run.cache[name].at[lo:lo + 2].add(1.0)
        return run.feed(7)

    clean = logits()
    for t in range(4):
        assert np.abs(logits(t) - clean).max() > 1e-3


def test_an_idle_slots_pages_and_len_stay(model, f32_matmuls):
    """Slot 0 is idle with pages of a session that left: its rows in
    every pass and its ``len`` are what they were after steps of slot
    1 (an idle slot's row goes to the pass's garbage page)."""
    cfg, m, lm, params = model
    run = _Paged(lm, params, np.arange(20, dtype=np.int32))
    run.bt[0] = 0
    run.cache["len"] = run.cache["len"].at[0].set(9)
    held = np.r_[17:20]                       # pages slot 0 once held
    for name in ("pk0", "pv2"):
        for t in range(4):
            run.cache[name] = run.cache[name].at[held + t * PAGES].set(3.0)
    before = {n: np.asarray(a) for n, a in run.cache.items()}
    for tok in (5, 6, 7):
        run.feed(tok)
    after = {n: np.asarray(a) for n, a in run.cache.items()}
    assert after["len"].tolist() == [9, 23]
    for name in ("pk0", "pv2"):
        for t in range(4):
            np.testing.assert_array_equal(after[name][held + t * PAGES],
                                          before[name][held + t * PAGES])
    # slot 1 wrote positions 20-22 of its page 2 in every pass
    for t in range(4):
        page = after["pk0"][2 + t * PAGES]
        assert np.abs(page[4:7]).min() > 0 and not page[7:].any()


@pytest.mark.parametrize("start,n", [(0, 32), (32, 20), (32, 3), (224, 32)])
def test_a_span_writes_its_own_rows_and_nothing_else(model, f32_matmuls,
                                                     start, n):
    """One span of slot 1 into pools that hold 3.0 everywhere: in every
    pass the session's positions ``start .. start + n - 1`` are
    written, and NOTHING else: the rows past ``n`` of the last live
    page keep what lay there (``span_attention.write`` merges), a page
    wholly past ``n`` and the pass's garbage page stay as they were."""
    cfg, m, lm, params = model
    pps = lm.max_seq // PAGE
    cache = {name: jnp.full_like(a, 3.0) if name != "len" else a
             for name, a in T.empty_paged_cache(lm, PAGES, 2, PAGE).items()}
    bt = 1 + np.random.default_rng(n).permutation(PAGES - 1)[:pps] \
        .astype(np.int32)
    ids = np.arange(lm.fill_span, dtype=np.int32) + 7
    fill = jax.jit(T.make_paged_span_fill(lm, PAGE))
    after = fill(params, cache, jnp.asarray(bt), np.int32(1),
                 np.int32(start), np.int32(n), ids)
    assert after["len"].tolist() == [0, start + n]
    for name in ("pk0", "pv1", "pk2"):
        pool = np.asarray(after[name])
        written = np.zeros(pool.shape[:2], bool)
        for t in range(4):
            for pos in range(start, start + n):
                written[t * PAGES + bt[pos // PAGE], pos % PAGE] = True
        assert (pool[~written] == 3.0).all()
        assert (np.abs(pool[written] - 3.0).max(axis=(-1, -2)) > 0).all()


# -- (c) the span kernel over whole heads --------------------------------------

@pytest.mark.parametrize("paged", [True, False])
@pytest.mark.parametrize("start,w", [(0, 64), (64, 64), (100, 128)])
def test_span_flash_kernel_over_whole_heads(start, w, paged):
    """``span_flash_attention`` (interpreted) against the plain form
    over a whole-head pool ``(pages, page, heads, hd)``: the grouped
    layout with a group of one."""
    rng = np.random.default_rng(start + w)
    heads, hd, pps = 4, 128, 16
    pk = jnp.asarray(rng.standard_normal((40, PAGE, heads, hd)), jnp.float32)
    pv = jnp.asarray(rng.standard_normal((40, PAGE, heads, hd)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((w, heads, hd)), jnp.float32)
    ids = jnp.asarray(rng.permutation(39)[:pps] + 1, jnp.int32)
    want = span_attention.reference(q, pk, pv, ids, start, 0, PAGE)
    got = span_attention.span_flash_attention(q, pk, pv, ids, start, 0,
                                              PAGE, interpret=True,
                                              paged=paged)
    assert np.abs(np.asarray(got - want)).max() < 3e-2      # bf16 operands
    flat = pk.reshape(40, PAGE * heads, hd), pv.reshape(40, PAGE * heads, hd)
    np.testing.assert_array_equal(
        np.asarray(span_attention.reference(q, *flat, ids, start, 0, PAGE)),
        np.asarray(want))


# -- (d) the counts, against hand arithmetic -----------------------------------

def test_counts_against_hand_arithmetic():
    cfg, m = _bench("configs/ouro-2.6b.json")
    layer = 2048 * 6144 + 2048 * 2048 + 2048 * 11264 + 5632 * 2048
    assert m.layer_matmul_params(cfg) == layer == 51_380_224   # 51.38 M
    assert 48 * (layer + 4 * 2048) == 2_466_643_968            # 2,466.6 M
    table = 49152 * 2048
    assert m.table_params(cfg) == table == 100_663_296         # 100.66 M
    total = 48 * (layer + 8192) + 2 * table + 2048 + 2049
    assert m.total_params(cfg) == total == 2_667_974_657       # 2,667.9 M
    assert round(2 * total / 1e7) == 534                       # 5.34 GB
    lm = T.LMConfig(remat=False, **m.lm_kwargs(cfg))
    assert (lm.passes, lm.depth, lm.post_norms, lm.final_norm) \
        == (4, 48, True, True)
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), lm))
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) == total
    token = 4 * 48 * 2 * 16 * 128 * 4
    assert m.token_kv_bytes(cfg) == token == 3_145_728         # 3.1 MB
    svc = cfg["service"]
    assert T.paged_page_bytes(lm, svc["page"]) == 16 * token == 50_331_648
    assert round(196 * 16 * token / 1e7) == 987                # 9.87 GB
    assert 195 * 16 == 3120                                    # tokens held
    assert round((2 * total + 196 * 16 * token) / 1e7) == 1520  # 15.20 GB
    # eight times the one-pass 24-layer block at these widths
    once = T.LMConfig(dim=2048, heads=16, depth=24, max_seq=2048)
    assert T.paged_page_bytes(once, 16) == 16 * 393_216 == 16 * token // 8
    pools = jax.eval_shape(lambda: T.empty_paged_cache(
        lm, svc["kv_pages"], svc["decode_slots"], svc["page"]))
    assert pools["pk47"].shape == (4 * 196, 16, 16, 128)
    assert sum(a.size * 4 for n, a in pools.items() if n[0] == "p") \
        == 196 * 16 * token
    # a max_seq stripe a (pass, layer): what a whole-prompt prefill
    # would hand to insert
    assert 192 * 2 * 2048 * 16 * 128 * 4 == 6_442_450_944      # 6.4 GB
    with pytest.raises(T.UnsupportedBlock, match="6,442,450,944"):
        T.make_prefill(lm)()
    # a step of four rows at 600 live positions each
    lives = [600] * 4
    flops, nbytes = m.step_work(cfg, lives, 1)
    weights = 2 * (4 * 48 * layer + table)
    assert weights == pytest.approx(19.93e9, rel=1e-3)         # 19.9 GB
    assert nbytes == weights + token * (2400 + 4)
    assert flops == 2 * 4 * (4 * 48 * layer + table) \
        + 192 * 4 * 2048 * 2400
    assert m.paged_attn_work(cfg, lives, 1) == (192 * 4 * 2048 * 2400,
                                                token * 2400)
    assert m.kernel_calls(cfg, "paged_decode_attention") == 192
    # the issue's round numbers: ~2,500 live tokens are 7.9 GB a step
    assert token * 2500 == pytest.approx(7.86e9, rel=1e-3)
    # a fill of 256 rows from 512
    f_fill, b_fill = m.fill_work(cfg, 512, 256)
    assert f_fill == 2 * 256 * 4 * 48 * layer \
        + 192 * 4 * 2048 * (256 * 512 + 256 * 257 / 2)
    assert b_fill == 2 * 4 * 48 * layer + token * 768
    assert m.fill_work(cfg, 5, 0) == (0.0, 0.0)


def test_the_fills_counts_against_hand_arithmetic():
    """``kv_stats()["fill"]`` over one cycle of the batch mix at the
    cell's widths (no program runs: the fill is a stub): 20 spans of
    256 rows, 4,600 real rows of 5,120, 288 pages written, and the
    spans' attention fetches the 640 table entries they reach where
    whole tables would be 2,560; ``pages_fetched`` is what the kernel
    is held to by
    ``test_the_span_kernel_reads_the_pages_it_reaches_where_they_lie``."""
    from types import SimpleNamespace

    from benchmarks.harness import spec
    from brpc_tpu.models.lm_service import ContinuousBatcher
    cfg, m = _bench("configs/ouro-2.6b.json")
    mix = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                      "batch.json"))
    prompts = mix["session"]["prompt_len"]["values"]
    assert sorted(prompts) == [128, 256, 384, 512, 640, 768, 896, 1024]
    lm = T.LMConfig(remat=False, **m.lm_kwargs(cfg))
    bat = ContinuousBatcher(lm, {}, page=cfg["service"]["page"])
    bat._span_fill = lambda cache, *_a: cache
    for n in prompts:
        assert bat._fill_spans(
            SimpleNamespace(prompt=np.zeros((n,), np.int32)), 0,
            np.zeros((128,), np.int32), n - 1)
    fill = bat.kv_stats()["fill"]
    assert fill == {"spans": 20, "rows": 4600, "pages_written": 288,
                    "pages_attended": 640, "pages_table": 2560}
    assert 20 * 256 == 5120 and sum(-(-(n - 1) // 16) for n in prompts) == 288
    # 8 spans from row 0, 6 from 256, 4 from 512, 2 from 768
    assert 8 * 16 + 6 * 32 + 4 * 48 + 2 * 64 == 640
    assert bat.kv_stats()["loop"]["fill_spans"] == 20
    # the window schedule at ITS cell's widths gathers what it can
    # reach (sixteen query heads a key/value head: 16 query blocks a
    # span): 322 entries a window layer, the table a global one
    assert not span_attention.in_place(1024, 16)
    assert span_attention.pages_fetched(4096, 1024, 16, 816, 16, 4096) == 322
    assert span_attention.pages_fetched(4096, 1024, 16, 816, 16) == 816
    assert span_attention.pages_fetched(512, 256, 16, 128, 1) == 48


def test_the_toy_has_the_cells_structure():
    toy, m = _bench()
    real, _m = _bench("configs/ouro-2.6b.json")
    a, b = m.lm_kwargs(toy), m.lm_kwargs(real)
    assert set(a) == set(b)
    same = ("passes", "post_norms", "final_norm", "ffn", "rope_theta",
            "norm_eps", "exit_threshold")
    assert {k: a[k] for k in same} == {k: b[k] for k in same}


# -- (e) the exit rule ----------------------------------------------------------

def test_the_exit_rule_against_hand_arithmetic():
    _cfg, m = _bench()
    lams = np.array([[0.5, 0.1, 0.9], [0.5, 0.1, 0.5],
                     [0.5, 0.1, 0.5], [0.5, 0.1, 0.5]])
    # p = (.5 .25 .125 rest), (.1 .09 .081 rest), (.9 .05 .025 rest)
    assert m.exit_pass(lams, 1.0).tolist() == [3, 3, 3]
    assert m.exit_pass(lams, 0.9).tolist() == [3, 3, 0]
    assert m.exit_pass(lams, 0.75).tolist() == [1, 3, 0]
    assert m.exit_pass(lams, 0.1).tolist() == [0, 0, 0]
    # a gate that saturates at 1 in float32 leaves at its pass
    lams[:, 0] = (0.0, 1.0, 0.5, 0.5)
    assert m.exit_pass(lams, 1.0).tolist() == [1, 3, 3]


def test_a_threshold_under_one_raises_by_name():
    cfg, m = _bench()
    with pytest.raises(T.UnsupportedBlock, match="exit_threshold 0.9"):
        T.LMConfig(remat=False, **m.lm_kwargs(
            {**cfg, "early_exit_threshold": 0.9}))


# -- (f) through the batcher ----------------------------------------------------

class _FakeStream:
    def __init__(self):
        self.closed, self.close_reason, self.tokens = False, None, []
        self.id, self._native_tx = 0, None
        self.options = StreamOptions()

    def write(self, data):
        self.tokens.append(struct.unpack("<i", bytes(data))[0])
        return 0

    def close(self, reason=None):
        self.closed, self.close_reason = True, reason


class _Span:
    def __init__(self):
        self.notes = []

    def annotate(self, note):
        self.notes.append(note)

    def finish(self, *_a):
        pass


def test_batcher_serves_the_references_tokens_and_counts_the_loop(
        model, f32_matmuls):
    """Three sessions on two slots (one waits, one slot is reused):
    each is served what the reference decodes greedily;
    ``kv_stats()["loop"]`` counts the layer bodies the steps ran and
    the spans the fills queued; no prefix cache is built; ``LM.Info``,
    the fingerprint and the session span show the passes."""
    from brpc_tpu.models.lm_service import ContinuousBatcher, LMService
    cfg, m, lm, params = model
    ref = m.Reference(cfg, params)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg["vocab_size"], (n,), dtype=np.int32)
               for n in (5, 70, 1)]
    bat = ContinuousBatcher(lm, params, slots=2, page=PAGE, pages=17,
                            idle_linger_s=0.2)
    streams, span = [_FakeStream() for _ in prompts], _Span()
    with jax.default_matmul_precision("highest"):
        for i, (st, p) in enumerate(zip(streams, prompts)):
            bat.join(st, p, 6, span=span if i == 0 else None)
        deadline = time.monotonic() + 240.0
        while not all(s.closed for s in streams) \
                and time.monotonic() < deadline:
            time.sleep(0.002)
    assert [s.close_reason for s in streams] == ["finished"] * 3
    for st, p in zip(streams, prompts):
        toks = np.asarray(st.tokens, np.int32)
        logits = ref.served_logits(p, toks)
        assert (logits.max(axis=-1) - logits[np.arange(6), toks]
                <= 1e-4 * logits.std(axis=-1)).all()
    kv = bat.kv_stats()
    token = 4 * 3 * 2 * 4 * 16 * 4
    assert kv["loop"] == {
        "passes": 4, "layers": 3, "token_bytes": token,
        "steps": kv["steps"], "layer_passes": 12 * kv["steps"],
        # (the prompt of one token has no context to fill)
        "fills": 2, "fill_rows": 4 + 69, "fill_spans": 1 + 3}
    # contexts of 4 and 69 rows: spans from rows 0; 0, 32, 64 write 1;
    # 2, 2, 1 pages and reach 2; 2, 4, 6 entries of a 16-wide table
    assert kv["fill"] == {"spans": 4, "rows": 73, "pages_written": 6,
                          "pages_attended": 14, "pages_table": 64}
    assert kv["prefills_run"] == 2 and "prefix" not in kv
    assert bat._prefix is None
    assert bat._alloc.page_bytes == T.paged_page_bytes(lm, PAGE) \
        == PAGE * token
    assert kv["alloc"]["in_use"] == 0
    assert span.notes[:2] == ["lm_join", "lm_schedule:aaa*4"]
    svc = LMService(cfg=lm, params=params, page=PAGE, decode_slots=2)
    info = json.loads(svc.Info(None, b""))
    assert info["mixers"] == "aaa"
    assert info["loop"] == {"passes": 4, "layers": 3, "post_norms": True,
                            "token_bytes": token, "fill_span": 32}
    assert info["fill"] == {"fill_span": 32, "span_pages": 2,
                            "table_pages": 16}
    assert b":4p1:" in svc.model_fingerprint()
    assert svc.model_fingerprint() != LMService(
        cfg=_model(2)[2], params=params, page=PAGE,
        decode_slots=2).model_fingerprint()


# -- (g) the passes are a loop in the program -----------------------------------

def _count_eqns(jaxpr, pred) -> int:
    """Equations of ``jaxpr`` that ``pred`` holds for, those of its
    inner programs too (a kernel's own body apart)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += bool(pred(eqn))
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += _count_eqns(sub, pred)
    return n


def test_the_passes_are_one_loop_of_the_program(model, monkeypatch):
    """The step and the fill hold ONE body of ``depth`` layers (with
    the kernels chosen, as on the TPU: one call of the decode kernel a
    layer, not a (pass, layer)), iterated by one loop, named
    ``lm_pass``."""
    from brpc_tpu.ops import device_ops
    cfg, m, lm, params = model
    _prefill, step = T.make_paged_batch_decode(lm, PAGE)
    fill = T.make_paged_span_fill(lm, PAGE)
    cache = T.empty_paged_cache(lm, 9, 2, PAGE)
    bt = jnp.zeros((2, lm.max_seq // PAGE), jnp.int32)
    step_args = (params, cache, bt, jnp.zeros((2,), jnp.int32),
                 jnp.asarray([True, False]))
    fill_args = (params, cache, bt[0], np.int32(1), np.int32(0),
                 np.int32(5), np.zeros((lm.fill_span,), np.int32))
    for fn, args in ((step, step_args), (fill, fill_args)):
        text = jax.jit(fn).lower(*args).as_text(debug_info=True)
        assert "lm_pass" in text
        assert text.count("stablehlo.while") == 1

    def kernel(name):
        return lambda eqn: eqn.primitive.name == "pallas_call" and name in (
            str(eqn.params.get("name", ""))
            + str(eqn.params.get("name_and_src_info", "")))

    def loops(eqn):
        return eqn.primitive.name in ("while", "scan")

    # (programs made anew: a trace is kept by function and shapes)
    monkeypatch.setattr(device_ops, "_on_tpu", lambda: True)
    _prefill, step = T.make_paged_batch_decode(lm, PAGE)
    fill = T.make_paged_span_fill(lm, PAGE)
    for fn, args, name in ((step, step_args, "paged_decode_attention"),
                           (fill, fill_args, "span_flash_attention")):
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
        assert _count_eqns(jaxpr, kernel(name)) == 3
        assert _count_eqns(jaxpr, loops) == 1


def _pool_moves(jaxpr, pools):
    """``(pages a gather takes from a pool, index rows of a scatter
    into a pool)`` over the program, inner programs too."""
    gathers, scatters = [], []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            shapes = [getattr(v.aval, "shape", None) for v in eqn.invars]
            if eqn.primitive.name == "gather" and shapes[0] in pools:
                gathers.append(eqn.outvars[0].aval.shape[0])
            if eqn.primitive.name.startswith("scatter") \
                    and shapes[0] in pools:
                scatters.append(shapes[1][0])
            if eqn.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

    walk(jaxpr)
    return gathers, scatters


@pytest.mark.parametrize("block,rows_a_block", [
    ("looped", None), ("window", None), ("window", 64)])
def test_a_span_moves_whole_pages_of_its_own(block, rows_a_block,
                                             monkeypatch):
    """The fill with the kernel chosen, as on the TPU: a pool is
    written by ONE scatter of ``fill_span // page`` pages a layer (not
    ``fill_span`` rows) behind a gather of as many (the merge of the
    partial page), and where the span reads in place nothing else is
    taken from a pool: no operand of the table's keys.  Where a span
    takes several query blocks (the third case: the block shrunk to 64
    rows) a window layer gathers the 6 entries it can reach and the
    global one the table.  Either way ONE program whatever ``start``
    and ``n`` are."""
    from brpc_tpu.ops import device_ops
    if block == "looped":
        cfg, m = _bench()
    else:
        cfg, m = _bench("tests/toy_command_a/config.json")
    lm = T.LMConfig(remat=False, **m.lm_kwargs(cfg))
    params = T.init_params(jax.random.PRNGKey(0), lm)
    pps, pages = lm.max_seq // PAGE, lm.fill_span // PAGE
    cache = T.empty_paged_cache(lm, PAGES, 2, PAGE)
    rows = (jnp.arange(pps, dtype=jnp.int32),) * (2 if lm.has_window else 1)

    def args(start, n):
        return (params, cache, *rows, np.int32(1), np.int32(start),
                np.int32(n), np.zeros((lm.fill_span,), np.int32))

    fill = jax.jit(T.make_paged_span_fill(lm, PAGE))
    for start, n in ((0, 32), (32, 5), (224, 32)):
        fill(*args(start, n))
    assert fill._cache_size() == 1
    monkeypatch.setattr(device_ops, "_on_tpu", lambda: True)
    if rows_a_block:
        monkeypatch.setattr(span_attention, "_BLOCK_ROWS", rows_a_block)
    pools = {a.shape for name, a in cache.items() if name != "len"}
    gathers, scatters = _pool_moves(
        jax.make_jaxpr(T.make_paged_span_fill(lm, PAGE))(*args(32, 5)).jaxpr,
        pools)
    assert scatters == [pages] * (2 * lm.depth)
    wins = sum(map(bool, lm.windows))
    read = [] if not rows_a_block else \
        [6] * (2 * wins) + [pps] * (2 * (lm.depth - wins))
    assert sorted(gathers) == sorted([pages] * (2 * lm.depth) + read)
    assert pages == 2 and pps == 16 and lm.fill_span == 32


def test_chip_span_refuses_the_cpu_and_rehearses_at_toy_widths(
        monkeypatch, tmp_path, capsys):
    """``chip_span.py`` (the span's kernels alone, on the chip): without
    a TPU it says so and returns 2; with the device and the trace's
    reduction faked its whole course runs at toy widths, every kernel
    within its tolerance of the reference, a JSON line a measurement."""
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    import chip_span
    monkeypatch.setattr(sys, "argv", ["chip_span.py", "5"])
    assert chip_span.main() == 2
    assert "needs a TPU" in capsys.readouterr().out

    class Chip:
        platform, device_kind = "tpu", "rehearsal"

    monkeypatch.setattr(chip_span.jax, "devices", lambda: [Chip])
    monkeypatch.setattr(chip_span.xplane, "reduce_trace",
                        lambda *_a, **_k: {"programs": {}})
    monkeypatch.setattr(chip_span.jax.profiler, "start_trace",
                        lambda *_a, **_k: None)
    monkeypatch.setattr(chip_span.jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(chip_span.xplane, "find_xplane", lambda path: path)
    monkeypatch.setattr(chip_span, "ROOT", str(tmp_path))
    monkeypatch.setattr(chip_span, "CALLS", 1)
    monkeypatch.setattr(chip_span, "SHAPES", {
        "ouro": (32, 4, 4, 32, 40, 16, 0, True, (32,)),
        "longdoc_window": (32, 8, 2, 32, 40, 6, 40, False, (64,))})
    monkeypatch.setattr(sys, "argv", [
        "chip_span.py", "5", span_attention.__file__])
    assert chip_span.main() == 0
    lines = [json.loads(x) for x in open(
        tmp_path / "chiprun_out" / "span.jsonl")]
    assert {x["what"] for x in lines} == {
        "kernel", "kernel_bk128", "kernel_bk512", "parent", "write_pages",
        "write_plain", "write_rows"}
    errs = [x["max_err"] for x in lines if x["what"] == "kernel"]
    assert len(errs) == 2 and max(errs) < 2e-2


# -- (h) what declines, by name -------------------------------------------------

def _lm(**kw):
    cfg, m = _bench()
    return T.LMConfig(**{"remat": False, **m.lm_kwargs(cfg), **kw})


def _batcher(**kw):
    from brpc_tpu.models.lm_service import ContinuousBatcher
    return ContinuousBatcher(_lm(), {}, **{"page": PAGE, **kw})


def _generate_declines():
    from brpc_tpu.client.controller import Controller
    from brpc_tpu.models.lm_service import LMService, pack_generate_request
    lm = _lm()
    svc = LMService(cfg=lm, params=T.init_params(jax.random.PRNGKey(1), lm))
    cntl = Controller()
    assert svc.Generate(cntl, pack_generate_request(
        np.zeros((1, 4), np.int32), 2)) is None
    raise T.UnsupportedBlock(cntl.error_text)


def _prefix_declines():
    """No prefix cache is built for a looped schedule, whatever
    ``prefix=`` says: a hit would need the catch-up slice."""
    lm = _lm(max_seq=64)
    bat = _batcher_of(lm, prefix=True, slots=1, pages=5)
    bat._ensure_engine()
    assert bat._prefix is None and "prefix" not in bat.kv_stats()
    raise T.UnsupportedBlock("no prefix cache for a looped schedule")


def _batcher_of(lm, **kw):
    from brpc_tpu.models.lm_service import ContinuousBatcher
    return ContinuousBatcher(lm, T.init_params(jax.random.PRNGKey(1), lm),
                             **{"page": PAGE, **kw})


DECLINES = {
    "exit_threshold_under_one": lambda: _lm(exit_threshold=0.5),
    "training": lambda: T.make_forward(_lm()),
    "train_step": lambda: T.make_train_step(_lm()),
    "contiguous_decode": lambda: T.make_decode(_lm()),
    "whole_prompt_prefill": lambda: T.make_prefill(_lm())(),
    "insert": lambda: T.make_paged_io(_lm(), PAGE)[2]({}, None, None, 0),
    "kv_export_specs": lambda: T.kv_page_specs(_lm()),
    "kv_export": lambda: T.export_decode_cache(_lm(), {}),
    "scan_layers": lambda: T.init_params(jax.random.PRNGKey(0),
                                         _lm(scan_layers=True)),
    "host_spill": lambda: T.make_paged_io(_lm(), PAGE)[0]({}, None),
    "host_resume": lambda: T.make_paged_io(_lm(), PAGE)[1]({}, None, None),
    "catch_up": lambda: T.make_paged_io(_lm(), PAGE, chunk=8)[3](),
    "riding_step": lambda: T.make_paged_batch_decode(
        _lm(), PAGE, chunk=8)[2](),
    "batcher_park": lambda: _batcher(host_slots=4),
    "batcher_chunked": lambda: _batcher(prefill_chunk_tokens=16),
    "kv_import": lambda: _batcher().join_imported(None, 0, 4, 2, {}),
    "generate": _generate_declines,
    "prefix_cache": _prefix_declines,
    # more than one pass, or the post-norms, beside another layer
    "passes_beside_ssm": lambda: T.LMConfig(
        depth=2, mixers=("attn", "ssm"), passes=2),
    "passes_beside_kda": lambda: T.LMConfig(
        depth=2, mixers=("attn", "kda"), passes=2),
    "passes_beside_latent": lambda: T.LMConfig(
        depth=2, mixers=("mla", "mla"), rope=False, passes=2,
        kv_lora_rank=8, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8),
    "passes_beside_windows": lambda: T.LMConfig(
        depth=2, windows=(8, 0), kv_heads=1, passes=2),
    "passes_beside_experts": lambda: T.LMConfig(
        depth=2, ffns=("dense", "experts"), expert_dim=8, experts_routed=4,
        experts_top_k=2, passes=2),
    "passes_over_grouped_heads": lambda: T.LMConfig(
        depth=2, kv_heads=1, passes=2),
    "post_norms_in_the_parallel_block": lambda: T.LMConfig(
        depth=2, parallel_block=True, post_norms=True),
}


@pytest.mark.parametrize("path", sorted(DECLINES))
def test_unported_paths_decline_by_name(path):
    with pytest.raises(T.UnsupportedBlock) as err:
        DECLINES[path]()
    assert str(err.value)


def test_the_declines_name_the_loop():
    with pytest.raises(T.UnsupportedBlock, match="looped schedule"):
        T.make_decode(_lm())
    with pytest.raises(T.UnsupportedBlock, match="4 passes.*bytes"):
        T.make_paged_io(_lm(), PAGE)[2]({}, None, None, 0)
