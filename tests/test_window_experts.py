"""Window layers with rotary beside global layers without, in a
parallel block whose feed-forward is sigmoid-routed experts beside
averaged shared ones (``LMConfig.windows`` / ``ropes`` /
``parallel_block``, ``"experts"`` beside ``"attn"``), at toy widths
with the structure of the benchmark's ``command-a-plus``: one period of
three window layers and a global one, 8 query heads on 2 key/value
heads of 16, a window of 40 (NOT a multiple of the page), 16 routed
experts of which this program holds 8, 4 a token, two shared.

The yardstick is ``benchmarks/models/command_a.py``'s ``Reference``:
the whole sequence at once, attention a masked softmax, the expert
layer a plain loop; it imports nothing of the program.
"""

import hashlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.kv.pages import KvPageError, PageAllocator, WindowTable
from brpc_tpu.models import moe
from brpc_tpu.models import transformer_lm as T
from brpc_tpu.ops import paged_attention, quant, span_attention

PAGE = 16


def _bench(name="tests/toy_command_a/config.json"):
    from benchmarks.harness import spec
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, name))
    return cfg, spec.load_module("models", cfg["model"])


@pytest.fixture(scope="module")
def model():
    """``(file, module, LMConfig, params)`` of the toy configuration,
    weights float32 (the benchmark's are bfloat16: widened once, so
    that float32 arithmetic is exact on both sides)."""
    cfg, m = _bench()
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    m.make_params(cfg, 3))
    return cfg, m, T.LMConfig(remat=False, **m.lm_kwargs(cfg)), params


@pytest.fixture
def f32_matmuls(monkeypatch):
    """Every matmul of the serving path in float32: the paged path and
    the reference then differ by summation order alone."""
    monkeypatch.setattr(quant, "qmatmul", lambda x, w: x @ w)
    monkeypatch.setattr(quant, "mxu_operand", lambda x: x)


class _Paged:
    """One session in slot 1 of 2 through the two page classes: the
    prompt in spans, then steps; the window class's row moved before
    every program as the batcher moves it, and CHECKED: every page a
    program reaches is held at the generation it was taken at."""

    def __init__(self, lm, params, ctx):
        self.lm, self.params = lm, params
        self.pps = lm.max_seq // PAGE
        _prefill, step = T.make_paged_batch_decode(lm, PAGE)
        fill = jax.jit(T.make_paged_span_fill(lm, PAGE))
        self._step = jax.jit(step)
        self.cache = T.empty_paged_cache(lm, 2 * self.pps + 1, 2, PAGE)
        self.bt = np.zeros((2, self.pps), np.int32)
        self.bt[1] = 1 + np.arange(self.pps)
        self.wt = WindowTable(
            PageAllocator(lm.window_pages(2, PAGE), PAGE), 2, self.pps)
        self.pos = len(ctx)
        self.held_most = 0
        w, win = lm.fill_span, lm.window
        for start in range(0, len(ctx), w):
            n = min(w, len(ctx) - start)
            self._cover(max(0, start - win + 1), start + n - 1)
            ids = np.zeros((w,), np.int32)
            ids[:n] = ctx[start:start + n]
            self.cache = fill(params, self.cache, jnp.asarray(self.bt[1]),
                              jnp.asarray(self.wt.bt[1].copy()),
                              np.int32(1), np.int32(start), np.int32(n), ids)

    def _cover(self, first, last):
        assert self.wt.cover(1, first, last)
        self.wt.check(1, first, last)

    def feed(self, tok):
        self._cover(max(0, self.pos - self.lm.window + 1), self.pos)
        self.held_most = max(self.held_most, self.wt.held(1))
        self.cache, logits, counts = self._step(
            self.params, self.cache,
            jnp.asarray(np.stack([self.bt, self.wt.bt])),
            jnp.asarray([0, tok], jnp.int32), jnp.asarray([False, True]))
        self.pos += 1
        return np.asarray(logits[1]), np.asarray(counts)


def _gaps(model, n_ctx, n_new=10, spoil=None, seed=0):
    """The paged path's logits against the reference's at every served
    position, in units of the position's logit standard deviation."""
    cfg, m, lm, params = model
    rng = np.random.default_rng(seed + n_ctx)
    prompt = rng.integers(0, cfg["vocab_size"], (n_ctx + 1,), dtype=np.int32)
    served = rng.integers(0, cfg["vocab_size"], (n_new,), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        run = _Paged(lm, params, prompt[:-1])
        got = []
        for tok in np.concatenate([prompt[-1:], served[:-1]]):
            if spoil is not None:
                spoil(run)
            got.append(run.feed(tok)[0])
    want = m.Reference(cfg, params).served_logits(prompt, served)
    return np.abs(np.stack(got) - want).max(axis=-1) / want.std(axis=-1), run


# (a) float32 on both sides: readings 2e-6 to 6e-6 at this size.  The
# window is 40 and the page 16: contexts of 0-39 never close it, 40
# reaches it with the first token, 64-199 lie 2-10 pages past it; 31,
# 32, 33 and 65 end a span with 31, 32, 1 and 1 rows
@pytest.mark.parametrize("n_ctx", [0, 1, 31, 32, 33, 39, 40, 65, 129, 199])
def test_spans_then_paged_steps_match_the_reference(model, f32_matmuls,
                                                    n_ctx):
    gaps, _run = _gaps(model, n_ctx)
    assert gaps.max() < 1e-4


def test_decode_across_the_windows_edge_and_three_pages_on(model,
                                                           f32_matmuls):
    gaps, run = _gaps(model, 30, n_new=70)          # 30 -> 100
    assert gaps.max() < 1e-4
    assert run.wt.released >= 3


@pytest.mark.parametrize("what", ["window_one_short", "rotate_global",
                                  "rows_in_bf16"])
def test_a_wrong_window_rotation_or_precision_fails_the_tolerance(
        model, f32_matmuls, what):
    cfg, m, lm, params = model
    if what == "rows_in_bf16":
        def spoil(run):
            for k in run.cache:
                if k.startswith("pk") or k.startswith("pv"):
                    run.cache[k] = run.cache[k].astype(jnp.bfloat16) \
                        .astype(jnp.float32)
        gaps, _ = _gaps(model, 65, spoil=spoil)
    else:
        kw = m.lm_kwargs(cfg)
        if what == "window_one_short":
            kw["windows"] = tuple(max(w - 1, 0) for w in kw["windows"])
        else:
            kw["ropes"] = (True,) * len(kw["ropes"])
        gaps, _ = _gaps((cfg, m, T.LMConfig(remat=False, **kw), params), 65)
    assert gaps.max() > 1e-3


# -- (b) the shares add up ----------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(f32_matmuls):
    """The parts all 8 shares of the expert layer give (2 of 16 experts
    each), the averaged shared experts counted once, are the uncut
    reference's layer."""
    cfg, m = _bench()
    d, e, sh = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_shared_experts"]
    routed, k = cfg["num_experts_published"], cfg["num_experts_per_tok"]
    whole = moe.init_served(jax.random.PRNGKey(2), moe.ExpertConfig(
        d, e, routed, (0, routed), k, shared=sh, bias=False))
    assert "bias" not in whole
    t = jax.random.normal(jax.random.PRNGKey(3), (24, d), jnp.float32)
    want = m._experts(t, whole, dict(cfg, num_experts=routed), False)
    none = dict(whole, ws1=jnp.zeros_like(whole["ws1"]))
    parts = []
    for lo in range(0, routed, 2):
        ec = moe.ExpertConfig(d, e, routed, (lo, lo + 2), k, shared=sh,
                              bias=False, shared_scale=1.0 / sh)
        mine = dict(none if lo else whole, w1=whole["w1"][lo:lo + 2],
                    w2=whole["w2"][lo:lo + 2])
        out, counts = moe.serve(mine, t, ec)
        parts.append(out)
        assert int(counts[0]) <= 24 * 2 and int(counts[1]) <= 2
    np.testing.assert_allclose(sum(parts), want, atol=2e-5)


def test_router_without_a_bias_against_hand_arithmetic():
    d, routed, k = 4, 6, 2
    router = jnp.asarray(np.arange(d * routed, dtype=np.float32)
                         .reshape(d, routed) / 10.0 - 1.0)
    t = jnp.asarray([[1.0, -1.0, 0.5, 0.25]], jnp.float32)
    ec = moe.ExpertConfig(d, 8, routed, (0, routed), k, bias=False)
    ids, w = moe.route({"router": router}, t, ec)
    sc = 1.0 / (1.0 + np.exp(-(np.asarray(t) @ np.asarray(router))))[0]
    best = np.argsort(-sc)[:k]
    assert sorted(np.asarray(ids)[0]) == sorted(best)
    np.testing.assert_allclose(np.sort(np.asarray(w)[0]),
                               np.sort(sc[best] / sc[best].sum()), rtol=1e-6)


@pytest.mark.parametrize("parallel", [False, True])
def test_experts_beside_attention_without_windows(f32_matmuls, parallel):
    """``"experts"`` beside ``"attn"`` where no layer has a window: the
    whole-prompt prefill, ``insert`` and the paged step serve it (the
    sequential block and the parallel one), and the step's logits are
    the prefill's at the same position."""
    lm = T.LMConfig(
        vocab=97, dim=32, heads=4, kv_heads=2, head_dim=16, depth=2,
        max_seq=64, remat=False, ffn="gated_silu", ffn_dim=24,
        tie_embed=True, final_norm=True, parallel_block=parallel,
        ffns=("dense", "experts"), expert_dim=8, experts_routed=8,
        experts_held=(2, 6), experts_top_k=2, shared_experts=2,
        shared_average=True, router_bias=False)
    assert not lm.has_window and not lm.plain_block()
    params = T.init_params(jax.random.PRNGKey(4), lm)
    assert ("ln2" in params["blk0"]) != parallel
    assert "bias" not in params["blk1"]["moe"]
    ids = np.random.default_rng(1).integers(0, 97, (16,), dtype=np.int32)
    prefill, step = T.make_paged_batch_decode(lm, PAGE)
    insert = T.make_paged_io(lm, PAGE)[2]
    with jax.default_matmul_precision("highest"):
        cache1, _ = jax.jit(prefill)(params, ids[None], jnp.int32(9))
        _, want = jax.jit(prefill)(params, ids[None], jnp.int32(10))
        cache = T.empty_paged_cache(lm, 9, 2, PAGE)
        bt = np.zeros((2, 4), np.int32)
        bt[1] = 1 + np.arange(4)
        cache = jax.jit(insert)(cache, jnp.asarray(bt[1]), cache1,
                                jnp.int32(1))
        cache["len"] = cache["len"].at[1].set(9)
        cache, got, counts = jax.jit(step)(
            params, cache, jnp.asarray(bt), jnp.asarray([0, ids[9]]),
            jnp.asarray([False, True]))
    np.testing.assert_allclose(got[1], want[0], atol=2e-5)
    assert int(counts[0]) <= 2 and int(counts[1]) <= 2


@pytest.mark.parametrize("start,n", [(0, 32), (64, 20), (64, 3), (224, 32)])
def test_a_span_writes_its_own_rows_and_nothing_else(model, f32_matmuls,
                                                     start, n):
    """One span of slot 1 into pools that hold 3.0 everywhere, both
    page classes: the session's positions ``start .. start + n - 1``
    are written through the class's row and NOTHING else: the rows
    past ``n`` of the last live page keep what lay there, a page
    wholly past ``n`` and the garbage pages stay as they were."""
    cfg, m, lm, params = model
    pps = lm.max_seq // PAGE
    cache = {name: jnp.full_like(a, 3.0) if name != "len" else a
             for name, a in T.empty_paged_cache(
                 lm, 2 * pps + 1, 2, PAGE).items()}
    r = np.random.default_rng(n)
    bt = 1 + r.permutation(2 * pps)[:pps].astype(np.int32)
    n_win = cache["pk0"].shape[0]
    btw = np.zeros((pps,), np.int32)
    first = max(0, start - lm.window + 1) // PAGE
    last = (start + n - 1) // PAGE
    btw[first:last + 1] = 1 + r.permutation(n_win - 1)[:last + 1 - first]
    ids = np.arange(lm.fill_span, dtype=np.int32) + 7
    fill = jax.jit(T.make_paged_span_fill(lm, PAGE))
    after = fill(params, cache, jnp.asarray(bt), jnp.asarray(btw),
                 np.int32(1), np.int32(start), np.int32(n), ids)
    assert after["len"].tolist() == [0, start + n]
    kvh = lm.kv_heads
    for i, row in ((0, btw), (3, bt)):
        assert bool(lm.windows[i]) == (row is btw)
        for name in (f"pk{i}", f"pv{i}"):
            pool = np.asarray(after[name])
            pool = pool.reshape(pool.shape[0], PAGE, kvh, -1)
            written = np.zeros(pool.shape[:2], bool)
            for pos in range(start, start + n):
                written[row[pos // PAGE], pos % PAGE] = True
            assert (pool[~written] == 3.0).all()
            assert (np.abs(pool[written] - 3.0).max(axis=(-1, -2)) > 0).all()


# -- (c) the kernels, interpreted ----------------------------------------------

# ``(kvh, window, wave_pages, pos)``.  The first four are a wave a slot
# (16 pages of 2-4 KB fit one); the others shrink the wave, as
# ``tests/test_paged_attention.py``'s ``wave_pages`` cases do, so that
# the ring of ``paged_attention._WINDOW_RING`` (6) buffers turns: the
# copies started five waves ahead cross slots' ends, windows' first
# pages and the last slot's end
WINDOW_DECODE_CASES = {
    "kvh2_w40": (2, 40, None, [0, 15, 16, 39, 40, 41, 100, 255]),
    "kvh2_global": (2, 0, None, [0, 15, 16, 39, 40, 41, 100, 255]),
    "kvh4_w48": (4, 48, None, [3, 47, 48, 49, 200, 254, 31, 32]),
    "kvh1_w24": (1, 24, None, [0, 23, 24, 25, 64, 65, 128, 250]),
    # waves of 2 pages: slots of 1, 2, ring - 1, ring and ring + 1
    # waves following each other, a last slot shorter than the ring
    "global_around_the_ring": (2, 0, 2, [20, 40, 150, 180, 210, 5]),
    "global_around_the_ring_falling": (2, 0, 2, [210, 180, 150, 40, 20]),
    # fourteen slots of one wave: the ring wraps twice across slots
    "global_one_wave_slots": (1, 0, 2, [0, 31, 16, 3, 30, 1, 17, 2, 29, 15,
                                        4, 18, 28, 0]),
    # sixteen waves in a slot, then one
    "global_one_page_waves": (4, 0, 1, [0, 255, 15, 16, 100]),
    # a window of 88 over waves of one page: 1, 2, 5, 6, 6 and 7 waves,
    # the last two from a first page behind which the table reads 0
    "window_around_the_ring": (2, 88, 1, [5, 20, 70, 80, 200, 208, 3]),
    # the same slots without the window: 1, 2, 5, 6, 13, 14, 1 waves
    "global_beside_that_window": (2, 0, 1, [5, 20, 70, 80, 200, 208, 3]),
    # a window that starts mid-page (position 61: row 13 of page 3)
    # after a slot with no window page behind it, and the other way
    "window_mid_page_after_none_behind": (2, 40, 1,
                                          [10, 100, 7, 255, 39, 40]),
    "kvh4_w48_two_page_waves": (4, 48, 2, [3, 47, 48, 49, 200, 254, 31, 32]),
}


def test_the_shrunk_waves_turn_the_ring():
    """The cases named for it hold slots of 1, 2, ring - 1, ring and
    ring + 1 waves: a deeper or shallower ring needs other cases."""
    ring = paged_attention._WINDOW_RING
    for name in ("global_around_the_ring", "global_around_the_ring_falling",
                 "window_around_the_ring"):
        _kvh, window, wave_pages, pos = WINDOW_DECODE_CASES[name]
        first = [max(0, p - window + 1) // PAGE if window else 0 for p in pos]
        waves = {-(-(p // PAGE + 1 - f) // wave_pages)
                 for p, f in zip(pos, first)}
        assert {1, 2, ring - 1, ring, ring + 1} <= waves, (name, waves)


@pytest.mark.parametrize("name", list(WINDOW_DECODE_CASES))
def test_window_decode_kernel(name, monkeypatch):
    """16 query heads on each key/value head; the walk starts at the
    window's first page and masks its head.  Operands bfloat16."""
    check_window_decode_kernel(*WINDOW_DECODE_CASES[name], 16, monkeypatch)


def check_window_decode_kernel(kvh, window, wave_pages, pos, g, monkeypatch):
    """(``g`` query heads a key/value head:
    ``tests/test_early_routed_experts.py`` runs these cases at 7.)"""
    r = np.random.default_rng(kvh)
    slots, hd, pps, pages = len(pos), 32, 16, 40
    q = jnp.asarray(r.normal(size=(slots, g * kvh, hd)), jnp.float32)
    pk, pv = (jnp.asarray(r.normal(size=(pages, PAGE * kvh, hd)),
                          jnp.float32) for _ in range(2))
    bt = np.asarray(1 + r.integers(0, pages - 1, (slots, pps)), np.int32)
    # what lies behind the window has been given back and what lies
    # past the last live page was never taken: the kernel must not need
    # those entries (a copy started waves ahead least of all), and page
    # 0 holds NaN here
    pk, pv = pk.at[0].set(jnp.nan), pv.at[0].set(jnp.nan)
    for s, p in enumerate(pos):
        if window:
            bt[s, :max(0, p - window + 1) // PAGE] = 0
        bt[s, p // PAGE + 1:] = 0
    pos = jnp.asarray(pos, jnp.int32)
    kernel = paged_attention.window_decode_attention
    if wave_pages:
        monkeypatch.setattr(paged_attention, "_WAVE_BYTES",
                            wave_pages * PAGE * kvh * hd * 4)
        assert paged_attention.pages_per_wave(PAGE, kvh, hd, pps) \
            == wave_pages

        # traced here and now: a cached trace keeps the wave it was
        # traced with
        kernel = jax.jit(paged_attention._window_call.__wrapped__,
                         static_argnames=("page", "window", "interpret"))
    got = kernel(q, pk, pv, jnp.asarray(bt), pos, page=PAGE, window=window,
                 interpret=True)
    clean = lambda p: jnp.nan_to_num(p)                   # noqa: E731
    want = paged_attention.reference(q, clean(pk), clean(pv),
                                     jnp.asarray(bt), pos, PAGE, window)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=2e-2)


@pytest.mark.parametrize("paged", [True, False])
@pytest.mark.parametrize("window,start,w,keys_from", [
    (40, 0, 32, 0), (40, 64, 32, 16), (40, 192, 32, 144), (0, 0, 32, 0),
    (0, 96, 32, 0), (24, 48, 16, 16)])
def test_span_flash_kernel(window, start, w, keys_from, paged):
    """Both forms of the kernel: the keys read where they lie, and
    gathered once."""
    check_span_flash_kernel(window, start, w, keys_from, paged, 4)


def check_span_flash_kernel(window, start, w, keys_from, paged, g):
    r = np.random.default_rng(start + window)
    kvh, hd, pages = 2, 32, 40
    q = jnp.asarray(r.normal(size=(w, kvh * g, hd)), jnp.float32)
    pk, pv = (jnp.asarray(r.normal(size=(pages, PAGE * kvh, hd)),
                          jnp.float32) for _ in range(2))
    n_pages = 6 if window else 16
    ids = jnp.asarray(1 + r.integers(0, pages - 1, (n_pages,)), jnp.int32)
    args = (q, pk, pv, ids, jnp.int32(start), jnp.int32(keys_from), PAGE,
            window)
    got = span_attention.span_flash_attention(
        *args, block_q=8, block_k=32, interpret=True, paged=paged)
    want = span_attention.reference(*args)
    np.testing.assert_allclose(got, want, atol=2e-2)


@pytest.mark.parametrize("blocks", [(8, 32), (None, None)])
@pytest.mark.parametrize("whole", [False, True])
@pytest.mark.parametrize("window,start", [
    (0, 0), (0, 32), (0, 224), (40, 0), (40, 32), (40, 224)])
def test_the_span_kernel_reads_the_pages_it_reaches_where_they_lie(
        window, start, whole, blocks):
    """A span of 32 rows at the table's start, one span in and at its
    end (``max_seq`` 256), both layouts: the table in scrambled order,
    every entry ahead of the span's last row or behind the window's
    reach naming page 0, which holds NaN.  ``pages_reached`` names
    exactly the entries the kernel fetches: one of them on page 0
    poisons the result."""
    r = np.random.default_rng(start + window)
    w, kvh, g, hd, pages, pps = 32, 2, 1 if whole else 4, 32, 40, 16
    dims = (pages, PAGE, kvh, hd) if whole else (pages, PAGE * kvh, hd)
    q = jnp.asarray(r.normal(size=(w, kvh * g, hd)), jnp.float32)
    pk, pv = (jnp.asarray(r.normal(size=dims), jnp.float32).at[0].set(
        jnp.nan) for _ in range(2))
    first, end = span_attention.pages_reached(start, w, PAGE, window)
    ids = np.zeros((pps,), np.int32)
    ids[first:end] = 1 + r.permutation(pages - 1)[:end - first]
    bq, bk = blocks

    def kernel(table):
        return span_attention.span_flash_attention(
            q, pk, pv, jnp.asarray(table), jnp.int32(start), jnp.int32(0),
            PAGE, window, block_q=bq, block_k=bk, interpret=True,
            paged=True)

    got = kernel(ids)
    want = span_attention.reference(
        q, jnp.nan_to_num(pk), jnp.nan_to_num(pv), jnp.asarray(ids),
        jnp.int32(start), jnp.int32(0), PAGE, window)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=2e-2)
    for lost in (first, end - 1):
        assert not bool(jnp.isfinite(kernel(
            np.where(np.arange(pps) == lost, 0, ids))).all())


@pytest.mark.parametrize("form", ["write_plain", "span_page_write"])
@pytest.mark.parametrize("whole", [False, True])
@pytest.mark.parametrize("n", [32, 27, 16, 3, 0])
def test_whole_pages_hold_what_the_rows_scatter_wrote(n, whole, form):
    """Both forms of ``span_attention.write`` (XLA's merge, and the
    kernel's copies, interpreted) against a scatter of the ``n`` real
    rows one at a time: the same pool everywhere: a row past ``n`` in
    the last live page keeps what lay there, a page wholly past ``n``
    names the garbage page, which stays as it was."""
    r = np.random.default_rng(n)
    w, kvh, hd, pages = 32, 2, 32, 12
    dims = (pages, PAGE, kvh, hd) if whole else (pages, PAGE * kvh, hd)
    pool = jnp.asarray(r.normal(size=dims), jnp.float32)
    rows = jnp.asarray(r.normal(size=(w, kvh, hd)), jnp.float32)
    table = np.asarray([7, 3], np.int32)
    mine = np.where(np.arange(2) * PAGE < n, table, 0)
    kw = {"interpret": True} if form == "span_page_write" else {}
    got = np.asarray(getattr(span_attention, form)(
        pool, rows, jnp.asarray(mine), jnp.int32(n), PAGE, **kw))
    want = np.asarray(pool).reshape(pages, PAGE, kvh, hd).copy()
    for j in range(n):
        want[table[j // PAGE], j % PAGE] = np.asarray(rows[j])
    np.testing.assert_array_equal(got, want.reshape(dims))


def test_on_the_tpu_whole_tiles_are_written_by_the_kernel(monkeypatch):
    """``span_attention.write`` with the TPU's choice made: rows of
    whole tiles (8 key/value heads of 128) go through
    ``span_page_write`` and nothing is gathered or scattered; narrower
    rows (the toys') take XLA's merge."""
    from brpc_tpu.ops import device_ops
    monkeypatch.setattr(device_ops, "_on_tpu", lambda: True)

    def prims(kvh, hd):
        pool = jnp.zeros((6, PAGE * kvh, hd), jnp.float32)
        rows = jnp.zeros((32, kvh, hd), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda pool, rows, ids, n: span_attention.write(
                pool, rows, ids, n, PAGE))(
            pool, rows, jnp.zeros((2,), jnp.int32), jnp.int32(20))
        return str(jaxpr)

    text = prims(8, 128)
    assert "span_page_write" in text
    assert "scatter" not in text and "gather" not in text
    text = prims(2, 16)
    assert "span_page_write" not in text and "scatter" in text


# -- (d) the window class gives pages back ----------------------------------------

def test_window_pages_return_as_the_context_grows():
    alloc = PageAllocator(64, PAGE)
    wt = WindowTable(alloc, 2, 16)
    window, most = 40, 40 // PAGE + 2
    free0 = alloc.free_pages()
    seen = set()
    for p in range(0, 250):
        assert wt.cover(1, max(0, p - window + 1), p)
        wt.check(1, max(0, p - window + 1), p)
        assert wt.held(1) <= most
        assert alloc.in_use() == wt.held(1)
        seen.update(int(x) for x in wt.bt[1] if x)
    assert wt.released == 250 // PAGE - (wt.held(1) - 1) and wt.released > 10
    assert len(seen) < 16              # pages came back and went out again
    # a page that was given back is not read again: its entry is 0 and
    # its generation has moved on
    assert (wt.bt[1, :wt.lo[1]] == 0).all()
    with pytest.raises(KvPageError):
        wt.check(1, 0, 249)
    stale = int(wt.bt[1, wt.lo[1]])
    alloc.release(stale)               # behind the table's back
    with pytest.raises(KvPageError):
        wt.check(1, 249 - window + 1, 249)
    assert alloc.alloc(1) == [stale]   # the same page, a generation on
    with pytest.raises(KvPageError):
        wt.check(1, 249 - window + 1, 249)
    wt.release_slot(1)
    assert alloc.free_pages() == free0 and wt.held(1) == 0
    assert (wt.bt == 0).all()


def test_the_window_class_refuses_rather_than_overcommits():
    wt = WindowTable(PageAllocator(4, PAGE), 2, 16)       # 3 pages
    assert wt.cover(0, 0, 40)
    assert not wt.cover(1, 0, 15) and wt.held(1) == 0
    assert wt.cover(0, 32, 63) and wt.held(0) == 2
    assert wt.cover(1, 0, 15)


def test_batcher_gives_pages_back_and_counts_it(model, f32_matmuls):
    """Four sessions on two slots through ``ContinuousBatcher``: the
    tokens are the reference's best, a slot never holds more than
    ``window // page + 2`` pages of the window class while it decodes,
    every page is back at the end, and ``kv_stats()["window"]`` says
    what was held against what whole contexts would hold."""
    import struct
    import time

    from brpc_tpu.models.lm_service import ContinuousBatcher
    from brpc_tpu.streaming import StreamOptions

    cfg, m, lm, params = model

    class Stream:
        def __init__(self):
            self.tokens, self.closed, self.reason = [], False, None
            self.options, self._native_tx = StreamOptions(), None

        def write(self, data):
            self.tokens.append(struct.unpack("<i", data)[0])
            return 0

        def close(self, reason=None):
            self.closed, self.reason = True, reason

    bat = ContinuousBatcher(lm, params, slots=2, page=PAGE)
    held = []
    cover = bat._cover_windows

    def watched():
        ok = cover()
        held.extend(bat._wt.held(s) for s in bat._sessions
                    if bat._active[s])
        return ok

    bat._cover_windows = watched
    rng = np.random.default_rng(11)
    jobs = [(rng.integers(0, cfg["vocab_size"], (n,), dtype=np.int32), new)
            for n, new in ((30, 14), (200, 10), (70, 12), (130, 11))]
    streams = [Stream() for _ in jobs]
    with jax.default_matmul_precision("highest"):
        for st, (prompt, new) in zip(streams, jobs):
            bat.join(st, prompt, new)
        deadline = time.monotonic() + 300
        while not all(s.closed for s in streams) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
    assert [s.reason for s in streams] == ["finished"] * 4
    ref = m.Reference(cfg, params)
    for st, (prompt, new) in zip(streams, jobs):
        assert len(st.tokens) == new
        logits = ref.served_logits(prompt, np.asarray(st.tokens, np.int32))
        gaps = (logits.max(axis=-1)
                - logits[np.arange(new), st.tokens]) / logits.std(axis=-1)
        assert gaps.max() < 1e-4
    assert max(held) <= lm.window // PAGE + 2
    stats = bat.kv_stats()
    win = stats["window"]
    assert win["layers"] == 3 and win["window"] == 40
    assert win["pages"] == lm.window_pages(2, PAGE) == 2 * 4 + 2 + 1
    assert win["alloc"]["in_use"] == 0 and stats["alloc"]["in_use"] == 0
    assert win["released"] > 15
    assert 0 < win["pages_held"] < win["pages_whole"]
    assert win["pages_held"] == sum(held)
    assert stats["moe"]["held"] == 8 and stats["moe"]["routed"] == 16
    assert stats["moe"]["steps"] == stats["steps"] > 0
    assert "prefix" not in stats                # the prefix cache declines
    assert stats["prefills_run"] == 4
    # the fills: contexts of 29, 199, 69 and 129 rows in spans of 32;
    # a span from row s reaches (s + 32) / 16 entries of the global
    # table and those from (s - 39) // 16 on of the window class's
    starts = [s for n in (29, 199, 69, 129) for s in range(0, n, 32)]
    assert stats["fill"] == {
        "spans": 16, "rows": 426, "pages_written": 2 + 13 + 5 + 9,
        "pages_attended": sum(2 * (s // 16 + 2) - max(s - 39, 0) // 16
                              for s in starts),
        "pages_table": 16 * 2 * 16}
    assert stats["fill"]["pages_attended"] == 165


# -- (e) the counts ------------------------------------------------------------

def test_counts_against_hand_arithmetic():
    cfg, m = _bench("configs/command-a-plus.json")
    M = 1e6
    assert m.attn_params(cfg) / M == pytest.approx(142.61, abs=0.005)
    assert m.shared_params(cfg) / M == pytest.approx(201.33, abs=0.005)
    assert m.expert_params(cfg) / M == pytest.approx(50.33, abs=0.005)
    assert m.layer_dense_params(cfg) / M == pytest.approx(344.46, abs=0.005)
    assert m.layer_params(cfg) / M == pytest.approx(1149.8, abs=0.05)
    assert m.total_params(cfg) * cfg["weight_bytes"] / 1e9 == \
        pytest.approx(9.47, abs=0.005)
    assert m.kv_token_layer_bytes(cfg) == 8192
    lm = T.LMConfig(remat=False, **m.lm_kwargs(cfg))
    svc = cfg["service"]
    assert lm.head_dim * lm.heads == 16384 != lm.dim
    assert lm.windows == (4096, 4096, 4096, 0)
    assert lm.ropes == (True, True, True, False)
    assert lm.window_pages(svc["decode_slots"], svc["page"]) \
        == 16 * 258 + 64 + 1 == svc["window_pages"]
    # a page of either class pins its layers' rows
    assert T.paged_page_bytes(lm, 16) == 16 * 8192
    assert T.paged_page_bytes(lm, 16, window_class=True) == 3 * 16 * 8192
    shapes = jax.eval_shape(lambda: T.empty_paged_cache(
        lm, svc["kv_pages"], svc["decode_slots"], svc["page"]))
    assert shapes["pk3"].shape == (svc["kv_pages"], 16 * 8, 128)
    assert shapes["pk0"].shape == (svc["window_pages"], 16 * 8, 128)
    pools = sum(a.size * 4 for k, a in shapes.items() if k[0] == "p")
    assert pools / 1e9 == pytest.approx(1.258 + 1.649, abs=0.005)


# -- (f) what declines, by name ------------------------------------------------

def _lm(**kw):
    cfg, m = _bench()
    return T.LMConfig(**{"remat": False, **m.lm_kwargs(cfg), **kw})


def declines(_lm) -> dict:
    """The paths a window schedule declines, by name, for the block
    ``_lm(**kw)`` makes (``tests/test_early_routed_experts.py`` runs
    them for its own)."""
    def _batcher(**kw):
        from brpc_tpu.models.lm_service import ContinuousBatcher
        return ContinuousBatcher(_lm(), {}, **{"page": PAGE, **kw})

    def _generate_declines():
        from brpc_tpu.client.controller import Controller
        from brpc_tpu.models.lm_service import (LMService,
                                                pack_generate_request)
        lm = _lm()
        svc = LMService(cfg=lm,
                        params=T.init_params(jax.random.PRNGKey(1), lm))
        cntl = Controller()
        assert svc.Generate(cntl, pack_generate_request(
            np.zeros((1, 4), np.int32), 2)) is None
        raise T.UnsupportedBlock(cntl.error_text)

    return {
        "training": lambda: T.make_forward(_lm()),
        "contiguous_decode": lambda: T.make_decode(_lm()),
        "whole_prompt_prefill": lambda: T.make_prefill(_lm())(),
        "insert": lambda: T.make_paged_io(_lm(), PAGE)[2]({}, None, None, 0),
        "kv_export_specs": lambda: T.kv_page_specs(_lm()),
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
        # a span fill is a window schedule's alone
        "span_fill_of_another_block": lambda: T.make_paged_span_fill(
            T.LMConfig(depth=2, remat=False), PAGE)(),
        # window layers beside another mixer, or over ungrouped heads
        "windows_beside_ssm": lambda: T.LMConfig(
            depth=2, mixers=("attn", "ssm"), windows=(8, 0), kv_heads=1),
        "windows_over_whole_heads": lambda: T.LMConfig(depth=2, windows=(8, 0)),
    }


DECLINES = declines(_lm)


@pytest.mark.parametrize("path", sorted(DECLINES))
def test_unported_paths_decline_by_name(path):
    with pytest.raises(T.UnsupportedBlock) as err:
        DECLINES[path]()
    assert str(err.value)


def test_the_decline_names_the_window():
    with pytest.raises(T.UnsupportedBlock, match="window"):
        T.make_decode(_lm())


# -- (g) the blocks that were there lower to the programs that were there -------

def _program_hashes(cfg, page):
    """The step's and a 16-token prefill's lowered text, locations
    stripped, hashed (``tests/test_latent_experts.py``'s, which holds
    the first block and the state-layer block to the same parents)."""
    spec = lambda tree: jax.tree_util.tree_map(        # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    params = spec(jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg)))
    cache = spec(jax.eval_shape(
        lambda: T.empty_paged_cache(cfg, 9, 2, page)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)   # noqa: E731
    prefill, step = T.make_paged_batch_decode(cfg, page)
    out = {}
    for name, fn, args in (
            ("step", step, (params, cache, i32(2, cfg.max_seq // page),
                            i32(2), jax.ShapeDtypeStruct((2,), jnp.bool_))),
            ("prefill", prefill, (params, i32(1, 16), i32()))):
        text = jax.jit(fn).lower(*args).as_text()
        text = re.sub(r"\s*loc\([^\n]*\)$", "", text, flags=re.M)
        text = "\n".join(ln for ln in text.splitlines()
                         if not ln.startswith("#loc"))
        out[name] = hashlib.sha256(text.encode()).hexdigest()[:16]
    return out


def _toy_lm(name):
    cfg, m = _bench(name)
    return T.LMConfig(remat=False, **m.lm_kwargs(cfg)), cfg["service"]["page"]


# read at the commit before the window schedule (5a73c40), with this
# function, on this installation
PARENTS = {
    "neox": ("tests/toy/config.json",
             {"step": "c3a86a3d44ab60a5", "prefill": "9712af90e953a25f"}),
    "jamba2-3b": ("tests/toy_jamba/config.json",
                  {"step": "b95629a258e2c88f",
                   "prefill": "66821575d05d1acd"}),
    # read anew at PR 36, which changed the latent layer's products on
    # purpose (``mla_mixer.pack``: these are the programs of a tree as
    # ``init_params`` makes it, packed in the trace), and at PR 38, which
    # changed the expert layer's combine on purpose
    # (``ops/expert_combine.py``: off the TPU its plain form, a gather
    # from the token's side where the buffer's scatter-add stood)
    "kimi-k2.7-code": ("tests/toy_kimi/config.json",
                       {"step": "c04b1284a35f76cf",
                        "prefill": "c26f21a3e42ece05"}),
}


@pytest.mark.parametrize("block", sorted(PARENTS))
def test_accepted_blocks_lower_to_the_parents_text(block):
    name, want = PARENTS[block]
    prev = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    try:
        assert _program_hashes(*_toy_lm(name)) == want
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", prev)


def test_the_window_step_calls_its_kernels_by_layer(model, monkeypatch):
    """On the TPU a window layer's call is ``window_decode_attention``
    and the global layer's ``paged_decode_attention``: three and one in
    the traced step; ``expert_gmm`` twice a layer."""
    from brpc_tpu.ops import device_ops
    _cfg, _m, lm, params = model
    monkeypatch.setattr(device_ops, "_on_tpu", lambda: True)
    _prefill, step = T.make_paged_batch_decode(lm, PAGE)
    pps = lm.max_seq // PAGE
    cache = T.empty_paged_cache(lm, 9, 2, PAGE)
    jaxpr = jax.make_jaxpr(step)(
        params, cache, jnp.zeros((2, 2, pps), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), bool)).jaxpr

    def calls(jaxpr, name) -> int:
        """Kernels of that name in ``jaxpr`` and in every jaxpr its
        equations carry."""
        n = 0
        for eqn in jaxpr.eqns:
            n += eqn.primitive.name == "pallas_call" \
                and eqn.params["name"] == name
            for val in eqn.params.values():
                for sub in val if isinstance(val, (list, tuple)) else (val,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        n += calls(sub, name)
        return n

    cfg = _cfg
    assert [calls(jaxpr, k) for k in (
        "window_decode_attention", "paged_decode_attention",
        "expert_gmm")] == [3, 1, 8] == [_m.kernel_calls(cfg, k) for k in (
            "window_decode_attention", "paged_decode_attention",
            "expert_gmm")]
