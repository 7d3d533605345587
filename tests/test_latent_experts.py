"""Latent attention over a paged latent cache and dropless routed
experts beside a shared one (``LMConfig.mixers`` ``"mla"``,
``LMConfig.ffns`` ``"experts"``), at toy widths with the structure of
the benchmark's ``kimi-k2.7-code``: a leading dense layer and two
expert layers, 4 latent heads, YaRN rotary, 64 routed experts of which
this program holds 4, 4 a token, one shared expert.

The yardstick is ``benchmarks/models/kimi_k2.py``'s ``Reference``: the
whole sequence at once, the EXPANDED attention as a full causal
softmax, the expert layer a plain loop over the held experts; it
imports nothing of the program.
"""

import functools
import hashlib
import json
import math
import os
import re
import struct
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.models import mla_mixer, moe
from brpc_tpu.models import transformer_lm as T
from brpc_tpu.ops import expert_combine as comb
from brpc_tpu.ops import expert_gmm as gmm
from brpc_tpu.ops import paged_attention, quant
from brpc_tpu.streaming import StreamOptions

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 16


def _bench(name="tests/toy_kimi/config.json"):
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
    the reference then differ by summation order alone, which is what
    lets a tolerance catch a latent row kept in bf16."""
    monkeypatch.setattr(quant, "qmatmul", lambda x, w: x @ w)
    monkeypatch.setattr(quant, "mxu_operand", lambda x: x)


class _Paged:
    """One session in slot 1 of 2: bucketed prefill, insert, steps."""

    def __init__(self, lm, params, ctx):
        self.lm, self.params = lm, params
        prefill, step = T.make_paged_batch_decode(lm, PAGE)
        insert = T.make_paged_io(lm, PAGE)[2]
        bucket = 1
        while bucket < max(len(ctx), 1):
            bucket <<= 1
        ids = np.zeros((bucket,), np.int32)
        ids[:len(ctx)] = ctx
        cache1, _ = jax.jit(prefill)(params, ids[None], jnp.int32(len(ctx)))
        self.cache = T.empty_paged_cache(lm, 33, 2, PAGE)
        self.bt = np.zeros((2, lm.max_seq // PAGE), np.int32)
        self.bt[1] = 1 + np.arange(self.bt.shape[1])
        self.cache = jax.jit(insert)(self.cache, jnp.asarray(self.bt[1]),
                                     cache1, jnp.int32(1))
        self.cache["len"] = self.cache["len"].at[1].set(len(ctx))
        self._step = jax.jit(step)

    def feed(self, tok):
        self.cache, logits, counts = self._step(
            self.params, self.cache, jnp.asarray(self.bt),
            jnp.asarray([0, tok], jnp.int32), jnp.asarray([False, True]))
        return np.asarray(logits[1]), np.asarray(counts)


def _gaps(model, n_ctx, spoil=None, seed=0):
    """The paged path's logits against the reference's at every served
    position, in units of the position's logit standard deviation."""
    cfg, m, lm, params = model
    rng = np.random.default_rng(seed + n_ctx)
    prompt = rng.integers(0, 256, (n_ctx + 1,), dtype=np.int32)
    served = rng.integers(0, 256, (10,), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        run = _Paged(lm, params, prompt[:-1])
        got = []
        for tok in np.concatenate([prompt[-1:], served[:-1]]):
            if spoil is not None:
                spoil(run)
            got.append(run.feed(tok)[0])
    want = m.Reference(cfg, params).served_logits(prompt, served)
    return np.abs(np.stack(got) - want).max(axis=-1) / want.std(axis=-1)


# float32 on both sides: readings 3e-6 to 9e-6 at this size; a latent
# row kept in bf16 reads 2e-3 and more
@pytest.mark.parametrize("n_ctx", [0, 1, 15, 16, 17, 31])
def test_prefill_then_paged_steps_match_the_reference(model, f32_matmuls,
                                                      n_ctx):
    assert _gaps(model, n_ctx).max() < 1e-4


def test_bf16_latent_rows_would_fail_the_tolerance(model, f32_matmuls):
    def spoil(run):
        for k in run.cache:
            if k.startswith("pc"):
                run.cache[k] = run.cache[k].astype(jnp.bfloat16) \
                    .astype(jnp.float32)
    assert _gaps(model, 17, spoil).max() > 1e-3


def test_served_precision_stays_near_the_reference(model):
    """As served (bf16 operands): a position's logits lie a few
    hundredths of their standard deviation from the reference's,
    except where the rounding flips a router's choice (a whole expert:
    about 1); so the median is held, and the worst to what a wrong
    formula would pass."""
    gaps = np.concatenate([_gaps(model, n) for n in (1, 16, 31)])
    assert np.median(gaps) < 0.15 and gaps.max() < 3.0, gaps


def test_expanded_and_absorbed_attention_agree(model, f32_matmuls):
    """The bucket's expanded form at its last position against the
    step's absorbed form over the same cached rows."""
    _cfg, _m, lm, params = model
    bp = params["blk1"]
    rng = np.random.default_rng(5)
    s = 16
    x = jnp.asarray(rng.normal(size=(1, s, lm.dim)).astype(np.float32))
    out, latent = mla_mixer.prefill(lm, bp, x, T._rope_at(lm, jnp.arange(s)))
    pages = lm.max_seq // PAGE
    pc = jnp.zeros((pages + 1, PAGE, lm.latent_row_padded()), jnp.float32)
    bt = 1 + jnp.arange(pages)[None]
    pc = pc.at[bt[0]].set(latent[0].reshape(pages, PAGE, -1))
    # the row of position s - 1 is written again by the step
    pos = jnp.asarray([s - 1])
    got, pc2 = mla_mixer.step(lm, bp, x[:, s - 1], pc, bt, pos, pos,
                              T._rope_at(lm, pos[:, None]), PAGE)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(out[0, s - 1]),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(pc2), np.asarray(pc), atol=1e-6)
    assert latent.shape == (1, lm.max_seq, 128) and lm.latent_row() == 40
    assert not np.asarray(latent[0, :s, 40:]).any()        # the padding


# a wave of the kernel is 256 rows (16 pages of 16; 64 pages of 4); its
# ring holds four
MLA_CASES = {
    "three_slots": (3, 4, 16, 8, 4, 8, [0, 13, 31]),
    "more_than_one_wave": (2, 8, 128, 64, 16, 6, [95, 40]),
    "two_waves": (1, 2, 32, 16, 16, 20, [300]),
    "pos_0": (2, 2, 32, 16, 16, 20, [0, 0]),
    "last_row_of_a_wave_and_the_next": (4, 2, 32, 16, 16, 36,
                                        [255, 256, 511, 512]),
    "one_full_wave_beside_one_row": (4, 2, 32, 16, 16, 20,
                                     [255, 0, 255, 0]),
    # slots of one wave each: the copies started three waves ahead
    # cross three slots, and the ring wraps between them
    "ring_across_slots": (7, 2, 32, 16, 16, 18, [3, 270, 17, 0, 100, 5, 257]),
    "more_waves_than_the_ring": (2, 2, 32, 16, 16, 100, [1599, 1300]),
    "real_widths": (2, 64, 512, 64, 16, 20, [300, 40]),
    "table_narrower_than_a_wave": (3, 4, 32, 16, 16, 5, [79, 3, 64]),
}


def _mla_case(name, bf16_operands=False):
    slots, heads, kl, rope, page, pps, pos = MLA_CASES[name]
    r = np.random.default_rng(slots + heads)
    pages = slots * pps + 1
    ql = r.normal(size=(slots, heads, kl)).astype(np.float32)
    qr = r.normal(size=(slots, heads, rope)).astype(np.float32)
    pc = r.normal(size=(pages, page, -(-(kl + rope) // 128) * 128)) \
        .astype(np.float32)
    pc[..., kl + rope:] = 0.0
    scale = (kl + rope) ** -0.5
    if bf16_operands:
        def rounded(a):
            return np.array(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))

        # the kernel rounds the SCALED queries
        ql, qr, pc = rounded(ql * scale) / scale, rounded(qr * scale) / scale, \
            rounded(pc)
    bt = (1 + r.permutation(pages - 1)).reshape(slots, pps).astype(np.int32)
    return ql, qr, pc, bt, np.asarray(pos, np.int32), scale


@pytest.mark.parametrize("name", sorted(MLA_CASES) + ["bf16_operands"])
def test_mla_decode_attention_kernel(name):
    """The kernel, interpreted, against the plain gather (bf16
    operands in the kernel: a few thousandths).  Where both get
    operands that bfloat16 holds exactly, what is left is the rounding
    of the softmax's weights and the order of the sums: ten times
    less, so a change of the accumulation's order is told apart from a
    change of precision."""
    exact = name == "bf16_operands"
    ql, qr, pc, bt, pos, scale = _mla_case(
        "more_waves_than_the_ring" if exact else name, bf16_operands=exact)
    page = pc.shape[1]
    with jax.default_matmul_precision("highest"):
        want = paged_attention.mla_reference(ql, qr, pc, bt, pos, scale)
    # pages past a slot's last live one are never read: poison them
    for b in range(len(pos)):
        pc[bt[b, pos[b] // page + 1:]] = np.nan
    got = paged_attention.mla_decode_attention(ql, qr, pc, bt, pos, scale,
                                               interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3 if exact else 2e-2)


def test_router_against_hand_arithmetic():
    """The bias chooses and does not weigh; the chosen scores are
    renormalised, then scaled."""
    ecfg = moe.ExpertConfig(dim=2, hidden=4, routed=4, held=(0, 2),
                            top_k=2, route_scale=2.5)
    logits = np.array([[2.0, 1.0, 0.0, -1.0]], np.float32)
    p = {"router": jnp.asarray(np.vstack([logits, np.zeros((1, 4))])
                               .astype(np.float32)),
         "bias": jnp.asarray([0.0, 0.0, 0.0, 0.6], jnp.float32)}
    t = jnp.asarray([[1.0, 7.0]], jnp.float32)
    ids, w = moe.route(p, t, ecfg)
    sc = 1.0 / (1.0 + np.exp(-logits[0]))
    # sc = .881 .731 .5 .269; with the bias expert 3 reads .869 and
    # passes expert 1: chosen 0 and 3, weighed .881 and .269
    assert ids.tolist() == [[0, 3]]
    want = np.array([sc[0], sc[3]]) / (sc[0] + sc[3] + 1e-20) * 2.5
    np.testing.assert_allclose(np.asarray(w[0]), want, rtol=1e-6)


def test_nothing_is_dropped_where_the_capacity_layer_drops(f32_matmuls):
    """Sixteen identical tokens choose the same experts: the
    capacity-factor layer (training's ``forward``) serves the first
    few and drops the rest; ``serve`` gives every one the same
    output."""
    d = 8
    x = jnp.tile(jnp.asarray(np.random.default_rng(0).normal(size=(1, d))
                             .astype(np.float32)), (16, 1))
    tcfg = moe.MoEConfig(dim=d, hidden=16, num_experts=4,
                         capacity_factor=1.0, top_k=1)
    out, _aux = moe.forward(moe.init_params(jax.random.PRNGKey(0), tcfg),
                            x, tcfg)
    kept = np.abs(np.asarray(out)).sum(axis=-1) > 0
    assert kept.sum() == tcfg.capacity(16) == 4            # 12 dropped
    ecfg = moe.ExpertConfig(dim=d, hidden=16, routed=4, held=(0, 4),
                            top_k=2, route_scale=1.0, shared=0)
    p = moe.init_served(jax.random.PRNGKey(1), ecfg)
    out, counts = moe.serve(p, x, ecfg)
    out = np.asarray(out)
    assert np.abs(out[0]).sum() > 0
    np.testing.assert_allclose(out, np.tile(out[:1], (16, 1)), atol=1e-6)
    # 32 pairs on 2 experts, 16 rows each
    assert counts.tolist() == [32, 2, 16]
    ids, w = moe.route(p, x[:1], ecfg)
    hand = sum(float(w[0, k]) * np.asarray(moe._gated(
        x[:1], p["w1"][int(ids[0, k])], p["w2"][int(ids[0, k])]))
        for k in range(2))
    np.testing.assert_allclose(out[:1], hand, atol=1e-5)


def test_rows_that_are_not_live_route_nowhere(f32_matmuls):
    ecfg = moe.ExpertConfig(dim=8, hidden=16, routed=8, held=(2, 6),
                            top_k=3, route_scale=1.0, shared=1)
    p = moe.init_served(jax.random.PRNGKey(2), ecfg)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(6, 8))
                    .astype(np.float32))
    live = jnp.asarray([True, False, True, True, False, False])
    out, counts = moe.serve(p, x, ecfg, live)
    only, counts3 = moe.serve(p, x[np.asarray(live)], ecfg)
    assert counts.tolist() == counts3.tolist()
    np.testing.assert_allclose(np.asarray(out)[np.asarray(live)],
                               np.asarray(only), atol=1e-5)
    # a row that is not live keeps the shared expert alone
    np.testing.assert_allclose(
        np.asarray(out[1:2]), np.asarray(moe._gated(x[1:2], p["ws1"],
                                                    p["ws2"])), atol=1e-5)
    assert ecfg.buffer_rows(6) == 18


def test_the_shares_add_up_to_the_uncut_layer(f32_matmuls):
    """Over all sixteen shares of the toy's 64 experts, the routed
    parts plus the shared expert counted ONCE equal the uncut layer,
    which the benchmark's reference computes with every expert."""
    cfg, m = _bench()
    d, e = cfg["hidden_size"], cfg["moe_intermediate_size"]
    routed, k = cfg["n_routed_experts_published"], cfg["num_experts_per_tok"]
    whole = moe.ExpertConfig(dim=d, hidden=e, routed=routed,
                             held=(0, routed), top_k=k,
                             route_scale=cfg["routed_scaling_factor"],
                             shared=1)
    p = moe.init_served(jax.random.PRNGKey(4), whole)
    t = jnp.asarray(np.random.default_rng(2).normal(size=(24, d))
                    .astype(np.float32))
    with jax.default_matmul_precision("highest"):
        uncut = m._experts(t, p, cfg, False, held=(0, routed))
        shared = moe._gated(t, p["ws1"], p["ws2"])
        total, pairs = shared, 0
        for lo in range(0, routed, 4):
            share = moe.ExpertConfig(dim=d, hidden=e, routed=routed,
                                     held=(lo, lo + 4), top_k=k,
                                     route_scale=whole.route_scale, shared=1)
            mine = {**p, "w1": p["w1"][lo:lo + 4], "w2": p["w2"][lo:lo + 4]}
            out, counts = moe.serve(mine, t, share)
            total = total + (out - shared)
            pairs += int(counts[0])
            # the reference, given the same share, says the same
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(m._experts(
                    t, mine, cfg, False, held=(lo, lo + 4))), atol=2e-5)
    assert pairs == 24 * k                   # every pair fell somewhere
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=5e-5)



# -- the grouped product's kernel ---------------------------------------------

# a decode step's layer at the cell's load: 16 rows on 9 of 12 experts
STEP_SIZES = [2, 0, 1, 3, 2, 0, 1, 2, 0, 3, 1, 1]

# name: (buffer rows, K, N, sizes, row tile, (tk, tn) or None for whole)
GMM_CASES = {
    "empty_groups_first_last_and_between":
        (64, 32, 48, [0, 3, 0, 5, 2, 0], 8, None),
    "every_row_in_one_group": (64, 32, 48, [0, 64, 0, 0], 8, None),
    "groups_cross_row_tiles_in_a_full_buffer":
        (64, 32, 48, [10, 11, 10, 11, 11, 11], 8, None),
    "no_rows_at_all": (64, 32, 48, [0, 0, 0, 0], 8, None),
    "a_step_one_row_tile_of_four": (512, 64, 128, STEP_SIZES, 128, None),
    "a_buffer_that_is_no_multiple_of_the_tile":
        (10, 32, 48, [1, 2, 3], 128, None),
    "two_blocks_of_n": (40, 128, 256, [7, 0, 20, 5], 16, (128, 128)),
    "two_blocks_of_k_and_of_n": (40, 256, 256, [7, 0, 20, 5], 16,
                                 (128, 128)),
}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", sorted(GMM_CASES))
def test_expert_gmm_kernel(case, dtype):
    """The kernel, interpreted, against ``jax.lax.ragged_dot`` on the
    same operands: the live rows agree; the rows a visited tile holds
    behind the last group read 0 whatever the buffer held there (NaN
    here); an expert without rows is multiplied into nothing."""
    rows, k, n, sizes, tm, blocks = GMM_CASES[case]
    dtype = jnp.dtype(dtype)
    r = np.random.default_rng(rows + k)
    live = sum(sizes)
    xs = r.normal(size=(rows, k)).astype(np.float32)
    xs[live:] = np.nan
    w = r.normal(size=(len(sizes), k, n)).astype(np.float32) / math.sqrt(k)
    xs, w = jnp.asarray(xs, dtype), jnp.asarray(w, dtype)
    sz = jnp.asarray(sizes, jnp.int32)
    budget = gmm._BLOCK_BYTES
    if blocks is not None:
        budget = blocks[0] * blocks[1] * dtype.itemsize
        assert gmm._blocks(k, n, dtype.itemsize, budget) == blocks
    got = np.asarray(gmm.expert_gmm(xs, w, sz, tm=tm, block_bytes=budget,
                                    interpret=True))
    assert got.shape == (rows, n) and got.dtype == np.float32
    want = np.asarray(jax.lax.ragged_dot(
        xs, w, sz, preferred_element_type=jnp.float32))
    np.testing.assert_allclose(got[:live], want[:live], atol=1e-5)
    tm = gmm._row_tile(rows, tm)
    reached = min(rows, -(-live // tm) * tm)
    assert (got[live:reached] == 0).all()


# -- the combine behind it ---------------------------------------------------

# name: (tokens, top_k, routed, held, dim, live rows or None for all,
#        (out bytes, tile bytes) or None for the kernel's own)
COMBINE_CASES = {
    "a_step": (16, 4, 16, (0, 8), 256, None, None),
    "a_bucket_in_row_tiles_and_column_blocks":
        (64, 4, 32, (4, 12), 256, None, (64 * 128 * 4, 8 * 128 * 4)),
    "fewer_held_than_top_k_cuts_the_buffer_short":
        (12, 4, 8, (3, 5), 128, None, None),
    "rows_not_live": (16, 4, 16, (0, 8), 128,
                      [0, 2, 3, 7, 8, 13], None),
    "an_expert_with_no_row": (3, 2, 32, (0, 16), 128, None, None),
    "no_pair_local_at_all": (8, 2, 64, (62, 64), 128, [], None),
    "every_row_live_in_a_buffer_that_is_no_multiple_of_the_row_tile":
        (11, 3, 12, (0, 12), 128, None, (2 << 20, 8 * 128 * 4)),
    "a_width_that_fills_no_whole_lanes": (16, 4, 16, (0, 8), 40, None,
                                          None),
}


def _scatter_add(ys, order, w, n_live):
    """``serve``'s combine as it stood until PR 38: the whole buffer
    masked, weighed and scatter-added."""
    live_row = (jnp.arange(ys.shape[0]) < n_live)[:, None]
    return jnp.zeros((w.shape[0], ys.shape[1]), jnp.float32).at[
        order // w.shape[1]].add(jnp.where(live_row, ys, 0.0)
                                 * w.reshape(-1)[order][:, None])


def _combine_operands(case):
    """What ``moe.serve`` hands its combine, made as ``serve`` makes
    it, the buffer's rows behind the live ones NaN; and the sum as it
    stood until PR 38."""
    tokens, k, routed, (lo, hi), dim, rows, _tiles = COMBINE_CASES[case]
    r = np.random.default_rng(tokens * k + dim)
    ecfg = moe.ExpertConfig(dim=dim, hidden=8, routed=routed, held=(lo, hi),
                            top_k=k)
    n = ecfg.n_held
    ids = jnp.asarray(np.stack([r.permutation(routed)[:k]
                                for _ in range(tokens)]), jnp.int32)
    if case == "no_pair_local_at_all":
        ids = ids % lo
    w = jnp.asarray(r.uniform(0.1, 1.0, (tokens, k)).astype(np.float32))
    local = (ids >= lo) & (ids < hi)
    if rows is not None:
        local = local & jnp.zeros((tokens,), bool).at[
            jnp.asarray(rows, jnp.int32)].set(True)[:, None]
    key = jnp.where(local, ids - lo, n).reshape(tokens * k)
    order = jnp.argsort(key, stable=True)[:ecfg.buffer_rows(tokens)]
    n_live = jnp.int32(local.sum())
    # the local pairs' rows are the buffer's first
    assert ((key[order] < n) == (jnp.arange(order.shape[0]) < n_live)).all()
    ys = r.normal(size=(order.shape[0], dim)).astype(np.float32)
    ys[int(n_live):] = np.nan
    ys = jnp.asarray(ys)
    return ys, order, w, n_live, np.asarray(_scatter_add(ys, order, w,
                                                         n_live))


@pytest.mark.parametrize("form", ["plain", "kernel", "chosen"])
@pytest.mark.parametrize("case", sorted(COMBINE_CASES))
def test_the_combine_is_the_scatter_add_of_the_masked_weighed_buffer(
        case, form, monkeypatch):
    """Each form of the combine against the scatter-add of the WHOLE
    buffer that stood in ``serve``: the plain form from the token's
    side, the kernel (interpreted) from the live rows' side, and what
    ``serve`` is handed off the TPU.  Only the order of a token's
    additions may differ.  A width that fills no whole lanes has no
    kernel: there ``combine`` takes the plain form on the TPU too."""
    ys, order, w, n_live, want = _combine_operands(case)
    dim, tiles = COMBINE_CASES[case][4], COMBINE_CASES[case][6]
    assert np.isfinite(want).all()
    if case == "no_pair_local_at_all":
        assert int(n_live) == 0 and not want.any()
    if form == "plain":
        got = comb.plain(ys, order, w, n_live)
    elif form == "chosen":
        got = comb.combine(ys, order, w, n_live)
    elif dim % 128:
        from brpc_tpu.ops import device_ops
        monkeypatch.setattr(device_ops, "_on_tpu", lambda: True)
        got = comb.combine(ys, order, w, n_live)
    else:
        kw = {} if tiles is None else dict(out_bytes=tiles[0],
                                           tile_bytes=tiles[1])
        if tiles is not None:
            assert comb._tiles(w.shape[0], ys.shape[0], dim, *tiles) \
                == (128, 8)
        got = comb.expert_combine(ys, order, w, n_live, interpret=True,
                                  **kw)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("form", ["plain", "kernel"])
def test_serve_gives_what_it_gave_with_the_scatter_add(form, monkeypatch):
    """``serve`` whole, against itself with the combine it had until
    PR 38 in place of the new one: the same output to a rounding, the
    same counts."""
    ecfg = moe.ExpertConfig(dim=128, hidden=16, routed=16, held=(2, 8),
                            top_k=4, route_scale=1.5, shared=1)
    p = moe.init_served(jax.random.PRNGKey(5), ecfg)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(24, 128))
                    .astype(np.float32))
    live = jnp.asarray(np.arange(24) % 5 != 1)

    if form == "kernel":
        monkeypatch.setattr(
            comb, "combine", functools.partial(comb.expert_combine,
                                               interpret=True))
    got, counts = moe.serve(p, x, ecfg, live)
    monkeypatch.setattr(comb, "combine", _scatter_add)
    want, counts0 = moe.serve(p, x, ecfg, live)
    assert counts.tolist() == counts0.tolist()
    assert counts[0] > 0 and float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_on_the_tpu_serve_calls_the_combine_kernel_once(monkeypatch):
    """Traced as on the TPU at a width that fills whole lanes, the
    layer holds two ``expert_gmm`` and one ``expert_combine`` and no
    scatter-add of rows; at a width that does not, the plain form under
    the same name's scope."""
    from brpc_tpu.ops import device_ops
    monkeypatch.setattr(device_ops, "_on_tpu", lambda: True)

    def kernels(dim):
        ecfg = moe.ExpertConfig(dim=dim, hidden=128, routed=16, held=(0, 4),
                                top_k=4)
        p = jax.eval_shape(
            lambda: moe.init_served(jax.random.PRNGKey(0), ecfg))
        jaxpr = jax.make_jaxpr(lambda p, t: moe.serve(p, t, ecfg))(
            p, jax.ShapeDtypeStruct((8, dim), jnp.float32)).jaxpr
        return [_count_eqns(jaxpr, lambda e: e.primitive.name == "pallas_call"
                            and e.params["name"] == name)
                for name in ("expert_gmm", "expert_combine")] + [
            _count_eqns(jaxpr, lambda e: e.primitive.name == "scatter-add"
                        and e.outvars[0].aval.shape == (8, dim))]

    assert kernels(128) == [2, 1, 0]
    assert kernels(192) == [2, 0, 0]


@pytest.mark.parametrize("sizes,rows,tm", [
    (STEP_SIZES, 512, 128), (STEP_SIZES, 512, 8), ([0, 0, 0], 64, 16),
    ([0, 64, 0, 0], 64, 16), ([10, 11, 10, 11, 11, 11], 64, 8),
    ([1, 0, 0, 0, 0, 5], 32, 4), ([16, 16], 32, 16),
])
def test_the_visit_list_names_only_groups_with_rows(sizes, rows, tm):
    """Against a walk over every (row tile, group) pair: a visit is a
    pair that shares a row; they come in row order; a group without
    rows is in none, a row tile behind the last group in none; the
    entries past the last visit repeat it."""
    tiles_m = rows // tm
    offs, group, tile, visits = [np.asarray(a) for a in gmm.visit_list(
        jnp.asarray(sizes, jnp.int32), tiles_m, tm)]
    ends = np.cumsum(sizes)
    starts = ends - sizes
    want = [(g, t) for g in range(len(sizes)) for t in range(tiles_m)
            if max(starts[g], t * tm) < min(ends[g], (t + 1) * tm)]
    assert offs.tolist() == [0] + ends.tolist()
    assert int(visits) == len(want) <= tiles_m + len(sizes) - 1 == len(group)
    assert list(zip(group[:len(want)], tile[:len(want)])) == want
    assert all(sizes[g] > 0 for g in group[:len(want)])
    if want:
        assert set(zip(group[len(want):], tile[len(want):])) <= {want[-1]}


def _count_eqns(jaxpr, pred) -> int:
    """The equations ``pred`` holds for, in ``jaxpr`` and in every
    jaxpr its equations carry (pjit, cond, scan, while)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += bool(pred(eqn))
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count_eqns(sub, pred)
    return n


def _traced_step(lm, params):
    """The paged step of two slots as a jaxpr."""
    _prefill, step = T.make_paged_batch_decode(lm, PAGE)
    cache = T.empty_paged_cache(lm, 9, 2, PAGE)
    return jax.make_jaxpr(step)(
        params, cache, jnp.zeros((2, lm.max_seq // PAGE), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.asarray([True, True])).jaxpr


def test_the_step_calls_expert_gmm_twice_an_expert_layer(model):
    """What ``benchmarks/models/kimi_k2.py kernel_calls`` counts on:
    the traced step holds two kernels named ``expert_gmm`` an expert
    layer (gate and up in one product, down in the other) and no
    grouped product of XLA's."""
    cfg, m, lm, params = model
    jaxpr = _traced_step(lm, params)
    calls = _count_eqns(
        jaxpr, lambda e: e.primitive.name == "pallas_call"
        and e.params["name"] == "expert_gmm")
    assert calls == 2 * lm.ffns.count("experts") == 4 \
        == m.kernel_calls(cfg, "expert_gmm")
    assert _count_eqns(
        jaxpr, lambda e: e.primitive.name.startswith("ragged_dot")) == 0


def test_the_step_calls_the_latent_kernel_once_a_layer(model):
    """What ``benchmarks/readers/step_kernel_work.py`` counts on: it
    adds up every operation of ``jit_step`` whose name STARTS with
    ``mla_decode_attention`` and answers nothing unless they are
    ``kernel_calls`` a step, so the traced step holds one such kernel a
    latent layer and nothing else by that prefix (the step runs the
    plain gather off the TPU: the kernel is named here)."""
    cfg, m, lm, params = model
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(paged_attention, "mla_attention",
                   lambda *a: paged_attention.mla_decode_attention(
                       *a, interpret=True))
        jaxpr = _traced_step(lm, params)
    calls = _count_eqns(
        jaxpr, lambda e: e.primitive.name == "pallas_call"
        and e.params["name"].startswith("mla_decode_attention"))
    assert calls == lm.mixers.count("mla") == cfg["num_hidden_layers"] \
        == m.kernel_calls(cfg, "mla_decode_attention")
    assert _count_eqns(
        jaxpr, lambda e: e.primitive.name == "pallas_call"
        and e.params["name"] == "mla_decode_attention") == calls


def test_yarn_frequencies_and_the_scale_against_the_formulas():
    cfg, m = _bench("configs/kimi-k2.7-code.json")
    lm = T.LMConfig(remat=False, **m.lm_kwargs(cfg))
    base, dim = 50000.0, 64
    f = np.array([base ** (-2 * i / dim) for i in range(32)])

    def cd(r):
        return dim * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(base))

    low, high = math.floor(cd(32)), math.ceil(cd(1))
    assert (low, high) == (8, 20)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = f / 64 * ramp + f * (1 - ramp)
    np.testing.assert_allclose(mla_mixer.inv_freq(lm), want, rtol=1e-6)
    np.testing.assert_allclose(m.yarn_inv_freq(cfg), want, rtol=1e-12)
    assert want[0] == 1.0 and want[31] == pytest.approx(f[31] / 64)
    mscale = 0.1 * math.log(64) + 1
    assert mscale == pytest.approx(1.4159, abs=1e-4)
    for s in (mla_mixer.softmax_scale(lm), m.softmax_scale(cfg)):
        assert s == pytest.approx(192 ** -0.5 * mscale ** 2, rel=1e-12)
    sin, cos = T._rope_at(lm, jnp.asarray([3]))
    np.testing.assert_allclose(np.asarray(sin[0, 0]), np.sin(3 * want),
                               rtol=1e-4, atol=1e-6)
    assert lm.norm_eps == 1e-5 and lm.latent_row() == 576 \
        and lm.latent_row_padded() == 640


def test_counts_against_hand_arithmetic():
    cfg, m = _bench("configs/kimi-k2.7-code.json")
    mla = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 \
        + 8192 * 7168
    assert m.mla_params(cfg) == mla == 101_122_048
    expert = 3 * 7168 * 2048
    assert m.expert_params(cfg) == expert == 44_040_192
    layer = mla + 7168 * 384 + 13 * expert
    assert m.expert_layer_params(cfg) == layer and round(layer / 1e5) == 6764
    dense = mla + 3 * 7168 * 18432
    total = 7 * layer + dense + 2 * 20480 * 7168
    assert m.total_params(cfg) == total
    assert round(2 * total / 1e7) == 1105                  # 11.05 GB
    lm = T.LMConfig(remat=False, **m.lm_kwargs(cfg))
    assert T.paged_page_bytes(lm, 16) == 16 * 8 * 640 * 4  # as it lies
    # a step of 64 rows at 1,000 live positions each
    lives = [1000] * 64
    flops, nbytes = m.step_work(cfg, lives, 1)
    every_step = 7 * (layer - 12 * expert) + dense + 7168 * 20480
    touched = 7 * 12 * (1 - (47 / 48) ** 64)
    assert touched / 84 == pytest.approx(0.74, abs=0.005)
    assert nbytes == pytest.approx(
        2 * (every_step + touched * expert) + 8 * 576 * 4 * (64_000 + 64))
    assert 9.5e9 < nbytes < 10.5e9                          # ~10 GB a step
    counted = {"experts_touched": 50, "local_pairs": 100}
    _f, nb2 = m.step_work(cfg, lives, 1, counted)
    assert nb2 == 2 * (every_step + 50 * expert) + 8 * 576 * 4 * 64_064
    att = 8 * 2 * 64 * (576 + 512) * 64_000
    assert m.mla_decode_work(cfg, lives, 1) == (att, 8 * 576 * 4 * 64_000)
    per_row = 2 * (every_step - 8 * 512 * 64 * 256) \
        + 8 * 2 * 64 * 512 * 256
    assert flops == pytest.approx(
        64 * per_row + att + 2 * expert * 7 * 64 * 8 * 12 / 384)
    assert m.kernel_calls(cfg, "mla_decode_attention") == 8
    assert m.expert_work(cfg, lives, 1, counted) == (2 * expert * 100,
                                                     2 * 50 * expert)


# -- through the batcher -------------------------------------------------------

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


def _served_tokens(lm, params, prompts, n=6):
    from brpc_tpu.models.lm_service import ContinuousBatcher
    bat = ContinuousBatcher(lm, params, slots=2, page=PAGE, pages=17,
                            idle_linger_s=0.2)
    streams = [_FakeStream() for _ in prompts]
    with jax.default_matmul_precision("highest"):
        for st, p in zip(streams, prompts):
            bat.join(st, p, n)
        deadline = time.monotonic() + 120.0
        while not all(s.closed for s in streams) \
                and time.monotonic() < deadline:
            time.sleep(0.002)
    assert [s.close_reason for s in streams] == ["finished"] * len(prompts)
    return bat, [s.tokens for s in streams]


def test_batcher_serves_the_references_tokens_and_counts_routing(
        model, f32_matmuls):
    """Three sessions on two slots (one waits, one slot is reused):
    each is served what the reference decodes greedily; the routing
    counts arrive with the tokens; the latent pool and the pages
    read are accounted; ``LM.Info`` shows the schedule."""
    from brpc_tpu.models.lm_service import LMService
    cfg, m, lm, params = model
    ref = m.Reference(cfg, params)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 256, (n,), dtype=np.int32)
               for n in (5, 18, 1)]
    bat, served = _served_tokens(lm, params, prompts)
    for toks, p in zip(served, prompts):
        toks = np.asarray(toks, np.int32)
        logits = ref.served_logits(p, toks)
        best = logits.max(axis=-1)
        assert (best - logits[np.arange(6), toks]
                <= 1e-4 * logits.std(axis=-1)).all()
    kv = bat.kv_stats()
    moe_c = kv["moe"]
    assert {k: moe_c[k] for k in ("layers", "held", "routed", "top_k")} \
        == {"layers": 2, "held": 4, "routed": 64, "top_k": 4}
    assert moe_c["rows"] == 18 and moe_c["steps"] == kv["steps"]
    assert 0 < moe_c["local_pairs"] <= 18 * 4 * 2
    assert 0 < moe_c["experts_touched"] <= moe_c["local_pairs"]
    assert 1 <= moe_c["max_load"] <= 2
    assert kv["latent"] == {"row_bytes": 160, "layers": 3,
                            "packed_bytes": _projection_bytes(lm, params),
                            "pool_bytes": 17 * PAGE * 3 * 128 * 4}
    assert kv["attn"]["pages_read"] > 0 and "prefix" not in kv
    assert bat._alloc.page_bytes == T.paged_page_bytes(lm, PAGE) \
        == PAGE * 3 * 128 * 4
    svc = LMService(cfg=lm, params=params, page=PAGE, decode_slots=2)
    info = json.loads(svc.Info(None, b""))
    assert info["mixers"] == "mmm" and info["ffns"] == "dee"
    assert info["experts"]["held"] == [0, 4] \
        and info["experts"]["routed"] == 64
    assert info["latent_pool"] == {"layers": 3, "row": 40, "row_bytes": 160,
                                   "token_bytes": 3 * 128 * 4}
    assert b":dee:" in svc.model_fingerprint()


# -- the projections packed once, at the service's start -----------------------

def _projection_bytes(lm, params) -> int:
    """``wq_b`` and ``wkv_b`` of every latent layer, as the caller
    holds them."""
    return sum(params[f"blk{i}"][k].nbytes
               for i in lm.mla_layers() for k in ("wq_b", "wkv_b"))


def _packed_against_unpacked(program, model, monkeypatch):
    """``(unpacked, packed)`` outputs of one program of the toy model,
    as numpy trees."""
    cfg, m, lm, params = model
    packed = mla_mixer.pack_params(lm, params)
    assert "wq_h" in packed["blk0"] and "wq_b" not in packed["blk0"]
    rng = np.random.default_rng(11)
    if program == "batcher":
        prompts = [rng.integers(0, 256, (n,), dtype=np.int32)
                   for n in (7, 17)]
        theirs, got = _served_tokens(lm, params, prompts)
        assert "wq_h" in theirs.params["blk1"]
        with monkeypatch.context() as mp:
            mp.setattr(mla_mixer, "pack_params", lambda cfg, p: p)
            ours, want = _served_tokens(lm, params, prompts)
        assert "wq_b" in ours.params["blk1"]
        return want, got
    prefill, step = T.make_paged_batch_decode(lm, PAGE)
    if program == "prefill":
        ids = rng.integers(0, 256, (1, 16), dtype=np.int32)
        run = lambda p: jax.jit(prefill)(p, ids, jnp.int32(13))  # noqa: E731
    else:
        cache = T.empty_paged_cache(lm, 9, 2, PAGE)
        cache["len"] = jnp.asarray([3, 0], jnp.int32)
        bt = jnp.asarray(np.arange(1, 9, dtype=np.int32).reshape(2, 4))
        bt = jnp.pad(bt, ((0, 0), (0, lm.max_seq // PAGE - 4)))
        tok = jnp.asarray([5, 9], jnp.int32)
        run = lambda p: jax.jit(step)(      # noqa: E731
            p, cache, bt, tok, jnp.asarray([True, True]))
    with jax.default_matmul_precision("highest"):
        return [jax.tree_util.tree_map(np.asarray, run(p))
                for p in (params, packed)]


@pytest.mark.parametrize("program", ["step", "prefill", "batcher"])
def test_packed_layers_compute_what_unpacked_layers_compute(
        model, f32_matmuls, monkeypatch, program):
    """A tree the service packed against the tree as the caller holds
    it (which the program packs in its own trace): the same values
    through the same products, so in float32 the same bits, and
    through the batcher the same tokens."""
    want, got = _packed_against_unpacked(program, model, monkeypatch)
    assert jax.tree_util.tree_structure(want) \
        == jax.tree_util.tree_structure(got)
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(a, b)


_MOVES = ("slice", "dynamic_slice", "transpose", "concatenate", "gather",
          "copy", "rev", "pad")
_PASSES = ("convert_element_type", "reshape", "pjit", "jit", "closed_call",
           "custom_jvp_call", "squeeze")


def _relaid(jaxpr, var, path=()):
    """The data movements (``_MOVES``) applied to ``var`` before a
    product takes it: empty when it reaches every ``dot_general`` /
    ``pallas_call`` as it lies."""
    found = []
    for eqn in jaxpr.eqns:
        for pos, v in enumerate(eqn.invars):
            if v is not var:
                continue
            name = eqn.primitive.name
            if name in ("dot_general", "pallas_call"):
                continue
            if name in _MOVES:
                found.append(path + (name,))
            elif name in _PASSES:
                sub = [x for x in eqn.params.values()
                       if hasattr(getattr(x, "jaxpr", x), "eqns")]
                if sub:
                    inner = getattr(sub[0], "jaxpr", sub[0])
                    found += _relaid(inner, inner.invars[pos], path + (name,))
                else:
                    found += _relaid(jaxpr, eqn.outvars[0], path + (name,))
            else:
                found.append(path + ("?" + name,))
    return found


def test_packed_weights_reach_their_products_as_they_lie(model):
    """What the chip's per-step ``copy`` of a weight betrays, read from
    the traced step on the CPU: every matrix of a PACKED latent layer
    enters its ``dot_general`` with no slice, transpose or
    concatenation of it in the way (a cast to the MXU's operand type
    and a reshape move nothing); of an unpacked layer ``wq_b`` and
    ``wkv_b`` do not."""
    cfg, m, lm, params = model
    for tree, relaid in ((mla_mixer.pack_params(lm, params), set()),
                         (params, {"wq_b", "wkv_b"})):
        flat, _ = jax.tree_util.tree_flatten_with_path(
            (tree, T.empty_paged_cache(lm, 9, 2, PAGE)))
        jaxpr = _traced_step(lm, tree)
        assert len(jaxpr.invars) == len(flat) + 3
        got = set()
        for (path, leaf), var in zip(flat, jaxpr.invars):
            keys = [getattr(k, "key", None) for k in path]
            if keys[1] in [f"blk{i}" for i in lm.mla_layers()] \
                    and keys[2] != "moe" and leaf.ndim >= 2 \
                    and _relaid(jaxpr, var):
                got.add(keys[2])
        assert got == relaid


def test_pack_leaves_the_callers_tree_and_the_fingerprint_alone(model):
    """The service's tree is its own: every leaf the caller holds is
    the object it was, ``param_bytes`` and the fingerprint are those of
    the tree as handed over, the cost is reported; a layer whose
    projections are ``QuantTensor`` is not packed (and not served)."""
    from brpc_tpu.models.lm_service import LMService
    cfg, m, lm, params = model
    before = {id(x) for x in jax.tree_util.tree_leaves(params)}
    keys = {k: set(v) for k, v in params.items() if isinstance(v, dict)}
    svc = LMService(cfg=lm, params=params, page=PAGE, decode_slots=2)
    assert {id(x) for x in jax.tree_util.tree_leaves(params)} == before
    assert {k: set(v) for k, v in params.items()
            if isinstance(v, dict)} == keys
    assert svc.params is not params and "wq_b" not in svc.params["blk2"]
    assert svc.params["blk2"]["wo"] is params["blk2"]["wo"]
    info = json.loads(svc.Info(None, b""))
    assert info["param_bytes"] == quant.quantized_nbytes(params)
    assert info["packed_bytes"] == _projection_bytes(lm, params) \
        == mla_mixer.packed_bytes(lm, svc.params)
    assert mla_mixer.packed_bytes(lm, params) == 0
    assert f":{quant.quantized_nbytes(params)}:".encode() \
        in svc.model_fingerprint()
    held = dict(params["blk1"],
                wq_b=quant.quantize_int8(params["blk1"]["wq_b"]))
    assert mla_mixer.pack(lm, held) is held
    mixed = mla_mixer.pack_params(lm, {**params, "blk1": held})
    assert mixed["blk1"] is held and "wk_b" in mixed["blk0"]
    with pytest.raises(T.UnsupportedBlock, match="QuantTensor"):
        _traced_step(lm, mixed)


def test_packing_twice_is_packing_once(model):
    cfg, m, lm, params = model
    once = mla_mixer.pack_params(lm, params)
    assert mla_mixer.pack_params(lm, once) is once
    assert mla_mixer.pack(lm, once["blk0"]) is once["blk0"]
    first = T.LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=64,
                       remat=False)
    plain = T.init_params(jax.random.PRNGKey(0), first)
    assert mla_mixer.pack_params(first, plain) is plain


# -- what declines, by name ----------------------------------------------------

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


def _flash_declines():
    lm = _lm(attn_impl="flash")
    params = T.init_params(jax.random.PRNGKey(0), lm)
    T.make_prefill(lm)(params, np.zeros((1, 16), np.int32), np.int32(3))


DECLINES = {
    "training": lambda: T.make_forward(_lm()),
    "train_step": lambda: T.make_train_step(_lm()),
    "contiguous_decode": lambda: T.make_decode(_lm()),
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
    "flash_prefill": _flash_declines,
    # experts beside a state layer (beside "attn" they are served since
    # the window schedule: tests/test_window_experts.py)
    "experts_beside_ssm": lambda: T.LMConfig(
        depth=2, mixers=("attn", "ssm"), ffns=("dense", "experts"),
        expert_dim=8, experts_routed=4, experts_top_k=2),
}


@pytest.mark.parametrize("path", sorted(DECLINES))
def test_unported_paths_decline_by_name(path):
    with pytest.raises(T.UnsupportedBlock):
        DECLINES[path]()


# -- the blocks that were there are served by the programs that were there ------

def _program_hashes(cfg, page):
    """The step's and a 16-token prefill's lowered text, locations
    stripped, hashed."""
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


# read at the commit before latent attention and the served experts
# (817a8ef), with this function, on this installation
PARENTS = {
    "first_block": (
        dict(vocab=64, dim=32, heads=4, depth=2, max_seq=64, remat=False),
        16, {"step": "66a4fce0a8f0e8b9", "prefill": "d3a796e6d3661691"}),
    "state_layers_grouped_heads": (
        dict(vocab=97, dim=40, heads=5, kv_heads=1, depth=4, max_seq=64,
             remat=False, rope=False, ffn="gated_silu", ffn_dim=96,
             tie_embed=True, final_norm=True,
             mixers=("ssm", "attn", "ssm", "ssm"), ssm_dt_rank=6),
        8, {"step": "41894b873c845530", "prefill": "128f3ea7122e3b66"}),
}


@pytest.mark.parametrize("block", sorted(PARENTS))
def test_default_blocks_traced_programs_are_unchanged(block):
    kw, page, want = PARENTS[block]
    prev = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    try:
        assert _program_hashes(T.LMConfig(**kw), page) == want
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", prev)
