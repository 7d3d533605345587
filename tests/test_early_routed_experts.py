"""A router that reads the layer's INPUT, before the first norm and
before attention (``LMConfig.router_at`` ``"layer_input"``), scores by a
softmax over all the experts (``router_scoring``), and feeds ReLU-gated
experts (``ffn`` ``"gated_relu"``) the normed row AFTER attention, in a
sequential RMS block over two page classes; at toy widths with the
structure of the benchmark's ``smallthinker-21b``: one period of a
global layer without rotation and three window layers with it, 14 query
heads on 2 key/value heads of 16 (groups of SEVEN), a window of 40 (not
a multiple of the page), 16 experts all held, 4 a token, an untied head.

The yardstick is ``benchmarks/models/smallthinker.py``'s ``Reference``:
the whole sequence at once, the router first on the layer's input,
attention a masked softmax, the expert layer a plain loop; it imports
nothing of the program.  The driver of the two page classes, the
kernels' cases and the list of declined paths are
``tests/test_window_experts.py``'s, run here for this block.  (Mosaic's
compiles of the two attention kernels at a group of seven, at the cell's
shapes, are in ``tests/test_paged_attention.py`` with every other: ONE
file loads the TPU's compiler.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_window_experts import (PAGE, WINDOW_DECODE_CASES,  # noqa: F401
                                 _bench, _gaps, _Paged,
                                 check_span_flash_kernel,
                                 check_window_decode_kernel, declines,
                                 f32_matmuls)

from brpc_tpu.models import moe
from brpc_tpu.models import transformer_lm as T

TOY = "tests/toy_smallthinker/config.json"


@pytest.fixture(scope="module")
def model():
    """``(file, module, LMConfig, params)`` of the toy configuration,
    weights float32 (the benchmark's are bfloat16: widened once, so
    that float32 arithmetic is exact on both sides)."""
    cfg, m = _bench(TOY)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    m.make_params(cfg, 3))
    return cfg, m, T.LMConfig(remat=False, **m.lm_kwargs(cfg)), params


def test_the_toy_has_the_cells_structure(model):
    cfg, m, lm, params = model
    assert lm.heads // lm.kv_heads == 7
    assert lm.windows == (0, 40, 40, 40) and lm.ropes == (
        False, True, True, True)
    assert (lm.router_at, lm.router_scoring, lm.ffn) == (
        "layer_input", "softmax", "gated_relu")
    assert not lm.parallel_block and not lm.tie_embed and lm.final_norm
    ec = lm.expert_cfg()
    assert (ec.scoring, ec.act, ec.held, ec.bias, ec.shared) == (
        "softmax", "relu", (0, 16), False, 0)
    assert set(params["blk0"]) == {"ln1", "ln2", "wqkv", "wo", "moe"}
    assert set(params["blk0"]["moe"]) == {"router", "w1", "w2"}
    # the program's own seeded tree has the same leaves
    own = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), lm))
    assert jax.tree_util.tree_structure(own) \
        == jax.tree_util.tree_structure(params)
    assert jax.tree_util.tree_map(lambda a: a.shape, own) \
        == jax.tree_util.tree_map(lambda a: a.shape, params)


# (a) float32 on both sides: readings 2e-6 to 4e-6 at this size.  The
# window is 40 and the page 16: contexts of 0-39 never close it, 40
# reaches it with the first token, 64-199 lie 2-10 pages past it
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


def _route_on_the_normed_row(cfg, bp, x):
    return moe.route(bp["moe"],
                     T._norm(cfg, x, bp["ln1"]).reshape(-1, cfg.dim),
                     cfg.expert_cfg())


# what each wrong reading of the block changes in the program
WRONG = {
    "route_on_the_normed_row": {"patch": _route_on_the_normed_row},
    "route_on_the_row_the_experts_read": {"router_at": "ffn"},
    "sigmoid_for_softmax": {"router_scoring": "sigmoid"},
    "silu_for_relu": {"ffn": "gated_silu"},
    "rotate_a_global_layer": {"ropes": (True,) * 4},
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_a_wrong_reading_of_the_block_fails_the_tolerance(
        model, f32_matmuls, monkeypatch, what):
    """Each of the five is a block somebody could have meant; put in the
    program's place it lies far outside the 1e-4 the right one keeps
    (readings 0.03 to 1.5 of a logit's deviation)."""
    cfg, m, _lm, params = model
    kw = dict(WRONG[what])
    patch = kw.pop("patch", None)
    if patch is not None:
        monkeypatch.setattr(T, "_early_route", patch)
    lm = T.LMConfig(remat=False, **{**m.lm_kwargs(cfg), **kw})
    gaps, _ = _gaps((cfg, m, lm, params), 65)
    assert gaps.max() > 1e-2


def test_a_layers_choice_does_not_depend_on_its_attention(model,
                                                          monkeypatch):
    """``(ids, w)`` of layer ``i`` are made from the layer's input: with
    layer ``i``'s pages spoilt (so its attention reads other keys and
    values) the choice of layers ``0 .. i`` is bit for bit what it was,
    the step's logits are not, and the next layer's weights move."""
    cfg, m, lm, params = model
    rng = np.random.default_rng(5)
    ctx = rng.integers(0, cfg["vocab_size"], (70,), dtype=np.int32)
    run = _Paged(lm, params, ctx)
    run._step = T.make_paged_batch_decode(lm, PAGE)[1]      # eagerly
    seen = []
    serve = moe.serve

    def watched(p, t, ecfg, live=None, routed=None):
        assert routed is not None           # the layer routed, not serve
        seen.append(tuple(np.asarray(a) for a in routed))
        return serve(p, t, ecfg, live, routed)

    monkeypatch.setattr(moe, "serve", watched)
    cache0, pos0 = dict(run.cache), run.pos

    def step(spoil=None):
        run.cache, run.pos = dict(cache0), pos0
        if spoil is not None:
            for name in (f"pk{spoil}", f"pv{spoil}"):
                run.cache[name] = run.cache[name] + 1.0
        del seen[:]
        logits, _counts = run.feed(7)
        return logits, list(seen)

    want, clean = step()
    assert len(clean) == lm.depth and clean[0][0].shape == (2, 4)
    for i in range(lm.depth):
        got, routed = step(spoil=i)
        assert np.abs(got - want).max() > 1e-3
        for j in range(i + 1):
            for a, b in zip(routed[j], clean[j]):
                np.testing.assert_array_equal(a, b)
        if i + 1 < lm.depth:
            assert np.abs(routed[i + 1][1][1] - clean[i + 1][1][1]).max() > 0


# -- (b) the shares add up, under either scoring --------------------------------

@pytest.mark.parametrize("scoring,act", [("sigmoid", "silu"),
                                         ("softmax", "relu")])
def test_the_shares_add_up_to_the_uncut_layer(f32_matmuls, scoring, act):
    """64 experts, 6 a token, routed ONCE on rows of their own: the
    parts that the holders of ``(0,16) (16,32) (32,48) (48,64)`` give
    add up to the layer with all 64 held, which is the plain sum over
    the chosen experts."""
    d, e, routed, k = 32, 16, 64, 6
    whole_cfg = moe.ExpertConfig(d, e, routed, (0, routed), k, bias=False,
                                 scoring=scoring, act=act)
    whole = moe.init_served(jax.random.PRNGKey(2), whole_cfg)
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(3), (24, d), jnp.float32)
    t = jax.random.normal(jax.random.PRNGKey(4), (24, d), jnp.float32)
    ids, w = moe.route(whole, x, whole_cfg)
    np.testing.assert_allclose(w.sum(axis=-1), 1.0, atol=1e-6)
    if scoring == "softmax":
        # softmax, choice, renormalise = the softmax over the chosen
        np.testing.assert_allclose(w, jax.nn.softmax(jnp.take_along_axis(
            x @ whole["router"], ids, axis=-1), axis=-1), atol=1e-6)
    fn = jax.nn.relu if act == "relu" else jax.nn.silu
    want = sum(
        w[:, j, None] * jnp.stack([
            (fn(t[r] @ whole["w1"][ids[r, j]][:, :e])
             * (t[r] @ whole["w1"][ids[r, j]][:, e:]))
            @ whole["w2"][ids[r, j]] for r in range(24)])
        for j in range(k))
    out, counts = moe.serve(whole, t, whole_cfg, routed=(ids, w))
    np.testing.assert_allclose(out, want, atol=2e-5)
    assert int(counts[0]) == 24 * k
    parts = []
    for lo in range(0, routed, 16):
        ec = moe.ExpertConfig(d, e, routed, (lo, lo + 16), k, bias=False,
                              scoring=scoring, act=act)
        mine = dict(whole, w1=whole["w1"][lo:lo + 16],
                    w2=whole["w2"][lo:lo + 16])
        part, counts = moe.serve(mine, t, ec, routed=(ids, w))
        parts.append(part)
        assert int(counts[1]) <= 16
        # a holder that routes for itself on the same rows agrees
        np.testing.assert_allclose(moe.serve(mine, x, ec)[0],
                                   moe.serve(mine, x, ec,
                                             routed=(ids, w))[0], atol=1e-6)
    np.testing.assert_allclose(sum(parts), want, atol=2e-5)


def test_softmax_router_against_hand_arithmetic():
    d, routed, k = 4, 6, 2
    router = jnp.asarray(np.arange(d * routed, dtype=np.float32)
                         .reshape(d, routed) / 10.0 - 1.0)
    t = jnp.asarray([[1.0, -1.0, 0.5, 0.25]], jnp.float32)
    ec = moe.ExpertConfig(d, 8, routed, (0, routed), k, bias=False,
                          scoring="softmax")
    ids, w = moe.route({"router": router}, t, ec)
    logits = (np.asarray(t) @ np.asarray(router))[0]
    sc = np.exp(logits) / np.exp(logits).sum()
    best = np.argsort(-sc)[:k]
    assert sorted(np.asarray(ids)[0]) == sorted(best)
    np.testing.assert_allclose(np.sort(np.asarray(w)[0]),
                               np.sort(sc[best] / sc[best].sum()), rtol=1e-6)


# -- (c) the kernels at a group of seven, interpreted ------------------------------

@pytest.mark.parametrize("name", [n for n in WINDOW_DECODE_CASES
                                  if n != "kvh1_w24"])
def test_window_decode_kernel_at_a_group_of_seven(name, monkeypatch):
    """7-row slices of the queries, ``(7, 1)`` and ``(7, hd)``
    accumulators: against ``paged_attention.reference``."""
    check_window_decode_kernel(*WINDOW_DECODE_CASES[name], 7, monkeypatch)


@pytest.mark.parametrize("paged", [True, False])
@pytest.mark.parametrize("window,start,w,keys_from", [
    (40, 0, 32, 0), (40, 192, 32, 144), (0, 96, 32, 0), (24, 48, 16, 16)])
def test_span_flash_kernel_at_a_group_of_seven(window, start, w, keys_from,
                                               paged):
    """Both forms (query blocks of 8 tokens are 56 rows), against
    ``span_attention.reference``."""
    check_span_flash_kernel(window, start, w, keys_from, paged, 7)


def test_the_step_calls_its_kernels_by_layer(model, monkeypatch):
    """On the TPU a window layer's call is ``window_decode_attention``
    and a global layer's ``paged_decode_attention``; ``expert_gmm``
    twice a layer, and the router's product once a layer AHEAD of the
    layer's attention in the traced program."""
    from brpc_tpu.ops import device_ops
    cfg, m, lm, params = model
    monkeypatch.setattr(device_ops, "_on_tpu", lambda: True)
    _prefill, step = T.make_paged_batch_decode(lm, PAGE)
    pps = lm.max_seq // PAGE
    cache = T.empty_paged_cache(lm, 9, 2, PAGE)
    jaxpr = jax.make_jaxpr(step)(
        params, cache, jnp.zeros((2, 2, pps), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), bool)).jaxpr
    order = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                order.append(eqn.params["name"])
            elif eqn.primitive.name == "dot_general" \
                    and eqn.outvars[0].aval.shape == (2, 16):
                order.append("router")
            for val in eqn.params.values():
                for sub in val if isinstance(val, (list, tuple)) else (val,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jaxpr)
    names = ("window_decode_attention", "paged_decode_attention",
             "expert_gmm")
    assert [order.count(k) for k in names] == [3, 1, 8] \
        == [m.kernel_calls(cfg, k) for k in names]
    attn = [i for i, k in enumerate(order) if k.endswith("_attention")]
    routers = [i for i, k in enumerate(order) if k == "router"]
    assert len(routers) == 4 and all(r < a for r, a in zip(routers, attn))


# -- (d) tracing ------------------------------------------------------------------

def test_the_service_says_where_and_how_it_routes(model):
    import json
    import time

    from brpc_tpu.models.lm_service import ContinuousBatcher, LMService
    from brpc_tpu.streaming import StreamOptions
    _cfg, _m, lm, params = model
    svc = LMService(cfg=lm, params=params, page=PAGE, decode_slots=2)
    info = json.loads(svc.Info(None, b""))
    assert info["experts"]["scoring"] == "softmax" \
        and info["experts"]["router_at"] == "layer_input"
    assert info["ffn"] == "gated_relu"
    fp = svc.model_fingerprint()
    assert fp.endswith(b":route@layer_input/softmax") and b"gated_relu" in fp
    bat = ContinuousBatcher(lm, params, slots=2, page=PAGE,
                            idle_linger_s=0.2)
    stats = bat.kv_stats()["moe"]
    assert (stats["scoring"], stats["router_at"], stats["held"]) == (
        "softmax", "layer_input", 16)

    class Span:
        notes = []

        def annotate(self, note):
            self.notes.append(note)

        def finish(self, *_a):
            pass

    class Stream:
        def __init__(self):
            self.tokens, self.closed = [], False
            self.options, self._native_tx = StreamOptions(), None

        def write(self, data):
            self.tokens.append(data)
            return 0

        def close(self, reason=None):
            self.closed = True

    st = Stream()
    bat.join(st, np.arange(5, dtype=np.int32), 2, span=Span())
    deadline = time.monotonic() + 120.0
    while not st.closed and time.monotonic() < deadline:
        time.sleep(0.002)
    assert st.closed and len(st.tokens) == 2
    assert Span.notes[:2] == ["lm_join",
                              "lm_schedule:aaaa:route@layer_input/softmax"]
    assert bat.kv_stats()["moe"]["steps"] == 2
    # the blocks that were there say what they said
    old_cfg, old = _bench()
    old_lm = T.LMConfig(remat=False, **old.lm_kwargs(old_cfg))
    assert b"route@" not in LMService(
        cfg=old_lm, params={}, page=PAGE,
        decode_slots=2).model_fingerprint()


# -- (e) what declines, by name ------------------------------------------------------

def _lm(**kw):
    cfg, m = _bench(TOY)
    return T.LMConfig(**{"remat": False, **m.lm_kwargs(cfg), **kw})


DECLINES = {
    **declines(_lm),
    # the early router outside the sequential window block of expert
    # layers over 'attn' mixers
    "early_router_in_the_parallel_block": lambda: _lm(parallel_block=True),
    "early_router_without_windows": lambda: _lm(
        windows=(0,) * 4, ropes=(False,) * 4),
    "early_router_beside_a_dense_layer": lambda: _lm(
        ffns=("dense", "experts", "experts", "experts")),
    "early_router_beside_a_state_layer": lambda: T.LMConfig(
        depth=2, mixers=("attn", "ssm"), kv_heads=1, ffns=("experts",) * 2,
        expert_dim=8, experts_routed=4, router_at="layer_input"),
    "early_router_beside_latent_attention": lambda: T.LMConfig(
        depth=1, mixers=("mla",), rope=False, kv_lora_rank=8, qk_nope_dim=8,
        qk_rope_dim=4, v_head_dim=8, ffns=("experts",), expert_dim=8,
        experts_routed=4, router_at="layer_input"),
}


@pytest.mark.parametrize("path", sorted(DECLINES))
def test_unported_paths_decline_by_name(path):
    with pytest.raises(T.UnsupportedBlock) as err:
        DECLINES[path]()
    assert str(err.value)


def test_the_decline_names_the_router():
    with pytest.raises(T.UnsupportedBlock, match="router_at"):
        _lm(parallel_block=True)
    with pytest.raises(AssertionError):
        _lm(router_scoring="tanh")
    with pytest.raises(AssertionError):
        _lm(ffn="gated_gelu")
