"""Layers of ONE part (``LMConfig.mixers`` / ``ffns`` ``"none"``): a
Mamba-2 mixer alone in 8 groups whose gated norm divides each group of
channels by its own RMS, an expert layer alone whose routed and shared
experts are ungated squared-ReLU MLPs (``ffn`` ``"relu2"``, a shared
width of its own), or grouped attention alone that rotates nothing; at
toy widths with the structure of the benchmark's
``nemotron-3-nano-30b``: the pattern ``MEM*EM`` (every neighbour the
cell's ``MEMEM*EME`` has: ``ME``, ``EM``, ``M*``, ``*E``); 8 heads of
16 in 4 groups over a state of 16; 8 query heads of 16 on 2, twice the
hidden width; 16 routed experts of which this program holds 8, 4 a
token, scaled by 2.5, and a shared expert of 48.

The yardstick is ``benchmarks/models/nemotron.py``'s ``Reference``: the
whole sequence at once, the recurrence a scan over POSITIONS from the
zero state, attention a full causal softmax, the expert layer a plain
loop over the held experts; it imports nothing of the program.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_granite_hybrid import (_count_eqns, _named,  # noqa: F401
                                 _recurrence_case, _served_tokens,
                                 f32_matmuls)

from brpc_tpu.models import mamba2_mixer, moe
from brpc_tpu.models import transformer_lm as T
from brpc_tpu.ops import expert_gmm as eg
from brpc_tpu.ops import ssd

PAGE = 16


def _bench(name="tests/toy_nemotron/config.json"):
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


@pytest.fixture(scope="module")
def ref(model):
    """The reference over the toy's weights, compiled once."""
    cfg, m, _lm, params = model
    return m.Reference(cfg, params)


def test_each_layer_is_one_part_with_one_norm(model):
    """The toy's layers hold the leaves of their one part and that
    part's one norm, in the benchmark's tree and in the program's own;
    the pools hold nothing for the part a layer lacks."""
    cfg, m, lm, params = model
    assert m.pattern(cfg) == "MEM*EM"
    assert lm.schedule() == "M-Ma-M" and lm.ffn_schedule() == "nennen"
    assert lm.layer_parts() == {"mixer_only": {"mamba2": 3, "attn": 1},
                                "ffn_only": {"experts": 2}, "both": {}}
    assert set(params["blk1"]) == {"ln2", "moe"}
    assert set(params["blk1"]["moe"]) == {"router", "bias", "w1", "w2",
                                          "ws1", "ws2"}
    # not gated, and the up weights lie as the down weights do
    assert params["blk1"]["moe"]["w1"].shape == (8, 40, 64) \
        == params["blk1"]["moe"]["w2"].shape
    assert params["blk1"]["moe"]["ws1"].shape == (64, 48)
    assert set(params["blk3"]) == {"ln1", "wqkv", "wo"}
    assert {"ln1", "a_log", "norm"} <= set(params["blk0"]) \
        and "ln2" not in params["blk0"]
    own = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), lm))
    assert jax.tree_util.tree_map(lambda a: a.shape, own) \
        == jax.tree_util.tree_map(lambda a: a.shape, params)
    cache = T.empty_paged_cache(lm, 9, 3, PAGE)
    assert sorted(cache) == ["len", "pk3", "pv3", "sc0", "sc2", "sc5",
                             "sh0", "sh2", "sh5"]
    assert lm.expert_layers() == (1, 4) and lm.state_layers() == (0, 2, 5)
    with pytest.raises(AssertionError, match="a mixer, a feed-forward"):
        T.LMConfig(depth=2, mixers=("none", "attn"), ffns=("none", "dense"))


# -- the parts against the reference's --------------------------------------

def test_the_mixer_norms_each_group_by_its_own_rms(model, f32_matmuls):
    """One Mamba-2 mixer of the toy (chunks of 16, 4 groups of heads
    and of the gated norm's 32 channels) against the reference's
    ``_mamba2``; the norm over all of ``d_inner`` parts from it."""
    cfg, m, lm, params = model
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 40, lm.dim))
    whole = T.LMConfig(remat=False, **{**m.lm_kwargs(cfg),
                                       "mamba2_norm_groups": 1})

    @jax.jit
    def run(bp, x):
        return (m._mamba2(x[0], bp, cfg, False),
                mamba2_mixer.prefill(lm, bp, x, jnp.int32(40))[0][0],
                mamba2_mixer.prefill(whole, bp, x, jnp.int32(40))[0][0])

    with jax.default_matmul_precision("highest"):
        want, out, other = (np.asarray(t) for t in run(params["blk0"], x))
    np.testing.assert_allclose(out, want, atol=1e-4)
    assert np.abs(other - want).max() > 1e-2


def test_the_shares_add_up_to_the_uncut_layer(f32_matmuls):
    """The two shares of the toy's 16 experts (0-7 and 8-15), the
    routed parts plus the shared expert counted ONCE, equal the uncut
    layer as the benchmark's reference computes it; each share is the
    reference's given the same share."""
    cfg, m = _bench()
    d, e = cfg["hidden_size"], cfg["moe_intermediate_size"]
    routed, k = cfg["n_routed_experts_published"], cfg["num_experts_per_tok"]

    def share(lo, hi):
        return moe.ExpertConfig(
            dim=d, hidden=e, routed=routed, held=(lo, hi), top_k=k,
            route_scale=cfg["routed_scaling_factor"], shared=1,
            act="relu2",
            shared_dim=cfg["moe_shared_expert_intermediate_size"])

    p = moe.init_served(jax.random.PRNGKey(4), share(0, routed))
    assert p["w1"].shape == (routed, e, d) and p["ws1"].shape == (d, 48)
    t = jnp.asarray(np.random.default_rng(2).normal(size=(24, d))
                    .astype(np.float32))
    half = cfg["n_routed_experts"]
    @jax.jit
    def layers(p, t):
        """The uncut reference, the shared expert, and each share as
        served beside the reference given that share."""
        shares = []
        for lo in (0, half):
            mine = {**p, "w1": p["w1"][lo:lo + half],
                    "w2": p["w2"][lo:lo + half]}
            shares.append(moe.serve(mine, t, share(lo, lo + half))
                          + (m._experts(t, mine, cfg, False,
                                        held=(lo, lo + half)),))
        return (m._experts(t, p, cfg, False, held=(0, routed)),
                moe._mlp(share(0, routed), t, p["ws1"], p["ws2"]), shares)

    with jax.default_matmul_precision("highest"):
        uncut, shared, shares = layers(p, t)
    total, pairs = shared, 0
    for out, counts, want in shares:
        total = total + (out - shared)
        pairs += int(counts[0])
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5)
    assert pairs == 24 * k                   # every pair fell somewhere
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=5e-5)


# -- prefill, insert, paged steps against the reference's full forward --------

_PROGRAMS = {}


def _programs(lm):
    """``(prefill, step, insert)`` jitted once a configuration."""
    key = id(lm)
    if key not in _PROGRAMS:
        prefill, step = T.make_paged_batch_decode(lm, PAGE)
        _PROGRAMS[key] = (lm, jax.jit(prefill), jax.jit(step),
                          jax.jit(T.make_paged_io(lm, PAGE)[2]))
    return _PROGRAMS[key][1:]


def _gaps(model, ref, n_ctx, lm=None, params=None, spoil=None):
    """One session in slot 1 of 2 (bucketed prefill, insert over a
    state the slot's last session left, steps): the paged path's
    logits against the reference's at every served position, in units
    of the position's logit standard deviation."""
    cfg, m, lm0, ref_params = model
    lm = lm0 if lm is None else lm
    params = ref_params if params is None else params
    prefill, step, insert = _programs(lm)
    rng = np.random.default_rng(n_ctx)
    prompt = rng.integers(0, 256, (n_ctx + 1,), dtype=np.int32)
    served = rng.integers(0, 256, (8,), dtype=np.int32)
    bucket = 1
    while bucket < max(n_ctx, 1):
        bucket <<= 1
    ids = np.zeros((bucket,), np.int32)
    ids[:n_ctx] = prompt[:-1]
    bt = np.zeros((2, lm.max_seq // PAGE), np.int32)
    bt[1] = 1 + np.arange(bt.shape[1])
    got = []
    with jax.default_matmul_precision("highest"):
        cache1, _ = prefill(params, ids[None], jnp.int32(n_ctx))
        cache = {k: v + 7.0 if k.startswith(("sh", "sc")) else v
                 for k, v in T.empty_paged_cache(lm, 9, 2, PAGE).items()}
        cache = insert(cache, jnp.asarray(bt[1]), cache1, jnp.int32(1))
        cache["len"] = cache["len"].at[1].set(n_ctx)
        for tok in np.concatenate([prompt[-1:], served[:-1]]):
            if spoil is not None:
                cache = spoil(cache)
            cache, logits, _counts = step(
                params, cache, jnp.asarray(bt), jnp.asarray([0, tok],
                                                            jnp.int32),
                jnp.asarray([False, True]))
            got.append(np.asarray(logits[1]))
    want = ref.served_logits(prompt, served)
    return np.abs(np.stack(got) - want).max(axis=-1) / want.std(axis=-1)


# float32 on both sides: readings 1e-6 to 3e-6 at this size (the chunks'
# products against the reference's positions, summation order alone);
# each wrong form below reads 10 x that limit and more
@pytest.mark.parametrize("n_ctx", [0, 17, 40])
def test_prefill_then_paged_steps_match_the_reference(model, ref,
                                                      f32_matmuls, n_ctx):
    assert _gaps(model, ref, n_ctx).max() < 1e-4


def _wrong_forms(model):
    cfg, m, lm, params = model

    def gated_silu():
        """A gated SiLU expert whose gate is its up product:
        ``silu(h) h`` where the source squares ``relu(h)``."""
        def doubled(mp):
            w1 = mp["w1"].swapaxes(1, 2)
            return {**mp, "w1": jnp.concatenate([w1, w1], axis=-1),
                    "ws1": jnp.concatenate([mp["ws1"]] * 2, axis=-1)}

        twice = {k: {**v, "moe": doubled(v["moe"])} if "moe" in v else v
                 for k, v in params.items()}
        return dict(lm=T.LMConfig(remat=False, **{**m.lm_kwargs(cfg),
                                                  "ffn": "gated_silu"}),
                    params=twice)

    def bf16_state():
        return dict(spoil=lambda cache: {
            k: v.astype(jnp.bfloat16).astype(jnp.float32)
            if k.startswith("sh") else v for k, v in cache.items()})

    return {"gated_silu_experts": gated_silu,
            "one_norm_over_d_inner": lambda: dict(lm=T.LMConfig(
                remat=False, **{**m.lm_kwargs(cfg),
                                "mamba2_norm_groups": 1})),
            "bf16_state": bf16_state}


@pytest.mark.parametrize("form", ["gated_silu_experts",
                                  "one_norm_over_d_inner", "bf16_state"])
def test_a_wrong_form_would_fail_the_tolerance(model, ref, f32_matmuls,
                                               form):
    assert _gaps(model, ref, 17, **_wrong_forms(model)[form]()).max() > 1e-3


# -- the kernels at the cell's structure, interpreted -------------------------

def test_ssd_step_kernel_in_eight_groups(monkeypatch):
    """The step kernel, interpreted, at 16 heads of 64 in EIGHT groups
    (two heads a group fill one row of lanes: a row a group, as the
    cell's 8 heads a group fill four) against the plain scan; an idle
    slot's block is untouched."""
    x, dt, a, b, c, d, s0 = _recurrence_case(8, 2, 1, 16, 64, 8, 8)
    assert ssd._rows_in_one_group(16, 64, 8, 8) \
        and ssd._rows_in_one_group(64, 64, 128, 8)
    active = jnp.asarray([True, False])
    pool = ssd.to_pool(s0)
    want_y, want_s = jax.jit(ssd.sequential)(x, dt, a, b, c, d, s0,
                                             active.astype(jnp.int32))
    y, s = jax.jit(lambda *t: ssd.ssd_step(*t, interpret=True))(
        x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], d, pool, active)
    np.testing.assert_allclose(np.asarray(ssd.from_pool(s, 16))[0],
                               np.asarray(want_s)[0], atol=1e-5)
    np.testing.assert_allclose(np.asarray(y)[0], np.asarray(want_y)[0, 0],
                               atol=1e-4)
    assert np.asarray(s)[1].tobytes() == np.asarray(pool)[1].tobytes()


def test_expert_gmm_at_a_width_that_is_no_lane_multiple():
    """The grouped product, interpreted, at a width of 232 (1.8 lane
    tiles) both ways and with the weights laid transposed, against a
    plain product a group; the whole-width blocks it takes, pinned at
    the cell's 1,856 (14.5 lane tiles)."""
    assert eg._blocks(2688, 1856, 2, eg._BLOCK_BYTES) == (896, 1856)
    assert eg._blocks(1856, 2688, 2, eg._BLOCK_BYTES) == (1856, 896)
    sizes = np.asarray([3, 0, 5, 1], np.int32)
    groups = np.repeat(np.arange(4), sizes)
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    for k, n in ((256, 232), (232, 256)):
        assert eg._blocks(k, n, 4, eg._BLOCK_BYTES)[1] == n
        xs = jax.random.normal(ks[0], (16, k))
        w = jax.random.normal(ks[1], (4, k, n))
        got = eg.expert_gmm(xs, w, jnp.asarray(sizes), interpret=True)
        with jax.default_matmul_precision("highest"):
            want = jnp.einsum("rk,rkn->rn", xs[:9], w[groups])
        np.testing.assert_allclose(np.asarray(got[:9]), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)
        got_t = eg.expert_gmm(xs, w.swapaxes(1, 2), jnp.asarray(sizes),
                              interpret=True, transposed=True)
        np.testing.assert_allclose(np.asarray(got_t[:9]), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)


# -- the counts, against hand arithmetic --------------------------------------

def test_counts_against_hand_arithmetic():
    cfg, m = _bench("configs/nemotron-3-nano-30b.json")
    assert m.pattern(cfg) == "MEMEM*EME" and m.n_parts(cfg) == (4, 4, 1)
    assert len(cfg["hybrid_override_pattern"]) == 52
    w_in = 2688 * (4096 + 6144 + 64)
    assert w_in == 27_697_152
    mixer = w_in + 4096 * 2688 + 5 * 6144 + 3 * 64 + 4096 + 2688
    assert m.mamba2_params(cfg) == mixer == 38_744_896
    assert m.attn_params(cfg) == 2688 * 4608 + 4096 * 2688 + 2688 \
        == 23_399_040
    assert m.expert_params(cfg) == 2 * 2688 * 1856 == 9_977_856
    assert m.shared_params(cfg) == 19_955_712
    assert m.router_params(cfg) == 344_064 + 128
    total = 4 * mixer + 4 * (344_192 + 64 * 9_977_856 + 19_955_712 + 2688) \
        + 23_399_040 + 2 * 65_536 * 2688 + 2688
    assert m.total_params(cfg) == total == 3_166_244_352
    assert round(2 * total / 1e6) == 6332                     # 6.332 GB
    lm = T.LMConfig(remat=False, **m.lm_kwargs(cfg))
    slot_layer = 64 * 64 * 128 * 4 + 3 * 6144 * 4
    assert mamba2_mixer.state_bytes(lm) == slot_layer == 2_170_880
    assert m.state_values(cfg) * 4 == slot_layer
    assert T.state_slot_bytes(lm) == 4 * slot_layer == 8_683_520
    assert round(128 * T.state_slot_bytes(lm) / 1e6) == 1111  # 1.111 GB
    assert T.paged_page_bytes(lm, 16) == 16 * 2048
    assert round(16_385 * T.paged_page_bytes(lm, 16) / 1e6) == 537
    ec = lm.expert_cfg()
    assert (ec.act, ec.gated, ec.shared_dim, ec.route_scale, ec.top_k,
            ec.held, ec.routed) == ("relu2", False, 3712, 2.5, 6, (0, 64),
                                    128)
    assert lm.head_dim * lm.heads == 4096 and lm.heads // lm.kv_heads == 16
    # a step of 128 rows: each held expert is touched with 1 - (1 -
    # 6/128)^128, 99.8%; the experts' weights are 5.1 GB of ~8.4
    touched = 4 * 64 * (1 - (122 / 128) ** 128)
    assert touched / 256 == pytest.approx(0.9979, abs=1e-4)
    lives = [1100] * 128
    flops, nbytes = m.step_work(cfg, lives, 1)
    every_step = total - 4 * 64 * 9_977_856 - 65_536 * 2688
    state = 2.0 * 128 * 4 * slot_layer
    assert nbytes == pytest.approx(2 * (every_step + touched * 9_977_856)
                                   + 2048 * (128 * 1100 + 128) + state)
    assert 2 * touched * 9_977_856 == pytest.approx(5.10e9, rel=1e-2)
    assert 8.0e9 < nbytes < 9.0e9
    counted = {"experts_touched": 250, "local_pairs": 380}
    assert m.expert_work(cfg, lives, 1, counted) == (
        2 * 9_977_856 * 380, 2 * 250 * 9_977_856)
    assert m.ssd_step_work(cfg, lives, 1) == (
        128 * 4 * 5 * 64 * 64 * 128, 2 * 128 * 4 * 64 * 64 * 128 * 4)
    assert [m.kernel_calls(cfg, k) for k in (
        "ssd_step", "paged_decode_attention", "expert_gmm")] == [4, 1, 8]


# -- through the batcher ------------------------------------------------------

def test_batcher_serves_the_references_tokens_and_counts_the_parts(
        model, ref, f32_matmuls):
    """Three sessions on two slots through the batcher: each is served
    what the reference decodes greedily; ``kv_stats()`` names the
    layers by part, the experts' form and the norm's groups, and counts
    the routing of the expert layers alone."""
    cfg, m, lm, params = model
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 256, (n,), dtype=np.int32)
               for n in (5, 17, 7)]
    bat, served = _served_tokens(lm, params, prompts)
    for toks, p in zip(served, prompts):
        toks = np.asarray(toks, np.int32)
        logits = ref.served_logits(p, toks)
        assert (logits.max(axis=-1) - logits[np.arange(6), toks]
                <= 1e-4 * logits.std(axis=-1)).all()
    kv = bat.kv_stats()
    assert kv["schedule"] == lm.layer_parts()
    assert {k: kv["moe"][k] for k in ("layers", "held", "routed", "act",
                                      "gated", "shared_dim")} == {
        "layers": 2, "held": 8, "routed": 16, "act": "relu2",
        "gated": False, "shared_dim": 48}
    assert kv["moe"]["rows"] == 18
    assert 0 < kv["moe"]["local_pairs"] <= 18 * 2 * 4
    assert (kv["mamba2"]["layers"], kv["mamba2"]["groups"],
            kv["mamba2"]["norm_group"]) == (3, 4, 32)
    assert json.loads(json.dumps(kv["schedule"]))["ffn_only"] == {
        "experts": 2}


def test_the_step_names_its_parts(model, monkeypatch):
    """On the TPU the step holds one ``ssd_step`` a Mamba-2 layer where a
    row of lanes lies in one group (the toy in one group of 8 heads of
    16), one paged attention, the grouped product twice an EXPERT layer
    and none elsewhere; the shared expert and the gated norm run under
    the scopes a device trace is read by."""
    from brpc_tpu.ops import device_ops
    cfg, m, lm, params = model
    monkeypatch.setattr(device_ops, "_on_tpu", lambda: True)
    one = T.LMConfig(remat=False, **{**m.lm_kwargs(cfg), "mamba2_groups": 1,
                                     "mamba2_norm_groups": 1})
    step = T.make_paged_batch_decode(one, PAGE)[1]
    args = (T.empty_paged_cache(one, 9, 2, PAGE),
            jnp.zeros((2, one.max_seq // PAGE), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.asarray([True, False]))
    jaxpr = jax.make_jaxpr(step)(jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), one)), *args).jaxpr
    names = ("ssd_step", "paged_decode_attention", "expert_gmm")
    assert [_count_eqns(jaxpr, _named(k)) for k in names] == [3, 1, 4]
    assert [m.kernel_calls(cfg, k) for k in names] == [3, 1, 4]
    monkeypatch.setattr(device_ops, "_on_tpu", lambda: False)
    text = jax.jit(T.make_paged_batch_decode(lm, PAGE)[1]).lower(
        params, T.empty_paged_cache(lm, 9, 2, PAGE),
        *args[1:]).as_text(debug_info=True)
    assert "shared_expert" in text and "mamba2_norm" in text


# -- what declines, by name ---------------------------------------------------

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
    """The prefix cache is there and declines every lookup, counted."""
    from brpc_tpu.kv.pages import PageAllocator, PrefixCache
    pc = PrefixCache(PageAllocator(9, PAGE, 1), state_layers=_lm().has_state)
    assert pc.lookup(np.arange(40, dtype=np.int32)) == ([], 0)
    assert pc.stats()["declined_state"] == 1
    raise T.UnsupportedBlock("declined_state")


# granite's list (tests/test_granite_hybrid.py), case for case
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
    "span_fill": lambda: T.make_paged_span_fill(_lm(), PAGE)(),
    "batcher_park": lambda: _batcher(host_slots=4),
    "batcher_chunked": lambda: _batcher(prefill_chunk_tokens=16),
    "kv_import": lambda: _batcher().join_imported(None, 0, 4, 2, {}),
    "generate": _generate_declines,
    "prefix_cache": _prefix_declines,
    # a layer of one part outside the paged serving factories, in a
    # schedule of attention alone: training and the contiguous cache
    "ffn_less_training": lambda: T.make_forward(T.LMConfig(
        depth=2, ffns=("dense", "none"))),
    "ffn_less_decode": lambda: T.make_decode(T.LMConfig(
        depth=2, ffns=("none", "dense"))),
    # and beside the blocks whose prompts go through the pages
    "ffn_less_window": lambda: T.LMConfig(
        depth=2, heads=4, kv_heads=2, windows=(8, 0), ffns=("dense", "none")),
    "ffn_less_loop": lambda: T.LMConfig(depth=2, passes=2,
                                        ffns=("dense", "none")),
}


@pytest.mark.parametrize("path", sorted(DECLINES))
def test_unported_paths_decline_by_name(path):
    with pytest.raises(T.UnsupportedBlock) as err:
        DECLINES[path]()
    if path.startswith("ffn_less"):
        assert "one part" in str(err.value)


# -- the accepted blocks keep their trees -------------------------------------

# the program's seeded trees of the toy granite and the toy jamba, read
# at the parent of the one-part layers: the number of leaves and the
# first 16 hex digits of the sha256 of the sorted (path, shape, dtype)
# triples, and a few of them written out (a layer of both parts keeps
# both norms and every leaf it had)
TREES = {
    "toy_granite": ("tests/toy_granite/config.json", 71, "c6838438826af538",
                    {"blk0/ln1": (64,), "blk0/ln2": (64,),
                     "blk0/in_proj": (64, 328), "blk0/norm": (128,),
                     "blk2/wqkv": (64, 128), "blk2/moe/w1": (8, 64, 64),
                     "blk2/moe/ws1": (64, 128), "blk2/moe/ws2": (64, 64)}),
    "toy_jamba": ("tests/toy_jamba/config.json", 56, "331f2b24555d3f1f",
                  {"blk0/ln1": (80,), "blk0/ln2": (80,),
                   "blk0/w1": (80, 384), "blk0/w2": (192, 80),
                   "blk0/in_proj": (80, 320)}),
}


@pytest.mark.parametrize("toy", sorted(TREES))
def test_accepted_blocks_keep_their_param_trees(toy):
    import hashlib

    name, leaves, digest, pinned = TREES[toy]
    cfg, m = _bench(name)
    lm = T.LMConfig(remat=False, **m.lm_kwargs(cfg))
    assert not lm.single_part() and not lm.layer_parts()["mixer_only"]
    own = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), lm))
    flat = sorted(("/".join(str(getattr(k, "key", k)) for k in path),
                   tuple(leaf.shape), str(leaf.dtype))
                  for path, leaf in jax.tree_util.tree_flatten_with_path(
                      own)[0])
    assert len(flat) == leaves
    assert hashlib.sha256(repr(flat).encode()).hexdigest()[:16] == digest
    shapes = {path: shape for path, shape, _dtype in flat}
    assert all(shapes[k] == v for k, v in pinned.items())
    assert jax.tree_util.tree_map(lambda a: a.shape, own) \
        == jax.tree_util.tree_map(
            lambda a: a.shape, jax.eval_shape(lambda: m.make_params(cfg, 0)))
