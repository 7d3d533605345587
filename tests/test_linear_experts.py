"""Gated delta-rule linear-attention layers (``LMConfig.mixers``
``"kda"``) whose matrix state lives a slot, three to every latent layer
without a query down-projection and without positions, each under
dropless routed experts, at toy widths with the structure of the
benchmark's ``kimi-linear-48b``: a leading dense layer and four expert
layers, KDA at layers 1, 2, 3, 5 and latent attention at 4; 4 heads of
16; 64 routed experts of which this program holds 4, 4 a token, one
shared expert.

The yardstick is ``benchmarks/models/kimi_linear.py``'s ``Reference``:
the whole sequence at once, the recurrence a scan over positions from
the zero state, the EXPANDED attention as a full causal softmax, the
expert layer a plain loop over the held experts; it imports nothing of
the program.
"""

import json
import os
import struct
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.models import kda_mixer, mla_mixer, moe
from brpc_tpu.models import transformer_lm as T
from brpc_tpu.ops import delta_rule, paged_attention, quant
from brpc_tpu.streaming import StreamOptions

PAGE = 16


def _bench(name="tests/toy_kimi_linear/config.json"):
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


# -- the recurrence: two kernels against the plain scan ------------------------

def _recurrence_case(seed, batch, n_pos, heads, d):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (batch, n_pos, heads, d)

    def unit(t):
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    return (unit(jax.random.normal(ks[0], shape)) * d ** -0.5,
            unit(jax.random.normal(ks[1], shape)),
            jax.random.normal(ks[2], shape),
            jnp.exp(-3.0 * jax.random.uniform(ks[3], shape)),
            jax.nn.sigmoid(jax.random.normal(ks[4], shape[:-1])),
            jax.random.normal(ks[5], (batch, heads, d, d)))


def _by_hand(q, k, v, a, b, s0, n):
    """One sequence, position by position in numpy float64, straight
    from the layer's equation: ``S = (I - b k k^T) Diag(a) S + b k
    v^T``."""
    q, k, v, a, b = (np.asarray(x, np.float64) for x in (q, k, v, a, b))
    s = np.asarray(s0, np.float64).copy()
    ys = np.zeros(v.shape)
    eye = np.eye(q.shape[-1])
    for t in range(n):
        for h in range(q.shape[1]):
            kk = np.outer(k[t, h], k[t, h])
            s[h] = (eye - b[t, h] * kk) @ (a[t, h][:, None] * s[h]) \
                + b[t, h] * np.outer(k[t, h], v[t, h])
            ys[t, h] = s[h].T @ q[t, h]
    return ys, s


def test_the_sequential_form_is_the_layers_equation():
    q, k, v, a, b, s0 = _recurrence_case(0, 1, 9, 2, 8)
    y, s = delta_rule.sequential(q, k, v, a, b, s0, jnp.asarray([7]))
    want_y, want_s = _by_hand(q[0], k[0], v[0], a[0], b[0], s0[0], 7)
    np.testing.assert_allclose(np.asarray(s[0]), want_s, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y[0, :7]), want_y[:7], atol=1e-5)


# name: (batch, positions, heads, d, lens); a chunk is 32 positions
SCAN_CASES = {
    "one_chunk_short": (1, 8, 2, 8, [5]),
    "two_sequences_padded": (2, 64, 2, 8, [50, 64]),
    "length_that_is_no_multiple_of_the_chunk": (1, 96, 3, 16, [77]),
    "nothing_live": (1, 32, 2, 8, [0]),
    "a_whole_register_a_head": (1, 32, 2, 128, [31]),
}


@pytest.mark.parametrize("name", sorted(SCAN_CASES))
def test_kda_scan_kernel(name):
    """The sequence kernel, interpreted: the state AT THE TRUE LENGTH
    (a padded position decays nothing and writes nothing) and every
    live position's output, BIT-equal to the plain scan (the same
    products in the same association: ``k (b u)``)."""
    batch, n_pos, heads, d, lens = SCAN_CASES[name]
    q, k, v, a, b, s0 = _recurrence_case(len(name), batch, n_pos, heads, d)
    lens = jnp.asarray(lens, jnp.int32)
    want_y, want_s = delta_rule.sequential(q, k, v, a, b, s0, lens)
    y, s = delta_rule.kda_scan(q, k, v, a, b, s0, lens, interpret=True)
    assert np.asarray(s).tobytes() == np.asarray(want_s).tobytes()
    for i, n in enumerate(np.asarray(lens)):
        assert np.asarray(y[i, :n]).tobytes() \
            == np.asarray(want_y[i, :n]).tobytes()
    if name == "nothing_live":
        assert (np.asarray(s) == np.asarray(s0)).all()


@pytest.mark.parametrize("active", [
    [1, 0, 1, 1, 0, 0], [0] * 6, [1] * 6, [0, 0, 0, 0, 0, 1], [1, 0, 0, 0, 0, 0]])
def test_kda_step_kernel_updates_in_place_and_leaves_idle_slots(active):
    """The step kernel, interpreted: active slots move one position as
    the plain scan moves them, an inactive slot's state is bit-equal
    to what it was (it is never read: it may hold anything, NaN
    included), and the pool handed in is the pool handed back."""
    q, k, v, a, b, s0 = _recurrence_case(sum(active), 6, 1, 2, 8)
    active = jnp.asarray(active, bool)
    idle = ~np.asarray(active)
    pool = np.array(s0)
    pool[idle, 0, 0, 0] = np.nan
    want_y, want_s = delta_rule.sequential(
        q, k, v, a, b, s0, active.astype(jnp.int32))
    y, s = delta_rule.kda_step(q[:, 0], k[:, 0], v[:, 0], a[:, 0], b[:, 0],
                               jnp.asarray(pool), active, interpret=True)
    y, s = np.asarray(y), np.asarray(s)
    # (bit-equal on the chip, ``chip_kimi_linear.py numerics``; the CPU
    # backend's batched reduction rounds the last bit another way)
    np.testing.assert_allclose(s[~idle], np.asarray(want_s)[~idle],
                               atol=1e-6)
    np.testing.assert_allclose(y[~idle], np.asarray(want_y)[~idle, 0],
                               atol=1e-6)
    assert (y[idle] == 0).all()
    assert s[idle].tobytes() == pool[idle].tobytes()
    # in place: the kernel's pool result (0) aliases its pool operand
    # (5: after the two prefetched scalars, the columns, ``b`` and
    # ``v``)
    jaxpr = jax.make_jaxpr(lambda *x: delta_rule.kda_step(
        *x, interpret=True))(q[:, 0], k[:, 0], v[:, 0], a[:, 0], b[:, 0],
                             s0, active).jaxpr
    calls = []
    _count_eqns(jaxpr, lambda e: e.primitive.name == "pallas_call"
                and calls.append(e))
    assert len(calls) == 1
    assert tuple(calls[0].params["input_output_aliases"]) == ((5, 0),)
    assert calls[0].invars[5].aval.shape == s0.shape


# -- the mixer: prefill and step against a position at a time -----------------

def _mixer_case(seed=0, slots=3):
    lm = T.LMConfig(vocab=32, dim=24, heads=2, depth=1, max_seq=64,
                    remat=False, mixers=("kda",), kda_heads=2,
                    kda_head_dim=8, norm_eps=1e-5)
    bp = kda_mixer.init_layer(jax.random.PRNGKey(seed), lm)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, 40, lm.dim))
    return lm, bp, x


def _stepwise(lm, bp, x, n):
    """``n`` positions of one sequence through :func:`kda_mixer.step`
    from the zero state, slot 1 of 2 (slot 0 idle)."""
    state, tail = (jnp.zeros(s, jnp.float32)
                   for s in kda_mixer.state_shapes(lm, 2))
    active = jnp.asarray([False, True])
    outs = []
    for t in range(n):
        out, state, tail = kda_mixer.step(
            lm, bp, jnp.stack([x[0, t] * 0, x[0, t]]), state, tail, active,
            jnp.asarray([0, t], jnp.int32))
        outs.append(out[1])
    assert not np.asarray(state[0]).any() and not np.asarray(tail[0]).any()
    return (jnp.stack(outs) if outs else None), state[1], tail[1]


@pytest.mark.parametrize("ctx_len", [0, 1, 2, 3, 17, 33, 40])
def test_prefill_returns_the_state_at_the_true_length(f32_matmuls, ctx_len):
    """A zero-padded bucket through :func:`kda_mixer.prefill` against
    the same positions one at a time: outputs of the live positions,
    the matrix state and the convolutions' tails as they stand after
    ``ctx_len`` positions, whatever follows in the bucket.  The tails
    are a ring: position ``t``'s input lies in row ``t mod 3`` (the
    lengths cover the three phases)."""
    lm, bp, x = _mixer_case()
    live = (jnp.arange(x.shape[1]) < ctx_len)[None, :, None]
    out, state, tail = kda_mixer.prefill(lm, bp, jnp.where(live, x, 0.0),
                                         jnp.int32(ctx_len))
    want_out, want_state, want_tail = _stepwise(lm, bp, x, ctx_len)
    assert tail.shape == (1, 3, 6, 8)
    inputs = np.asarray(x[0] @ bp["wqkv"]).reshape(-1, 6, 8)
    for t in range(ctx_len - 3, ctx_len):
        np.testing.assert_allclose(
            np.asarray(tail[0, t % 3]), inputs[t] if t >= 0 else 0.0,
            atol=1e-6)
    np.testing.assert_allclose(np.asarray(state[0]), np.asarray(want_state),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(tail[0]), np.asarray(want_tail),
                               atol=1e-6)
    if ctx_len:
        np.testing.assert_allclose(np.asarray(out[0, :ctx_len]),
                                   np.asarray(want_out), atol=1e-4)


def test_the_mixer_is_the_references_layer(model, f32_matmuls):
    """One KDA mixer of the toy against the reference's ``_kda`` on the
    same leaves."""
    cfg, m, lm, params = model
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 32, lm.dim))
    with jax.default_matmul_precision("highest"):
        out, _s, _t = kda_mixer.prefill(lm, params["blk0"], x, jnp.int32(32))
        want = m._kda(x[0], params["blk0"], cfg, False)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               atol=1e-4)


# -- prefill, insert, paged steps against the reference's full forward --------

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
        # what the slot's last session left must not show
        for name in self.cache:
            if name.startswith(("sh", "sc")):
                self.cache[name] = self.cache[name] + 7.0
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


# float32 on both sides: readings 2e-6 to 2e-5 at this size; a state
# kept in bf16 reads 1e-3 and more
@pytest.mark.parametrize("n_ctx", [0, 1, 3, 15, 16, 17, 31, 33])
def test_prefill_then_paged_steps_match_the_reference(model, f32_matmuls,
                                                      n_ctx):
    assert _gaps(model, n_ctx).max() < 1e-4


def test_a_bf16_state_would_fail_the_tolerance(model, f32_matmuls):
    def spoil(run):
        for name in run.cache:
            if name.startswith("sh"):
                run.cache[name] = run.cache[name].astype(
                    jnp.bfloat16).astype(jnp.float32)

    assert _gaps(model, 17, spoil).max() > 3e-4


def test_served_precision_stays_near_the_reference(model):
    """bf16 operands as served: far from float32's agreement, near
    enough that greedy tokens rarely part (the cell's own limits are
    read on the chip: benchmarks/KIMI_LINEAR.md)."""
    gaps = _gaps(model, 17)
    assert 1e-4 < gaps.max() < 0.3


def test_the_state_pool_lies_beside_the_latent_pool(model):
    cfg, m, lm, params = model
    cache = T.empty_paged_cache(lm, 9, 3, PAGE)
    shapes = {k: v.shape for k, v in cache.items()}
    assert shapes == {
        **{f"sh{i}": (3, 4, 16, 16) for i in (0, 1, 2, 4)},
        # a ring of three rows of ``3 H x d``: a row is one block
        **{f"sc{i}": (3, 3, 12, 16) for i in (0, 1, 2, 4)},
        "pc3": (9, PAGE, 128), "len": (3,)}
    assert T.state_kinds(lm) == {"kda": {"layers": 4, "slot_bytes":
                                         4 * 4 * (4 * 16 * 16 + 3 * 192)}}
    assert T.state_slot_bytes(lm) == 4 * kda_mixer.state_bytes(lm)
    assert lm.schedule() == "kkkmk" and lm.ffn_schedule() == "deeee"
    assert lm.has_state and lm.has_latent and not lm.rope
    # both kinds of state layer in one schedule
    both = T.LMConfig(depth=3, mixers=("ssm", "kda", "attn"), rope=False,
                      kda_heads=2, kda_head_dim=8, remat=False)
    assert set(T.state_kinds(both)) == {"ssm", "kda"}
    assert both.state_layers() == (0, 1)


# -- latent attention: 32 heads, a direct query, nothing rotated --------------

def test_mla_decode_attention_at_32_heads():
    """The latent kernel, interpreted, at the cell's head count and
    widths against the plain gather."""
    r = np.random.default_rng(32)
    slots, heads, kl, rope, page, pps = 3, 32, 512, 64, 16, 24
    pages = slots * pps + 1
    ql = r.normal(size=(slots, heads, kl)).astype(np.float32)
    qr = r.normal(size=(slots, heads, rope)).astype(np.float32)
    pc = r.normal(size=(pages, page, 640)).astype(np.float32)
    pc[..., kl + rope:] = 0.0
    bt = (1 + r.permutation(pages - 1)).reshape(slots, pps).astype(np.int32)
    pos = np.asarray([300, 0, 257], np.int32)
    scale = (128 + rope) ** -0.5
    with jax.default_matmul_precision("highest"):
        want = paged_attention.mla_reference(ql, qr, pc, bt, pos, scale)
    for b in range(slots):
        pc[bt[b, pos[b] // page + 1:]] = np.nan
    got = paged_attention.mla_decode_attention(ql, qr, pc, bt, pos, scale,
                                               interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2)


def test_a_direct_query_packs_into_the_same_form(model, f32_matmuls):
    """``wq`` (no ``q_lora_rank``) is relaid as ``wq_b`` is: ``wq_h (H,
    nope + rope, dim)``; no position enters: a latent layer's output
    for a row does not change with where the row stands."""
    cfg, m, lm, params = model
    bp = params["blk3"]
    assert "wq" in bp and "wq_a" not in bp
    packed = mla_mixer.pack(lm, bp)
    assert packed["wq_h"].shape == (4, 24, 64) and "wq" not in packed
    assert mla_mixer.pack(lm, packed) is packed
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 16, lm.dim))
    assert T._rope_at(lm, jnp.arange(16)) is None
    with jax.default_matmul_precision("highest"):
        out, rows = mla_mixer.prefill(lm, bp, x, None)
        want = m._mla(x[0], bp, cfg, False)
        # the first row alone sees only itself, wherever it stands
        alone, _ = mla_mixer.prefill(lm, bp, x[:, :1], None)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(alone[0, 0]), np.asarray(out[0, 0]),
                               atol=1e-5)
    assert rows.shape == (1, lm.max_seq, 128)


# -- the share of the experts ties to the uncut layer -------------------------

def test_the_shares_add_up_to_the_uncut_layer(f32_matmuls):
    """Over all sixteen shares of the toy's 64 experts, the routed
    parts plus the shared expert counted ONCE equal the uncut layer,
    which the benchmark's reference computes with every expert."""
    cfg, m = _bench()
    d, e = cfg["hidden_size"], cfg["moe_intermediate_size"]
    routed, k = cfg["num_experts_published"], cfg["num_experts_per_token"]
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
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(m._experts(
                    t, mine, cfg, False, held=(lo, lo + 4))), atol=2e-5)
    assert pairs == 24 * k                   # every pair fell somewhere
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=5e-5)


# -- the counts, against hand arithmetic --------------------------------------

def test_counts_against_hand_arithmetic():
    cfg, m = _bench("configs/kimi-linear-48b.json")
    assert m.mixers(cfg) == ("kda", "kda", "kda", "mla") * 3 + ("kda",)
    assert m.n_mixers(cfg) == (10, 3) and m.n_layers(cfg) == (1, 12)
    proj = 2304 * 4096
    kda = 4 * proj + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 \
        + 4 * 3 * 4096 + 32 + 4096 + 4096 + 128
    assert m.kda_params(cfg) == kda == 39_518_368             # 39.52 M
    mla = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304
    assert m.mla_params(cfg) == mla == 29_114_368             # 29.11 M
    expert = 3 * 2304 * 1024
    assert m.expert_params(cfg) == expert == 7_077_888        # 7.078 M
    assert m.router_params(cfg) == 2304 * 256 == 589_824      # 0.590 M
    assert 32 * expert == 226_492_416                         # 226.49 M
    dense_mlp = 3 * 2304 * 9216
    assert m.dense_mlp_params(cfg) == dense_mlp == 63_700_992  # 63.70 M
    ffn = 2304 * 256 + 33 * expert
    assert m.expert_ffn_params(cfg) == ffn
    assert round((kda + ffn) / 1e4) == 27368                  # 273.68 M
    assert round((mla + ffn) / 1e4) == 26327                  # 263.27 M
    assert round((kda + dense_mlp) / 1e4) == 10322            # 103.22 M
    table = 2 * 20480 * 2304
    assert table == 94_371_840                                # 94.37 M
    total = 10 * kda + 3 * mla + dense_mlp + 12 * ffn + table
    assert m.total_params(cfg) == total
    assert round(2 * total / 1e7) == 690                      # 6.90 GB
    # whole, a layer's 256 experts are 3.62 GB
    assert round(2 * 256 * expert / 1e7) == 362
    lm = T.LMConfig(remat=False, **m.lm_kwargs(cfg))
    assert kda_mixer.state_bytes(lm) == 4 * (32 * 128 * 128 + 3 * 3 * 4096) \
        == 2_097_152 + 147_456 == 2_244_608
    assert T.state_slot_bytes(lm) == 22_446_080               # 22.4 MB
    assert round(128 * T.state_slot_bytes(lm) / 1e7) == 287   # 2.87 GB
    assert T.paged_page_bytes(lm, 16) == 16 * 3 * 640 * 4     # as it lies
    assert round(16385 * T.paged_page_bytes(lm, 16) / 1e7) == 201  # 2.01 GB
    assert m.kda_state_values(cfg) * 4 == 2_244_608
    # a step of 128 rows at 1,100 live positions each
    lives = [1100] * 128
    flops, nbytes = m.step_work(cfg, lives, 1)
    every_step = 10 * kda + 3 * mla + dense_mlp \
        + 12 * (ffn - 32 * expert) + 2304 * 20480
    touched = 12 * 32 * (1 - (31 / 32) ** 128)
    assert touched / 384 == pytest.approx(0.983, abs=0.001)
    state = 2.0 * 128 * 10 * 2_244_608
    assert state == pytest.approx(5.75e9, rel=2e-3)           # in and out
    latent = 3 * 576 * 4 * (128 * 1100 + 128)
    assert nbytes == pytest.approx(
        2 * (every_step + touched * expert) + latent + state)
    assert 13.0e9 < nbytes < 14.0e9                           # ~13.5 GB
    assert 2 * touched * expert == pytest.approx(5.34e9, rel=2e-3)
    assert 2 * every_step == pytest.approx(1.37e9, rel=5e-3)
    counted = {"experts_touched": 300, "local_pairs": 500}
    _f, nb2 = m.step_work(cfg, lives, 1, counted)
    assert nb2 == 2 * (every_step + 300 * expert) + latent + state
    att = 3 * 2 * 32 * (576 + 512) * 128 * 1100
    assert m.mla_decode_work(cfg, lives, 1) \
        == (att, 3 * 576 * 4 * 128 * 1100)
    update = 7 * 32 * 128 * 128 + 2 * 4 * 3 * 4096
    assert m.kda_step_work(cfg, lives, 1) == (128 * 10 * update, state)
    per_row = 2 * (every_step - 3 * 512 * 32 * 256) \
        + 3 * 2 * 32 * 512 * 256 + 10 * update
    assert flops == pytest.approx(
        128 * per_row + att + 2 * expert * 12 * 128 * 8 * 32 / 256)
    assert m.kernel_calls(cfg, "mla_decode_attention") == 3
    assert m.kernel_calls(cfg, "kda_step") == 10
    assert m.kernel_calls(cfg, "expert_gmm") == 24
    assert m.expert_work(cfg, lives, 1, counted) == (2 * expert * 500,
                                                     2 * 300 * expert)
    f_fill, b_fill = m.fill_work(cfg, 0, 512)
    assert b_fill > 2 * (every_step - 2304 * 20480) + 10 * 2_244_608
    assert f_fill > 512 * 2 * (every_step - 2304 * 20480)


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


def _served_tokens(lm, params, prompts, n=6, slots=2):
    from brpc_tpu.models.lm_service import ContinuousBatcher
    bat = ContinuousBatcher(lm, params, slots=slots, page=PAGE,
                            pages=8 * slots + 1, idle_linger_s=0.2)
    streams = [_FakeStream() for _ in prompts]
    with jax.default_matmul_precision("highest"):
        for st, p in zip(streams, prompts):
            bat.join(st, p, n)
        deadline = time.monotonic() + 240.0
        while not all(s.closed for s in streams) \
                and time.monotonic() < deadline:
            time.sleep(0.002)
    assert [s.close_reason for s in streams] == ["finished"] * len(prompts)
    return bat, [s.tokens for s in streams]


def test_batcher_serves_the_references_tokens_and_counts_the_state(
        model, f32_matmuls):
    """Three sessions on two slots (one waits, one slot is reused: its
    state is written over): each is served what the reference decodes
    greedily; ``kv_stats()["kda"]`` counts the blocks the step read and
    wrote and the prompts filled, ``["state"]`` names the kinds, the
    prefix cache declines, ``LM.Info`` and the fingerprint show the
    schedule."""
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
    slot_bytes = 4 * 4 * (4 * 16 * 16 + 3 * 192)
    assert kv["state"]["kinds"] == {"kda": {"layers": 4,
                                            "slot_bytes": slot_bytes}}
    assert kv["state"]["bytes"] == 2 * slot_bytes
    assert kv["state"]["inserts"] == 3 and kv["state"]["releases"] == 3
    assert kv["state"]["slot_steps"] == 2 * kv["steps"]
    assert kv["kda"] == {"layers": 4, "slot_bytes": slot_bytes,
                         "steps": kv["steps"], "slot_steps": 18,
                         "fills": 3, "fill_rows": 4 + 17 + 0}
    assert kv["moe"]["rows"] == 18 and kv["moe"]["layers"] == 4
    assert kv["latent"]["layers"] == 1
    assert kv["prefix"]["declined_state"] == 3
    assert bat._alloc.page_bytes == T.paged_page_bytes(lm, PAGE) \
        == PAGE * 128 * 4
    svc = LMService(cfg=lm, params=params, page=PAGE, decode_slots=2)
    info = json.loads(svc.Info(None, b""))
    assert info["mixers"] == "kkkmk" and info["ffns"] == "deeee"
    assert info["state_pool"] == {"slots": 2, "bytes": 2 * slot_bytes,
                                  "kinds": kv["state"]["kinds"]}
    assert info["latent_pool"]["layers"] == 1
    fp = svc.model_fingerprint()
    assert b":kkkmk:" in fp and b":4x16x4" in fp and b":deeee:" in fp


def test_128_slots_run_through_the_batcher(model, f32_matmuls):
    """More sessions than any cell has run at once: 130 callers on 128
    slots, each served its reference's tokens, two of them in a reused
    slot."""
    cfg, m, lm, params = model
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, (1 + i % 7,), dtype=np.int32)
               for i in range(130)]
    bat, served = _served_tokens(lm, params, prompts, n=3, slots=128)
    assert all(len(t) == 3 for t in served)
    ref = m.Reference(cfg, params)
    for i in (0, 64, 127, 128, 129):
        toks = np.asarray(served[i], np.int32)
        logits = ref.served_logits(prompts[i], toks)
        assert (logits.max(axis=-1) - logits[np.arange(3), toks]
                <= 1e-4 * logits.std(axis=-1)).all()
    kv = bat.kv_stats()
    assert kv["state"]["slots"] == 128 and kv["kda"]["slot_steps"] == 390
    assert kv["kda"]["fills"] == 130


def test_the_session_span_shows_the_schedule(model):
    from brpc_tpu.models.lm_service import ContinuousBatcher
    cfg, m, lm, params = model

    class Span:
        notes = []

        def annotate(self, note):
            self.notes.append(note)

        def finish(self, *_a):
            pass

    bat = ContinuousBatcher(lm, params, slots=2, page=PAGE, pages=17,
                            idle_linger_s=0.2)
    st = _FakeStream()
    bat.join(st, np.asarray([3, 4, 5], np.int32), 2, span=Span())
    deadline = time.monotonic() + 120.0
    while not st.closed and time.monotonic() < deadline:
        time.sleep(0.002)
    assert Span.notes[:2] == ["lm_join", "lm_schedule:kkkmk"]


# -- the step names its kernels ------------------------------------------------

def _count_eqns(jaxpr, pred) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += bool(pred(eqn))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count_eqns(sub, pred)
    return n


def _named(name):
    def pred(eqn):
        return eqn.primitive.name == "pallas_call" \
            and name in str(eqn.params.get("name", "")) + str(
                eqn.params.get("name_and_src_info", ""))
    return pred


def test_the_programs_call_the_delta_rule_once_a_kda_layer(model,
                                                           monkeypatch):
    """With the kernels chosen (as on the TPU) the step holds one
    ``kda_step`` a KDA layer and the prefill one ``kda_scan``, beside
    the latent kernel once a latent layer."""
    from brpc_tpu.ops import device_ops
    cfg, m, lm, params = model
    monkeypatch.setattr(device_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(delta_rule, "_use_kernels", lambda d: True)
    prefill, step = T.make_paged_batch_decode(lm, PAGE)
    cache = T.empty_paged_cache(lm, 9, 2, PAGE)
    bt = jnp.zeros((2, lm.max_seq // PAGE), jnp.int32)
    jaxpr = jax.make_jaxpr(step)(
        params, cache, bt, jnp.zeros((2,), jnp.int32),
        jnp.asarray([True, False])).jaxpr
    assert _count_eqns(jaxpr, _named("kda_step")) == 4
    assert _count_eqns(jaxpr, _named("mla_decode_attention")) == 1
    assert _count_eqns(jaxpr, _named("expert_gmm")) == 8
    jaxpr = jax.make_jaxpr(prefill)(
        params, jnp.zeros((1, 32), jnp.int32), jnp.int32(20)).jaxpr
    assert _count_eqns(jaxpr, _named("kda_scan")) == 4


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


def _prefix_declines():
    """The prefix cache is there and declines every lookup, counted."""
    from brpc_tpu.kv.pages import PageAllocator, PrefixCache
    pc = PrefixCache(PageAllocator(9, PAGE, 1), state_layers=_lm().has_state)
    assert pc.lookup(np.arange(40, dtype=np.int32)) == ([], 0)
    assert pc.stats()["declined_state"] == 1
    raise T.UnsupportedBlock("declined_state")


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
    # what is still not served, by name
    "experts_beside_ssm": lambda: T.LMConfig(
        depth=2, mixers=("attn", "ssm"), ffns=("dense", "experts"),
        expert_dim=8, experts_routed=4, experts_top_k=2),
    "window_beside_kda": lambda: T.LMConfig(
        depth=2, mixers=("attn", "kda"), windows=(8, 0), kv_heads=1),
    "latent_layers_that_differ_in_rotation": lambda: _lm(
        mixers=("mla",) * 5, ropes=(True, False, True, False, True)),
}


@pytest.mark.parametrize("path", sorted(DECLINES))
def test_unported_paths_decline_by_name(path):
    with pytest.raises(T.UnsupportedBlock):
        DECLINES[path]()


# -- the convolutions' tails are a ring ---------------------------------------
# (these run last: up to here the file loads the six workers' machine as it
# did before them, and the time-bound tests beside it see what they saw)

def _time_ordered_step(lm, bp, x, state, tail, active):
    """The step with the tails in TIME order, ``tail (slots, 3, 3 H
    d)`` rewritten whole where the slot is active, as the program had
    it before the ring."""
    qkv = quant.qmatmul(x, bp["wqkv"])
    window = jnp.concatenate([tail, qkv[:, None]], axis=1)
    tail = jnp.where(active[:, None, None], window[:, 1:], tail)
    qkv = jax.nn.silu(jnp.sum(bp["conv_w"][None] * window, axis=1))
    q, k, v, a, b = kda_mixer._recurrence_inputs(
        lm, bp, x, qkv.reshape(len(x), 3 * lm.kda_heads, lm.kda_head_dim))
    y, state = delta_rule.sequential(
        q[:, None], k[:, None], v[:, None], a[:, None], b[:, None], state,
        active.astype(jnp.int32))
    y = jnp.where(active[:, None, None], y[:, 0], 0.0)
    return kda_mixer._out(lm, bp, x, y), state, tail


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["plain", "kda_step"])
@pytest.mark.parametrize("ctx_len", [5, 6, 7])
def test_the_ring_is_the_time_ordered_tail(f32_matmuls, monkeypatch,
                                           ctx_len, kernel):
    """Three prompts prefilled at ``ctx_len`` (the three phases), then
    six steps under masks that part the slots' phases: outputs, states
    and the ring against the time-ordered form, on the plain path and
    through the interpreted kernel; an idle slot's three rows are
    bit-identical after a step."""
    monkeypatch.setattr(delta_rule, "_use_kernels", lambda d: kernel)
    lm, bp, _x = _mixer_case()
    xs = jax.random.normal(jax.random.PRNGKey(ctx_len), (3, 16, lm.dim))
    live = (jnp.arange(8) < ctx_len)[None, :, None]
    filled = [kda_mixer.prefill(lm, bp, jnp.where(live, xs[i:i + 1, :8],
                                                  0.0), jnp.int32(ctx_len))
              for i in range(3)]
    state = jnp.concatenate([f[1] for f in filled])
    ring = jnp.concatenate([f[2] for f in filled])
    lens = np.full((3,), ctx_len)
    # in time order: row j of a slot is position ``len - 3 + j``
    want_tail = jnp.stack([ring[i, (np.arange(3) + lens[i]) % 3]
                           for i in range(3)]).reshape(3, 3, -1)
    want_state = state
    masks = [[1, 1, 0], [1, 0, 1], [0, 0, 0], [1, 1, 1], [0, 1, 0],
             [1, 0, 0]]
    for t, mask in enumerate(masks):
        active = jnp.asarray(mask, bool)
        x = xs[:, 8 + t]
        before = np.asarray(ring)
        out, state, ring = kda_mixer.step(lm, bp, x, state, ring, active,
                                          jnp.asarray(lens, jnp.int32))
        want_out, want_state, want_tail = _time_ordered_step(
            lm, bp, x, want_state, want_tail, active)
        lens = lens + np.asarray(mask)
        on = np.asarray(active)
        np.testing.assert_allclose(np.asarray(out)[on],
                                   np.asarray(want_out)[on], atol=1e-5)
        np.testing.assert_allclose(np.asarray(state),
                                   np.asarray(want_state), atol=1e-5)
        for i in range(3):
            got = np.asarray(ring[i, (np.arange(3) + lens[i]) % 3])
            assert got.reshape(3, -1).tobytes() \
                == np.asarray(want_tail[i]).tobytes()
            if not on[i]:
                assert np.asarray(ring[i]).tobytes() == before[i].tobytes()
    assert sorted(lens % 3) == [0, 1, 2]        # the phases have parted


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["plain", "kda_step"])
def test_the_step_writes_no_whole_tail_pool(model, monkeypatch, kernel):
    """The traced step moves ONE row a slot of a KDA layer's ring: no
    ``select_n`` and no ``concatenate`` (nor anything else) yields an
    array the size of a whole tail pool but the one-row scatter, off
    the TPU and, with every kernel in the trace, on it."""
    from brpc_tpu.ops import device_ops
    cfg, m, lm, params = model
    if kernel:
        monkeypatch.setattr(device_ops, "_on_tpu", lambda: True)
        monkeypatch.setattr(delta_rule, "_use_kernels", lambda d: True)
    _prefill, step = T.make_paged_batch_decode(lm, PAGE)
    cache = T.empty_paged_cache(lm, 9, 2, PAGE)
    pool = cache["sc0"].shape
    jaxpr = jax.make_jaxpr(step)(
        params, cache, jnp.zeros((2, lm.max_seq // PAGE), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.asarray([True, False])).jaxpr
    whole = []
    _count_eqns(jaxpr, lambda e: any(
        getattr(v.aval, "shape", None) == pool for v in e.outvars)
        and whole.append(e.primitive.name))
    assert whole == ["scatter"] * 4


def _sequential_as_it_was(q, k, v, a, b, s0, lens):
    """:func:`delta_rule.sequential` with the write as ``(b k) u^T``,
    the association the yardstick had before ``b`` became the head's
    scalar on the row ``u``."""
    a, b = delta_rule._frozen_past(a, b, lens)

    def step(s, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        s = a_t[..., None] * s
        u = v_t - jnp.sum(k_t[..., None] * s, axis=-2)
        s = s + (b_t[..., None] * k_t)[..., None] * u[..., None, :]
        return s, jnp.sum(q_t[..., None] * s, axis=-2)

    s, ys = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, a, b)))
    return jnp.moveaxis(ys, 0, 1), s


@pytest.mark.parametrize("n_pos", [1, 8, 64])
def test_the_yardstick_moved_by_rounding_alone(n_pos):
    """The kernels are held bit-equal to ``sequential``, which changed
    its association WITH them (``k (b u)`` for ``(b k) u``): so the
    yardstick's own move is pinned here, at the cell's widths (32 heads
    of 128): after ``n_pos`` positions the new form is within four
    units in the last place of the largest entry of the old one."""
    q, k, v, a, b, s0 = _recurrence_case(5, 2, n_pos, 32, 128)
    lens = jnp.asarray([n_pos, max(n_pos - 3, 0)], jnp.int32)
    y, s = delta_rule.sequential(q, k, v, a, b, s0, lens)
    was_y, was_s = _sequential_as_it_was(q, k, v, a, b, s0, lens)
    ulp = 2.0 ** -23
    for got, was in ((s, was_s), (y, was_y)):
        got, was = np.asarray(got), np.asarray(was)
        assert np.abs(got - was).max() <= 4 * ulp * np.abs(was).max()
