"""Mamba-2 state-space layers (``LMConfig.mixers`` ``"mamba2"``: one
scalar decay a head over a matrix state, prompts in the chunked SSD
form) beside grouped attention that rotates nothing, every layer under
softmax-routed experts and a summed shared MLP, with Granite's four
scalars (embedding, residual, attention and logit), at toy widths with
the structure of the benchmark's ``granite-4.0-h-small``: Mamba-2 at
layers 0, 1, 3, 4 and attention at 2; 8 heads of 16 over a state of 16
in 2 groups; 16 routed experts of which this program holds 8, 4 a
token.

The yardstick is ``benchmarks/models/granite.py``'s ``Reference``: the
whole sequence at once, the recurrence a scan over POSITIONS (not the
program's chunks) from the zero state, attention a full causal softmax,
the expert layer the source's top-k gating and a plain loop over the
held experts; it imports nothing of the program.
"""

import json
import os
import struct
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.models import mamba2_mixer, moe
from brpc_tpu.models import transformer_lm as T
from brpc_tpu.ops import quant, ssd
from brpc_tpu.streaming import StreamOptions

PAGE = 16


def _bench(name="tests/toy_granite/config.json"):
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


# -- the recurrence: the chunked form and the kernel against the plain scan ---

def _recurrence_case(seed, batch, n_pos, heads, p, n, groups):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(ks[0], (batch, n_pos, heads, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (batch, n_pos, heads))
                            - 2.0),
            -jax.random.uniform(ks[2], (heads,), minval=1.0, maxval=16.0),
            jax.random.normal(ks[3], (batch, n_pos, groups, n)),
            jax.random.normal(ks[4], (batch, n_pos, groups, n)),
            jax.random.normal(ks[5], (heads,)),
            jax.random.normal(ks[6], (batch, heads, p, n)))


def _by_hand(x, dt, a, b, c, d, s0, n_pos):
    """One sequence, position by position in numpy float64, straight
    from the layer's equation: ``S = exp(dt A) S + dt x B^T``, ``y = S
    C + D x``, head ``h`` in group ``h // (heads / groups)``."""
    x, dt, a, b, c, d = (np.asarray(t, np.float64) for t in (x, dt, a, b, c,
                                                             d))
    s = np.asarray(s0, np.float64).copy()
    heads, groups = x.shape[1], b.shape[1]
    ys = np.zeros(x.shape)
    for t in range(n_pos):
        for h in range(heads):
            g = h // (heads // groups)
            s[h] = np.exp(dt[t, h] * a[h]) * s[h] \
                + dt[t, h] * np.outer(x[t, h], b[t, g])
            ys[t, h] = s[h] @ c[t, g] + d[h] * x[t, h]
    return ys, s


def test_the_sequential_form_is_the_layers_equation():
    x, dt, a, b, c, d, s0 = _recurrence_case(0, 1, 9, 4, 8, 16, 2)
    y, s = ssd.sequential(x, dt, a, b, c, d, s0, jnp.asarray([7]))
    want_y, want_s = _by_hand(x[0], dt[0], a, b[0], c[0], d, s0[0], 7)
    np.testing.assert_allclose(np.asarray(s[0]), want_s, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y[0, :7]), want_y[:7], atol=1e-5)


# name: (positions, chunk, groups): a few chunks, a partial last one
CHUNK_CASES = {"one_chunk": (8, 8, 1), "three_and_a_part": (53, 16, 2),
               "shorter_than_a_chunk": (5, 256, 1)}


@pytest.mark.parametrize("name", sorted(CHUNK_CASES))
def test_the_chunked_form_is_the_scan(name):
    """:func:`ssd.chunk_scan` from a state that is not zero against the
    scan over positions: every output and the state it leaves."""
    n_pos, chunk, groups = CHUNK_CASES[name]
    x, dt, a, b, c, d, s0 = _recurrence_case(n_pos, 2, n_pos, 4, 8, 16,
                                             groups)
    want_y, want_s = ssd.sequential(x, dt, a, b, c, d, s0,
                                    jnp.full((2,), n_pos, jnp.int32))
    y, s = ssd.chunk_scan(x, dt, a, b, c, d, s0, chunk)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=2e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=2e-5)


# (heads, P): one row of 32 lanes; two heads a row of 128; a head a row
@pytest.mark.parametrize("heads,p", [(4, 8), (4, 64), (3, 128)])
def test_the_pool_lays_n_on_the_sublanes_and_head_p_on_the_lanes(heads, p):
    """:func:`ssd.to_pool` puts entry ``(h, p, n)`` of a slot's state at
    ``(row, n, lane)`` with ``row w + lane = h P + p``, and
    :func:`ssd.from_pool` takes it back bit for bit."""
    s = jax.random.normal(jax.random.PRNGKey(p), (2, 3, heads, p, 16))
    pool = ssd.to_pool(s)
    rows, n, w = ssd.pool_shape(heads, p, 16)
    assert pool.shape == (2, 3, rows, n, w) and rows * w == heads * p
    assert w == (128 if heads * p % 128 == 0 else heads * p)
    flat = np.asarray(s).reshape(2, 3, heads * p, n)
    want = flat.reshape(2, 3, rows, w, n).swapaxes(-1, -2)
    assert np.asarray(pool).tobytes() == want.tobytes()
    assert np.asarray(ssd.from_pool(pool, heads)).tobytes() \
        == np.asarray(s).tobytes()


@pytest.mark.parametrize("active", [
    [1, 0, 1, 1, 0, 0], [0] * 6, [1] * 6, [0, 0, 0, 0, 0, 1]])
def test_ssd_step_kernel_updates_in_place_and_leaves_idle_slots(active):
    """The step kernel, interpreted, at 4 heads of 64 in 2 groups (two
    heads share a row of lanes, a row a group): active slots move one
    position as the plain scan moves them (both read in the equation's
    layout), an inactive slot's state is bit-equal to what it was (it
    is never read: it may hold anything, NaN included) and its ``y`` is
    zero, and the pool handed in is the pool handed back."""
    x, dt, a, b, c, d, s0 = _recurrence_case(sum(active), 6, 1, 4, 64, 16,
                                             2)
    active = jnp.asarray(active, bool)
    idle = ~np.asarray(active)
    pool = np.array(ssd.to_pool(s0))
    assert pool.shape == (6, 2, 16, 128)
    pool[idle, 0, 0, 0] = np.nan
    want_y, want_s = ssd.sequential(x, dt, a, b, c, d, s0,
                                    active.astype(jnp.int32))
    args = (x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], d)
    y, s = ssd.ssd_step(*args, jnp.asarray(pool), active, interpret=True)
    y, s = np.asarray(y), np.asarray(s)
    np.testing.assert_allclose(np.asarray(ssd.from_pool(s, 4))[~idle],
                               np.asarray(want_s)[~idle], atol=1e-6)
    np.testing.assert_allclose(y[~idle], np.asarray(want_y)[~idle, 0],
                               atol=1e-5)
    assert (y[idle] == 0).all()
    assert s[idle].tobytes() == pool[idle].tobytes()
    # in place: the kernel's pool result (0) aliases its pool operand
    # (8: after the two prefetched scalars, dt, the decay, D, x, B, C)
    jaxpr = jax.make_jaxpr(lambda *t: ssd.ssd_step(*t, interpret=True))(
        *args, jnp.asarray(pool), active).jaxpr
    calls = []
    _count_eqns(jaxpr, lambda e: e.primitive.name == "pallas_call"
                and calls.append(e))
    assert len(calls) == 1
    assert tuple(calls[0].params["input_output_aliases"]) == ((8, 0),)
    assert calls[0].invars[8].aval.shape == pool.shape


# (heads, P, N, groups): rows that straddle two groups; N not whole
# sublane tiles
@pytest.mark.parametrize("shape", [(4, 64, 16, 4), (4, 64, 12, 2)])
def test_a_pool_the_kernel_cannot_take_runs_the_plain_scan(shape,
                                                           monkeypatch):
    """Where a row of lanes would straddle a group (``B`` and ``C``
    would differ along it) or ``N`` is not whole sublane tiles,
    :func:`ssd.step` runs the plain scan on the TPU too, in the pool's
    one layout, and the kernel refuses the shape."""
    from brpc_tpu.ops import device_ops
    heads, p, n, groups = shape
    monkeypatch.setattr(device_ops, "_on_tpu", lambda: True)
    assert not ssd._use_kernel(heads, p, n, groups)
    assert ssd._use_kernel(heads, p, 16, 2)
    x, dt, a, b, c, d, s0 = _recurrence_case(1, 3, 1, heads, p, n, groups)
    active = jnp.asarray([True, False, True])
    args = (x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], d, ssd.to_pool(s0),
            active)
    jaxpr = jax.make_jaxpr(ssd.step)(*args).jaxpr
    assert _count_eqns(jaxpr, lambda e: e.primitive.name == "pallas_call") \
        == 0
    monkeypatch.setattr(device_ops, "_on_tpu", lambda: False)
    y, s = ssd.step(*args)
    want_y, want_s = ssd.sequential(x, dt, a, b, c, d, s0,
                                    active.astype(jnp.int32))
    np.testing.assert_allclose(np.asarray(ssd.from_pool(s, heads)),
                               np.asarray(want_s), atol=1e-6)
    np.testing.assert_allclose(np.asarray(y)[[0, 2]],
                               np.asarray(want_y)[[0, 2], 0], atol=1e-5)
    with pytest.raises(ValueError, match="one group each"):
        ssd.ssd_step(*args, interpret=True)


# -- the mixer: the chunked prefill against a position at a time --------------

def _mixer_lm(chunk=256):
    return T.LMConfig(vocab=32, dim=16, heads=2, depth=1, max_seq=1024,
                      remat=False, mixers=("mamba2",), mamba2_heads=4,
                      mamba2_head_dim=8, mamba2_state=16, mamba2_groups=2,
                      mamba2_chunk=chunk, norm_eps=1e-5)


@pytest.fixture(scope="module")
def stepped():
    """One sequence of 773 positions through :func:`mamba2_mixer.step`
    from the zero state, slot 1 of 2 (slot 0 idle): the outputs, and
    the state and ring after each position."""
    lm = _mixer_lm()
    bp = mamba2_mixer.init_layer(jax.random.PRNGKey(0), lm)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 1024, lm.dim))
    step = jax.jit(lambda x, s, r, lens: mamba2_mixer.step(
        lm, bp, x, s, r, jnp.asarray([False, True]), lens))
    state, ring = (jnp.zeros(s, jnp.float32)
                   for s in mamba2_mixer.state_shapes(lm, 2))
    outs, states, rings = [], [state[1]], [ring[1]]
    with jax.default_matmul_precision("highest"), \
            pytest.MonkeyPatch.context() as mp:
        # (a module's fixture is made before ``f32_matmuls`` is)
        mp.setattr(quant, "qmatmul", lambda x, w: x @ w)
        for t in range(773):
            out, state, ring = step(jnp.stack([x[0, t] * 0, x[0, t]]),
                                    state, ring,
                                    jnp.asarray([0, t], jnp.int32))
            outs.append(out[1])
            states.append(state[1])
            rings.append(ring[1])
    assert not np.asarray(state[0]).any() and not np.asarray(ring[0]).any()
    return lm, bp, x, jnp.stack(outs), states, rings


# the chunk is 256: one position, a chunk less one, a chunk, a chunk and
# one, three chunks and five
@pytest.mark.parametrize("ctx_len", [1, 255, 256, 257, 3 * 256 + 5])
def test_the_chunked_prefill_is_the_recurrence_at_the_true_length(
        f32_matmuls, stepped, ctx_len):
    """A zero-padded bucket of 1,024 through :func:`mamba2_mixer.prefill`
    against the same positions one at a time: outputs of the live
    positions, the state and the convolution's ring as they stand
    after ``ctx_len`` positions, whatever follows in the bucket (the
    padding's ``dt`` is 0: it neither decays nor feeds the state)."""
    lm, bp, x, outs, states, rings = stepped
    live = (jnp.arange(x.shape[1]) < ctx_len)[None, :, None]
    with jax.default_matmul_precision("highest"):
        out, state, ring = mamba2_mixer.prefill(
            lm, bp, jnp.where(live, x, 1e3), jnp.int32(ctx_len))
    assert ring.shape == (1, 3, 32 + 2 * 2 * 16)
    np.testing.assert_allclose(np.asarray(state[0]),
                               np.asarray(states[ctx_len]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(ring[0]),
                               np.asarray(rings[ctx_len]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out[0, :ctx_len]),
                               np.asarray(outs[:ctx_len]), atol=1e-4)


def test_the_mixers_step_is_the_same_through_the_kernel(f32_matmuls,
                                                        monkeypatch):
    """:func:`mamba2_mixer.step` as the TPU runs it (the interpreted
    kernel: 4 heads of 32 in one group fill a row of lanes) against the
    plain scan, nine positions from a state that is not zero, slot 0
    idle: the outputs and the pool, which has one layout on both."""
    lm = T.LMConfig(vocab=32, dim=16, heads=2, depth=1, max_seq=1024,
                    remat=False, mixers=("mamba2",), mamba2_heads=4,
                    mamba2_head_dim=32, mamba2_state=16, mamba2_groups=1,
                    norm_eps=1e-5)
    bp = mamba2_mixer.init_layer(jax.random.PRNGKey(2), lm)
    x = jax.random.normal(jax.random.PRNGKey(3), (9, 3, lm.dim))
    shape, ring_shape = mamba2_mixer.state_shapes(lm, 3)
    assert shape == (3, 1, 16, 128)
    state0 = jax.random.normal(jax.random.PRNGKey(4), shape)
    active = jnp.asarray([False, True, True])

    def run():
        state, ring = state0, jnp.zeros(ring_shape)
        outs = []
        for t in range(9):
            out, state, ring = mamba2_mixer.step(
                lm, bp, x[t], state, ring, active, jnp.full((3,), t))
            outs.append(out)
        return np.asarray(jnp.stack(outs)), np.asarray(state)

    with jax.default_matmul_precision("highest"):
        want_out, want_state = run()
        monkeypatch.setattr(ssd, "_use_kernel", ssd._rows_in_one_group)
        jaxpr = jax.make_jaxpr(lambda s: mamba2_mixer.step(
            lm, bp, x[0], s, jnp.zeros(ring_shape), active,
            jnp.zeros((3,), jnp.int32)))(state0).jaxpr
        assert _count_eqns(jaxpr, _named("ssd_step")) == 1
        out, state = run()
    np.testing.assert_allclose(out, want_out, atol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=1e-5)
    assert state[0].tobytes() == np.asarray(state0[0]).tobytes()


def test_the_mixer_is_the_references_layer(model, f32_matmuls):
    """One Mamba-2 mixer of the toy (chunks of 16, two groups) against
    the reference's ``_mamba2`` on the same leaves: 40 positions, three
    chunks."""
    cfg, m, lm, params = model
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 40, lm.dim))
    with jax.default_matmul_precision("highest"):
        out, _s, _r = mamba2_mixer.prefill(lm, params["blk0"], x,
                                           jnp.int32(40))
        want = m._mamba2(x[0], params["blk0"], cfg, False)
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


def _gaps(model, n_ctx, spoil=None, params=None, seed=0):
    """The paged path's logits against the reference's at every served
    position, in units of the position's logit standard deviation."""
    cfg, m, lm, ref_params = model
    rng = np.random.default_rng(seed + n_ctx)
    prompt = rng.integers(0, 256, (n_ctx + 1,), dtype=np.int32)
    served = rng.integers(0, 256, (10,), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        run = _Paged(lm, ref_params if params is None else params,
                     prompt[:-1])
        got = []
        for tok in np.concatenate([prompt[-1:], served[:-1]]):
            if spoil is not None:
                spoil(run)
            got.append(run.feed(tok)[0])
    want = m.Reference(cfg, ref_params).served_logits(prompt, served)
    return np.abs(np.stack(got) - want).max(axis=-1) / want.std(axis=-1)


# float32 on both sides: readings 3e-6 to 5e-6 at this size (the chunks'
# products against the reference's positions, summation order alone);
# each spoiled path below reads 10 x that limit and more
@pytest.mark.parametrize("n_ctx", [0, 1, 3, 15, 16, 17, 33, 47])
def test_prefill_then_paged_steps_match_the_reference(model, f32_matmuls,
                                                      n_ctx):
    assert _gaps(model, n_ctx).max() < 1e-4


def test_a_bf16_state_would_fail_the_tolerance(model, f32_matmuls):
    def spoil(run):
        for name in run.cache:
            if name.startswith("sh"):
                run.cache[name] = run.cache[name].astype(
                    jnp.bfloat16).astype(jnp.float32)

    assert _gaps(model, 17, spoil).max() > 1e-3


def test_the_ssd_without_d_would_fail_the_tolerance(model, f32_matmuls):
    """The program's ``D`` zeroed (the reference keeps it)."""
    _cfg, _m, _lm, params = model
    no_d = {k: ({**v, "d": v["d"] * 0.0} if "d" in v else v)
            if k.startswith("blk") else v for k, v in params.items()}
    assert _gaps(model, 17, params=no_d).max() > 1e-3


def test_the_ssd_without_the_gate_would_fail_the_tolerance(
        model, f32_matmuls, monkeypatch):
    """The gated norm without ``silu(z)``."""
    from brpc_tpu.models.transformer_lm import _rmsnorm

    def ungated(cfg, bp, y, z):
        return quant.qmatmul(_rmsnorm(y, bp["norm"], cfg.norm_eps),
                             bp["out_proj"])

    monkeypatch.setattr(mamba2_mixer, "_out", ungated)
    assert _gaps(model, 17).max() > 1e-3


def test_served_precision_stays_near_the_reference(model):
    """bf16 operands as served: far from float32's agreement, near
    enough that greedy tokens rarely part (the cell's own limits are
    read on the chip: benchmarks/GRANITE.md)."""
    gaps = _gaps(model, 17)
    assert 1e-4 < gaps.max() < 0.3


def test_each_scalar_reaches_the_logits(model, f32_matmuls):
    """The toy's four scalars differ from the defaults, and each moves
    the program's logits: a program that left one out would part from
    the reference by more than the tolerance above."""
    cfg, m, lm, params = model
    assert (lm.embed_scale, lm.residual_scale, lm.attn_scale,
            lm.logit_scale) == (3.0, 0.5, 0.1, 0.25)
    assert lm.attn_scale != lm.head_dim ** -0.5
    prefill = T.make_prefill(lm)
    ids = jnp.asarray(np.arange(1, 17, dtype=np.int32))[None]
    with jax.default_matmul_precision("highest"):
        base = prefill(params, ids, jnp.int32(16))[1]
        for key, plain in (("embed_scale", 1.0), ("residual_scale", 1.0),
                           ("attn_scale", None), ("logit_scale", 1.0)):
            other = T.LMConfig(remat=False,
                               **{**m.lm_kwargs(cfg), key: plain})
            got = T.make_prefill(other)(params, ids, jnp.int32(16))[1]
            gap = np.abs(np.asarray(got - base)).max() / np.asarray(base).std()
            assert gap > 1e-2, key


# -- the state pool, the schedule ---------------------------------------------

def test_the_state_pool_lies_beside_the_pages(model):
    cfg, m, lm, params = model
    cache = T.empty_paged_cache(lm, 9, 3, PAGE)
    shapes = {k: v.shape for k, v in cache.items()}
    conv = 8 * 16 + 2 * 2 * 16
    assert shapes == {
        # 8 heads of 16 = one row of 128 lanes over the state's 16
        # sublanes (``ssd.to_pool``)
        **{f"sh{i}": (3, 1, 16, 128) for i in (0, 1, 3, 4)},
        # a ring of three rows of the convolution's channels
        **{f"sc{i}": (3, 3, conv) for i in (0, 1, 3, 4)},
        "pk2": (9, PAGE * 2, 16), "pv2": (9, PAGE * 2, 16), "len": (3,)}
    slot_bytes = 4 * 4 * (8 * 16 * 16 + 3 * conv)
    assert T.state_kinds(lm) == {"mamba2": {"layers": 4,
                                            "slot_bytes": slot_bytes}}
    assert mamba2_mixer.state_bytes(lm) == slot_bytes // 4
    assert lm.schedule() == "MMaMM" and lm.ffn_schedule() == "eeeee"
    assert lm.has_state and lm.has_mamba2 and not lm.rope
    # every kind keeps its letter, the new one a letter of its own
    assert len(set(T.MIXER_INITIALS.values())) == len(T.MIXER_INITIALS)
    assert T.LMConfig(depth=5, mixers=("attn", "ssm", "mla", "kda",
                                       "mamba2"), kv_lora_rank=8,
                      qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
                      rope=False).schedule() == "asmkM"


# -- the share of the experts ties to the uncut layer -------------------------

def test_the_shares_add_up_to_the_uncut_layer(f32_matmuls):
    """The two shares of the toy's 16 experts (0-7 and 8-15), the
    routed parts plus the shared MLP counted ONCE, equal the uncut
    layer, which the benchmark's reference computes with every expert
    through the source's own gating (a softmax of the chosen logits):
    ``moe.route``'s softmax over all experts, renormalised over the
    chosen, is that gating."""
    cfg, m = _bench()
    d, e = cfg["hidden_size"], cfg["intermediate_size"]
    routed, k = cfg["num_local_experts_published"], cfg["num_experts_per_tok"]
    sh = cfg["shared_intermediate_size"] // e

    def share(lo, hi):
        return moe.ExpertConfig(dim=d, hidden=e, routed=routed,
                                held=(lo, hi), top_k=k, shared=sh,
                                bias=False, scoring="softmax")

    p = moe.init_served(jax.random.PRNGKey(4), share(0, routed))
    t = jnp.asarray(np.random.default_rng(2).normal(size=(24, d))
                    .astype(np.float32))
    half = cfg["num_local_experts"]
    with jax.default_matmul_precision("highest"):
        uncut = m._experts(t, p, cfg, False, held=(0, routed))
        shared = moe._mlp(share(0, routed), t, p["ws1"], p["ws2"])
        total, pairs = shared, 0
        for lo in (0, half):
            mine = {**p, "w1": p["w1"][lo:lo + half],
                    "w2": p["w2"][lo:lo + half]}
            out, counts = moe.serve(mine, t, share(lo, lo + half))
            total = total + (out - shared)
            pairs += int(counts[0])
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(m._experts(
                    t, mine, cfg, False, held=(lo, lo + half))), atol=2e-5)
    assert pairs == 24 * k                   # every pair fell somewhere
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=5e-5)


# -- the counts, against hand arithmetic --------------------------------------

def test_counts_against_hand_arithmetic():
    cfg, m = _bench("configs/granite-4.0-h-small.json")
    assert m.mixers(cfg) == ("mamba2",) * 5 + ("attn",) + ("mamba2",) * 4
    assert m.n_mixers(cfg) == (9, 1)
    w_in = 4096 * (8192 + 8448 + 128)
    assert w_in == 68_681_728
    mixer = w_in + 8192 * 4096 + 4 * 8448 + 8448 + 3 * 128 + 8192
    assert m.mamba2_params(cfg) == mixer == 102_286_976
    router, shared = 4096 * 72, 3 * 4096 * 1536
    assert (router, shared) == (294_912, 18_874_368)
    assert m.layer_params(cfg, "mamba2") == mixer + 8192 + router + shared \
        == 121_464_448
    assert m.attn_params(cfg) == 4096 * 48 * 128 + 4096 * 4096 \
        == 41_943_040
    assert m.layer_params(cfg, "attn") == 61_120_512
    expert = 3 * 4096 * 768
    assert m.expert_params(cfg) == expert == 9_437_184
    # whole, a Mamba-2 layer is 800.9 M parameters = 1.60 GB
    assert round((121_464_448 + 72 * expert) / 1e5) == 8009
    total = 9 * (121_464_448 + 36 * expert) + 61_120_512 + 36 * expert \
        + 50176 * 4096 + 4096
    assert m.total_params(cfg) == total == 4_757_211_776
    assert round(2 * total / 1e6) == 9514                     # 9.514 GB
    lm = T.LMConfig(remat=False, **m.lm_kwargs(cfg))
    assert mamba2_mixer.state_bytes(lm) == 128 * 64 * 128 * 4 + 3 * 8448 * 4 \
        == 4_194_304 + 101_376 == 4_295_680
    assert m.state_values(cfg) * 4 == 4_295_680
    assert T.state_slot_bytes(lm) == 9 * 4_295_680 == 38_661_120
    assert round(64 * T.state_slot_bytes(lm) / 1e6) == 2474   # 2.474 GB
    assert T.paged_page_bytes(lm, 16) == 16 * 8 * 128 * 2 * 4 == 16 * 8192
    assert round(8193 * T.paged_page_bytes(lm, 16) / 1e6) == 1074
    assert lm.schedule() == "MMMMMaMMMM"
    # a step of 64 rows at 1,100 live positions each
    lives = [1100] * 64
    flops, nbytes = m.step_work(cfg, lives, 1)
    every_step = total - 10 * 36 * expert
    touched = 10 * 36 * (1 - (62 / 72) ** 64)
    assert touched / 360 == pytest.approx(0.9999, abs=1e-4)
    state = 2.0 * 64 * 9 * 4_295_680
    assert state == pytest.approx(4.95e9, rel=1e-3)            # in and out
    assert 2.0 * 64 * 9 * 4_194_304 == pytest.approx(4.83e9, rel=1e-3)
    kv = 8192 * (64 * 1100 + 64)
    assert nbytes == pytest.approx(2 * (every_step + touched * expert) + kv
                                   + state)
    assert 2 * touched * expert == pytest.approx(6.79e9, rel=1e-3)
    assert 14.0e9 < nbytes < 15.5e9
    ssd_flops = 5 * 128 * 64 * 128
    per_row = 2 * (every_step - 4096 * 50176 - 4096) + 2 * 4096 * 50176 \
        + 9 * (ssd_flops + 2 * 4 * 8448)
    assert flops == pytest.approx(64 * per_row + 4 * 4096 * 64 * 1100
                                  + 2 * expert * 10 * 64 * 10 * 36 / 72)
    assert m.ssd_step_work(cfg, lives, 1) == (64 * 9 * ssd_flops,
                                              2 * 64 * 9 * 4_194_304)
    assert [m.kernel_calls(cfg, k) for k in (
        "ssd_step", "paged_decode_attention", "expert_gmm")] == [9, 1, 20]
    counted = {"experts_touched": 300, "local_pairs": 500}
    assert m.expert_work(cfg, lives, 1, counted) == (2 * expert * 500,
                                                     2 * 300 * expert)
    _f0, b0 = m.fill_work(cfg, 0, 512)
    assert b0 > 2 * (every_step - 4096 * 50176) + 9 * 4_295_680


# -- through the batcher ------------------------------------------------------

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


def _served_tokens(lm, params, prompts, n=6, slots=2, span=None):
    from brpc_tpu.models.lm_service import ContinuousBatcher
    bat = ContinuousBatcher(lm, params, slots=slots, page=PAGE,
                            pages=8 * slots + 1, idle_linger_s=0.2)
    streams = [_FakeStream() for _ in prompts]
    with jax.default_matmul_precision("highest"):
        for st, p in zip(streams, prompts):
            bat.join(st, p, n, span=span)
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
    greedily; ``kv_stats()["mamba2"]`` counts the blocks the step read
    and wrote, the prompts filled and their chunks, ``["state"]`` names
    the kind, the prefix cache declines, ``LM.Info``, the fingerprint
    and the session span show the schedule."""
    from brpc_tpu.models.lm_service import LMService
    cfg, m, lm, params = model

    class Span:
        notes = []

        def annotate(self, note):
            self.notes.append(note)

        def finish(self, *_a):
            pass

    ref = m.Reference(cfg, params)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 256, (n,), dtype=np.int32)
               for n in (5, 40, 1)]
    bat, served = _served_tokens(lm, params, prompts, span=Span())
    for toks, p in zip(served, prompts):
        toks = np.asarray(toks, np.int32)
        logits = ref.served_logits(p, toks)
        best = logits.max(axis=-1)
        assert (best - logits[np.arange(6), toks]
                <= 1e-4 * logits.std(axis=-1)).all()
    kv = bat.kv_stats()
    slot_bytes = 4 * 4 * (8 * 16 * 16 + 3 * 192)
    assert kv["state"]["kinds"] == {"mamba2": {"layers": 4,
                                               "slot_bytes": slot_bytes}}
    assert kv["state"]["inserts"] == 3 and kv["state"]["releases"] == 3
    # buckets of 4, 64 and 1 positions, chunks of 16: 1 + 4 + 1
    assert kv["mamba2"] == {"layers": 4, "slot_bytes": slot_bytes,
                            "groups": 2, "norm_group": 128,
                            "steps": kv["steps"], "slot_steps": 18,
                            "fills": 3, "fill_rows": 4 + 39 + 0,
                            "chunks": 6}
    assert kv["moe"]["rows"] == 18 and kv["moe"]["layers"] == 5
    assert kv["moe"]["scoring"] == "softmax"
    assert kv["prefix"]["declined_state"] == 3
    assert Span.notes[:2] == ["lm_join",
                              "lm_schedule:MMaMM:route@ffn/softmax"]
    svc = LMService(cfg=lm, params=params, page=PAGE, decode_slots=2)
    info = json.loads(svc.Info(None, b""))
    assert info["mixers"] == "MMaMM" and info["ffns"] == "eeeee"
    assert info["state_pool"] == {"slots": 2, "bytes": 2 * slot_bytes,
                                  "kinds": kv["state"]["kinds"]}
    assert info["mamba2"] == {"heads": 8, "head_dim": 16, "state": 16,
                              "groups": 2, "conv": 4, "chunk": 16,
                              "layers": 4, "slot_bytes": slot_bytes}
    assert info["scales"] == {"embed": 3.0, "residual": 0.5, "attn": 0.1,
                              "logit": 0.25}
    fp = svc.model_fingerprint()
    assert b":MMaMM:" in fp and b":8x16x16x2x4x16" in fp \
        and b":scaled3.0/0.5/0.1/0.25" in fp


# -- the programs name their kernels ------------------------------------------

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


def test_the_step_calls_ssd_step_once_a_mamba2_layer(model, monkeypatch):
    """On the TPU the step holds one ``ssd_step`` a Mamba-2 layer where
    a row of the pool's lanes lies in one group (the toy in ONE group:
    8 heads of 16 fill a row), none where a row straddles two (the toy
    as it is, 2 groups of 4 heads: half a row each); the paged
    attention once and the grouped expert product twice a layer; the
    prefill's chunks run under ``ssd_chunk_scan``, one scan a Mamba-2
    layer."""
    from brpc_tpu.ops import device_ops
    cfg, m, lm, params = model
    monkeypatch.setattr(device_ops, "_on_tpu", lambda: True)
    one = T.LMConfig(remat=False, **{**m.lm_kwargs(cfg), "mamba2_groups": 1})
    names = ("ssd_step", "paged_decode_attention", "expert_gmm")
    for lm_, want in ((one, [4, 1, 10]), (lm, [0, 1, 10])):
        step = T.make_paged_batch_decode(lm_, PAGE)[1]
        cache = T.empty_paged_cache(lm_, 9, 2, PAGE)
        bt = jnp.zeros((2, lm_.max_seq // PAGE), jnp.int32)
        p_ = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  lm_))
        jaxpr = jax.make_jaxpr(step)(
            p_, cache, bt, jnp.zeros((2,), jnp.int32),
            jnp.asarray([True, False])).jaxpr
        assert [_count_eqns(jaxpr, _named(k)) for k in names] == want
    assert [m.kernel_calls(cfg, k) for k in names] == [4, 1, 10]
    prefill = T.make_paged_batch_decode(lm, PAGE)[0]
    monkeypatch.setattr(device_ops, "_on_tpu", lambda: False)
    text = jax.jit(prefill).lower(params, jnp.zeros((1, 64), jnp.int32),
                                  jnp.int32(40)).as_text(debug_info=True)
    assert "ssd_chunk_scan" in text
    jaxpr = jax.make_jaxpr(prefill)(params, jnp.zeros((1, 64), jnp.int32),
                                    jnp.int32(40)).jaxpr
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 4 and all(e.params["length"] == 4 for e in scans)


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
    # what is still not served, by name: experts beside a Mamba-1 layer
    "experts_beside_ssm": lambda: T.LMConfig(
        depth=2, mixers=("mamba2", "ssm"), ffns=("experts", "experts"),
        expert_dim=8, experts_routed=4, experts_top_k=2),
}


@pytest.mark.parametrize("path", sorted(DECLINES))
def test_unported_paths_decline_by_name(path):
    with pytest.raises(T.UnsupportedBlock):
        DECLINES[path]()


def test_the_decline_names_the_state_kinds_experts_are_served_beside():
    with pytest.raises(T.UnsupportedBlock, match="'kda' or a 'mamba2'"):
        DECLINES["experts_beside_ssm"]()
