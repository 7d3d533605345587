"""The linear-attention mixer of a layer schedule (``LMConfig.mixers``
``"kda"``): a gated delta-rule layer with a decay for each key channel
(Kimi Delta Attention, as Kimi-Linear's ``linear_attn_config`` sizes
it), in the two forms serving needs.

For one position ``x_t`` of width ``dim`` (already normed), ``H`` heads
of size ``d``::

    [q', k', v'] = x_t W_qkv                 dim -> 3 x H x d, no bias
    q, k, v = silu(conv(q')), silu(conv(k')), silu(conv(v'))
                                             depthwise, causal, own taps, no bias
    q_h, k_h = q_h / |q_h| d^-0.5, k_h / |k_h|          L2 norm a head
    g_t    = -exp(A_log_h) softplus((x_t W_fa) W_fb + dt_bias)
                                             dim -> d -> H x d: a log-decay a KEY CHANNEL
    a_t    = exp(g_t)                        in (0, 1)
    b_t    = sigmoid(x_t W_b)                dim -> H: one a head
    S_t,h  = (I - b k k^T) Diag(a) S_{t-1,h} + b k v^T      d x d, float32
    o_h    = S_t,h^T q_h
    out    = [RMSNorm_d(o_h) sigmoid((x_t W_ga) W_gb + b_g)]_h W_o      H x d -> dim

What a sequence carries from token to token is ``S`` (``H x d x d``
float32, key x value: ``ops.delta_rule``'s layout) and the three
convolutions' last ``n = kda_conv - 1`` inputs, a RING of ``n`` rows of
``3 H x d`` (q's heads, then k's, then v's): the input of position ``t``
lies in row ``t mod n``, so a step writes ONE row, over the oldest, at
the slot's length modulo ``n`` (its phase), and the convolution reads
the other rows where they lie, its taps turned by the phase.  A one-row
scatter writes that row where the ring lies: an idle slot's row is
dropped and its ring not touched.  Every weight matmul goes through
``qmatmul`` like the attention layers' (bf16 operands, float32
accumulation); the convolution, the norms, the gates and the
recurrence are float32.

- :func:`prefill`: a whole zero-padded bucket from the zero state, the
  state returned AT THE TRUE LENGTH ``ctx_len``;
- :func:`step`: one position for each slot of the state pool; a slot
  that is not ``active`` keeps what it holds.
"""

from __future__ import annotations

import math

_L2_EPS = 1e-6


def init_layer(key, cfg) -> dict:
    """Seeded weights of one mixer, in the tree both forms read
    (``a_log`` holds the log of a rate uniform in 1..16 a head,
    ``dt_bias`` puts ``softplus(dt_bias)`` log-uniformly in
    1e-3..1e-1, as the state-space layer's does)."""
    import jax
    import jax.numpy as jnp

    d, h, hd, kc = cfg.dim, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
    ks = jax.random.split(key, 10)

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

    dt = jnp.exp(jax.random.uniform(ks[8], (h * hd,), jnp.float32)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return {
        "wqkv": normal(ks[0], (d, 3 * h * hd), d),
        "conv_w": normal(ks[1], (kc, 3 * h * hd), kc),
        "wf_a": normal(ks[2], (d, hd), d),
        "wf_b": normal(ks[3], (hd, h * hd), hd),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),       # softplus^-1(dt)
        "a_log": jnp.log(jax.random.uniform(ks[9], (h,), jnp.float32,
                                            1.0, 16.0)),
        "wb": normal(ks[4], (d, h), d),
        "wg_a": normal(ks[5], (d, hd), d),
        "wg_b": normal(ks[6], (hd, h * hd), hd),
        "bg": jnp.zeros((h * hd,), jnp.float32),
        "o_norm": jnp.ones((hd,), jnp.float32),
        "wo": normal(ks[7], (h * hd, d), h * hd),
    }


def state_shapes(cfg, batch: int) -> tuple:
    """``(S, tail)`` shapes of ``batch`` sequences' state."""
    h, hd = cfg.kda_heads, cfg.kda_head_dim
    return ((batch, h, hd, hd), (batch, cfg.kda_conv - 1, 3 * h, hd))


def state_bytes(cfg) -> int:
    """Bytes one sequence's state takes in one KDA layer (float32)."""
    h, hd = cfg.kda_heads, cfg.kda_head_dim
    return 4 * h * hd * (hd + 3 * (cfg.kda_conv - 1))


def _recurrence_inputs(cfg, bp, x, qkv):
    """``q, k, v, a (..., H, d)`` and ``b (..., H)`` of the recurrence
    from the normed rows ``x`` and the convolved ``qkv (..., 3 H, d)``."""
    import jax
    import jax.numpy as jnp

    from ..ops.quant import qmatmul

    h, hd = cfg.kda_heads, cfg.kda_head_dim
    heads = qkv.shape[:-2] + (h, hd)
    q, k, v = jnp.split(qkv, 3, axis=-2)

    def unit(t):
        return t * jax.lax.rsqrt(
            jnp.sum(t * t, axis=-1, keepdims=True) + _L2_EPS)

    f = qmatmul(qmatmul(x, bp["wf_a"]), bp["wf_b"]) + bp["dt_bias"]
    g = -jnp.exp(bp["a_log"])[:, None] * jax.nn.softplus(f).reshape(heads)
    b = jax.nn.sigmoid(qmatmul(x, bp["wb"]))
    return unit(q) * hd ** -0.5, unit(k), v, jnp.exp(g), b


def _out(cfg, bp, x, y):
    """The head norm, the output gate and ``W_o`` over ``y (..., H,
    d)``."""
    import jax

    from ..ops.quant import qmatmul
    from .transformer_lm import _rmsnorm

    gate = jax.nn.sigmoid(qmatmul(qmatmul(x, bp["wg_a"]), bp["wg_b"])
                          + bp["bg"])
    y = _rmsnorm(y, bp["o_norm"], cfg.norm_eps)
    return qmatmul(y.reshape(gate.shape) * gate, bp["wo"])


def prefill(cfg, bp, x, ctx_len):
    """``x (1, s, dim)``, normed, zero-padded past ``ctx_len`` ->
    ``(out (1, s, dim), S, ring)``: the state after position
    ``ctx_len - 1`` (the recurrence is frozen past it) and the
    convolutions' inputs at ``ctx_len - kda_conv + 1 .. ctx_len - 1``
    (zeros before the sequence's start), each in the ring's row of its
    position."""
    import jax
    import jax.numpy as jnp

    from ..ops import delta_rule
    from ..ops.quant import qmatmul

    s, kc = x.shape[1], cfg.kda_conv
    state_shape, ring_shape = state_shapes(cfg, 1)
    qkv = qmatmul(x, bp["wqkv"])
    pad = jnp.pad(qkv, ((0, 0), (kc - 1, 0), (0, 0)))
    ring = ring_of(pad, ctx_len, kc - 1)
    qkv = jax.nn.silu(sum(bp["conv_w"][j] * pad[:, j:j + s]
                          for j in range(kc)))
    q, k, v, a, b = _recurrence_inputs(
        cfg, bp, x, qkv.reshape((1, s) + ring_shape[2:]))
    y, state = delta_rule.scan(
        q, k, v, a, b, jnp.zeros(state_shape, jnp.float32),
        jnp.reshape(ctx_len, (1,)).astype(jnp.int32))
    return _out(cfg, bp, x, y), state, ring.reshape(ring_shape)


def step(cfg, bp, x, state, ring, active, lens):
    """``x (slots, dim)``, normed; ``state``, ``ring`` the layer's
    state pool; ``lens (slots,)`` the slots' lengths, the position this
    step is -> ``(out (slots, dim), state, ring)`` with the state of
    ``active`` slots advanced one position."""
    import jax

    from ..ops import delta_rule
    from ..ops.quant import qmatmul

    n = cfg.kda_conv - 1
    row = qmatmul(x, bp["wqkv"]).reshape(ring.shape[:1] + ring.shape[2:])
    taps = bp["conv_w"].reshape((n + 1,) + ring.shape[2:])
    phase = lens % n
    qkv = jax.nn.silu(ring_conv(ring, row, taps, phase))
    q, k, v, a, b = _recurrence_inputs(cfg, bp, x, qkv)
    y, state = delta_rule.step(q, k, v, a, b, state, active)
    ring = ring_write(ring, row, active, phase)
    return _out(cfg, bp, x, y), state, ring


# -- the convolutions' ring (the Mamba-2 mixer's too) -------------------------

def ring_of(pad, ctx_len, n: int):
    """The ring a prefill leaves: ``pad (1, n + s, ...)``, a bucket's
    inputs behind ``n`` zero rows -> ``(1, n, ...)``, the inputs of
    positions ``ctx_len - n .. ctx_len - 1`` (zeros before the
    sequence's start), position ``t``'s in row ``t mod n``."""
    import jax
    import jax.numpy as jnp

    tail = jax.lax.dynamic_slice(pad, (0, ctx_len) + (0,) * (pad.ndim - 2),
                                 (1, n) + pad.shape[2:])
    # ``tail[j]`` is position ``ctx_len - n + j``
    return jnp.roll(tail, ctx_len % n, axis=1)


def ring_conv(ring, row, taps, phase):
    """One position of a depthwise causal convolution for each slot:
    ``ring (slots, n, ...)`` the last ``n`` inputs, ``row (slots, ...)``
    this position's, ``taps (n + 1, ...)`` oldest first, ``phase`` the
    slots' lengths modulo ``n`` -> the taps' sum."""
    import jax.numpy as jnp

    n = ring.shape[1]
    turned = [(phase == p)[(slice(None),) + (None,) * (row.ndim - 1)]
              for p in range(n)]

    def past(j):
        # the input of position ``lens - n + j``, in row ``(phase + j)
        # mod n`` of its slot's ring
        rows = ring[:, j]
        for p in range(1, n):
            rows = jnp.where(turned[p], ring[:, (p + j) % n], rows)
        return rows

    return sum(taps[j] * past(j) for j in range(n)) + taps[n] * row


def ring_write(ring, row, active, phase):
    """The ring with each ACTIVE slot's ``row`` over its oldest (row
    ``phase``), in place; an idle slot's place is past its ring, so its
    row is dropped."""
    import jax.numpy as jnp

    n = ring.shape[1]
    return ring.at[jnp.arange(ring.shape[0]),
                   jnp.where(active, phase, n)
                   ].set(row, mode="drop", unique_indices=True,
                         indices_are_sorted=True)
