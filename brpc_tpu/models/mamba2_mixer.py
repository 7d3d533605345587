"""The Mamba-2 mixer of a layer schedule (``LMConfig.mixers``
``"mamba2"``): a state-space layer with ONE scalar decay a head over a
``head_dim x state`` matrix state (SSD), as Granite 4.0-H has it, in the
two forms serving needs.

For one position ``x_t`` of width ``dim`` (already normed), ``H`` heads
of ``P``, a state of ``N``, ``G`` groups of heads sharing ``B`` and
``C``, ``d_inner = H P``::

    [z | xBC | dt] = x_t W_in              dim -> 2 d_inner + 2 G N + H
    xBC = silu(conv(xBC) + b_conv)         depthwise, causal, with bias
    [x | B | C] = xBC                      d_inner, G N, G N
    dt_h = softplus(dt_h + dt_bias_h),  A_h = -exp(A_log_h)
    S_h  = exp(dt_h A_h) S_h + (dt_h x_h) B_g^T        P x N, float32
    y_h  = S_h C_g + D_h x_h
    out  = RMSNorm(y * silu(z); g_norm) W_out          each of R groups of
                                                       d_inner / R channels
                                                       by its own RMS

``R`` is ``LMConfig.mamba2_norm_groups``: 1 (all of ``d_inner`` at
once, as Granite's modelling code has it) or ``G`` (Nemotron-H's).

What a sequence carries from token to token is ``S`` (``H x P x N``
float32, laid in the pool as ``ops.ssd.to_pool`` has it: ``N`` on the
sublanes, ``(head, p)`` on the lanes) and the convolution's last
``conv - 1`` inputs, a RING (``kda_mixer.ring_of``): position ``t``'s
``xBC`` in row ``t mod (conv - 1)``, written where it lies by a one-row
scatter that drops an idle slot's row.  Every weight matmul goes
through ``qmatmul`` (bf16 operands, float32 accumulation); the
convolution, ``dt``, the recurrence and the gated norm are float32.

- :func:`prefill`: a whole zero-padded bucket from the zero state in
  the chunked form (``ops.ssd.chunk_scan``, chunks of ``LMConfig
  .mamba2_chunk``), ``dt`` taken as 0 past ``ctx_len`` so the padding
  neither decays nor feeds the state: state and ring are returned AT
  THE TRUE LENGTH;
- :func:`step`: one position for each slot of the state pool; a slot
  that is not ``active`` keeps what it holds (``ops.ssd.ssd_step`` on
  the TPU: its block is not touched at all).
"""

from __future__ import annotations

import math


def conv_width(cfg) -> int:
    """Channels the convolution runs over: ``x``, ``B`` and ``C``."""
    return cfg.mamba2_heads * cfg.mamba2_head_dim \
        + 2 * cfg.mamba2_groups * cfg.mamba2_state


def init_layer(key, cfg) -> dict:
    """Seeded weights of one mixer, in the tree both forms read
    (``a_log`` the log of a rate uniform in 1..16 a head, ``dt_bias``
    with ``softplus(dt_bias)`` log-uniform in 1e-3..1e-1, ``d`` ones,
    ``conv_b`` zeros: Mamba's initialisation)."""
    import jax
    import jax.numpy as jnp

    d, h, kc = cfg.dim, cfg.mamba2_heads, cfg.mamba2_conv
    di, cw = h * cfg.mamba2_head_dim, conv_width(cfg)
    ks = jax.random.split(key, 5)

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

    dt = jnp.exp(jax.random.uniform(ks[3], (h,), jnp.float32)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return {
        "in_proj": normal(ks[0], (d, di + cw + h), d),
        "conv_w": normal(ks[1], (kc, cw), kc),
        "conv_b": jnp.zeros((cw,), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),          # softplus^-1
        "a_log": jnp.log(jax.random.uniform(ks[4], (h,), jnp.float32,
                                            1.0, 16.0)),
        "d": jnp.ones((h,), jnp.float32),
        "norm": jnp.ones((di,), jnp.float32),
        "out_proj": normal(ks[2], (di, d), di),
    }


def state_shapes(cfg, batch: int) -> tuple:
    """``(S, ring)`` shapes of ``batch`` sequences' state, ``S`` as the
    pool lays it (``ops.ssd.pool_shape``)."""
    from ..ops.ssd import pool_shape

    return ((batch,) + pool_shape(cfg.mamba2_heads, cfg.mamba2_head_dim,
                                  cfg.mamba2_state),
            (batch, cfg.mamba2_conv - 1, conv_width(cfg)))


def state_bytes(cfg) -> int:
    """Bytes one sequence's state takes in one Mamba-2 layer
    (float32)."""
    return 4 * (cfg.mamba2_heads * cfg.mamba2_head_dim * cfg.mamba2_state
                + (cfg.mamba2_conv - 1) * conv_width(cfg))


def _split(cfg, xbc):
    """``xBC (..., conv_width)`` -> ``x (..., H, P)``, ``B``, ``C
    (..., G, N)``."""
    import jax.numpy as jnp

    h, p = cfg.mamba2_heads, cfg.mamba2_head_dim
    g, n = cfg.mamba2_groups, cfg.mamba2_state
    x, b, c = jnp.split(xbc, [h * p, h * p + g * n], axis=-1)
    lead = xbc.shape[:-1]
    return (x.reshape(lead + (h, p)), b.reshape(lead + (g, n)),
            c.reshape(lead + (g, n)))


def _out(cfg, bp, y, z):
    """The gated norm and ``W_out``: ``y * silu(z)``, each of the
    ``mamba2_norm_groups`` groups of channels divided by its own RMS
    (one group: all of ``d_inner``), times the gain (``mamba2_norm`` in
    a device trace)."""
    import jax

    from ..ops.quant import qmatmul
    from .transformer_lm import _rmsnorm

    g = cfg.mamba2_norm_groups
    with jax.named_scope("mamba2_norm"):
        v = y * jax.nn.silu(z)
        if g == 1:
            v = _rmsnorm(v, bp["norm"], cfg.norm_eps)
        else:
            v = _rmsnorm(v.reshape(v.shape[:-1] + (g, -1)),
                         bp["norm"].reshape(g, -1),
                         cfg.norm_eps).reshape(v.shape)
    return qmatmul(v, bp["out_proj"])


def prefill(cfg, bp, x, ctx_len):
    """``x (1, s, dim)``, normed, zero-padded past ``ctx_len`` ->
    ``(out (1, s, dim), S, ring)``: the state after position ``ctx_len
    - 1``, in the pool's layout, and the convolution's inputs at
    ``ctx_len - conv + 1 .. ctx_len - 1`` (zeros before the sequence's
    start), each in the ring's row of its position."""
    import jax
    import jax.numpy as jnp

    from ..ops import ssd
    from ..ops.quant import qmatmul
    from .kda_mixer import ring_of

    s, kc = x.shape[1], cfg.mamba2_conv
    di, cw = cfg.mamba2_heads * cfg.mamba2_head_dim, conv_width(cfg)
    z, xbc, dt = jnp.split(qmatmul(x, bp["in_proj"]), [di, di + cw], axis=-1)
    pad = jnp.pad(xbc, ((0, 0), (kc - 1, 0), (0, 0)))
    ring = ring_of(pad, ctx_len, kc - 1)
    xbc = jax.nn.silu(sum(bp["conv_w"][j] * pad[:, j:j + s]
                          for j in range(kc)) + bp["conv_b"])
    xs, b, c = _split(cfg, xbc)
    dt = jnp.where(jnp.arange(s)[None, :, None] < ctx_len,
                   jax.nn.softplus(dt + bp["dt_bias"]), 0.0)
    y, state = ssd.chunk_scan(
        xs, dt, -jnp.exp(bp["a_log"]), b, c, bp["d"],
        jnp.zeros((1,) + xs.shape[2:] + (cfg.mamba2_state,), jnp.float32),
        cfg.mamba2_chunk)
    return _out(cfg, bp, y.reshape(1, s, di), z), ssd.to_pool(state), ring


def step(cfg, bp, x, state, ring, active, lens):
    """``x (slots, dim)``, normed; ``state``, ``ring`` the layer's
    state pool; ``lens (slots,)`` the slots' lengths, the position this
    step is -> ``(out (slots, dim), state, ring)`` with the state of
    ``active`` slots advanced one position."""
    import jax
    import jax.numpy as jnp

    from ..ops import ssd
    from ..ops.quant import qmatmul
    from .kda_mixer import ring_conv, ring_write

    di, cw = cfg.mamba2_heads * cfg.mamba2_head_dim, conv_width(cfg)
    z, row, dt = jnp.split(qmatmul(x, bp["in_proj"]), [di, di + cw], axis=-1)
    phase = lens % (cfg.mamba2_conv - 1)
    xs, b, c = _split(cfg, jax.nn.silu(
        ring_conv(ring, row, bp["conv_w"], phase) + bp["conv_b"]))
    y, state = ssd.step(xs, jax.nn.softplus(dt + bp["dt_bias"]),
                        -jnp.exp(bp["a_log"]), b, c, bp["d"], state, active)
    return (_out(cfg, bp, y.reshape(-1, di), z), state,
            ring_write(ring, row, active, phase))
