"""The state-space mixer of a layer schedule (``LMConfig.mixers``): a
Mamba-1 layer as Jamba has it, in the two forms serving needs.

For one position ``x_t`` of width ``dim`` (already normed)::

    [u, z] = x_t W_in                        dim -> 2 d_inner, no bias
    u      = silu(conv(u))                   depthwise, causal, with bias
    [r, B, C] = u W_x                        d_inner -> dt_rank + 2 d_state
    r, B, C = RMSNorm(r), RMSNorm(B), RMSNorm(C)      learned gains
    dt     = softplus(r W_dt + b_dt)         dt_rank -> d_inner
    h_t    = exp(dt (x) A) . h_{t-1} + (dt . u) (x) B,   A = -exp(A_log)
    y      = h_t C + D . u
    out    = (y . silu(z)) W_out             d_inner -> dim, no bias

What a sequence carries from token to token is ``h`` (``d_state x
d_inner`` float32, in ``ops.selective_scan.state_shape``'s layout) and
the convolution's last ``d_conv - 1`` inputs.  Every weight matmul goes
through ``qmatmul`` like the attention layers' (bf16 operands); the
convolution, the norms and the recurrence are float32.

- :func:`prefill`: a whole zero-padded bucket from the zero state, the
  state returned AT THE TRUE LENGTH ``ctx_len``;
- :func:`step`: one position for each slot of the state pool; a slot
  that is not ``active`` keeps what it holds.
"""

from __future__ import annotations

import math


def init_layer(key, cfg) -> dict:
    """Seeded weights of one mixer, in the tree both forms read
    (``A_log`` holds ``log(1..d_state)`` per channel, ``D`` ones, and
    ``b_dt`` puts ``softplus(b_dt)`` log-uniformly in 1e-3..1e-1, as
    Mamba initialises them)."""
    import jax
    import jax.numpy as jnp

    d, di, n = cfg.dim, cfg.ssm_inner, cfg.ssm_state
    r, kc = cfg.ssm_dt_rank, cfg.ssm_conv
    ks = jax.random.split(key, 6)

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

    dt = jnp.exp(jax.random.uniform(ks[5], (di,), jnp.float32)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return {
        "in_proj": normal(ks[0], (d, 2 * di), d),
        "conv_w": normal(ks[1], (kc, di), kc),
        "conv_b": jnp.zeros((di,), jnp.float32),
        "x_proj": normal(ks[2], (di, r + 2 * n), di),
        "dt_norm": jnp.ones((r,), jnp.float32),
        "b_norm": jnp.ones((n,), jnp.float32),
        "c_norm": jnp.ones((n,), jnp.float32),
        "dt_proj": normal(ks[3], (r, di), r),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),     # softplus^-1(dt)
        "a_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, n + 1, dtype=jnp.float32)[:, None], (n, di))),
        "d": jnp.ones((di,), jnp.float32),
        "out_proj": normal(ks[4], (di, d), di),
    }


def state_shapes(cfg, batch: int) -> tuple:
    """``(h, tail)`` shapes of ``batch`` sequences' state."""
    from ..ops.selective_scan import state_shape

    return ((batch,) + state_shape(cfg.ssm_inner, cfg.ssm_state),
            (batch, cfg.ssm_conv - 1, cfg.ssm_inner))


def state_bytes(cfg) -> int:
    """Bytes one sequence's state takes in one state layer (float32)."""
    return 4 * cfg.ssm_inner * (cfg.ssm_state + cfg.ssm_conv - 1)


def _coefficients(cfg, bp, u):
    """``dt, B, C`` of the recurrence from the convolved ``u``."""
    import jax
    import jax.numpy as jnp

    from ..ops.quant import qmatmul
    from .transformer_lm import _rmsnorm

    r, n = cfg.ssm_dt_rank, cfg.ssm_state
    rbc = qmatmul(u, bp["x_proj"])
    dt, b, c = jnp.split(rbc, [r, r + n], axis=-1)
    eps = cfg.norm_eps
    dt = qmatmul(_rmsnorm(dt, bp["dt_norm"], eps), bp["dt_proj"]) \
        + bp["dt_bias"]
    return (jax.nn.softplus(dt), _rmsnorm(b, bp["b_norm"], eps),
            _rmsnorm(c, bp["c_norm"], eps))


def _grouped(cfg, x):
    """``(..., d_inner)`` -> ``(..., groups, lanes)``."""
    from ..ops.selective_scan import state_shape

    return x.reshape(*x.shape[:-1],
                     *state_shape(cfg.ssm_inner, cfg.ssm_state)[1:])


def _neg_exp_a(cfg, bp):
    import jax.numpy as jnp

    return _grouped(cfg, -jnp.exp(bp["a_log"]))


def prefill(cfg, bp, x, ctx_len):
    """``x (1, s, dim)``, normed, zero-padded past ``ctx_len`` ->
    ``(out (1, s, dim), h, tail)``: the state after position
    ``ctx_len - 1`` (the recurrence is frozen past it) and the
    convolution's inputs at ``ctx_len - d_conv + 1 .. ctx_len - 1``
    (zeros before the sequence's start)."""
    import jax
    import jax.numpy as jnp

    from ..ops import selective_scan
    from ..ops.quant import qmatmul

    s, kc, di = x.shape[1], cfg.ssm_conv, cfg.ssm_inner
    u, z = jnp.split(qmatmul(x, bp["in_proj"]), 2, axis=-1)
    u_pad = jnp.pad(u, ((0, 0), (kc - 1, 0), (0, 0)))
    tail = jax.lax.dynamic_slice(u_pad, (0, ctx_len, 0),
                                 (1, kc - 1, di))
    u = jax.nn.silu(sum(bp["conv_w"][j] * u_pad[:, j:j + s]
                        for j in range(kc)) + bp["conv_b"])
    dt, b, c = _coefficients(cfg, bp, u)
    h0 = jnp.zeros(state_shapes(cfg, 1)[0], jnp.float32)
    y, h = selective_scan.scan(
        _grouped(cfg, u), _grouped(cfg, dt), _neg_exp_a(cfg, bp), b, c,
        h0, jnp.reshape(ctx_len, (1,)).astype(jnp.int32))
    y = y.reshape(u.shape) + bp["d"] * u
    return qmatmul(y * jax.nn.silu(z), bp["out_proj"]), h, tail


def step(cfg, bp, x, h, tail, active, lens):
    """``x (slots, dim)``, normed; ``h``, ``tail`` the layer's state
    pool; ``lens (slots,)`` the slots' lengths, which every state layer
    is handed and this one does not read (its tail is kept in time
    order) -> ``(out (slots, dim), h, tail)`` with the state of
    ``active`` slots advanced one position."""
    del lens
    import jax
    import jax.numpy as jnp

    from ..ops import selective_scan
    from ..ops.quant import qmatmul

    u, z = jnp.split(qmatmul(x, bp["in_proj"]), 2, axis=-1)
    window = jnp.concatenate([tail, u[:, None]], axis=1)
    tail = jnp.where(active[:, None, None], window[:, 1:], tail)
    u = jax.nn.silu(jnp.sum(bp["conv_w"][None] * window, axis=1)
                    + bp["conv_b"])
    dt, b, c = _coefficients(cfg, bp, u)
    y, h = selective_scan.step(
        _grouped(cfg, u), _grouped(cfg, dt), _neg_exp_a(cfg, bp), b, c,
        h, active)
    y = y.reshape(u.shape) + bp["d"] * u
    return qmatmul(y * jax.nn.silu(z), bp["out_proj"]), h, tail
