"""The state-space recurrence of a Mamba-2 layer (SSD: one scalar decay
a head over a matrix state).

For one sequence and one head ``h`` with a ``P x N`` state ``S``
(head size x state size), float32, and per position an input row ``x``
(``P``), a step size ``dt > 0``, the head's ``A < 0`` and skip ``D``,
and the rows ``B``, ``C`` (``N``) of the head's group::

    S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T
    y_t = S_t C_t + D x_t

What a sequence carries from token to token is ``S``; the projections,
the convolution, the gate and the norm around it are the caller's
(``models/mamba2_mixer.py``).  A position with ``dt`` 0 leaves ``S`` as
it is (a decay of 1, nothing written): that is how a bucket's padding
is kept out of a prompt's state.

Two forms a caller needs, as ``ops/delta_rule.py`` has them:

- the prompt, :func:`chunk_scan`: the SSD chunked form, chunks of
  ``chunk`` positions one ``lax.scan`` step each (``ssd_chunk_scan`` in
  a device trace).  Inside a chunk the outputs are the quadratic form
  ``y = (L o C B^T) (dt x)`` with ``L`` the segment-sum decay mask, and
  the contributions of the state the chunk starts from and to the state
  it leaves are products too: every one of them on the MXU in float32
  at ``highest``;
- the batched decode step, one position a slot: :func:`ssd_step`, a
  Pallas kernel that updates the state pool IN PLACE, a grid step an
  ACTIVE slot (``delta_rule.active_order``), so an idle slot's block is
  neither read nor written.  Its plain form is :func:`sequential` (a
  ``lax.scan`` over positions, the tests' yardstick and what runs off
  the TPU), which takes the same association as the kernel.

Layout.  A state lies ``(heads, P, N)``: ``N`` (128 at the served
widths) on the lanes, so a head's state is whole vector registers and
the pool is read and written where it lies.  ``dt x`` multiplies the
state by ROW, so the kernel takes ``x`` transposed, ``(P, heads)``, a
head's column a static lane slice, and returns ``y`` the same way;
``B`` and ``C`` are rows as they arrive; ``dt``, the decay ``exp(dt
A)`` and ``D`` are scalars, read from SMEM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .delta_rule import active_block, active_order
from .flash_attention import _resolve_interpret

LANES = 128
# the step's blocks (a slot's 4 MB state in and out, twice each) pass
# the 16 MB Mosaic scopes by default
_VMEM_LIMIT = 64 * 1024 * 1024


def _by_head(t, heads: int):
    """``(..., groups, N)`` -> ``(..., heads, N)``: each head's row of
    its group (heads are the groups' in order)."""
    import jax.numpy as jnp

    return jnp.repeat(t, heads // t.shape[-2], axis=-2)


def sequential(x, dt, a, b, c, d, s0, lens):
    """The recurrence as a plain scan over positions.

    ``x (batch, L, heads, P)``; ``dt (batch, L, heads)``; ``a``, ``d``
    ``(heads,)``; ``b``, ``c (batch, L, groups, N)``; ``s0 (batch,
    heads, P, N)``; ``lens (batch,)`` int32 -> ``y`` like ``x`` and the
    state after position ``lens - 1`` (``s0`` where ``lens`` is 0)."""
    import jax.numpy as jnp

    heads = x.shape[2]
    dt = jnp.where(jnp.arange(x.shape[1])[None, :, None]
                   < lens[:, None, None], dt, 0.0)

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs               # (batch, heads, ...)
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        y = jnp.sum(s * c_t[..., None, :], axis=-1) + d[:, None] * x_t
        return s, y

    s, ys = jax.lax.scan(step, s0, (
        jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
        jnp.moveaxis(_by_head(b, heads), 1, 0),
        jnp.moveaxis(_by_head(c, heads), 1, 0)))
    return jnp.moveaxis(ys, 0, 1), s


def chunk_scan(x, dt, a, b, c, d, s0, chunk: int):
    """A sequence from ``s0`` in the chunked form; shapes as
    :func:`sequential`, whose ``lens`` is here ``dt`` 0 past the true
    length.  Returns ``y`` and the state after the last position."""
    import jax.numpy as jnp

    batch, n_pos, heads, p = x.shape
    groups, n = b.shape[-2:]
    hg = heads // groups
    w = min(chunk, n_pos)
    pad = -n_pos % w
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad))
                               + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    k = x.shape[1] // w
    hi = jax.lax.Precision.HIGHEST

    def chunks(t):
        # (batch, k w, ...) -> (k, batch, w, ...)
        return jnp.moveaxis(t.reshape((batch, k, w) + t.shape[2:]), 1, 0)

    causal = jnp.arange(w)[:, None] >= jnp.arange(w)[None, :]

    def one(s, xs):
        # a chunk: x (batch, w, groups, hg, P), dt (batch, w, groups,
        # hg), b / c (batch, w, groups, N); s (batch, groups, hg, P, N)
        x_c, dt_c, b_c, c_c = xs
        cs = jnp.cumsum(dt_c * a.reshape(groups, hg), axis=1)
        xdt = x_c * dt_c[..., None]
        # the segment sums cs_t - cs_s, s <= t: every exponent <= 0
        seg = cs[:, :, None] - cs[:, None, :]          # (b, t, s, g, hg)
        decay = jnp.exp(jnp.where(causal[None, :, :, None, None], seg,
                                  -jnp.inf))
        cb = jnp.einsum("btgn,bsgn->btsg", c_c, b_c, precision=hi)
        y = jnp.einsum("btsgh,bsghp->btghp", decay * cb[..., None], xdt,
                       precision=hi)
        # the state the chunk starts from, decayed to each position
        y = y + jnp.exp(cs)[..., None] * jnp.einsum(
            "btgn,bghpn->btghp", c_c, s, precision=hi)
        last = cs[:, -1]                               # (b, g, hg)
        s = jnp.exp(last)[..., None, None] * s + jnp.einsum(
            "bsghp,bsgn->bghpn", xdt * jnp.exp(last[:, None] - cs)[..., None],
            b_c, precision=hi)
        return s, y

    with jax.named_scope("ssd_chunk_scan"):
        s, ys = jax.lax.scan(one, s0.reshape(batch, groups, hg, p, n), (
            chunks(x.reshape(batch, -1, groups, hg, p)),
            chunks(dt.reshape(batch, -1, groups, hg)), chunks(b),
            chunks(c)))
    y = jnp.moveaxis(ys, 0, 1).reshape(batch, -1, heads, p)[:, :n_pos]
    return y + d[:, None] * x[:, :n_pos], s.reshape(s0.shape)


def _step_kernel(order_ref, n_ref, dt_ref, da_ref, d_ref, x_ref, b_ref,
                 c_ref, s_in, s_ref, y_ref, *, heads: int, groups: int):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    hg = heads // groups

    # grid step i is the i-th ACTIVE slot (``delta_rule.active_order``)
    @pl.when(i < n_ref[0])
    def _():
        xs = x_ref[0]                                     # (P, heads)
        for h in range(heads):
            g = h // hg
            x = xs[:, h:h + 1]                            # (P, 1)
            s = da_ref[0, 0, h] * s_in[0, h] \
                + (dt_ref[0, 0, h] * x) * b_ref[0, g:g + 1]
            s_ref[0, h] = s
            y_ref[0, :, h:h + 1] = jnp.sum(s * c_ref[0, g:g + 1], axis=1,
                                           keepdims=True) + d_ref[h] * x

    # no slot is active: the one block that was fetched goes back as it
    # came
    @pl.when(jnp.logical_and(i == 0, n_ref[0] == 0))
    def _():
        s_ref[...] = s_in[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(x, dt, a, b, c, d, s, active, interpret: bool):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, p = x.shape
    groups, n = b.shape[1:]
    order, n_act = active_order(active)
    at = active_block
    smem = pltpu.SMEM
    # a slot's ``dt`` and decay: ``(1, 1, heads)`` blocks, whole in
    # their last two dimensions
    row = pl.BlockSpec((1, 1, heads), at(0, 0), memory_space=smem)
    cols = pl.BlockSpec((1, p, heads), at(0, 0))
    state = pl.BlockSpec((1, heads, p, n), at(0, 0, 0))
    s, y = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots,),
            in_specs=[row, row, pl.BlockSpec(memory_space=smem), cols,
                      pl.BlockSpec((1, groups, n), at(0, 0)),
                      pl.BlockSpec((1, groups, n), at(0, 0)), state],
            out_specs=[state, cols]),
        out_shape=[jax.ShapeDtypeStruct(s.shape, jnp.float32),
                   jax.ShapeDtypeStruct((slots, p, heads), jnp.float32)],
        # the pool is updated where it lies (operand 8: after ``order``,
        # ``n``, ``dt``, the decay, ``D``, ``x``, ``B`` and ``C``)
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ssd_step",
    )(order, n_act.reshape(1), dt[:, None], jnp.exp(dt * a)[:, None], d,
      jnp.swapaxes(x, 1, 2), b, c, s)
    # an inactive slot's row was never written
    return jnp.where(active[:, None, None], jnp.swapaxes(y, 1, 2), 0.0), s


def ssd_step(x, dt, a, b, c, d, s, active, interpret: Optional[bool] = None):
    """One position for each slot.  ``x (slots, heads, P)``; ``dt
    (slots, heads)``; ``a``, ``d (heads,)``; ``b``, ``c (slots, groups,
    N)``; ``s (slots, heads, P, N)``, updated in place; ``active
    (slots,)`` bool: a slot that is not keeps its state, which is
    neither read nor written, and its ``y`` is zero -> ``y`` like ``x``
    (``S C + D x``, before the gate) and the pool."""
    return _step_call(x, dt, a, b, c, d, s, active,
                      interpret=_resolve_interpret(interpret))


def _use_kernel(n: int) -> bool:
    from .device_ops import _on_tpu
    return n % LANES == 0 and _on_tpu()


def step(x, dt, a, b, c, d, s, active):
    """What the batched decode step calls (shapes as :func:`ssd_step`):
    the kernel on the TPU where a head's state is whole lanes, the plain
    scan elsewhere."""
    import jax.numpy as jnp

    if _use_kernel(b.shape[-1]):
        return ssd_step(x, dt, a, b, c, d, s, active)
    with jax.named_scope("ssd_step"):
        y, s = sequential(x[:, None], dt[:, None], a, b[:, None],
                          c[:, None], d, s, active.astype(jnp.int32))
    return jnp.where(active[:, None, None], y[:, 0], 0.0), s
