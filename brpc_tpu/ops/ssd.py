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
  the TPU), which takes the same association as the kernel but for
  the order of ``y``'s sum over ``N``.

Layout.  The equation's state is ``(heads, P, N)``, and so are
:func:`sequential` and :func:`chunk_scan`'s.  The POOL lays a slot's
state transposed, ``(heads P // w, N, w)`` (:func:`to_pool`,
:func:`from_pool`): ``N`` on the sublanes and the flattened ``(head,
p)`` index on the lanes, ``w`` = 128 lanes a row where ``heads P``
fills whole rows (at ``P`` = 64 a row holds two heads side by side).
Every lane is then one ``(head, p)`` pair and the step is elementwise:
row ``r`` is ``S_r = decay_r S_r + B (dt x)_r`` with ``B`` a column
spread over the lanes, and ``y_r`` a sum over the sublanes.  ``x``
arrives as rows in its own ``(heads P)`` order and ``y`` leaves so;
the kernel builds each row's decay, ``dt`` and ``D`` from SMEM scalars.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .delta_rule import active_block, active_order
from .flash_attention import _resolve_interpret

LANES = 128
SUBLANES = 8
# the step's blocks (a slot's 4 MB state in and out, twice each) pass
# the 16 MB Mosaic scopes by default
_VMEM_LIMIT = 64 * 1024 * 1024


def pool_shape(heads: int, p: int, n: int) -> tuple:
    """One slot's state as the pool lays it: ``(rows, N, w)``, ``w``
    128 lanes where ``heads P`` fills whole rows of them, else one row
    of ``heads P``."""
    w = LANES if heads * p % LANES == 0 else heads * p
    return (heads * p // w, n, w)


def to_pool(s):
    """``(..., heads, P, N)``, the equation's layout -> the pool's
    ``(..., rows, N, w)`` (:func:`pool_shape`)."""
    *lead, heads, p, n = s.shape
    rows, _n, w = pool_shape(heads, p, n)
    t = s.reshape(*lead, heads * p, n).swapaxes(-1, -2)
    return t.reshape(*lead, n, rows, w).swapaxes(-2, -3)


def from_pool(s, heads: int):
    """The pool's ``(..., rows, N, w)`` -> ``(..., heads, P, N)``."""
    *lead, rows, n, w = s.shape
    t = s.swapaxes(-2, -3).reshape(*lead, n, rows * w).swapaxes(-1, -2)
    return t.reshape(*lead, heads, rows * w // heads, n)


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


def _by_lane(value, r: int, w: int, p: int, lane):
    """Row ``r``'s ``(1, w)`` lanes of a number a head (``value(h)``, a
    scalar read from SMEM): each lane takes its head's."""
    import jax.numpy as jnp

    first, last = r * w // p, (r * w + w - 1) // p
    out = jnp.full((1, w), value(last))
    for h in range(last - 1, first - 1, -1):
        out = jnp.where(lane < (h + 1) * p - r * w, value(h), out)
    return out


def _step_kernel(order_ref, n_ref, dt_ref, da_ref, d_ref, x_ref, b_ref,
                 c_ref, s_in, s_ref, y_ref, *, p: int, group_rows: int):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    rows, n, w = s_in.shape[1:]

    # grid step i is the i-th ACTIVE slot (``delta_rule.active_order``)
    @pl.when(i < n_ref[0])
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
        # a group's B and C columns spread over the lanes once a slot
        spread = {}
        for r in range(rows):
            g = r // group_rows
            if g not in spread:
                spread[g] = [jnp.broadcast_to(t[0, :, g:g + 1], (n, w))
                             for t in (b_ref, c_ref)]
            b, c = spread[g]
            x = x_ref[0, r:r + 1]                         # (1, w)
            xdt = _by_lane(lambda h: dt_ref[0, 0, h], r, w, p, lane) * x
            s = _by_lane(lambda h: da_ref[0, 0, h], r, w, p, lane) \
                * s_in[0, r] + b * xdt
            s_ref[0, r] = s
            y_ref[0, r:r + 1] = jnp.sum(s * c, axis=0, keepdims=True) \
                + _by_lane(lambda h: d_ref[h], r, w, p, lane) * x

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
    rows, _n, w = pool_shape(heads, p, n)
    if s.shape != (slots, rows, n, w) \
            or not _rows_in_one_group(heads, p, n, groups):
        raise ValueError(
            f"ssd_step takes a pool of {(slots, rows, n, w)} whose rows of "
            f"lanes lie in one group each and N whole sublane tiles: "
            f"pool {s.shape}, {heads} heads of {p} in {groups} groups, "
            f"N {n}")
    order, n_act = active_order(active)
    at = active_block
    smem = pltpu.SMEM
    # a slot's ``dt`` and decay: ``(1, 1, heads)`` blocks, whole in
    # their last two dimensions
    per_head = pl.BlockSpec((1, 1, heads), at(0, 0), memory_space=smem)
    lanes = pl.BlockSpec((1, rows, w), at(0, 0))
    cols = pl.BlockSpec((1, n, groups), at(0, 0))
    state = pl.BlockSpec((1, rows, n, w), at(0, 0, 0))
    s, y = pl.pallas_call(
        functools.partial(_step_kernel, p=p, group_rows=rows // groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots,),
            in_specs=[per_head, per_head, pl.BlockSpec(memory_space=smem),
                      lanes, cols, cols, state],
            out_specs=[state, lanes]),
        out_shape=[jax.ShapeDtypeStruct(s.shape, jnp.float32),
                   jax.ShapeDtypeStruct((slots, rows, w), jnp.float32)],
        # the pool is updated where it lies (operand 8: after ``order``,
        # ``n``, ``dt``, the decay, ``D``, ``x``, ``B`` and ``C``)
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ssd_step",
    )(order, n_act.reshape(1), dt[:, None], jnp.exp(dt * a)[:, None], d,
      x.reshape(slots, rows, w), b.swapaxes(1, 2), c.swapaxes(1, 2), s)
    # an inactive slot's row was never written
    return jnp.where(active[:, None, None], y.reshape(x.shape), 0.0), s


def ssd_step(x, dt, a, b, c, d, s, active, interpret: Optional[bool] = None):
    """One position for each slot.  ``x (slots, heads, P)``; ``dt
    (slots, heads)``; ``a``, ``d (heads,)``; ``b``, ``c (slots, groups,
    N)``; ``s (slots, rows, N, w)`` the pool (:func:`pool_shape`),
    updated in place; ``active (slots,)`` bool: a slot that is not
    keeps its state, which is neither read nor written, and its ``y``
    is zero -> ``y`` like ``x`` (``S C + D x``, before the gate) and the
    pool.  A row of lanes must lie in one group of heads and ``N`` be
    whole sublane tiles (:func:`_rows_in_one_group`)."""
    return _step_call(x, dt, a, b, c, d, s, active,
                      interpret=_resolve_interpret(interpret))


def _rows_in_one_group(heads: int, p: int, n: int, groups: int) -> bool:
    return (heads // groups) * p % LANES == 0 and n % SUBLANES == 0


def _use_kernel(heads: int, p: int, n: int, groups: int) -> bool:
    from .device_ops import _on_tpu
    return _rows_in_one_group(heads, p, n, groups) and _on_tpu()


def step(x, dt, a, b, c, d, s, active):
    """What the batched decode step calls (shapes as :func:`ssd_step`):
    the kernel on the TPU where a row of the pool's lanes lies in one
    group, the plain scan in the equation's layout elsewhere."""
    import jax.numpy as jnp

    heads, p = x.shape[1:]
    groups, n = b.shape[1:]
    if _use_kernel(heads, p, n, groups):
        return ssd_step(x, dt, a, b, c, d, s, active)
    with jax.named_scope("ssd_step"):
        y, s = sequential(x[:, None], dt[:, None], a, b[:, None],
                          c[:, None], d, from_pool(s, heads),
                          active.astype(jnp.int32))
    return jnp.where(active[:, None, None], y[:, 0], 0.0), to_pool(s)
