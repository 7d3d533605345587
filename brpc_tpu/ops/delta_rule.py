"""Gated delta rule — the recurrence of a KDA linear-attention layer.

For one sequence and one head, with a ``d x d`` state ``S`` (key x
value), float32, and per position a query ``q``, a key ``k`` (both
normalised by the caller), a value ``v``, a decay ``a`` in ``(0, 1]``
for each KEY CHANNEL and a write strength ``b`` in ``[0, 1]``::

    S'  = Diag(a_t) S_{t-1}
    u_t = v_t - S'^T k_t                   what the state does not yet say
    S_t = S' + b_t k_t u_t^T               = (I - b k k^T) Diag(a) S + b k v^T
    y_t = S_t^T q_t

What a sequence carries from token to token is ``S``; convolutions,
norms and gates around it are the caller's (``models/kda_mixer.py``).

Two callers, one recurrence, as ``ops/selective_scan.py`` has it: the
prefill runs a whole bucket from a given state and needs the state AT
THE TRUE LENGTH, so positions ``t >= lens`` decay nothing and write
nothing (``a`` is taken as 1 and ``b`` as 0 there); the batched decode
step is one position a slot, and a slot that is not ``active`` is not
touched at all.

Every form here computes the write as ``k (b u)^T``: ``b`` is one number
a head, so it scales the ROW ``u`` and ``k``'s column serves ``S'^T k``
and the write both.  The kernels are held BIT-equal to
:func:`sequential`, which therefore takes the same association.

Layout.  A state lies ``(heads, d, d)`` with the KEY channel on the
sublanes and the value on the lanes, so the two reductions over keys
(``S'^T k``, ``S^T q``) are vector adds and the value rows (``v``,
``u``, ``b u``, ``y``) are rows as they arrive.  What multiplies a state
by KEY channel (``a``, ``k``, ``q``) must be a column: the wrappers hand
the kernels those three packed and transposed, ``(..., d, 3 heads)``,
key on the sublanes and ``[q | k | a]`` by head on the lanes, and a
kernel takes head ``h``'s column by a static lane slice.  ``b`` arrives
as a row of ``heads`` numbers a position.

- :func:`sequential` is the plain ``jax.numpy`` form, a ``lax.scan``
  over time: the tests' yardstick, and what runs off the TPU;
- :func:`kda_step` (one position for each slot, the state pool updated
  IN PLACE: each ACTIVE slot's state is read once and written once, an
  inactive slot's neither) and :func:`kda_scan` (a sequence in chunks
  of positions: the recurrence inside a chunk with a head's state in
  registers, the state carried between chunks in VMEM) are the Pallas
  kernels, under those names in a device trace.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .flash_attention import _resolve_interpret

LANES = 128
# positions a grid step of the sequence kernel covers: a chunk's packed
# columns are (chunk, d, 3 heads) float32, 2 MB at 32 (96 lanes lie in 128)
_CHUNK = 32
# the step's blocks (a slot's state in and out, twice each) pass the
# 16 MB Mosaic scopes by default
_VMEM_LIMIT = 48 * 1024 * 1024


def sequential(q, k, v, a, b, s0, lens):
    """The recurrence as a plain scan over time.

    ``q, k, v, a (batch, L, heads, d)``; ``b (batch, L, heads)``; ``s0
    (batch, heads, d, d)``; ``lens (batch,)`` int32 -> ``y (batch, L,
    heads, d)`` and the state after position ``lens - 1`` (``s0``
    where ``lens`` is 0)."""
    import jax.numpy as jnp

    a, b = _frozen_past(a, b, lens)

    def step(s, xs):
        q_t, k_t, v_t, a_t, b_t = xs                 # (batch, heads, ...)
        s = a_t[..., None] * s
        u = v_t - jnp.sum(k_t[..., None] * s, axis=-2)
        s = s + k_t[..., None] * (b_t[..., None] * u)[..., None, :]
        return s, jnp.sum(q_t[..., None] * s, axis=-2)

    s, ys = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, a, b)))
    return jnp.moveaxis(ys, 0, 1), s


def _frozen_past(a, b, lens):
    """``a``, ``b`` with the positions ``t >= lens`` frozen: a decay of
    1 and a write strength of 0 leave a state as it is."""
    import jax.numpy as jnp

    live = jnp.arange(a.shape[1])[None, :] < lens[:, None]
    return (jnp.where(live[:, :, None, None], a, 1.0),
            jnp.where(live[:, :, None], b, 0.0))


def _columns(q, k, a):
    """``[q | k | a]`` by head, transposed: ``(..., heads, d)`` three
    times -> ``(..., d, 3 heads)``, the key channel on the sublanes."""
    import jax.numpy as jnp

    return jnp.swapaxes(jnp.concatenate([q, k, a], axis=-2), -1, -2)


def _update(s, cols, b_row, v_row, h: int, heads: int):
    """One position of one head: ``s (d, d)``; ``cols (d, 3 heads)``
    the packed columns of the position; ``b_row (1, heads)``; ``v_row
    (1, d)``.  Returns the new state and ``y (1, d)``."""
    import jax.numpy as jnp

    def col(j):
        return cols[:, j * heads + h:j * heads + h + 1]      # (d, 1)

    # ``k``'s column is spread over the lanes once, for both its uses
    k = jnp.broadcast_to(col(1), s.shape)
    s = col(2) * s
    u = v_row - jnp.sum(k * s, axis=0, keepdims=True)
    s = s + k * (b_row[:, h:h + 1] * u)
    return s, jnp.sum(col(0) * s, axis=0, keepdims=True)


def _step_kernel(order_ref, n_ref, cols_ref, b_ref, v_ref, s_in, s_ref,
                 y_ref, *, heads: int):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    # grid step i is the i-th ACTIVE slot; the steps past the last one
    # name its blocks again, which the pipeline neither fetches nor
    # writes a second time
    @pl.when(i < n_ref[0])
    def _():
        cols, b_row = cols_ref[0], b_ref[0]
        for h in range(heads):
            s, y = _update(s_in[0, h], cols, b_row, v_ref[0, h:h + 1], h,
                           heads)
            s_ref[0, h] = s
            y_ref[0, h:h + 1] = y

    # no slot is active: the one block that was fetched goes back as it
    # came
    @pl.when(jnp.logical_and(i == 0, n_ref[0] == 0))
    def _():
        s_ref[...] = s_in[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def active_order(active):
    """What a step kernel over the state pool walks: ``(order, n)``,
    the ``n`` ACTIVE slots first, in order, then the last of them
    repeated (int32, both scalar-prefetched).  A grid step ``i`` names
    slot ``order[i]``'s blocks (:func:`active_block`); the steps past
    ``n`` name the last active slot's again, which the pipeline neither
    fetches nor writes a second time, so an inactive slot's block is
    never read nor written."""
    import jax.numpy as jnp

    n = jnp.sum(active.astype(jnp.int32))
    order = jnp.argsort(jnp.logical_not(active), stable=True
                        ).astype(jnp.int32)
    order = jnp.where(jnp.arange(active.shape[0]) < n, order,
                      order[jnp.maximum(n - 1, 0)])
    return order, n


def active_block(*rest):
    """The index map of a slot's block in a grid over
    :func:`active_order`: ``(order[i],) + rest``."""
    return lambda i, order, n: (order[i],) + rest


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(q, k, v, a, b, s, active, interpret: bool):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, d = q.shape
    order, n = active_order(active)
    at = active_block

    state = pl.BlockSpec((1, heads, d, d), at(0, 0, 0))
    row = pl.BlockSpec((1, heads, d), at(0, 0))
    s, y = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots,),
            in_specs=[pl.BlockSpec((1, d, 3 * heads), at(0, 0)),
                      pl.BlockSpec((1, 1, heads), at(0, 0)), row, state],
            out_specs=[state, row]),
        out_shape=[jax.ShapeDtypeStruct(s.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)],
        # the pool is updated where it lies (operand 5: after ``order``,
        # ``n``, the columns, ``b`` and ``v``)
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="kda_step",
    )(order, n.reshape(1), _columns(q, k, a), b[:, None], v, s)
    # an inactive slot's row was never written
    return jnp.where(active[:, None, None], y, 0.0), s


def kda_step(q, k, v, a, b, s, active, interpret: Optional[bool] = None):
    """One position for each slot.  ``q, k, v, a (slots, heads, d)``;
    ``b (slots, heads)``; ``s (slots, heads, d, d)``, updated in place;
    ``active (slots,)`` bool: a slot that is not keeps its state, which
    is neither read nor written, and its ``y`` is zero -> ``y`` like
    ``v`` and the pool."""
    return _step_call(q, k, v, a, b, s, active,
                      interpret=_resolve_interpret(interpret))


def _scan_kernel(cols_ref, b_ref, v_ref, s0_ref, s_ref, y_ref, *,
                 heads: int, chunk: int):
    from jax import lax
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    # a head at a time through the chunk's positions, its state the
    # loop's carry (16 registers at d = 128) and not a buffer read and
    # written back every position
    for h in range(heads):
        def body(t, s, h=h):
            s, y = _update(s, cols_ref[0, t], b_ref[0, pl.ds(t, 1)],
                           v_ref[0, t, h:h + 1], h, heads)
            y_ref[0, t, h:h + 1] = y
            return s

        s_ref[0, h] = lax.fori_loop(0, chunk, body, s_ref[0, h])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_call(q, k, v, a, b, s0, lens, interpret: bool):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, n_pos, heads, d = q.shape
    chunk = min(_CHUNK, n_pos)
    if n_pos % chunk:
        raise ValueError(f"a sequence of {n_pos} is no multiple of {chunk}")
    a, b = _frozen_past(a, b, lens)
    seq = pl.BlockSpec((1, chunk, heads, d), lambda i, l: (i, l, 0, 0))
    state = pl.BlockSpec((1, heads, d, d), lambda i, l: (i, 0, 0, 0))
    # the state is the first result: a device trace names an operation
    # with its first result's shape, which is then the same for every
    # bucket (``selective_scan._scan_call`` has the same order)
    s, y = pl.pallas_call(
        functools.partial(_scan_kernel, heads=heads, chunk=chunk),
        grid=(batch, n_pos // chunk),
        in_specs=[pl.BlockSpec((1, chunk, d, 3 * heads),
                               lambda i, l: (i, l, 0, 0)),
                  pl.BlockSpec((1, chunk, heads), lambda i, l: (i, l, 0)),
                  seq, state],
        out_specs=[state, seq],
        out_shape=[jax.ShapeDtypeStruct(s0.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="kda_scan",
    )(_columns(q, k, a), b, v, s0)
    return y, s


def kda_scan(q, k, v, a, b, s0, lens, interpret: Optional[bool] = None):
    """A sequence from a given state, shapes as :func:`sequential`;
    ``L`` is a multiple of 32 or at most 32."""
    return _scan_call(q, k, v, a, b, s0, lens,
                      interpret=_resolve_interpret(interpret))


def _use_kernels(d: int) -> bool:
    from .device_ops import _on_tpu
    return d == LANES and _on_tpu()


def scan(q, k, v, a, b, s0, lens):
    """What the prefill calls (shapes as :func:`sequential`): the
    kernel on the TPU where a head's state fills whole registers, the
    plain scan elsewhere, as ``selective_scan.scan`` chooses."""
    n_pos = q.shape[1]
    if _use_kernels(q.shape[-1]) and (n_pos <= _CHUNK
                                      or n_pos % _CHUNK == 0):
        return kda_scan(q, k, v, a, b, s0, lens)
    with jax.named_scope("kda_scan"):
        return sequential(q, k, v, a, b, s0, lens)


def step(q, k, v, a, b, s, active):
    """What the batched decode step calls (shapes as
    :func:`kda_step`)."""
    import jax.numpy as jnp

    if _use_kernels(q.shape[-1]):
        return kda_step(q, k, v, a, b, s, active)
    with jax.named_scope("kda_step"):
        y, s = sequential(q[:, None], k[:, None], v[:, None], a[:, None],
                          b[:, None], s, active.astype(jnp.int32))
    return jnp.where(active[:, None, None], y[:, 0], 0.0), s
