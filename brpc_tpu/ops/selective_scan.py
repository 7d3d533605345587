"""Selective scan — the recurrence of a Mamba-1 state-space layer.

For one sequence, per channel ``c`` of ``d_inner`` and state index
``k`` of ``d_state``::

    h_t[k, c] = exp(dt_t[c] * A[k, c]) * h_{t-1}[k, c]
                + dt_t[c] * u_t[c] * B_t[k]
    y_t[c]    = sum_k h_t[k, c] * C_t[k]

everything float32.  What a sequence carries from token to token is
``h``; the skip term ``D * u`` and the gate are the caller's.

Two callers, one recurrence: the prefill runs it over a whole bucket
from a given state and needs the state AT THE TRUE LENGTH, so
positions ``t >= lens[b]`` leave ``h`` as it is (``dt`` is taken as 0
there: the decay is 1 and nothing is added); the batched decode step
is the same with one position a slot and ``lens = active``.

Layout.  The state is kept as ``(batch, d_state, groups, lanes)`` with
``groups * lanes = d_inner`` (:func:`state_shape`): where ``d_inner``
is a multiple of 128 a group of 8 x 128 channels of ONE state index is
one vector register, so the kernels update ``d_state`` registers a
position with no relayout and the state pool is read and written where
it lies.  ``B_t[k]`` and ``C_t[k]`` are scalars read from SMEM.

- :func:`sequential` is the plain ``jax.numpy`` form, a ``lax.scan``
  over time: the tests' yardstick, and what runs off the TPU;
- :func:`ssm_scan` (a sequence, from ``h0`` to ``y`` and the state at
  ``lens``) and :func:`ssm_step` (one position for each slot, the
  state pool updated in place) are the Pallas kernels, under those
  names in a device trace.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .flash_attention import _resolve_interpret

LANES = 128
_SUBLANES = 8
# positions a grid step of the sequence kernel covers: u, dt and y
# blocks of (chunk, 8, 128) float32 are 0.5 MB each at 128
_CHUNK = 128


def state_shape(d_inner: int, d_state: int) -> tuple:
    """``(d_state, groups, lanes)``: how one sequence's state is laid
    out (see the module docstring)."""
    lanes = LANES if d_inner % LANES == 0 else d_inner
    return (d_state, d_inner // lanes, lanes)


def sequential(u, dt, a, b, c, h0, lens):
    """The recurrence as a plain scan over time.

    ``u, dt (batch, L, d_inner)``; ``a (d_state, d_inner)``;
    ``b, c (batch, L, d_state)``; ``h0 (batch, d_state, d_inner)``;
    ``lens (batch,)`` int32 -> ``y (batch, L, d_inner)`` and the state
    after position ``lens - 1`` (``h0`` where ``lens`` is 0)."""
    import jax.numpy as jnp

    n_pos = u.shape[1]
    live = jnp.arange(n_pos)[None, :] < lens[:, None]
    dt = jnp.where(live[:, :, None], dt, 0.0)

    def step(h, xs):
        u_t, dt_t, b_t, c_t = xs                  # (batch, ...)
        h = jnp.exp(dt_t[:, None, :] * a[None]) * h \
            + (dt_t * u_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    h, ys = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (u, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1), h


def _update(h, a_ref, u_t, dt_t, b_at, c_at, d_state: int):
    """One position of one sequence: ``h`` is a list of ``d_state``
    ``(groups, lanes)`` values; ``b_at(k)``, ``c_at(k)`` give the
    scalars.  Returns the new list and ``y_t``."""
    import jax.numpy as jnp

    du = dt_t * u_t
    y = None
    out = []
    for k in range(d_state):
        hk = jnp.exp(dt_t * a_ref[k]) * h[k] + du * b_at(k)
        out.append(hk)
        y = hk * c_at(k) if y is None else y + hk * c_at(k)
    return out, y


def _scan_kernel(lens_ref, u_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref,
                 h_ref, y_ref, *, d_state: int, chunk: int):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    bi, li = pl.program_id(0), pl.program_id(2)

    @pl.when(li == 0)
    def _():
        h_ref[...] = h0_ref[...]

    n_live = lens_ref[bi] - li * chunk      # live positions of this chunk

    def body(t, h):
        dt_t = jnp.where(t < n_live, dt_ref[0, t], 0.0)
        h, y = _update(h, a_ref, u_ref[0, t], dt_t,
                       lambda k: b_ref[(t * d_state + k)],
                       lambda k: c_ref[(t * d_state + k)], d_state)
        y_ref[0, t] = y
        return h

    h = lax.fori_loop(0, chunk, body,
                      [h_ref[0, k] for k in range(d_state)])
    for k in range(d_state):
        h_ref[0, k] = h[k]


def _group_block(groups: int) -> int:
    return _SUBLANES if groups % _SUBLANES == 0 else groups


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_call(u, dt, a, b, c, h0, lens, interpret: bool):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, n_pos, groups, lanes = u.shape
    d_state = a.shape[0]
    chunk = min(_CHUNK, n_pos)
    if n_pos % chunk:
        raise ValueError(f"a sequence of {n_pos} is no multiple of {chunk}")
    n_chunks = n_pos // chunk
    gb = _group_block(groups)
    seq = pl.BlockSpec((1, chunk, gb, lanes),
                       lambda i, j, l, lens: (i, l, j, 0))
    coef = pl.BlockSpec((chunk * d_state,),
                        lambda i, j, l, lens: (i * n_chunks + l,),
                        memory_space=pltpu.SMEM)
    state = pl.BlockSpec((1, d_state, gb, lanes),
                         lambda i, j, l, lens: (i, 0, j, 0))
    # the state is the first result: a device trace names an operation
    # with its first result's shape, which is then the same for every
    # bucket (benchmarks/harness/xplane.py adds them up under one name)
    h, y = pl.pallas_call(
        functools.partial(_scan_kernel, d_state=d_state, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, groups // gb, n_chunks),
            in_specs=[seq, seq,
                      pl.BlockSpec((d_state, gb, lanes),
                                   lambda i, j, l, lens: (0, j, 0)),
                      coef, coef, state],
            out_specs=[state, seq]),
        out_shape=[jax.ShapeDtypeStruct(h0.shape, jnp.float32),
                   jax.ShapeDtypeStruct(u.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(lens, u, dt, a, b.reshape(-1), c.reshape(-1), h0)
    return y, h


def ssm_scan(u, dt, a, b, c, h0, lens, interpret: Optional[bool] = None):
    """A sequence from a given state.  ``u, dt (batch, L, groups,
    lanes)``; ``a (d_state, groups, lanes)``; ``b, c (batch, L,
    d_state)``; ``h0 (batch, d_state, groups, lanes)``; ``lens
    (batch,)`` -> ``y`` like ``u`` and the state after position
    ``lens - 1``.  ``L`` is a multiple of 128 or at most 128."""
    return _scan_call(u, dt, a, b, c, h0, lens,
                      interpret=_resolve_interpret(interpret))


def _step_kernel(act_ref, u_ref, dt_ref, a_ref, b_ref, c_ref, h_in,
                 h_ref, y_ref, *, d_state: int, slots: int):
    import jax.numpy as jnp

    for s in range(slots):
        dt_s = jnp.where(act_ref[s] > 0, dt_ref[s], 0.0)
        h, y = _update([h_in[s, k] for k in range(d_state)], a_ref,
                       u_ref[s], dt_s,
                       lambda k: b_ref[s * d_state + k],
                       lambda k: c_ref[s * d_state + k], d_state)
        y_ref[s] = y
        for k in range(d_state):
            h_ref[s, k] = h[k]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(u, dt, a, b, c, h, active, interpret: bool):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, groups, lanes = u.shape
    d_state = a.shape[0]
    gb = _group_block(groups)
    row = pl.BlockSpec((slots, gb, lanes), lambda j, act: (0, j, 0))
    coef = pl.BlockSpec((slots * d_state,), lambda j, act: (0,),
                        memory_space=pltpu.SMEM)
    state = pl.BlockSpec((slots, d_state, gb, lanes),
                         lambda j, act: (0, 0, j, 0))
    h, y = pl.pallas_call(
        functools.partial(_step_kernel, d_state=d_state, slots=slots),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(groups // gb,),
            in_specs=[row, row,
                      pl.BlockSpec((d_state, gb, lanes),
                                   lambda j, act: (0, j, 0)),
                      coef, coef, state],
            out_specs=[state, row]),
        out_shape=[jax.ShapeDtypeStruct(h.shape, jnp.float32),
                   jax.ShapeDtypeStruct(u.shape, jnp.float32)],
        # the pool is updated where it lies (operand 6: after ``act``)
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="ssm_step",
    )(active.astype(jnp.int32), u, dt, a, b.reshape(-1), c.reshape(-1), h)
    return y, h


def ssm_step(u, dt, a, b, c, h, active, interpret: Optional[bool] = None):
    """One position for each slot.  ``u, dt (slots, groups, lanes)``;
    ``a (d_state, groups, lanes)``; ``b, c (slots, d_state)``; ``h
    (slots, d_state, groups, lanes)``, updated in place; ``active
    (slots,)``: a slot that is not keeps its state -> ``y`` like ``u``
    and the pool."""
    return _step_call(u, dt, a, b, c, h, active,
                      interpret=_resolve_interpret(interpret))


def _use_kernels(lanes: int) -> bool:
    from .device_ops import _on_tpu
    return lanes == LANES and _on_tpu()


def scan(u, dt, a, b, c, h0, lens):
    """What the prefill calls (shapes as :func:`ssm_scan`): the kernel
    on the TPU where the channels fill whole registers, the plain
    scan elsewhere, as ``paged_attention.attention`` chooses."""
    n_pos, lanes = u.shape[1], u.shape[-1]
    if _use_kernels(lanes) and (n_pos <= _CHUNK or n_pos % _CHUNK == 0):
        return ssm_scan(u, dt, a, b, c, h0, lens)
    flat = lambda x: x.reshape(*x.shape[:-2], -1)      # noqa: E731
    y, h = sequential(flat(u), flat(dt), flat(a), b, c, flat(h0), lens)
    return y.reshape(u.shape), h.reshape(h0.shape)


def step(u, dt, a, b, c, h, active):
    """What the batched decode step calls (shapes as :func:`ssm_step`)."""
    import jax.numpy as jnp

    if _use_kernels(u.shape[-1]):
        return ssm_step(u, dt, a, b, c, h, active)
    y, h = scan(u[:, None], dt[:, None], a, b[:, None], c[:, None], h,
                active.astype(jnp.int32))
    return y[:, 0], h
