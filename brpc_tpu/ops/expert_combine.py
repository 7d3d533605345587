"""The held experts' combine (``models/moe.py serve``).

``ys (M, dim)`` float32 is what ``expert_gmm`` left of the sorted
worst-case buffer: its first ``n_live`` rows hold an expert's output
for one (token, choice) pair, the rest was never written.  ``order
(M,)`` says which pair ``t * k + j`` lies at each row and ``w (T, k)``
what the pair weighs: ``out[t] = sum of w[t, j] * ys[r]`` over the live
rows ``r`` of token ``t``.  A decode step has ~16 live rows in 512, so
the combine is bound by the LIVE rows' bytes and the output's own, if it
is not made to walk the buffer (XLA's scatter-add of the whole buffer,
which stood in ``serve`` until PR 38, took the 512 rows one after
another at an eighth of the HBM rate; PERF.md §6):

- the grid is (column block, row tile) and its second extent is
  dynamic: the row tiles up to the last live row, so the buffer behind
  it is never fetched;
- a column block of ALL ``T`` output rows stays in VMEM while the row
  tiles go by: it is zeroed at the first, each live row is added into
  its token's row, and it is written back once;
- pair and weight are read from scalar memory (``order`` and ``w``
  scalar-prefetched), so weighting and "was this row computed" cost no
  pass of their own.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .flash_attention import _resolve_interpret

# the output's column block (all T rows) and a row tile of the buffer in
# VMEM; two of each are resident (the pipeline's double buffer).  On the
# v5e (PERF.md §6, PR 38: ``chip_gmm.py combine tiles``, blocks of 1-8 MB
# x tiles of 0.25-4 MB) a step's call costs least at the smallest tile
# (6.3-6.7 us at 8 rows of 7,168 against 11.2 at 128: what is fetched
# beyond the ~16 live rows is waste) and a 1,024-row bucket's 87 us at a
# 2-MB block against 139 at 1 MB and 72-78 at 4-8 MB
_OUT_BYTES = 2 << 20
_TILE_BYTES = 256 << 10
_ROW_TILE = 128


def _tiles(t: int, m: int, dim: int, out_bytes: int, tile_bytes: int):
    """``(td, tm)``: the widest column block of whole 128-lane tiles
    that divides ``dim`` and keeps ``T`` float32 rows within
    ``out_bytes`` (at least one lane tile), and the row tile, whole
    sublanes of at most ``_ROW_TILE`` rows within ``tile_bytes``; a
    buffer shorter than that is one tile."""
    fits = [c for c in range(128, dim + 1, 128)
            if dim % c == 0 and t * c * 4 <= out_bytes]
    td = max(fits) if fits else 128
    tm = max(8, min(_ROW_TILE, tile_bytes // (td * 4) // 8 * 8))
    return td, (m if m <= tm else tm)


def _kernel(order_ref, w_ref, n_ref, y_ref, o_ref, *, tm: int, k: int):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def add(r, carry):
        pair = order_ref[i * tm + r]
        row = pl.ds(pair // k, 1)
        o_ref[row, :] = o_ref[row, :] + w_ref[pair] * y_ref[pl.ds(r, 1), :]
        return carry

    lax.fori_loop(0, jnp.clip(n_ref[0] - i * tm, 0, tm), add, 0)


@functools.partial(jax.jit, static_argnames=("out_bytes", "tile_bytes",
                                             "interpret"))
def _call(ys, order, w, n_live, out_bytes: int, tile_bytes: int,
          interpret: bool):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (m, dim), (t, k) = ys.shape, w.shape
    td, tm = _tiles(t, m, dim, out_bytes, tile_bytes)
    n_live = n_live.astype(jnp.int32).reshape(1)
    # no live row at all: one tile goes by, so that the zeros are written
    tiles = jnp.maximum(pl.cdiv(n_live[0], tm), 1)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(dim // td, tiles),
            in_specs=[pl.BlockSpec((tm, td),
                                   lambda j, i, order, w, n: (i, j))],
            out_specs=pl.BlockSpec((t, td),
                                   lambda j, i, order, w, n: (0, j))),
        out_shape=jax.ShapeDtypeStruct((t, dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=2 * (out_bytes + tile_bytes) + (16 << 20)),
        interpret=interpret,
        name="expert_combine",
    )(order.astype(jnp.int32), w.astype(jnp.float32).reshape(t * k),
      n_live, ys)


def expert_combine(ys, order, w, n_live, out_bytes: int = _OUT_BYTES,
                   tile_bytes: int = _TILE_BYTES,
                   interpret: Optional[bool] = None):
    """``ys (M, dim)`` float32, ``order (M,)`` int32 (row ``r`` holds
    pair ``order[r] = t * k + j``), ``w (T, k)`` float32, ``n_live ()``
    int32 -> ``(T, dim)`` float32: every row ``r < n_live`` weighed by
    its pair's ``w`` and added into its token's row, in row order; the
    rows behind are not read, whatever they hold.  ``dim`` is a multiple
    of 128."""
    return _call(ys, order, w, n_live, out_bytes=out_bytes,
                 tile_bytes=tile_bytes,
                 interpret=_resolve_interpret(interpret))


def plain(ys, order, w, n_live):
    """The same sum from the token's side, in plain XLA: the inverse of
    ``order`` says at which row a pair lies, and a token gathers its
    own.  What runs off the TPU and the kernel's reference."""
    import jax.numpy as jnp

    (m, _dim), (t, k) = ys.shape, w.shape
    # a pair that is in no row keeps ``m``, which is no live row
    at = jnp.full((t * k,), m, jnp.int32).at[order].set(
        jnp.arange(m, dtype=jnp.int32)).reshape(t, k)
    rows = ys[jnp.minimum(at, m - 1)]                      # (T, k, dim)
    # a ``where``, not a product: a row that is not live may hold
    # anything
    return jnp.sum(jnp.where((at < n_live)[:, :, None],
                             w[:, :, None] * rows, 0.0), axis=1)


def combine(ys, order, w, n_live):
    """What ``serve`` calls (operands as :func:`expert_combine`): the
    kernel on the TPU where ``dim`` fills whole lanes, the plain form
    elsewhere, as ``delta_rule.step`` chooses."""
    from .device_ops import _on_tpu

    if ys.shape[1] % 128 == 0 and _on_tpu():
        return expert_combine(ys, order, w, n_live)
    with jax.named_scope("expert_combine"):
        return plain(ys, order, w, n_live)
