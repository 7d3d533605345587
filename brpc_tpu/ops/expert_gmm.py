"""The held experts' grouped matrix product (``models/moe.py serve``).

``xs (M, K)`` holds the rows that fell on the experts held here,
sorted by expert, at the FRONT of a buffer sized for the worst case;
``sizes (n,)`` says how many rows each expert took; ``w (n, K, N)`` are
the experts' weights as stored (or ``(n, N, K)``, ``transposed``).  Row
``r`` of group ``g`` gives ``xs[r] @ w[g]`` (``xs[r] @ w[g].T``).  A
decode step puts ~16 rows into a 512-row buffer, 1-3 an expert, so the
product is bound by the bytes of the TOUCHED experts' weights and by
nothing else, if the kernel lets it be:

- the grid walks VISITS, not the buffer: a visit is one (row tile,
  expert) pair that has rows.  The list is built from ``sizes``
  (:func:`visit_list`), scalar-prefetched, and its length is the
  grid's dynamic extent: the rows of padding behind the last group are
  never touched, and an expert without rows is never visited, so its
  weights are never fetched;
- a weight block holds the WHOLE contraction where that fits
  (``_BLOCK_BYTES``), so an expert whose rows cross a row-tile boundary
  meets the same block index on consecutive grid steps and the pipeline
  fetches it once; each touched expert's ``(K, N)`` goes by once a call
  in double-buffered ``(tk, tn)`` blocks;
- the order is (column block, visit, contraction block), megablox's
  (``jax.experimental.pallas.ops.tpu.megablox``): the output tile of a
  row tile is revisited by consecutive visits and written back once.

Rows of a VISITED tile that belong to no group read 0; tiles no visit
names are not written at all (``serve`` masks them).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .flash_attention import _resolve_interpret

# rows a visit puts through the MXU.  On the v5e (PERF.md §6, PR 32:
# ``chip_gmm.py tiles``, 16-256 rows) a decode step's 16 live rows cost
# the same at every height up to 128 (the weights' DMA sets the pace),
# and a prefill's 256 live rows in 8,192 cost least at 128-256
_ROW_TILE = 128
# one weight block in VMEM; two are resident (the pipeline's double
# buffer).  2, 4 and 7 MB read within 4% of each other; 4 is never worst
_BLOCK_BYTES = 4 << 20


def visit_list(sizes, tiles_m: int, tm: int):
    """``sizes (n,)`` int32, the groups' rows in order from row 0 of a
    buffer of ``tiles_m`` row tiles of ``tm`` -> ``(offsets (n + 1,),
    group (V,), tile (V,), visits ())``, all int32: the first
    ``visits`` entries of ``group`` / ``tile`` name every (expert, row
    tile) pair that has rows, in row order (so by expert, then by
    tile); ``V = tiles_m + n - 1`` is the most there can be, and the
    entries past ``visits`` repeat the last one.  A group without rows
    is in no visit."""
    import jax.numpy as jnp

    sizes = sizes.astype(jnp.int32)
    n = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tm
    per = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    vend = jnp.cumsum(per)
    visits = vend[-1]
    v = jnp.clip(jnp.arange(tiles_m + n - 1, dtype=jnp.int32), 0,
                 jnp.maximum(visits - 1, 0))[:, None]
    # visit v is group g's where g's visits begin at or before it and
    # end behind it: one group, or none where there is no visit at all
    mine = (v >= (vend - per)[None, :]) & (v < vend[None, :])
    group = jnp.sum(mine * jnp.arange(n, dtype=jnp.int32)[None, :], axis=1)
    tile = v[:, 0] + jnp.sum(mine * (first - vend + per)[None, :], axis=1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, tile, visits


def _blocks(k: int, n: int, itemsize: int, block_bytes: int):
    """``(tk, tn)``: the whole contraction and the widest column block
    of whole 128-lane tiles that divides ``n`` and fits ``block_bytes``;
    where a 128-wide slab of the whole contraction does not fit, the
    contraction is cut too.  A width that is no multiple of 128 goes
    whole: an output tile's lanes must be whole tiles or the whole row
    (a published expert width takes this branch, nemotron-3-nano-30b's
    1,856: its up product's block is ``(896, 1856)``, its down
    product's ``(1856, 896)``; the toys' widths take it too)."""
    def widest(full: int, other: int) -> int:
        fits = [t for t in range(128, full + 1, 128)
                if full % t == 0 and t * other * itemsize <= block_bytes]
        return max(fits) if fits else (128 if full % 128 == 0 else full)

    tn = widest(n, k)
    tk = k if k * tn * itemsize <= block_bytes else widest(k, tn)
    return tk, tn


def _row_tile(m: int, tm: int) -> int:
    """A buffer shorter than the row tile is one tile of its own rows,
    rounded up to whole sublanes."""
    return min(tm, -(-m // 8) * 8)


def _kernel(offs_ref, group_ref, tile_ref, x_ref, w_ref, o_ref, acc, *,
            tm: int, transposed: bool = False):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    v, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    if transposed:
        # a ``(tn, tk)`` block of the weights as they lie: the MXU takes
        # its rows as the product's columns
        acc[...] += lax.dot_general(x_ref[...], w_ref[...],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    else:
        acc[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        g, t = group_ref[v], tile_ref[v]
        row = t * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (row >= offs_ref[g]) & (row < offs_ref[g + 1])
        # the first visit of a row tile finds whatever the buffer held
        fresh = (v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != t)
        held = jnp.where(fresh, 0.0, o_ref[...])
        o_ref[...] = jnp.where(mine, acc[...], held)


@functools.partial(jax.jit, static_argnames=("tm", "block_bytes", "interpret",
                                             "transposed"))
def _call(xs, w, sizes, tm: int, block_bytes: int, interpret: bool,
          transposed: bool = False):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (m, k), n = xs.shape, w.shape[1 if transposed else 2]
    tm = _row_tile(m, tm)
    tiles_m = -(-m // tm)
    if tiles_m * tm != m:
        xs = jnp.pad(xs, ((0, tiles_m * tm - m), (0, 0)))
    tk, tn = _blocks(k, n, w.dtype.itemsize, block_bytes)
    with jax.named_scope("moe_route"):      # ``models/moe.py serve``'s
        offsets, group, tile, visits = visit_list(sizes, tiles_m, tm)
    if transposed:
        w_spec = pl.BlockSpec((None, tn, tk), lambda j, v, kb, offs, grp,
                              til: (grp[v], j, kb))
        body = functools.partial(_kernel, tm=tm, transposed=True)
    else:
        w_spec = pl.BlockSpec((None, tk, tn), lambda j, v, kb, offs, grp,
                              til: (grp[v], kb, j))
        body = functools.partial(_kernel, tm=tm)
    out = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, visits, k // tk),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda j, v, kb, offs, grp, til: (til[v], kb)),
                w_spec,
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, kb, offs, grp, til: (til[v], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tiles_m * tm, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            # two weight blocks, two row tiles of the whole contraction,
            # the output tile and the accumulator; the default is 16 MB
            vmem_limit_bytes=4 * block_bytes + (16 << 20)),
        interpret=interpret,
        name="expert_gmm",
    )(offsets, group, tile, xs, w)
    return out[:m]


def expert_gmm(xs, w, sizes, tm: int = _ROW_TILE,
               block_bytes: int = _BLOCK_BYTES,
               interpret: Optional[bool] = None, transposed: bool = False):
    """``xs (M, K)`` rows sorted by group from row 0, ``w (n, K, N)``,
    ``sizes (n,)`` int32 -> ``(M, N)`` float32: row ``r`` of group
    ``g`` is ``xs[r] @ w[g]``, operands as they come (bfloat16 in the
    served program), accumulated in float32.  ``transposed``: ``w`` is
    ``(n, N, K)`` and row ``r`` gives ``xs[r] @ w[g].T`` (weights whose
    ``N`` is no multiple of 128 lie so: the device's default layout of
    ``(n, K, N)`` would put ``K`` on the lanes, and the kernel, which
    reads its operands row-major, would have them copied each call).  What
    ``jax.lax.ragged_dot`` gives, but for the rows behind the last
    group: those of a row tile some group reaches read 0, the others
    are NOT WRITTEN.  The cost follows the rows present: a grid step a
    (row tile, group) pair with rows, a weight fetch a touched group."""
    return _call(xs, w, sizes, tm=tm, block_bytes=block_bytes,
                 interpret=_resolve_interpret(interpret),
                 transposed=transposed)
