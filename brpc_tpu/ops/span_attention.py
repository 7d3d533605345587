"""Span attention — a span of a prompt's rows into and against its
session's pages: for key/value heads shared by groups of query heads
and layers whose reach is a window, and for whole heads without one.

A window schedule (``LMConfig.windows``) and a looped one
(``LMConfig.passes``) fill a prompt in spans of ``fill_span`` rows
(``transformer_lm.make_paged_span_fill``), whole pages each: a span's
keys and values go into its session's pages as whole pages
(:func:`write`: on the TPU the kernel ``span_page_write``, copies from
HBM to HBM into the pool in place), then its queries attend over what
lies in them (:func:`attention`).  A whole-head pool ``(num_pages, page, heads,
hd)`` is the grouped layout with a group of one (the same bytes in the
same order), and is taken as it is.

The attention is a flash kernel (``span_flash_attention`` in a device
trace) with an online softmax, operands bfloat16, scores, softmax and
accumulation float32, in one of two forms, chosen by the span's shape
(:func:`in_place`):

- where ONE query block holds the span's rows of a key/value head,
  the keys are read where they lie: the block table is looked up in
  the kernel and whole pages are copied from the float32 pools into
  key blocks in VMEM, two buffers, a page ahead of the span's last row
  or behind a window's reach never fetched.  No operand of the
  table's keys is built, cast or transposed;
- where the span takes several query blocks, each of them would fetch
  and re-lay its keys again (a page's rows are (token, head) pairs:
  a head's keys are a strided read), so the table's entries the span
  can reach are gathered ONCE into ``(kv_heads, keys, hd)`` bfloat16
  (a window layer: ``(window + W) // page + 2`` entries; a layer
  without: the whole table) and the kernel walks that in blocks,
  ``(kv_heads, query blocks, key blocks)``, a key block wholly ahead
  of the query block's rows or wholly behind their window neither
  fetched anew nor computed.

Off the TPU the plain formulation (:func:`reference`) runs instead, in
float32; the kernels are interpreted in ``tests/test_window_experts.py``
and ``tests/test_looped_lm.py``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .flash_attention import _resolve_interpret

_BLOCK_Q = 64          # query TOKENS a block of the gathered form
_BLOCK_ROWS = 1024     # query rows of ONE key/value head that read in
#                        place: one block (the gathered form's: 64 x 16)
_BLOCK_K = 512         # keys a block at most
_BLOCK_K_BYTES = 2 << 20   # a block's keys in a pool, read in place
# the in-place kernel's buffers (two blocks of keys and of values, a
# query block's rows, sums and output) at 1,024 rows of 16 heads: 56 MB
# of the chip's 128
_VMEM_BYTES = 64 << 20


def _kv_heads(pool, page: int) -> int:
    return pool.shape[2] if pool.ndim == 4 else pool.shape[1] // page


def in_place(w: int, group: int) -> bool:
    """Whether a span of ``w`` rows on groups of ``group`` query heads
    reads its keys where they lie: its rows of a key/value head are
    one query block."""
    return w * group <= _BLOCK_ROWS


def pages_reached(start: int, w: int, page: int, window: int = 0):
    """``(first, end)``: the entries of a session's block table that
    hold what a span of ``w`` rows from position ``start`` attends
    (``end`` is one past)."""
    first = max(start - window + 1, 0) // page if window else 0
    return first, (start + w - 1) // page + 1


def table_reach(table: int, w: int, page: int, window: int = 0) -> int:
    """Entries of a block table of ``table`` pages that a span of ``w``
    rows can reach, wherever it starts."""
    return min(table, (window + w) // page + 2) if window else table


def pages_fetched(start: int, w: int, page: int, table: int, group: int,
                  window: int = 0) -> int:
    """Entries of a block table of ``table`` pages whose pages a span's
    attention fetches in one layer: those it reaches where it reads
    in place, those it can reach where it gathers."""
    if not in_place(w, group):
        return table_reach(table, w, page, window)
    first, end = pages_reached(start, w, page, window)
    return min(end, table) - first


def _table_slice(row, start, w: int, page: int, window: int):
    """``(entries, k0)``: the :func:`table_reach` entries of ``row``
    from the page that holds the first position the span's first row
    reaches, and that page's first position."""
    import jax.numpy as jnp

    if not window:
        return row, 0
    reach = table_reach(row.shape[0], w, page, window)
    p0 = jnp.clip((start - window + 1) // page, 0, row.shape[0] - reach)
    return jax.lax.dynamic_slice(row, (p0,), (reach,)), p0 * page


def write_plain(pool, rows, page_ids, n, page: int):
    """:func:`write` as XLA runs it: the span's pages read, merged with
    the real rows and written by one scatter (a page wholly past ``n``
    goes back as it was, and so does the garbage page that stands for
    it)."""
    import jax.numpy as jnp

    pages = page_ids.shape[0]
    by_row = (pages, page) + rows.shape[1:]
    real = (jnp.arange(pages * page) < n).reshape(pages, page, 1, 1)
    merged = jnp.where(real, rows.reshape(by_row),
                       pool[page_ids].reshape(by_row))
    return pool.at[page_ids].set(merged.reshape((pages,) + pool.shape[1:]))


def _write_kernel(ids_ref, n_ref, rows_hbm, pool_hbm, out_hbm, sem, *,
                  page: int, kvh: int):
    """Copies from HBM to HBM, all started, then all waited for: a page
    whose rows are all real whole, the partial page's real rows a token
    (``kvh`` rows) at a time; a page wholly past ``n`` not at all."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del pool_hbm                       # (the pool, updated in place)
    pages, n, rows = ids_ref.shape[0], n_ref[0], page * kvh
    last = n // page                   # the partial page, if any
    pid = ids_ref[jnp.minimum(last, pages - 1)]

    def copies(go):
        for j in range(pages):

            @pl.when((j + 1) * page <= n)
            def _():
                go(pltpu.make_async_copy(
                    rows_hbm.at[pl.ds(j * rows, rows)],
                    out_hbm.at[ids_ref[j]], sem))

        for t in range(page):

            @pl.when(last * page + t < n)
            def _():
                go(pltpu.make_async_copy(
                    rows_hbm.at[pl.ds((last * page + t) * kvh, kvh)],
                    out_hbm.at[pid, pl.ds(t * kvh, kvh)], sem))

    copies(lambda c: c.start())
    copies(lambda c: c.wait())


@functools.partial(jax.jit, static_argnames=("page", "interpret"))
def _write_call(pool, rows, page_ids, n, page: int, interpret: bool):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w, kvh, hd = rows.shape
    flat = (pool.shape[0], page * kvh, hd)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_write_kernel, page=page, kvh=kvh),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[anywhere, anywhere],
            out_specs=anywhere,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(flat, pool.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
        name="span_page_write",
    )(page_ids.astype(jnp.int32), jnp.reshape(n, (1,)).astype(jnp.int32),
      rows.reshape(w * kvh, hd), pool.reshape(flat)).reshape(pool.shape)


def span_page_write(pool, rows, page_ids, n, page: int,
                    interpret: Optional[bool] = None):
    """:func:`write` as a kernel (``span_page_write`` in a device
    trace): the pool updated in place by copies of whole pages from the
    rows where XLA left them, nothing read back, nothing staged."""
    return _write_call(pool, rows, page_ids, n, page=page,
                       interpret=_resolve_interpret(interpret))


def write(pool, rows, page_ids, n, page: int):
    """A span's rows ``(W, kv_heads, hd)``, ``n`` of them real, into
    the ``W // page`` pages ``page_ids`` of ``pool`` (either layout)
    they fill from their first row on: whole pages.  A row from ``n``
    on keeps what lay in its place, and a page wholly past ``n`` is
    not written (its entry should name the garbage page).  The kernel
    on the TPU where a token's rows are whole tiles,
    :func:`write_plain` elsewhere."""
    from .device_ops import _on_tpu
    if _on_tpu() and rows.shape[1] % 8 == 0 and rows.shape[2] % 128 == 0:
        return span_page_write(pool, rows, page_ids, n, page)
    return write_plain(pool, rows, page_ids, n, page)


def _gather(pool, page_ids, page: int):
    """``pool (num_pages, page * kv_heads, hd)`` (a row a (token,
    key/value head) pair; or ``(num_pages, page, heads, hd)``) at
    ``page_ids (P,)`` -> ``(kv_heads, P * page, hd)``."""
    kvh = _kv_heads(pool, page)
    x = pool[page_ids].reshape(page_ids.shape[0] * page, kvh,
                               pool.shape[-1])
    return x.transpose(1, 0, 2)


def _allowed(qpos, kpos, window: int):
    ok = kpos <= qpos
    if window:
        ok = ok & (kpos > qpos - window)
    return ok


def reference(q, pk, pv, page_ids, q0, k0, page: int, window: int = 0):
    """The plain formulation: ``q (W, heads, hd)`` at positions ``q0 +
    0..W-1`` against the rows of the pages ``page_ids``, which hold
    positions ``k0 + 0..P*page-1``; row ``p`` attends ``p - window < j
    <= p`` (``window`` 0: every ``j <= p``).  Float32."""
    import jax.numpy as jnp

    w, heads, hd = q.shape
    k, v = _gather(pk, page_ids, page), _gather(pv, page_ids, page)
    kvh = k.shape[0]
    qg = q.reshape(w, kvh, heads // kvh, hd)
    s = jnp.einsum("qhgd,hkd->hgqk", qg, k,
                   preferred_element_type=jnp.float32) / (hd ** 0.5)
    ok = _allowed((q0 + jnp.arange(w))[:, None],
                  (k0 + jnp.arange(k.shape[1]))[None, :], window)
    p = jax.nn.softmax(jnp.where(ok[None, None], s, -1e30), axis=-1)
    out = jnp.einsum("hgqk,hkd->qhgd", p, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(w, heads, hd)


def _reach(pos_ref, iq, bq: int, keys: int, window: int):
    """First and last key (counted from the first page handed in) the
    rows of query block ``iq`` reach."""
    import jax.numpy as jnp

    a = pos_ref[0] + iq * bq - pos_ref[1]      # its first row, in keys
    hi = jnp.clip(a + bq - 1, 0, keys - 1)
    lo = jnp.minimum(jnp.maximum(a - window + 1, 0), hi) if window else 0
    return lo, hi


def _flash_update(s, v, m_scr, l_scr, acc_scr):
    """One key block's scores ``s`` (masked) and values ``v`` into the
    running maximum, denominator and accumulator."""
    import jax.numpy as jnp

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)


# -- the keys read where they lie ---------------------------------------------

def _paged_kernel(ids_ref, pos_ref, q_ref, pk_hbm, pv_hbm, o_ref,
                  kbuf, vbuf, sems, g_ref, m_scr, l_scr, acc_scr, *,
                  page: int, kvh: int, group: int, bq: int, bk: int,
                  window: int):
    """One grid step a block of ``bq`` query tokens (every head's rows
    of them).  Its key blocks, ``bk`` keys each from the first it
    reaches to the last, are ONE sequence through two buffers with the
    next query block's: whole pages copied from the pools where the
    block table says they lie, the next block's on their way while this
    one is computed.  A page no row of the query block reaches is not
    fetched; what stands in its place in the buffer meets a zero
    weight, as zeros."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    iq, nq = pl.program_id(0), pl.num_programs(0)
    n_ids = ids_ref.shape[0]
    keys = n_ids * page
    ppb, rows = bk // page, page * kvh
    bf = jnp.bfloat16

    def block_dma(i, kb, buf, go):
        lo, hi = _reach(pos_ref, i, bq, keys, window)
        for j in range(ppb):
            idx = kb * ppb + j

            @pl.when(jnp.logical_and(idx >= lo // page, idx <= hi // page))
            def _():
                pid = ids_ref[jnp.minimum(idx, n_ids - 1)]
                dst = pl.ds(j * rows, rows)
                for hbm, vmem, s in ((pk_hbm, kbuf, 0), (pv_hbm, vbuf, 1)):
                    go(pltpu.make_async_copy(
                        hbm.at[pid], vmem.at[buf, dst], sems.at[s, buf]))

    start = functools.partial(block_dma, go=lambda c: c.start())
    wait = functools.partial(block_dma, go=lambda c: c.wait())

    lo, hi = _reach(pos_ref, iq, bq, keys, window)

    @pl.when(iq == 0)
    def _():
        g_ref[0] = 0
        start(0, lo // bk, 0)

    m_scr[...] = jnp.full_like(m_scr, -1e30)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    qpos = pos_ref[0] + iq * bq + lax.broadcasted_iota(
        jnp.int32, (bq * group, 1), 0) // group           # (rows, 1)
    nt = (((1,), (1,)), ((), ()))

    def block(kb, _):
        g = g_ref[0]
        buf = lax.rem(g, 2)

        @pl.when(kb < hi // bk)
        def _():
            start(iq, kb + 1, 1 - buf)

        @pl.when(jnp.logical_and(kb == hi // bk, iq + 1 < nq))
        def _():
            start(iq + 1,
                  _reach(pos_ref, iq + 1, bq, keys, window)[0] // bk,
                  1 - buf)

        wait(iq, kb, buf)
        krow = kb * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        kcol = kb * bk + lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        ok = _allowed(qpos, pos_ref[1] + krow, window)
        # rows of pages not fetched: 0 * NaN is NaN
        there = jnp.logical_and(kcol >= lo, kcol <= hi)
        for h in range(kvh):
            sel = pl.ds(h, bk, stride=kvh) if kvh > 1 else pl.ds(0, bk)
            k = kbuf[buf, sel, :].astype(bf)              # (bk, hd)
            v = jnp.where(there, vbuf[buf, sel, :], 0.0).astype(bf)
            s = lax.dot_general(q_ref[h], k, nt,
                                preferred_element_type=jnp.float32)
            _flash_update(jnp.where(ok, s, -1e30), v, m_scr.at[h],
                          l_scr.at[h], acc_scr.at[h])
        g_ref[0] = g + 1
        return 0

    lax.fori_loop(lo // bk, hi // bk + 1, block, 0)
    o_ref[...] = acc_scr[...] / l_scr[...]


@functools.partial(jax.jit, static_argnames=(
    "page", "group", "window", "bq", "bk", "interpret"))
def _paged_call(q, pk, pv, page_ids, pos, page: int, group: int,
                window: int, bq: int, bk: int, interpret: bool):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kvh, rows, hd = q.shape
    br = bq * group
    mine = pl.BlockSpec((kvh, br, hd), lambda iq, ids, pos: (0, iq, 0))
    buffers = (2, bk * kvh, hd)
    # (a whole-head pool is the grouped layout: the same bytes)
    flat = (pk.shape[0], page * kvh, hd)
    return pl.pallas_call(
        functools.partial(_paged_kernel, page=page, kvh=kvh, group=group,
                          bq=bq, bk=bk, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // br,),
            in_specs=[
                mine,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=mine,
            scratch_shapes=[
                pltpu.VMEM(buffers, pk.dtype),
                pltpu.VMEM(buffers, pv.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),            # blocks so far
                pltpu.VMEM((kvh, br, 1), jnp.float32),  # running max
                pltpu.VMEM((kvh, br, 1), jnp.float32),  # running denom
                pltpu.VMEM((kvh, br, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((kvh, rows, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="span_flash_attention",
    )(page_ids, pos, q, pk.reshape(flat), pv.reshape(flat))


# -- the keys gathered once ---------------------------------------------------

def _key_blocks(pos_ref, iq, bq: int, bk: int, nk: int, window: int):
    """First and last key block the query block ``iq`` reaches."""
    lo, hi = _reach(pos_ref, iq, bq, nk * bk, window)
    return lo // bk, hi // bk


def _gathered_kernel(pos_ref, qt_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                     l_scr, acc_scr, *, bq: int, bk: int, nk: int,
                     window: int):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    iq, ik = pl.program_id(1), pl.program_id(2)
    lo, hi = _key_blocks(pos_ref, iq, bq, bk, nk, window)

    @pl.when(ik == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(jnp.logical_and(ik >= lo, ik <= hi))
    def _():
        s = lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        qpos = pos_ref[0] + qt_ref[:]                     # (rows, 1)
        kpos = pos_ref[1] + ik * bk + lax.broadcasted_iota(
            jnp.int32, (1, bk), 1)
        _flash_update(jnp.where(_allowed(qpos, kpos, window), s, -1e30),
                      v_ref[0], m_scr, l_scr, acc_scr)

    @pl.when(ik == nk - 1)
    def _():
        o_ref[0] = acc_scr[...] / l_scr[...]


@functools.partial(jax.jit, static_argnames=(
    "group", "window", "bq", "bk", "interpret"))
def _gathered_call(q, k, v, pos, group: int, window: int, bq: int,
                   bk: int, interpret: bool):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kvh, rows, hd = q.shape
    nq, nk = rows // (bq * group), k.shape[1] // bk
    br = bq * group
    qtok = (jnp.arange(rows, dtype=jnp.int32) // group)[:, None]

    def kv_map(h, iq, ik, pos):
        lo, hi = _key_blocks(pos, iq, bq, bk, nk, window)
        return h, jnp.clip(ik, lo, hi), 0

    return pl.pallas_call(
        functools.partial(_gathered_kernel, bq=bq, bk=bk, nk=nk,
                          window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(kvh, nq, nk),
            in_specs=[
                pl.BlockSpec((br, 1), lambda h, iq, ik, pos: (iq, 0)),
                pl.BlockSpec((1, br, hd),
                             lambda h, iq, ik, pos: (h, iq, 0)),
                pl.BlockSpec((1, bk, hd), kv_map),
                pl.BlockSpec((1, bk, hd), kv_map)],
            out_specs=pl.BlockSpec((1, br, hd),
                                   lambda h, iq, ik, pos: (h, iq, 0)),
            scratch_shapes=[pltpu.VMEM((br, 1), jnp.float32),
                            pltpu.VMEM((br, 1), jnp.float32),
                            pltpu.VMEM((br, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((kvh, rows, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="span_flash_attention",
    )(pos, qtok, q, k, v)


def span_flash_attention(q, pk, pv, page_ids, q0, k0, page: int,
                         window: int = 0, block_q: Optional[int] = None,
                         block_k: Optional[int] = None,
                         interpret: Optional[bool] = None,
                         paged: Optional[bool] = None):
    """:func:`reference` as a flash kernel: operands bfloat16, the
    softmax float32.  ``paged`` (by default :func:`in_place`'s answer)
    reads the keys where they lie: entries of ``page_ids`` ahead of
    the span's last row, or behind a window's reach, are then never
    read; otherwise the pages of ``page_ids`` are gathered, and padded
    to whole key blocks with rows no query is allowed.  ``W`` must be
    a multiple of the query block (``block_q`` tokens; by default the
    whole span in place, ``_BLOCK_Q`` gathered); a key block is
    ``block_k`` keys (by default ``_BLOCK_K``; in place whole pages of
    ``_BLOCK_K_BYTES`` in a pool, if that is fewer)."""
    import jax.numpy as jnp

    w, heads, hd = q.shape
    kvh = _kv_heads(pk, page)
    g = heads // kvh
    if paged is None:
        paged = in_place(w, g)
    bq = min(block_q or (w if paged else _BLOCK_Q), w)
    assert w % bq == 0
    keys = page_ids.shape[0] * page
    bf = jnp.bfloat16
    qg = (q * (1.0 / hd ** 0.5)).astype(bf).reshape(w, kvh, g, hd) \
        .transpose(1, 0, 2, 3).reshape(kvh, w * g, hd)
    pos = jnp.stack([jnp.asarray(q0, jnp.int32),
                     jnp.asarray(k0, jnp.int32)])
    interpret = _resolve_interpret(interpret)
    if paged:
        bk = block_k or min(_BLOCK_K, _BLOCK_K_BYTES // (kvh * hd * 4))
        bk = max(page, min(bk, keys) // page * page)
        out = _paged_call(qg, pk, pv, page_ids.astype(jnp.int32), pos,
                          page=page, group=g, window=int(window), bq=bq,
                          bk=bk, interpret=interpret)
    else:
        bk = min(block_k or _BLOCK_K, keys)
        pad = -keys % bk
        if pad:
            # (the garbage page: its rows lie ahead of every query)
            page_ids = jnp.concatenate(
                [page_ids, jnp.zeros((pad // page,), page_ids.dtype)])
        out = _gathered_call(
            qg, _gather(pk, page_ids, page).astype(bf),
            _gather(pv, page_ids, page).astype(bf), pos, group=g,
            window=int(window), bq=bq, bk=bk, interpret=interpret)
    return out.reshape(kvh, w, g, hd).transpose(1, 0, 2, 3) \
        .reshape(w, heads, hd)


def attention(q, pk, pv, row, start, page: int, window: int = 0):
    """A span's attention, ``q (W, heads, hd)`` at positions ``start +
    0..W-1``, through its session's row of the block table: the
    kernel on the TPU, :func:`reference` on the cpu backend.  A window
    layer is handed the :func:`table_reach` entries the span can
    reach, so what lies behind them may have been given back."""
    from .device_ops import _on_tpu
    ids, k0 = _table_slice(row, start, q.shape[0], page, window)
    run = span_flash_attention if _on_tpu() else reference
    return run(q, pk, pv, ids, start, k0, page, window)
