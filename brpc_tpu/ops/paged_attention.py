"""Paged decode attention — the batched step's attention over the LIVE
pages of each slot, read where they lie in the layer's page pool.

The pool keeps its layout, ``(num_pages, page, heads, hd)`` float32
(``make_paged_io``, the KV export and the prefix cache depend on it):
one page is one contiguous block, the DMA unit.  Per slot the kernel
walks ``pos // page + 1`` pages of the block table in waves of a few
pages, double-buffered (the next wave — of this slot, or the first of
the next slot — is in flight while this one is computed), with an
online softmax; no ``(slots, max_seq, heads, hd)`` copy is ever built.

Heads lie on the sublanes of a page and ``hd`` on its lanes, so a wave
``(tokens, heads, hd)`` is used as it arrives, in float32 on the VPU:
scores are ``sum(k * q, lanes)``, one per (token, head); weights and
the accumulator ``(heads, hd)`` follow.  With one query row a head the
MXU would want the page relaid out (tokens on sublanes) or bfloat16
operands; the VPU needs neither and keeps up with the pages' bytes
(measured on the v5e: 710-745 GB/s of 819 over live pages, PERF.md).
The arithmetic is float32 throughout, as XLA runs the einsums of
:func:`reference` (the formulation the step had, and has off the TPU).

Where fewer key/value heads serve groups of query heads the pool is
``(num_pages, page * kv_heads, hd)``, a row a (token, key/value head)
pair, and a second kernel under the same name walks it
(:func:`_grouped_kernel`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .flash_attention import _resolve_interpret

# one wave's K (or V) bytes in VMEM: two of each are resident, plus two
# products of a wave's size.  Measured on the v5e at 0.5, 1, 2 and 4 MB:
# the same rate over long contexts, the smallest best over short ones
_WAVE_BYTES = 512 << 10


def reference(q, pk, pv, bt, pos, page: Optional[int] = None,
              window: int = 0):
    """The plain formulation: gather every slot's whole block table
    into a ``(slots, max_seq, heads, hd)`` view and run a dense masked
    attention over it.  What the step runs off the TPU, and what the
    kernels are tested against.  Pools of three dimensions are the
    grouped layout, ``(num_pages, page * kv_heads, hd)`` with a row a
    (token, key/value head) pair and each key/value head shared by
    ``heads // kv_heads`` query heads; they need ``page``.  With
    ``window`` a slot attends ``pos - window < j <= pos`` only."""
    import jax.numpy as jnp

    b, heads, hd = q.shape
    if pk.ndim == 4:
        page, kv_heads = pk.shape[1], heads
    else:
        kv_heads = pk.shape[1] // page
    max_seq = bt.shape[1] * page

    def view(pool):
        x = pool[bt].reshape(b, max_seq, kv_heads, hd)
        return x if kv_heads == heads else \
            jnp.repeat(x, heads // kv_heads, axis=2)

    s_mat = jnp.einsum("bhd,bkhd->bhk", q, view(pk),
                       preferred_element_type=jnp.float32) / (hd ** 0.5)
    live = jnp.arange(max_seq)[None, :] <= pos[:, None]
    if window:
        live = live & (jnp.arange(max_seq)[None, :]
                       > pos[:, None] - window)
    s_mat = jnp.where(live[:, None, :], s_mat, -1e30)
    p = jax.nn.softmax(s_mat, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", p, view(pv),
                      preferred_element_type=jnp.float32)


def pages_per_wave(page: int, heads: int, hd: int, pps: int) -> int:
    """Pages a wave fetches, from the bytes of one page as given."""
    return max(1, min(pps, _WAVE_BYTES // (page * heads * hd * 4)))


def _kernel(bt_ref, pos_ref, q_ref, pk_hbm, pv_hbm, o_ref,
            kbuf, vbuf, sems, m_scr, l_scr, acc_scr, *,
            page: int, heads: int, wave: int):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, _, hd = q_ref.shape
    toks = wave * page
    scale = 1.0 / (hd ** 0.5)

    def n_pages(b):
        return pos_ref[b] // page + 1

    def wave_dma(b, w, buf, go):
        """Start (or wait for) the live pages of wave ``w`` of slot
        ``b`` into buffer ``buf``: nothing past the slot's last live
        page is fetched."""
        for i in range(wave):
            idx = w * wave + i

            @pl.when(idx < n_pages(b))
            def _():
                pid = bt_ref[b, idx]
                dst = pl.ds(i * page, page)
                for hbm, vmem, j in ((pk_hbm, kbuf, 0), (pv_hbm, vbuf, 1)):
                    go(pltpu.make_async_copy(
                        hbm.at[pid], vmem.at[buf, dst], sems.at[j, buf]))

    start = functools.partial(wave_dma, go=lambda c: c.start())
    wait = functools.partial(wave_dma, go=lambda c: c.wait())

    tok = lax.broadcasted_iota(jnp.int32, (toks, heads, 1), 0)

    start(0, 0, 0)

    def slot_body(b, g):
        n_waves = (n_pages(b) + wave - 1) // wave
        m_scr[:] = jnp.full_like(m_scr, -1e30)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        q = q_ref[b] * scale

        def wave_body(w, g):
            buf = lax.rem(g, 2)

            @pl.when(w + 1 < n_waves)
            def _():
                start(b, w + 1, 1 - buf)

            @pl.when(jnp.logical_and(w + 1 == n_waves, b + 1 < slots))
            def _():
                start(b + 1, 0, 1 - buf)

            wait(b, w, buf)
            # tokens of this wave at positions <= pos
            lim = pos_ref[b] + 1 - w * toks

            @pl.when(lim < toks)
            def _():
                # what lies past pos (stale rows, pages not fetched)
                # would meet a zero weight, and 0 * NaN is NaN
                vbuf[buf] = jnp.where(tok < lim, vbuf[buf], 0.0)

            s = jnp.sum(kbuf[buf] * q[None], axis=-1, keepdims=True)
            s = jnp.where(tok < lim, s, -1e30)          # (toks, heads, 1)
            m_prev = m_scr[:]
            m_new = jnp.maximum(m_prev, s.max(axis=0))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[None])
            l_scr[:] = l_scr[:] * corr + p.sum(axis=0)
            m_scr[:] = m_new
            acc_scr[:] = acc_scr[:] * corr + (p * vbuf[buf]).sum(axis=0)
            return g + 1

        g = lax.fori_loop(0, n_waves, wave_body, g)
        o_ref[b] = acc_scr[:] / l_scr[:]
        return g

    lax.fori_loop(0, slots, slot_body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_call(q, pk, pv, bt, pos, interpret: bool):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, hd = q.shape
    page = pk.shape[1]
    wave = pages_per_wave(page, heads, hd, bt.shape[1])
    toks = wave * page
    whole = pl.BlockSpec((slots, heads, hd), lambda i, bt, pos: (0, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, page=page, heads=heads, wave=wave),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[whole,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((2, toks, heads, hd), pk.dtype),
                pltpu.VMEM((2, toks, heads, hd), pv.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((heads, 1), jnp.float32),    # running max
                pltpu.VMEM((heads, 1), jnp.float32),    # running denom
                pltpu.VMEM((heads, hd), jnp.float32),   # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((slots, heads, hd), jnp.float32),
        interpret=interpret,
        name="paged_decode_attention",
    )(bt, pos, q, pk, pv)


def paged_decode_attention(q, pk, pv, bt, pos,
                           interpret: Optional[bool] = None):
    """``q (slots, heads, hd)`` against positions ``0..pos[b]`` of slot
    ``b``, whose pages ``bt[b, :pos[b] // page + 1]`` lie in the pools
    ``pk`` / ``pv (num_pages, page, heads, hd)`` -> ``(slots, heads,
    hd)`` float32.  Block-table entries past the last live page are
    never read.  Traced once however many layers call it."""
    return _paged_call(q, pk, pv, bt, pos,
                       interpret=_resolve_interpret(interpret))


def _grouped_kernel(bt_ref, pos_ref, q_ref, pk_hbm, pv_hbm, o_ref,
                    kbuf, vbuf, sems, *, page: int, kv_heads: int,
                    wave: int):
    """Key/value heads shared by groups of query heads: a wave's rows
    are (token, key/value head) pairs on the sublanes, so a group's
    ``(g, hd)`` queries meet one head's ``(tokens, hd)`` keys as the
    two matrices they are, on the MXU in float32 (``highest``: the
    arithmetic of :func:`reference`).  Same walk over live
    pages, same double buffer, same online softmax as :func:`_kernel`."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, hd = q_ref.shape
    g = heads // kv_heads
    toks = wave * page
    rows = page * kv_heads
    scale = 1.0 / (hd ** 0.5)
    hi = lax.Precision.HIGHEST

    def n_pages(b):
        return pos_ref[b] // page + 1

    def wave_dma(b, w, buf, go):
        for i in range(wave):
            idx = w * wave + i

            @pl.when(idx < n_pages(b))
            def _():
                pid = bt_ref[b, idx]
                dst = pl.ds(i * rows, rows)
                for hbm, vmem, j in ((pk_hbm, kbuf, 0), (pv_hbm, vbuf, 1)):
                    go(pltpu.make_async_copy(
                        hbm.at[pid], vmem.at[buf, dst], sems.at[j, buf]))

    start = functools.partial(wave_dma, go=lambda c: c.start())
    wait = functools.partial(wave_dma, go=lambda c: c.wait())

    tok_col = lax.broadcasted_iota(jnp.int32, (toks, 1), 0)
    tok_row = lax.broadcasted_iota(jnp.int32, (1, toks), 1)

    start(0, 0, 0)

    def slot_body(b, gcount):
        n_waves = (n_pages(b) + wave - 1) // wave
        q = q_ref[b] * scale                              # (heads, hd)

        def wave_body(w, carry):
            gcount, m, l, acc = carry
            buf = lax.rem(gcount, 2)

            @pl.when(w + 1 < n_waves)
            def _():
                start(b, w + 1, 1 - buf)

            @pl.when(jnp.logical_and(w + 1 == n_waves, b + 1 < slots))
            def _():
                start(b + 1, 0, 1 - buf)

            wait(b, w, buf)
            lim = pos_ref[b] + 1 - w * toks
            ms, ls, accs = [], [], []
            for kh in range(kv_heads):
                sel = pl.ds(kh, toks, stride=kv_heads) if kv_heads > 1 \
                    else pl.ds(0, toks)
                k = kbuf[buf, sel, :]                     # (toks, hd)
                # rows past pos are stale or were never fetched: they
                # meet a zero weight, and 0 * NaN is NaN
                v = jnp.where(tok_col < lim, vbuf[buf, sel, :], 0.0)
                qh = q[kh * g:(kh + 1) * g]               # (g, hd)
                s = lax.dot_general(qh, k, (((1,), (1,)), ((), ())),
                                    precision=hi,
                                    preferred_element_type=jnp.float32)
                s = jnp.where(tok_row < lim, s, -1e30)    # (g, toks)
                m_prev = m[kh]
                m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                corr = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                ms.append(m_new)
                ls.append(l[kh] * corr + p.sum(axis=1, keepdims=True))
                accs.append(acc[kh] * corr + jnp.dot(
                    p, v, precision=hi,
                    preferred_element_type=jnp.float32))
            return gcount + 1, ms, ls, accs

        init = (gcount,
                [jnp.full((g, 1), -1e30, jnp.float32)] * kv_heads,
                [jnp.zeros((g, 1), jnp.float32)] * kv_heads,
                [jnp.zeros((g, hd), jnp.float32)] * kv_heads)
        gcount, _m, l, acc = lax.fori_loop(0, n_waves, wave_body, init)
        for kh in range(kv_heads):
            o_ref[b, kh * g:(kh + 1) * g, :] = acc[kh] / l[kh]
        return gcount

    lax.fori_loop(0, slots, slot_body, 0)


@functools.partial(jax.jit, static_argnames=("page", "interpret"))
def _grouped_call(q, pk, pv, bt, pos, page: int, interpret: bool):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, hd = q.shape
    kv_heads = pk.shape[1] // page
    wave = pages_per_wave(page, kv_heads, hd, bt.shape[1])
    whole = pl.BlockSpec((slots, heads, hd), lambda i, bt, pos: (0, 0, 0))
    return pl.pallas_call(
        functools.partial(_grouped_kernel, page=page, kv_heads=kv_heads,
                          wave=wave),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[whole,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[
                pltpu.VMEM((2, wave * page * kv_heads, hd), pk.dtype),
                pltpu.VMEM((2, wave * page * kv_heads, hd), pv.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=jax.ShapeDtypeStruct((slots, heads, hd), jnp.float32),
        interpret=interpret,
        name="paged_decode_attention",
    )(bt, pos, q, pk, pv)


def paged_decode_attention_grouped(q, pk, pv, bt, pos, page: int,
                                   interpret: Optional[bool] = None):
    """:func:`paged_decode_attention` for ``heads`` query heads over
    fewer key/value heads: the pools are ``(num_pages, page *
    kv_heads, hd)``, a row a (token, key/value head) pair."""
    return _grouped_call(q, pk, pv, bt, pos, page=page,
                         interpret=_resolve_interpret(interpret))


# -- window layers beside global ones ----------------------------------------
#
# The grouped layout again, for a schedule in which some layers attend a
# window of the context (``LMConfig.windows``): a slot's walk STARTS at
# the page that holds the window's first position and masks that page's
# head, so a window layer reads ``window // page + 1`` pages however
# long the context is (entries of the block table behind the window may
# have been given back: they are never read).  One grid step a slot,
# the slots' waves one sequence through a ring of buffers, the copies
# of the next ``ring - 1`` waves in flight while one is computed, into
# the next slots where this one ends; a group's ``(g, hd)`` queries meet a
# key/value head's ``(tokens, hd)`` rows on the MXU in bfloat16 with
# float32 accumulation (in float32 at ``highest``, as
# :func:`_grouped_kernel`, sixteen queries a head would cost six passes
# a product and hold the pages' bytes back).  A window layer's call is
# ``window_decode_attention`` in a device trace, a global layer's of
# the same schedule ``paged_decode_attention``.

# waves the window kernel keeps in VMEM: the one it computes and five
# whose pages are on their way (6 x 512 KB x 2 pools).  Measured on the
# v5e at 2 to 8 (``chip_window.py``; PERF.md §6, PR 44): five or six
# reach what the copies reach alone at both cells' pages; at 32-KB
# pages four fall 3% short (a window's 257th page is a wave of its own,
# whose instructions take what sixteen pages' do) and eight 6% (224
# copies in flight); at 64-KB pages four to eight read the same
_WINDOW_RING = 6


def _window_kernel(bt_ref, pos_ref, q_ref, pk_hbm, pv_hbm, o_ref,
                   kbuf, vbuf, sems, g_ref, *, page: int, kv_heads: int,
                   wave: int, window: int):
    """One grid step a slot; the slots' waves are ONE sequence through
    the ring, as in :func:`_latent_kernel` (whose comment says why each
    point pays): the ring, its semaphores and the count of waves so far
    live across grid steps; a wave waits for its pages, computes, and
    only THEN starts the copies of the wave ``ring - 1`` after it, in
    this slot or the next ones, in the one block the wave is (a copy
    with no page to fetch is a predicated instruction), so the copies'
    scalar work runs beside the products' vector work.  A key page and
    its value page signal one semaphore, the buffer's."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    slots = pl.num_programs(0)
    heads, hd = q_ref.shape[1], q_ref.shape[2]
    g = heads // kv_heads
    ring = kbuf.shape[0]
    toks = wave * page
    rows = page * kv_heads
    bf = jnp.bfloat16

    def first_page(s):
        """The page of the first position slot ``s`` attends."""
        if not window:
            return 0
        return lax.div(jnp.maximum(pos_ref[s] - window + 1, 0), page)

    def n_pages(s):
        return lax.div(pos_ref[s], page) + 1 - first_page(s)

    def n_waves(s):
        return lax.div(n_pages(s) + wave - 1, wave)

    def next_wave(s, w):
        """The wave after wave ``w`` of slot ``s``; slot ``slots`` and
        beyond: none."""
        more = w + 1 < n_waves(jnp.minimum(s, slots - 1))
        return jnp.where(more, s, s + 1), jnp.where(more, w + 1, 0)

    def wave_dma(s, w, buf, go):
        """``go`` on the copies of every live page of slot ``s``'s wave
        ``w``, from the window's first page on; none where ``s`` is
        past the last slot."""
        at = jnp.minimum(s, slots - 1)
        first = first_page(at) + w * wave
        live = jnp.where(s < slots, n_pages(at) - w * wave, 0)
        for i in range(wave):

            @pl.when(i < live)
            def _():
                pid = bt_ref[at, first + i]
                dst = pl.ds(i * rows, rows)
                for hbm, vmem in ((pk_hbm, kbuf), (pv_hbm, vbuf)):
                    go(pltpu.make_async_copy(
                        hbm.at[pid], vmem.at[buf, dst], sems.at[buf]))

    start = functools.partial(wave_dma, go=lambda c: c.start())
    wait = functools.partial(wave_dma, go=lambda c: c.wait())

    @pl.when(b == 0)
    def _():
        g_ref[0] = 0
        s, w = jnp.int32(0), jnp.int32(0)
        for buf in range(ring - 1):
            start(s, w, buf)
            s, w = next_wave(s, w)

    tok_col = lax.broadcasted_iota(jnp.int32, (toks, 1), 0)
    tok_row = lax.broadcasted_iota(jnp.int32, (1, toks), 1)
    pos = pos_ref[b]
    base = first_page(b) * page
    q = (q_ref[0] * (1.0 / hd ** 0.5)).astype(bf)         # (heads, hd)
    nt = (((1,), (1,)), ((), ()))

    def wave_body(w, carry):
        m, l, acc = carry
        gcount = g_ref[0]
        buf = lax.rem(gcount, ring)
        wait(b, w, buf)
        # this wave's tokens lie at t0 + 0..toks-1; those past pos are
        # stale or were never fetched, those at or behind pos - window
        # are the first page's head
        t0 = base + w * toks
        hi = pos + 1 - t0
        lo = pos - window + 1 - t0 if window else -1
        ms, ls, accs = [], [], []
        for kh in range(kv_heads):
            sel = pl.ds(kh, toks, stride=kv_heads) if kv_heads > 1 \
                else pl.ds(0, toks)
            k = kbuf[buf, sel, :].astype(bf)              # (toks, hd)
            ok_col = tok_col < hi
            ok_row = tok_row < hi
            if window:
                ok_col = jnp.logical_and(ok_col, tok_col >= lo)
                ok_row = jnp.logical_and(ok_row, tok_row >= lo)
            # a weight of zero on a stale row is still 0 * NaN
            v = jnp.where(ok_col, vbuf[buf, sel, :], 0.0).astype(bf)
            s = lax.dot_general(q[kh * g:(kh + 1) * g], k, nt,
                                preferred_element_type=jnp.float32)
            s = jnp.where(ok_row, s, -1e30)               # (g, toks)
            m_new = jnp.maximum(m[kh], s.max(axis=1, keepdims=True))
            corr = jnp.exp(m[kh] - m_new)
            p = jnp.exp(s - m_new)
            ms.append(m_new)
            ls.append(l[kh] * corr + p.sum(axis=1, keepdims=True))
            accs.append(acc[kh] * corr + jnp.dot(
                p.astype(bf), v, preferred_element_type=jnp.float32))
        # into the buffer the wave before this one was read from
        ahead = (b, w)
        for _ in range(ring - 1):
            ahead = next_wave(*ahead)
        start(*ahead, lax.rem(gcount + ring - 1, ring))
        g_ref[0] = gcount + 1
        return ms, ls, accs

    init = ([jnp.full((g, 1), -1e30, jnp.float32)] * kv_heads,
            [jnp.zeros((g, 1), jnp.float32)] * kv_heads,
            [jnp.zeros((g, hd), jnp.float32)] * kv_heads)
    _m, l, acc = lax.fori_loop(0, n_waves(b), wave_body, init)
    for kh in range(kv_heads):
        o_ref[0, kh * g:(kh + 1) * g, :] = acc[kh] / l[kh]


@functools.partial(jax.jit, static_argnames=("page", "window", "interpret"))
def _window_call(q, pk, pv, bt, pos, page: int, window: int,
                 interpret: bool):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, hd = q.shape
    kv_heads = pk.shape[1] // page
    wave = pages_per_wave(page, kv_heads, hd, bt.shape[1])
    mine = pl.BlockSpec((1, heads, hd), lambda b, bt, pos: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_window_kernel, page=page, kv_heads=kv_heads,
                          wave=wave, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots,),
            in_specs=[mine,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=mine,
            scratch_shapes=[
                pltpu.VMEM((_WINDOW_RING, wave * page * kv_heads, hd),
                           pk.dtype),
                pltpu.VMEM((_WINDOW_RING, wave * page * kv_heads, hd),
                           pv.dtype),
                pltpu.SemaphoreType.DMA((_WINDOW_RING,)),
                pltpu.SMEM((1,), jnp.int32),            # waves so far
            ]),
        out_shape=jax.ShapeDtypeStruct((slots, heads, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="window_decode_attention" if window
        else "paged_decode_attention",
    )(bt, pos, q, pk, pv)


def window_decode_attention(q, pk, pv, bt, pos, page: int, window: int = 0,
                            interpret: Optional[bool] = None):
    """``q (slots, heads, hd)`` against positions ``max(pos[b] - window
    + 1, 0)..pos[b]`` of slot ``b`` (``window`` 0: ``0..pos[b]``) in the
    grouped pools ``pk`` / ``pv (num_pages, page * kv_heads, hd)`` ->
    ``(slots, heads, hd)`` float32.  Only the pages that hold those
    positions are read; operands bfloat16, softmax and accumulation
    float32."""
    return _window_call(q, pk, pv, bt, pos, page=page, window=int(window),
                        interpret=_resolve_interpret(interpret))


# -- latent attention -------------------------------------------------------
#
# A third layout and a third kernel, under its own name
# (``mla_decode_attention``; ``models/mla_mixer.py``'s step calls it).
# One row a token and layer, ``(num_pages, page, row)`` float32: the
# normed latent, then the rotated shared key part, then zeros up to a
# multiple of 128 lanes (the device's tiled layout pads a row to that
# anyway, and a DMA moves whole tiles).  Keys
# and values are the SAME rows (values the first ``kv_lora`` of each), so
# the absorbed decode form reads every live page once: ``heads``
# queries ``[q_lat (kv_lora), q_rope (rope)]`` against one shared row.

# tokens a wave of the latent kernel holds: two MXU tiles of key rows
_LATENT_WAVE_TOKENS = 256
# waves the latent kernel keeps in VMEM: the one it computes and three
# whose pages are on their way.  Measured on the v5e at 2 to 8 (PERF.md
# §6, PR 34): with two the copies of a short last wave or of a slot's
# first end before the products that hide them, and the queue runs dry;
# four reach what the copies reach alone, more add nothing
_LATENT_RING = 4


def mla_reference(q_lat, q_rope, pc, bt, pos, scale: float):
    """The plain formulation over the latent layout: gather every
    slot's whole block table into ``(slots, max_seq, kv_lora + rope)``
    and run a dense masked attention over it, float32.  What the step
    runs off the TPU, and what :func:`mla_decode_attention` is tested
    against."""
    import jax.numpy as jnp

    b = q_lat.shape[0]
    kl, rope = q_lat.shape[-1], q_rope.shape[-1]
    page = pc.shape[1]
    max_seq = bt.shape[1] * page
    rows = pc[bt].reshape(b, max_seq, pc.shape[-1])
    s_mat = (jnp.einsum("bhc,bkc->bhk", q_lat, rows[..., :kl],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhr,bkr->bhk", q_rope,
                          rows[..., kl:kl + rope],
                          preferred_element_type=jnp.float32)) * scale
    live = jnp.arange(max_seq)[None, :] <= pos[:, None]
    s_mat = jnp.where(live[:, None, :], s_mat, -1e30)
    p = jax.nn.softmax(s_mat, axis=-1)
    return jnp.einsum("bhk,bkc->bhc", p, rows[..., :kl],
                      preferred_element_type=jnp.float32)


def _latent_kernel(bt_ref, pos_ref, ql_ref, qr_ref, pc_hbm, o_ref,
                   cbuf, sems, g_ref, *, page: int, wave: int, scale: float):
    """One grid step a slot (the queries of one slot are a block).  The
    slots' waves are ONE sequence through a ring of buffers: the ring,
    its semaphores and the count of waves so far ``g`` live across grid
    steps, and every wave starts the copies of the wave ``ring - 1``
    after it, in this slot or in the next ones, so the copies never
    wait for a slot to end.  What one wave costs beside its copies
    decides the rest (a wave is ~900 instruction bundles against the
    0.98 us its 16 pages take; every page's copy is ~30 scalar
    instructions of address and bounds check):

    - no branch in a wave: a copy that has no page to fetch is a
      predicated instruction, not a block of its own, so the compiler
      schedules the whole wave as one sequence;
    - the next copies are started AFTER the wave's products, where
      their scalar work runs beside the vector work (started first,
      they stand between the wait and the first load of a row);
    - the softmax's state is the loop's carry, not a scratch buffer
      read and written back every wave.

    A wave's rows are tokens on the sublanes: ``(heads, kv_lora +
    rope)`` queries meet ``(tokens, kv_lora + rope)`` rows as the two
    matrices they are, on the MXU in bfloat16 with float32
    accumulation; the weights then meet the same rows' first
    ``kv_lora`` lanes."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    slots = pl.num_programs(0)
    heads, kl, rope = ql_ref.shape[1], ql_ref.shape[2], qr_ref.shape[2]
    ring = cbuf.shape[0]
    toks = wave * page
    bf = jnp.bfloat16

    def n_waves(s):
        return lax.div(pos_ref[s] + toks, toks)

    def next_wave(s, w):
        """The wave after wave ``w`` of slot ``s``; slot ``slots`` and
        beyond: none."""
        more = w + 1 < n_waves(jnp.minimum(s, slots - 1))
        return jnp.where(more, s, s + 1), jnp.where(more, w + 1, 0)

    def wave_dma(s, w, buf, go):
        """``go`` on the copy of every live page of slot ``s``'s wave
        ``w``; none where ``s`` is past the last slot."""
        at = jnp.minimum(s, slots - 1)
        live = jnp.where(s < slots,
                         lax.div(pos_ref[at], page) + 1 - w * wave, 0)
        for i in range(wave):

            @pl.when(i < live)
            def _():
                go(pltpu.make_async_copy(
                    pc_hbm.at[bt_ref[at, w * wave + i]],
                    cbuf.at[buf, pl.ds(i * page, page)], sems.at[buf]))

    start = functools.partial(wave_dma, go=lambda c: c.start())
    wait = functools.partial(wave_dma, go=lambda c: c.wait())

    @pl.when(b == 0)
    def _():
        g_ref[0] = 0
        s, w = jnp.int32(0), jnp.int32(0)
        for buf in range(ring - 1):
            start(s, w, buf)
            s, w = next_wave(s, w)

    tok_col = lax.broadcasted_iota(jnp.int32, (toks, 1), 0)
    tok_row = lax.broadcasted_iota(jnp.int32, (1, toks), 1)
    ql = (ql_ref[0] * scale).astype(bf)                   # (heads, kl)
    qr = (qr_ref[0] * scale).astype(bf)                   # (heads, rope)
    nt = (((1,), (1,)), ((), ()))

    def wave_body(w, carry):
        m, l, acc = carry
        g = g_ref[0]
        buf = lax.rem(g, ring)
        wait(b, w, buf)
        lim = pos_ref[b] + 1 - w * toks
        # rows past pos are stale or were never fetched: they meet a
        # zero weight, and 0 * NaN is NaN
        rows = jnp.where(tok_col < lim, cbuf[buf], 0.0).astype(bf)
        c = rows[:, :kl]                                  # (toks, kl)
        s = lax.dot_general(ql, c, nt,
                            preferred_element_type=jnp.float32) \
            + lax.dot_general(qr, rows[:, kl:kl + rope], nt,
                              preferred_element_type=jnp.float32)
        s = jnp.where(tok_row < lim, s, -1e30)            # (heads, toks)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * corr + p.sum(axis=1, keepdims=True)
        acc = acc * corr + jnp.dot(p.astype(bf), c,
                                   preferred_element_type=jnp.float32)
        # into the buffer the wave before this one was read from
        ahead = (b, w)
        for _ in range(ring - 1):
            ahead = next_wave(*ahead)
        start(*ahead, lax.rem(g + ring - 1, ring))
        g_ref[0] = g + 1
        return m_new, l, acc

    _m, l, acc = lax.fori_loop(
        0, n_waves(b), wave_body,
        (jnp.full((heads, 1), -1e30, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, kl), jnp.float32)))
    o_ref[0] = acc / l


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _latent_call(q_lat, q_rope, pc, bt, pos, scale: float,
                 interpret: bool):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, kl = q_lat.shape
    rope = q_rope.shape[-1]
    page = pc.shape[1]
    wave = max(1, min(bt.shape[1], _LATENT_WAVE_TOKENS // page))
    return pl.pallas_call(
        functools.partial(_latent_kernel, page=page, wave=wave,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots,),
            in_specs=[pl.BlockSpec((1, heads, kl),
                                   lambda b, bt, pos: (b, 0, 0)),
                      pl.BlockSpec((1, heads, rope),
                                   lambda b, bt, pos: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, heads, kl),
                                   lambda b, bt, pos: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((_LATENT_RING, wave * page, pc.shape[-1]),
                           pc.dtype),
                pltpu.SemaphoreType.DMA((_LATENT_RING,)),
                pltpu.SMEM((1,), jnp.int32),            # waves so far
            ]),
        out_shape=jax.ShapeDtypeStruct((slots, heads, kl), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode_attention",
    )(bt, pos, q_lat, q_rope, pc)


def mla_decode_attention(q_lat, q_rope, pc, bt, pos, scale: float,
                         interpret: Optional[bool] = None):
    """Absorbed latent attention of one decode step: ``q_lat (slots,
    heads, kv_lora)`` and ``q_rope (slots, heads, rope)`` against
    positions ``0..pos[b]`` of slot ``b``, whose pages ``bt[b, :pos[b]
    // page + 1]`` lie in the latent pool ``pc (num_pages, page,
    row)`` (``row`` = ``kv_lora + rope`` padded to 128) -> ``sum p c``, ``(slots, heads, kv_lora)``
    float32 (the caller's ``W_kvb^V`` makes values of it).  Scores are
    ``(q_lat . c + q_rope . k_rope) * scale``; operands bfloat16,
    accumulation and softmax float32.  Each live page is fetched
    once; entries past the last live page are never read."""
    return _latent_call(q_lat, q_rope, pc, bt, pos, scale=float(scale),
                        interpret=_resolve_interpret(interpret))


def import_pallas() -> None:
    """Import what the kernel is written in.  Over a second of pure
    Python on the chip's host, and the first thing the first traced
    step asks for: a serving process that will run the paged step
    starts this beside the loads of its first programs."""
    import jax.experimental.pallas.tpu  # noqa: F401


def attention(q, pk, pv, bt, pos, page: Optional[int] = None):
    """The step's attention: the kernel on the TPU, :func:`reference`
    on the cpu backend (where the kernel would only be interpreted), as
    ``flash_attention.attention(impl="auto")`` chooses for prefill.
    Pools of three dimensions are the grouped layout and need
    ``page``."""
    from .device_ops import _on_tpu
    if not _on_tpu():
        return reference(q, pk, pv, bt, pos, page)
    if pk.ndim == 3:
        return paged_decode_attention_grouped(q, pk, pv, bt, pos, page)
    return paged_decode_attention(q, pk, pv, bt, pos)


def window_attention(q, pk, pv, bt, pos, page: int, window: int = 0):
    """The step's attention in a window schedule, for its window layers
    and its global ones: :func:`window_decode_attention` on the TPU,
    :func:`reference` under the window's mask on the cpu backend."""
    from .device_ops import _on_tpu
    if not _on_tpu():
        return reference(q, pk, pv, bt, pos, page, window)
    return window_decode_attention(q, pk, pv, bt, pos, page, window)


def mla_attention(q_lat, q_rope, pc, bt, pos, scale: float):
    """The step's latent attention: :func:`mla_decode_attention` on the
    TPU, :func:`mla_reference` on the cpu backend."""
    from .device_ops import _on_tpu
    if not _on_tpu():
        return mla_reference(q_lat, q_rope, pc, bt, pos, scale)
    return mla_decode_attention(q_lat, q_rope, pc, bt, pos, scale)
