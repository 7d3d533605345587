"""Span attention — a span of a prompt's rows against the pages written
so far: for key/value heads shared by groups of query heads and layers
whose reach is a window, and for whole heads without one.

A window schedule (``LMConfig.windows``) and a looped one
(``LMConfig.passes``) fill a prompt in spans of ``fill_span`` rows
(``transformer_lm.make_paged_span_fill``): a span's keys and values are
scattered into its session's pages, then its queries attend over what
lies in them.  A whole-head pool ``(num_pages, page, heads, hd)`` is
the grouped layout with a group of one (the same bytes in the same
order), and is taken as it is.  The pages are gathered once
into ``(kv_heads, keys, hd)`` (a window layer: only those the window
reaches) and a flash kernel (``span_flash_attention`` in a device
trace) walks them in blocks with an online softmax: ``(kv_heads, query
blocks, key blocks)``, the key blocks innermost; a key block that lies
wholly ahead of the query block's rows, or wholly behind their window,
is neither fetched anew nor computed.  Operands bfloat16, scores,
softmax and accumulation float32.

Off the TPU the plain formulation (:func:`reference`) runs instead, in
float32; the kernel is interpreted in ``tests/test_window_experts.py``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .flash_attention import _resolve_interpret

_BLOCK_Q = 64          # query TOKENS a block (times the group: rows)
_BLOCK_K = 512         # keys a block


def _gather(pool, page_ids, page: int):
    """``pool (num_pages, page * kv_heads, hd)`` (a row a (token,
    key/value head) pair; or ``(num_pages, page, heads, hd)``) at
    ``page_ids (P,)`` -> ``(kv_heads, P * page, hd)``."""
    kvh = _kv_heads(pool, page)
    x = pool[page_ids].reshape(page_ids.shape[0] * page, kvh,
                               pool.shape[-1])
    return x.transpose(1, 0, 2)


def _kv_heads(pool, page: int) -> int:
    return pool.shape[2] if pool.ndim == 4 else pool.shape[1] // page


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


def _key_blocks(pos_ref, iq, bq: int, bk: int, nk: int, window: int):
    """First and last key block the query block ``iq`` reaches."""
    import jax.numpy as jnp

    a = pos_ref[0] + iq * bq - pos_ref[1]      # its first row, in keys
    hi = jnp.clip((a + bq - 1) // bk, 0, nk - 1)
    lo = jnp.minimum(jnp.maximum(a - window + 1, 0) // bk, hi) \
        if window else 0
    return lo, hi


def _kernel(pos_ref, qt_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, bq: int, bk: int, nk: int, window: int):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    iq, ik = pl.program_id(1), pl.program_id(2)
    lo, hi = _key_blocks(pos_ref, iq, bq, bk, nk, window)

    @pl.when(ik == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, -1e30)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(jnp.logical_and(ik >= lo, ik <= hi))
    def _():
        k = k_ref[0]                                      # (bk, hd)
        s = lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        qpos = pos_ref[0] + qt_ref[:]                     # (rows, 1)
        kpos = pos_ref[1] + ik * bk + lax.broadcasted_iota(
            jnp.int32, (1, bk), 1)
        s = jnp.where(_allowed(qpos, kpos, window), s, -1e30)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[:] = l_scr[:] * corr + p.sum(axis=1, keepdims=True)
        m_scr[:] = m_new
        acc_scr[:] = acc_scr[:] * corr + jnp.dot(
            p.astype(k.dtype), v_ref[0],
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _():
        o_ref[0] = acc_scr[:] / l_scr[:]


@functools.partial(jax.jit, static_argnames=(
    "group", "window", "bq", "bk", "interpret"))
def _call(q, k, v, pos, group: int, window: int, bq: int, bk: int,
          interpret: bool):
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
        functools.partial(_kernel, bq=bq, bk=bk, nk=nk, window=window),
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
                         window: int = 0, block_q: int = _BLOCK_Q,
                         block_k: int = _BLOCK_K,
                         interpret: Optional[bool] = None):
    """:func:`reference` as a flash kernel: operands bfloat16, the
    softmax float32.  ``W`` must be a multiple of the query block (or
    shorter than one); the gathered keys are padded to whole key blocks
    with rows no query is allowed."""
    import jax.numpy as jnp

    w, heads, hd = q.shape
    kvh = _kv_heads(pk, page)
    g = heads // kvh
    bq = min(block_q, w)
    assert w % bq == 0
    keys = page_ids.shape[0] * page
    bk = min(block_k, keys)
    pad = -keys % bk
    if pad:
        # (the garbage page: its rows lie ahead of every query)
        page_ids = jnp.concatenate(
            [page_ids, jnp.zeros((pad // page,), page_ids.dtype)])
    bf = jnp.bfloat16
    k = _gather(pk, page_ids, page).astype(bf)
    v = _gather(pv, page_ids, page).astype(bf)
    qg = (q * (1.0 / hd ** 0.5)).astype(bf).reshape(w, kvh, g, hd) \
        .transpose(1, 0, 2, 3).reshape(kvh, w * g, hd)
    pos = jnp.stack([jnp.asarray(q0, jnp.int32),
                     jnp.asarray(k0, jnp.int32)])
    out = _call(qg, k, v, pos, group=g, window=int(window), bq=bq, bk=bk,
                interpret=_resolve_interpret(interpret))
    return out.reshape(kvh, w, g, hd).transpose(1, 0, 2, 3) \
        .reshape(w, heads, hd)


def attention(q, pk, pv, page_ids, q0, k0, page: int, window: int = 0):
    """A span's attention: the kernel on the TPU, :func:`reference` on
    the cpu backend."""
    from .device_ops import _on_tpu
    if not _on_tpu():
        return reference(q, pk, pv, page_ids, q0, k0, page, window)
    return span_flash_attention(q, pk, pv, page_ids, q0, k0, page, window)
