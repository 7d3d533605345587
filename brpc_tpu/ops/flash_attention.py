"""Pallas flash attention — the hot-op kernel for the dense models.

No reference counterpart (the reference is an RPC framework; its hot
path is framing/IO).  This is the TPU-first answer to SURVEY §5.7's
"blockwise attention" prescription, written against the Pallas TPU
playbook (/opt/skills/guides/pallas_guide.md):

- forward: grid (b, h, q_blocks, k_blocks), innermost dimension
  "arbitrary" — VMEM scratch (running max / denominator / accumulator)
  persists across the k-block sweep, the classic online-softmax flash
  schedule with O(seq) memory per q block; also emits the per-row
  logsumexp for the backward pass;
- backward: FUSED flash kernels too — a dq kernel sweeping k blocks and
  a dk/dv kernel sweeping q blocks, both recomputing p = exp(s - lse)
  blockwise from the saved logsumexp (the standard flash backward), so
  training memory is O(seq) as well, never O(seq²);
- q·kᵀ / p·v / ds·k / dsᵀ·q on the MXU via dot_general with
  ``preferred_element_type=float32``; masking from ``broadcasted_iota``
  (TPU-safe, pitfall #4); causal blocks above the diagonal predicated
  off with ``pl.when``;
- head dim padded to the 128 lane, sequence padded to lcm(bq, bk); pad
  keys are masked in-kernel; pad q rows are gradient-safe because their
  cotangents and dd are zero (they do attend real keys forward, but the
  rows are sliced off and contribute nothing backward).  The lse/dd
  blocks use a 1-wide lane (legal: equal to the array's last dim —
  forward and both backward kernels compile and run on the v5e under
  JAX 0.9 / libtpu 0.0.34 with float32 and bf16 inputs:
  ``chip_smoke.py``'s kernel stage);
- ``interpret=True`` automatically on the cpu backend, so the same code
  paths are unit-tested on the CPU mesh; any other non-TPU backend is
  an error.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# -- forward ----------------------------------------------------------------

def _fwd_kernel(*refs, scale: float, causal: bool, bq: int, bk: int,
                seq_len: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if causal:
        # triangular causal grid: prefetched arrays carry the
        # linearized (iq, ik<=iq) pair per step
        (iq_ref, ik_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
        t = pl.program_id(2)
        iq, ik = iq_ref[t], ik_ref[t]
        is_last = ik == iq
    else:
        (q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
        iq, ik = pl.program_id(2), pl.program_id(3)
        is_last = ik == pl.num_programs(3) - 1

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -1e30)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q0 = iq * bq
    k0 = ik * bk
    # the causal grid is triangular — blocks above the diagonal are
    # statically absent; only the padded k tail needs skipping (and on
    # the triangular grid k0 <= q0 < seq_len always holds)
    live = k0 < seq_len

    @pl.when(live)
    def _step():
        # matmuls keep the INPUT dtype (bf16 stays bf16 — upcasting to
        # f32 first starves the MXU; measured ~1.7x on the whole
        # kernel) and accumulate in f32
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # (bq, bk)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < seq_len
        if causal:
            qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask = jnp.logical_and(mask, qpos >= kpos)
        s = jnp.where(mask, s, -1e30)
        m_prev = m_scr[:]                                      # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[:] = l_scr[:] * corr + p.sum(axis=-1, keepdims=True)
        m_scr[:] = m_new
        # p rides the MXU in the value dtype (the flash-standard bf16
        # cast; exact when v is f32)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # (bq, d)

    @pl.when(is_last)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)
        # logsumexp per row.  The dead-row guard only matters if a
        # future mask can fully mask a LIVE row (today even pad q rows
        # attend k block 0): exp(s - 1e30) underflows to zero then.
        lse = m_scr[:] + jnp.log(l)                            # (bq, 1)
        dead = l_scr[:] <= 0.0
        lse_ref[0, 0] = jnp.where(dead, 1e30, lse)


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """None = compile on the TPU, interpret on the cpu backend the
    tests force; any other backend raises (``device_ops._on_tpu``)."""
    from .device_ops import _on_tpu
    return (not _on_tpu()) if interpret is None else interpret


def _make_prep(s_pad: int, d_pad: int, s: int, d: int):
    """(b, s, h, d) -> (b, h, s_pad, d_pad), zero-padded."""
    import jax.numpy as jnp

    def prep(x):
        x = jnp.moveaxis(x, 2, 1)
        return jnp.pad(x, ((0, 0), (0, 0), (0, s_pad - s),
                           (0, d_pad - d)))

    return prep


# index maps shared by every kernel: block row iq / ik is the third
# grid axis for forward+dq, swapped for dkdv
_IXQ = lambda ib, ih, iq, ik: (ib, ih, iq, 0)       # noqa: E731
_IXK = lambda ib, ih, iq, ik: (ib, ih, ik, 0)       # noqa: E731
_IXQ2 = lambda ib, ih, ik, iq: (ib, ih, iq, 0)      # noqa: E731
_IXK2 = lambda ib, ih, ik, iq: (ib, ih, ik, 0)      # noqa: E731


# -- causal triangular grid -------------------------------------------------
#
# A rectangular (iq, ik) grid wastes HALF the machine on causal
# attention: blocks strictly above the diagonal are masked to nothing,
# but the grid still streams their K/V blocks and burns their MXU
# issue slots (measured: causal was SLOWER than non-causal at 16k).
# Instead the causal kernels linearize only the valid lower-triangle
# pairs into one grid axis; the (iq, ik) pair per step rides in as
# SCALAR-PREFETCHED index arrays so the pipeline can still compute the
# next step's DMAs ahead of time (computing them with arithmetic inside
# the index maps measured 2.2x slower per step — the prefetcher
# couldn't run ahead).  q-major order keeps each q block's k sweep
# contiguous, so the VMEM scratch carries across it exactly as in the
# rectangular schedule.

def _tri_arrays(nq: int):
    """q-major lower-triangle enumeration: (iq_arr, ik_arr), len T."""
    import numpy as np
    idx = np.arange(nq)
    iq = np.repeat(idx, idx + 1)
    ik = np.concatenate([np.arange(i + 1) for i in idx]) if nq else idx
    return iq.astype(np.int32), ik.astype(np.int32)


def _tri_arrays_rev(nq: int):
    """k-major enumeration for the dk/dv sweep: for each ik the valid
    iq >= ik ascend contiguously."""
    import numpy as np
    idx = np.arange(nq)
    ik = np.repeat(idx, nq - idx)
    iq = np.concatenate([np.arange(i, nq) for i in idx]) if nq else idx
    return iq.astype(np.int32), ik.astype(np.int32)


# index maps for the prefetched triangular grid: block row from the
# prefetched arrays, everything else straight through
_TRIQ = lambda ib, ih, t, iqr, ikr: (ib, ih, iqr[t], 0)     # noqa: E731
_TRIK = lambda ib, ih, t, iqr, ikr: (ib, ih, ikr[t], 0)     # noqa: E731


def _block_geometry(s: int, d: int, block_q, block_k,
                    causal: bool = False):
    d_pad = _ceil_to(max(d, 1), 128)
    if block_q is None or block_k is None:
        # round 5, earlier set-up (not re-timed on the current chip):
        # 1024/1024 was fastest everywhere the kernel is actually
        # dispatched (the auto impl uses dense below 2k) — bigger
        # blocks amortize the per-block scratch round trips.  fp32
        # scores at 1024^2 fit the 16MB scoped VMEM for float32 inputs
        # too (compiled and run by chip_smoke.py); 2048^2 does not
        auto = 1024 if s >= 2048 else 256
        block_q = auto if block_q is None else block_q
        block_k = auto if block_k is None else block_k
    bq = min(block_q, _ceil_to(s, 8))
    bk = min(block_k, _ceil_to(s, 8))
    if causal:
        # the triangular grid linearizes (iq, ik<=iq) pairs — that
        # needs a SQUARE block lattice (forward and backward recompute
        # this geometry independently; keep it a pure function)
        bq = bk = min(bq, bk)
    # pad to a common multiple: padding only to max(bq, bk) would
    # floor-truncate the other grid dimension and silently drop keys
    s_pad = _ceil_to(s, math.lcm(bq, bk))
    return d_pad, bq, bk, s_pad


def _pallas_forward(q, k, v, causal: bool, block_q: Optional[int],
                    block_k: Optional[int],
                    interpret: Optional[bool]) -> Tuple:
    """Returns (out (b,s,h,d), lse (b,h,s_pad,1) fp32 — padded layout,
    consumed only by _pallas_backward which recomputes the identical
    block geometry)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    interpret = _resolve_interpret(interpret)
    d_pad, bq, bk, s_pad = _block_geometry(s, d, block_q, block_k,
                                           causal)
    nq, nk = s_pad // bq, s_pad // bk
    kernel = functools.partial(
        _fwd_kernel, scale=1.0 / (d ** 0.5), causal=causal,
        bq=bq, bk=bk, seq_len=s)
    prep = _make_prep(s_pad, d_pad, s, d)
    qp, kp, vp = prep(q), prep(k), prep(v)
    out_shape = [
        jax.ShapeDtypeStruct((b, h, s_pad, d_pad), q.dtype),
        jax.ShapeDtypeStruct((b, h, s_pad, 1), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((bq, 1), jnp.float32),       # running max
        pltpu.VMEM((bq, 1), jnp.float32),       # running denom
        pltpu.VMEM((bq, d_pad), jnp.float32),   # accumulator
    ]
    if causal:
        iq_arr, ik_arr = _tri_arrays(nq)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, int(iq_arr.size)),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d_pad), _TRIQ),
                pl.BlockSpec((1, 1, bk, d_pad), _TRIK),
                pl.BlockSpec((1, 1, bk, d_pad), _TRIK),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bq, d_pad), _TRIQ),
                pl.BlockSpec((1, 1, bq, 1), _TRIQ),
            ],
            scratch_shapes=scratch,
        )
        out, lse = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
        )(jnp.asarray(iq_arr), jnp.asarray(ik_arr), qp, kp, vp)
        return jnp.moveaxis(out[:, :, :s, :d], 1, 2), lse
    qblk, kblk, rowblk = _IXQ, _IXK, _IXQ
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d_pad), qblk,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d_pad), kblk,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d_pad), kblk,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d_pad), qblk,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq, 1), rowblk,
                         memory_space=pltpu.VMEM),
        ],
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return jnp.moveaxis(out[:, :, :s, :d], 1, 2), lse


# -- backward ---------------------------------------------------------------

def _masked_p(q, k, lse, scale, causal, q0, k0, bq, bk, seq_len):
    """Recompute p = exp(s - lse) for one block (shared by dq/dkdv)."""
    import jax
    import jax.numpy as jnp

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < seq_len
    if causal:
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        mask = jnp.logical_and(mask, qpos >= kpos)
    s = jnp.where(mask, s, -1e30)
    return jnp.exp(s - lse)                       # (bq, bk)


def _dq_kernel(*refs, scale: float, causal: bool, bq: int, bk: int,
               seq_len: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if causal:
        (iq_ref, ik_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
         dq_ref, acc_scr) = refs
        t = pl.program_id(2)
        iq, ik = iq_ref[t], ik_ref[t]
        is_last = ik == iq
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
         dq_ref, acc_scr) = refs
        iq, ik = pl.program_id(2), pl.program_id(3)
        is_last = ik == pl.num_programs(3) - 1

    @pl.when(ik == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q0 = iq * bq
    k0 = ik * bk
    live = k0 < seq_len          # triangular grid when causal

    @pl.when(live)
    def _step():
        # native-dtype MXU inputs, f32 accumulation (see _fwd_kernel)
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                       # (bq, 1)
        dd = dd_ref[0, 0]                         # D = rowsum(do * o)
        p = _masked_p(q, k, lse, scale, causal, q0, k0, bq, bk, seq_len)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dd)                        # (bq, bk) f32
        acc_scr[:] = acc_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(is_last)
    def _finalize():
        dq_ref[0, 0] = acc_scr[:].astype(dq_ref.dtype)


def _dkdv_kernel(*refs, scale: float, causal: bool,
                 bq: int, bk: int, seq_len: int, tri_nq: int = 0):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if causal:
        # k-major triangle: for each ik, sweep the valid iq >= ik
        (iq_ref, ik_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        t = pl.program_id(2)
        iq, ikb = iq_ref[t], ik_ref[t]
        is_first = iq == ikb
        is_last = iq == tri_nq - 1
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        ikb = pl.program_id(2)
        iq = pl.program_id(3)              # q innermost: sweep per k blk
        is_first = iq == 0
        is_last = iq == pl.num_programs(3) - 1

    @pl.when(is_first)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    k0 = ikb * bk
    q0 = iq * bq
    live = k0 < seq_len          # triangular grid when causal

    @pl.when(live)
    def _step():
        # native-dtype MXU inputs, f32 accumulation (see _fwd_kernel)
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                       # (bq, 1)
        dd = dd_ref[0, 0]
        p = _masked_p(q, k, lse, scale, causal, q0, k0, bq, bk, seq_len)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)   # (bk, d)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dd)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(is_last)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _pallas_backward(q, k, v, o, lse, g, causal: bool,
                     block_q: Optional[int], block_k: Optional[int],
                     interpret: Optional[bool]):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    interpret = _resolve_interpret(interpret)
    d_pad, bq, bk, s_pad = _block_geometry(s, d, block_q, block_k,
                                           causal)
    nq, nk = s_pad // bq, s_pad // bk
    tri_T = nq * (nq + 1) // 2 if causal else 0
    scale = 1.0 / (d ** 0.5)
    prep = _make_prep(s_pad, d_pad, s, d)
    qp, kp, vp, op, dop = prep(q), prep(k), prep(v), prep(o), prep(g)
    # lse arrives already in the padded layout: _block_geometry is a
    # pure function of (s, d, block_q, block_k), so forward and
    # backward always agree on s_pad
    assert lse.shape == (b, h, s_pad, 1), (lse.shape, s_pad)
    lsep = lse
    dd = jnp.sum(dop.astype(jnp.float32) * op.astype(jnp.float32),
                 axis=-1, keepdims=True)           # (b, h, s_pad, 1)

    dq_kernel = functools.partial(_dq_kernel, scale=scale, causal=causal,
                                  bq=bq, bk=bk, seq_len=s)
    dq_shape = jax.ShapeDtypeStruct((b, h, s_pad, d_pad), q.dtype)
    dq_scratch = [pltpu.VMEM((bq, d_pad), jnp.float32)]
    if causal:
        iq_arr, ik_arr = _tri_arrays(nq)
        # dq: sweep k blocks per q block over the lower triangle
        dq = pl.pallas_call(
            dq_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(b, h, int(iq_arr.size)),
                in_specs=[
                    pl.BlockSpec((1, 1, bq, d_pad), _TRIQ),
                    pl.BlockSpec((1, 1, bk, d_pad), _TRIK),
                    pl.BlockSpec((1, 1, bk, d_pad), _TRIK),
                    pl.BlockSpec((1, 1, bq, d_pad), _TRIQ),
                    pl.BlockSpec((1, 1, bq, 1), _TRIQ),
                    pl.BlockSpec((1, 1, bq, 1), _TRIQ),
                ],
                out_specs=pl.BlockSpec((1, 1, bq, d_pad), _TRIQ),
                scratch_shapes=dq_scratch,
            ),
            out_shape=dq_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
        )(jnp.asarray(iq_arr), jnp.asarray(ik_arr),
          qp, kp, vp, dop, lsep, dd)
    else:
        qblk, kblk, qrow = _IXQ, _IXK, _IXQ
        dq = pl.pallas_call(
            dq_kernel,
            grid=(b, h, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d_pad), qblk,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, bk, d_pad), kblk,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, bk, d_pad), kblk,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, bq, d_pad), qblk,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, bq, 1), qrow,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, bq, 1), qrow,
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, d_pad), qblk,
                                   memory_space=pltpu.VMEM),
            out_shape=dq_shape,
            scratch_shapes=dq_scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
        )(qp, kp, vp, dop, lsep, dd)

    # dk/dv: sweep q blocks per k block (q is the innermost dimension)
    kv_kernel = functools.partial(_dkdv_kernel, scale=scale,
                                  causal=causal, bq=bq, bk=bk, seq_len=s,
                                  tri_nq=nq)
    kv_shape = [
        jax.ShapeDtypeStruct((b, h, s_pad, d_pad), k.dtype),
        jax.ShapeDtypeStruct((b, h, s_pad, d_pad), v.dtype),
    ]
    kv_scratch = [pltpu.VMEM((bk, d_pad), jnp.float32),
                  pltpu.VMEM((bk, d_pad), jnp.float32)]
    if causal:
        iq_arr2, ik_arr2 = _tri_arrays_rev(nq)
        dk, dv = pl.pallas_call(
            kv_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(b, h, int(iq_arr2.size)),
                in_specs=[
                    pl.BlockSpec((1, 1, bq, d_pad), _TRIQ),
                    pl.BlockSpec((1, 1, bk, d_pad), _TRIK),
                    pl.BlockSpec((1, 1, bk, d_pad), _TRIK),
                    pl.BlockSpec((1, 1, bq, d_pad), _TRIQ),
                    pl.BlockSpec((1, 1, bq, 1), _TRIQ),
                    pl.BlockSpec((1, 1, bq, 1), _TRIQ),
                ],
                out_specs=[
                    pl.BlockSpec((1, 1, bk, d_pad), _TRIK),
                    pl.BlockSpec((1, 1, bk, d_pad), _TRIK),
                ],
                scratch_shapes=kv_scratch,
            ),
            out_shape=kv_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
        )(jnp.asarray(iq_arr2), jnp.asarray(ik_arr2),
          qp, kp, vp, dop, lsep, dd)
    else:
        kblk2, qblk2, qrow2 = _IXK2, _IXQ2, _IXQ2
        dk, dv = pl.pallas_call(
            kv_kernel,
            grid=(b, h, nk, nq),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d_pad), qblk2,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, bk, d_pad), kblk2,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, bk, d_pad), kblk2,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, bq, d_pad), qblk2,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, bq, 1), qrow2,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, bq, 1), qrow2,
                             memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bk, d_pad), kblk2,
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, bk, d_pad), kblk2,
                             memory_space=pltpu.VMEM),
            ],
            out_shape=kv_shape,
            scratch_shapes=kv_scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
        )(qp, kp, vp, dop, lsep, dd)

    unprep = lambda x: jnp.moveaxis(x[:, :, :s, :d], 1, 2)  # noqa: E731
    return unprep(dq), unprep(dk), unprep(dv)


# -- public api -------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Flash attention: (b, s, h, d) q/k/v -> (b, s, h, d).

    Forward AND backward run fused Pallas kernels (interpret mode
    off-TPU) — O(seq) memory in both directions.  ``block_q``/
    ``block_k`` default to None = auto (``_block_geometry``: 256 for
    short context, 1024 from 2k tokens); pass explicit sizes to
    override."""
    out, _ = _pallas_forward(q, k, v, causal, block_q, block_k, interpret)
    return out


# Crossover from round 5's earlier set-up, not re-measured on the
# current chip (bench.py device-compute section): at 2k tokens one
# XLA-fused einsum→softmax→einsum chain was on par with or ahead of the
# kernel's block pipeline (0.8-1.3x), while from ~4k the O(s) memory +
# streaming K/V blocks won (2-2.6x at 16k).  Dense also costs O(s^2)
# activation memory, so the crossover stays low enough that the scores
# tensor is cheap.
DENSE_FLASH_CROSSOVER = 2048


def dense_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None):
    """XLA-fused dense attention — materializes the (s, s) scores and
    lets the compiler tile the matmul chain onto the MXU.  The fastest
    impl below the crossover; the correctness oracle everywhere.  The
    softmax is over ``q k^T * scale`` (None: ``d^-0.5``)."""
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    s = s / (d ** 0.5) if scale is None else s * scale
    if causal:
        n = q.shape[1]
        pos = jnp.arange(n)
        mask = (pos[:, None] >= pos[None, :])[None, None]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype)


def attention(q, k, v, causal: bool = False, impl: str = "auto",
              block_q: Optional[int] = None,
              block_k: Optional[int] = None,
              interpret: Optional[bool] = None,
              scale: Optional[float] = None):
    """Sequence-adaptive attention dispatch.

    ``impl="auto"`` picks dense (XLA-fused, O(s²) memory) below
    :data:`DENSE_FLASH_CROSSOVER` tokens and the Pallas flash kernel
    (O(s) memory) at or above it — each impl where it measures faster.
    Off-TPU, auto always picks dense: the kernel would run in Pallas
    interpret mode there, which is never the faster choice.
    ``impl="dense"``/``"flash"`` force.  Shapes are static under jit,
    so the choice is made at trace time: no runtime branching.
    ``scale`` is the softmax's (None: ``d^-0.5``, the kernel's own: any
    other reaches it through the queries)."""
    if impl == "auto":
        from .device_ops import _on_tpu
        impl = "flash" if (q.shape[1] >= DENSE_FLASH_CROSSOVER
                           and _on_tpu()) else "dense"
    if impl == "dense":
        return dense_attention(q, k, v, causal, scale)
    if impl == "flash":
        if scale is not None:
            q = q * (scale * q.shape[-1] ** 0.5)
        return flash_attention(q, k, v, causal, block_q=block_q,
                               block_k=block_k, interpret=interpret)
    raise ValueError(f"unknown attention impl {impl!r}")


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _pallas_forward(q, k, v, causal, block_q, block_k,
                               interpret)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    return _pallas_backward(q, k, v, o, lse, g, causal, block_q, block_k,
                            interpret)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
