"""Device-side ops for the payload path.

- :func:`checksum_u32` — pallas TPU kernel (VPU wrapping-sum fold)
  computing a 32-bit checksum of a device-resident payload without
  staging it to the host; the device analogue of butil's crc32c on the
  wire path (/root/reference/src/butil/crc32c.cc — capability, not
  algorithm).
- :func:`embedding_bag` — fused lookup+mean for the parameter-server
  model family.
- :func:`tensor_bytes` / :func:`bytes_to_tensor` — tensor ↔ wire bytes
  for carrying device payloads in RPC attachments.

Kernels compile for the TPU; on the ``cpu`` backend (what the tests
force) they run in Pallas interpret mode.  Any other backend is an
error, never a silent interpret fallback.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

_LANES = 128
_SUBLANES = 8


def _on_tpu() -> bool:
    """True on the TPU backend (kernels compile), False on ``cpu``
    (kernels interpret — the test configuration).  Anything else
    raises: a device the kernels were never compiled for must not run
    them interpreted without a word."""
    import jax
    backend = jax.default_backend()
    if backend == "tpu":
        return True
    if backend == "cpu":
        return False
    raise RuntimeError(
        f"unsupported JAX backend {backend!r}: the Pallas kernels "
        "compile for 'tpu' and interpret on 'cpu' only")


@functools.lru_cache(maxsize=None)
def _checksum_fn(padded_rows: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_rows = padded_rows
    for cand in (512, 256, 64, _SUBLANES):
        if padded_rows % cand == 0:
            block_rows = cand
            break
    grid = (padded_rows // block_rows,)

    def kernel(x_ref, out_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            out_ref[0, 0] = jnp.int32(0)

        # wrapping i32 sum on the VPU (mosaic has no unsigned
        # reductions; two's-complement wrap gives the same 32 bits);
        # grid steps are sequential on TPU so accumulating into the
        # SMEM scalar is well-defined
        out_ref[0, 0] = out_ref[0, 0] + jnp.sum(x_ref[...],
                                                dtype=jnp.int32)

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))],
        # scalar accumulator lives in SMEM: VMEM cannot take scalar stores
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        interpret=interpret,
    )
    return jax.jit(call)


def checksum_u32(x) -> int:
    """32-bit xor-fold checksum of an arbitrary device array (its raw
    bytes, zero-padded to a lane multiple)."""
    import jax
    import jax.numpy as jnp

    arr = jnp.atleast_1d(jnp.asarray(x))
    if arr.dtype.itemsize != 4:
        # non-32-bit payloads are checksummed via their f32 widening —
        # integrity of the values, not of a particular bit layout
        arr = arr.astype(jnp.float32)
    raw = jnp.ravel(jax.lax.bitcast_convert_type(arr, jnp.int32))
    n = raw.size
    rows = max(_SUBLANES, -(-n // _LANES))
    rows = -(-rows // _SUBLANES) * _SUBLANES
    padded = jnp.zeros((rows * _LANES,), jnp.int32).at[:n].set(raw)
    padded = padded.reshape(rows, _LANES)
    fn = _checksum_fn(rows, interpret=not _on_tpu())
    return int(np.uint32(fn(padded)[0, 0]))


@functools.lru_cache(maxsize=None)
def _embedding_bag_fn():
    import jax
    import jax.numpy as jnp

    def bag(table, ids):
        # (batch, slots) ids → mean of rows; XLA fuses gather+reduce and
        # inserts the collective when `table` is vocab-sharded
        emb = jnp.take(table, ids, axis=0)        # (b, s, d)
        return emb.mean(axis=1)

    return jax.jit(bag)


def embedding_bag(table, ids):
    """Fused multi-slot embedding lookup + mean pool (the parameter-server
    hot op). Works on replicated or vocab-sharded tables."""
    return _embedding_bag_fn()(table, ids)


def tensor_bytes(x) -> Tuple[memoryview, str, Tuple[int, ...]]:
    """Device/host array → (raw buffer, dtype str, shape) for shipping
    as an RPC attachment (zero serializer in the path).  The buffer is
    a read-only view over the host array's storage — no tobytes copy;
    the view keeps the array alive.  CONTRACT: when ``x`` is already a
    host numpy array, the view ALIASES it (readonly blocks writes
    through the view, not through the array) — the caller must not
    mutate ``x`` until the RPC's write completes; device arrays are
    immune (``np.asarray`` lands them in a fresh host copy)."""
    host = np.ascontiguousarray(np.asarray(x))
    return memoryview(host).cast("B").toreadonly(), \
        str(host.dtype), tuple(host.shape)


def bytes_to_tensor(data, dtype: str, shape: Tuple[int, ...],
                    device=None):
    """Wire buffer (bytes or any contiguous view) → host/device tensor.
    np.frombuffer aliases the storage — the landing copy is the device
    put (or nothing, for host consumers)."""
    arr = np.frombuffer(data, dtype=np.dtype(dtype)).reshape(shape)
    if device is None:
        return arr
    import jax
    return jax.device_put(arr, device)
