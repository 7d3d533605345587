"""Weight-only int8 quantization for the serving path.

Single-token decode is HBM-bandwidth-bound: every step streams every
weight matrix through the MXU once, so halving the bytes ≈ halves the
step time.  Symmetric per-output-channel int8 (scale = amax/127 over
the contraction axis) keeps matmul outputs within ~0.5% of bf16 for
transformer-scale weights; the int8→bf16 convert fuses into the
matmul's RHS load under XLA, so no dequantized copy ever materializes.

TPU-first notes: int8 values are exactly representable in bf16, so the
compute path stays on the MXU's bf16 pipeline (no XLA int8-matmul
special-casing needed); scales apply per OUTPUT channel, a cheap fused
multiply on the (..., n) result.

Reference scope note: the reference (an RPC framework) has no model
serving layer; this module serves the framework's own LM family
(models/transformer_lm.py), the capability its PS/LM examples build on.
"""

from __future__ import annotations

from typing import Any, NamedTuple


class QuantTensor(NamedTuple):
    """int8 weights + per-output-channel scales (a pytree node)."""
    q: Any          # int8, same shape as the original weight
    s: Any          # float32, shape = (out_channels,)

    @property
    def shape(self):
        return self.q.shape

    @property
    def nbytes(self) -> int:
        return int(self.q.size) + int(self.s.size) * 4


def quantize_int8(w, contract_axis: int = 0) -> QuantTensor:
    """Symmetric per-channel quantization of a 2D weight.

    ``contract_axis`` is the axis the matmul reduces over (0 for the
    ``x @ w`` layout used throughout the LM); scales are computed per
    channel of the OTHER axis so each output feature keeps its own
    dynamic range.  Idempotent: an already-quantized tensor passes
    through unchanged."""
    import jax.numpy as jnp

    if isinstance(w, QuantTensor):
        return w
    w = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=contract_axis, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return QuantTensor(q=q, s=scale.squeeze(contract_axis))


def qmatmul(x, w):
    """``x @ w`` where ``w`` is a QuantTensor (or a plain array, for
    call sites that handle both).  x is taken to bf16 (the MXU input
    dtype); the result is f32 with scales applied per output channel."""
    import jax.numpy as jnp

    if not isinstance(w, QuantTensor):
        return (x.astype(jnp.bfloat16)
                @ jnp.asarray(w).astype(jnp.bfloat16)).astype(jnp.float32)
    y = (x.astype(jnp.bfloat16)
         @ w.q.astype(jnp.bfloat16)).astype(jnp.float32)
    return y * w.s


def mxu_operand(x):
    """A matmul operand as the MXU takes it: bfloat16 (a weight stored
    in bfloat16 as it lies).  The latent-attention and expert layers
    cast through here and accumulate in float32; a test that pins
    float32 arithmetic patches this one name, as it does
    :func:`qmatmul`."""
    import jax.numpy as jnp
    return x.astype(jnp.bfloat16)


def mxu_matmul(x, w):
    """``x @ w`` on :func:`mxu_operand` operands, accumulated in
    float32."""
    import jax.numpy as jnp
    return jnp.dot(mxu_operand(x), mxu_operand(w),
                   preferred_element_type=jnp.float32)


def dequantize(w):
    """Materialize the f32 weight (tests / fallback paths)."""
    import jax.numpy as jnp
    if not isinstance(w, QuantTensor):
        return w
    return w.q.astype(jnp.float32) * w.s


_LM_QUANT_KEYS = ("wqkv", "wo", "w1", "w2")


def quantize_lm_params(params: dict) -> dict:
    """Quantize a TransformerLM parameter tree for serving: the block
    matmul weights and the unembedding go int8; embeddings (gather, not
    matmul), layernorm gains, and MoE trees stay as-is.  Returns a new
    tree; the original is untouched.

    Both layer layouts are served: unrolled ``blk{i}`` trees and
    stacked ``scan_layers`` trees (weights (depth, in, out) quantize
    with the contraction on axis 1, giving per-(layer, out-channel)
    scales — ``lax.scan`` then slices each layer's QuantTensor off the
    leading axis)."""
    out: dict = {}
    for key, val in params.items():
        if key == "unembed":
            out[key] = quantize_int8(val)
        elif key == "blocks" and isinstance(val, dict):
            out[key] = {
                bk: (quantize_int8(bv, contract_axis=1)
                     if bk in _LM_QUANT_KEYS else bv)
                for bk, bv in val.items()}
        elif key.startswith("blk") and isinstance(val, dict):
            blk = {}
            for bk, bv in val.items():
                blk[bk] = quantize_int8(bv) if bk in _LM_QUANT_KEYS \
                    else bv
            out[key] = blk
        else:
            out[key] = val
    return out


def quantized_nbytes(params: dict) -> int:
    """Total parameter bytes (QuantTensor-aware) — the serving-memory
    story a /status page or capacity planner reads."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, QuantTensor)):
        if isinstance(leaf, QuantTensor):
            total += leaf.nbytes
        else:
            total += int(leaf.size) * leaf.dtype.itemsize
    return total
