"""The latent-attention mixer of a layer schedule (``LMConfig.mixers``
``"mla"``): multi-head latent attention as DeepSeek-V3's modelling code
has it, in the two forms serving needs.

For one position ``t`` of width ``dim`` (already normed), ``H`` heads::

    q      = RMSNorm(t W_qa) W_qb           dim -> q_lora -> H x (nope + rope)
             (or t W_q, dim -> H x (nope + rope), where q_lora_rank is None)
    [c, kr] = t W_kva                        dim -> kv_lora + rope
    c      = RMSNorm(c)
    [k_nope_h, v_h] = c W_kvb               kv_lora -> H x (nope + v)
    q_rope_h, kr rotated at the position     kr is ONE row, shared by heads
             (not rotated where LMConfig.ropes says so: no positional term)
    score_h = (q_nope_h . k_nope_h + q_rope_h . kr) * scale
    out    = [sum softmax(score_h) v_h]_h W_o        H x v -> dim

What a sequence keeps for a token is ``c`` after its norm and ``kr``
after its rotation: one row of ``kv_lora + rope`` values a layer, the
same row for keys and for values (``LMConfig.latent_row``).

- :func:`prefill`: a whole bucket in the EXPANDED form (keys and values
  made from the latent rows, a dense causal softmax), returning the
  latent rows;
- :func:`step`: one position for each slot in the ABSORBED form:
  ``q'_h = [q_nope_h W_kvb,h^K, q_rope_h]`` against the cached rows,
  ``o_h = (sum p c) W_kvb,h^V``: every live page of the latent pool is
  read once (``ops.paged_attention.mla_attention``).  The same numbers
  as the expanded form up to rounding.

Both read a PACKED layer (:func:`pack`): ``W_qb`` and ``W_kvb`` do not
lie as the checkpoint has them but as the operands of the products
that use them, each by head as ``(H, out, in)`` with the contraction
last::

    wq_h  (H, nope + rope, q_lora)  [q_nope_h, q_rope_h] = q wq_h_h^T
          (a direct ``wq`` packs into the same form, ``q_lora`` = dim)
    wk_b  (H, kv_lora, nope)        step: q'_h = q_nope_h wk_b_h^T
                                    prefill: k_nope_h = c wk_b_h
    wv_b  (H, v, kv_lora)           step: out_h = o_h wv_b_h^T
                                    prefill: v_h = c wv_b_h^T

Why packed, and once: the 64-row product with ``W_qb`` (which XLA
reads head-major with the contraction last) and the per-head
contractions of a reshaped and sliced ``W_kvb`` made XLA re-lay both
weights in EVERY step: two synchronous ``copy`` operations a layer,
37.7 + 16.8 MB at the benchmark's widths (PERF.md section 6, PR 36).
A serving program's weights are jit ARGUMENTS
(``transformer_lm.jit_with_params``), so a layout the compiler wants
is made again each call, where a constant's would be folded at compile
time (with the weights embedded in every program: PERF.md section 6,
PR 21).  So a service packs when it takes its weights (``lm_service``:
:func:`pack_params`) and every program of it reads the one packed tree
as it lies; the prefill's two expansions contract the same forms (a
transposed read inside a 50-ms program).  A program handed a layer as
:func:`init_layer` makes it packs it in its own trace: the same
numbers, the copies back.

Rotation: the rotary dimensions pair as halves (``i`` with ``i +
rope/2``, :func:`transformer_lm._rope`'s), at YaRN-scaled frequencies
where the configuration has ``rope_yarn``.  Weight matmuls take bf16
operands and accumulate in float32; norms, softmax and the residual are
float32.
"""

from __future__ import annotations

import functools
import math


def init_layer(key, cfg) -> dict:
    """Seeded weights of one mixer: matrices normal at
    ``1/sqrt(fan_in)``, norms one."""
    import jax
    import jax.numpy as jnp

    d, h = cfg.dim, cfg.heads
    ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, v = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

    query = {"wq": normal(ks[1], (d, h * (nope + rope)), d)} if ql is None \
        else {"wq_a": normal(ks[0], (d, ql), d),
              "q_norm": jnp.ones((ql,), jnp.float32),
              "wq_b": normal(ks[1], (ql, h * (nope + rope)), ql)}
    return {**query,
            "wkv_a": normal(ks[2], (d, kl + rope), d),
            "kv_norm": jnp.ones((kl,), jnp.float32),
            "wkv_b": normal(ks[3], (kl, h * (nope + v)), kl),
            "wo": normal(ks[4], (h * v, d), h * v)}


def inv_freq(cfg):
    """The rotary frequencies of the ``rope / 2`` pairs (numpy):
    ``theta^(-2i/rope)``, under YaRN blended with the same divided by
    ``factor`` by a linear ramp between the dimensions that turn
    ``beta_fast`` and ``beta_slow`` times over the original context."""
    import numpy as np

    dim = cfg.qk_rope_dim
    f = cfg.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    y = cfg.rope_yarn
    if not y:
        return f.astype(np.float32)

    def correction_dim(rotations):
        return dim * math.log(y["original_max"] / (rotations * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001                   # the source's guard
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (f / y["factor"] * ramp + f * (1.0 - ramp)).astype(np.float32)


def _mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1.0 else 0.1 * m * math.log(scale) + 1.0


def softmax_scale(cfg) -> float:
    """``(nope + rope)^-0.5``, under YaRN times ``(0.1 mscale_all_dim
    ln(factor) + 1)^2``."""
    s = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    y = cfg.rope_yarn
    if y and y.get("mscale_all_dim"):
        s *= _mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return s


def rotation(cfg, pos):
    """``(sin, cos)`` of the rotary part at ``pos`` (anything
    ``transformer_lm._rope_at`` takes), ``(..., 1, rope / 2)``; under
    YaRN times ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``."""
    import jax.numpy as jnp

    y = cfg.rope_yarn
    m = 1.0 if not y else _mscale(y["factor"], y.get("mscale", 1.0)) \
        / _mscale(y["factor"], y.get("mscale_all_dim", 0.0) or 0.0)
    ang = jnp.asarray(pos).astype(jnp.float32)[..., None, None] \
        * jnp.asarray(inv_freq(cfg))
    return jnp.sin(ang) * m, jnp.cos(ang) * m


_PACKED = ("wq_h", "wk_b", "wv_b")


def _relayout(wq_b, wkv_b, heads: int, nope: int, v: int):
    """``wq_b (ql, H x (nope + rope))`` and ``wkv_b (kl, H x (nope +
    v))`` as ``_PACKED``: each product's operand by head as ``(H, out,
    in)``, the contraction last.  The same values in the same dtype."""
    wkv = wkv_b.reshape(wkv_b.shape[0], heads, nope + v)
    return {"wq_h": wq_b.reshape(wq_b.shape[0], heads, -1).transpose(1, 2, 0),
            "wk_b": wkv[..., :nope].transpose(1, 0, 2),
            "wv_b": wkv[..., nope:].transpose(1, 2, 0)}


@functools.lru_cache(maxsize=None)
def _relayout_program(heads: int, nope: int, v: int):
    """:func:`_relayout` jitted: one program for every layer of a
    schedule (its widths are the cache's key)."""
    import jax
    return jax.jit(functools.partial(_relayout, heads=heads, nope=nope,
                                     v=v))


def pack(cfg, bp):
    """One latent layer's leaves with ``wq_b`` and ``wkv_b`` replaced
    (``wq`` where the query is direct) by the three forms its products
    read (module docstring), in a new
    dict: the caller's is never written.  A layer that is packed
    already, or whose projections are no plain arrays
    (``ops.quant.QuantTensor``), is returned as it is."""
    from ..ops.quant import QuantTensor

    wq = "wq_b" if "wq_b" in bp else "wq"     # a direct query: one matrix
    if wq not in bp or isinstance(bp[wq], QuantTensor) \
            or isinstance(bp["wkv_b"], QuantTensor):
        return bp
    out = {k: w for k, w in bp.items() if k not in (wq, "wkv_b")}
    out.update(_relayout_program(cfg.heads, cfg.qk_nope_dim, cfg.v_head_dim)(
        bp[wq], bp["wkv_b"]))
    return out


def pack_params(cfg, params):
    """``params`` with every latent layer packed (:func:`pack`): a new
    tree that shares every other leaf, or ``params`` itself where
    nothing is left to pack.  A service packs ONCE, when it takes its
    weights, and hands every program the one tree."""
    blocks = {f"blk{i}": pack(cfg, params[f"blk{i}"])
              for i in cfg.mla_layers()}
    if all(blocks[k] is params[k] for k in blocks):
        return params
    return {**params, **blocks}


def packed_bytes(cfg, params) -> int:
    """The bytes of the packed forms in ``params``: what the device
    holds twice while a caller keeps the leaves it handed over."""
    return sum(int(w.size) * w.dtype.itemsize
               for i in cfg.mla_layers()
               for k, w in params[f"blk{i}"].items() if k in _PACKED)


def _project(cfg, bp, t, rot):
    """``t (..., dim)`` -> ``q_nope (..., H, nope)``, ``q_rope (..., H,
    rope)`` rotated, and the latent row as it is cached: ``c`` normed,
    ``kr`` rotated, zeros up to ``LMConfig.latent_row_padded``; with
    ``rot`` None nothing is rotated.  ``bp``
    is a PACKED layer: the query product reads ``wq_h`` as it lies,
    head-major, and what is sliced is its RESULT."""
    import jax.numpy as jnp

    from ..ops.quant import mxu_matmul as _mm
    from ..ops.quant import mxu_operand as bf
    from .transformer_lm import UnsupportedBlock, _rmsnorm, _rope

    if "wq_h" not in bp:
        raise UnsupportedBlock(
            "latent attention multiplies plain arrays: 'wq_b' / 'wkv_b' "
            "held as QuantTensor are not packed, and not served")
    q_in = _rmsnorm(_mm(t, bp["wq_a"]), bp["q_norm"], cfg.norm_eps) \
        if "wq_a" in bp else t
    q = jnp.einsum("...q,hdq->...hd", bf(q_in), bf(bp["wq_h"]),
                   preferred_element_type=jnp.float32)
    nope = cfg.qk_nope_dim
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    if rot is not None:
        q_rope = _rope(q_rope, *rot)
    c, kr = jnp.split(_mm(t, bp["wkv_a"]), [cfg.kv_lora_rank], axis=-1)
    c = _rmsnorm(c, bp["kv_norm"], cfg.norm_eps)
    if rot is not None:
        kr = _rope(kr[..., None, :], *rot)[..., 0, :]
    pad = jnp.zeros(c.shape[:-1] + (cfg.latent_row_padded()
                                    - cfg.latent_row(),), jnp.float32)
    return q_nope, q_rope, jnp.concatenate([c, kr, pad], axis=-1)


def prefill(cfg, bp, x, rot):
    """``x (1, s, dim)``, normed -> ``(out (1, s, dim), latent (1,
    max_seq, latent_row_padded))``: the expanded form over the bucket,
    the rows a token caches written from position 0 (a causal softmax
    forgives the bucket's padding, whose rows the step overwrites
    before its mask admits them)."""
    import jax
    import jax.numpy as jnp

    from .transformer_lm import UnsupportedBlock

    if cfg.use_flash or cfg.attn_impl == "flash":
        raise UnsupportedBlock(
            "the flash kernel declines latent attention (heads of "
            f"{cfg.qk_nope_dim + cfg.qk_rope_dim} / {cfg.v_head_dim}): "
            "an 'mla' prefill runs the dense path")
    from ..ops import quant

    b, s, _ = x.shape
    bf = quant.mxu_operand
    bp = pack(cfg, bp)
    q_nope, q_rope, row = _project(cfg, bp, x, rot)
    c = row[..., :cfg.kv_lora_rank]
    kr = row[..., cfg.kv_lora_rank:cfg.latent_row()]
    k_nope = jnp.einsum("bsc,hcn->bshn", bf(c), bf(bp["wk_b"]),
                        preferred_element_type=jnp.float32)
    v = jnp.einsum("bsc,hvc->bshv", bf(c), bf(bp["wv_b"]),
                   preferred_element_type=jnp.float32)
    scores = (jnp.einsum("bqhn,bkhn->bhqk", bf(q_nope),
                         bf(k_nope),
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,bkr->bhqk", bf(q_rope),
                           bf(kr),
                           preferred_element_type=jnp.float32)) \
        * softmax_scale(cfg)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None, None], scores, -1e30),
                       axis=-1)
    att = jnp.einsum("bhqk,bkhv->bqhv", bf(p), bf(v),
                     preferred_element_type=jnp.float32)
    out = quant.mxu_matmul(
        att.reshape(b, s, cfg.heads * cfg.v_head_dim), bp["wo"])
    latent = jax.lax.dynamic_update_slice(
        jnp.zeros((b, cfg.max_seq, row.shape[-1]), jnp.float32), row,
        (0, 0, 0))
    return out, latent


def step(cfg, bp, x, pc, bt, pos, att_pos, rot, page: int):
    """``x (slots, dim)``, normed; ``pc`` the layer's latent pool
    ``(num_pages, page, latent_row_padded)`` -> ``(out (slots, dim),
    pc)``: each slot's row written at ``pos`` through the block table,
    then the absorbed attention over positions ``0..att_pos``."""
    import jax.numpy as jnp

    from ..ops import paged_attention, quant

    b = x.shape[0]
    bf = quant.mxu_operand
    bp = pack(cfg, bp)
    q_nope, q_rope, row = _project(cfg, bp, x[:, None], rot)
    pc = pc.at[bt[jnp.arange(b), pos // page], pos % page].set(row[:, 0])
    q_lat = jnp.einsum("bhn,hcn->bhc", bf(q_nope[:, 0]), bf(bp["wk_b"]),
                       preferred_element_type=jnp.float32)
    o_lat = paged_attention.mla_attention(
        q_lat, q_rope[:, 0], pc, bt, att_pos, softmax_scale(cfg))
    att = jnp.einsum("bhc,hvc->bhv", bf(o_lat), bf(bp["wv_b"]),
                     preferred_element_type=jnp.float32)
    return quant.mxu_matmul(
        att.reshape(b, cfg.heads * cfg.v_head_dim), bp["wo"]), pc
