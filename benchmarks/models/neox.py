"""The block ``"model": "neox"`` names: everything the harness needs that
depends on WHICH block a configuration file describes.  A configuration
names its module (``configs/<config>.json``: ``"model": "<name>"`` ->
``models/<name>.py``); ``harness/spec.py`` loads it by that key, so a
configuration with another block brings a file like this one and edits
none.  What the harness calls:

- ``make_params(cfg, seed)``: the weights, on the device, from the seed,
  in the program's tree and in the type they are served in;
- ``make_service(cfg, params)``: the program's service object for this
  configuration, as a deployment would construct it;
- ``Reference(cfg, params, int8=False)`` with ``served_logits(prompt,
  served)``: the plain float32 forward of the block, importing nothing
  of the program (``int8=True``: the control);
- ``step_work(cfg, lives, steps)`` and ``fill_work(cfg, start, n)``:
  operations and bytes the MODEL needs, from the configuration's shapes.

The block, as the configuration files state it (the PROGRAM'S block at
GPT-NeoX / Pythia widths, not NeoX itself: ``differs_from_source`` in
each file): token embedding; per layer RMSNorm -> fused q,k,v projection
-> full rotary on q and k -> causal multi-head attention (heads x
head_dim = hidden) -> output projection -> residual; RMSNorm -> up
projection -> GELU (tanh form) -> down projection -> residual; no final
norm; an untied unembedding.  No biases.
"""

from __future__ import annotations

import math

import numpy as np

# ---------------------------------------------------------------------------
# the system under test: weights from the seed, the program's service
# ---------------------------------------------------------------------------

def lm_kwargs(cfg: dict) -> dict:
    """The program's ``LMConfig`` arguments for a configuration file."""
    d = cfg["hidden_size"]
    if cfg["num_attention_heads"] * cfg["head_dim"] != d:
        raise ValueError("heads x head_dim must equal hidden_size")
    if cfg["intermediate_size"] % d:
        raise ValueError("intermediate_size must be a multiple of hidden")
    return dict(vocab=cfg["vocab_size"], dim=d,
                heads=cfg["num_attention_heads"],
                depth=cfg["num_hidden_layers"],
                mlp_mult=cfg["intermediate_size"] // d,
                max_seq=cfg["max_position_embeddings"])


def make_params(cfg: dict, seed: int):
    """Seeded normal weights in the program's tree (``embed``,
    ``unembed``, ``blk<i>`` of ``wqkv wo w1 w2 ln1 ln2``) at the scales
    its own initialiser uses, made on the device, float32 as served.
    Three compiled programs (the two tables, one whole layer) and
    ``depth + 2`` calls: one program for the whole tree holds every
    leaf's random bits at once and set the process's memory peak."""
    import jax
    import jax.numpy as jnp

    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    v, depth = cfg["vocab_size"], cfg["num_hidden_layers"]
    scale = 1.0 / math.sqrt(d)

    def normal(k, shape, s):
        return jax.random.normal(k, shape, jnp.float32) * s

    @jax.jit
    def layer(key):
        bk = jax.random.split(key, 4)
        return {"wqkv": normal(bk[0], (d, 3 * d), scale),
                "wo": normal(bk[1], (d, d), scale),
                "w1": normal(bk[2], (d, f), scale),
                "w2": normal(bk[3], (f, d), scale * d / f),
                "ln1": jnp.ones((d,), jnp.float32),
                "ln2": jnp.ones((d,), jnp.float32)}

    # --seed may pass 2**31: fold the high bits in instead of wrapping
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl="rbg"),
                             seed >> 31)
    ks = jax.random.split(key, 2 + depth)
    params = {"embed": jax.jit(lambda k: normal(k, (v, d), scale))(ks[0]),
              "unembed": jax.jit(lambda k: normal(k, (d, v), scale))(ks[1])}
    for i in range(depth):
        params[f"blk{i}"] = layer(ks[2 + i])
    return params


def make_service(cfg: dict, params):
    """The program's paged ``LMService`` for this configuration, with
    the configuration file's ``service`` settings."""
    from brpc_tpu.models.lm_service import LMService
    from brpc_tpu.models.transformer_lm import LMConfig

    svc = cfg["service"]
    return LMService(
        cfg=LMConfig(remat=False, **lm_kwargs(cfg)), params=params,
        paged=True, page=svc["page"], decode_slots=svc["decode_slots"],
        kv_pages=svc["kv_pages"], max_new_cap=svc["max_new_cap"])


# ---------------------------------------------------------------------------
# the plain reference, and the control
# ---------------------------------------------------------------------------
#
# Straight ``jax.numpy`` in float32 with every matmul at ``highest``
# precision, no kernel, no cache, no batching: one request at a time,
# one layer at a time.  It imports nothing of the program.
#
# The control is this same reference with every weight matmul computed
# from int8 operands (symmetric; the weight with one scale per output
# channel, as the program's own ``quantize=True`` path rounds it, the
# activation with one scale per row): the step below the configuration's
# bf16 matmuls, and the one a v5e's int8 MXU peak tempts.

# gelu(x) = 0.5 x (1 + tanh(sqrt(2/pi) (x + 0.044715 x^3)))
_GELU_C = math.sqrt(2.0 / math.pi)


def _round_int8(w):
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0,
                        1e-8)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _round_rows_int8(x):
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0,
                        1e-8)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _matmul(x, w, int8: bool):
    """``x @ w``; in int8 both operands are rounded first: the weight
    with one scale per output channel, the activation with one per row."""
    if int8:
        return _round_rows_int8(x) @ _round_int8(w)
    return x @ w


def _layer(x, bp, heads: int, int8: bool):
    """One block over one sequence ``x`` of (s, d)."""
    import jax
    import jax.numpy as jnp

    def mm(t, name):
        return _matmul(t, bp[name], int8)

    def norm(t, g):
        return t * g / jnp.sqrt(jnp.mean(t * t, axis=-1, keepdims=True)
                                + 1e-6)

    s, d = x.shape
    hd = d // heads
    half = hd // 2
    q, k, v = jnp.split(mm(norm(x, bp["ln1"]), "wqkv"), 3, axis=-1)
    pos = jnp.arange(s, dtype=jnp.float32)[:, None, None]
    freq = jnp.exp(-math.log(10000.0)
                   * jnp.arange(half, dtype=jnp.float32) / half)
    sin, cos = jnp.sin(pos * freq), jnp.cos(pos * freq)

    def rope(t):
        t = t.reshape(s, heads, hd)
        a, b = t[..., :half], t[..., half:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    q, k, v = rope(q), rope(k), v.reshape(s, heads, hd)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    att = jnp.einsum("hqk,khd->qhd", p, v).reshape(s, d)
    x = x + mm(att, "wo")
    up = mm(norm(x, bp["ln2"]), "w1")
    act = 0.5 * up * (1.0 + jnp.tanh(_GELU_C * (up + 0.044715 * up ** 3)))
    return x + mm(act, "w2")


class Reference:
    """Holds the weights (the benchmark's own, made from the seed) and
    two compiled functions: one layer, and the unembedding of the rows
    that were served."""

    def __init__(self, cfg: dict, params, int8: bool = False):
        import jax

        self.cfg, self.params, self.int8 = cfg, params, int8
        heads = cfg["num_attention_heads"]
        self._layer = jax.jit(lambda x, bp: _layer(x, bp, heads, int8))
        self._unembed = jax.jit(lambda x, w: _matmul(x, w, int8))

    def served_logits(self, prompt, served) -> np.ndarray:
        """Logits (len(served), vocab) at the positions whose next
        token was served: the last prompt position and every served
        token but the last."""
        import jax
        import jax.numpy as jnp

        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        n = len(seq)
        pad = 256                      # few shapes: a causal pass is
        while pad < n:                 # unchanged by what follows it
            pad <<= 1
        ids = np.zeros((pad,), np.int32)
        ids[:n] = seq
        with jax.default_matmul_precision("highest"):
            x = self.params["embed"][jnp.asarray(ids)]
            for i in range(self.cfg["num_hidden_layers"]):
                x = self._layer(x, self.params[f"blk{i}"])
            rows = x[len(prompt) - 1:n]
            if rows.shape[0] % 128:    # one compiled shape a sample
                rows = jnp.pad(rows, ((0, 128 - rows.shape[0] % 128), (0, 0)))
            out = self._unembed(rows, self.params["unembed"])
        return np.asarray(out)[:len(served)]


# ---------------------------------------------------------------------------
# operations and bytes the MODEL needs, from the configuration's shapes
# ---------------------------------------------------------------------------
#
# Whatever implements the step, these do not change, so a later kernel
# can neither push a share over 100% nor leave it without a base.
#
# - weights are read ONCE per step or prefill call at 2 bytes a
#   parameter (the matmuls are bf16; a stored-bf16 copy is the same
#   arithmetic);
# - keys and values are counted for LIVE tokens only, at the cache's
#   stated dtype (``kv_cache_bytes`` of the configuration file);
# - attention FLOPs are over live lengths; the unembed is included; the
#   embedding lookup is a gather and counts no FLOP.

def layer_matmul_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return 3 * d * d + d * d + 2 * d * f       # wqkv, wo, w1, w2


def matmul_params(cfg: dict) -> int:
    """Parameters that every token multiplies: the blocks and the
    unembed (not the embedding table, which is looked up)."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return (matmul_params(cfg) + cfg["vocab_size"] * d
            + cfg["num_hidden_layers"] * 2 * d)          # + the norms


def token_flops(cfg: dict, live: int, unembed: bool = True) -> float:
    """FLOPs of one token that attends over ``live`` positions (itself
    included): 2 per multiply-add in every matmul, and q.k plus p.v
    over the live positions."""
    d = cfg["hidden_size"]
    flops = 2.0 * cfg["num_hidden_layers"] * layer_matmul_params(cfg)
    flops += 4.0 * cfg["num_hidden_layers"] * d * live
    if unembed:
        flops += 2.0 * d * cfg["vocab_size"]
    return flops


def span_flops(cfg: dict, start: int, n: int) -> float:
    """FLOPs of prefilling positions ``start .. start + n - 1`` (each
    attends causally over everything before it and itself); the one
    unembed of a prefill's last position rides the first decode step
    and is counted there."""
    if n <= 0:
        return 0.0
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    lives = n * start + n * (n + 1) / 2.0
    return (2.0 * layers * layer_matmul_params(cfg) * n
            + 4.0 * layers * d * lives)


def weight_bytes(cfg: dict) -> float:
    """Bytes of weights one step (or one prefill call) has to read."""
    return 2.0 * matmul_params(cfg)


def kv_bytes(cfg: dict, tokens: float) -> float:
    """Bytes of keys and values of ``tokens`` positions, all layers."""
    return (2.0 * cfg["num_hidden_layers"] * cfg["hidden_size"]
            * cfg["kv_cache_bytes"] * tokens)


def step_work(cfg: dict, lives: list, steps: int = 1) -> tuple:
    """``(flops, bytes)`` of ``steps`` decode steps that between them
    produce one token for each entry of ``lives`` (the positions that
    token attends over, itself included).  A step reads the weights
    once, the live keys and values of every active slot, and writes one
    position a slot."""
    flops = sum(token_flops(cfg, n) for n in lives)
    nbytes = steps * weight_bytes(cfg) \
        + kv_bytes(cfg, sum(lives) + len(lives))
    return flops, nbytes


def fill_work(cfg: dict, start: int, n: int) -> tuple:
    """``(flops, bytes)`` of filling ``n`` positions of a context from
    ``start``: the weights once (without the unembedding), the ``start``
    cached positions read, ``n`` written."""
    flops = span_flops(cfg, start, n)
    nbytes = (weight_bytes(cfg) - 2.0 * cfg["hidden_size"]
              * cfg["vocab_size"] + kv_bytes(cfg, start + n))
    return flops, nbytes
