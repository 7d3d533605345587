"""The block ``"model": "jamba"`` names (``harness/spec.py`` loads this
file by that key; see ``models/neox.py`` for what the harness calls).

The block, from the source's ``config.json`` (``model_type: jamba``):
token embedding ``E``; layer ``i`` is an attention layer iff ``i %
attn_layer_period == attn_layer_offset`` and a Mamba layer otherwise;
every layer is ``h = x + Mixer(RMSNorm(x))``, ``y = h + MLP(RMSNorm(h))``
with ``MLP(t) = W_down(silu(W_gate t) * W_up t)`` (``num_experts`` 1: no
layer routes); a final RMSNorm; logits ``x E^T`` (tied).  No biases but
the convolution's and ``b_dt``.

- attention: ``heads`` query heads on ``num_key_value_heads`` key/value
  heads of ``hidden / heads``, NO rotary or other positional term,
  causal softmax at ``head_dim ** -0.5``, an output projection;
- Mamba (``d_inner = mamba_expand * hidden``): ``[u, z] = x W_in``;
  ``u = silu(conv1d(u))`` depthwise, causal, kernel ``mamba_d_conv``,
  with bias; ``[r, B, C] = u W_x``; ``r, B, C`` each through an RMSNorm
  with a learned gain (Jamba's addition to Mamba-1); ``dt = softplus(r
  W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(dt_t (x) A) . h_{t-1}
  + (dt_t . u_t) (x) B_t``; ``y_t = h_t C_t + D . u_t``; ``out = (y_t .
  silu(z_t)) W_out``.

The weights are in the PROGRAM'S tree (``brpc_tpu/models/
transformer_lm.py _init_block_params``): ``embed``, ``norm_f``, per
layer ``ln1 ln2 w1 w2`` (``w1`` holds gate and up side by side) and
either ``wqkv wo`` (query, key and value columns side by side) or
``in_proj conv_w conv_b x_proj dt_norm b_norm c_norm dt_proj dt_bias
a_log d out_proj`` (``a_log`` is ``(d_state, d_inner)``).
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.models.neox import _matmul

# ---------------------------------------------------------------------------
# the system under test: weights from the seed, the program's service
# ---------------------------------------------------------------------------


def is_attention(cfg: dict, i: int) -> bool:
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def n_layers(cfg: dict) -> tuple:
    """``(attention layers, Mamba layers)``."""
    n_att = sum(is_attention(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return n_att, cfg["num_hidden_layers"] - n_att


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def lm_kwargs(cfg: dict) -> dict:
    """The program's ``LMConfig`` arguments for a configuration file."""
    if cfg["num_experts"] != 1:
        raise ValueError("this block's feed-forward part is the dense MLP")
    if cfg["hidden_size"] % cfg["num_attention_heads"]:
        raise ValueError("heads must divide hidden_size")
    return dict(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        depth=cfg["num_hidden_layers"], rope=False,
        ffn="gated_silu", ffn_dim=cfg["intermediate_size"],
        tie_embed=cfg["tie_word_embeddings"], final_norm=True,
        mixers=tuple("attn" if is_attention(cfg, i) else "ssm"
                     for i in range(cfg["num_hidden_layers"])),
        ssm_expand=cfg["mamba_expand"], ssm_state=cfg["mamba_d_state"],
        ssm_conv=cfg["mamba_d_conv"], ssm_dt_rank=cfg["mamba_dt_rank"],
        max_seq=cfg["service"]["max_seq"])


def make_params(cfg: dict, seed: int):
    """Seeded weights on the device, float32 as served: matrices normal
    at ``1/sqrt(fan_in)``, norms one, ``A_log = log(1..d_state)`` in
    every channel, ``D`` one, ``b_dt`` with ``softplus(b_dt)``
    log-uniform in 1e-3..1e-1, the convolution's bias normal at 0.1
    (``assumed`` in the configuration file).  One compiled program a
    layer kind and ``depth + 1`` calls, as ``models/neox.py`` does and
    for its reason."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models.transformer_lm import LMConfig

    if not cfg["tie_word_embeddings"]:
        raise ValueError("this block's table is tied")
    # a program that does not know this block fails here, at once, and
    # not after 12 GB of weights have been made
    LMConfig(remat=False, **lm_kwargs(cfg))
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    di, n = d_inner(cfg), cfg["mamba_d_state"]
    r, kc = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    hd = head_dim(cfg)
    nq = cfg["num_attention_heads"] * hd
    nkv = cfg["num_key_value_heads"] * hd

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

    def ffn(ks):
        return {"ln1": jnp.ones((d,), jnp.float32),
                "ln2": jnp.ones((d,), jnp.float32),
                "w1": normal(ks[0], (d, 2 * f), d),
                "w2": normal(ks[1], (f, d), f)}

    @jax.jit
    def attention_layer(key):
        ks = jax.random.split(key, 4)
        return {**ffn(ks), "wqkv": normal(ks[2], (d, nq + 2 * nkv), d),
                "wo": normal(ks[3], (nq, d), nq)}

    @jax.jit
    def mamba_layer(key):
        ks = jax.random.split(key, 9)
        dt = jnp.exp(jax.random.uniform(ks[8], (di,), jnp.float32)
                     * math.log(100.0) + math.log(1e-3))
        return {**ffn(ks),
                "in_proj": normal(ks[2], (d, 2 * di), d),
                "conv_w": normal(ks[3], (kc, di), kc),
                "conv_b": jax.random.normal(ks[4], (di,), jnp.float32) * 0.1,
                "x_proj": normal(ks[5], (di, r + 2 * n), di),
                "dt_norm": jnp.ones((r,), jnp.float32),
                "b_norm": jnp.ones((n,), jnp.float32),
                "c_norm": jnp.ones((n,), jnp.float32),
                "dt_proj": normal(ks[6], (r, di), r),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "a_log": jnp.log(jnp.broadcast_to(jnp.arange(
                    1, n + 1, dtype=jnp.float32)[:, None], (n, di))),
                "d": jnp.ones((di,), jnp.float32),
                "out_proj": normal(ks[7], (di, d), di)}

    # --seed may pass 2**31: fold the high bits in instead of wrapping
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl="rbg"),
                             seed >> 31)
    ks = jax.random.split(key, 1 + cfg["num_hidden_layers"])
    params = {"embed": jax.jit(lambda k: normal(k, (v, d), d))(ks[0]),
              "norm_f": jnp.ones((d,), jnp.float32)}
    for i in range(cfg["num_hidden_layers"]):
        make = attention_layer if is_attention(cfg, i) else mamba_layer
        params[f"blk{i}"] = make(ks[1 + i])
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
# precision: one request at a time, one layer at a time, the recurrence
# a plain scan over time, the attention a full causal softmax.  No
# kernel, no cache, no pages, no slots.  It imports nothing of the
# program.  The control is the same with every weight matmul computed
# from int8 operands: ``models/neox.py``'s ``_matmul``, which says which
# scales and why.


def _norm(t, g, eps: float):
    import jax.numpy as jnp

    return t * g / jnp.sqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)


def _mlp(x, bp, eps: float, int8: bool):
    import jax
    import jax.numpy as jnp

    gate, up = jnp.split(_matmul(_norm(x, bp["ln2"], eps), bp["w1"], int8),
                         2, axis=-1)
    return x + _matmul(jax.nn.silu(gate) * up, bp["w2"], int8)


def _attention_layer(x, bp, cfg: dict, int8: bool):
    """One attention layer over one sequence ``x`` of (s, hidden)."""
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    eps, hd = cfg["rms_norm_eps"], head_dim(cfg)
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    qkv = _matmul(_norm(x, bp["ln1"], eps), bp["wqkv"], int8)
    q, k, v = jnp.split(qkv, [heads * hd, (heads + kvh) * hd], axis=-1)
    q = q.reshape(s, kvh, heads // kvh, hd)
    k, v = k.reshape(s, kvh, hd), v.reshape(s, kvh, hd)
    scores = jnp.einsum("qhgd,khd->hgqk", q, k) / math.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("hgqk,khd->qhgd", p, v).reshape(s, heads * hd)
    x = x + _matmul(att, bp["wo"], int8)
    return _mlp(x, bp, eps, int8)


def _mamba_layer(x, bp, cfg: dict, int8: bool):
    """One Mamba layer over one sequence ``x`` of (s, hidden), from
    the zero state."""
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    eps, n = cfg["rms_norm_eps"], cfg["mamba_d_state"]
    r, kc = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    u, z = jnp.split(_matmul(_norm(x, bp["ln1"], eps), bp["in_proj"], int8),
                     2, axis=-1)
    padded = jnp.pad(u, ((kc - 1, 0), (0, 0)))
    u = bp["conv_b"]
    for j in range(kc):            # tap j meets the input kc-1-j back
        u = u + bp["conv_w"][j] * padded[j:j + s]
    u = jax.nn.silu(u)
    dt, b, c = jnp.split(_matmul(u, bp["x_proj"], int8), [r, r + n], axis=-1)
    dt = jax.nn.softplus(
        _matmul(_norm(dt, bp["dt_norm"], eps), bp["dt_proj"], int8)
        + bp["dt_bias"])
    b, c = _norm(b, bp["b_norm"], eps), _norm(c, bp["c_norm"], eps)
    a = -jnp.exp(bp["a_log"])                          # (d_state, d_inner)

    def step(h, xs):
        u_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[None, :] * a) * h \
            + (dt_t * u_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    _h, y = jax.lax.scan(step, jnp.zeros_like(a), (u, dt, b, c), unroll=8)
    y = y + bp["d"] * u
    x = x + _matmul(y * jax.nn.silu(z), bp["out_proj"], int8)
    return _mlp(x, bp, eps, int8)


class Reference:
    """Holds the weights (the benchmark's own, made from the seed) and
    three compiled functions: the two kinds of layer, and the final
    norm with the unembedding of the rows that were served."""

    def __init__(self, cfg: dict, params, int8: bool = False):
        import jax

        self.cfg, self.params = cfg, params
        eps = cfg["rms_norm_eps"]
        self._attention = jax.jit(
            lambda x, bp: _attention_layer(x, bp, cfg, int8))
        self._mamba = jax.jit(lambda x, bp: _mamba_layer(x, bp, cfg, int8))
        self._unembed = jax.jit(
            lambda x, g, e: _matmul(_norm(x, g, eps), e.T, int8))

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
                layer = self._attention if is_attention(self.cfg, i) \
                    else self._mamba
                x = layer(x, self.params[f"blk{i}"])
            rows = x[len(prompt) - 1:n]
            if rows.shape[0] % 128:    # one compiled shape a sample
                rows = jnp.pad(rows, ((0, 128 - rows.shape[0] % 128), (0, 0)))
            out = self._unembed(rows, self.params["norm_f"],
                                self.params["embed"])
        return np.asarray(out)[:len(served)]


# ---------------------------------------------------------------------------
# operations and bytes the MODEL needs, from the configuration's shapes
# ---------------------------------------------------------------------------
#
# As in ``models/neox.py``: whatever implements a step, these do not
# change.  Weights are read ONCE a step or prefill call at the
# configuration's ``weight_bytes`` a parameter (2: the source's
# bfloat16; a program that stores float32 reads twice that and its
# share of the roofline says so); every live slot's recurrent state is
# read and written once a step at ``state_bytes``; keys and values are
# counted for LIVE tokens of the attention layers at
# ``kv_cache_bytes``; the table's lookup is a gather and counts no FLOP.


def mamba_mixer_params(cfg: dict) -> int:
    d, di, n = cfg["hidden_size"], d_inner(cfg), cfg["mamba_d_state"]
    r, kc = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    return (d * 2 * di + di * (r + 2 * n) + r * di + di     # in, x, dt, b_dt
            + di * d + kc * di + di                         # out, conv + bias
            + di * n + di + r + 2 * n)                      # A_log, D, norms


def mamba_mixer_matmul_params(cfg: dict) -> int:
    d, di, n = cfg["hidden_size"], d_inner(cfg), cfg["mamba_d_state"]
    r = cfg["mamba_dt_rank"]
    return d * 2 * di + di * (r + 2 * n) + r * di + di * d


def attention_mixer_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], head_dim(cfg)
    nq = cfg["num_attention_heads"] * hd
    return d * (nq + 2 * cfg["num_key_value_heads"] * hd) + nq * d


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def matmul_params(cfg: dict) -> int:
    """Parameters that every token multiplies: every layer's matrices
    and the table once, as the unembedding."""
    n_att, n_mamba = n_layers(cfg)
    return (n_att * attention_mixer_params(cfg)
            + n_mamba * mamba_mixer_matmul_params(cfg)
            + cfg["num_hidden_layers"] * mlp_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    n_att, n_mamba = n_layers(cfg)
    d = cfg["hidden_size"]
    return (n_att * attention_mixer_params(cfg)
            + n_mamba * mamba_mixer_params(cfg)
            + cfg["num_hidden_layers"] * (mlp_params(cfg) + 2 * d)
            + d * cfg["vocab_size"] + d)          # the tied table, norm_f


def scan_flops(cfg: dict) -> float:
    """The recurrence of one Mamba layer for one token: per (channel,
    state) the decay's product and exponential, the update's two
    products and sum, the read-out's product and sum; about 9."""
    return 9.0 * d_inner(cfg) * cfg["mamba_d_state"]


def state_bytes(cfg: dict) -> float:
    """One sequence's recurrent state in one Mamba layer: ``h`` and
    the convolution's last ``d_conv - 1`` inputs."""
    return float(cfg["state_bytes"] * d_inner(cfg)
                 * (cfg["mamba_d_state"] + cfg["mamba_d_conv"] - 1))


def token_flops(cfg: dict, live: int, unembed: bool = True) -> float:
    """FLOPs of one token that attends over ``live`` positions."""
    n_att, n_mamba = n_layers(cfg)
    di, nq = d_inner(cfg), cfg["num_attention_heads"] * head_dim(cfg)
    flops = 2.0 * (matmul_params(cfg)
                   - cfg["hidden_size"] * cfg["vocab_size"])
    flops += n_mamba * (scan_flops(cfg) + 2.0 * cfg["mamba_d_conv"] * di)
    flops += 4.0 * n_att * nq * live
    if unembed:
        flops += 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return flops


def weight_bytes(cfg: dict) -> float:
    return float(cfg["weight_bytes"]) * matmul_params(cfg)


def kv_bytes(cfg: dict, tokens: float) -> float:
    """Keys and values of ``tokens`` positions, the attention layers."""
    return (2.0 * n_layers(cfg)[0] * cfg["num_key_value_heads"]
            * head_dim(cfg) * cfg["kv_cache_bytes"] * tokens)


def step_work(cfg: dict, lives: list, steps: int = 1) -> tuple:
    """``(flops, bytes)`` of ``steps`` decode steps that between them
    produce one token for each entry of ``lives`` (the positions that
    token attends over, itself included): the weights once a step; a
    token's slot state read and written in every Mamba layer; the live
    keys and values of the attention layers, one position written."""
    flops = sum(token_flops(cfg, n) for n in lives)
    nbytes = (steps * weight_bytes(cfg)
              + 2.0 * n_layers(cfg)[1] * state_bytes(cfg) * len(lives)
              + kv_bytes(cfg, sum(lives) + len(lives)))
    return flops, nbytes


def fill_work(cfg: dict, start: int, n: int) -> tuple:
    """``(flops, bytes)`` of filling ``n`` positions of a context from
    ``start``: the weights once (without the unembedding), the
    ``start`` cached positions read and ``n`` written in the attention
    layers, the Mamba layers' state written once."""
    if n <= 0:
        return 0.0, 0.0
    lives = n * start + n * (n + 1) / 2.0
    flops = n * token_flops(cfg, 0, unembed=False) \
        + 4.0 * n_layers(cfg)[0] * cfg["num_attention_heads"] \
        * head_dim(cfg) * lives
    nbytes = (weight_bytes(cfg) - cfg["weight_bytes"] * cfg["hidden_size"]
              * cfg["vocab_size"] + kv_bytes(cfg, start + n)
              + n_layers(cfg)[1] * state_bytes(cfg))
    return flops, nbytes


# -- the sequence scan's own count (readers/kernel_work.py) ---------------------
#
# What the operation named ``ssm_scan`` in the device trace computes:
# the recurrence alone (the projections around it are XLA's matmuls, in
# ``fill_work``).  Per position of a Mamba layer it reads ``u`` and
# ``dt`` and writes ``y`` (d_inner each) and reads ``B`` and ``C``.
# (The step's ``ssm_step`` has no such count: XLA fetches its operands,
# the state pool among them, into on-chip memory ahead of the call, so
# the operation's own time holds its arithmetic and none of its bytes:
# 4.2 us a call against 6.4 us for the bytes alone, PERF.md section 5.)


def kernel_calls(cfg: dict, kernel: str) -> int:
    """Calls of the named kernel in one execution of its program: one
    a Mamba layer."""
    return {"ssm_scan": n_layers(cfg)[1]}[kernel]


def ssm_scan_work(cfg: dict, ctx_lens: list) -> tuple:
    """``(flops, bytes)`` of the sequence scan over the contexts
    filled, all Mamba layers: each position's inputs and output, ``A``
    read and the final ``h`` written once a context."""
    n_mamba, di = n_layers(cfg)[1], d_inner(cfg)
    tokens = float(sum(ctx_lens))
    h_bytes = float(cfg["state_bytes"] * di * cfg["mamba_d_state"])
    flops = n_mamba * scan_flops(cfg) * tokens
    nbytes = n_mamba * (tokens * 4.0 * (3 * di + 2 * cfg["mamba_d_state"])
                        + 2.0 * len(ctx_lens) * h_bytes)
    return flops, nbytes
