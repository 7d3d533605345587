"""The block ``"model": "kimi_linear"`` names (``harness/spec.py`` loads
this file by that key; see ``models/neox.py`` for what the harness
calls).

The block, from the source's ``config.json`` (``model_type:
kimi_linear``).  ``x`` a token's residual row, ``RMS(t; g) = t g /
sqrt(mean(t^2) + rms_norm_eps)``, every norm with a learned gain:

- layer ``l`` (1-based): ``h = x + Mixer_l(RMS(x))``, ``y = h +
  F_l(RMS(h))``; ``Mixer_l`` is KDA for ``l`` in
  ``linear_attn_config.kda_layers`` and latent attention for ``l`` in
  ``linear_attn_config.full_attn_layers`` (the lists are the source's,
  whole; the first ``num_hidden_layers`` layers are read); ``F_l`` for
  ``l <= first_k_dense_replace`` the dense gated MLP ``W_d(silu(W_g t)
  * W_u t)`` at ``intermediate_size``, after that the expert layer
  (``moe_layer_freq`` 1); a final ``RMS``; logits ``x W_head``
  (untied);
- KDA (``linear_attn_config``: ``num_heads`` H, ``head_dim`` d,
  ``short_conv_kernel_size`` 4), position ``t``, normed input ``x_t``:
  ``q', k', v' = x_t W_q, x_t W_k, x_t W_v`` (hidden -> H x d each, no
  bias); ``q, k, v = silu(conv(q')), silu(conv(k')), silu(conv(v'))``,
  each a depthwise causal convolution over the last 4 positions with
  its own taps, no bias; ``q_h <- q_h / sqrt(|q_h|^2 + 1e-6) * d^-0.5``,
  ``k_h <- k_h / sqrt(|k_h|^2 + 1e-6)``; ``g_t = -exp(A_log_h) *
  softplus((x_t W_fa) W_fb + dt_bias)`` (hidden -> d -> H x d: a
  log-decay for each KEY CHANNEL), ``a_t = exp(g_t)``; ``b_t =
  sigmoid(x_t W_b)`` (hidden -> H); with ``S_h`` a ``d x d`` float32
  state (key x value) from zero: ``S' = Diag(a_t,h) S_{t-1,h}``,
  ``S_t,h = S' + b_t,h k_h (v_h - S'^T k_h)^T``, ``o_h = S_t,h^T q_h``;
  out ``W_o [RMS_d(o_h; g_o) * sigmoid((x_t W_ga) W_gb + b_g)]_h`` (the
  head norm over ``d`` with one gain shared by the heads);
- latent attention (``q_lora_rank`` null, ``mla_use_nope`` true): ``q =
  x W_q`` (hidden -> heads x (``qk_nope_head_dim`` +
  ``qk_rope_head_dim``)), ``[c, kr] = x W_kva`` (hidden ->
  ``kv_lora_rank`` + rope), ``c <- RMS(c)``, ``[k_nope_h, v_h] = c
  W_kvb``; NOTHING is rotated (no positional term: the KDA layers carry
  order); ``score_h = (q_nope_h . k_nope_h + q_rope_h . kr) * (nope +
  rope)^-0.5``, causal softmax, ``o_h = sum p v_h``, out ``W_o [o_1 ..
  o_H]``;
- experts: ``sc = sigmoid(W_r t)`` in float32 over ALL
  ``num_experts_published``; the top ``num_experts_per_token`` of ``sc
  + b`` (``b`` the correction bias; ``num_expert_group`` 1, so the
  grouped top-k is the plain one); weights ``w = sc[chosen]`` WITHOUT
  ``b``, ``w <- w / (sum w + 1e-20)`` (``moe_renormalize``), ``w <-
  routed_scaling_factor w``; ``F(t) = sum_k w_k E_k(t) + E_shared(t)``,
  every ``E`` the gated MLP at ``moe_intermediate_size``.  THIS chip
  holds experts ``0 .. num_experts - 1``: it routes over all of them,
  normalises over all the chosen, and adds ``w_k E_k(t)`` only for
  chosen ``k`` it holds.  That partial sum goes on to the next layer,
  in the program and in the reference alike.

Readings taken into the source (the configuration file lists them under
``assumed``): the gates' inner width is ``head_dim`` and ``W_gb`` has a
bias where ``W_fb`` has ``dt_bias``; the taps have no bias and SiLU
follows them; the L2 norm's 1e-6 lies under the root; the state and the
convolutions' tails are float32.

The weights are in the PROGRAM'S tree (``brpc_tpu/models/
transformer_lm.py _init_block_params``): ``embed``, ``unembed``,
``norm_f``, per layer ``ln1 ln2``, a mixer, either ``wqkv conv_w wf_a
wf_b dt_bias a_log wb wg_a wg_b bg o_norm wo`` (KDA: ``wqkv`` and
``conv_w`` hold q, k and v side by side) or ``wq wkv_a kv_norm wkv_b
wo`` (latent), and a feed-forward, either ``w1 w2`` (gate and up side
by side in ``w1``) or ``moe``: ``router bias w1 w2 ws1 ws2``.  Matrices
are bfloat16, as the source stores them and as the program serves
them; norms, taps, ``a_log``, the biases and ``b`` float32.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.models.neox import _matmul

L2_EPS = 1e-6

# ---------------------------------------------------------------------------
# the system under test: weights from the seed, the program's service
# ---------------------------------------------------------------------------


def mixers(cfg: dict) -> tuple:
    """``"kda"`` or ``"mla"`` for each of the layers held here."""
    lin = cfg["linear_attn_config"]
    out = []
    for layer in range(1, cfg["num_hidden_layers"] + 1):
        if layer in lin["kda_layers"]:
            out.append("kda")
        elif layer in lin["full_attn_layers"]:
            out.append("mla")
        else:
            raise ValueError(f"layer {layer} is in neither list")
    return tuple(out)


def n_mixers(cfg: dict) -> tuple:
    """``(KDA layers, latent layers)``."""
    m = mixers(cfg)
    return m.count("kda"), m.count("mla")


def n_layers(cfg: dict) -> tuple:
    """``(dense layers, expert layers)``."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def lm_kwargs(cfg: dict) -> dict:
    """The program's ``LMConfig`` arguments for a configuration file."""
    if cfg["num_expert_group"] != 1 or cfg["topk_group"] != 1 \
            or cfg["moe_router_activation_func"] != "sigmoid" \
            or not cfg["moe_renormalize"] or cfg["moe_layer_freq"] != 1:
        raise ValueError("this block routes by sigmoid scores over one "
                         "group, renormalised, every layer past the dense")
    if cfg["q_lora_rank"] is not None or not cfg["mla_use_nope"] \
            or cfg["rope_scaling"] is not None:
        raise ValueError("this block's latent layers project the query "
                         "directly and rotate nothing")
    lin = cfg["linear_attn_config"]
    depth = cfg["num_hidden_layers"]
    dense, experts = n_layers(cfg)
    return dict(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], depth=depth,
        max_seq=cfg["service"]["max_seq"], mixers=mixers(cfg),
        ropes=(False,) * depth,
        ffn="gated_silu", ffn_dim=cfg["intermediate_size"],
        tie_embed=cfg["tie_word_embeddings"], final_norm=True,
        norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"],
        q_lora_rank=None, kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        ffns=("dense",) * dense + ("experts",) * experts,
        expert_dim=cfg["moe_intermediate_size"],
        experts_routed=cfg["num_experts_published"],
        experts_held=(0, cfg["num_experts"]),
        experts_top_k=cfg["num_experts_per_token"],
        route_scale=cfg["routed_scaling_factor"],
        shared_experts=cfg["num_shared_experts"])


def make_params(cfg: dict, seed: int):
    """Seeded weights on the device as served: matrices normal at
    ``1/sqrt(fan_in)`` rounded to bfloat16 ONCE; norms one, ``b_g``
    zero, the correction bias normal at ``correction_bias_std``,
    ``a_log`` the log of a rate uniform in 1..16 a head, ``dt_bias``
    with ``softplus(dt_bias)`` log-uniform in 1e-3..1e-1, all float32
    (``assumed`` in the configuration file).  One compiled program a
    layer kind."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models.transformer_lm import LMConfig

    if cfg["tie_word_embeddings"]:
        raise ValueError("this block's table is untied")
    # a program that does not know this block fails here, at once, and
    # not after 7 GB of weights have been made
    LMConfig(remat=False, **lm_kwargs(cfg))
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    lin = cfg["linear_attn_config"]
    kh, kd, kc = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    kl, nope, rope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], \
        cfg["qk_rope_head_dim"]
    vd, f, e = cfg["v_head_dim"], cfg["intermediate_size"], \
        cfg["moe_intermediate_size"]
    routed, held = cfg["num_experts_published"], cfg["num_experts"]
    sh = cfg["num_shared_experts"]

    def f32(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

    def normal(k, shape, fan_in):
        return f32(k, shape, fan_in).astype(jnp.bfloat16)

    def norms():
        return {"ln1": jnp.ones((d,), jnp.float32),
                "ln2": jnp.ones((d,), jnp.float32)}

    def kda(ks):
        dt = jnp.exp(jax.random.uniform(ks[8], (kh * kd,), jnp.float32)
                     * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return {**norms(),
                "wqkv": normal(ks[0], (d, 3 * kh * kd), d),
                "conv_w": f32(ks[1], (kc, 3 * kh * kd), kc),
                "wf_a": normal(ks[2], (d, kd), d),
                "wf_b": normal(ks[3], (kd, kh * kd), kd),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "a_log": jnp.log(jax.random.uniform(
                    ks[9], (kh,), jnp.float32, 1.0, 16.0)),
                "wb": normal(ks[4], (d, kh), d),
                "wg_a": normal(ks[5], (d, kd), d),
                "wg_b": normal(ks[6], (kd, kh * kd), kd),
                "bg": jnp.zeros((kh * kd,), jnp.float32),
                "o_norm": jnp.ones((kd,), jnp.float32),
                "wo": normal(ks[7], (kh * kd, d), kh * kd)}

    def mla(ks):
        return {**norms(),
                "wq": normal(ks[0], (d, h * (nope + rope)), d),
                "wkv_a": normal(ks[1], (d, kl + rope), d),
                "kv_norm": jnp.ones((kl,), jnp.float32),
                "wkv_b": normal(ks[2], (kl, h * (nope + vd)), kl),
                "wo": normal(ks[3], (h * vd, d), h * vd)}

    def dense(ks):
        return {"w1": normal(ks[0], (d, 2 * f), d),
                "w2": normal(ks[1], (f, d), f)}

    def experts(ks):
        return {"moe": {
            "router": normal(ks[0], (d, routed), d),
            "bias": jax.random.normal(ks[1], (routed,), jnp.float32)
            * cfg["correction_bias_std"],
            "w1": normal(ks[2], (held, d, 2 * e), d),
            "w2": normal(ks[3], (held, e, d), e),
            "ws1": normal(ks[4], (d, 2 * sh * e), d),
            "ws2": normal(ks[5], (sh * e, d), sh * e)}}

    def layer(mixer, ffn):
        def make(key):
            ks = jax.random.split(key, 16)
            return {**mixer(ks[:10]), **ffn(ks[10:])}
        return jax.jit(make)

    makers = {(m, f_): layer(kda if m == "kda" else mla,
                             dense if f_ == "dense" else experts)
              for m in ("kda", "mla") for f_ in ("dense", "experts")}
    # --seed may pass 2**31: fold the high bits in instead of wrapping
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl="rbg"),
                             seed >> 31)
    ks = jax.random.split(key, 2 + cfg["num_hidden_layers"])
    params = {"embed": jax.jit(lambda k: normal(k, (v, d), d))(ks[0]),
              "unembed": jax.jit(lambda k: normal(k, (d, v), d))(ks[1]),
              "norm_f": jnp.ones((d,), jnp.float32)}
    n_dense = n_layers(cfg)[0]
    for i, m in enumerate(mixers(cfg)):
        params[f"blk{i}"] = makers[
            m, "dense" if i < n_dense else "experts"](ks[2 + i])
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
# precision: one request at a time, one layer at a time; the KDA
# recurrence a sequential scan over the positions from the zero state,
# the convolution a sum of four shifted copies; latent attention in the
# EXPANDED form as a full causal softmax over the whole context; the
# expert layer a plain loop over the held experts with a mask.  No
# kernel, no cache, no pages, no slots, no state pool, no chunks, no
# absorbed form.  It imports nothing of the program and is given the
# same share of the experts.  The control is the same with every weight
# matmul computed from int8 operands (``models/neox.py``'s ``_matmul``);
# the router, whose choice the model's mathematics keeps in float32,
# and the recurrence stay float32 in both.


def _norm(t, g, eps: float):
    import jax.numpy as jnp

    return t * g / jnp.sqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)


def _f32(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _gated(t, w1, w2, int8: bool):
    import jax
    import jax.numpy as jnp

    gate, up = jnp.split(_matmul(t, w1, int8), 2, axis=-1)
    return _matmul(jax.nn.silu(gate) * up, w2, int8)


def route(t, mp, cfg: dict):
    """``(ids (s, k), w (s, k))`` over ALL published experts."""
    import jax
    import jax.numpy as jnp

    sc = jax.nn.sigmoid(t @ mp["router"])
    ids = jnp.argsort(-(sc + mp["bias"]), axis=-1,
                      stable=True)[:, :cfg["num_experts_per_token"]]
    w = jnp.take_along_axis(sc, ids, axis=-1)
    w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return ids, w * cfg["routed_scaling_factor"]


def _experts(t, mp, cfg: dict, int8: bool, held=None):
    """The expert layer's output for normed rows ``t``: the held
    experts' weighted part and the shared expert.  ``held`` (a range
    of expert ids whose weights are ``mp["w1"]``'s rows in order)
    defaults to ``0 .. num_experts - 1``."""
    import jax.numpy as jnp

    ids, w = route(t, mp, cfg)
    lo, hi = held if held is not None else (0, cfg["num_experts"])
    out = _gated(t, mp["ws1"], mp["ws2"], int8)
    for e in range(lo, hi):
        w_e = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
        out = out + w_e[:, None] * _gated(t, mp["w1"][e - lo],
                                          mp["w2"][e - lo], int8)
    return out


def _kda(t, bp, cfg: dict, int8: bool):
    """KDA over one sequence ``t`` of (s, hidden), normed, from the zero
    state: a scan over the positions."""
    import jax
    import jax.numpy as jnp

    lin = cfg["linear_attn_config"]
    h, d, kc = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    s = t.shape[0]
    pre = jnp.pad(_matmul(t, bp["wqkv"], int8), ((kc - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(bp["conv_w"][j] * pre[j:j + s]
                          for j in range(kc)))
    q, k, v = (x.reshape(s, h, d) for x in jnp.split(qkv, 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) \
        * d ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    f = _matmul(_matmul(t, bp["wf_a"], int8), bp["wf_b"], int8) \
        + bp["dt_bias"]
    a = jnp.exp(-jnp.exp(bp["a_log"])[:, None]
                * jax.nn.softplus(f).reshape(s, h, d))
    b = jax.nn.sigmoid(_matmul(t, bp["wb"], int8))            # (s, h)

    def step(S, xs):
        # (products and sums written out: float32 as they stand, where
        # an einsum would go through the matrix unit's passes)
        q_t, k_t, v_t, a_t, b_t = xs
        S = a_t[:, :, None] * S                               # (h, key, value)
        u = v_t - jnp.sum(S * k_t[:, :, None], axis=1)
        S = S + b_t[:, None, None] * k_t[:, :, None] * u[:, None, :]
        return S, jnp.sum(S * q_t[:, :, None], axis=1)

    _S, o = jax.lax.scan(step, jnp.zeros((h, d, d), jnp.float32),
                         (q, k, v, a, b))
    gate = jax.nn.sigmoid(
        _matmul(_matmul(t, bp["wg_a"], int8), bp["wg_b"], int8) + bp["bg"])
    o = _norm(o, bp["o_norm"], cfg["rms_norm_eps"]).reshape(s, h * d)
    return _matmul(o * gate, bp["wo"], int8)


def _mla(t, bp, cfg: dict, int8: bool):
    """Latent attention over one sequence ``t`` of (s, hidden), normed:
    the expanded form, nothing rotated."""
    import jax
    import jax.numpy as jnp

    s = t.shape[0]
    eps, h = cfg["rms_norm_eps"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, kl = cfg["v_head_dim"], cfg["kv_lora_rank"]
    q = _matmul(t, bp["wq"], int8).reshape(s, h, nope + rope)
    c, kr = jnp.split(_matmul(t, bp["wkv_a"], int8), [kl], axis=-1)
    kv = _matmul(_norm(c, bp["kv_norm"], eps), bp["wkv_b"], int8
                 ).reshape(s, h, nope + vd)
    scores = (jnp.einsum("qhn,khn->hqk", q[..., :nope], kv[..., :nope])
              + jnp.einsum("qhr,kr->hqk", q[..., nope:], kr)) \
        * (nope + rope) ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    att = jnp.einsum("hqk,khv->qhv", p, kv[..., nope:]).reshape(s, h * vd)
    return _matmul(att, bp["wo"], int8)


def _layer(x, bp, cfg: dict, int8: bool):
    """One layer over one sequence ``x`` of (s, hidden): which mixer and
    which feed-forward it has is read off its leaves."""
    bp = _f32(bp)
    eps = cfg["rms_norm_eps"]
    mixer = _kda if "a_log" in bp else _mla
    x = x + mixer(_norm(x, bp["ln1"], eps), bp, cfg, int8)
    t = _norm(x, bp["ln2"], eps)
    if "moe" in bp:
        return x + _experts(t, bp["moe"], cfg, int8)
    return x + _gated(t, bp["w1"], bp["w2"], int8)


class Reference:
    """Holds the weights (the benchmark's own, made from the seed) and
    two compiled functions: a layer (one program a kind of layer), and
    the final norm with the unembedding of the rows that were served."""

    def __init__(self, cfg: dict, params, int8: bool = False):
        import jax
        import jax.numpy as jnp

        self.cfg, self.params = cfg, params
        eps = cfg["rms_norm_eps"]
        self._layer = jax.jit(lambda x, bp: _layer(x, bp, cfg, int8))
        self._unembed = jax.jit(lambda x, g, w: _matmul(
            _norm(x, g, eps), w.astype(jnp.float32), int8))

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
            x = self.params["embed"][jnp.asarray(ids)].astype(jnp.float32)
            for i in range(self.cfg["num_hidden_layers"]):
                x = self._layer(x, self.params[f"blk{i}"])
            rows = x[len(prompt) - 1:n]
            if rows.shape[0] % 128:    # one compiled shape a sample
                rows = jnp.pad(rows, ((0, 128 - rows.shape[0] % 128), (0, 0)))
            out = self._unembed(rows, self.params["norm_f"],
                                self.params["unembed"])
        return np.asarray(out)[:len(served)]


# ---------------------------------------------------------------------------
# operations and bytes the MODEL needs, from the configuration's shapes
# ---------------------------------------------------------------------------
#
# As in ``models/kimi_k2.py``: whatever implements a step, these do not
# change.  Per step the DENSE weights (both mixers, router, shared
# expert, the dense layer, the head) are read once at ``weight_bytes``
# a parameter; of the routed experts held here only those TOUCHED by a
# row (from the program's own counts where the reader can pass them,
# else the expectation); the live latent rows of the LATENT layers once
# at ``kv_cache_bytes`` a value; and each stepped session's KDA state
# (the matrices and the convolutions' tails) once in and once out at
# ``state_bytes`` a value, every KDA layer.  The table's lookup is a
# gather and counts no FLOP.


def kda_params(cfg: dict) -> int:
    """One KDA mixer: the four projections, the two low-rank gates,
    ``W_b``, the taps, ``A_log``, ``dt_bias``, ``b_g``, the head norm."""
    lin, d = cfg["linear_attn_config"], cfg["hidden_size"]
    h, hd, kc = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    return (4 * d * h * hd + 2 * (d * hd + hd * h * hd) + d * h
            + kc * 3 * h * hd + h + 2 * h * hd + hd)


def mla_params(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kl = cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return (d * h * (nope + rope) + d * (kl + rope)
            + kl * h * (nope + vd) + h * vd * d)


def mixer_params(cfg: dict) -> int:
    """Every layer's mixer held here, summed."""
    n_kda, n_mla = n_mixers(cfg)
    return n_kda * kda_params(cfg) + n_mla * mla_params(cfg)


def expert_params(cfg: dict) -> int:
    """One expert's gated MLP."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["num_experts_published"]


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_ffn_params(cfg: dict) -> int:
    """One expert layer's feed-forward as held here: router, the shared
    and the held experts."""
    return router_params(cfg) + expert_params(cfg) \
        * (cfg["num_shared_experts"] + cfg["num_experts"])


def total_params(cfg: dict) -> int:
    """What is held here: the layers, the table and the head."""
    dense, experts = n_layers(cfg)
    return (mixer_params(cfg) + dense * dense_mlp_params(cfg)
            + experts * expert_ffn_params(cfg)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def dense_step_params(cfg: dict) -> int:
    """Parameters every step reads whatever was routed: all but the
    routed experts and the table (a gather)."""
    dense, experts = n_layers(cfg)
    return (mixer_params(cfg) + dense * dense_mlp_params(cfg)
            + experts * (expert_ffn_params(cfg) - expert_params(cfg)
                         * cfg["num_experts"])
            + cfg["hidden_size"] * cfg["vocab_size"])


def expected_touched(cfg: dict, rows: float) -> float:
    """Held experts with at least one of ``rows`` tokens, one layer:
    a token misses a given expert with ``1 - k/routed``."""
    miss = 1.0 - cfg["num_experts_per_token"] / cfg["num_experts_published"]
    return cfg["num_experts"] * (1.0 - miss ** rows)


def expected_local_pairs(cfg: dict, rows: float) -> float:
    return rows * cfg["num_experts_per_token"] * cfg["num_experts"] \
        / cfg["num_experts_published"]


def _routing(cfg: dict, rows: int, steps: int, counters) -> tuple:
    """``(experts touched, local pairs)`` summed over expert layers and
    ``steps`` steps of ``rows`` tokens in all: the program's counts
    (``kv_stats()["moe"]`` deltas) or the expectation."""
    if counters:
        return float(counters["experts_touched"]), \
            float(counters["local_pairs"])
    layers = n_layers(cfg)[1]
    return (layers * steps * expected_touched(cfg, rows / max(steps, 1)),
            layers * expected_local_pairs(cfg, rows))


def latent_row_values(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_bytes(cfg: dict, tokens: float) -> float:
    """``tokens`` rows of every LATENT layer's cache."""
    return float(n_mixers(cfg)[1] * latent_row_values(cfg)
                 * cfg["kv_cache_bytes"]) * tokens


def kda_state_values(cfg: dict) -> int:
    """Values one session keeps in one KDA layer: the heads' matrices
    and the three convolutions' last inputs."""
    lin = cfg["linear_attn_config"]
    h, hd = lin["num_heads"], lin["head_dim"]
    return h * hd * (hd + 3 * (lin["short_conv_kernel_size"] - 1))


def kda_state_bytes(cfg: dict, sessions: float) -> float:
    """``sessions`` sessions' state in every KDA layer, once."""
    return float(n_mixers(cfg)[0] * kda_state_values(cfg)
                 * cfg["state_bytes"]) * sessions


def kda_update_flops(cfg: dict) -> float:
    """One position of one KDA layer's recurrence and convolutions: a
    head's decay, ``S'^T k``, the rank-one write and ``S^T q`` are 7
    d^2; four taps on 3 H d columns."""
    lin = cfg["linear_attn_config"]
    h, hd = lin["num_heads"], lin["head_dim"]
    return 7.0 * h * hd * hd \
        + 2.0 * lin["short_conv_kernel_size"] * 3 * h * hd


def attention_flops(cfg: dict, live: float) -> float:
    """One token's absorbed attention over ``live`` rows, one latent
    layer: scores over ``kv_lora + rope``, weights times ``kv_lora``."""
    return 2.0 * cfg["num_attention_heads"] * (
        latent_row_values(cfg) + cfg["kv_lora_rank"]) * live


def absorb_flops(cfg: dict) -> float:
    """One token, one latent layer: ``q_nope W_kvb^K`` and ``o' W_kvb^V``
    in place of the expansion of every cached row."""
    return 2.0 * cfg["num_attention_heads"] * cfg["kv_lora_rank"] \
        * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])


def _wkvb(cfg: dict) -> int:
    return cfg["kv_lora_rank"] * cfg["num_attention_heads"] \
        * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])


def token_dense_flops(cfg: dict, unembed: bool = True) -> float:
    """One token's matmuls outside attention's own, the recurrence and
    the routed experts: ``W_kvb`` is counted in :func:`absorb_flops` (a
    step) or the expansion (a fill), not here."""
    flops = 2.0 * (dense_step_params(cfg) - cfg["hidden_size"]
                   * cfg["vocab_size"] - n_mixers(cfg)[1] * _wkvb(cfg))
    if unembed:
        flops += 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return flops


def step_work(cfg: dict, lives: list, steps: int = 1,
              counters=None) -> tuple:
    """``(flops, bytes)`` of ``steps`` decode steps that between them
    produce one token for each entry of ``lives`` (the positions that
    token attends over in a latent layer, itself included)."""
    rows = len(lives)
    n_kda, n_mla = n_mixers(cfg)
    touched, pairs = _routing(cfg, rows, steps, counters)
    flops = (rows * (token_dense_flops(cfg) + n_mla * absorb_flops(cfg)
                     + n_kda * kda_update_flops(cfg))
             + n_mla * attention_flops(cfg, sum(lives))
             + 2.0 * expert_params(cfg) * pairs)
    nbytes = (cfg["weight_bytes"] * (steps * dense_step_params(cfg)
                                     + touched * expert_params(cfg))
              + latent_bytes(cfg, sum(lives) + rows)
              + 2.0 * kda_state_bytes(cfg, rows))
    return flops, nbytes


def fill_work(cfg: dict, start: int, n: int) -> tuple:
    """``(flops, bytes)`` of filling ``n`` positions of a context from
    ``start``: the dense weights once (without the head), the held
    experts the ``n`` rows touch, the latent layers in the expanded
    form (``start`` cached rows read and ``n`` written), the KDA
    layers' recurrence over ``n`` positions with the state written
    once (and read once where ``start`` > 0)."""
    if n <= 0:
        return 0.0, 0.0
    h = cfg["num_attention_heads"]
    n_kda, n_mla = n_mixers(cfg)
    lives = n * start + n * (n + 1) / 2.0
    expand = 2.0 * _wkvb(cfg)
    touched, pairs = _routing(cfg, n, 1, None)
    flops = (n * (token_dense_flops(cfg, unembed=False) + n_mla * expand
                  + n_kda * kda_update_flops(cfg))
             + start * n_mla * expand
             + 2.0 * n_mla * h * (cfg["qk_nope_head_dim"]
                                  + cfg["qk_rope_head_dim"]
                                  + cfg["v_head_dim"]) * lives
             + 2.0 * expert_params(cfg) * pairs)
    nbytes = (cfg["weight_bytes"] * (
        dense_step_params(cfg) - cfg["hidden_size"] * cfg["vocab_size"]
        + touched * expert_params(cfg)) + latent_bytes(cfg, start + n)
        + kda_state_bytes(cfg, 1 + (start > 0)))
    return flops, nbytes


# -- the step kernels' own counts (readers/step_kernel_work.py) ---------------


def kernel_calls(cfg: dict, kernel: str) -> int:
    """Calls of the named kernel in one execution of the step: the
    latent attention once a latent layer, the delta rule once a KDA
    layer, the grouped expert product twice an expert layer."""
    n_kda, n_mla = n_mixers(cfg)
    return {"mla_decode_attention": n_mla, "kda_step": n_kda,
            "expert_gmm": 2 * n_layers(cfg)[1]}[kernel]


def mla_decode_work(cfg: dict, lives: list, steps: int = 1,
                    counters=None) -> tuple:
    """``(flops, bytes)`` of the absorbed attention alone, the latent
    layers: each live latent row read once, scores and weighted sum
    over it."""
    return (n_mixers(cfg)[1] * attention_flops(cfg, sum(lives)),
            latent_bytes(cfg, sum(lives)))


def kda_step_work(cfg: dict, lives: list, steps: int = 1,
                  counters=None) -> tuple:
    """``(flops, bytes)`` of the delta rule's step alone, the KDA
    layers: each stepped session's state and tails once in and once
    out, the update's FLOPs (one entry of ``lives`` is one session
    stepped once)."""
    rows = len(lives)
    return (rows * n_mixers(cfg)[0] * kda_update_flops(cfg),
            2.0 * kda_state_bytes(cfg, rows))


def expert_work(cfg: dict, lives: list, steps: int = 1,
                counters=None) -> tuple:
    """``(flops, bytes)`` of the routed experts' grouped products
    alone: the rows that fell here through a gated MLP, each touched
    expert's weights once."""
    touched, pairs = _routing(cfg, len(lives), steps, counters)
    return (2.0 * expert_params(cfg) * pairs,
            cfg["weight_bytes"] * touched * expert_params(cfg))
