"""The block ``"model": "kimi_k2"`` names (``harness/spec.py`` loads this
file by that key; see ``models/neox.py`` for what the harness calls).

The block, from the source's ``config.json`` (``model_type: kimi_k2``,
whose modelling code is DeepSeek-V3's).  ``x`` a token's residual row,
``RMS(t; g) = t g / sqrt(mean(t^2) + rms_norm_eps)``, every norm with a
learned gain, no bias anywhere:

- layer ``i``: ``h = x + MLA(RMS(x))``, ``y = h + F_i(RMS(h))``; ``F_i``
  for ``i < first_k_dense_replace`` the dense gated MLP ``W_d(silu(W_g
  t) * W_u t)`` at ``intermediate_size``, after that the expert layer;
  a final ``RMS``; logits ``x W_head`` (untied);
- MLA: ``q = W_qb RMS(W_qa t)`` (hidden -> ``q_lora_rank`` -> heads x
  (``qk_nope_head_dim`` + ``qk_rope_head_dim``)), split per head into
  ``q_nope`` and ``q_rope``; ``[c, k_rope] = W_kva t`` (hidden ->
  ``kv_lora_rank`` + rope), ``c <- RMS(c)``; ``[k_nope_h, v_h] = W_kvb
  c`` (``kv_lora_rank`` -> heads x (nope + ``v_head_dim``)); ``q_rope``
  and ``k_rope`` rotated at the token's position (``k_rope`` is ONE row
  shared by all heads); ``score_h = (q_nope_h . k_nope_h + q_rope_h .
  k_rope) * s``, causal softmax, ``o_h = sum p v_h``, out ``W_o [o_1 ..
  o_H]``.  ``s = (nope + rope)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim
  * ln(factor) + 1``;
- rotary: YaRN over the ``rope`` rotary dimensions, base ``rope_theta``,
  ``i = 0 .. rope/2 - 1``: ``f_i = base^(-2i/rope)``; ``cd(r) = rope
  ln(original_max / (2 pi r)) / (2 ln base)``, ``low = floor(cd(
  beta_fast))``, ``high = ceil(cd(beta_slow))`` clipped to ``0 .. rope -
  1``; ``ramp_i = clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i
  = (f_i / factor) ramp_i + f_i (1 - ramp_i)``; sin and cos times
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` (1 here).
  The rotary dimensions pair as halves (``i`` with ``i + rope/2``);
- experts: ``sc = sigmoid(W_r t)`` in float32 over ALL
  ``n_routed_experts_published``; the top ``num_experts_per_tok`` of
  ``sc + b`` (``b`` the correction bias; one group); weights ``w =
  sc[chosen]`` WITHOUT ``b``, ``w <- w / (sum w + 1e-20)``, ``w <-
  routed_scaling_factor w``; ``F(t) = sum_k w_k E_k(t) + E_shared(t)``,
  every ``E`` the gated MLP at ``moe_intermediate_size``.  THIS chip
  holds experts ``0 .. n_routed_experts - 1``: it routes over all of
  them, normalises over all the chosen, and adds ``w_k E_k(t)`` only
  for chosen ``k`` it holds; a token none of whose experts fall here
  gets the shared expert alone.  That partial sum goes on to the next
  layer, in the program and in the reference alike.

The weights are in the PROGRAM'S tree (``brpc_tpu/models/
transformer_lm.py _init_block_params``): ``embed``, ``unembed``,
``norm_f``, per layer ``ln1 ln2 wq_a q_norm wq_b wkv_a kv_norm wkv_b
wo`` and either ``w1 w2`` (gate and up side by side in ``w1``) or
``moe``: ``router bias w1 w2 ws1 ws2`` (``w1``/``w2`` the held experts
stacked, ``ws1``/``ws2`` the shared expert).  Matrices are bfloat16, as
the source stores them and as the program serves them; norms and ``b``
float32.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.models.neox import _matmul

# ---------------------------------------------------------------------------
# the system under test: weights from the seed, the program's service
# ---------------------------------------------------------------------------


def n_layers(cfg: dict) -> tuple:
    """``(dense layers, expert layers)``."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def lm_kwargs(cfg: dict) -> dict:
    """The program's ``LMConfig`` arguments for a configuration file."""
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
            or cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"] \
            or cfg["moe_layer_freq"] != 1:
        raise ValueError("this block routes by sigmoid scores over one "
                         "group, renormalised, every layer past the dense")
    y = cfg["rope_scaling"]
    dense, experts = n_layers(cfg)
    return dict(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], depth=cfg["num_hidden_layers"],
        max_seq=cfg["service"]["max_seq"],
        mixers=("mla",) * cfg["num_hidden_layers"],
        ffn="gated_silu", ffn_dim=cfg["intermediate_size"],
        tie_embed=cfg["tie_word_embeddings"], final_norm=True,
        norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        rope_yarn=dict(factor=y["factor"],
                       original_max=y["original_max_position_embeddings"],
                       beta_fast=y["beta_fast"], beta_slow=y["beta_slow"],
                       mscale=y["mscale"],
                       mscale_all_dim=y["mscale_all_dim"]),
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        ffns=("dense",) * dense + ("experts",) * experts,
        expert_dim=cfg["moe_intermediate_size"],
        experts_routed=cfg["n_routed_experts_published"],
        experts_held=(0, cfg["n_routed_experts"]),
        experts_top_k=cfg["num_experts_per_tok"],
        route_scale=cfg["routed_scaling_factor"],
        shared_experts=cfg["n_shared_experts"])


def make_params(cfg: dict, seed: int):
    """Seeded weights on the device as served: matrices normal at
    ``1/sqrt(fan_in)`` rounded to bfloat16 ONCE, norms one and the
    correction bias normal at ``correction_bias_std`` in float32
    (``assumed`` in the
    configuration file).  One compiled program a layer kind."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models.transformer_lm import LMConfig

    if cfg["tie_word_embeddings"]:
        raise ValueError("this block's table is untied")
    # a program that does not know this block fails here, at once, and
    # not after 11 GB of weights have been made
    LMConfig(remat=False, **lm_kwargs(cfg))
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, f, e = cfg["v_head_dim"], cfg["intermediate_size"], \
        cfg["moe_intermediate_size"]
    routed, held = cfg["n_routed_experts_published"], cfg["n_routed_experts"]
    sh = cfg["n_shared_experts"]

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(jnp.bfloat16)

    def mixer(ks):
        return {"ln1": jnp.ones((d,), jnp.float32),
                "ln2": jnp.ones((d,), jnp.float32),
                "wq_a": normal(ks[0], (d, ql), d),
                "q_norm": jnp.ones((ql,), jnp.float32),
                "wq_b": normal(ks[1], (ql, h * (nope + rope)), ql),
                "wkv_a": normal(ks[2], (d, kl + rope), d),
                "kv_norm": jnp.ones((kl,), jnp.float32),
                "wkv_b": normal(ks[3], (kl, h * (nope + vd)), kl),
                "wo": normal(ks[4], (h * vd, d), h * vd)}

    @jax.jit
    def dense_layer(key):
        ks = jax.random.split(key, 7)
        return {**mixer(ks), "w1": normal(ks[5], (d, 2 * f), d),
                "w2": normal(ks[6], (f, d), f)}

    @jax.jit
    def expert_layer(key):
        ks = jax.random.split(key, 11)
        return {**mixer(ks), "moe": {
            "router": normal(ks[5], (d, routed), d),
            "bias": jax.random.normal(ks[6], (routed,), jnp.float32)
            * cfg["correction_bias_std"],
            "w1": normal(ks[7], (held, d, 2 * e), d),
            "w2": normal(ks[8], (held, e, d), e),
            "ws1": normal(ks[9], (d, 2 * sh * e), d),
            "ws2": normal(ks[10], (sh * e, d), sh * e)}}

    # --seed may pass 2**31: fold the high bits in instead of wrapping
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl="rbg"),
                             seed >> 31)
    ks = jax.random.split(key, 2 + cfg["num_hidden_layers"])
    params = {"embed": jax.jit(lambda k: normal(k, (v, d), d))(ks[0]),
              "unembed": jax.jit(lambda k: normal(k, (d, v), d))(ks[1]),
              "norm_f": jnp.ones((d,), jnp.float32)}
    dense, _experts = n_layers(cfg)
    for i in range(cfg["num_hidden_layers"]):
        make = dense_layer if i < dense else expert_layer
        params[f"blk{i}"] = make(ks[2 + i])
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
# precision: one request at a time, one layer at a time, the EXPANDED
# attention (keys and values made from the latent rows) as a full
# causal softmax over the whole context, the expert layer a plain loop
# over the held experts with a mask.  No kernel, no cache, no pages, no
# slots, no sort, no absorbed form.  It imports nothing of the program
# and is given the same share of the experts.  The control is the same
# with every weight matmul computed from int8 operands
# (``models/neox.py``'s ``_matmul``); the router, whose choice the
# model's mathematics keeps in float32, stays float32 in both.


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    y, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        cfg["rope_theta"]
    f = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def cd(r):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (r * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(cd(y["beta_fast"])), 0)
    high = min(math.ceil(cd(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return f / y["factor"] * ramp + f * (1.0 - ramp)


def _yarn_mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1.0 else 0.1 * m * math.log(scale) + 1.0


def softmax_scale(cfg: dict) -> float:
    y = cfg["rope_scaling"]
    m = _yarn_mscale(y["factor"], y["mscale_all_dim"]) \
        if y["mscale_all_dim"] else 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


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
                      stable=True)[:, :cfg["num_experts_per_tok"]]
    w = jnp.take_along_axis(sc, ids, axis=-1)
    w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return ids, w * cfg["routed_scaling_factor"]


def _experts(t, mp, cfg: dict, int8: bool, held=None):
    """The expert layer's output for normed rows ``t``: the held
    experts' weighted part and the shared expert.  ``held`` (a range
    of expert ids whose weights are ``mp["w1"]``'s rows in order)
    defaults to ``0 .. n_routed_experts - 1``."""
    import jax.numpy as jnp

    ids, w = route(t, mp, cfg)
    lo, hi = held if held is not None else (0, cfg["n_routed_experts"])
    out = _gated(t, mp["ws1"], mp["ws2"], int8)
    for e in range(lo, hi):
        w_e = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
        out = out + w_e[:, None] * _gated(t, mp["w1"][e - lo],
                                          mp["w2"][e - lo], int8)
    return out


def _mla(t, bp, cfg: dict, int8: bool):
    """Latent attention over one sequence ``t`` of (s, hidden), normed:
    the expanded form."""
    import jax
    import jax.numpy as jnp

    s = t.shape[0]
    eps, h = cfg["rms_norm_eps"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, kl = cfg["v_head_dim"], cfg["kv_lora_rank"]
    q = _matmul(_norm(_matmul(t, bp["wq_a"], int8), bp["q_norm"], eps),
                bp["wq_b"], int8).reshape(s, h, nope + rope)
    c, kr = jnp.split(_matmul(t, bp["wkv_a"], int8), [kl], axis=-1)
    kv = _matmul(_norm(c, bp["kv_norm"], eps), bp["wkv_b"], int8
                 ).reshape(s, h, nope + vd)
    y = cfg["rope_scaling"]
    m = _yarn_mscale(y["factor"], y["mscale"]) \
        / _yarn_mscale(y["factor"], y["mscale_all_dim"])
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_inv_freq(cfg), jnp.float32)[None, :]
    sin, cos = jnp.sin(ang) * m, jnp.cos(ang) * m         # (s, rope/2)

    def rot(x):                 # (s, ..., rope): pairs are halves
        a, b = x[..., :rope // 2], x[..., rope // 2:]
        sn = sin.reshape(s, *([1] * (x.ndim - 2)), rope // 2)
        cs = cos.reshape(s, *([1] * (x.ndim - 2)), rope // 2)
        return jnp.concatenate([a * cs - b * sn, a * sn + b * cs], axis=-1)

    q_nope, q_rope = q[..., :nope], rot(q[..., nope:])
    kr = rot(kr)
    scores = (jnp.einsum("qhn,khn->hqk", q_nope, kv[..., :nope])
              + jnp.einsum("qhr,kr->hqk", q_rope, kr)) * softmax_scale(cfg)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    att = jnp.einsum("hqk,khv->qhv", p, kv[..., nope:]).reshape(s, h * vd)
    return _matmul(att, bp["wo"], int8)


def _layer(x, bp, cfg: dict, int8: bool):
    """One layer over one sequence ``x`` of (s, hidden)."""
    bp = _f32(bp)
    eps = cfg["rms_norm_eps"]
    x = x + _mla(_norm(x, bp["ln1"], eps), bp, cfg, int8)
    t = _norm(x, bp["ln2"], eps)
    if "moe" in bp:
        return x + _experts(t, bp["moe"], cfg, int8)
    return x + _gated(t, bp["w1"], bp["w2"], int8)


class Reference:
    """Holds the weights (the benchmark's own, made from the seed) and
    two compiled functions: a layer of either kind, and the final norm
    with the unembedding of the rows that were served."""

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
# As in ``models/neox.py``: whatever implements a step, these do not
# change.  Per step the DENSE weights (attention, router, shared
# expert, the dense layer, the head) are read once at ``weight_bytes``
# a parameter; of the routed experts held here only those TOUCHED by a
# row (from the program's own counts where the reader can pass them,
# else the expectation ``held (1 - (1 - k/routed)^rows)`` a layer); the
# live latent rows once at ``kv_cache_bytes`` a value (``kv_lora_rank +
# qk_rope_head_dim`` values a token and layer, not the row's padding);
# FLOPs of the absorbed form.  The table's lookup is a gather and
# counts no FLOP.


def mla_params(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return (d * ql + ql * h * (nope + rope) + d * (kl + rope)
            + kl * h * (nope + vd) + h * vd * d)


def expert_params(cfg: dict) -> int:
    """One expert's gated MLP."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["n_routed_experts_published"]


def expert_layer_params(cfg: dict) -> int:
    """One expert layer as held here: attention, router, the shared
    and the held experts."""
    return mla_params(cfg) + router_params(cfg) + expert_params(cfg) \
        * (cfg["n_shared_experts"] + cfg["n_routed_experts"])


def dense_layer_params(cfg: dict) -> int:
    return mla_params(cfg) + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def total_params(cfg: dict) -> int:
    """Matrices held here: the layers, the table and the head."""
    dense, experts = n_layers(cfg)
    return (dense * dense_layer_params(cfg)
            + experts * expert_layer_params(cfg)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def dense_step_params(cfg: dict) -> int:
    """Parameters every step reads whatever was routed: all but the
    routed experts and the table (a gather)."""
    dense, experts = n_layers(cfg)
    return (dense * dense_layer_params(cfg)
            + experts * (expert_layer_params(cfg) - expert_params(cfg)
                         * cfg["n_routed_experts"])
            + cfg["hidden_size"] * cfg["vocab_size"])


def expected_touched(cfg: dict, rows: float) -> float:
    """Held experts with at least one of ``rows`` tokens, one layer:
    a token misses a given expert with ``1 - k/routed``."""
    miss = 1.0 - cfg["num_experts_per_tok"] / cfg["n_routed_experts_published"]
    return cfg["n_routed_experts"] * (1.0 - miss ** rows)


def expected_local_pairs(cfg: dict, rows: float) -> float:
    return rows * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["n_routed_experts_published"]


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
    return float(cfg["num_hidden_layers"] * latent_row_values(cfg)
                 * cfg["kv_cache_bytes"]) * tokens


def attention_flops(cfg: dict, live: float) -> float:
    """One token's absorbed attention over ``live`` rows, one layer:
    scores over ``kv_lora + rope``, weights times ``kv_lora``."""
    return 2.0 * cfg["num_attention_heads"] * (
        latent_row_values(cfg) + cfg["kv_lora_rank"]) * live


def absorb_flops(cfg: dict) -> float:
    """One token, one layer: ``q_nope W_kvb^K`` and ``o' W_kvb^V`` in
    place of the expansion of every cached row."""
    return 2.0 * cfg["num_attention_heads"] * cfg["kv_lora_rank"] \
        * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])


def token_dense_flops(cfg: dict, unembed: bool = True) -> float:
    """One token's matmuls outside attention's own and the routed
    experts: ``W_kvb`` is counted in :func:`absorb_flops` (a step) or
    the expansion (a fill), not here."""
    h, kl = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    wkvb = kl * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    flops = 2.0 * (dense_step_params(cfg) - cfg["hidden_size"]
                   * cfg["vocab_size"] - cfg["num_hidden_layers"] * wkvb)
    if unembed:
        flops += 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return flops


def step_work(cfg: dict, lives: list, steps: int = 1,
              counters=None) -> tuple:
    """``(flops, bytes)`` of ``steps`` decode steps that between them
    produce one token for each entry of ``lives`` (the positions that
    token attends over, itself included)."""
    rows = len(lives)
    touched, pairs = _routing(cfg, rows, steps, counters)
    flops = (rows * (token_dense_flops(cfg)
                     + cfg["num_hidden_layers"] * absorb_flops(cfg))
             + cfg["num_hidden_layers"] * attention_flops(cfg, sum(lives))
             + 2.0 * expert_params(cfg) * pairs)
    nbytes = (cfg["weight_bytes"] * (steps * dense_step_params(cfg)
                                     + touched * expert_params(cfg))
              + latent_bytes(cfg, sum(lives) + rows))
    return flops, nbytes


def fill_work(cfg: dict, start: int, n: int) -> tuple:
    """``(flops, bytes)`` of filling ``n`` positions of a context from
    ``start`` in the expanded form: the dense weights once (without
    the head), the held experts the ``n`` rows touch, the ``start``
    cached rows read and ``n`` written."""
    if n <= 0:
        return 0.0, 0.0
    h, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    lives = n * start + n * (n + 1) / 2.0
    expand = 2.0 * cfg["kv_lora_rank"] * h \
        * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    touched, pairs = _routing(cfg, n, 1, None)
    flops = (n * (token_dense_flops(cfg, unembed=False) + layers * expand)
             + start * layers * expand
             + 2.0 * layers * h * (cfg["qk_nope_head_dim"]
                                   + cfg["qk_rope_head_dim"]
                                   + cfg["v_head_dim"]) * lives
             + 2.0 * expert_params(cfg) * pairs)
    nbytes = (cfg["weight_bytes"] * (
        dense_step_params(cfg) - cfg["hidden_size"] * cfg["vocab_size"]
        + touched * expert_params(cfg)) + latent_bytes(cfg, start + n))
    return flops, nbytes


# -- the step kernels' own counts (readers/step_kernel_work.py) ---------------


def kernel_calls(cfg: dict, kernel: str) -> int:
    """Calls of the named kernel in one execution of the step: the
    latent attention once a layer, the grouped expert product (were it
    a kernel of the program's) twice an expert layer."""
    return {"mla_decode_attention": cfg["num_hidden_layers"],
            "expert_gmm": 2 * n_layers(cfg)[1]}[kernel]


def mla_decode_work(cfg: dict, lives: list, steps: int = 1,
                    counters=None) -> tuple:
    """``(flops, bytes)`` of the absorbed attention alone, all layers:
    each live latent row read once, scores and weighted sum over it
    (the projections around it are XLA's matmuls, in ``step_work``)."""
    return (cfg["num_hidden_layers"] * attention_flops(cfg, sum(lives)),
            latent_bytes(cfg, sum(lives)))


def expert_work(cfg: dict, lives: list, steps: int = 1,
                counters=None) -> tuple:
    """``(flops, bytes)`` of the routed experts' grouped products
    alone: the rows that fell here through a gated MLP, each touched
    expert's weights once."""
    touched, pairs = _routing(cfg, len(lives), steps, counters)
    return (2.0 * expert_params(cfg) * pairs,
            cfg["weight_bytes"] * touched * expert_params(cfg))
