"""The block ``"model": "nemotron"`` names (``harness/spec.py`` loads this
file by that key; see ``models/neox.py`` for what the harness calls).

The block, from the source's ``config.json`` (``model_type:
nemotron_h``).  ``x`` a token's float32 residual row, ``RMS(t; g) = t g
/ sqrt(mean t^2 + layer_norm_epsilon)``, every norm with a learned
gain, no bias but the convolution's:

- ``x0 = E_in[ids]`` (an untied table, no multiplier);
- layer ``l`` is ONE part with ONE pre-norm: ``x <- x +
  Part_l(RMS(x; g_l))``; ``hybrid_override_pattern[l]`` names the part
  (``M`` Mamba-2, ``E`` the expert layer, ``*`` attention; the string
  is the source's, whole; its first ``num_hidden_layers`` characters
  are read);
- after the last layer ``RMS(x; g_f)``, logits ``= x W_head^T`` (an
  untied head);
- ``M`` (``mamba_num_heads`` H heads of ``mamba_head_dim`` P, ``d_inner
  = H P``, a state of ``ssm_state_size`` N, ``n_groups`` G, ``conv_kernel``
  taps): ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC) + b_conv)``
  (depthwise, causal); ``[x | B | C] = xBC`` (``d_inner``, ``G N``, ``G
  N``); ``dt_h = softplus(dt_h + dt_bias_h)`` (no clamp), ``A_h =
  -exp(A_log_h)``; with ``S_h`` a ``P x N`` float32 state from zero and
  head ``h`` in group ``h // (H / G)``: ``S_h <- exp(dt_h A_h) S_h +
  dt_h x_h B_g^T``, ``y_h = S_h C_g + D_h x_h``; ``v = y * silu(z)``,
  each of the G groups of ``d_inner / G`` channels divided by ITS OWN
  RMS, times ``g_norm``; out ``v W_out``;
- ``E`` (``n_routed_experts_published`` routed, ``num_experts_per_tok``
  a token, ``n_shared_experts`` shared): scores ``s = sigmoid(u W_r)``
  in float32 over ALL routed experts; ids the top-k of ``s +
  e_score_correction_bias`` (the bias chooses, it does not weigh;
  ``n_group`` = ``topk_group`` = 1: one group); ``w = s[ids] / (sum
  s[ids] + 1e-20) * routed_scaling_factor``; ``expert(u) = relu(u
  W_up)^2 W_down`` at ``moe_intermediate_size``, NOT gated; ``shared(u)``
  the same form at ``moe_shared_expert_intermediate_size``; out ``sum_k
  w_k expert_ids[k](u) + shared(u)``.  THIS chip holds experts ``0 ..
  n_routed_experts - 1``: it routes over all of them and adds ``w_k
  expert_k(u)`` only for chosen ``k`` it holds, in the program and in
  the reference alike;
- ``*``: ``q = u W_q`` (``num_attention_heads`` heads of ``head_dim``:
  wider than hidden, ``W_o`` maps them back), ``k, v = u W_k, u W_v``
  (``num_key_value_heads``, each shared by ``heads / kv_heads`` query
  heads), NOTHING rotated, a causal softmax over ``q k^T /
  sqrt(head_dim)``, out ``concat(heads) W_o``.

Readings taken into the source (``assumed`` in the configuration file):
attention rotates nothing although ``rope_theta`` and
``partial_rotary_factor`` are in the config; the gated norm works a
group at a time; ``moe_intermediate_size`` is a routed expert's width;
``A_log``, ``dt_bias``, ``D`` and the convolution's bias as Mamba-2
initialises them.

The weights are in the PROGRAM'S tree (``brpc_tpu/models/
transformer_lm.py _init_block_params``): ``embed``, ``unembed``,
``norm_f``, per layer the part it has and that part's norm: ``ln1`` and
``in_proj conv_w conv_b dt_bias a_log d norm out_proj`` (Mamba-2:
``in_proj`` holds z, xBC and dt side by side) or ``ln1`` and ``wqkv
wo``; or ``ln2`` and ``moe``: ``router bias w1 w2 ws1 ws2`` (nothing
is gated: ``w1`` is ``W_up`` alone, and a routed expert's lies
transposed, ``(moe_intermediate_size, hidden_size)`` as its ``w2``
does).  Matrices are bfloat16, as the source stores them and as the
program serves them; norms, taps, ``a_log``, ``d``, ``dt_bias`` and the
biases float32.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.models.neox import _matmul

# the parts a layer may be, by the pattern's letter: (mixer, ffn) in
# the program's ``LMConfig``
PARTS = {"M": ("mamba2", "none"), "E": ("none", "experts"),
         "*": ("attn", "none")}

# ---------------------------------------------------------------------------
# the system under test: weights from the seed, the program's service
# ---------------------------------------------------------------------------


def pattern(cfg: dict) -> str:
    """The pattern's letters of the layers held here."""
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def n_parts(cfg: dict) -> tuple:
    """``(Mamba-2 layers, expert layers, attention layers)``."""
    p = pattern(cfg)
    return p.count("M"), p.count("E"), p.count("*")


def lm_kwargs(cfg: dict) -> dict:
    """The program's ``LMConfig`` arguments for a configuration file."""
    if cfg["attention_bias"] or cfg["mlp_bias"] or cfg["use_bias"] \
            or cfg["mamba_proj_bias"] or not cfg["use_conv_bias"] \
            or cfg["mlp_hidden_act"] != "relu2" \
            or cfg["mamba_hidden_act"] != "silu" \
            or cfg["tie_word_embeddings"] or cfg["sliding_window"]:
        raise ValueError("this block has no bias but the convolution's, "
                         "ungated squared-ReLU experts, SiLU in the "
                         "mixer, an untied table and no window")
    if (cfg["n_group"], cfg["topk_group"], cfg["norm_topk_prob"]) \
            != (1, 1, True) or cfg["n_shared_experts"] != 1:
        raise ValueError("one group of experts, the chosen weights "
                         "renormalised, one shared expert")
    if set(pattern(cfg)) - set(PARTS):
        raise ValueError(f"a layer is one of {sorted(PARTS)}")
    parts = [PARTS[c] for c in pattern(cfg)]
    e = cfg["moe_intermediate_size"]
    return dict(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        depth=cfg["num_hidden_layers"], max_seq=cfg["service"]["max_seq"],
        mixers=tuple(m for m, _f in parts), rope=False, ffn="relu2",
        ffn_dim=e, tie_embed=False, final_norm=True,
        norm_eps=cfg["layer_norm_epsilon"], rope_theta=cfg["rope_theta"],
        ffns=tuple(f for _m, f in parts), expert_dim=e,
        experts_routed=cfg["n_routed_experts_published"],
        experts_held=(0, cfg["n_routed_experts"]),
        experts_top_k=cfg["num_experts_per_tok"],
        route_scale=cfg["routed_scaling_factor"],
        shared_experts=cfg["n_shared_experts"],
        shared_dim=cfg["moe_shared_expert_intermediate_size"],
        router_bias=True, router_scoring="sigmoid",
        mamba2_heads=cfg["mamba_num_heads"],
        mamba2_head_dim=cfg["mamba_head_dim"],
        mamba2_state=cfg["ssm_state_size"], mamba2_groups=cfg["n_groups"],
        mamba2_norm_groups=cfg["n_groups"], mamba2_conv=cfg["conv_kernel"],
        mamba2_chunk=cfg["chunk_size"])


def make_params(cfg: dict, seed: int):
    """Seeded weights on the device as served: matrices normal at
    ``1/sqrt(fan_in)`` rounded to bfloat16 ONCE; norms and ``D`` one,
    the convolution's bias zero, ``a_log`` the log of a rate uniform in
    1..16 a head, ``dt_bias`` with ``softplus(dt_bias)`` log-uniform in
    ``time_step_min``..``time_step_max``, the correction bias normal at
    ``correction_bias_std``, all float32 (``assumed`` in the
    configuration file).  One compiled program a kind of layer."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models.transformer_lm import LMConfig

    # a program that does not know this block fails here, at once, and
    # not after 6.3 GB of weights have been made
    LMConfig(remat=False, **lm_kwargs(cfg))
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kvh, hd = cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    h, p, n = cfg["mamba_num_heads"], cfg["mamba_head_dim"], \
        cfg["ssm_state_size"]
    g, kc = cfg["n_groups"], cfg["conv_kernel"]
    di, cw = h * p, h * p + 2 * g * n
    e, sh = cfg["moe_intermediate_size"], \
        cfg["moe_shared_expert_intermediate_size"]
    routed, held = cfg["n_routed_experts_published"], cfg["n_routed_experts"]
    lo_dt, hi_dt = math.log(cfg["time_step_min"]), \
        math.log(cfg["time_step_max"])

    def f32(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

    def normal(k, shape, fan_in):
        return f32(k, shape, fan_in).astype(jnp.bfloat16)

    def ones():
        return jnp.ones((d,), jnp.float32)

    def mamba2(key):
        ks = jax.random.split(key, 5)
        dt = jnp.exp(jax.random.uniform(ks[3], (h,), jnp.float32)
                     * (hi_dt - lo_dt) + lo_dt)
        return {"in_proj": normal(ks[0], (d, di + cw + h), d),
                "conv_w": f32(ks[1], (kc, cw), kc),
                "conv_b": jnp.zeros((cw,), jnp.float32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "a_log": jnp.log(jax.random.uniform(
                    ks[4], (h,), jnp.float32, 1.0, 16.0)),
                "d": jnp.ones((h,), jnp.float32),
                "norm": jnp.ones((di,), jnp.float32),
                "out_proj": normal(ks[2], (di, d), di), "ln1": ones()}

    def attn(key):
        ks = jax.random.split(key, 2)
        return {"wqkv": normal(ks[0], (d, (heads + 2 * kvh) * hd), d),
                "wo": normal(ks[1], (heads * hd, d), heads * hd),
                "ln1": ones()}

    def experts(key):
        ks = jax.random.split(key, 6)
        return {"ln2": ones(), "moe": {
            "router": normal(ks[0], (d, routed), d),
            "bias": jax.random.normal(ks[1], (routed,), jnp.float32)
            * cfg["correction_bias_std"],
            "w1": normal(ks[2], (held, e, d), d),
            "w2": normal(ks[3], (held, e, d), e),
            "ws1": normal(ks[4], (d, sh), d),
            "ws2": normal(ks[5], (sh, d), sh)}}

    makers = {"M": jax.jit(mamba2), "E": jax.jit(experts),
              "*": jax.jit(attn)}
    # --seed may pass 2**31: fold the high bits in instead of wrapping
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl="rbg"),
                             seed >> 31)
    ks = jax.random.split(key, 2 + cfg["num_hidden_layers"])
    params = {"embed": jax.jit(lambda k: normal(k, (v, d), d))(ks[0]),
              "unembed": jax.jit(lambda k: normal(k, (d, v), d))(ks[1]),
              "norm_f": jnp.ones((d,), jnp.float32)}
    for i, c in enumerate(pattern(cfg)):
        params[f"blk{i}"] = makers[c](ks[2 + i])
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
# precision: one request at a time, one layer at a time; the Mamba-2
# recurrence the PER-POSITION scan from the zero state (not the chunked
# form the program's prompts take), the convolution a sum of shifted
# copies; attention a full causal softmax over the whole context; the
# expert layer a plain loop over the held experts with a mask.  No
# kernel, no cache, no pages, no slots, no state pool, no chunks.  It
# imports nothing of the program and is given the same share of the
# experts.  The control is the same with every weight matmul computed
# from int8 operands (``models/neox.py``'s ``_matmul``); the router,
# whose choice the model's mathematics keeps in float32, and the
# recurrence stay float32 in both.


def _norm(t, g, eps: float):
    import jax.numpy as jnp

    return t * g / jnp.sqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)


def _f32(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _relu2(t, w1, w2, int8: bool):
    import jax
    import jax.numpy as jnp

    return _matmul(jnp.square(jax.nn.relu(_matmul(t, w1, int8))), w2, int8)


def route(t, mp, cfg: dict):
    """``(ids (s, k), w (s, k))`` over ALL published experts: chosen by
    score plus bias, weighed by the score alone."""
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
    out = _relu2(t, mp["ws1"], mp["ws2"], int8)
    for e in range(lo, hi):
        w_e = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
        out = out + w_e[:, None] * _relu2(t, mp["w1"][e - lo].T,
                                          mp["w2"][e - lo], int8)
    return out


def _mamba2(t, bp, cfg: dict, int8: bool):
    """Mamba-2 over one sequence ``t`` of (s, hidden), normed, from the
    zero state: a scan over the positions, the gated norm a group at a
    time."""
    import jax
    import jax.numpy as jnp

    h, p, n = cfg["mamba_num_heads"], cfg["mamba_head_dim"], \
        cfg["ssm_state_size"]
    g, kc = cfg["n_groups"], cfg["conv_kernel"]
    di = h * p
    s = t.shape[0]
    z, xbc, dt = jnp.split(_matmul(t, bp["in_proj"], int8),
                           [di, 2 * di + 2 * g * n], axis=-1)
    pre = jnp.pad(xbc, ((kc - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(bp["conv_w"][j] * pre[j:j + s] for j in range(kc))
                      + bp["conv_b"])
    x, b, c = jnp.split(xbc, [di, di + g * n], axis=-1)
    x = x.reshape(s, h, p)
    # each head's rows of B and C: head i is in group i // (h / g)
    b = jnp.repeat(b.reshape(s, g, n), h // g, axis=1)
    c = jnp.repeat(c.reshape(s, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt + bp["dt_bias"])                   # (s, h)
    a = -jnp.exp(bp["a_log"])

    def step(S, xs):
        # (products and sums written out: float32 as they stand, where
        # an einsum would go through the matrix unit's passes)
        x_t, dt_t, b_t, c_t = xs
        S = jnp.exp(dt_t * a)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return S, jnp.sum(S * c_t[:, None, :], axis=-1) \
            + bp["d"][:, None] * x_t

    _S, y = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32),
                         (x, dt, b, c))
    v = (y.reshape(s, di) * jax.nn.silu(z)).reshape(s, g, di // g)
    v = _norm(v, bp["norm"].reshape(g, di // g), cfg["layer_norm_epsilon"])
    return _matmul(v.reshape(s, di), bp["out_proj"], int8)


def _attention(t, bp, cfg: dict, int8: bool):
    """Grouped attention over one sequence ``t`` of (s, hidden), normed:
    nothing rotated, the softmax over ``q k^T / sqrt(head_dim)``."""
    import jax
    import jax.numpy as jnp

    s = t.shape[0]
    heads, kvh, hd = cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    q, k, v = jnp.split(_matmul(t, bp["wqkv"], int8),
                        [heads * hd, (heads + kvh) * hd], axis=-1)
    q = q.reshape(s, heads, hd)
    k = jnp.repeat(k.reshape(s, kvh, hd), heads // kvh, axis=1)
    v = jnp.repeat(v.reshape(s, kvh, hd), heads // kvh, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    att = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return _matmul(jnp.einsum("hqk,khd->qhd", att, v).reshape(s, heads * hd),
                   bp["wo"], int8)


def _layer(x, bp, cfg: dict, int8: bool):
    """One layer over one sequence ``x`` of (s, hidden): its one part,
    read off its leaves, behind its one norm."""
    bp = _f32(bp)
    eps = cfg["layer_norm_epsilon"]
    if "moe" in bp:
        return x + _experts(_norm(x, bp["ln2"], eps), bp["moe"], cfg, int8)
    mixer = _mamba2 if "a_log" in bp else _attention
    return x + mixer(_norm(x, bp["ln1"], eps), bp, cfg, int8)


class Reference:
    """Holds the weights (the benchmark's own, made from the seed) and
    two compiled functions: a layer (one program a kind of layer), and
    the final norm with the unembedding of the rows that were served."""

    def __init__(self, cfg: dict, params, int8: bool = False):
        import jax
        import jax.numpy as jnp

        self.cfg, self.params = cfg, params
        eps = cfg["layer_norm_epsilon"]
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
# As in ``models/granite.py``: whatever implements a step, these do not
# change.  Per step the DENSE weights (the mixers, the routers, the
# shared experts, the head) are read once at ``weight_bytes`` a
# parameter; of the routed experts held here only those TOUCHED by a
# row (from the program's own counts where the reader can pass them,
# else the expectation); the live keys and values of the ATTENTION
# layers once at ``kv_cache_bytes`` a value; and each stepped session's
# Mamba-2 state (the matrices and the convolution's ring) once in and
# once out at ``state_bytes`` a value, every Mamba-2 layer.  The
# table's lookup is a gather and counts no FLOP.  A routed expert is
# ``d x e`` up and ``e x d`` down: nothing gated.


def _widths(cfg: dict) -> tuple:
    """``(H, P, N, G, conv taps, d_inner, conv channels)``."""
    h, p, n = cfg["mamba_num_heads"], cfg["mamba_head_dim"], \
        cfg["ssm_state_size"]
    g = cfg["n_groups"]
    return h, p, n, g, cfg["conv_kernel"], h * p, h * p + 2 * g * n


def mamba2_params(cfg: dict) -> int:
    """One Mamba-2 layer: ``W_in``, the taps and their bias, ``A_log``,
    ``D``, ``dt_bias``, the gated norm's gain, ``W_out``, the layer's
    norm."""
    h, _p, _n, _g, kc, di, cw = _widths(cfg)
    d = cfg["hidden_size"]
    return d * (di + cw + h) + (kc + 1) * cw + 3 * h + di + di * d + d


def attn_params(cfg: dict) -> int:
    """One attention layer with its norm."""
    d, heads, hd = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["head_dim"]
    return d * (heads + 2 * cfg["num_key_value_heads"]) * hd \
        + heads * hd * d + d


def expert_params(cfg: dict) -> int:
    """One routed expert: up and down, nothing gated."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router over every published expert and its correction
    bias."""
    return (cfg["hidden_size"] + 1) * cfg["n_routed_experts_published"]


def shared_params(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"]


def expert_layer_params(cfg: dict) -> int:
    """One expert layer as held here but its routed experts: the
    router, the shared expert, the layer's norm."""
    return router_params(cfg) + shared_params(cfg) + cfg["hidden_size"]


def total_params(cfg: dict) -> int:
    """What is held here: the layers, both tables, the final norm."""
    n_m, n_e, n_a = n_parts(cfg)
    d = cfg["hidden_size"]
    return (n_m * mamba2_params(cfg) + n_a * attn_params(cfg)
            + n_e * (expert_layer_params(cfg)
                     + cfg["n_routed_experts"] * expert_params(cfg))
            + 2 * d * cfg["vocab_size"] + d)


def dense_step_params(cfg: dict) -> int:
    """Parameters every step reads whatever was routed: all but the
    routed experts and the input table (a gather)."""
    return total_params(cfg) - n_parts(cfg)[1] * cfg["n_routed_experts"] \
        * expert_params(cfg) - cfg["hidden_size"] * cfg["vocab_size"]


def expected_touched(cfg: dict, rows: float) -> float:
    """Held experts with at least one of ``rows`` tokens, one layer:
    a token misses a given expert with ``1 - k/routed``."""
    miss = 1.0 - cfg["num_experts_per_tok"] \
        / cfg["n_routed_experts_published"]
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
    layers = n_parts(cfg)[1]
    return (layers * steps * expected_touched(cfg, rows / max(steps, 1)),
            layers * expected_local_pairs(cfg, rows))


def kv_row_bytes(cfg: dict) -> int:
    """One token's keys and values in every ATTENTION layer."""
    return n_parts(cfg)[2] * 2 * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * cfg["kv_cache_bytes"]


def ssd_state_values(cfg: dict) -> int:
    """Values of one session's SSD state in one Mamba-2 layer."""
    h, p, n = _widths(cfg)[:3]
    return h * p * n


def state_values(cfg: dict) -> int:
    """Values one session keeps in one Mamba-2 layer: the SSD state and
    the convolution's last ``conv_kernel - 1`` inputs."""
    kc, cw = _widths(cfg)[4], _widths(cfg)[6]
    return ssd_state_values(cfg) + (kc - 1) * cw


def state_bytes(cfg: dict, sessions: float) -> float:
    """``sessions`` sessions' state in every Mamba-2 layer, once."""
    return float(n_parts(cfg)[0] * state_values(cfg)
                 * cfg["state_bytes"]) * sessions


def ssd_update_flops(cfg: dict) -> float:
    """One position of one Mamba-2 layer's recurrence: a head's decay,
    the rank-one write ``(dt x) B^T``, ``S C`` and ``D x`` are 5 P N
    (and a few P); the taps on the convolution's channels."""
    h, p, n, _g, kc, _di, cw = _widths(cfg)
    return 5.0 * h * p * n + 2.0 * kc * cw


def ssd_chunk_flops(cfg: dict, n_pos: int) -> float:
    """The chunked form over ``n_pos`` positions (chunks of
    ``chunk_size``), one layer: ``C B^T`` a chunk, the masked quadratic
    form, the start state's part of each output and the state the
    chunk leaves (each a product of its widths), the taps."""
    h, p, n, g, kc, _di, cw = _widths(cfg)
    w = min(cfg["chunk_size"], n_pos)
    chunks = -(-n_pos // w)
    per_chunk = 2.0 * w * w * g * n + 2.0 * h * w * w * p \
        + 2.0 * 2.0 * w * h * p * n
    return chunks * per_chunk + 2.0 * kc * cw * n_pos


def attention_flops(cfg: dict, live: float) -> float:
    """One token's attention over ``live`` rows, one attention layer:
    scores and the weighted sum over every query head."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * live


def token_dense_flops(cfg: dict, unembed: bool = True) -> float:
    """One token's matmuls outside attention's own, the recurrence and
    the routed experts."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return 2.0 * (dense_step_params(cfg) - d * v) \
        + (2.0 * d * v if unembed else 0.0)


def step_work(cfg: dict, lives: list, steps: int = 1,
              counters=None) -> tuple:
    """``(flops, bytes)`` of ``steps`` decode steps that between them
    produce one token for each entry of ``lives`` (the positions that
    token attends over in an attention layer, itself included)."""
    rows = len(lives)
    n_m, _n_e, n_a = n_parts(cfg)
    touched, pairs = _routing(cfg, rows, steps, counters)
    flops = (rows * (token_dense_flops(cfg) + n_m * ssd_update_flops(cfg))
             + n_a * attention_flops(cfg, sum(lives))
             + 2.0 * expert_params(cfg) * pairs)
    nbytes = (cfg["weight_bytes"] * (steps * dense_step_params(cfg)
                                     + touched * expert_params(cfg))
              + kv_row_bytes(cfg) * (sum(lives) + rows)
              + 2.0 * state_bytes(cfg, rows))
    return flops, nbytes


def fill_work(cfg: dict, start: int, n: int) -> tuple:
    """``(flops, bytes)`` of filling ``n`` positions of a context from
    ``start``: the dense weights once (without the head), the held
    experts the ``n`` rows touch, the attention layers over ``start +
    n`` rows with ``n`` written, the Mamba-2 layers' chunked form over
    ``n`` positions with the state written once (and read once where
    ``start`` > 0)."""
    if n <= 0:
        return 0.0, 0.0
    n_m, _n_e, n_a = n_parts(cfg)
    lives = n * start + n * (n + 1) / 2.0
    touched, pairs = _routing(cfg, n, 1, None)
    flops = (n * token_dense_flops(cfg, unembed=False)
             + n_m * ssd_chunk_flops(cfg, n)
             + n_a * attention_flops(cfg, lives)
             + 2.0 * expert_params(cfg) * pairs)
    nbytes = (cfg["weight_bytes"] * (
        dense_step_params(cfg) - cfg["hidden_size"] * cfg["vocab_size"]
        + touched * expert_params(cfg)) + kv_row_bytes(cfg) * (start + n)
        + state_bytes(cfg, 1 + (start > 0)))
    return flops, nbytes


# -- the step kernels' own counts (readers/step_kernel_work.py) ---------------


def kernel_calls(cfg: dict, kernel: str) -> int:
    """Calls of the named kernel in one execution of the step: the SSD
    update once a Mamba-2 layer, the paged attention once an attention
    layer, the grouped expert product twice an EXPERT layer."""
    n_m, n_e, n_a = n_parts(cfg)
    return {"ssd_step": n_m, "paged_decode_attention": n_a,
            "expert_gmm": 2 * n_e}[kernel]


def ssd_step_work(cfg: dict, lives: list, steps: int = 1,
                  counters=None) -> tuple:
    """``(flops, bytes)`` of the SSD update alone, the Mamba-2 layers:
    each stepped session's ``H x P x N`` state once in and once out (the
    convolution's ring is written outside the kernel), the update's
    FLOPs (one entry of ``lives`` is one session stepped once)."""
    rows = len(lives)
    h, p, n = _widths(cfg)[:3]
    n_m = n_parts(cfg)[0]
    return (rows * n_m * 5.0 * h * p * n,
            2.0 * rows * n_m * ssd_state_values(cfg) * cfg["state_bytes"])


def expert_work(cfg: dict, lives: list, steps: int = 1,
                counters=None) -> tuple:
    """``(flops, bytes)`` of the routed experts' grouped products
    alone: the rows that fell here through ``d x e`` up and ``e x d``
    down, each touched expert's weights once."""
    touched, pairs = _routing(cfg, len(lives), steps, counters)
    return (2.0 * expert_params(cfg) * pairs,
            cfg["weight_bytes"] * touched * expert_params(cfg))
