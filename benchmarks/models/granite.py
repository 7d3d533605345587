"""The block ``"model": "granite"`` names (``harness/spec.py`` loads this
file by that key; see ``models/neox.py`` for what the harness calls).

The block, from the source's ``config.json`` (``model_type:
granitemoehybrid``).  ``x`` a token's float32 residual row, ``RMS(t; g)
= t g / sqrt(mean t^2 + rms_norm_eps)``, every norm with a learned gain,
no bias but the convolution's:

- ``x0 = embedding_multiplier * E[ids]`` (``E`` the tied table);
- layer ``l``: ``h = x + residual_multiplier * Mix_l(RMS(x))``, ``x' =
  h + residual_multiplier * (MoE(RMS(h)) + Shared(RMS(h)))``; ``Mix_l``
  is Mamba-2 where ``layer_types[l]`` is ``"mamba"``, attention where it
  is ``"attention"`` (the list is the source's, whole; its first
  ``num_hidden_layers`` entries are read); every layer has experts;
- after the last layer ``RMS``, logits ``= (h E^T) / logits_scaling``;
- attention: ``q = h W_q`` (``num_attention_heads`` heads of hidden /
  heads), ``k, v = h W_k, h W_v`` (``num_key_value_heads``, each shared
  by ``heads / kv_heads`` query heads), NOTHING rotated
  (``position_embedding_type: nope``), a causal softmax over ``q k^T *
  attention_multiplier``, out ``concat(heads) W_o``;
- Mamba-2 (``mamba_n_heads`` H heads of ``mamba_d_head`` P, ``d_inner =
  mamba_expand * hidden = H P``, a state of ``mamba_d_state`` N,
  ``mamba_n_groups`` G, ``mamba_d_conv`` taps): ``[z | xBC | dt] = h
  W_in``; ``xBC = silu(conv(xBC) + b_conv)`` (depthwise, causal);
  ``[x | B | C] = xBC``; ``dt_h = softplus(dt_h + dt_bias_h)``, ``A_h =
  -exp(A_log_h)``; with ``S_h`` a ``P x N`` float32 state from zero:
  ``S_h <- exp(dt_h A_h) S_h + dt_h x_h B^T``, ``y_h = S_h C + D_h x_h``;
  out ``RMS(y * silu(z); g_norm) W_out`` (the gated norm over all of
  ``d_inner``);
- experts: logits ``r = t W_r`` in float32 over ALL
  ``num_local_experts_published``; the ``num_experts_per_tok`` largest;
  weights the SOFTMAX OF THOSE LOGITS ALONE (the source's top-k gating);
  ``MoE(t) = sum_k w_k E_k(t)``, ``E(t) = (silu(t W_g) * (t W_u)) W_d``
  at ``intermediate_size``; ``Shared`` the same gated MLP at
  ``shared_intermediate_size``.  THIS chip holds experts ``0 ..
  num_local_experts - 1``: it routes over all of them and adds ``w_k
  E_k(t)`` only for chosen ``k`` it holds, in the program and in the
  reference alike.

Readings taken into the source (``assumed`` in the configuration file):
``intermediate_size`` is one routed expert's width; the gated norm has
one gain over all of ``d_inner``; ``A_log``, ``dt_bias``, ``D`` and the
convolution's bias as Mamba-2 initialises them.

The weights are in the PROGRAM'S tree (``brpc_tpu/models/
transformer_lm.py _init_block_params``): ``embed``, ``norm_f``, per
layer ``ln1 ln2``, a mixer, either ``in_proj conv_w conv_b dt_bias
a_log d norm out_proj`` (Mamba-2: ``in_proj`` holds z, xBC and dt side
by side) or ``wqkv wo``, and ``moe``: ``router w1 w2 ws1 ws2`` (gate and
up side by side in ``w1`` / ``ws1``).  Matrices are bfloat16, as the
source stores them and as the program serves them; norms, taps,
``a_log``, ``d``, ``dt_bias`` and the biases float32.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.models.neox import _matmul

# ---------------------------------------------------------------------------
# the system under test: weights from the seed, the program's service
# ---------------------------------------------------------------------------


def mixers(cfg: dict) -> tuple:
    """``"mamba2"`` or ``"attn"`` for each of the layers held here."""
    names = {"mamba": "mamba2", "attention": "attn"}
    return tuple(names[t] for t in
                 cfg["layer_types"][:cfg["num_hidden_layers"]])


def n_mixers(cfg: dict) -> tuple:
    """``(Mamba-2 layers, attention layers)``."""
    m = mixers(cfg)
    return m.count("mamba2"), m.count("attn")


def lm_kwargs(cfg: dict) -> dict:
    """The program's ``LMConfig`` arguments for a configuration file."""
    if cfg["position_embedding_type"] != "nope" or cfg["attention_bias"] \
            or cfg["mamba_proj_bias"] or not cfg["mamba_conv_bias"] \
            or cfg["hidden_act"] != "silu" \
            or cfg["normalization_function"] != "rmsnorm":
        raise ValueError("this block rotates nothing, has no bias but the "
                         "convolution's, SiLU gates and RMS norms")
    if cfg["mamba_expand"] * cfg["hidden_size"] \
            != cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
            or cfg["shared_intermediate_size"] % cfg["intermediate_size"]:
        raise ValueError("d_inner is mamba_n_heads x mamba_d_head, and the "
                         "shared MLP whole experts' widths")
    depth = cfg["num_hidden_layers"]
    e = cfg["intermediate_size"]
    return dict(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], depth=depth,
        max_seq=cfg["service"]["max_seq"], mixers=mixers(cfg), rope=False,
        ffn="gated_silu", ffn_dim=e, tie_embed=cfg["tie_word_embeddings"],
        final_norm=True, norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"], ffns=("experts",) * depth,
        expert_dim=e, experts_routed=cfg["num_local_experts_published"],
        experts_held=(0, cfg["num_local_experts"]),
        experts_top_k=cfg["num_experts_per_tok"],
        shared_experts=cfg["shared_intermediate_size"] // e,
        router_bias=False, router_scoring="softmax",
        mamba2_heads=cfg["mamba_n_heads"], mamba2_head_dim=cfg["mamba_d_head"],
        mamba2_state=cfg["mamba_d_state"], mamba2_groups=cfg["mamba_n_groups"],
        mamba2_conv=cfg["mamba_d_conv"], mamba2_chunk=cfg["mamba_chunk_size"],
        embed_scale=cfg["embedding_multiplier"],
        residual_scale=cfg["residual_multiplier"],
        attn_scale=cfg["attention_multiplier"],
        logit_scale=1.0 / cfg["logits_scaling"])


def make_params(cfg: dict, seed: int):
    """Seeded weights on the device as served: matrices normal at
    ``1/sqrt(fan_in)`` (the tied table at ``1 / (embedding_multiplier
    sqrt(hidden))``) rounded to bfloat16 ONCE; norms and ``D`` one,
    the convolution's bias zero, ``a_log`` the log of a rate uniform in
    1..16 a head, ``dt_bias`` with ``softplus(dt_bias)`` log-uniform in
    1e-3..1e-1, all float32 (``assumed`` in the configuration file).
    One compiled program a layer kind."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models.transformer_lm import LMConfig

    if not cfg["tie_word_embeddings"]:
        raise ValueError("this block's table is tied")
    # a program that does not know this block fails here, at once, and
    # not after 9.5 GB of weights have been made
    LMConfig(remat=False, **lm_kwargs(cfg))
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    g, kc = cfg["mamba_n_groups"], cfg["mamba_d_conv"]
    di, cw = h * p, h * p + 2 * g * n
    e, sh = cfg["intermediate_size"], cfg["shared_intermediate_size"]
    routed, held = cfg["num_local_experts_published"], \
        cfg["num_local_experts"]

    def f32(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

    def normal(k, shape, fan_in):
        return f32(k, shape, fan_in).astype(jnp.bfloat16)

    def mamba2(ks):
        dt = jnp.exp(jax.random.uniform(ks[3], (h,), jnp.float32)
                     * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return {"in_proj": normal(ks[0], (d, di + cw + h), d),
                "conv_w": f32(ks[1], (kc, cw), kc),
                "conv_b": jnp.zeros((cw,), jnp.float32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "a_log": jnp.log(jax.random.uniform(
                    ks[4], (h,), jnp.float32, 1.0, 16.0)),
                "d": jnp.ones((h,), jnp.float32),
                "norm": jnp.ones((di,), jnp.float32),
                "out_proj": normal(ks[2], (di, d), di)}

    def attn(ks):
        return {"wqkv": normal(ks[0], (d, (heads + 2 * kvh) * hd), d),
                "wo": normal(ks[1], (heads * hd, d), heads * hd)}

    def layer(mixer):
        def make(key):
            ks = jax.random.split(key, 10)
            return {**mixer(ks[:5]), "ln1": jnp.ones((d,), jnp.float32),
                    "ln2": jnp.ones((d,), jnp.float32), "moe": {
                        "router": normal(ks[5], (d, routed), d),
                        "w1": normal(ks[6], (held, d, 2 * e), d),
                        "w2": normal(ks[7], (held, e, d), e),
                        "ws1": normal(ks[8], (d, 2 * sh), d),
                        "ws2": normal(ks[9], (sh, d), sh)}}
        return jax.jit(make)

    makers = {"mamba2": layer(mamba2), "attn": layer(attn)}
    # --seed may pass 2**31: fold the high bits in instead of wrapping
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl="rbg"),
                             seed >> 31)
    ks = jax.random.split(key, 1 + cfg["num_hidden_layers"])
    # the tied table at 1 / (embedding_multiplier sqrt(hidden)): a row
    # times the multiplier is the unit-norm row every other block enters
    # with (``assumed`` in the configuration file says why)
    scale = cfg["embedding_multiplier"]
    params = {"embed": jax.jit(lambda k: (f32(k, (v, d), d) / scale).astype(
        jnp.bfloat16))(ks[0]),
              "norm_f": jnp.ones((d,), jnp.float32)}
    for i, m in enumerate(mixers(cfg)):
        params[f"blk{i}"] = makers[m](ks[1 + i])
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
# form the program's prompts take), the convolution a sum of four
# shifted copies; attention a full causal softmax over the whole
# context; the expert layer the source's top-k gating (a softmax of the
# chosen logits) and a plain loop over the held experts with a mask.
# No kernel, no cache, no pages, no slots, no state pool, no chunks.  It
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


def _gated(t, w1, w2, int8: bool):
    import jax
    import jax.numpy as jnp

    gate, up = jnp.split(_matmul(t, w1, int8), 2, axis=-1)
    return _matmul(jax.nn.silu(gate) * up, w2, int8)


def route(t, mp, cfg: dict):
    """``(ids (s, k), w (s, k))``: the ``k`` largest logits over ALL
    published experts, weighed by the softmax of those ``k`` logits."""
    import jax
    import jax.numpy as jnp

    logits = t @ mp["router"]
    ids = jnp.argsort(-logits, axis=-1,
                      stable=True)[:, :cfg["num_experts_per_tok"]]
    return ids, jax.nn.softmax(jnp.take_along_axis(logits, ids, axis=-1),
                               axis=-1)


def _experts(t, mp, cfg: dict, int8: bool, held=None):
    """The expert layer's output for normed rows ``t``: the held
    experts' weighted part and the shared MLP.  ``held`` (a range of
    expert ids whose weights are ``mp["w1"]``'s rows in order) defaults
    to ``0 .. num_local_experts - 1``."""
    import jax.numpy as jnp

    ids, w = route(t, mp, cfg)
    lo, hi = held if held is not None else (0, cfg["num_local_experts"])
    out = _gated(t, mp["ws1"], mp["ws2"], int8)
    for e in range(lo, hi):
        w_e = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
        out = out + w_e[:, None] * _gated(t, mp["w1"][e - lo],
                                          mp["w2"][e - lo], int8)
    return out


def _mamba2(t, bp, cfg: dict, int8: bool):
    """Mamba-2 over one sequence ``t`` of (s, hidden), normed, from the
    zero state: a scan over the positions."""
    import jax
    import jax.numpy as jnp

    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    g, kc = cfg["mamba_n_groups"], cfg["mamba_d_conv"]
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
    y = _norm(y.reshape(s, di) * jax.nn.silu(z), bp["norm"],
              cfg["rms_norm_eps"])
    return _matmul(y, bp["out_proj"], int8)


def _attention(t, bp, cfg: dict, int8: bool):
    """Grouped attention over one sequence ``t`` of (s, hidden), normed:
    nothing rotated, the softmax over ``q k^T * attention_multiplier``."""
    import jax
    import jax.numpy as jnp

    s, d = t.shape
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    q, k, v = jnp.split(_matmul(t, bp["wqkv"], int8),
                        [heads * hd, (heads + kvh) * hd], axis=-1)
    q = q.reshape(s, heads, hd)
    k = jnp.repeat(k.reshape(s, kvh, hd), heads // kvh, axis=1)
    v = jnp.repeat(v.reshape(s, kvh, hd), heads // kvh, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * cfg["attention_multiplier"]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    att = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return _matmul(jnp.einsum("hqk,khd->qhd", att, v).reshape(s, d),
                   bp["wo"], int8)


def _layer(x, bp, cfg: dict, int8: bool):
    """One layer over one sequence ``x`` of (s, hidden): which mixer it
    has is read off its leaves."""
    bp = _f32(bp)
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mixer = _mamba2 if "a_log" in bp else _attention
    x = x + r * mixer(_norm(x, bp["ln1"], eps), bp, cfg, int8)
    return x + r * _experts(_norm(x, bp["ln2"], eps), bp["moe"], cfg, int8)


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
            _norm(x, g, eps), w.astype(jnp.float32).T, int8)
            / cfg["logits_scaling"])

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
            x = self.params["embed"][jnp.asarray(ids)].astype(jnp.float32) \
                * self.cfg["embedding_multiplier"]
            for i in range(self.cfg["num_hidden_layers"]):
                x = self._layer(x, self.params[f"blk{i}"])
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
# As in ``models/kimi_linear.py``: whatever implements a step, these do
# not change.  Per step the DENSE weights (both mixers, the routers, the
# shared MLPs, the table as the head) are read once at ``weight_bytes``
# a parameter; of the routed experts held here only those TOUCHED by a
# row (from the program's own counts where the reader can pass them,
# else the expectation); the live keys and values of the ATTENTION
# layers once at ``kv_cache_bytes`` a value; and each stepped session's
# Mamba-2 state (the matrices and the convolution's ring) once in and
# once out at ``state_bytes`` a value, every Mamba-2 layer.  The
# table's lookup is a gather and counts no FLOP.


def _widths(cfg: dict) -> tuple:
    """``(H, P, N, G, conv taps, d_inner, conv channels)``."""
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    g = cfg["mamba_n_groups"]
    return h, p, n, g, cfg["mamba_d_conv"], h * p, h * p + 2 * g * n


def mamba2_params(cfg: dict) -> int:
    """One Mamba-2 mixer: ``W_in``, the taps and their bias, ``A_log``,
    ``D``, ``dt_bias``, the gated norm's gain, ``W_out``."""
    h, _p, _n, _g, kc, di, cw = _widths(cfg)
    d = cfg["hidden_size"]
    return d * (di + cw + h) + (kc + 1) * cw + 3 * h + di + di * d


def attn_params(cfg: dict) -> int:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = d // heads
    return d * (heads + 2 * cfg["num_key_value_heads"]) * hd + heads * hd * d


def expert_params(cfg: dict) -> int:
    """One routed expert's gated MLP."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["num_local_experts_published"]


def shared_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def layer_params(cfg: dict, mixer: str) -> int:
    """One layer as held here but its routed experts: the mixer, the two
    norms, the router and the shared MLP."""
    return (mamba2_params(cfg) if mixer == "mamba2" else attn_params(cfg)) \
        + 2 * cfg["hidden_size"] + router_params(cfg) + shared_params(cfg)


def total_params(cfg: dict) -> int:
    """What is held here: the layers, the tied table, the final norm."""
    d = cfg["hidden_size"]
    return (sum(layer_params(cfg, m) for m in mixers(cfg))
            + cfg["num_hidden_layers"] * cfg["num_local_experts"]
            * expert_params(cfg) + d * cfg["vocab_size"] + d)


def dense_step_params(cfg: dict) -> int:
    """Parameters every step reads whatever was routed: all but the
    routed experts."""
    return total_params(cfg) - cfg["num_hidden_layers"] \
        * cfg["num_local_experts"] * expert_params(cfg)


def expected_touched(cfg: dict, rows: float) -> float:
    """Held experts with at least one of ``rows`` tokens, one layer:
    a token misses a given expert with ``1 - k/routed``."""
    miss = 1.0 - cfg["num_experts_per_tok"] \
        / cfg["num_local_experts_published"]
    return cfg["num_local_experts"] * (1.0 - miss ** rows)


def expected_local_pairs(cfg: dict, rows: float) -> float:
    return rows * cfg["num_experts_per_tok"] * cfg["num_local_experts"] \
        / cfg["num_local_experts_published"]


def _routing(cfg: dict, rows: int, steps: int, counters) -> tuple:
    """``(experts touched, local pairs)`` summed over expert layers and
    ``steps`` steps of ``rows`` tokens in all: the program's counts
    (``kv_stats()["moe"]`` deltas) or the expectation."""
    if counters:
        return float(counters["experts_touched"]), \
            float(counters["local_pairs"])
    layers = cfg["num_hidden_layers"]
    return (layers * steps * expected_touched(cfg, rows / max(steps, 1)),
            layers * expected_local_pairs(cfg, rows))


def kv_row_bytes(cfg: dict) -> int:
    """One token's keys and values in every ATTENTION layer."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return n_mixers(cfg)[1] * 2 * cfg["num_key_value_heads"] \
        * (d // heads) * cfg["kv_cache_bytes"]


def ssd_state_values(cfg: dict) -> int:
    """Values of one session's SSD state in one Mamba-2 layer."""
    h, p, n = _widths(cfg)[:3]
    return h * p * n


def state_values(cfg: dict) -> int:
    """Values one session keeps in one Mamba-2 layer: the SSD state and
    the convolution's last ``d_conv - 1`` inputs."""
    kc, cw = _widths(cfg)[4], _widths(cfg)[6]
    return ssd_state_values(cfg) + (kc - 1) * cw


def state_bytes(cfg: dict, sessions: float) -> float:
    """``sessions`` sessions' state in every Mamba-2 layer, once."""
    return float(n_mixers(cfg)[0] * state_values(cfg)
                 * cfg["state_bytes"]) * sessions


def ssd_update_flops(cfg: dict) -> float:
    """One position of one Mamba-2 layer's recurrence: a head's decay,
    the rank-one write ``(dt x) B^T``, ``S C`` and ``D x`` are 5 P N
    (and a few P); the taps on the convolution's channels."""
    h, p, n, _g, kc, _di, cw = _widths(cfg)
    return 5.0 * h * p * n + 2.0 * kc * cw


def ssd_chunk_flops(cfg: dict, n_pos: int) -> float:
    """The chunked form over ``n_pos`` positions (chunks of
    ``mamba_chunk_size``), one layer: ``C B^T`` a chunk, the masked
    quadratic form, the start state's part of each output and the
    state the chunk leaves (each a product of its widths), the taps."""
    h, p, n, g, kc, _di, cw = _widths(cfg)
    w = min(cfg["mamba_chunk_size"], n_pos)
    chunks = -(-n_pos // w)
    per_chunk = 2.0 * w * w * g * n + 2.0 * h * w * w * p \
        + 2.0 * 2.0 * w * h * p * n
    return chunks * per_chunk + 2.0 * kc * cw * n_pos


def attention_flops(cfg: dict, live: float) -> float:
    """One token's attention over ``live`` rows, one attention layer:
    scores and the weighted sum over every query head."""
    return 4.0 * cfg["hidden_size"] * live


def token_dense_flops(cfg: dict, unembed: bool = True) -> float:
    """One token's matmuls outside attention's own, the recurrence and
    the routed experts."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return 2.0 * (dense_step_params(cfg) - d * v - d) \
        + (2.0 * d * v if unembed else 0.0)


def step_work(cfg: dict, lives: list, steps: int = 1,
              counters=None) -> tuple:
    """``(flops, bytes)`` of ``steps`` decode steps that between them
    produce one token for each entry of ``lives`` (the positions that
    token attends over in an attention layer, itself included)."""
    rows = len(lives)
    n_mamba, n_attn = n_mixers(cfg)
    touched, pairs = _routing(cfg, rows, steps, counters)
    flops = (rows * (token_dense_flops(cfg) + n_mamba * ssd_update_flops(cfg))
             + n_attn * attention_flops(cfg, sum(lives))
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
    n_mamba, n_attn = n_mixers(cfg)
    lives = n * start + n * (n + 1) / 2.0
    touched, pairs = _routing(cfg, n, 1, None)
    flops = (n * token_dense_flops(cfg, unembed=False)
             + n_mamba * ssd_chunk_flops(cfg, n)
             + n_attn * attention_flops(cfg, lives)
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
    layer, the grouped expert product twice a layer."""
    n_mamba, n_attn = n_mixers(cfg)
    return {"ssd_step": n_mamba, "paged_decode_attention": n_attn,
            "expert_gmm": 2 * cfg["num_hidden_layers"]}[kernel]


def ssd_step_work(cfg: dict, lives: list, steps: int = 1,
                  counters=None) -> tuple:
    """``(flops, bytes)`` of the SSD update alone, the Mamba-2 layers:
    each stepped session's ``H x P x N`` state once in and once out (the
    convolution's ring is written outside the kernel), the update's
    FLOPs (one entry of ``lives`` is one session stepped once)."""
    rows = len(lives)
    h, p, n = _widths(cfg)[:3]
    n_mamba = n_mixers(cfg)[0]
    return (rows * n_mamba * 5.0 * h * p * n,
            2.0 * rows * n_mamba * ssd_state_values(cfg) * cfg["state_bytes"])


def expert_work(cfg: dict, lives: list, steps: int = 1,
                counters=None) -> tuple:
    """``(flops, bytes)`` of the routed experts' grouped products
    alone: the rows that fell here through a gated MLP, each touched
    expert's weights once."""
    touched, pairs = _routing(cfg, len(lives), steps, counters)
    return (2.0 * expert_params(cfg) * pairs,
            cfg["weight_bytes"] * touched * expert_params(cfg))
