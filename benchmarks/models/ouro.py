"""The block ``"model": "ouro"`` names (``harness/spec.py`` loads this
file by that key; see ``models/neox.py`` for what the harness calls).

The block, from the source's ``config.json`` (``model_type: ouro``) and
the published description ("Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741).  ``x`` a token's residual row;
``RMS(t; g) = t g / sqrt(mean(t^2) + rms_norm_eps)``; no bias on any
projection, no norm on ``q`` or ``k``.  ALL weights are shared by the
passes.  For pass ``t = 1 .. total_ut_steps``, layer ``l = 1 ..
num_hidden_layers``, position ``p``:

- ``a = RMS(x; g1_l)``; ``q, k, v = a W_q, a W_k, a W_v``
  (``num_attention_heads`` heads of ``head_dim``, as many key/value
  heads); ``q`` and ``k`` rotated over the whole head, column ``i``
  paired with ``i + head_dim / 2``, by ``p * rope_theta^(-2i /
  head_dim)``; ``k, v`` belong to ``(t, l, p)``; ``o = softmax(q k_j /
  sqrt(head_dim)) v_j`` over ``j <= p`` of the SAME pass and layer;
- ``h = x + RMS(o W_o; g2_l)``; ``m = RMS(h; g3_l)``; ``y = h +
  RMS(W_d (silu(W_g m) * W_u m); g4_l)``; ``y`` is layer ``l + 1``'s
  ``x``;
- after the last layer of pass ``t``: ``x <- RMS(y; g_f)``, the ONE
  final norm, and that row enters layer 1 of pass ``t + 1``; the row
  unembedded is a pass's normed row, ``logits = x W_head``
  (``tie_word_embeddings`` false);
- exit gate: ``lam_t = sigmoid(x_t w_e + b_e)`` on each pass's normed
  row, ``p_t = lam_t prod_{s<t} (1 - lam_s)``, the last pass takes the
  rest; the row unembedded is that of the FIRST pass whose cumulated
  ``p`` reaches ``early_exit_threshold``.  The config states 1: the
  last pass, always, and all of them run.

Read into the source (``assumed`` in the configuration file): the two
norms AFTER attention and feed-forward (``g2``, ``g4``: the
"sandwich"), the final norm applied after EVERY pass, keys and values
kept per pass (not shared or averaged between passes), no bias and no
q/k norm (the config has no key for either), the gate's form.

The weights are in the PROGRAM'S tree (``brpc_tpu/models/
transformer_lm.py _init_block_params``): ``embed``, ``unembed``,
``norm_f`` (``g_f``), ``exit_w`` / ``exit_b`` (the gate), per layer
``ln1`` (``g1``) ``pn1`` (``g2``) ``ln2`` (``g3``) ``pn2`` (``g4``)
``wqkv`` (``W_q W_k W_v`` side by side) ``wo`` ``w1`` (``W_g W_u`` side
by side) ``w2`` (``W_d``).  Matrices are bfloat16, as the source stores
them and as the program serves them; gains and the gate float32.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.models.neox import _matmul

# ---------------------------------------------------------------------------
# the system under test: weights from the seed, the program's service
# ---------------------------------------------------------------------------


def lm_kwargs(cfg: dict) -> dict:
    """The program's ``LMConfig`` arguments for a configuration file."""
    if set(cfg["layer_types"][:cfg["num_hidden_layers"]]) \
            != {"full_attention"} or cfg["sliding_window"] is not None \
            or cfg["use_sliding_window"] or cfg["rope_scaling"] is not None \
            or cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"] \
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("this block is the looped one: full attention "
                         "over whole heads, plain rotary, a gated SiLU "
                         "feed-forward, an untied head")
    svc = cfg["service"]
    return dict(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        depth=cfg["num_hidden_layers"], max_seq=svc["max_seq"],
        fill_span=svc["fill_span"], norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"], ffn="gated_silu",
        ffn_dim=cfg["intermediate_size"], final_norm=True,
        post_norms=True, passes=cfg["total_ut_steps"],
        exit_threshold=cfg["early_exit_threshold"])


def make_params(cfg: dict, seed: int):
    """Seeded weights on the device as served: matrices normal at
    ``1/sqrt(fan_in)`` rounded to bfloat16 ONCE, gains one in float32,
    the gate's weight normal at ``1/sqrt(hidden)`` and its bias 0 in
    float32.  One compiled program for the layers."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models.transformer_lm import LMConfig

    # a program that does not know this block fails here, at once, and
    # not after 5 GB of weights have been made
    LMConfig(remat=False, **lm_kwargs(cfg))
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    hw = cfg["num_attention_heads"] * cfg["head_dim"]

    def normal(k, shape, fan_in, dtype=jnp.bfloat16):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    @jax.jit
    def layer(key):
        ks = jax.random.split(key, 4)
        one = jnp.ones((d,), jnp.float32)
        return {"ln1": one, "pn1": one, "ln2": one, "pn2": one,
                "wqkv": normal(ks[0], (d, 3 * hw), d),
                "wo": normal(ks[1], (hw, d), hw),
                "w1": normal(ks[2], (d, 2 * f), d),
                "w2": normal(ks[3], (f, d), f)}

    # --seed may pass 2**31: fold the high bits in instead of wrapping
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl="rbg"),
                             seed >> 31)
    ks = jax.random.split(key, 3 + cfg["num_hidden_layers"])
    params = {
        "embed": jax.jit(lambda k: normal(k, (v, d), d))(ks[0]),
        "unembed": jax.jit(lambda k: normal(k, (d, v), d))(ks[1]),
        "norm_f": jnp.ones((d,), jnp.float32),
        "exit_w": normal(ks[2], (d,), d, jnp.float32),
        "exit_b": jnp.zeros((), jnp.float32)}
    for i in range(cfg["num_hidden_layers"]):
        params[f"blk{i}"] = layer(ks[3 + i])
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
# precision: one request at a time, one layer at a time, no kernel, no
# cache, no pages, no loop in a program: the passes are as many full
# forwards over the prompt and the served tokens, each from the normed
# rows the one before left.  The exit rule is evaluated and the pass it
# picks for each row is the one unembedded.  It imports nothing of the
# program.  The control is the same with every weight matmul computed
# from int8 operands (``models/neox.py``'s ``_matmul``); the gate, a
# float32 vector, stays float32 in both.


def _rms(t, g, eps: float):
    import jax.numpy as jnp

    return t * g / jnp.sqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)


def _layer(x, bp, cfg: dict, int8: bool):
    """One layer of one pass over one sequence ``x`` of (s, hidden)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps, hd = cfg["rms_norm_eps"], cfg["head_dim"]
    heads, half = cfg["num_attention_heads"], cfg["head_dim"] // 2
    s = x.shape[0]

    def mm(t, name):
        return _matmul(t, bp[name].astype(f32), int8)

    q, k, v = jnp.split(mm(_rms(x, bp["ln1"], eps), "wqkv"), 3, axis=-1)
    pos = jnp.arange(s, dtype=f32)[:, None, None]
    freq = jnp.exp(-math.log(cfg["rope_theta"])
                   * jnp.arange(half, dtype=f32) / half)
    sin, cos = jnp.sin(pos * freq), jnp.cos(pos * freq)

    def rope(t):
        t = t.reshape(s, heads, hd)
        a, b = t[..., :half], t[..., half:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    q, k, v = rope(q), rope(k), v.reshape(s, heads, hd)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    att = jnp.einsum("hqk,khd->qhd", p, v).reshape(s, heads * hd)
    h = x + _rms(mm(att, "wo"), bp["pn1"], eps)
    gate, up = jnp.split(mm(_rms(h, bp["ln2"], eps), "w1"), 2, axis=-1)
    return h + _rms(mm(jax.nn.silu(gate) * up, "w2"), bp["pn2"], eps)


def exit_pass(lams, threshold: float) -> np.ndarray:
    """The pass each row leaves at, 0-based: ``lams (passes, rows)`` the
    gate's ``lam_t``; ``p_t = lam_t prod_{s<t} (1 - lam_s)``, the last
    pass takes the rest, and a row leaves at the first pass whose
    cumulated ``p`` reaches ``threshold`` (the last where none does:
    its cumulated share is the whole by definition, whatever the
    rounding)."""
    lams = np.asarray(lams, np.float64)
    stay = np.cumprod(1.0 - lams, axis=0)
    p = lams * np.concatenate([np.ones_like(stay[:1]), stay[:-1]])
    reached = np.cumsum(p, axis=0) >= threshold
    reached[-1] = True
    return reached.argmax(axis=0)


class Reference:
    """Holds the weights (the benchmark's own, made from the seed) and
    three compiled functions: one layer, a pass's end (the final norm
    and the gate), and the unembedding of the rows that were served.
    ``exits`` keeps the pass the exit rule picked for each row of the
    last call."""

    def __init__(self, cfg: dict, params, int8: bool = False):
        import jax
        import jax.numpy as jnp

        self.cfg, self.params, self.int8 = cfg, params, int8
        self.exits = None
        eps = cfg["rms_norm_eps"]
        self._layer = jax.jit(lambda x, bp: _layer(x, bp, cfg, int8))

        def pass_end(y, g, w, b):
            x = _rms(y, g, eps)
            return x, jax.nn.sigmoid(x @ w + b)

        self._pass_end = jax.jit(pass_end)
        self._unembed = jax.jit(
            lambda x, w: _matmul(x, w.astype(jnp.float32), int8))

    def passes(self, ids):
        """Every pass's normed rows and gate over the sequence ``ids``:
        ``(rows (passes, s, hidden), lams (passes, s))``."""
        import jax.numpy as jnp

        p = self.params
        x = p["embed"][jnp.asarray(ids)].astype(jnp.float32)
        rows, lams = [], []
        for _t in range(self.cfg["total_ut_steps"]):
            for i in range(self.cfg["num_hidden_layers"]):
                x = self._layer(x, p[f"blk{i}"])
            x, lam = self._pass_end(x, p["norm_f"], p["exit_w"],
                                    p["exit_b"])
            rows.append(x)
            lams.append(lam)
        return jnp.stack(rows), jnp.stack(lams)

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
            rows, lams = self.passes(ids)
            first = len(prompt) - 1
            self.exits = exit_pass(np.asarray(lams)[:, first:n],
                                   self.cfg["early_exit_threshold"])
            rows = rows[jnp.asarray(self.exits), first + jnp.arange(n - first)]
            if rows.shape[0] % 128:    # one compiled shape a sample
                rows = jnp.pad(rows, ((0, 128 - rows.shape[0] % 128), (0, 0)))
            out = self._unembed(rows, self.params["unembed"])
        return np.asarray(out)[:len(served)]


# ---------------------------------------------------------------------------
# operations and bytes the MODEL needs, from the configuration's shapes
# ---------------------------------------------------------------------------
#
# As in ``models/neox.py``: whatever implements a step, these do not
# change.  The layers' weights are read once a PASS at ``weight_bytes``
# a parameter (a pass needs all of them before the next can start; a
# chip keeps none of 4.9 GB between passes), the head once a step; each
# live token's keys and values once a (pass, layer) at
# ``kv_cache_bytes`` a value; attention over live lengths.  The table's
# lookup is a gather and counts no FLOP; the gate is not evaluated
# where the threshold is 1.


def layer_matmul_params(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hw = cfg["num_attention_heads"] * cfg["head_dim"]
    return d * 3 * hw + hw * d + d * 2 * f + f * d     # wqkv wo w1 w2


def pass_params(cfg: dict) -> int:
    """Matrix parameters one pass multiplies a token by."""
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg)


def table_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def total_params(cfg: dict) -> int:
    """Everything stored: the layers with their four gains, the table
    and the head, the final norm and the gate."""
    d = cfg["hidden_size"]
    return (pass_params(cfg) + cfg["num_hidden_layers"] * 4 * d
            + 2 * table_params(cfg) + d + d + 1)


def layer_bodies(cfg: dict) -> int:
    """Layer bodies a token runs: passes x layers."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def token_kv_bytes(cfg: dict) -> int:
    """Keys and values one token pins: a row a (pass, layer)."""
    return layer_bodies(cfg) * 2 * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * cfg["kv_cache_bytes"]


def attention_flops(cfg: dict) -> float:
    """Scores and weighted sum of one query token over one key row, in
    one (pass, layer)."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]


def step_weight_bytes(cfg: dict) -> float:
    """Weights one step reads: the layers once a pass, the head once."""
    return cfg["weight_bytes"] * (cfg["total_ut_steps"] * pass_params(cfg)
                                  + table_params(cfg))


def step_work(cfg: dict, lives: list, steps: int = 1) -> tuple:
    """``(flops, bytes)`` of ``steps`` decode steps that between them
    produce one token for each entry of ``lives`` (the positions that
    token attends over, itself included)."""
    rows, reach = len(lives), float(sum(lives))
    flops = (2.0 * rows * (cfg["total_ut_steps"] * pass_params(cfg)
                           + table_params(cfg))
             + layer_bodies(cfg) * attention_flops(cfg) * reach)
    nbytes = steps * step_weight_bytes(cfg) \
        + token_kv_bytes(cfg) * (reach + rows)
    return flops, nbytes


def fill_work(cfg: dict, start: int, n: int) -> tuple:
    """``(flops, bytes)`` of filling ``n`` positions of a context from
    ``start``: the layers' weights once a pass (no head: a fill
    unembeds nothing), the ``start`` cached positions read and ``n``
    written in every (pass, layer)."""
    if n <= 0:
        return 0.0, 0.0
    lives = n * start + n * (n + 1) / 2.0
    flops = (2.0 * n * cfg["total_ut_steps"] * pass_params(cfg)
             + layer_bodies(cfg) * attention_flops(cfg) * lives)
    nbytes = (cfg["weight_bytes"] * cfg["total_ut_steps"] * pass_params(cfg)
              + token_kv_bytes(cfg) * (start + n))
    return flops, nbytes


# -- the step kernel's own counts (readers/step_kernel_work.py) ---------------


def kernel_calls(cfg: dict, kernel: str) -> int:
    """Calls of the named kernel in one execution of the step: the
    whole-head decode kernel once a (pass, layer)."""
    return {"paged_decode_attention": layer_bodies(cfg)}[kernel]


def paged_attn_work(cfg: dict, lives: list, steps: int = 1,
                    counters=None) -> tuple:
    """``(flops, bytes)`` of the decode attention alone: each live
    token's keys and values read once a (pass, layer), scores and
    weighted sum over them."""
    reach = float(sum(lives))
    return (layer_bodies(cfg) * attention_flops(cfg) * reach,
            token_kv_bytes(cfg) * reach)
