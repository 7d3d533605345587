"""The block ``"model": "command_a"`` names (``harness/spec.py`` loads
this file by that key; see ``models/neox.py`` for what the harness
calls).

The block, from the source's ``config.json`` (``model_type:
cohere2_moe``) and the catalog's ``described_as``.  ``x`` a token's
residual row; ``LN(t; g) = (t - mean t) g / sqrt(var t + layer_norm_eps)``
with a learned gain and NO bias; no bias anywhere, no q/k norm.  Layer
``i`` is ``layer_types[i]``: three ``sliding_attention`` then one
``full_attention``, eight times in the source, once here.  One norm a
layer and the PARALLEL block (``use_parallel_block``):

- ``h = LN(x)``; ``q = h W_q`` (hidden -> heads x ``head_dim``), ``k =
  h W_k``, ``v = h W_v`` (hidden -> key/value heads x ``head_dim``);
- a sliding layer rotates ``q`` and ``k`` over all ``head_dim`` columns
  (``rotary_pct`` 1) at the token's position ``p``: pair ``(2i, 2i+1)``
  turns by ``p * rope_theta^(-2i/head_dim)`` (``rope_gptj``), and row
  ``p`` attends ``p - sliding_window < j <= p``; a full layer rotates
  NOTHING (no positional term) and attends every ``j <= p``.  Both:
  ``heads / kv_heads`` query heads on each key/value head, scores times
  ``head_dim^-0.5``, softmax in float32;
- ``a = concat(heads) W_o``;
- the feed-forward reads the SAME ``h``: ``s = sigmoid(h W_r)`` in
  float32 over ALL ``num_experts_published`` experts (no correction
  bias, no scale), the ``num_experts_per_tok`` largest chosen, ``w_k =
  s_k / sum of the chosen s``; every expert the gated MLP ``E(t) =
  (silu(t W_g) * (t W_u)) W_d`` at ``intermediate_size``; ``m = sum_k
  w_k E_k(h) + (1 / num_shared_experts) sum_s S_s(h)``.  THIS chip
  holds experts ``0 .. num_experts - 1``: it routes over all of them,
  normalises over all the chosen, and adds ``w_k E_k(h)`` only for
  chosen ``k`` it holds; the shared experts whole.  That partial sum
  goes on, in the program and in the reference alike;
- ``x' = x + a + m``.  After the last layer ``LN``, logits ``=
  logit_scale * h E^T`` on the tied table (``logit_scale`` 1).

Read into the source (``assumed`` in the configuration file): the
window's edge (``j > p - sliding_window``, the Cohere2 family's);
``shared_expert_combination_strategy: average`` as the mean of the
shared experts added to the routed sum; the expert width
(``intermediate_size``, as the catalog notes); global layers without
rotation (``described_as``: "global NoPE"); the pairing of rotary
columns (a column order of seeded weights).

The weights are in the PROGRAM'S tree (``brpc_tpu/models/
transformer_lm.py _init_block_params``): ``embed``, ``norm_f``, per
layer ``ln1 wqkv wo moe``; ``wqkv`` holds ``W_q W_k W_v`` side by side,
``moe`` is ``router w1 w2 ws1 ws2``: ``w1`` the held experts' ``[W_g
W_u]`` stacked, ``w2`` their ``W_d``, ``ws1`` the shared experts'
gates side by side, then their ups, ``ws2`` their downs stacked.
Matrices are bfloat16, as the source stores them and as the program
serves them; norms float32.  The tied table is seeded at
``table_std_scale`` times the other matrices' ``1/sqrt(fan_in)``
(``assumed.table_std_scale`` in the configuration file: at 1, with 4
of 32 layers, a token's own row wins the tied head and greedy decoding
repeats one token).
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.models.neox import _matmul

# ---------------------------------------------------------------------------
# the system under test: weights from the seed, the program's service
# ---------------------------------------------------------------------------


def layer_kinds(cfg: dict) -> list:
    """``layer_types`` of the layers held here (the source's list is
    kept whole in the file)."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    if set(kinds) - {"sliding_attention", "full_attention"}:
        raise ValueError("layer_types of another kind")
    return kinds


def n_layers(cfg: dict) -> tuple:
    """``(sliding layers, full layers)``."""
    kinds = layer_kinds(cfg)
    n_win = kinds.count("sliding_attention")
    return n_win, len(kinds) - n_win


def lm_kwargs(cfg: dict) -> dict:
    """The program's ``LMConfig`` arguments for a configuration file."""
    if cfg["expert_selection_fn"] != "sigmoid" or not cfg["norm_topk_prob"] \
            or cfg["first_k_dense_replace"] != 0 or cfg["logit_scale"] != 1 \
            or cfg["shared_expert_combination_strategy"] != "average" \
            or cfg["position_embedding_type"] != "rope_gptj" \
            or cfg["rotary_pct"] != 1 or not cfg["use_parallel_block"] \
            or cfg["use_qk_norm"] or cfg["attention_bias"] \
            or not cfg["tie_word_embeddings"]:
        raise ValueError("this block is the parallel one: sigmoid "
                         "routing renormalised, shared experts averaged, "
                         "full interleaved rotary on window layers, a "
                         "tied table")
    sliding = [k == "sliding_attention" for k in layer_kinds(cfg)]
    depth, svc = cfg["num_hidden_layers"], cfg["service"]
    return dict(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        depth=depth, max_seq=svc["max_seq"], fill_span=svc["fill_span"],
        norm="layer", norm_eps=cfg["layer_norm_eps"], parallel_block=True,
        windows=tuple(cfg["sliding_window"] * s for s in sliding),
        ropes=tuple(sliding), rope_pairs="interleaved",
        rope_theta=cfg["rope_theta"],
        ffn="gated_silu", ffn_dim=cfg["intermediate_size"],
        tie_embed=True, final_norm=True,
        ffns=("experts",) * depth, expert_dim=cfg["intermediate_size"],
        experts_routed=cfg["num_experts_published"],
        experts_held=(0, cfg["num_experts"]),
        experts_top_k=cfg["num_experts_per_tok"], route_scale=1.0,
        shared_experts=cfg["num_shared_experts"], shared_average=True,
        router_bias=False)


def make_params(cfg: dict, seed: int):
    """Seeded weights on the device as served: matrices normal at
    ``1/sqrt(fan_in)`` (the tied table at ``table_std_scale`` times
    that) rounded to bfloat16 ONCE, norm gains one in float32.  One
    compiled program for the layers."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models.transformer_lm import LMConfig

    # a program that does not know this block fails here, at once, and
    # not after 9 GB of weights have been made
    LMConfig(remat=False, **lm_kwargs(cfg))
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, sh = cfg["intermediate_size"], cfg["num_shared_experts"]
    routed, held = cfg["num_experts_published"], cfg["num_experts"]

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(jnp.bfloat16)

    @jax.jit
    def layer(key):
        ks = jax.random.split(key, 7)
        return {"ln1": jnp.ones((d,), jnp.float32),
                "wqkv": normal(ks[0], (d, (h + 2 * kvh) * hd), d),
                "wo": normal(ks[1], (h * hd, d), h * hd),
                "moe": {"router": normal(ks[2], (d, routed), d),
                        "w1": normal(ks[3], (held, d, 2 * e), d),
                        "w2": normal(ks[4], (held, e, d), e),
                        "ws1": normal(ks[5], (d, 2 * sh * e), d),
                        "ws2": normal(ks[6], (sh * e, d), e)}}

    # --seed may pass 2**31: fold the high bits in instead of wrapping
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl="rbg"),
                             seed >> 31)
    ks = jax.random.split(key, 1 + cfg["num_hidden_layers"])
    # the tied table at ``table_std_scale / sqrt(hidden)`` (``assumed``
    # in the configuration file says why it is not 1)
    scale = cfg["table_std_scale"]
    params = {"embed": jax.jit(
        lambda k: normal(k, (v, d), d / scale ** 2))(ks[0]),
        "norm_f": jnp.ones((d,), jnp.float32)}
    for i in range(cfg["num_hidden_layers"]):
        params[f"blk{i}"] = layer(ks[1 + i])
    return params


def make_service(cfg: dict, params):
    """The program's paged ``LMService`` for this configuration, with
    the configuration file's ``service`` settings.  The window class's
    pages follow from the schedule (``LMConfig.window_pages``); the
    file states the number, and it has to be the program's."""
    from brpc_tpu.models.lm_service import LMService
    from brpc_tpu.models.transformer_lm import LMConfig

    svc = cfg["service"]
    lm = LMConfig(remat=False, **lm_kwargs(cfg))
    if lm.window_pages(svc["decode_slots"], svc["page"]) \
            != svc["window_pages"]:
        raise ValueError("service.window_pages is not what the program "
                         "sizes the window class to")
    return LMService(
        cfg=lm, params=params, paged=True, page=svc["page"],
        decode_slots=svc["decode_slots"], kv_pages=svc["kv_pages"],
        max_new_cap=svc["max_new_cap"])


# ---------------------------------------------------------------------------
# the plain reference, and the control
# ---------------------------------------------------------------------------
#
# Straight ``jax.numpy`` in float32 with every matmul at ``highest``
# precision: one request at a time, one layer at a time, attention as a
# masked softmax over the whole context taken a block of query rows at
# a time (so that 13,056 rows fit), the expert layer a plain loop over
# the held experts with a mask, each shared expert on its own.  No
# kernel, no cache, no pages, no spans, no sort.  It imports nothing of
# the program and is given the same share of the experts.  The control
# is the same with every weight matmul computed from int8 operands
# (``models/neox.py``'s ``_matmul``); the router, whose choice the
# model's mathematics keeps in float32, stays float32 in both.

_Q_BLOCK = 128         # query rows a block of the reference's attention


def _ln(t, g, eps: float):
    import jax.numpy as jnp

    c = t - jnp.mean(t, axis=-1, keepdims=True)
    return c * g / jnp.sqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps)


def _gated(t, wg, wu, wd, int8: bool):
    import jax

    return _matmul(jax.nn.silu(_matmul(t, wg, int8)) * _matmul(t, wu, int8),
                   wd, int8)


def route(t, router, cfg: dict):
    """``(ids (s, k), w (s, k))`` over ALL published experts."""
    import jax
    import jax.numpy as jnp

    sc = jax.nn.sigmoid(t @ router)
    ids = jnp.argsort(-sc, axis=-1, stable=True)[
        :, :cfg["num_experts_per_tok"]]
    w = jnp.take_along_axis(sc, ids, axis=-1)
    return ids, w / w.sum(axis=-1, keepdims=True)


def _experts(t, mp, cfg: dict, int8: bool, held=None):
    """The feed-forward's output for normed rows ``t``: the held
    experts' weighted part and the mean of the shared experts.
    ``held`` (a range of expert ids whose weights are ``mp["w1"]``'s
    rows in order) defaults to ``0 .. num_experts - 1``."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    e, sh = cfg["intermediate_size"], cfg["num_shared_experts"]
    ids, w = route(t, mp["router"].astype(f32), cfg)
    lo, hi = held if held is not None else (0, cfg["num_experts"])
    out = jnp.zeros_like(t)
    for s in range(sh):
        out = out + _gated(
            t, mp["ws1"][:, s * e:(s + 1) * e].astype(f32),
            mp["ws1"][:, (sh + s) * e:(sh + s + 1) * e].astype(f32),
            mp["ws2"][s * e:(s + 1) * e].astype(f32), int8)
    out = out / sh

    def one(out, ew):
        eid, w1, w2 = ew
        w_e = jnp.sum(jnp.where(ids == eid, w, 0.0), axis=-1)
        w1 = w1.astype(f32)
        return out + w_e[:, None] * _gated(t, w1[:, :e], w1[:, e:],
                                           w2.astype(f32), int8), None

    # one expert's float32 copy at a time
    out, _ = jax.lax.scan(one, out, (jnp.arange(lo, hi), mp["w1"],
                                     mp["w2"]))
    return out


def _attention(q, k, v, window: int):
    """``q (s, heads, hd)`` on ``k``, ``v (s, kv_heads, hd)``: row ``p``
    attends ``p - window < j <= p`` (``window`` 0: every ``j <= p``), a
    block of query rows at a time."""
    import jax
    import jax.numpy as jnp

    s, heads, hd = q.shape
    kvh = k.shape[1]
    blk = min(_Q_BLOCK, s)
    qb = q.reshape(s // blk, blk, kvh, heads // kvh, hd)
    cols = jnp.arange(s)[None, :]

    def one(args):
        qi, i0 = args
        rows = (i0 + jnp.arange(blk))[:, None]
        ok = cols <= rows
        if window:
            ok = ok & (cols > rows - window)
        sc = jnp.einsum("qhgd,khd->hgqk", qi, k) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(ok[None, None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    out = jax.lax.map(one, (qb, jnp.arange(0, s, blk)))
    return out.reshape(s, heads * hd)


def _layer(x, bp, cfg: dict, sliding: bool, int8: bool, held=None):
    """One layer over one sequence ``x`` of (s, hidden)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    s = x.shape[0]
    h, kvh, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    t = _ln(x, bp["ln1"], cfg["layer_norm_eps"])
    q, k, v = jnp.split(_matmul(t, bp["wqkv"].astype(f32), int8),
                        [h * hd, (h + kvh) * hd], axis=-1)
    q, k, v = q.reshape(s, h, hd), k.reshape(s, kvh, hd), \
        v.reshape(s, kvh, hd)
    if sliding:
        ang = jnp.arange(s, dtype=f32)[:, None] * jnp.asarray(
            cfg["rope_theta"] ** (-np.arange(0, hd, 2, dtype=np.float64)
                                  / hd), f32)[None, :]
        sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]

        def rot(u):             # pairs (2i, 2i + 1)
            a, b = u[..., 0::2], u[..., 1::2]
            return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                             axis=-1).reshape(u.shape)

        q, k = rot(q), rot(k)
    att = _attention(q, k, v, cfg["sliding_window"] if sliding else 0)
    return x + _matmul(att, bp["wo"].astype(f32), int8) \
        + _experts(t, bp["moe"], cfg, int8, held)


class Reference:
    """Holds the weights (the benchmark's own, made from the seed) and
    three compiled functions: a layer of either kind, and the final
    norm with the unembedding of the rows that were served."""

    def __init__(self, cfg: dict, params, int8: bool = False):
        import jax
        import jax.numpy as jnp

        self.cfg, self.params = cfg, params
        self._layer = {
            kind: jax.jit(lambda x, bp, kind=kind: _layer(
                x, bp, cfg, kind == "sliding_attention", int8))
            for kind in set(layer_kinds(cfg))}
        self._unembed = jax.jit(lambda x, g, w: cfg["logit_scale"] * _matmul(
            _ln(x, g, cfg["layer_norm_eps"]), w.astype(jnp.float32).T, int8))

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
            for i, kind in enumerate(layer_kinds(self.cfg)):
                x = self._layer[kind](x, self.params[f"blk{i}"])
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
# change.  Per step the weights outside the routed experts (attention,
# router, shared experts, the table as the head) are read once at
# ``weight_bytes`` a parameter; of the routed experts held here only
# those TOUCHED by a row (the program's own counts where the reader can
# pass them, else the expectation ``held (1 - (1 - k/routed)^rows)`` a
# layer); keys and values of the rows a layer ATTENDS, once, at
# ``kv_cache_bytes`` a value: a sliding layer ``min(live,
# sliding_window)``, a full layer every live row.  The table's lookup
# is a gather and counts no FLOP.


def attn_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * h * hd + 2 * d * kvh * hd + h * hd * d


def expert_params(cfg: dict) -> int:
    """One expert's gated MLP."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["num_experts_published"]


def shared_params(cfg: dict) -> int:
    return cfg["num_shared_experts"] * expert_params(cfg)


def layer_dense_params(cfg: dict) -> int:
    """One layer outside its routed experts."""
    return attn_params(cfg) + shared_params(cfg) + router_params(cfg)


def layer_params(cfg: dict) -> int:
    """One layer as held here."""
    return layer_dense_params(cfg) + cfg["num_experts"] * expert_params(cfg)


def table_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def total_params(cfg: dict) -> int:
    """Matrices held here: the layers and the tied table."""
    return cfg["num_hidden_layers"] * layer_params(cfg) + table_params(cfg)


def dense_step_params(cfg: dict) -> int:
    """Parameters every step reads whatever was routed."""
    return cfg["num_hidden_layers"] * layer_dense_params(cfg) \
        + table_params(cfg)


def kv_token_layer_bytes(cfg: dict) -> int:
    """Key and value of one token in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] \
        * cfg["kv_cache_bytes"]


def expected_touched(cfg: dict, rows: float) -> float:
    """Held experts with at least one of ``rows`` tokens, one layer."""
    miss = 1.0 - cfg["num_experts_per_tok"] / cfg["num_experts_published"]
    return cfg["num_experts"] * (1.0 - miss ** rows)


def expected_local_pairs(cfg: dict, rows: float) -> float:
    return rows * cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_published"]


def _routing(cfg: dict, rows: int, steps: int, counters) -> tuple:
    """``(experts touched, local pairs)`` summed over the layers and
    ``steps`` steps of ``rows`` tokens in all: the program's counts
    (``kv_stats()["moe"]`` deltas) or the expectation."""
    if counters:
        return float(counters["experts_touched"]), \
            float(counters["local_pairs"])
    layers = cfg["num_hidden_layers"]
    return (layers * steps * expected_touched(cfg, rows / max(steps, 1)),
            layers * expected_local_pairs(cfg, rows))


def attended(cfg: dict, lives) -> tuple:
    """Rows attended over ``lives`` (each a count of positions, the
    token's own included): ``(in one sliding layer, in one full)``."""
    w = cfg["sliding_window"]
    return float(sum(min(n, w) for n in lives)), float(sum(lives))


def attention_flops(cfg: dict) -> float:
    """Scores and weighted sum of one query token over one key row."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]


def step_work(cfg: dict, lives: list, steps: int = 1,
              counters=None) -> tuple:
    """``(flops, bytes)`` of ``steps`` decode steps that between them
    produce one token for each entry of ``lives`` (the positions that
    token attends over in a full layer, itself included)."""
    rows = len(lives)
    n_win, n_full = n_layers(cfg)
    touched, pairs = _routing(cfg, rows, steps, counters)
    a_win, a_full = attended(cfg, lives)
    reach = n_win * a_win + n_full * a_full
    flops = (2.0 * rows * dense_step_params(cfg)
             + attention_flops(cfg) * reach
             + 2.0 * expert_params(cfg) * pairs)
    nbytes = (cfg["weight_bytes"] * (steps * dense_step_params(cfg)
                                     + touched * expert_params(cfg))
              + kv_token_layer_bytes(cfg)
              * (reach + rows * cfg["num_hidden_layers"]))
    return flops, nbytes


def fill_work(cfg: dict, start: int, n: int) -> tuple:
    """``(flops, bytes)`` of filling ``n`` positions of a context from
    ``start``: the weights outside the routed experts once (without
    the head), the held experts the ``n`` rows touch, the cached rows
    the first new row reaches read and ``n`` written."""
    if n <= 0:
        return 0.0, 0.0
    n_win, n_full = n_layers(cfg)
    a_win, a_full = attended(cfg, range(start + 1, start + n + 1))
    touched, pairs = _routing(cfg, n, 1, None)
    dense = dense_step_params(cfg) - table_params(cfg)
    flops = (2.0 * n * dense
             + attention_flops(cfg) * (n_win * a_win + n_full * a_full)
             + 2.0 * expert_params(cfg) * pairs)
    nbytes = (cfg["weight_bytes"] * (dense + touched * expert_params(cfg))
              + kv_token_layer_bytes(cfg)
              * (n_win * (min(start, cfg["sliding_window"]) + n)
                 + n_full * (start + n)))
    return flops, nbytes


# -- the step kernels' own counts (readers/step_kernel_work.py) ---------------


def kernel_calls(cfg: dict, kernel: str) -> int:
    """Calls of the named kernel in one execution of the step: the
    grouped expert product twice a layer, the window kernel once a
    sliding layer, the same kernel under the paged one's name once a
    full layer."""
    n_win, n_full = n_layers(cfg)
    return {"expert_gmm": 2 * cfg["num_hidden_layers"],
            "window_decode_attention": n_win,
            "paged_decode_attention": n_full}[kernel]


def window_attn_work(cfg: dict, lives: list, steps: int = 1,
                     counters=None) -> tuple:
    """``(flops, bytes)`` of the sliding layers' attention alone: the
    rows the window reaches read once, scores and weighted sum over
    them."""
    n_win, _n_full = n_layers(cfg)
    a_win, _a_full = attended(cfg, lives)
    return (n_win * attention_flops(cfg) * a_win,
            n_win * kv_token_layer_bytes(cfg) * a_win)


def expert_work(cfg: dict, lives: list, steps: int = 1,
                counters=None) -> tuple:
    """``(flops, bytes)`` of the routed experts' grouped products
    alone: the rows that fell here through a gated MLP, each touched
    expert's weights once."""
    touched, pairs = _routing(cfg, len(lives), steps, counters)
    return (2.0 * expert_params(cfg) * pairs,
            cfg["weight_bytes"] * touched * expert_params(cfg))
