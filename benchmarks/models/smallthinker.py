"""The block ``"model": "smallthinker"`` names (``harness/spec.py`` loads
this file by that key; see ``models/neox.py`` for what the harness
calls).

The block, from the source's ``config.json`` (``model_name:
smallthinker_21b_instruct``, arXiv:2507.20984) and the catalog's
``described_as``.  ``x`` a token's float32 residual row at position
``p``; ``RMS(t; g) = t g / sqrt(mean t^2 + rms_norm_eps)``; no bias
anywhere, no q/k norm.  Layer ``i`` is a SEQUENTIAL block whose router
reads the layer's INPUT:

- ``r = x W_r`` (hidden -> ``moe_num_primary_experts``, float32) from
  the layer's input ``x`` itself, UN-NORMED, before attention; ``s =
  softmax(r)`` over all the experts (``moe_primary_router_apply_softmax``);
  the ``moe_num_active_primary_experts`` largest chosen; ``w_k = s_k /
  sum of the chosen s`` (``norm_topk_prob``);
- ``h = RMS(x; g1)``; ``q = h W_q`` (hidden -> heads x ``head_dim``),
  ``k = h W_k``, ``v = h W_v`` (hidden -> key/value heads x
  ``head_dim``); ``heads / kv_heads`` (SEVEN) query heads on each
  key/value head, scores times ``head_dim^-0.5``, softmax in float32;
- ``sliding_window_layout[i] == 1`` (and ``rope_layout[i] == 1``: the
  two lists are equal in the source): rotate ``q`` and ``k`` over the
  whole head, column ``c`` with ``c + head_dim / 2``, by ``p *
  rope_theta^(-2c/head_dim)``, and attend ``p - sliding_window_size < j
  <= p``.  Layout 0 (layers 0, 4, 8, ...): rotate NOTHING, attend every
  ``j <= p``;
- ``x1 = x + concat(heads) W_o``; ``h2 = RMS(x1; g2)``;
- ``m = sum over chosen k of w_k E_k(h2)``, ``E(t) = (relu(t W_g) * (t
  W_u)) W_d`` at width ``moe_ffn_hidden_size``: the experts read the
  normed row AFTER attention, the router read the row BEFORE it;
- ``x' = x1 + m``.  After the last layer ``RMS``, logits ``= h W_head``
  (untied, ``vocab_size`` wide).

Every routed expert of a layer is held here (``held`` below exists for
the test that adds shares up).  Read into the source (``assumed`` in
the configuration file): the router's operand (``described_as``:
"router placed before attention"); the window's edge; the pairing of
rotary columns; no attention bias; ``described_as``'s "secondary
experts / sparse ReGLU predictor" has no key in the published config
and nothing of it is run.

The weights are in the PROGRAM'S tree (``brpc_tpu/models/
transformer_lm.py _init_block_params``): ``embed``, ``unembed``,
``norm_f``, per layer ``ln1 ln2 wqkv wo moe``; ``wqkv`` holds ``W_q W_k
W_v`` side by side, ``moe`` is ``router w1 w2``: ``w1`` the experts'
``[W_g W_u]`` stacked, ``w2`` their ``W_d``.  Matrices are bfloat16, as
the source stores them and as the program serves them; norms float32.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.models.command_a import _attention
from benchmarks.models.neox import _matmul

# ---------------------------------------------------------------------------
# the system under test: weights from the seed, the program's service
# ---------------------------------------------------------------------------


def layer_windows(cfg: dict) -> list:
    """``sliding_window_layout`` of the layers held here, as booleans
    (the source's lists are kept whole in the file; a layer rotates
    where, and only where, it has a window)."""
    depth = cfg["num_hidden_layers"]
    win, rope = cfg["sliding_window_layout"][:depth], \
        cfg["rope_layout"][:depth]
    if win != rope or set(win) - {0, 1}:
        raise ValueError("a layer rotates exactly where it has a window")
    return [bool(w) for w in win]


def n_layers(cfg: dict) -> tuple:
    """``(window layers, global layers)``."""
    win = layer_windows(cfg)
    return sum(win), len(win) - sum(win)


def lm_kwargs(cfg: dict) -> dict:
    """The program's ``LMConfig`` arguments for a configuration file."""
    if not cfg["moe_primary_router_apply_softmax"] \
            or not cfg["norm_topk_prob"] or cfg["tie_word_embeddings"] \
            or cfg["rope_scaling"] is not None:
        raise ValueError("this block is the early-routed one: softmax "
                         "routing renormalised, an untied head, plain "
                         "rotary on the window layers")
    win = layer_windows(cfg)
    depth, svc = cfg["num_hidden_layers"], cfg["service"]
    return dict(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        depth=depth, max_seq=svc["max_seq"], fill_span=svc["fill_span"],
        norm="rms", norm_eps=cfg["rms_norm_eps"],
        windows=tuple(cfg["sliding_window_size"] * w for w in win),
        ropes=tuple(win), rope_pairs="halves",
        rope_theta=cfg["rope_theta"],
        ffn="gated_relu", ffn_dim=cfg["moe_ffn_hidden_size"],
        tie_embed=False, final_norm=True,
        ffns=("experts",) * depth, expert_dim=cfg["moe_ffn_hidden_size"],
        experts_routed=cfg["moe_num_primary_experts"],
        experts_held=(0, cfg["moe_num_primary_experts"]),
        experts_top_k=cfg["moe_num_active_primary_experts"],
        route_scale=1.0, shared_experts=0, router_bias=False,
        router_at="layer_input", router_scoring="softmax")


def make_params(cfg: dict, seed: int):
    """Seeded weights on the device as served: matrices normal at
    ``1/sqrt(fan_in)`` rounded to bfloat16 ONCE, norm gains one in
    float32.  One compiled program for the layers."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models.transformer_lm import LMConfig

    # a program that does not know this block fails here, at once, and
    # not after 8 GB of weights have been made
    LMConfig(remat=False, **lm_kwargs(cfg))
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, routed = cfg["moe_ffn_hidden_size"], cfg["moe_num_primary_experts"]

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(jnp.bfloat16)

    @jax.jit
    def layer(key):
        ks = jax.random.split(key, 5)
        return {"ln1": jnp.ones((d,), jnp.float32),
                "ln2": jnp.ones((d,), jnp.float32),
                "wqkv": normal(ks[0], (d, (h + 2 * kvh) * hd), d),
                "wo": normal(ks[1], (h * hd, d), h * hd),
                "moe": {"router": normal(ks[2], (d, routed), d),
                        "w1": normal(ks[3], (routed, d, 2 * e), d),
                        "w2": normal(ks[4], (routed, e, d), e)}}

    # --seed may pass 2**31: fold the high bits in instead of wrapping
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl="rbg"),
                             seed >> 31)
    ks = jax.random.split(key, 2 + cfg["num_hidden_layers"])
    params = {
        "embed": jax.jit(lambda k: normal(k, (v, d), d))(ks[0]),
        "unembed": jax.jit(lambda k: normal(k, (d, v), d))(ks[1]),
        "norm_f": jnp.ones((d,), jnp.float32)}
    for i in range(cfg["num_hidden_layers"]):
        params[f"blk{i}"] = layer(ks[2 + i])
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
# precision: one request at a time, one layer at a time, the router on
# the layer's input before anything else, attention as a masked softmax
# over the whole context taken a block of query rows at a time (so that
# 13,056 rows fit: ``models/command_a.py``'s ``_attention``, the same
# grouped heads under the same window's edge), the expert layer a plain
# loop over the experts with a mask.  No kernel, no cache, no pages, no spans, no sort.  It imports
# nothing of the program.  The control is the same with every weight
# matmul computed from int8 operands (``models/neox.py``'s ``_matmul``);
# the router, whose choice the model's mathematics keeps in float32,
# stays float32 in both.

def _rms(t, g, eps: float):
    import jax.numpy as jnp

    return t * g / jnp.sqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)


def _gated(t, wg, wu, wd, int8: bool):
    import jax

    return _matmul(jax.nn.relu(_matmul(t, wg, int8)) * _matmul(t, wu, int8),
                   wd, int8)


def route(x, router, cfg: dict):
    """``(ids (s, k), w (s, k))`` from the layer's input rows ``x``:
    the softmax over ALL the experts, the largest chosen, their scores
    renormalised."""
    import jax
    import jax.numpy as jnp

    sc = jax.nn.softmax(x @ router, axis=-1)
    ids = jnp.argsort(-sc, axis=-1, stable=True)[
        :, :cfg["moe_num_active_primary_experts"]]
    w = jnp.take_along_axis(sc, ids, axis=-1)
    return ids, w / w.sum(axis=-1, keepdims=True)


def _experts(t, ids, w, mp, cfg: dict, int8: bool, held=None):
    """``sum_k w_k E_k(t)`` over the chosen experts whose weights are
    here: ``held`` is the range of expert ids that ``mp["w1"]``'s rows
    are, in order (by default all of them)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    e = cfg["moe_ffn_hidden_size"]
    lo, hi = held if held is not None else (0, cfg["moe_num_primary_experts"])

    def one(out, ew):
        eid, w1, w2 = ew
        w_e = jnp.sum(jnp.where(ids == eid, w, 0.0), axis=-1)
        w1 = w1.astype(f32)
        return out + w_e[:, None] * _gated(t, w1[:, :e], w1[:, e:],
                                           w2.astype(f32), int8), None

    # one expert's float32 copy at a time
    out, _ = jax.lax.scan(one, jnp.zeros_like(t),
                          (jnp.arange(lo, hi), mp["w1"], mp["w2"]))
    return out


def _layer(x, bp, cfg: dict, window: bool, int8: bool):
    """One layer over one sequence ``x`` of (s, hidden)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    s = x.shape[0]
    h, kvh, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    # the router first, on the layer's input as it is
    ids, w = route(x, bp["moe"]["router"].astype(f32), cfg)
    t = _rms(x, bp["ln1"], eps)
    q, k, v = jnp.split(_matmul(t, bp["wqkv"].astype(f32), int8),
                        [h * hd, (h + kvh) * hd], axis=-1)
    q, k, v = q.reshape(s, h, hd), k.reshape(s, kvh, hd), \
        v.reshape(s, kvh, hd)
    if window:
        half = hd // 2
        ang = jnp.arange(s, dtype=f32)[:, None] * jnp.asarray(
            cfg["rope_theta"] ** (-np.arange(0, hd, 2, dtype=np.float64)
                                  / hd), f32)[None, :]
        sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]

        def rot(u):             # column c with c + hd / 2
            a, b = u[..., :half], u[..., half:]
            return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                                   axis=-1)

        q, k = rot(q), rot(k)
    att = _attention(q, k, v, cfg["sliding_window_size"] if window else 0)
    x1 = x + _matmul(att, bp["wo"].astype(f32), int8)
    return x1 + _experts(_rms(x1, bp["ln2"], eps), ids, w, bp["moe"], cfg,
                         int8)


class Reference:
    """Holds the weights (the benchmark's own, made from the seed) and
    three compiled functions: a layer of either kind, and the final
    norm with the head on the rows that were served."""

    def __init__(self, cfg: dict, params, int8: bool = False):
        import jax
        import jax.numpy as jnp

        self.cfg, self.params = cfg, params
        self._layer = {
            win: jax.jit(lambda x, bp, win=win: _layer(x, bp, cfg, win,
                                                       int8))
            for win in set(layer_windows(cfg))}
        self._unembed = jax.jit(lambda x, g, w: _matmul(
            _rms(x, g, cfg["rms_norm_eps"]), w.astype(jnp.float32), int8))

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
            for i, win in enumerate(layer_windows(self.cfg)):
                x = self._layer[win](x, self.params[f"blk{i}"])
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
# change.  Per step the weights outside the experts (attention, router,
# the head) are read once at ``weight_bytes`` a parameter; of the experts
# only those TOUCHED by a row (the program's own counts where the reader
# can pass them, else the expectation ``experts (1 - (1 - k/experts)^rows)``
# a layer); keys and values of the rows a layer ATTENDS, once, at
# ``kv_cache_bytes`` a value: a window layer ``min(live,
# sliding_window_size)``, a global layer every live row.  The table's
# lookup is a gather: no FLOP, and a row a token of bytes, which is not
# counted.


def attn_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * h * hd + 2 * d * kvh * hd + h * hd * d


def expert_params(cfg: dict) -> int:
    """One expert's gated MLP."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["moe_num_primary_experts"]


def layer_dense_params(cfg: dict) -> int:
    """One layer outside its experts."""
    return attn_params(cfg) + router_params(cfg)


def layer_params(cfg: dict) -> int:
    return layer_dense_params(cfg) \
        + cfg["moe_num_primary_experts"] * expert_params(cfg)


def table_params(cfg: dict) -> int:
    """The table, or the head: each ``vocab_size x hidden_size``."""
    return cfg["vocab_size"] * cfg["hidden_size"]


def total_params(cfg: dict) -> int:
    """Matrices held here: the layers, the table and the untied head."""
    return cfg["num_hidden_layers"] * layer_params(cfg) \
        + 2 * table_params(cfg)


def dense_step_params(cfg: dict) -> int:
    """Parameters every step reads whatever was routed: the layers
    outside their experts and the head (the table is looked up)."""
    return cfg["num_hidden_layers"] * layer_dense_params(cfg) \
        + table_params(cfg)


def kv_token_layer_bytes(cfg: dict) -> int:
    """Key and value of one token in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] \
        * cfg["kv_cache_bytes"]


def expected_touched(cfg: dict, rows: float) -> float:
    """Experts with at least one of ``rows`` tokens, one layer."""
    n = cfg["moe_num_primary_experts"]
    miss = 1.0 - cfg["moe_num_active_primary_experts"] / n
    return n * (1.0 - miss ** rows)


def _routing(cfg: dict, rows: int, steps: int, counters) -> tuple:
    """``(experts touched, pairs)`` summed over the layers and ``steps``
    steps of ``rows`` tokens in all: the program's counts
    (``kv_stats()["moe"]`` deltas) or the expectation (every expert is
    held, so every pair falls here)."""
    if counters:
        return float(counters["experts_touched"]), \
            float(counters["local_pairs"])
    layers = cfg["num_hidden_layers"]
    return (layers * steps * expected_touched(cfg, rows / max(steps, 1)),
            layers * rows * cfg["moe_num_active_primary_experts"])


def attended(cfg: dict, lives) -> tuple:
    """Rows attended over ``lives`` (each a count of positions, the
    token's own included): ``(in one window layer, in one global)``."""
    w = cfg["sliding_window_size"]
    return float(sum(min(n, w) for n in lives)), float(sum(lives))


def attention_flops(cfg: dict) -> float:
    """Scores and weighted sum of one query token over one key row."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]


def step_work(cfg: dict, lives: list, steps: int = 1,
              counters=None) -> tuple:
    """``(flops, bytes)`` of ``steps`` decode steps that between them
    produce one token for each entry of ``lives`` (the positions that
    token attends over in a global layer, itself included)."""
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
    ``start``: the weights outside the experts once (without the head),
    the experts the ``n`` rows touch, the cached rows the first new row
    reaches read and ``n`` written."""
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
              * (n_win * (min(start, cfg["sliding_window_size"]) + n)
                 + n_full * (start + n)))
    return flops, nbytes


# -- the step kernels' own counts (readers/step_kernel_work.py) ---------------


def kernel_calls(cfg: dict, kernel: str) -> int:
    """Calls of the named kernel in one execution of the step: the
    grouped expert product twice a layer, the window kernel once a
    window layer, the same kernel under the paged one's name once a
    global layer."""
    n_win, n_full = n_layers(cfg)
    return {"expert_gmm": 2 * cfg["num_hidden_layers"],
            "window_decode_attention": n_win,
            "paged_decode_attention": n_full}[kernel]


def window_attn_work(cfg: dict, lives: list, steps: int = 1,
                     counters=None) -> tuple:
    """``(flops, bytes)`` of the window layers' attention alone: the
    rows the window reaches read once, scores and weighted sum over
    them."""
    n_win, _n_full = n_layers(cfg)
    a_win, _a_full = attended(cfg, lives)
    return (n_win * attention_flops(cfg) * a_win,
            n_win * kv_token_layer_bytes(cfg) * a_win)


def expert_work(cfg: dict, lives: list, steps: int = 1,
                counters=None) -> tuple:
    """``(flops, bytes)`` of the experts' grouped products alone: every
    (token, chosen expert) pair through a gated MLP, each touched
    expert's weights once."""
    touched, pairs = _routing(cfg, len(lives), steps, counters)
    return (2.0 * expert_params(cfg) * pairs,
            cfg["weight_bytes"] * touched * expert_params(cfg))
