"""Mixture-of-Experts FFN with expert parallelism (ep).

The third flagship model family next to EmbeddingPS (sparse lookup) and
TransformerLM (dense compute): sparse *compute*, where each token visits
only ``k`` of E expert FFNs and experts shard over an ``ep`` mesh axis.

TPU-first design (the reference has no MoE; its ep analogue is
partitioned services — ``DynamicPartitionChannel`` routing a request to
the shard that owns it, /root/reference/src/brpc/partition_channel.h):

- **static shapes**: capacity-factor routing — each expert processes a
  fixed ``C = ceil(k * T / E * capacity)`` token slots; overflow tokens
  are dropped (their residual passes through), so nothing in the traced
  program is data-dependent and XLA can tile every einsum on the MXU;
- **dispatch/combine as einsums** (the Mesh-TensorFlow formulation):
  a (T, E, C) one-hot dispatch tensor gathers token slots, expert FFNs
  run batched as (E, C, d) einsums, and the combine einsum scatters
  results back weighted by router probabilities;
- **expert parallelism by sharding, not message passing**: expert
  weights carry ``P("ep", ...)`` specs; under ``jit`` over a mesh XLA
  inserts the all_to_all/all_gather collectives that move token slots
  onto the devices owning their experts (ICI, not host);
- router in fp32 (numerics), expert matmuls in bf16 (MXU);
- **grouped routing** (GShard): :func:`forward_grouped` routes within
  fixed-size groups, so dispatch memory is linear in total tokens and
  the routing cumsum never crosses a dp shard boundary (groups align
  with the data-parallel batch dim).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple


class MoEConfig:
    def __init__(self, dim: int = 64, hidden: int = 128,
                 num_experts: int = 4, capacity_factor: float = 1.5,
                 aux_loss_weight: float = 0.01, top_k: int = 1):
        assert 1 <= top_k <= num_experts
        self.dim = dim
        self.hidden = hidden
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.aux_loss_weight = aux_loss_weight
        # top_k=1 is Switch-style routing; top_k=2 the GShard/Mixtral
        # configuration (each token visits its k best experts, outputs
        # mixed by the renormalized router probabilities)
        self.top_k = top_k

    def capacity(self, tokens: int) -> int:
        c = math.ceil(tokens * self.top_k / self.num_experts
                      * self.capacity_factor)
        return max(1, c)


def init_params(rng, cfg: MoEConfig) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    kg, k1, k2 = jax.random.split(rng, 3)
    scale = 1.0 / math.sqrt(cfg.dim)
    return {
        "wg": jax.random.normal(kg, (cfg.dim, cfg.num_experts),
                                jnp.float32) * scale,
        "w1": jax.random.normal(k1, (cfg.num_experts, cfg.dim, cfg.hidden),
                                jnp.float32) * scale,
        "w2": jax.random.normal(k2, (cfg.num_experts, cfg.hidden, cfg.dim),
                                jnp.float32) * (scale / 2),
    }


def param_specs(cfg: MoEConfig, ep_axis: str = "ep") -> Dict[str, Any]:
    """PartitionSpecs: experts shard over the ep axis, router replicated."""
    from jax.sharding import PartitionSpec as P

    return {
        "wg": P(None, None),
        "w1": P(ep_axis, None, None),
        "w2": P(ep_axis, None, None),
    }


def forward(params: Dict[str, Any], x, cfg: MoEConfig
            ) -> Tuple[Any, Any]:
    """MoE FFN: x (T, d) -> (out (T, d), aux_loss ()).

    Top-k routing with capacity; each of a token's k expert slots is
    dispatched as its own "slot token", outputs mix back weighted by
    the renormalized router probabilities.  Dropped slots contribute
    zero (the caller's residual carries the token through)."""
    import jax
    import jax.numpy as jnp

    T, d = x.shape
    E = cfg.num_experts
    K = cfg.top_k
    C = cfg.capacity(T)

    logits = x @ params["wg"]                      # (T, E) fp32 router
    probs = jax.nn.softmax(logits, axis=-1)
    topv, tope = jax.lax.top_k(probs, K)           # (T, K)
    if K > 1:
        # renormalize over the selected experts (Mixtral-style mixing);
        # K=1 keeps the raw router prob as the scale (Switch style —
        # renormalizing would pin the gate to 1.0 and starve the router
        # of gate gradients)
        topv = topv / jnp.maximum(topv.sum(axis=-1, keepdims=True), 1e-9)
    # capacity positions are assigned over (choice-major) slots so every
    # token's FIRST choice queues ahead of all second choices; since a
    # token's K chosen experts are distinct, its slots never collide and
    # the K per-choice masks fold into ONE (T, E, C) dispatch/combine —
    # the Mesh-TF formulation, keeping every einsum at T rows
    slot_expert = tope.transpose(1, 0).reshape(K * T)          # (K*T,)
    slot_onehot = jax.nn.one_hot(slot_expert, E,
                                 dtype=jnp.int32)              # (K*T, E)
    pos = jnp.cumsum(slot_onehot, axis=0) * slot_onehot - 1    # (K*T, E)
    pos_in_expert = pos.max(axis=1)                            # (K*T,)
    kept = pos_in_expert < C                                   # drop tail

    dispatch = jnp.zeros((T, E, C), x.dtype)      # slot indicator
    combine = jnp.zeros((T, E, C), x.dtype)       # gate-weighted
    for k in range(K):                            # static unroll, K small
        sl = slice(k * T, (k + 1) * T)
        # slot_onehot[sl] IS one_hot(tope[:, k]) in choice-major layout
        mask_k = (slot_onehot[sl].astype(x.dtype)[:, :, None]
                  * jax.nn.one_hot(jnp.clip(pos_in_expert[sl], 0, C - 1),
                                   C, dtype=x.dtype)[:, None, :]
                  * kept[sl][:, None, None].astype(x.dtype))
        dispatch = dispatch + mask_k
        combine = combine + mask_k * topv[:, k].astype(
            x.dtype)[:, None, None]

    # gather token slots, run every expert as one batched bf16 einsum
    expert_in = jnp.einsum("td,tec->ecd", x.astype(jnp.bfloat16),
                           dispatch.astype(jnp.bfloat16))     # (E, C, d)
    h = jnp.einsum("ecd,edh->ech", expert_in,
                   params["w1"].astype(jnp.bfloat16))
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(jnp.bfloat16)
    expert_out = jnp.einsum("ech,ehd->ecd", h,
                            params["w2"].astype(jnp.bfloat16))

    # scatter back, weighted by the (renormalized) router probability
    out = jnp.einsum("ecd,tec->td", expert_out.astype(x.dtype), combine)

    # load-balancing aux loss (Switch Transformer): fraction of FIRST-
    # choice assignments per expert x mean router prob, scaled by E
    frac = jnp.mean(slot_onehot[:T].astype(jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_prob) * cfg.aux_loss_weight
    return out, aux


def forward_grouped(params: Dict[str, Any], x, cfg: MoEConfig
                    ) -> Tuple[Any, Any]:
    """Grouped MoE: x (G, N, d) -> (out (G, N, d), aux ()).

    Routes each group of N tokens independently (capacity per group),
    so the (N, E, C) dispatch tensors stay linear in total tokens and —
    when G is the dp-sharded batch dim — routing is local to each data
    shard (no cross-replica cumsum).  This is the form the transformer
    block uses; plain :func:`forward` is the single-group case."""
    import jax

    out, aux = jax.vmap(lambda xg: forward(params, xg, cfg))(x)
    return out, aux.mean()


# -- serving: routed experts without drops, beside shared ones -------------
#
# The expert layer of a served schedule (``LMConfig.ffns``
# ``"experts"``), as DeepSeek-V3's modelling code has it: sigmoid
# scores, a correction bias that CHOOSES and does not weigh, the chosen
# weights renormalised and scaled, every expert a gated MLP (or, of
# ``act`` "relu2", the ungated ``relu(t W_up)^2 W_down``), shared
# experts on every token.  Nothing is dropped: the rows routed to the
# experts held here are sorted by expert into a buffer of the worst
# case and run as two grouped matrix products, each one call of the
# program's own kernel (``ops/expert_gmm.py``, ``expert_gmm`` in a
# device trace): a grid step a (row tile, expert) pair that has rows, so
# the cost follows the experts TOUCHED (their weights, once, at ~90% of
# the HBM rate on the v5e) and not the buffer.  (``jax.lax.ragged_dot``,
# which stood here until PR 32, followed the buffer's rows: a 512-row
# MXU tile a touched expert, 36% of the HBM rate at a decode step's 16
# rows in 512; PERF.md §6.)  The combine behind them is a kernel too
# (``ops/expert_combine.py``, ``expert_combine`` in a device trace, one
# call a layer): the buffer's LIVE rows, which lie at its front, each
# weighed and added into its token's row of an output block that stays
# in VMEM, the row tiles behind the last live row never fetched; its
# cost follows the pairs that fell on a held expert (``counts[0]``) and
# the output's own bytes, 6-9 us a step's layer on the v5e.  (The
# scatter-add of the whole masked, weighed buffer, which stood here
# until PR 38, took the buffer's rows one after another: 162 us at 14
# live rows in 512 x 7,168; PERF.md §6.)  Off the TPU, and at a width
# that fills no whole lanes, the same sum runs from the token's side in
# plain XLA.  The layer is TOLD which experts it holds:
# it routes over all of them, normalises over all the chosen ones, and
# adds only its own experts' part; what the others would add is another
# chip's.
# (``forward`` above, the capacity-factor layer that drops, is
# training's.)


class ExpertConfig:
    def __init__(self, dim: int, hidden: int, routed: int,
                 held: Tuple[int, int], top_k: int,
                 route_scale: float = 1.0, shared: int = 0,
                 bias: bool = True, shared_scale: float = 1.0,
                 scoring: str = "sigmoid", act: str = "silu",
                 shared_dim: Optional[int] = None):
        assert 0 <= held[0] < held[1] <= routed and 1 <= top_k <= routed
        assert scoring in ("sigmoid", "softmax") \
            and act in ("silu", "relu", "relu2")
        self.dim, self.hidden, self.routed = dim, hidden, routed
        self.held = (int(held[0]), int(held[1]))
        self.n_held = self.held[1] - self.held[0]
        self.top_k = top_k
        self.route_scale = route_scale
        self.shared = shared
        # a router that chooses by its scores alone has no ``bias``
        # leaf; shared experts that are AVERAGED are the one gated MLP
        # of ``shared * hidden`` times ``shared_scale`` = 1 / shared
        self.bias = bool(bias)
        self.shared_scale = float(shared_scale)
        # what makes scores of the router's logits (a softmax is over
        # ALL routed experts, before the choice), and the gate's
        # activation in every expert, routed or shared; "relu2" has no
        # gate: ``relu(t W_up)^2 W_down``, ``w1`` the up product alone,
        # its rows the hidden width's as ``w2``'s are: ``(n, hidden,
        # dim)``, read transposed.  (Laid ``(n, dim, hidden)`` at
        # nemotron-3-nano-30b's hidden of 1,856, no whole lane tiles,
        # the device's default layout put ``dim`` on the lanes and the
        # kernel's operand was copied into row-major every step: 39%
        # of the step's device time, measured on one v5e chip.)
        self.scoring, self.act = scoring, act
        self.gated = act != "relu2"
        # the shared experts' one MLP: ``shared * hidden`` wide unless
        # it has a width of its own
        self.shared_dim = shared * hidden if shared_dim is None \
            else int(shared_dim)

    def buffer_rows(self, rows: int) -> int:
        """The sorted buffer's rows for ``rows`` tokens: a token's
        chosen experts are distinct, so at most ``min(top_k, held)`` of
        them are held here."""
        return rows * min(self.top_k, self.n_held)


def init_served(rng, cfg: ExpertConfig) -> Dict[str, Any]:
    """Seeded weights of one served expert layer: the router over ALL
    routed experts and its correction bias, the HELD experts' MLPs
    stacked (a gated one's ``w1`` holds gate and up side by side, an
    ungated one's lies ``(n, hidden, dim)``), the shared experts as one
    MLP of ``shared_dim``."""
    import jax
    import jax.numpy as jnp

    d, e, n, sd = cfg.dim, cfg.hidden, cfg.n_held, cfg.shared_dim
    up = 2 if cfg.gated else 1
    ks = jax.random.split(rng, 6)

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

    p = {"router": normal(ks[0], (d, cfg.routed), d),
         "w1": normal(ks[2], (n, d, 2 * e) if cfg.gated else (n, e, d), d),
         "w2": normal(ks[3], (n, e, d), e)}
    if cfg.bias:
        p["bias"] = jax.random.normal(ks[1], (cfg.routed,),
                                      jnp.float32) * 0.1
    if cfg.shared:
        p["ws1"] = normal(ks[4], (d, up * sd), d)
        p["ws2"] = normal(ks[5], (sd, d), sd)
    return p


def route(p: Dict[str, Any], t, cfg: ExpertConfig) -> Tuple[Any, Any]:
    """``t (T, dim)`` float32 -> ``(ids (T, k), w (T, k))``: the top-k
    of ``score(t W_r) + bias`` over ALL routed experts (``score`` the
    sigmoid, or the softmax over all of them: ``cfg.scoring``), weighed
    by the scores WITHOUT the bias, renormalised over the chosen and
    scaled.  Float32 at ``highest``: a flipped choice changes a token's
    output by a whole expert.  ``t`` is whatever the block's router
    reads: the rows the experts are fed, or (``LMConfig.router_at``
    ``"layer_input"``) the layer's input before its first norm."""
    import jax
    import jax.numpy as jnp

    score = jax.nn.sigmoid if cfg.scoring == "sigmoid" else jax.nn.softmax
    sc = score(jnp.dot(
        t.astype(jnp.float32), p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _top, ids = jax.lax.top_k(sc + p["bias"] if cfg.bias else sc,
                              cfg.top_k)
    w = jnp.take_along_axis(sc, ids, axis=-1)
    w = w / (w.sum(axis=-1, keepdims=True) + 1e-20) * cfg.route_scale
    return ids, w


def _mlp(cfg: ExpertConfig, x, w1, w2):
    """One MLP of the layer's form (the shared experts'): ``_hidden(x
    w1) w2``."""
    from ..ops.quant import mxu_matmul

    return mxu_matmul(_hidden(cfg, mxu_matmul(x, w1)), w2)


def _hidden(cfg: ExpertConfig, h):
    """An expert's hidden rows from its first product ``h``: ``act(gate)
    * up``, or ``relu(h)^2`` where the experts are not gated."""
    import jax
    import jax.numpy as jnp

    if not cfg.gated:
        return jnp.square(jax.nn.relu(h))
    gate, up = jnp.split(h, 2, axis=-1)
    return (jax.nn.silu if cfg.act == "silu" else jax.nn.relu)(gate) * up


def serve(p: Dict[str, Any], t, cfg: ExpertConfig, live=None, routed=None
          ) -> Tuple[Any, Any]:
    """The expert layer: ``t (T, dim)`` float32, normed -> ``(out (T,
    dim), counts (3,) int32)``: ``sum_k w_k E_k(t)`` over the chosen
    experts HELD here plus the shared experts.  ``routed`` is ``(ids,
    w)`` where the caller routed (:func:`route` on rows of its own:
    routing and the experts' input are then two operands); without it
    the layer routes on ``t``.  Rows not ``live`` (a
    bucket's padding, an idle slot) are routed nowhere, so they cost no
    expert and count nowhere.  The routed sum is float32 and follows the
    live rows (``ops/expert_combine.py``): the buffer's rows behind them
    are neither computed nor read.  ``counts``: (token, expert) pairs
    that fell on a held expert, held experts with at least one row, the
    most rows one expert took."""
    import jax
    import jax.numpy as jnp

    from ..ops import quant
    from ..ops.expert_combine import combine
    from ..ops.expert_gmm import expert_gmm

    T, k, n = t.shape[0], cfg.top_k, cfg.n_held
    lo, hi = cfg.held
    # ``moe_route`` in a device trace: the router, the choice, the sort
    # (and, in ``ops/expert_gmm.py``, the visit lists), apart from the
    # products
    with jax.named_scope("moe_route"):
        ids, w = route(p, t, cfg) if routed is None else routed
        local = (ids >= lo) & (ids < hi)
        if live is not None:
            local = local & live[:, None]
        # sort the (token, choice) pairs by held expert; the others last
        key = jnp.where(local, ids - lo, n).reshape(T * k)
        order = jnp.argsort(key, stable=True)[:cfg.buffer_rows(T)]
        tok = order // k
        sizes = jnp.sum(key[:, None] == jnp.arange(n)[None, :], axis=0,
                        dtype=jnp.int32)
    bf = quant.mxu_operand
    xs = bf(t)[tok]                                         # (M, dim)
    h = expert_gmm(xs, bf(p["w1"]), sizes, transposed=not cfg.gated)
    ys = expert_gmm(bf(_hidden(cfg, h)), bf(p["w2"]), sizes)
    # the local pairs' rows are the first ``sizes.sum()``; the rows
    # behind them were not computed
    out = combine(ys, order, w, sizes.sum())
    if cfg.shared:
        with jax.named_scope("shared_expert"):
            shared = _mlp(cfg, t, p["ws1"], p["ws2"])
        out = out + (shared if cfg.shared_scale == 1.0
                     else shared * cfg.shared_scale)
    counts = jnp.stack([sizes.sum(), (sizes > 0).sum(), sizes.max()]
                       ).astype(jnp.int32)
    return out, counts


def make_train_step(cfg: MoEConfig, lr: float = 0.1):
    """(params, x, target) -> (new_params, loss): regression toy task
    exercising routing + expert grads end to end."""
    import jax
    import jax.numpy as jnp

    def loss_fn(params, x, target):
        out, aux = forward(params, x, cfg)
        return jnp.mean((out - target) ** 2) + aux

    def step(params, x, target):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, target)
        new_params = jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                            params, grads)
        return new_params, loss

    return step
