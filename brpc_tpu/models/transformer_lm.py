"""TransformerLM — the long-context flagship model family.

A decoder-only transformer built TPU-first:

- **bfloat16 matmuls on the MXU**: weights/activations cast to bf16 at
  the matmul boundary, accumulation in fp32;
- **sequence parallelism**: attention runs through
  :mod:`brpc_tpu.parallel.ring_attention` when a mesh axis is given —
  KV blocks rotate around the ring (ICI), so context length scales with
  the number of chips;
- **tensor parallelism**: MLP + attention projections shard on a ``tp``
  axis via ``NamedSharding`` specs (XLA inserts the collectives);
- **rematerialisation**: blocks are wrapped in ``jax.checkpoint`` to
  trade FLOPs for HBM on long sequences;
- static shapes; layers unrolled by default (tiny configs compile per
  depth), or ``scan_layers=True`` stacks the per-layer weights and runs
  one ``lax.scan`` over depth — compile time O(1) in depth for deep
  models.

The capability analogue in the reference is its flagship *service*
workloads (echo/PS); a TPU framework's flagship is a model — this plus
EmbeddingPS cover the dense-compute and sparse-lookup families.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple


class LMConfig:
    def __init__(self, vocab: int = 256, dim: int = 64, heads: int = 4,
                 depth: int = 2, mlp_mult: int = 4, max_seq: int = 256,
                 causal: bool = True, remat: bool = True,
                 lr: float = 0.05, moe_experts: int = 0,
                 moe_capacity: float = 2.0, moe_aux_weight: float = 0.01,
                 moe_top_k: int = 1, use_flash: bool = False,
                 scan_layers: bool = False, attn_impl: str = "auto",
                 kv_heads: Optional[int] = None, rope: bool = True,
                 ffn: str = "gelu", ffn_dim: Optional[int] = None,
                 tie_embed: bool = False, final_norm: bool = False,
                 mixers: Optional[Tuple[str, ...]] = None,
                 ssm_expand: int = 2, ssm_state: int = 16,
                 ssm_conv: int = 4, ssm_dt_rank: Optional[int] = None,
                 norm_eps: float = 1e-6, rope_theta: float = 10000.0,
                 rope_yarn: Optional[Dict[str, float]] = None,
                 q_lora_rank: Optional[int] = None,
                 kv_lora_rank: Optional[int] = None,
                 qk_nope_dim: Optional[int] = None,
                 qk_rope_dim: Optional[int] = None,
                 v_head_dim: Optional[int] = None,
                 ffns: Optional[Tuple[str, ...]] = None,
                 expert_dim: Optional[int] = None, experts_routed: int = 0,
                 experts_held: Optional[Tuple[int, int]] = None,
                 experts_top_k: int = 1, route_scale: float = 1.0,
                 shared_experts: int = 0, shared_dim: Optional[int] = None,
                 head_dim: Optional[int] = None,
                 norm: str = "rms", parallel_block: bool = False,
                 windows: Optional[Tuple[int, ...]] = None,
                 ropes: Optional[Tuple[bool, ...]] = None,
                 rope_pairs: str = "halves", router_bias: bool = True,
                 shared_average: bool = False, fill_span: int = 1024,
                 kda_heads: Optional[int] = None,
                 kda_head_dim: Optional[int] = None, kda_conv: int = 4,
                 passes: int = 1, post_norms: bool = False,
                 exit_threshold: float = 1.0, router_at: str = "ffn",
                 router_scoring: str = "sigmoid",
                 mamba2_heads: Optional[int] = None,
                 mamba2_head_dim: int = 64, mamba2_state: int = 128,
                 mamba2_groups: int = 1, mamba2_norm_groups: int = 1,
                 mamba2_conv: int = 4,
                 mamba2_chunk: int = 256, embed_scale: float = 1.0,
                 residual_scale: float = 1.0,
                 attn_scale: Optional[float] = None,
                 logit_scale: float = 1.0):
        assert head_dim is not None or dim % heads == 0
        hd = dim // heads if head_dim is None else int(head_dim)
        assert not rope or hd % 2 == 0, "head dim must be even for RoPE"
        self.vocab = vocab
        self.dim = dim
        self.heads = heads
        # the block beyond the program's first one (every default is
        # that block): key/value heads shared by groups of query heads,
        # attention with no positional term, a gated FFN of its own
        # width, the embedding table as the unembedding, a final norm,
        # and a per-layer schedule of mixers, "attn", "ssm" (a
        # Mamba-1 state-space layer, models/ssm_mixer.py, whose state
        # is per SEQUENCE, not per token), "mla" (latent attention,
        # models/mla_mixer.py, whose cache is ONE latent row a token)
        # "kda" (a gated delta-rule linear-attention layer,
        # models/kda_mixer.py, whose state is a matrix a head, per
        # SEQUENCE as a state-space layer's) or "mamba2" (a Mamba-2
        # state-space layer, models/mamba2_mixer.py: one scalar decay a
        # head over a matrix state, per SEQUENCE too).
        # The paged serving factories run all of it; every other
        # factory runs the first block only and declines the rest by
        # name (UnsupportedBlock)
        self.kv_heads = heads if kv_heads is None else int(kv_heads)
        assert heads % self.kv_heads == 0
        # a head's size is its own where it is given (heads x head_dim
        # need not be dim: ``wo`` maps it back)
        self.head_dim = hd
        self.rope = bool(rope)
        # a per-layer schedule of attention's reach and of its rotation:
        # ``windows[i]`` > 0 lets position p of layer i attend p -
        # windows[i] < j <= p only (0: every j <= p), ``ropes[i]`` says
        # whether layer i rotates q and k at all.  Window layers' pages
        # are a class of their own (``kv.pages.WindowTable``): given
        # back once every position in them lies behind the window.
        # ``rope_pairs`` "halves" rotates column i with i + hd/2,
        # "interleaved" 2i with 2i + 1
        self.windows = (0,) * depth if windows is None \
            else tuple(int(w) for w in windows)
        assert len(self.windows) == depth and min(self.windows) >= 0
        self.has_window = any(self.windows)
        self.window = max(self.windows)
        assert set(self.windows) <= {0, self.window}, \
            "window layers share one window (one page class)"
        self.ropes = (self.rope,) * depth if ropes is None \
            else tuple(bool(r) for r in ropes)
        assert len(self.ropes) == depth
        if ropes is not None:
            self.rope = any(self.ropes)
        assert rope_pairs in ("halves", "interleaved")
        self.rope_pairs = rope_pairs
        # "rms", or "layer": the mean subtracted, a gain and no bias
        assert norm in ("rms", "layer")
        self.norm = norm
        # attention and feed-forward both read ONE norm of the layer's
        # input and both add to it: x + A(h) + F(h), h = norm(x)
        self.parallel_block = bool(parallel_block)
        # a prompt of a window schedule, or of a looped one, is filled
        # in spans of this many rows (``make_paged_span_fill``), never
        # as one bucket
        self.fill_span = int(fill_span)
        # "gated_silu" / "gated_relu": ``(act(gate) * up) w2``, in the
        # dense feed-forward and in every expert; "relu2": ``relu(h
        # w1)^2 w2``, not gated
        assert ffn in ("gelu", "gated_silu", "gated_relu", "relu2")
        self.ffn = ffn
        self.ffn_dim = dim * mlp_mult if ffn_dim is None else int(ffn_dim)
        self.tie_embed = bool(tie_embed)
        self.final_norm = bool(final_norm)
        # a layer's mixer may be "none": the layer is its feed-forward
        # alone, with that part's one norm (``ffns`` may say "none" of
        # a layer with a mixer, never of one without)
        self.mixers = ("attn",) * depth if mixers is None \
            else tuple(mixers)
        assert len(self.mixers) == depth \
            and set(self.mixers) <= set(MIXER_INITIALS)
        # state layers of either kind: one block a SLOT in the state
        # pool, whatever the context's length
        self.has_state = bool(set(STATE_MIXERS) & set(self.mixers))
        self.has_kda = "kda" in self.mixers
        self.kda_heads = heads if kda_heads is None else int(kda_heads)
        self.kda_head_dim = hd if kda_head_dim is None \
            else int(kda_head_dim)
        self.kda_conv = int(kda_conv)
        # a Mamba-2 layer's widths: ``mamba2_heads`` heads of
        # ``mamba2_head_dim`` (d_inner = their product), a state of
        # ``mamba2_state`` a head row, ``mamba2_groups`` groups of heads
        # sharing B and C, a convolution of ``mamba2_conv`` taps, and
        # prompts scanned in chunks of ``mamba2_chunk`` positions; the
        # gated norm divides each of ``mamba2_norm_groups`` groups of
        # channels by its own RMS (1: all of d_inner at once)
        self.has_mamba2 = "mamba2" in self.mixers
        self.mamba2_heads = 2 * dim // int(mamba2_head_dim) \
            if mamba2_heads is None else int(mamba2_heads)
        self.mamba2_head_dim = int(mamba2_head_dim)
        self.mamba2_state = int(mamba2_state)
        self.mamba2_groups = int(mamba2_groups)
        self.mamba2_norm_groups = int(mamba2_norm_groups)
        self.mamba2_conv = int(mamba2_conv)
        self.mamba2_chunk = int(mamba2_chunk)
        assert self.mamba2_heads % self.mamba2_groups == 0 \
            and self.mamba2_heads * self.mamba2_head_dim \
            % self.mamba2_norm_groups == 0
        # four scalars of the residual stream (Granite's; 1 / None:
        # none): the table's rows times ``embed_scale``; each branch
        # times ``residual_scale`` before the residual adds it;
        # attention's softmax over ``q k^T * attn_scale`` (None: the
        # head size's ``hd^-0.5``); the logits times ``logit_scale``
        self.embed_scale = float(embed_scale)
        self.residual_scale = float(residual_scale)
        self.attn_scale = None if attn_scale is None else float(attn_scale)
        self.logit_scale = float(logit_scale)
        self.norm_eps = float(norm_eps)
        # latent attention: low-rank query and key/value paths (the
        # query's direct, one ``wq``, where ``q_lora_rank`` is None), a
        # rotary part of the head apart from the rest (not rotated
        # where ``ropes`` says so: the layer then has no positional
        # term), YaRN-scaled frequencies (``rope_yarn``: factor,
        # original_max, beta_fast, beta_slow, mscale, mscale_all_dim)
        # and the softmax scale they bring.  ``heads`` latent heads
        # need not divide ``dim``
        self.has_latent = "mla" in self.mixers
        self.rope_theta = float(rope_theta)
        self.rope_yarn = dict(rope_yarn) if rope_yarn else None
        self.q_lora_rank, self.kv_lora_rank = q_lora_rank, kv_lora_rank
        self.qk_nope_dim, self.qk_rope_dim = qk_nope_dim, qk_rope_dim
        self.v_head_dim = v_head_dim
        if self.has_latent:
            assert all(w and int(w) > 0 for w in (
                kv_lora_rank, qk_nope_dim, qk_rope_dim, v_head_dim)) \
                and (q_lora_rank is None or int(q_lora_rank) > 0), \
                "an mla mixer needs its widths"
            assert qk_rope_dim % 2 == 0
            if len({self.ropes[i] for i, m in enumerate(self.mixers)
                    if m == "mla"}) > 1:
                raise UnsupportedBlock(
                    "latent layers that rotate beside latent layers "
                    "that do not are not served")
            # one rotation a program (``_rope_at``): the latent one
            assert not (self.rope and "attn" in self.mixers), \
                "rotary 'attn' layers beside 'mla' layers are not served"
        # a per-layer feed-forward schedule, "dense" (the block's FFN)
        # or "experts" (models/moe.py ``serve``: routed experts without
        # drops beside shared ones).  ``experts_held`` is the range of
        # routed expert ids THIS program holds: it routes over all
        # ``experts_routed`` and adds its own experts' part
        # ("none": the layer is its mixer alone).
        self.ffns = ("dense",) * depth if ffns is None else tuple(ffns)
        assert len(self.ffns) == depth \
            and set(self.ffns) <= {"dense", "experts", "none"}
        assert ("none", "none") not in zip(self.mixers, self.ffns), \
            "a layer has a mixer, a feed-forward or both"
        self.has_experts = "experts" in self.ffns
        self.expert_dim = expert_dim
        self.experts_routed = int(experts_routed)
        self.experts_held = (0, self.experts_routed) \
            if experts_held is None else tuple(int(e) for e in experts_held)
        self.experts_top_k = int(experts_top_k)
        self.route_scale = float(route_scale)
        self.shared_experts = int(shared_experts)
        # the shared experts' one MLP is ``shared_experts * expert_dim``
        # wide unless it has a width of its own
        self.shared_dim = self.shared_experts * int(expert_dim or 0) \
            if shared_dim is None else int(shared_dim)
        # a router without a correction bias (its leaf is absent, not a
        # zero that is added), shared experts averaged and not summed
        self.router_bias = bool(router_bias)
        self.shared_average = bool(shared_average)
        # where an expert layer's router reads and what makes scores of
        # its logits.  ``router_at`` "ffn": the normed rows the experts
        # are fed (behind attention); "layer_input": the layer's INPUT,
        # before its first norm and before attention, so the choice is
        # made first in the layer and handed past attention
        # (:func:`_early_route`).  ``router_scoring`` "sigmoid", or
        # "softmax" over all routed experts before the choice
        assert router_at in ("ffn", "layer_input")
        assert router_scoring in ("sigmoid", "softmax")
        self.router_at, self.router_scoring = router_at, router_scoring
        if router_at == "layer_input" and (
                set(self.mixers) != {"attn"} or set(self.ffns) != {"experts"}
                or parallel_block or not self.has_window):
            raise UnsupportedBlock(
                "a router that reads the layer's input (router_at "
                "'layer_input') is served in the sequential block of a "
                "window schedule of 'attn' mixers whose every layer has "
                "experts: the paged step and the span fill hand the "
                "choice past attention, no other program does")
        if self.has_experts:
            lo, hi = self.experts_held
            assert expert_dim and 0 <= lo < hi <= self.experts_routed \
                and 1 <= self.experts_top_k <= self.experts_routed
            if set(m for m, f in zip(self.mixers, self.ffns)
                   if f == "experts") - {"mla", "attn", "kda", "mamba2",
                                         "none"}:
                raise UnsupportedBlock(
                    "an expert feed-forward layer is served beside an "
                    "'mla' or an 'attn' mixer, and of the state layers "
                    "beside a 'kda' or a 'mamba2' one: not beside a "
                    "Mamba-1 'ssm' layer")
        if self.has_window or self.parallel_block:
            if set(self.mixers) != {"attn"}:
                raise UnsupportedBlock(
                    "window layers and the parallel block are served "
                    "for a schedule of 'attn' mixers only")
            if self.has_window and self.kv_heads == heads:
                raise UnsupportedBlock(
                    "window layers are served over the grouped page "
                    "layout only (kv_heads < heads)")
        # a looped schedule: the whole stack of layers is run ``passes``
        # times a token, the weights shared by the passes; pass ``t``
        # reads the row pass ``t - 1`` left (the final norm, where the
        # block has one, closes EVERY pass) and attends the keys and
        # values of ITS OWN pass, so a token pins ``passes`` rows a
        # layer and a layer's pool holds ``passes`` times the pages
        # (pass ``t`` of logical page ``p`` lies at ``t * num_pages +
        # p``).  ``post_norms``: a norm of its own on each branch
        # (attention's, the feed-forward's) before the residual adds
        # it.  ``exit_threshold``: an exit gate (``exit_w``, ``exit_b``)
        # lets a row leave at the first pass whose cumulated exit
        # probability reaches it; at 1 that is always the last pass,
        # the only value served: under it rows of one step would stop
        # at different depths
        self.passes = int(passes)
        self.post_norms = bool(post_norms)
        self.exit_threshold = float(exit_threshold)
        assert self.passes >= 1
        if self.exit_threshold < 1.0:
            raise UnsupportedBlock(
                f"exit_threshold {self.exit_threshold} (a token's depth "
                "chosen by the exit gate) is not served: every row of a "
                "step runs all the passes (exit_threshold 1)")
        if self.passes > 1 or self.post_norms:
            if set(self.mixers) != {"attn"} or self.has_experts \
                    or self.has_window or self.parallel_block \
                    or self.kv_heads != heads:
                raise UnsupportedBlock(
                    "more than one pass over the layers, and the norms "
                    "behind attention and feed-forward, are served for "
                    "a schedule of 'attn' mixers over whole heads with "
                    "dense feed-forwards only: not beside a state, "
                    "latent, window or expert layer, nor grouped heads")
        if self.single_part() and (self.has_window or self.parallel_block
                                   or self.passes > 1):
            raise UnsupportedBlock(
                "layers of one part (a mixer alone or a feed-forward "
                "alone) are served in the sequential block without "
                "windows or passes: the paged step, make_prefill and "
                "insert walk them, no span fill does")
        self.ssm_inner = int(ssm_expand) * dim
        self.ssm_state = int(ssm_state)
        self.ssm_conv = int(ssm_conv)
        self.ssm_dt_rank = -(-dim // 16) if ssm_dt_rank is None \
            else int(ssm_dt_rank)
        self.depth = depth
        self.mlp_mult = mlp_mult
        self.max_seq = max_seq
        self.causal = causal
        self.remat = remat
        self.lr = lr
        # moe_experts > 0 swaps the dense MLP for a Mixture-of-Experts
        # FFN (models/moe.py): sparse compute, experts shardable over
        # the tp axis (expert parallelism)
        self.moe_experts = moe_experts
        self.moe_capacity = moe_capacity
        self.moe_aux_weight = moe_aux_weight
        self.moe_top_k = moe_top_k
        # single-device attention: "auto" picks dense (XLA-fused) vs
        # the Pallas flash kernel by sequence length
        # (ops/flash_attention.py attention()); use_flash=True forces
        # the kernel (back-compat); the sp path keeps ring attention
        self.use_flash = use_flash
        self.attn_impl = attn_impl
        # scan_layers stacks per-layer weights and runs one lax.scan
        # over the depth axis: trace/compile time is O(1) in depth
        # instead of O(depth) — the XLA-idiomatic deep-model form
        self.scan_layers = scan_layers

    def plain_block(self) -> bool:
        """True for the program's first block, the only one the
        training, contiguous, scanned and export factories run."""
        return (set(self.mixers) == {"attn"} and not self.has_experts
                and self.norm_eps == 1e-6
                and self.kv_heads == self.heads
                and self.rope and self.ffn == "gelu"
                and self.ffn_dim == self.dim * self.mlp_mult
                and set(self.ffns) == {"dense"}
                and not self.tie_embed and not self.final_norm
                and self.head_dim * self.heads == self.dim
                and not self.has_window and all(self.ropes)
                and self.rope_pairs == "halves" and self.norm == "rms"
                and not self.parallel_block
                and self.rope_theta == 10000.0
                and self.passes == 1 and not self.post_norms
                and self.embed_scale == 1.0 and self.residual_scale == 1.0
                and self.attn_scale is None and self.logit_scale == 1.0)

    def single_part(self) -> bool:
        """True where some layer is a mixer alone or a feed-forward
        alone (``"none"`` in ``mixers`` or ``ffns``)."""
        return "none" in self.mixers or "none" in self.ffns

    def layer_parts(self) -> Dict[str, Dict[str, int]]:
        """The layers by their parts: ``mixer_only`` and ``ffn_only``
        by kind, ``both`` by ``"<mixer>+<ffn>"``, each kind with its
        number of layers."""
        parts: Dict[str, Dict[str, int]] = {
            "mixer_only": {}, "ffn_only": {}, "both": {}}
        for m, f in zip(self.mixers, self.ffns):
            part, kind = ("ffn_only", f) if m == "none" else \
                ("mixer_only", m) if f == "none" else ("both", f"{m}+{f}")
            parts[part][kind] = parts[part].get(kind, 0) + 1
        return parts

    def window_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, w in enumerate(self.windows) if w)

    def window_pages(self, slots: int, page: int) -> int:
        """Pages of the window class for ``slots`` sessions: the window
        and a page on either side of it for each, one span of a fill in
        flight, and the garbage page."""
        return slots * (self.window // page + 2) \
            + -(-self.fill_span // page) + 1

    def schedule(self) -> str:
        """The mixers' initials in layer order (``"sass"``;
        :data:`MIXER_INITIALS`)."""
        return "".join(MIXER_INITIALS[m] for m in self.mixers)

    def ffn_schedule(self) -> str:
        """The feed-forward parts' initials in layer order
        (``"dee"``)."""
        return "".join(f[0] for f in self.ffns)

    def attn_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, m in enumerate(self.mixers) if m == "attn")

    def mla_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, m in enumerate(self.mixers) if m == "mla")

    def state_layers(self) -> Tuple[int, ...]:
        """The layers that hold a block of the state pool, of either
        kind."""
        return tuple(i for i, m in enumerate(self.mixers)
                     if m in STATE_MIXERS)

    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, f in enumerate(self.ffns) if f == "experts")

    def latent_row(self) -> int:
        """Values one token keeps in one latent layer: the normed
        latent and the rotated shared key part."""
        return self.kv_lora_rank + self.qk_rope_dim

    def latent_row_padded(self) -> int:
        """A latent pool's row as it lies: the values, then zeros up to
        the next multiple of 128 lanes (what the device's tiled layout
        takes anyway, and what lets a page be one DMA)."""
        return -(-self.latent_row() // 128) * 128

    def expert_cfg(self):
        from .moe import ExpertConfig
        return ExpertConfig(
            dim=self.dim, hidden=self.expert_dim,
            routed=self.experts_routed, held=self.experts_held,
            top_k=self.experts_top_k, route_scale=self.route_scale,
            shared=self.shared_experts, bias=self.router_bias,
            shared_scale=1.0 / self.shared_experts
            if self.shared_average else 1.0,
            scoring=self.router_scoring,
            act={"gated_relu": "relu", "relu2": "relu2"}.get(self.ffn,
                                                               "silu"),
            shared_dim=self.shared_dim)

    def moe_cfg(self):
        from .moe import MoEConfig
        return MoEConfig(dim=self.dim, hidden=self.dim * self.mlp_mult,
                         num_experts=self.moe_experts,
                         capacity_factor=self.moe_capacity,
                         aux_loss_weight=self.moe_aux_weight,
                         top_k=self.moe_top_k)


class UnsupportedBlock(NotImplementedError):
    """A factory was asked for a block it does not run: state layers,
    grouped key/value heads, attention without rotary, the gated FFN,
    a tied table or a final norm outside the paged serving factories.
    Nothing runs such a model wrong silently."""


# the mixer kinds whose state is one block a SLOT of the state pool
STATE_MIXERS = ("ssm", "kda", "mamba2")

# each mixer kind's letter in ``LMConfig.schedule()`` (a Mamba-2
# layer's is "M": "m" is latent attention's; a layer without a mixer
# "-")
MIXER_INITIALS = {"attn": "a", "ssm": "s", "mla": "m", "kda": "k",
                  "mamba2": "M", "none": "-"}


def _state_mixer(kind: str):
    """The module of a state layer's kind: each has ``init_layer``,
    ``state_shapes``, ``state_bytes``, ``prefill(cfg, bp, x, ctx_len)``
    and ``step(cfg, bp, x, state, tail, active, lens)``."""
    from . import kda_mixer, mamba2_mixer, ssm_mixer
    return {"ssm": ssm_mixer, "kda": kda_mixer,
            "mamba2": mamba2_mixer}[kind]


def require_plain_block(cfg: LMConfig, what: str) -> None:
    """Raise :class:`UnsupportedBlock` naming the path ``what`` unless
    ``cfg`` is the program's first block."""
    if cfg.plain_block():
        return
    why = "state layers (per-sequence recurrent state)" if cfg.has_state \
        else "layers of one part (a mixer alone or a feed-forward " \
        "alone)" if cfg.single_part() \
        else "latent attention (an 'mla' mixer and its latent cache)" \
        if cfg.has_latent \
        else "window layers (a page class that gives pages back)" \
        if cfg.has_window \
        else "a looped schedule (the layers run several times a token, " \
        "each pass with pages of its own)" if cfg.passes > 1 \
        else "a block other than MHA + rotary + GELU MLP + untied table"
    raise UnsupportedBlock(
        f"{what} declines {why}: only the paged serving factories "
        "(make_paged_batch_decode, make_paged_io) run it")


def init_params(rng, cfg: LMConfig) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    if not cfg.plain_block():
        return _init_block_params(rng, cfg)
    ks = jax.random.split(rng, 2 + cfg.depth)
    scale = 1.0 / math.sqrt(cfg.dim)
    params: Dict[str, Any] = {
        "embed": jax.random.normal(ks[0], (cfg.vocab, cfg.dim),
                                   jnp.float32) * scale,
        "unembed": jax.random.normal(ks[1], (cfg.dim, cfg.vocab),
                                     jnp.float32) * scale,
    }
    for i in range(cfg.depth):
        bk = jax.random.split(ks[2 + i], 6)
        h = cfg.dim * cfg.mlp_mult
        blk = {
            "wqkv": jax.random.normal(bk[0], (cfg.dim, 3 * cfg.dim),
                                      jnp.float32) * scale,
            "wo": jax.random.normal(bk[1], (cfg.dim, cfg.dim),
                                    jnp.float32) * scale,
            "ln1": jnp.ones((cfg.dim,), jnp.float32),
            "ln2": jnp.ones((cfg.dim,), jnp.float32),
        }
        if cfg.moe_experts > 0:
            from .moe import init_params as moe_init
            blk["moe"] = moe_init(bk[2], cfg.moe_cfg())
        else:
            blk["w1"] = jax.random.normal(bk[2], (cfg.dim, h),
                                          jnp.float32) * scale
            blk["w2"] = jax.random.normal(
                bk[3], (h, cfg.dim), jnp.float32) * (scale / cfg.mlp_mult)
        params[f"blk{i}"] = blk
    if cfg.scan_layers:
        # stack per-layer trees along a leading depth axis for lax.scan
        blks = [params.pop(f"blk{i}") for i in range(cfg.depth)]
        params["blocks"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *blks)
    return params


def _init_block_params(rng, cfg: LMConfig) -> Dict[str, Any]:
    """Seeded weights of a block beyond the first (see ``LMConfig``):
    matrices normal at ``1/sqrt(fan_in)``, norms one.  ``wqkv`` holds
    ``heads`` query and ``2 * kv_heads`` key/value heads side by side;
    a gated FFN's ``w1`` holds gate and up side by side."""
    import jax
    import jax.numpy as jnp

    from . import mla_mixer, moe

    if cfg.scan_layers or cfg.moe_experts > 0:
        raise UnsupportedBlock(
            "scan_layers and MoE run the program's first block only")
    d, hd, f = cfg.dim, cfg.head_dim, cfg.ffn_dim

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

    ks = jax.random.split(rng, 2 + cfg.depth)
    params: Dict[str, Any] = {"embed": normal(ks[0], (cfg.vocab, d), d)}
    if not cfg.tie_embed:
        params["unembed"] = normal(ks[1], (d, cfg.vocab), d)
    if cfg.final_norm:
        params["norm_f"] = jnp.ones((d,), jnp.float32)
    if cfg.passes > 1:
        # the exit gate of a looped schedule (see ``LMConfig``)
        params["exit_w"] = normal(ks[1], (d,), d)
        params["exit_b"] = jnp.zeros((), jnp.float32)
    gated = cfg.ffn not in ("gelu", "relu2")
    for i in range(cfg.depth):
        bk = jax.random.split(ks[2 + i], 5)
        # a part the layer does not have has no leaves, its norm none
        blk = {}
        if cfg.mixers[i] in STATE_MIXERS:
            blk = _state_mixer(cfg.mixers[i]).init_layer(bk[0], cfg)
        elif cfg.mixers[i] == "mla":
            blk = mla_mixer.init_layer(bk[0], cfg)
        elif cfg.mixers[i] == "attn":
            blk = {"wqkv": normal(
                bk[0], (d, (cfg.heads + 2 * cfg.kv_heads) * hd), d),
                "wo": normal(bk[1], (cfg.heads * hd, d), cfg.heads * hd)}
        if cfg.mixers[i] != "none":
            blk["ln1"] = jnp.ones((d,), jnp.float32)
        if not cfg.parallel_block and cfg.ffns[i] != "none":
            blk["ln2"] = jnp.ones((d,), jnp.float32)
        if cfg.post_norms:
            blk["pn1"] = jnp.ones((d,), jnp.float32)
            blk["pn2"] = jnp.ones((d,), jnp.float32)
        if cfg.ffns[i] == "experts":
            blk["moe"] = moe.init_served(bk[2], cfg.expert_cfg())
        elif cfg.ffns[i] == "dense":
            blk["w1"] = normal(bk[2], (d, 2 * f if gated else f), d)
            blk["w2"] = normal(bk[3], (f, d), f)
        params[f"blk{i}"] = blk
    return params


def jit_with_params(fn, params, donate_argnums=()):
    """``jax.jit`` a ``fn(params, *args)`` program and bind ``params``
    OUTSIDE the jit, returning a ``functools.partial`` callers invoke
    as ``f(*args)`` — the one way serving code holds a compiled
    program.  The weights enter the executable as ARGUMENTS: an array
    closed over inside ``jit`` lowers to a ``stablehlo.constant``, so
    every program (each prefill bucket, the step, the chunk slice)
    would embed its own copy of the weights — gigabyte modules, one
    HBM copy per executable, and a compile-cache key that hashes the
    weight values.  ``donate_argnums`` index ``*args`` (the bound
    params are never donated).  ``.func`` is the jitted program and
    ``.args[0]`` the params, for callers that lower it."""
    import functools

    import jax

    fn_j = jax.jit(fn, donate_argnums=tuple(i + 1 for i in donate_argnums))
    return functools.partial(fn_j, params)


def _rmsnorm(x, g, eps: float = 1e-6):
    import jax.numpy as jnp
    return x * g / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _norm(cfg: "LMConfig", x, g):
    """The block's norm: :func:`_rmsnorm`, or the mean-subtracting one
    (a gain, no bias) where ``cfg.norm`` says ``"layer"``."""
    import jax.numpy as jnp
    if cfg.norm == "rms":
        return _rmsnorm(x, g, cfg.norm_eps)
    c = x - jnp.mean(x, axis=-1, keepdims=True)
    return c * g / jnp.sqrt(jnp.mean(c * c, axis=-1, keepdims=True)
                            + cfg.norm_eps)


def _rope_tables(seq: int, head_dim: int):
    """sin/cos tables for rotary embedding, shaped (1, s, 1, d/2).
    Built once per forward and passed into every block so remat regions
    cover only the matmuls, not the table computation."""
    import jax.numpy as jnp
    half = head_dim // 2
    pos = jnp.arange(seq, dtype=jnp.float32)[None, :, None, None]
    freq = jnp.exp(-math.log(10000.0)
                   * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos * freq[None, None, None, :]
    return jnp.sin(ang), jnp.cos(ang)


def _rope(x, sin, cos):
    """Rotary position embedding — static shapes, fused by XLA."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1)


def _rope_pairs(x, sin, cos):
    """:func:`_rope` with the pairs interleaved: column ``2i`` turns
    with ``2i + 1``."""
    import jax.numpy as jnp
    p = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = p[..., 0], p[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _split_qkv(cfg: LMConfig, qkv):
    """The fused projection's columns: ``heads`` query heads, then
    ``kv_heads`` key and as many value heads."""
    import jax.numpy as jnp
    nq = cfg.heads * cfg.head_dim
    nkv = cfg.kv_heads * cfg.head_dim
    return jnp.split(qkv, [nq, nq + nkv], axis=-1)


def _ffn(cfg: LMConfig, bp, h):
    """The feed-forward part of every serving layer: ``gelu(h w1) w2``;
    gated, ``(act(gate) * up) w2`` with gate and up side by side in
    ``w1``, ``act`` the SiLU or the ReLU; ``relu(h w1)^2 w2``; or the
    Mixture-of-Experts FFN (models/moe.py)."""
    import jax
    import jax.numpy as jnp

    # every weight matmul goes through qmatmul: plain arrays take the
    # usual bf16 path, QuantTensors (quantize_lm_params) stream int8
    # weights — the serving win, since single-token decode is bound by
    # weight bytes read per step, not FLOPs (ops/quant.py)
    from ..ops.quant import qmatmul
    if cfg.moe_experts > 0:
        from .moe import forward_grouped
        out, _aux = forward_grouped(bp["moe"], h, cfg.moe_cfg())
        return out
    up = qmatmul(h, bp["w1"])
    if cfg.ffn == "relu2":
        return qmatmul(jnp.square(jax.nn.relu(up)), bp["w2"])
    if cfg.ffn != "gelu":
        gate, up = jnp.split(up, 2, axis=-1)
        act = jax.nn.silu if cfg.ffn == "gated_silu" else jax.nn.relu
        return qmatmul(act(gate) * up, bp["w2"])
    return qmatmul(jax.nn.gelu(up), bp["w2"])


def _ffn_residual(cfg: LMConfig, bp, x):
    """The second half of a serving layer, after either mixer: norm,
    :func:`_ffn`, residual (where the block has post-norms the branch
    is normed, ``pn2``, before the residual adds it)."""
    out = _ffn(cfg, bp, _norm(cfg, x, bp["ln2"]))
    if cfg.post_norms:
        out = _norm(cfg, out, bp["pn2"])
    return _residual(cfg, x, out)


def _residual(cfg: LMConfig, x, *branches):
    """The residual: ``x`` plus each branch, the branches times
    ``cfg.residual_scale`` where the block scales them."""
    if cfg.residual_scale == 1.0:
        for out in branches:
            x = x + out
        return x
    return x + cfg.residual_scale * sum(branches)


def _ffn_part(cfg: LMConfig, i: int, bp, h, live, routed=None):
    """Layer ``i``'s feed-forward by the schedule (``LMConfig.ffns``)
    for normed rows ``h`` ``(b, w, dim)``: :func:`_ffn`, or the expert
    layer (``moe.serve``) over the rows that are ``live`` ``(b, w)``,
    with the choice ``routed`` where the layer made it at its top
    (:func:`_early_route`).  Returns ``(out, counts)``, ``counts`` the
    expert layer's or None."""
    if cfg.ffns[i] != "experts":
        return _ffn(cfg, bp, h), None
    from . import moe
    b, w, d = h.shape
    out, counts = moe.serve(bp["moe"], h.reshape(b * w, d),
                            cfg.expert_cfg(), live.reshape(b * w), routed)
    return out.reshape(b, w, d), counts


def _ffn_scheduled(cfg: LMConfig, i: int, bp, x, live, routed=None):
    """Layer ``i``'s second half: norm, :func:`_ffn_part`, residual;
    ``x`` as it is where the layer has no feed-forward.  Returns ``(x,
    counts)``."""
    if cfg.ffns[i] == "none":
        return x, None
    if cfg.ffns[i] != "experts":
        return _ffn_residual(cfg, bp, x), None
    out, counts = _ffn_part(cfg, i, bp, _norm(cfg, x, bp["ln2"]), live,
                            routed)
    return _residual(cfg, x, out), counts


def _early_route(cfg: LMConfig, bp, x):
    """The choice of a layer's experts where the block's router reads
    the layer's INPUT (``LMConfig.router_at`` ``"layer_input"``): ``(ids,
    w)`` (``moe.route``) of the rows ``x`` ``(b, w, dim)`` as they
    enter the layer, un-normed; made FIRST in the layer and handed past
    attention to :func:`_attn_out`, so nothing of it waits for, or
    depends on, the layer's attention.  None where the router reads
    what the experts read.  ``moe_route`` in a device trace."""
    if cfg.router_at != "layer_input":
        return None
    import jax

    from . import moe
    with jax.named_scope("moe_route"):
        return moe.route(bp["moe"], x.reshape(-1, cfg.dim),
                         cfg.expert_cfg())


def _embed_rows(cfg: LMConfig, params, ids):
    """The table's rows as the float32 residual (a table stored in
    bfloat16 is widened here, once), times ``cfg.embed_scale`` where
    the block scales them."""
    import jax.numpy as jnp
    x = params["embed"][ids]
    x = x if x.dtype == jnp.float32 else x.astype(jnp.float32)
    return x if cfg.embed_scale == 1.0 else x * cfg.embed_scale


def _rope_at(cfg: LMConfig, pos):
    """sin/cos of the rotary embedding at the positions ``pos``,
    anything that broadcasts to a program's ``(b, w)``: a scalar (one
    stream's step), ``(b, 1)`` (a step of slots at their own depths),
    ``(w,)`` (a prompt, a chunk), ``(b, w)`` (a span a slot);
    None where the block has no rotary.  The math of
    :func:`_rope_tables`, so a slice rotates as the whole prompt does;
    the serving rotation's one home.  A program makes them once and
    hands them to every layer, as ``make_forward`` does its tables."""
    import jax.numpy as jnp
    if not cfg.rope:
        return None
    if cfg.has_latent:
        # the rotary part of a latent head, at its own frequencies
        from . import mla_mixer
        return mla_mixer.rotation(cfg, pos)
    half = cfg.head_dim // 2
    freq = jnp.exp(-math.log(cfg.rope_theta)
                   * jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.asarray(pos).astype(jnp.float32)[..., None, None] * freq
    return jnp.sin(ang), jnp.cos(ang)


def _qkv(cfg: LMConfig, bp, x, rot):
    """The first half of a serving attention layer for ``x`` ``(b, w,
    dim)``: norm, the fused projection, split into ``heads`` query and
    ``kv_heads`` key and value heads, rotated by ``rot``
    (:func:`_rope_at`'s) where the block has rotary.  What a program
    does with them (where the new rows are written, how attention
    reaches the cached ones) is its own."""
    from ..ops.quant import qmatmul
    b, w, _ = x.shape
    q, k, v = _split_qkv(cfg, qmatmul(_norm(cfg, x, bp["ln1"]),
                                      bp["wqkv"]))
    q = q.reshape(b, w, cfg.heads, cfg.head_dim)
    k = k.reshape(b, w, cfg.kv_heads, cfg.head_dim)
    v = v.reshape(b, w, cfg.kv_heads, cfg.head_dim)
    if rot is not None:
        turn = _rope if cfg.rope_pairs == "halves" else _rope_pairs
        q = turn(q, *rot)
        k = turn(k, *rot)
    return q, k, v


def _attn_out(cfg: LMConfig, bp, x, att, i: int = 0, live=None,
              routed=None):
    """The second half of a serving attention layer ``i``: the attended
    values ``att`` (``(b, w, heads, hd)``, or a step's ``(b, heads,
    hd)`` as its kernel returns them) through ``wo``, residual, then
    the layer's feed-forward (:func:`_ffn_scheduled`; ``live`` ``(b,
    w)`` are the rows an expert layer routes, ``routed`` their choice
    where :func:`_early_route` made it).  In the parallel block
    the feed-forward reads the norm attention read, and both add to
    the layer's input.  Returns ``(x, counts)``, ``counts`` an expert
    layer's or None."""
    from ..ops.quant import qmatmul
    b, w, _ = x.shape
    a = qmatmul(att.reshape(b, w, cfg.heads * cfg.head_dim), bp["wo"])
    if cfg.post_norms:
        a = _norm(cfg, a, bp["pn1"])
    if cfg.parallel_block:
        # the same norm :func:`_qkv` took: one in the compiled program
        m, counts = _ffn_part(cfg, i, bp, _norm(cfg, x, bp["ln1"]), live)
        return _residual(cfg, x, a, m), counts
    return _ffn_scheduled(cfg, i, bp, _residual(cfg, x, a), live, routed)


def _logits(cfg: LMConfig, params, x):
    """Final norm (where the block has one) and the unembedding."""
    if cfg.final_norm:
        x = _norm(cfg, x, params["norm_f"])
    return _unembed(cfg, params, x)


def _unembed(cfg: LMConfig, params, x):
    """The unembedding, the embedding table itself where it is tied;
    the logits times ``cfg.logit_scale`` where the block scales them."""
    from ..ops.quant import qmatmul
    out = qmatmul(x, params["embed"].T if cfg.tie_embed
                  else params["unembed"])
    return out if cfg.logit_scale == 1.0 else out * cfg.logit_scale


def _looped(cfg: LMConfig, params, cache, x, layer):
    """A looped schedule's layers (``LMConfig.passes``): the stack run
    ``cfg.passes`` times as ONE loop of the compiled program (a body
    of ``depth`` layers iterated with the pass as a value; ``lm_pass``
    in a device trace), the pools carried through it and written in
    place.  ``layer(i, bp, x, pk, pv, off) -> (x, pk, pv)`` is the
    calling program's attention layer ``i``, ``off`` the first page of
    the pass in every pool (``t * num_pages``: what shifts a block
    table into the pass's pages, its garbage page included).  The
    final norm closes every pass, so the row that leaves the last one
    goes to :func:`_unembed` as it is.  Returns ``(x, cache)``."""
    import jax

    n_pages = cache["pk0"].shape[0] // cfg.passes
    pools = {name: pool for name, pool in cache.items() if name != "len"}

    def one_pass(t, carry):
        x, pools = carry
        pools = dict(pools)
        for i in range(cfg.depth):
            x, pools[f"pk{i}"], pools[f"pv{i}"] = layer(
                i, params[f"blk{i}"], x, pools[f"pk{i}"], pools[f"pv{i}"],
                t * n_pages)
        if cfg.final_norm:
            x = _norm(cfg, x, params["norm_f"])
        return x, pools

    with jax.named_scope("lm_pass"):
        x, pools = jax.lax.fori_loop(0, cfg.passes, one_pass, (x, pools))
    return x, {**cache, **pools}


def make_forward(cfg: LMConfig, mesh=None, sp_axis: Optional[str] = None):
    """Forward fn: (params, ids[b, s]) -> logits[b, s, vocab].
    With ``mesh`` + ``sp_axis``, attention is ring attention over the
    mesh axis (sequence-parallel long context)."""
    import jax
    import jax.numpy as jnp

    require_plain_block(cfg, "make_forward (training)")
    if mesh is not None and sp_axis is not None:
        from ..parallel.ring_attention import make_ring_attention
        attend = make_ring_attention(mesh, sp_axis, causal=cfg.causal)
    else:
        from ..ops.flash_attention import attention
        impl = "flash" if cfg.use_flash else cfg.attn_impl

        def attend(q, k, v):
            # seq-adaptive: XLA-fused dense below the crossover, the
            # Pallas flash kernel above (each where it measures faster)
            return attention(q, k, v, causal=cfg.causal, impl=impl)

    if cfg.moe_experts > 0:
        from .moe import forward_grouped as moe_forward
        moe_cfg = cfg.moe_cfg()

    def block(bp, x, sin, cos):
        b, s, _ = x.shape
        h = _rmsnorm(x, bp["ln1"])
        qkv = (h.astype(jnp.bfloat16) @ bp["wqkv"].astype(jnp.bfloat16)
               ).astype(jnp.float32)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shp = (b, s, cfg.heads, cfg.dim // cfg.heads)
        q, k = (_rope(t.reshape(shp), sin, cos) for t in (q, k))
        v = v.reshape(shp)
        att = attend(q, k, v).reshape(b, s, cfg.dim)
        x = x + (att.astype(jnp.bfloat16) @ bp["wo"].astype(jnp.bfloat16)
                 ).astype(jnp.float32)
        h = _rmsnorm(x, bp["ln2"])
        if cfg.moe_experts > 0:
            # grouped routing: each batch row routes independently, so
            # dispatch stays linear in tokens and dp-local (moe.py)
            out, aux = moe_forward(bp["moe"], h, moe_cfg)
            return x + out, aux
        up = (h.astype(jnp.bfloat16) @ bp["w1"].astype(jnp.bfloat16))
        return x + (jax.nn.gelu(up.astype(jnp.float32)).astype(jnp.bfloat16)
                    @ bp["w2"].astype(jnp.bfloat16)
                    ).astype(jnp.float32), jnp.float32(0.0)

    if cfg.remat:
        block = jax.checkpoint(block)

    def forward(params, ids, with_aux: bool = False):
        assert ids.shape[-1] <= cfg.max_seq, (
            f"seq {ids.shape[-1]} exceeds max_seq {cfg.max_seq}")
        x = params["embed"][ids]
        sin, cos = _rope_tables(ids.shape[-1], cfg.dim // cfg.heads)
        if cfg.scan_layers:
            def body(x, bp):
                x, aux = block(bp, x, sin, cos)
                return x, aux

            x, auxs = jax.lax.scan(body, x, params["blocks"])
            aux_total = auxs.sum()
        else:
            aux_total = jnp.float32(0.0)
            for i in range(cfg.depth):
                x, aux = block(params[f"blk{i}"], x, sin, cos)
                aux_total = aux_total + aux
        logits = (x.astype(jnp.bfloat16)
                  @ params["unembed"].astype(jnp.bfloat16)).astype(
                      jnp.float32)
        return (logits, aux_total) if with_aux else logits

    return forward


def _prefill_attn_layer(cfg: LMConfig, bp, x, rot, i: int = 0, live=None):
    """Attention layer ``i`` of prompt processing, the one home of every
    serving prefill's: returns (x, k, v) with k/v (``kv_heads`` of
    them) written into fresh max_seq caches.  ``live`` ``(b, s)`` are
    the rows an expert layer routes (a bucket's padding is not)."""
    import jax
    import jax.numpy as jnp

    b = x.shape[0]
    q, k, v = _qkv(cfg, bp, x, rot)
    kc = jnp.zeros((b, cfg.max_seq, cfg.kv_heads, cfg.head_dim),
                   jnp.float32)
    vc = jnp.zeros((b, cfg.max_seq, cfg.kv_heads, cfg.head_dim),
                   jnp.float32)
    kc = jax.lax.dynamic_update_slice(kc, k, (0, 0, 0, 0))
    vc = jax.lax.dynamic_update_slice(vc, v, (0, 0, 0, 0))
    if cfg.kv_heads != cfg.heads:
        k, v = (jnp.repeat(t, cfg.heads // cfg.kv_heads, axis=2)
                for t in (k, v))
    # seq-adaptive: long prompts prefill through the flash kernel
    # (O(s) memory) instead of materializing (s, s) scores per
    # layer — honoring the same impl override as make_forward
    from ..ops.flash_attention import attention
    impl = "flash" if cfg.use_flash else cfg.attn_impl
    att = attention(q, k, v, causal=cfg.causal, impl=impl,
                    scale=cfg.attn_scale)
    return _attn_out(cfg, bp, x, att, i, live)[0], kc, vc


def make_prefill(cfg: LMConfig):
    """The serving engine's prompt pass, for every ``LMConfig`` it
    serves: ``prefill(params, ids[1, s], ctx_len) -> (cache, logits)``.
    ``ids`` is a zero-padded bucket and ``ctx_len`` its true length: a
    causal attention forgives the padding, a recurrence does not, so a
    state layer of either kind returns its state AT ``ctx_len``
    (``h<i>`` and the convolution's tail ``c<i>``), an attention layer
    ``k<i>``/``v<i>``
    as :func:`make_decode`'s does, a latent layer its latent rows
    ``l<i>``; the logits are those of position ``ctx_len - 1``.  Window
    layers and looped schedules decline: their prompts go through the
    pages (:func:`make_paged_span_fill`)."""
    import jax.numpy as jnp

    from . import mla_mixer

    if cfg.passes > 1:
        def declined(*_a, **_k):
            raise UnsupportedBlock(
                "make_prefill (a whole prompt into a max_seq cache) "
                f"declines a schedule of {cfg.passes} passes: "
                + _looped_stripes(cfg))
        return declined
    if cfg.has_window:
        def declined(*_a, **_k):
            raise UnsupportedBlock(
                "make_prefill (a whole prompt into a max_seq cache) "
                "declines window layers: their prompts are filled in "
                "spans through the pages (make_paged_span_fill)")
        return declined

    def prefill(params, ids, ctx_len):
        b, s = ids.shape
        assert b == 1 and s <= cfg.max_seq
        x = _embed_rows(cfg, params, ids)
        rot = _rope_at(cfg, jnp.arange(s))
        cache = {"len": jnp.int32(s)}
        for i in range(cfg.depth):
            bp = params[f"blk{i}"]
            if cfg.mixers[i] == "none":
                # a layer of its feed-forward alone
                x, _counts = _ffn_scheduled(
                    cfg, i, bp, x, (jnp.arange(s) < ctx_len)[None])
            elif cfg.mixers[i] in STATE_MIXERS:
                # (a dense feed-forward's ``_ffn_scheduled`` is
                # ``_ffn_residual``; the bucket's padding is routed to
                # no expert)
                out, h, tail = _state_mixer(cfg.mixers[i]).prefill(
                    cfg, bp, _rmsnorm(x, bp["ln1"], cfg.norm_eps), ctx_len)
                x, _counts = _ffn_scheduled(
                    cfg, i, bp, _residual(cfg, x, out),
                    (jnp.arange(s) < ctx_len)[None])
                cache[f"h{i}"], cache[f"c{i}"] = h, tail
            elif cfg.mixers[i] == "mla":
                # a latent layer returns the rows its tokens cache
                # (``l<i>``); the bucket's padding is routed nowhere
                out, cache[f"l{i}"] = mla_mixer.prefill(
                    cfg, bp, _rmsnorm(x, bp["ln1"], cfg.norm_eps),
                    rot if cfg.ropes[i] else None)
                x, _counts = _ffn_scheduled(
                    cfg, i, bp, _residual(cfg, x, out),
                    (jnp.arange(s) < ctx_len)[None])
            else:
                x, kc, vc = _prefill_attn_layer(
                    cfg, bp, x, rot if cfg.ropes[i] else None, i,
                    (jnp.arange(s) < ctx_len)[None])
                cache[f"k{i}"], cache[f"v{i}"] = kc, vc
        last = jnp.take(x, jnp.maximum(ctx_len - 1, 0), axis=1)
        return cache, _logits(cfg, params, last)

    return prefill


def _looped_stripes(cfg: LMConfig) -> str:
    """Why a looped schedule's prompt is not prefilled whole: the bytes
    of a ``max_seq`` stripe a (pass, layer), for the decline's text."""
    nbytes = cfg.passes * cfg.depth * 2 * cfg.max_seq * cfg.kv_heads \
        * cfg.head_dim * 4
    return (f"a max_seq stripe of keys and values a (pass, layer) is "
            f"{nbytes:,} bytes for ONE join; its prompts are filled in "
            "spans through the pages (make_paged_span_fill)")


def make_decode(cfg: LMConfig):
    """Autoregressive serving path: static-shape KV cache, one token per
    step — the jit-friendly inference loop (no dynamic shapes: the cache
    is (b, max_seq, heads, hd) from the start, positions masked).

    Returns ``(prefill, decode_step)``:
      - ``prefill(params, ids[b, s]) -> (cache, logits[b, vocab])`` —
        runs the prompt once, fills the cache, returns last-position
        logits;
      - ``decode_step(params, cache, token[b]) -> (cache, logits)`` —
        appends one token (rope at its true position) and attends over
        the cached prefix.  Donate the cache at the jit boundary for
        in-place updates."""
    import jax
    import jax.numpy as jnp

    require_plain_block(cfg, "make_decode (the contiguous cache)")
    hd = cfg.head_dim
    if cfg.scan_layers and cfg.moe_experts > 0:
        raise NotImplementedError(
            "scanned decode does not support MoE blocks — use "
            "scan_layers=False for MoE serving")

    def decode_layer(bp, x, kc, vc, pos, rot):
        """One block of single-token decode; returns (x, kc, vc) with
        this token's k/v written at ``pos``."""
        q, k, v = _qkv(cfg, bp, x, rot)
        kc = jax.lax.dynamic_update_slice(kc, k, (0, pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v, (0, pos, 0, 0))
        # attend the single query over the cached prefix
        s_mat = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                           preferred_element_type=jnp.float32
                           ) / (hd ** 0.5)
        live = jnp.arange(cfg.max_seq) <= pos        # prefix + self
        s_mat = jnp.where(live[None, None, None, :], s_mat, -1e30)
        p = jax.nn.softmax(s_mat, axis=-1)
        att = jnp.einsum("bhqk,bkhd->bqhd", p, vc,
                         preferred_element_type=jnp.float32)
        return _attn_out(cfg, bp, x, att)[0], kc, vc

    def prefill(params, ids):
        b, s = ids.shape
        assert s <= cfg.max_seq
        x = params["embed"][ids]
        rot = _rope_at(cfg, jnp.arange(s))
        if cfg.scan_layers:
            # one compiled layer body regardless of depth — the serving
            # answer to compile-time scaling (the train path's story,
            # make_forward): caches come back stacked (depth, ...)
            def body(x, bp):
                x, kc, vc = _prefill_attn_layer(cfg, bp, x, rot)
                return x, (kc, vc)

            x, (kcs, vcs) = jax.lax.scan(body, x, params["blocks"])
            cache = {"len": jnp.int32(s), "k": kcs, "v": vcs}
            return cache, _logits(cfg, params, x[:, -1])
        cache = {"len": jnp.int32(s)}
        for i in range(cfg.depth):
            x, kc, vc = _prefill_attn_layer(cfg, params[f"blk{i}"], x, rot)
            cache[f"k{i}"], cache[f"v{i}"] = kc, vc
        return cache, _logits(cfg, params, x[:, -1])

    def decode_step(params, cache, token):
        cache = dict(cache)      # never mutate the caller's dict (an
                                 # eager caller may fork it — beam/retry)
        pos = cache["len"]                           # traced scalar
        x = params["embed"][token][:, None, :]       # (b, 1, d)
        rot = _rope_at(cfg, pos)
        if cfg.scan_layers:
            def body(x, layer):
                bp, kc, vc = layer
                x, kc, vc = decode_layer(bp, x, kc, vc, pos, rot)
                return x, (kc, vc)

            x, (kcs, vcs) = jax.lax.scan(
                body, x, (params["blocks"], cache["k"], cache["v"]))
            cache["k"], cache["v"] = kcs, vcs
            cache["len"] = pos + 1
            return cache, _logits(cfg, params, x[:, 0])
        for i in range(cfg.depth):
            x, kc, vc = decode_layer(params[f"blk{i}"], x,
                                     cache[f"k{i}"], cache[f"v{i}"], pos,
                                     rot)
            cache[f"k{i}"], cache[f"v{i}"] = kc, vc
        cache["len"] = pos + 1
        return cache, _logits(cfg, params, x[:, 0])

    return prefill, decode_step


def empty_cache(cfg: LMConfig, batch: int, start_len: int = 1):
    """A fresh KV cache in the layout make_decode's steps expect — the
    model owns this structure; callers (benches, servers pre-allocating
    serving slots) must not hand-roll it.  ``scan_layers`` configs use
    stacked (depth, ...) caches matching the scanned decode."""
    import jax.numpy as jnp
    hd = cfg.dim // cfg.heads
    cache = {"len": jnp.int32(start_len)}
    if cfg.scan_layers:
        shape = (cfg.depth, batch, cfg.max_seq, cfg.heads, hd)
        # two DISTINCT buffers: donating a cache that aliases k and v
        # to one array is a double-donation error on TPU
        cache["k"] = jnp.zeros(shape, jnp.float32)
        cache["v"] = jnp.zeros(shape, jnp.float32)
        return cache
    for i in range(cfg.depth):
        cache[f"k{i}"] = jnp.zeros((batch, cfg.max_seq, cfg.heads, hd),
                                   jnp.float32)
        cache[f"v{i}"] = jnp.zeros((batch, cfg.max_seq, cfg.heads, hd),
                                   jnp.float32)
    return cache


def kv_page_specs(cfg: LMConfig, batch: int = 1):
    """Ordered ``(shape, dtype, nbytes)`` of a decode cache's
    transferable KV pages — k then v per layer, the page order
    :func:`export_decode_cache` emits and the import side rebuilds
    from.  Layout is owned by the MODEL (like :func:`empty_cache`):
    the wire carries sizes for validation only, never shape."""
    require_plain_block(cfg, "kv_page_specs (KV export / disagg)")
    if cfg.scan_layers:
        raise NotImplementedError(
            "paged KV export supports unrolled layers only (the "
            "continuous batcher's serving shape)")
    hd = cfg.dim // cfg.heads
    shape = (batch, cfg.max_seq, cfg.heads, hd)
    nbytes = batch * cfg.max_seq * cfg.heads * hd * 4      # float32
    return [(shape, "float32", nbytes) for _ in range(2 * cfg.depth)]


def export_decode_cache(cfg: LMConfig, cache):
    """A prefilled :func:`make_decode` cache (batch-1, unrolled) as its
    transferable page list ``[(device_array, nbytes), ...]`` in
    :func:`kv_page_specs` order.  No data motion here: the pages ARE
    the live cache arrays — the transfer plane decides whether they
    move as registered memory (descriptor) or bytes."""
    require_plain_block(cfg, "export_decode_cache (KV export / disagg)")
    if cfg.scan_layers:
        raise NotImplementedError(
            "paged KV export supports unrolled layers only")
    pages = []
    for i in range(cfg.depth):
        for key in (f"k{i}", f"v{i}"):
            arr = cache[key]
            pages.append((arr, int(arr.size) * arr.dtype.itemsize))
    return pages


def decode_cache_from_pages(cfg: LMConfig, arrays):
    """Imported page arrays (in :func:`kv_page_specs` order) back into
    the per-layer cache dict the batcher's slot insert consumes."""
    if len(arrays) != 2 * cfg.depth:
        raise ValueError(
            f"expected {2 * cfg.depth} pages, got {len(arrays)}")
    cache = {}
    it = iter(arrays)
    for i in range(cfg.depth):
        cache[f"k{i}"] = next(it)
        cache[f"v{i}"] = next(it)
    return cache


def make_paged_batch_decode(cfg: LMConfig, page: int,
                            chunk: Optional[int] = None):
    """Block-paged continuous batching: one compiled step over a FIXED
    pool of session slots, each at its OWN position — the serving shape
    where new sessions join the live batch between steps and finished
    ones evict (the streaming LM service's engine).  KV lives in ONE
    shared page pool per layer plus a per-slot **block table**: a
    session holds only ``ctx_len``-rounded pages, never a ``max_seq``
    stripe, and two sessions may ALIAS the same page (the
    cross-session prefix cache).

    Layout (one logical address space across layers): logical page ``p``
    is row-block ``p`` of EVERY layer's k/v pool, shaped
    ``(num_pages, page, heads, hd)``.  Page 0 is the reserved garbage
    page — unallocated block-table entries and inactive slots write
    there, and the attention mask never admits an unwritten row (the
    ``live`` mask only reaches rows <= pos, all of which the owning
    session has written).

    Returns ``(prefill, step)``: ``prefill`` is :func:`make_prefill`'s,
    run per joining session at batch 1 (the batcher blockifies its
    caches into the session's pages, :func:`make_paged_io`), and
    ``step(params, cache, bt, token[b], active[b]) -> (cache, logits)``
    advances every ACTIVE slot one token (a schedule with expert
    layers returns a third value, the step's routing counts ``(3,)``
    int32: ``moe.serve``'s, over its layers).  ``cache["len"]`` is a
    per-slot (b,) int32 position vector; inactive slots are
    position-clamped and never advance, and their logits are garbage
    by contract.  ``bt`` is the (slots, max_seq // page) int32 block
    table (host-owned, passed per step — NOT part of the donated
    cache).  The step scatters the new k/v row into
    ``pool[bt[b, pos // page], pos % page]`` and attends over positions
    ``0..pos`` through the block table (``ops.paged_attention``): on
    the TPU a kernel that reads each slot's live pages from the pool
    once, with an online softmax; off it the plain gather of
    ``pool[bt]`` under the live mask.  Per-element math is independent
    (attention never crosses the batch axis), so an active slot's
    tokens are those of a solo :func:`make_decode` run of the same
    session: by construction off the TPU (the per-lane tests pin it),
    and ``tests/test_paged_attention.py`` holds the kernel to the
    plain formulation.

    A block beyond the first (``LMConfig.mixers``, ``kv_heads``, ...)
    is served here and nowhere else.  Only attention layers have pools
    (``pk<i>``/``pv<i>``; grouped heads: :func:`_paged_pool_shape`); a
    state layer of any kind (``"ssm"``, ``"kda"``, ``"mamba2"``) has
    ``sh<i>``/``sc<i>``, one block of recurrent state for each SLOT,
    which the step moves one position where the slot is ``active``; a
    latent layer has ONE pool, ``pc<i>`` ``(num_pages,
    page, kv_lora + rope`` padded to 128 lanes``)``: its keys and
    values are the same rows.
    Unrolled layers only.

    A looped schedule (``LMConfig.passes`` > 1) runs ``passes x depth``
    layer bodies a token, the passes ONE loop of the compiled program
    (:func:`_looped`): each attention writes and reads the pages of
    ITS (pass, layer).  A layer's pools hold ``passes * num_pages``
    pages and pass ``t`` of logical page ``p`` lies at ``t * num_pages
    + p``: the block table is shifted by a scalar, so one logical page
    stands for its rows in every pass and the allocator, the garbage
    page (one a pass) and the kernel keep their meaning.  The final
    norm closes every pass and the last pass's row is unembedded.

    Where the experts' router reads the layer's input
    (``LMConfig.router_at`` ``"layer_input"``) an attention layer makes
    its choice first (:func:`_early_route`) and hands it past attention.

    With ``chunk`` set a THIRD program rides along, also named
    ``step``: the step with one catch-up slice on board
    (Sarathi-style, one pass over the weights for both), ``step(params,
    cache, bt, token[b], active[b], slot, start, n, ids[chunk]) ->
    (cache, logits[b])``.  The span is :func:`make_paged_io`'s
    ``chunk_prefill``'s (``n`` context tokens of ``slot`` at
    ``start..start+n-1``, padding rows to page 0, the slot's len set to
    ``start + n``) and the rest the step's; in every layer the ``b``
    decode rows and the ``chunk`` span rows share one operand a weight
    and part only for attention, the span first: a slot whose context
    the slice completes may be ``active`` in the same call, and its
    row (the prompt's last token, at ``start + n``) attends over what
    the span has just written.  The first block, and a looped schedule,
    whose span is its fill's (:func:`_looped_riding_step`, ``chunk`` =
    ``fill_span``)."""
    import jax.numpy as jnp

    if cfg.scan_layers:
        raise NotImplementedError(
            "paged batch decode supports unrolled layers only")
    if cfg.max_seq % page:
        raise ValueError(
            f"page size {page} must divide max_seq {cfg.max_seq}")

    from ..ops import paged_attention
    from . import mla_mixer

    grouped = cfg.kv_heads != cfg.heads
    kvh = cfg.kv_heads
    # the softmax's scale, where the block gives one of its own
    scaled = {} if cfg.attn_scale is None else {"scale": cfg.attn_scale}

    def attend(q, k, v, pk, pv, bt, pos, att_pos, window=0):
        """The step's own half of an attention layer, one token per
        slot (``q``/``k``/``v`` ``(b, 1, heads, hd)``), block-table
        addressing: write the row, attend over the live pages (of a
        window layer: those the window reaches)."""
        b = q.shape[0]
        # scatter this step's row into each slot's CURRENT page
        page_idx = bt[jnp.arange(b), pos // page]
        row = pos % page
        if grouped:
            # rows of a grouped pool are (token, key/value head) pairs
            rows = row[:, None] * kvh + jnp.arange(kvh)[None, :]
            pk = pk.at[page_idx[:, None], rows].set(k[:, 0])
            pv = pv.at[page_idx[:, None], rows].set(v[:, 0])
        else:
            pk = pk.at[page_idx, row].set(k[:, 0])
            pv = pv.at[page_idx, row].set(v[:, 0])

        # attend over each slot's live pages where they lie (rows past
        # ``att_pos`` are garbage and are never admitted); an inactive
        # slot's output is discarded, so it reads one page, not the
        # ``len`` its last session left behind
        if cfg.has_window:
            att = paged_attention.window_attention(
                q[:, 0], pk, pv, bt, att_pos, page, window)
        else:
            att = paged_attention.attention(q[:, 0], pk, pv, bt, att_pos,
                                            page, **scaled)
        return att, pk, pv

    def attn_layer(i, bp, x, pk, pv, bt, pos, att_pos, rot, active):
        """Attention layer ``i``, one token per slot."""
        routed = _early_route(cfg, bp, x)
        q, k, v = _qkv(cfg, bp, x, rot if cfg.ropes[i] else None)
        att, pk, pv = attend(q, k, v, pk, pv, bt, pos, att_pos,
                             cfg.windows[i])
        x, cnt = _attn_out(cfg, bp, x, att, i, active[:, None], routed)
        return x, pk, pv, cnt

    def step(params, cache, bt, token, active):
        cache = dict(cache)
        pos = jnp.minimum(cache["len"], cfg.max_seq - 1)
        att_pos = jnp.where(active, pos, 0)
        x = _embed_rows(cfg, params, token)[:, None, :]
        rot = _rope_at(cfg, pos[:, None])
        if cfg.passes > 1:
            x, cache = _looped(
                cfg, params, cache, x,
                lambda i, bp, x, pk, pv, off: attn_layer(
                    i, bp, x, pk, pv, bt + off, pos, att_pos, rot,
                    active)[:3])
            cache["len"] = jnp.where(active, cache["len"] + 1,
                                     cache["len"])
            return cache, _unembed(cfg, params, x[:, 0])
        # a window schedule has a block table a page class: ``bt`` is
        # ``(2, slots, max_seq // page)``, whole contexts then windows
        bts = (bt, bt) if not cfg.has_window else (bt[0], bt[1])
        counts = []
        for i in range(cfg.depth):
            bp = params[f"blk{i}"]
            if cfg.mixers[i] == "none":
                # a layer of its feed-forward alone; an idle slot's row
                # is routed to no expert
                x, cnt = _ffn_scheduled(cfg, i, bp, x, active[:, None])
            elif cfg.mixers[i] == "mla":
                # the slot's latent row is written, then the absorbed
                # attention reads its live pages; an idle slot's row is
                # routed to no expert
                out, cache[f"pc{i}"] = mla_mixer.step(
                    cfg, bp, _rmsnorm(x[:, 0], bp["ln1"], cfg.norm_eps),
                    cache[f"pc{i}"], bt, pos, att_pos,
                    rot if cfg.ropes[i] else None, page)
                x, cnt = _ffn_scheduled(cfg, i, bp,
                                        _residual(cfg, x, out[:, None]),
                                        active[:, None])
            elif cfg.mixers[i] in STATE_MIXERS:
                # the slot's recurrent state moves one position where
                # the slot is active and stays where it is not (a
                # ``"kda"`` layer's in place: an idle slot's block is
                # not touched at all; its tails are a ring that turns
                # with the slot's length)
                out, h, tail = _state_mixer(cfg.mixers[i]).step(
                    cfg, bp, _rmsnorm(x[:, 0], bp["ln1"], cfg.norm_eps),
                    cache[f"sh{i}"], cache[f"sc{i}"], active,
                    cache["len"])
                x, cnt = _ffn_scheduled(cfg, i, bp,
                                        _residual(cfg, x, out[:, None]),
                                        active[:, None])
                cache[f"sh{i}"], cache[f"sc{i}"] = h, tail
            else:
                x, pk, pv, cnt = attn_layer(
                    i, bp, x, cache[f"pk{i}"], cache[f"pv{i}"],
                    bts[bool(cfg.windows[i])], pos, att_pos, rot, active)
                cache[f"pk{i}"], cache[f"pv{i}"] = pk, pv
            if cnt is not None:
                counts.append(cnt)
        cache["len"] = jnp.where(active, cache["len"] + 1,
                                 cache["len"])
        if counts:
            # the expert layers' routing counts leave with the tokens:
            # pairs on held experts and experts touched, summed over
            # the layers, and the most rows one expert took
            c = jnp.stack(counts)
            return cache, _logits(cfg, params, x[:, 0]), jnp.concatenate(
                [c[:, :2].sum(axis=0), c[:, 2:].max(axis=0)])
        return cache, _logits(cfg, params, x[:, 0])

    if chunk is None:
        return make_prefill(cfg), step
    return make_prefill(cfg), step, _riding_step(cfg, page, int(chunk),
                                                 attend)


def _riding_step(cfg: LMConfig, page: int, cw: int, attend):
    """:func:`make_paged_batch_decode`'s step with a slice of ``cw``
    rows on board; ``attend`` is its step's attention half.  Of a
    looped schedule: :func:`_looped_riding_step`."""
    import jax.numpy as jnp

    if cfg.passes > 1:
        return _looped_riding_step(cfg, page, cw, attend)
    if not cfg.plain_block():
        def declined(*_a, **_k):
            require_plain_block(cfg, "make_paged_batch_decode's riding "
                                "step (catch-up)")
        return declined

    # under the plain step's name: a device trace books both under
    # ``jit_step``, and both produce the round's tokens
    def step(params, cache, bt, token, active, slot, start, n, ids):
        cache = dict(cache)
        b = token.shape[0]
        bt_row = bt[slot]
        page_idx, row, pos_s = _slice_rows(cfg, page, bt_row, start, n, cw)
        # the slot's len BEFORE the decode rows read it
        cache["len"] = cache["len"].at[slot].set(start + n)
        pos = jnp.minimum(cache["len"], cfg.max_seq - 1)
        att_pos = jnp.where(active, pos, 0)
        x = params["embed"][jnp.concatenate([token, ids])][:, None, :]
        rot = _rope_at(cfg, jnp.concatenate([pos, pos_s])[:, None])
        for i in range(cfg.depth):
            bp, pk, pv = params[f"blk{i}"], cache[f"pk{i}"], cache[f"pv{i}"]
            q, k, v = _qkv(cfg, bp, x, rot)      # (b + cw, 1, heads, hd)
            att_s, pk, pv = _span_attend(
                cfg, q[None, b:, 0], k[None, b:, 0], v[None, b:, 0], pk,
                pv, bt_row[None], page_idx[None], row[None], pos_s[None])
            att, pk, pv = attend(q[:b], k[:b], v[:b], pk, pv, bt, pos,
                                 att_pos)
            x = _attn_out(cfg, bp, x, jnp.concatenate([att, att_s[0]]))[0]
            cache[f"pk{i}"], cache[f"pv{i}"] = pk, pv
        cache["len"] = jnp.where(active, cache["len"] + 1,
                                 cache["len"])
        return cache, _logits(cfg, params, x[:b, 0])

    return step


def _looped_riding_step(cfg: LMConfig, page: int, cw: int, attend):
    """A looped schedule's step with one span of a prompt's fill on
    board: ``step(params, cache, bt, token[b], active[b], slot, start,
    n, ids[fill_span]) -> (cache, logits[b])``, the span
    :func:`_looped_span_fill`'s (``n`` context tokens of ``slot`` from
    ``start``, a multiple of ``fill_span``; the slot's len set to
    ``start + n``).  The span's rows and the ``b`` decode rows are ONE
    operand through :func:`_looped`'s loop, so one read of the weights
    serves both; in each (pass, layer) the span's half goes first
    (:func:`_looped_span_attend`, the fill's own), then the decode
    rows' (``attend``): a slot whose context the span completes may be
    ``active`` in the same call and attends over what the span has
    just written in that pass."""
    import jax.numpy as jnp

    w = cfg.fill_span
    if cw != w or w % page:
        raise ValueError(
            f"a looped schedule's span rides at fill_span {w} rows, whole "
            f"pages of {page} (asked {cw})")

    def step(params, cache, bt, token, active, slot, start, n, ids):
        cache = dict(cache)
        b = token.shape[0]
        bt_row = bt[slot]
        mine = _span_pages(cfg, page, bt_row, start, n)
        # the slot's len BEFORE the decode rows read it
        cache["len"] = cache["len"].at[slot].set(start + n)
        pos = jnp.minimum(cache["len"], cfg.max_seq - 1)
        att_pos = jnp.where(active, pos, 0)
        # an inactive row (an idle slot, the filling one) goes to the
        # pass's garbage page: the span's session gets its rows alone
        bt_d = jnp.where(active[:, None], bt, 0)
        # the span's rows FIRST: behind the decode rows, at the cell's
        # shapes, XLA took them out by an async slice queued behind the
        # weights' prefetch (8.4 ms a step) and moved a layer's pool
        # through VMEM and back (PERF.md §6, PR 45)
        x = _embed_rows(cfg, params,
                        jnp.concatenate([ids, token]))[:, None, :]
        rot = _rope_at(cfg, jnp.concatenate([start + jnp.arange(w), pos])
                       [:, None])

        def layer(i, bp, x, pk, pv, off):
            q, k, v = _qkv(cfg, bp, x, rot if cfg.ropes[i] else None)
            att_s, pk, pv = _looped_span_attend(
                page, q[:w, 0], k[:w, 0], v[:w, 0], pk, pv, mine, bt_row,
                start, n, off)
            att, pk, pv = attend(q[w:], k[w:], v[w:], pk, pv, bt_d + off,
                                 pos, att_pos)
            return _attn_out(cfg, bp, x, jnp.concatenate([att_s, att]))[0], \
                pk, pv

        x, cache = _looped(cfg, params, cache, x, layer)
        cache["len"] = jnp.where(active, cache["len"] + 1, cache["len"])
        return cache, _unembed(cfg, params, x[w:, 0])

    return step


def _paged_pool_shape(cfg: LMConfig, num_pages: int, page: int) -> tuple:
    """One attention layer's k (or v) pool: ``(num_pages, page, heads,
    hd)``, or, where fewer key/value heads serve groups of query
    heads, ``(num_pages, page * kv_heads, hd)``, a row a (token,
    key/value head) pair (``ops.paged_attention``'s two layouts)."""
    if cfg.kv_heads != cfg.heads:
        return (num_pages, page * cfg.kv_heads, cfg.head_dim)
    return (num_pages, page, cfg.heads, cfg.head_dim)


def empty_paged_cache(cfg: LMConfig, num_pages: int, slots: int,
                      page: int):
    """A fresh page-pool KV cache for :func:`make_paged_batch_decode`:
    per attention layer one ``(num_pages, page, heads, hd)`` k and v
    pool (page 0 reserved as the garbage page), per state layer the
    slots' recurrent state, plus the per-slot ``len`` vector.
    The block table is NOT here — it is host state
    (``kv.pages.PageAllocator`` decides it), passed to the step.  A
    window layer's pools hold ``cfg.window_pages(slots, page)`` pages:
    a class of their own, with a garbage page 0 of its own.  A looped
    schedule's pools hold ``cfg.passes * num_pages`` pages, a pass
    after the other, each with its garbage page first."""
    import jax.numpy as jnp
    if cfg.max_seq % page:
        raise ValueError(
            f"page size {page} must divide max_seq {cfg.max_seq}")
    shape = _paged_pool_shape(cfg, cfg.passes * num_pages, page)
    if cfg.has_window:
        wshape = _paged_pool_shape(cfg, cfg.window_pages(slots, page),
                                   page)
    cache = {}
    for i in cfg.attn_layers():
        shp = wshape if cfg.windows[i] else shape
        cache[f"pk{i}"] = jnp.zeros(shp, jnp.float32)
        cache[f"pv{i}"] = jnp.zeros(shp, jnp.float32)
    # a state layer holds no page: one block of recurrent state for
    # each SLOT, whatever the context length (the state pool)
    for i in cfg.state_layers():
        h, tail = _state_mixer(cfg.mixers[i]).state_shapes(cfg, slots)
        cache[f"sh{i}"] = jnp.zeros(h, jnp.float32)
        cache[f"sc{i}"] = jnp.zeros(tail, jnp.float32)
    # a latent layer holds one row a token: one pool, key and value
    for i in cfg.mla_layers():
        cache[f"pc{i}"] = jnp.zeros(
            (num_pages, page, cfg.latent_row_padded()), jnp.float32)
    cache["len"] = jnp.zeros((slots,), jnp.int32)
    return cache


def paged_page_bytes(cfg: LMConfig, page: int,
                     window_class: bool = False) -> int:
    """Device bytes one LOGICAL page pins across every attention
    layer's k+v pools and every latent layer's pool (the allocator's
    per-page accounting unit); of a window schedule, across the layers
    of the page's class; of a looped schedule, in every pass (a logical
    page stands for its rows in all of them)."""
    layers = len(cfg.attn_layers())
    if cfg.has_window:
        n_win = len(cfg.window_layers())
        layers = n_win if window_class else layers - n_win
    return 2 * cfg.passes * layers * page * cfg.kv_heads \
        * cfg.head_dim * 4 + latent_row_bytes(cfg) * page  # float32


def latent_row_bytes(cfg: LMConfig) -> int:
    """Device bytes one TOKEN pins across every latent layer's pool."""
    if not cfg.has_latent:
        return 0
    return len(cfg.mla_layers()) * cfg.latent_row_padded() * 4  # float32


def state_kinds(cfg: LMConfig) -> Dict[str, Dict[str, int]]:
    """The state pool by kind of layer: for each kind the schedule
    has, its ``layers`` and the ``slot_bytes`` one SLOT pins across
    them."""
    kinds = {}
    for kind in STATE_MIXERS:
        layers = cfg.mixers.count(kind)
        if layers:
            kinds[kind] = {"layers": layers, "slot_bytes": layers
                           * _state_mixer(kind).state_bytes(cfg)}
    return kinds


def attention_forms(cfg: LMConfig) -> Dict[str, int]:
    """The decode step's attention layers by the form they run
    (``paged_attention.decode_form`` of their pools' layout), each with
    the number of layers that run it."""
    from ..ops.paged_attention import decode_form

    forms: Dict[str, int] = {}
    layout = "grouped" if cfg.kv_heads != cfg.heads else "whole"
    for form in ([decode_form(layout, cfg.has_window)] * len(cfg.attn_layers())
                 + [decode_form("latent")] * len(cfg.mla_layers())):
        forms[form] = forms.get(form, 0) + 1
    return forms


def state_slot_bytes(cfg: LMConfig) -> int:
    """Device bytes one SLOT pins across every state layer's pool."""
    return sum(k["slot_bytes"] for k in state_kinds(cfg).values())


def _paged_span_layer(cfg: LMConfig, bp, x, pk, pv, bt, page_idx, row, pos):
    """One attention layer over a SPAN of ``w`` new positions a slot,
    block-table addressing: the chunk slice's (its ``b = 1`` case).
    ``x`` is ``(b, w, dim)``, ``bt`` ``(b, max_seq // page)``;
    ``page_idx``, ``row`` and ``pos`` are ``(b, w)``: where each new
    row is written, and the position it is rotated and masked at.
    Scatter before gather: the rows are written, then each query
    attends over its slot's block table gathered back into the
    contiguous ``max_seq`` view, under the live mask ``key <= pos`` —
    the decode step's own, so a span is identical by construction with
    as many single steps."""
    q, k, v = _qkv(cfg, bp, x, _rope_at(cfg, pos))
    att, pk, pv = _span_attend(cfg, q, k, v, pk, pv, bt, page_idx, row,
                               pos)
    return _attn_out(cfg, bp, x, att)[0], pk, pv


def _span_attend(cfg: LMConfig, q, k, v, pk, pv, bt, page_idx, row, pos):
    """A span's own half of an attention layer
    (:func:`_paged_span_layer`; ``q``/``k``/``v`` ``(b, w, heads,
    hd)``, rotated): write the rows, attend through the block table."""
    import jax
    import jax.numpy as jnp

    b = q.shape[0]
    pk = pk.at[page_idx, row].set(k)
    pv = pv.at[page_idx, row].set(v)
    kc = pk[bt].reshape(b, cfg.max_seq, cfg.heads, cfg.head_dim)
    vc = pv[bt].reshape(b, cfg.max_seq, cfg.heads, cfg.head_dim)
    s_mat = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                       preferred_element_type=jnp.float32
                       ) / (cfg.head_dim ** 0.5)
    live = jnp.arange(cfg.max_seq)[None, None, :] <= pos[:, :, None]
    s_mat = jnp.where(live[:, None, :, :], s_mat, -1e30)
    p = jax.nn.softmax(s_mat, axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", p, vc,
                     preferred_element_type=jnp.float32)
    return att, pk, pv


def _slice_rows(cfg: LMConfig, page: int, bt_row, start, n, width: int):
    """Where a chunk slice of ``width`` rows, ``n`` of them real,
    writes a slot's positions ``start..start+n-1``: ``(page_idx, row,
    pos)``, each ``(width,)``.  Padding rows go to the reserved
    garbage page 0."""
    import jax.numpy as jnp
    j = jnp.arange(width)
    posc = jnp.minimum(start + j, cfg.max_seq - 1)
    page_idx = jnp.where(j < n, bt_row[posc // page], 0)
    return page_idx, posc % page, start + j


def _span_pages(cfg: LMConfig, page: int, bt_row, start, n):
    """The ``fill_span // page`` entries of ``bt_row`` that a span of
    ``n`` real rows from position ``start`` (a multiple of ``page``)
    fills; a page wholly past ``start + n``: the garbage page 0."""
    import jax.numpy as jnp
    p = start // page + jnp.arange(cfg.fill_span // page)
    return jnp.where(p * page < start + n,
                     bt_row[jnp.minimum(p, cfg.max_seq // page - 1)], 0)


def make_paged_span_fill(cfg: LMConfig, page: int):
    """The prompt pass of the schedules whose prompts go through the
    pages: a window schedule's (below), and a looped schedule's
    (:func:`_looped_span_fill`, another signature: one page class).

    A window schedule's: ``fill(params, cache,
    bt_row[pps], btw_row[pps], slot, start, n, ids[fill_span]) ->
    cache`` writes ``n`` context tokens of ``slot`` at positions
    ``start..start+n-1`` straight into its pages (a global layer's
    through ``bt_row``, a window layer's through ``btw_row``) and sets
    the slot's len to ``start + n``.  A prompt is as many calls of
    this ONE program as it has spans, in order: no ``max_seq`` cache,
    no bucket, nothing to insert.  ``start`` is a multiple of
    ``fill_span``, itself whole pages.

    In each layer the span's rows go into the pools as ``fill_span //
    page`` whole pages (``span_attention.write``).  The last live
    page of a prompt is partial: its rows from ``n`` on KEEP what lay
    there (the kernel copies that page's real rows alone, the plain
    form reads, merges and writes the span's pages; pinned by
    ``test_a_span_writes_its_own_rows_and_nothing_else``), and a page
    wholly past ``n`` names the class's garbage page 0, which stays
    as it was: a span changes its ``n`` rows and nothing else.  Then
    its queries attend over the pages
    (``span_attention.attention``): a global layer's through the
    block table, a window layer's through the ``(window + fill_span)
    // page + 2`` entries from the page that holds the first position
    its first row reaches; so ``btw_row`` must hold live pages from
    there to the span's last row, and what lies behind may have been
    given back.  The expert layer routes the ``n`` real rows only (on
    the rows as they ENTER the layer where the block's router reads
    there: :func:`_early_route`, as the step does).
    Identical by construction with as many single steps (scatter before
    gather, the step's mask)."""
    import jax.numpy as jnp

    from ..ops import span_attention

    if not cfg.has_window and cfg.passes == 1:
        def declined(*_a, **_k):
            raise UnsupportedBlock(
                "make_paged_span_fill serves window schedules and "
                "looped schedules only: every other block fills "
                "through make_prefill + insert")
        return declined
    if cfg.max_seq % page or cfg.fill_span % page:
        raise ValueError(
            f"page size {page} must divide max_seq {cfg.max_seq} and "
            f"fill_span {cfg.fill_span}: a span is whole pages")
    if cfg.passes > 1:
        return _looped_span_fill(cfg, page)
    w = cfg.fill_span

    def fill(params, cache, bt_row, btw_row, slot, start, n, ids):
        cache = dict(cache)
        j = jnp.arange(w)
        real = j < n
        x = _embed_rows(cfg, params, ids)[None]           # (1, w, dim)
        rot = _rope_at(cfg, start + j)
        for i in range(cfg.depth):
            bp, win = params[f"blk{i}"], cfg.windows[i]
            row = btw_row if win else bt_row
            mine = _span_pages(cfg, page, row, start, n)
            routed = _early_route(cfg, bp, x)
            q, k, v = _qkv(cfg, bp, x, rot if cfg.ropes[i] else None)
            pk = span_attention.write(cache[f"pk{i}"], k[0], mine, n, page)
            pv = span_attention.write(cache[f"pv{i}"], v[0], mine, n, page)
            att = span_attention.attention(q[0], pk, pv, row, start, page,
                                           win)
            x, _counts = _attn_out(cfg, bp, x, att[None], i, real[None],
                                   routed)
            cache[f"pk{i}"], cache[f"pv{i}"] = pk, pv
        cache["len"] = cache["len"].at[slot].set(start + n)
        return cache

    return fill


def _looped_span_fill(cfg: LMConfig, page: int):
    """A looped schedule's prompt pass (whole heads, no window, every
    pass inside it): ``fill(params, cache, bt_row[pps], slot, start,
    n, ids[fill_span]) -> cache`` writes ``n`` context tokens of
    ``slot`` at positions ``start..start+n-1`` into its pages IN EVERY
    PASS and sets the slot's len to ``start + n``.  A prompt is as
    many calls of this one program as it has spans, in order.  The
    passes are the step's loop (:func:`_looped`): in each (pass,
    layer) the span's rows go into the pass's pages as whole pages
    (:func:`make_paged_span_fill`'s rule for the partial one; the
    garbage page is the pass's), then its queries attend over them
    (``ops/span_attention``: the pages the span reaches, read where
    they lie), so pass ``t`` of a later span finds pass ``t`` of the
    earlier ones.  Identical by construction with as many single
    steps.  The batcher runs a span on board a step instead
    (:func:`_looped_riding_step`, the same span half)."""
    import jax.numpy as jnp

    w = cfg.fill_span

    def fill(params, cache, bt_row, slot, start, n, ids):
        j = jnp.arange(w)
        real = j < n
        mine = _span_pages(cfg, page, bt_row, start, n)
        rot = _rope_at(cfg, start + j)

        def layer(i, bp, x, pk, pv, off):
            q, k, v = _qkv(cfg, bp, x, rot if cfg.ropes[i] else None)
            att, pk, pv = _looped_span_attend(page, q[0], k[0], v[0], pk, pv,
                                              mine, bt_row, start, n, off)
            return _attn_out(cfg, bp, x, att[None], i, real[None])[0], pk, pv

        _x, cache = _looped(cfg, params, cache,
                            _embed_rows(cfg, params, ids)[None], layer)
        cache["len"] = cache["len"].at[slot].set(start + n)
        return cache

    return fill


def _looped_span_attend(page: int, q, k, v, pk, pv, mine, bt_row, start, n,
                        off):
    """A span's half of a looped (pass, layer) (``q``/``k``/``v`` ``(w,
    heads, hd)``, rotated): its rows into the pass's pages (``mine``,
    :func:`_span_pages`'s, shifted by ``off``) as whole pages, then its
    queries over the pages they reach through ``bt_row + off``.
    Returns ``(att (w, heads, hd), pk, pv)``."""
    from ..ops import span_attention

    pk = span_attention.write(pk, k, mine + off, n, page)
    pv = span_attention.write(pv, v, mine + off, n, page)
    return span_attention.attention(q, pk, pv, bt_row + off, start,
                                    page), pk, pv


def make_paged_io(cfg: LMConfig, page: int, chunk: Optional[int] = None):
    """Page-granular device I/O for the paged cache — the spill /
    resume / prefill-insert data motion, all fixed-shape (padded to the
    block-table width with garbage-page entries) so each jits ONCE.

    Returns ``(gather, scatter, insert)``:
      - ``gather(cache, page_ids[pps]) -> (pps, 2*depth, page, heads,
        hd)`` — a session's logical pages as one host-transferable
        block (k then v per layer on axis 1);
      - ``scatter(cache, page_ids[pps], block) -> cache`` — the
        inverse (resume's H2D landing);
      - ``insert(cache, page_ids[pps], src, slot) -> cache`` — a
        batch-1 prefilled cache (:func:`make_prefill`'s): an attention
        layer's ``k<i>``/``v<i>`` blockified into the session's pages,
        a state layer's ``h<i>``/``c<i>`` written over WHATEVER the
        slot's last session left in its block of the state pool (for a
        schedule without state layers ``slot`` addresses nothing), a
        latent layer's rows ``l<i>`` blockified into ``pc<i>``.
    Padding entries point at page 0 and only ever write garbage there.

    With ``chunk`` set a FOURTH program rides along — the block-paged
    chunk-scatter path of SLO-tiered scheduling (Sarathi-style chunked
    prefill): ``chunk_prefill(params, cache, bt_row[pps], slot, start,
    n, ids[chunk]) -> cache`` prefills ``n`` context tokens of one slot
    at positions ``start..start+n-1``, scattering each row into
    ``bt_row[pos // page]`` and setting the slot's len to
    ``start + n``.  Padding entries write the garbage page 0 (the
    established paged-padding idiom), and a partial prefix hit's
    catch-up starts at a page-aligned ``covered`` — so aliased prefix
    pages are never written.  The slice attends as
    :func:`_paged_span_layer` does: a fully chunk-prefilled slot is
    identical-by-construction to a whole-prompt prefill insert.

    For a block beyond the first the other three decline by name: a
    page of keys restores no recurrent state, so spill/resume
    (``gather``/``scatter``) and the catch-up slices
    (``chunk_prefill``) are not entered for such a model (the batcher
    refuses the options that would)."""
    import jax
    import jax.numpy as jnp
    if cfg.max_seq % page:
        raise ValueError(
            f"page size {page} must divide max_seq {cfg.max_seq}")
    pps = cfg.max_seq // page

    def insert(cache, page_ids, src, slot):
        if cfg.passes > 1:
            raise UnsupportedBlock(
                f"make_paged_io insert declines a schedule of "
                f"{cfg.passes} passes: " + _looped_stripes(cfg))
        if cfg.has_window:
            raise UnsupportedBlock(
                "make_paged_io insert declines window layers: no whole-"
                "prompt cache exists to insert (make_paged_span_fill "
                "writes the pages)")
        cache = dict(cache)
        shape = _paged_pool_shape(cfg, pps, page)
        for i in range(cfg.depth):
            if cfg.mixers[i] == "none":
                continue
            if cfg.mixers[i] in STATE_MIXERS:
                for pool, new in ((f"sh{i}", f"h{i}"), (f"sc{i}", f"c{i}")):
                    cache[pool] = jax.lax.dynamic_update_slice(
                        cache[pool], src[new],
                        (slot,) + (0,) * (src[new].ndim - 1))
                continue
            if cfg.mixers[i] == "mla":
                cache[f"pc{i}"] = cache[f"pc{i}"].at[page_ids].set(
                    src[f"l{i}"][0].reshape(pps, page, -1))
                continue
            cache[f"pk{i}"] = cache[f"pk{i}"].at[page_ids].set(
                src[f"k{i}"][0].reshape(shape))
            cache[f"pv{i}"] = cache[f"pv{i}"].at[page_ids].set(
                src[f"v{i}"][0].reshape(shape))
        return cache

    if not cfg.plain_block():
        def declined(what):
            def fn(*_a, **_k):
                require_plain_block(cfg, what)
            return fn

        io = (declined("make_paged_io gather (host spill)"),
              declined("make_paged_io scatter (host resume)"), insert)
        if chunk is None:
            return io
        return io + (declined("make_paged_io chunk_prefill (catch-up)"),)

    def gather(cache, page_ids):
        blocks = []
        for i in range(cfg.depth):
            blocks.append(cache[f"pk{i}"][page_ids])
            blocks.append(cache[f"pv{i}"][page_ids])
        return jnp.stack(blocks, axis=1)

    def scatter(cache, page_ids, block):
        cache = dict(cache)
        for i in range(cfg.depth):
            cache[f"pk{i}"] = cache[f"pk{i}"].at[page_ids].set(
                block[:, 2 * i])
            cache[f"pv{i}"] = cache[f"pv{i}"].at[page_ids].set(
                block[:, 2 * i + 1])
        return cache

    if chunk is None:
        return gather, scatter, insert

    cw = int(chunk)

    def chunk_prefill(params, cache, bt_row, slot, start, n, ids):
        cache = dict(cache)
        page_idx, row, pos = _slice_rows(cfg, page, bt_row, start, n, cw)
        x = params["embed"][ids][None]            # (1, chunk, dim)
        for i in range(cfg.depth):
            x, pk, pv = _paged_span_layer(
                cfg, params[f"blk{i}"], x, cache[f"pk{i}"],
                cache[f"pv{i}"], bt_row[None], page_idx[None], row[None],
                pos[None])
            cache[f"pk{i}"], cache[f"pv{i}"] = pk, pv
        cache["len"] = cache["len"].at[slot].set(start + n)
        return cache

    return gather, scatter, insert, chunk_prefill


def make_decode_loop(cfg: LMConfig, steps: int):
    """Greedy generation as ONE compiled program: ``lax.scan`` feeds the
    argmax token back through ``decode_step`` for ``steps`` tokens, so a
    whole generation burst costs a single device dispatch (a per-token
    program pays the host round trip per TOKEN; the scan pays it per
    BURST) — the harness for weight-streaming measurements, where
    per-token time is device time.

    Returns (prefill, loop) where loop(params, cache, token) ->
    (cache, tokens (steps, b))."""
    import jax
    import jax.numpy as jnp

    prefill, decode_step = make_decode(cfg)

    def loop(params, cache, token):
        def body(carry, _):
            cache, tok = carry
            cache, logits = decode_step(params, cache, tok)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (cache, nxt), nxt

        (cache, _), toks = jax.lax.scan(body, (cache, token), None,
                                        length=steps)
        return cache, toks

    return prefill, loop


def make_generator(cfg: LMConfig, params):
    """Build a ``gen(prompt_ids, max_new, temperature=0.0, rng=None)``
    closure with the prefill and decode-step programs jitted ONCE —
    the serving form (LMService holds one of these; re-jitting per
    request would pay XLA compilation on every RPC).  temperature 0 is
    greedy; > 0 samples and REQUIRES an rng key (each call should pass
    a fresh one).  The decode step donates the cache for in-place
    updates."""
    import jax
    import jax.numpy as jnp

    prefill, decode_step = make_decode(cfg)
    prefill_j = jit_with_params(prefill, params)
    step_j = jit_with_params(decode_step, params, donate_argnums=(0,))

    def pick(logits, temperature, rng):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            rng, logits / temperature, axis=-1).astype(jnp.int32)

    def gen(prompt_ids, max_new: int, temperature: float = 0.0,
            rng=None):
        """temperature 0 = greedy (deterministic); > 0 samples from the
        softmax at that temperature (pass ``rng`` for reproducibility)."""
        _validate_gen_args(cfg, prompt_ids, max_new, temperature, rng)
        cache, logits = prefill_j(prompt_ids)
        out = []
        for i in range(max_new):
            if temperature > 0.0:
                rng, sub = jax.random.split(rng)
            else:
                sub = None
            token = pick(logits, temperature, sub)
            out.append(token)
            if i < max_new - 1:          # the last emitted token needs
                cache, logits = step_j(cache, token)   # no further step
        return jnp.stack(out, axis=1)

    return gen


def _validate_gen_args(cfg: LMConfig, prompt_ids, max_new: int,
                       temperature: float, rng) -> None:
    """Shared generation-contract checks (both generator forms)."""
    s = prompt_ids.shape[1]
    if s + max_new > cfg.max_seq:
        raise ValueError(
            f"prompt {s} + max_new {max_new} exceeds max_seq "
            f"{cfg.max_seq} (the cache would silently wrap)")
    if temperature > 0.0 and rng is None:
        raise ValueError(
            "temperature > 0 requires an rng key (a silent default "
            "would make every sampled completion identical)")


def make_scan_generator(cfg: LMConfig, params):
    """Whole-completion generation as ONE device program: prefill, then
    ``lax.scan`` over decode steps with token selection on-device —
    the host dispatches twice per request instead of once per token.

    Single-stream decode at small model sizes is dispatch-bound (each
    per-token program launch costs more than its compute; round 5,
    earlier set-up: ~200 -> ~530 tok/s, not measured on the current
    chip).  One program compiles per (batch, prompt_len, max_new,
    sampled?) tuple — serving paths should bucket ``max_new``
    (LMService rounds up to the next power of two and slices); the
    greedy specialization carries no sampling ops at all.

    Returns ``gen(prompt_ids, max_new, temperature=0.0, rng=None) ->
    (b, max_new) int32``, same contract as :func:`make_generator`."""
    import functools as _ft

    import jax
    import jax.numpy as jnp

    prefill, decode_step = make_decode(cfg)

    # params is an ARGUMENT of the program (see jit_with_params)
    @_ft.partial(jax.jit, static_argnums=(2, 3))
    def run(params, prompt_ids, max_new, sample, temperature, rng):
        cache, logits = prefill(params, prompt_ids)

        def pick(logits, sub):
            if sample:
                return jax.random.categorical(
                    sub, logits / temperature, axis=-1).astype(jnp.int32)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        if sample:
            rng, sub = jax.random.split(rng)
        else:
            sub = rng
        first = pick(logits, sub)           # from the prefill logits
        if max_new == 1:
            return first[:, None]

        def body(carry, _):
            cache, token, rng = carry
            cache, logits = decode_step(params, cache, token)
            if sample:
                rng, sub = jax.random.split(rng)
            else:
                sub = rng
            nxt = pick(logits, sub)
            return (cache, nxt, rng), nxt

        # step-then-pick, length max_new-1: no wasted forward after the
        # final token (matches make_generator's step count)
        (_, _, _), toks = jax.lax.scan(
            body, (cache, first, rng), None, length=max_new - 1)
        return jnp.concatenate([first[:, None],
                                jnp.moveaxis(toks, 0, 1)], axis=1)

    def gen(prompt_ids, max_new: int, temperature: float = 0.0,
            rng=None):
        _validate_gen_args(cfg, prompt_ids, max_new, temperature, rng)
        sample = temperature > 0.0
        if rng is None:
            rng = jax.random.PRNGKey(0)   # unused on the greedy path
        return run(params, jnp.asarray(prompt_ids), int(max_new), sample,
                   jnp.float32(temperature), rng)

    gen.program = run        # the jitted program, for callers that lower it
    return gen


def generate(params, cfg: LMConfig, prompt_ids, max_new: int):
    """One-off greedy decoding convenience (compiles per call — hold a
    :func:`make_generator` closure to amortize compilation)."""
    return make_generator(cfg, params)(prompt_ids, max_new)


def make_train_step(cfg: LMConfig, mesh=None, sp_axis=None,
                    accum: int = 1):
    """(params, ids, labels) -> (new_params, loss); plain SGD.

    ``accum`` > 1 turns on gradient accumulation: the leading batch dim
    must be ``accum * microbatch`` and one optimizer step scans the
    microbatches inside the jit (``lax.scan`` — compiler-friendly
    control flow, ONE compiled body), so a chip-filling tokens/step is
    reachable with the HBM footprint of a single microbatch."""
    import jax
    import jax.numpy as jnp

    forward = make_forward(cfg, mesh, sp_axis)

    def loss_fn(params, ids, labels):
        logits, aux = forward(params, ids, with_aux=True)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None],
                                   axis=-1).squeeze(-1)
        return nll.mean() + aux

    def train_step(params, ids, labels, lr: float = cfg.lr):
        if accum <= 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, ids, labels)
        else:
            if ids.shape[0] % accum != 0:
                raise ValueError(
                    f"batch {ids.shape[0]} not divisible by "
                    f"accum={accum} — trailing examples would be "
                    "silently dropped")
            b = ids.shape[0] // accum
            mids = ids.reshape(accum, b, *ids.shape[1:])
            mlbl = labels.reshape(accum, b, *labels.shape[1:])

            def body(carry, mb):
                loss_sum, gacc = carry
                l, g = jax.value_and_grad(loss_fn)(params, *mb)
                gacc = jax.tree_util.tree_map(jnp.add, gacc, g)
                return (loss_sum + l, gacc), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros_like(p), params)
            (loss_sum, grads), _ = jax.lax.scan(
                body, (jnp.float32(0.0), zeros), (mids, mlbl))
            loss = loss_sum / accum
            grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
        new_params = jax.tree_util.tree_map(
            lambda p, g: p - lr * g, params, grads)
        return new_params, loss

    return train_step


def param_specs(cfg: LMConfig) -> Dict[str, Any]:
    """NamedSharding PartitionSpecs for a ("dp", "tp") mesh: attention/
    MLP projections shard their wide dim over tp (XLA inserts the
    all-reduces), embeddings shard the vocab."""
    from jax.sharding import PartitionSpec as P

    specs: Dict[str, Any] = {
        "embed": P("tp", None),
        "unembed": P(None, "tp"),
    }
    blk = {
        "wqkv": P(None, "tp"),
        "wo": P("tp", None),
        "ln1": P(None),
        "ln2": P(None),
    }
    if cfg.moe_experts > 0:
        # expert parallelism over the tp axis: each device owns
        # num_experts/tp whole experts (moe.param_specs)
        from .moe import param_specs as moe_specs
        blk["moe"] = moe_specs(cfg.moe_cfg(), ep_axis="tp")
    else:
        blk["w1"] = P(None, "tp")
        blk["w2"] = P("tp", None)
    if cfg.scan_layers:
        import jax

        # stacked weights: replicated leading depth axis + per-layer spec
        specs["blocks"] = jax.tree_util.tree_map(
            lambda s: P(None, *s), blk,
            is_leaf=lambda x: isinstance(x, P))
    else:
        for i in range(cfg.depth):
            specs[f"blk{i}"] = blk
    return specs


def batch_specs() -> Tuple[Any, Any]:
    from jax.sharding import PartitionSpec as P
    return P("dp", None), P("dp", None)
