"""LM serving over the framework — autoregressive generation as an RPC.

The capstone wiring: the TransformerLM behind a Service (``Generate``
runs ``make_scan_generator``'s whole-completion program, ``Decode``
streams from the paged :class:`ContinuousBatcher`), so a Channel client
(or grpc/HTTP through the bridges) asks for completions the way it
would ask any brpc-style service.  The reference's analogue is its
model-serving example services; here the "model" is an actual LM.

Wire format (framework control plane is schema-free TLV; payloads are
the service's own): request = ``<u32 batch><u32 prompt_len>
<u32 max_new>`` + int32 prompt ids; response = int32 generated ids,
shape (batch, max_new).
"""

from __future__ import annotations

import struct
import threading
from collections import deque
from typing import NamedTuple, Optional

import numpy as np

from ..butil.logging_util import LOG
from ..butil.status import Errno
from ..bvar.multi_dimension import PassiveDimension
from ..server.admission import _MAX_TENANTS, normalize_tenant
from ..server.service import Service
from . import lm_telemetry as _lmt
from .lm_telemetry import (PH_CATCHUP_SLICE, PH_CHUNK_SLICE,
                           PH_DEVICE_WAIT, PH_EVICT, PH_HOST_RESUME,
                           PH_HOST_SPILL, PH_IDLE_WAIT,
                           PH_INSERT_DISPATCH, PH_PAGE_ALLOC,
                           PH_PREFILL_DISPATCH, PH_PREFIX_LOOKUP,
                           PH_SCHED, PH_STEP_DISPATCH, PH_STREAM_EMIT,
                           PH_TOKEN_WALK)
from . import mla_mixer
from .transformer_lm import (LMConfig, UnsupportedBlock, attention_forms,
                             init_params, latent_row_bytes, paged_page_bytes,
                             require_plain_block, state_kinds,
                             state_slot_bytes)


def pack_generate_request(prompt: np.ndarray, max_new: int) -> bytes:
    prompt = np.ascontiguousarray(prompt, dtype=np.int32)
    b, s = prompt.shape
    return struct.pack("<III", b, s, max_new) + prompt.tobytes()


def unpack_generated(data: bytes) -> np.ndarray:
    b, n = struct.unpack_from("<II", data)
    return np.frombuffer(data, dtype=np.int32, offset=8).reshape(b, n)


def unpack_token(chunk) -> int:
    """One streamed decode token (the ``Decode`` chunk wire format:
    int32 little-endian per token per step)."""
    (tok,) = struct.unpack("<i", bytes(chunk))
    return tok


# -- SLO tiers ---------------------------------------------------------------

# Per-tenant latency classes the batcher schedules by.  Rank = index:
# lower ranks win the chunk budget, drain first from pending, and are
# spilled LAST under pool pressure.
SLO_TIERS = ("interactive", "standard", "batch")
_TIER_RANK = {t: i for i, t in enumerate(SLO_TIERS)}
_RANK_BATCH = _TIER_RANK["batch"]


class TierRegistry:
    """Tenant → SLO tier, keyed on the SAME normalized TLV-22 identity
    the admission plane uses (``normalize_tenant``) so one tenant name
    means one thing across fair admission and the batch scheduler.
    Unregistered tenants get the default tier.  Bounded at the
    admission plane's tenant cardinality cap — an operator config
    table, not an unbounded per-request map."""

    def __init__(self, default: str = "standard"):
        if default not in SLO_TIERS:
            raise ValueError(f"unknown SLO tier: {default}")
        self._default = default
        self._map: dict = {}
        self._slo: dict = {}       # tier -> (ttft_ms, itl_ms) targets
        self._lock = threading.Lock()

    def set_tier(self, tenant, tier: str) -> None:
        if tier not in SLO_TIERS:
            raise ValueError(f"unknown SLO tier: {tier}")
        key = normalize_tenant(tenant)
        with self._lock:
            if key not in self._map and len(self._map) >= _MAX_TENANTS:
                raise ValueError("tier registry full")
            self._map[key] = tier

    def tier_of(self, tenant) -> str:
        with self._lock:
            return self._map.get(normalize_tenant(tenant),
                                 self._default)

    def rank_of(self, tenant) -> int:
        return _TIER_RANK[self.tier_of(tenant)]

    def set_slo(self, tier: str, ttft_ms: Optional[float] = None,
                itl_ms: Optional[float] = None) -> None:
        """Per-tier latency targets the SLO attainment verdicts
        (``lm_telemetry.LM_SLO_VERDICTS``) are judged against at
        session close.  A tier with no targets judges
        ``slo_untargeted``."""
        if tier not in SLO_TIERS:
            raise ValueError(f"unknown SLO tier: {tier}")
        with self._lock:
            self._slo[tier] = (ttft_ms, itl_ms)

    def slo_of(self, tier: str) -> tuple:
        # deliberately lock-free: the batcher reads targets while
        # finalizing a session inside its loop, and a dict.get of an
        # immutable tuple is GIL-atomic
        return self._slo.get(tier, (None, None))


# CLOSED enum (tools/check/enums.py pins every member to a test): the
# scheduler's named decisions.  count_sched asserts membership so an
# unregistered name fails loudly at the first count, not silently in a
# dashboard.
SLO_SCHED_EVENTS = (
    "sched_chunk_slice",        # one bounded prefill slice ran
    "sched_catchup_slice",      # slice replaying past a partial prefix hit
    "sched_interactive_first",  # interactive outranked lower tiers for budget
    "sched_preempt_batch",      # batch-tier victim spilled under pressure
)

_sched_lock = threading.Lock()
_sched = {r: 0 for r in SLO_SCHED_EVENTS}


def count_sched(event: str, n: int = 1) -> None:
    assert event in _sched, f"unregistered scheduler event: {event}"
    with _sched_lock:
        _sched[event] += n


def sched_counters() -> dict:
    with _sched_lock:
        return dict(_sched)


def _reset_sched_for_tests() -> None:
    with _sched_lock:
        for k in _sched:
            _sched[k] = 0


_sched_var = PassiveDimension(("event",), lambda: sched_counters(),
                              name="lm_slo_sched_total")


# ``paged`` stays a keyword (default True) of ``ContinuousBatcher`` and
# ``LMService`` only because the benchmark's model files pass it
# (ROADMAP D2): the contiguous slot cache it once deselected is gone
_PAGED_ONLY = ("paged=False: the contiguous slot cache was removed in "
               "PR 29; the paged engine is the only serving engine")


class _Session:
    __slots__ = ("stream", "prompt", "max_new", "sent", "queued",
                 "slot",
                 "cache1", "ctx_len", "last_token",
                 # SLO scheduling: resolved tier + rank, and the
                 # chunked-prefill fill watermark (context positions
                 # written so far; fill < ctx_len means the session
                 # occupies its slot but is NOT yet decoding)
                 "tier", "tier_rank", "fill",
                 # the kv/pages allocator: the session's
                 # block-table pages, its prefix-cache aliases, and
                 # its host-tier parking state
                 "pages", "n_alias", "n_priv",
                 "host_handles", "saved_len",
                 # observability: the session's timeline record
                 # (lm_telemetry.SessionTimeline, None when telemetry
                 # is off) and its forced rpcz decode-session span
                 # (None when the request was untraced)
                 "tl", "span")

    def __init__(self, stream, prompt: Optional[np.ndarray],
                 max_new: int):
        self.stream = stream
        self.prompt = prompt
        self.max_new = max_new
        self.sent = 0
        # steps dispatched for it: ``sent`` and, while the batcher has
        # a step in flight that it takes part in, one more
        self.queued = 0
        self.slot = -1
        self.tier = "standard"
        self.tier_rank = _TIER_RANK["standard"]
        self.fill = 0
        # disaggregated serving (kv/): a session whose prefill ran on
        # ANOTHER tier joins with its imported per-layer caches instead
        # of a prompt — the batcher inserts them into a slot between
        # steps exactly like a local prefill's
        self.cache1 = None
        self.ctx_len = 0
        self.last_token = 0
        # block-table pages this session HOLDS (one ref
        # each; the first n_alias are prefix-cache aliases, the next
        # n_priv private), and the host-tier handles while parked
        self.pages: list = []
        self.n_alias = 0
        self.n_priv = 0
        self.host_handles = None
        self.saved_len = 0
        self.tl = None
        self.span = None


def bucketed_prefill(prefill_j, cfg: LMConfig, prompt: np.ndarray):
    """Prompt-CONTEXT prefill (all but the last token), padded to a
    power-of-two bucket — returns ``(cache1, ctx_len)``.  ONE home for
    the bucketing: the continuous batcher's join and the kv prefill
    tier both run exactly this, which is the token-identity contract
    between monolithic and disaggregated serving (the prompt's last
    token then rides the first batch step on whichever tier decodes —
    teacher-forced equivalence, see :meth:`ContinuousBatcher._admit`).
    The prefill (``transformer_lm.make_prefill``) is handed the true
    length beside the bucket: a causal attention forgives the zero
    padding, a recurrent state does not."""
    ctx = prompt[:-1]
    padded = np.zeros((_bucket(cfg, len(ctx)),), np.int32)
    padded[:len(ctx)] = ctx
    cache1, _logits = prefill_j(padded[None, :], np.int32(len(ctx)))
    return cache1, len(ctx)


def _written(cache):
    """An output that the program which returned ``cache`` wrote, for
    the clock's readiness probe: a pool (every cache program writes
    every layer's), never ``len``, which most of them hand through."""
    for name, leaf in cache.items():
        if name != "len":
            return leaf
    return cache["len"]


def _bucket(cfg: LMConfig, ctx_len: int) -> int:
    """The prefill's bucket for a context of ``ctx_len``: the next
    power of two, at most ``max_seq``."""
    bucket = 1
    while bucket < max(ctx_len, 1):
        bucket <<= 1
    return min(bucket, cfg.max_seq)


def _router_note(cfg: LMConfig) -> str:
    """``":route@layer_input/softmax"`` where an expert layer's router
    reads or scores otherwise than every block before it did (the rows
    the experts are fed, a sigmoid): what the session span's
    ``lm_schedule`` note and the model fingerprint carry of it; empty
    for those blocks, whose notes and fingerprints stay what they
    were."""
    if not cfg.has_experts or (cfg.router_at, cfg.router_scoring) \
            == ("ffn", "sigmoid"):
        return ""
    return f":route@{cfg.router_at}/{cfg.router_scoring}"


def _scale_note(cfg: LMConfig) -> str:
    """``":scaled<embed>/<residual>/<attn>/<logit>"`` where the block
    scales its residual stream (``LMConfig.embed_scale`` ...); empty for
    every block that does not, whose fingerprints stay what they
    were."""
    scales = (cfg.embed_scale, cfg.residual_scale, cfg.attn_scale,
              cfg.logit_scale)
    if scales == (1.0, 1.0, None, 1.0):
        return ""
    return ":scaled" + "/".join(str(x) for x in scales)


def _setlen(cache, slot, val):
    """Jittable per-slot ``len`` poke of the engine's cache."""
    import jax.lax as lax
    cache = dict(cache)
    cache["len"] = lax.dynamic_update_slice(cache["len"], val[None],
                                            (slot,))
    return cache


def _settok(tokens, slot, val):
    """Jittable one-entry poke of the step's token vector: a joining
    session's first token goes in ON the device, over whatever the
    vector holds for the other slots (the last step's argmax, which
    the host may not have read yet)."""
    import jax.lax as lax
    return lax.dynamic_update_slice(tokens, val[None], (slot,))


class _Flight(NamedTuple):
    """A decode step that has been dispatched and not yet read: its
    tokens on the device, and who held which slot when it left (a slot
    may change hands before the step is walked)."""

    toks: object            # (slots,) int32, on the device
    snap: list              # [(slot, _Session)] of its active slots
    counts: object = None   # the expert layers' routing counts, (3,)
    #                         int32 on the device (None: no such layer)
    ordinal: int = -1       # its record in the clock's RoundLog (-1: none)


class ContinuousBatcher:
    """Continuous-batching decode engine: ONE decode-step loop over a
    fixed pool of session slots.  Per step, every live session advances
    one token and the tokens stream back per session (int32 chunks on
    each session's server stream); NEW sessions are admitted into free
    slots BETWEEN steps (bucketed prefill at batch 1, caches copied
    into the slot, first token emitted by the very next step — that
    write is the time-to-first-token); finished or broken sessions
    evict and free their slot, the stream closing with a NAMED reason.

    This is the fabric-lib serving shape (PAPERS.md): the transport —
    the engine's kind-5 stream lane — batch-writes one step's worth of
    tokens across ALL sessions as one coalesced call, so per-token
    transport cost amortizes exactly like per-token compute does.

    The loop runs on one daemon thread, started lazily at the first
    join and exiting after ``idle_linger_s`` with nothing to serve.

    **One step in flight.**  A pass admits, DISPATCHES the next step
    and only then blocks on the tokens of the step before it, walks,
    emits and evicts: the device always has the next step queued while
    the host reads the last one.  What makes that possible: the step's
    inputs live on the device (block table and mask are uploaded only
    in a pass where their host mirrors changed, a joining session's
    first token is poked into the device's token vector, every other
    entry of which is the last step's argmax as it lies), and a session
    ends at ``max_new``, which the host knows before the step that
    makes the last token runs, so the mask of a step that runs ahead
    already excludes it.  A client that hung up is found only at emit:
    its one step in flight writes a row into pages and a state block it
    still owned when the step was queued, nobody reads the token, and
    the device runs programs in order, so a later join's insert lands
    after it.  With nothing in flight (the first step after idle) the
    step is dispatched alone.
    ``kv_stats()["lookahead"]`` counts which way each step left.

    **A catch-up slice rides the step.**  A session that fills its
    context by slices (a partial prefix hit's remainder; a fresh prompt
    under ``prefill_chunk_tokens``) holds a slot with ``fill <
    ctx_len``.  A pass that sees one puts its next slice ON BOARD the
    step it dispatches (``make_paged_batch_decode``'s riding step: the
    decode rows and the slice's rows cross every weight together, the
    weights are read once), one slice a step; the slice that completes
    the context activates the session first, so its first token comes
    out of the same step.  What a ``prefill_chunk_tokens`` budget
    allows a round beyond one slice runs as programs of their own
    before the step.
    ``kv_stats()["lookahead"]`` counts ``slices`` and ``slices_rode``.

    **Paged KV** (the kv/pages allocator): one shared page pool per
    layer plus a per-slot block table, so a session pins only
    ``ctx_len``-rounded pages, never a ``max_seq`` stripe — the slot
    count decouples from device KV bytes and sessions-per-box scales
    with MEAN context, not max.  Three consequences ride along:

    - a cross-session :class:`~brpc_tpu.kv.pages.PrefixCache` lets a
      re-sent context ALIAS already-prefilled pages (refcounted, zero
      bytes copied) and skip prefill for the covered prefix, any
      remainder caught up through chunked-prefill slices (token
      identity with the uncached path by construction);
    - when the device pool runs dry the batcher first drops LRU
      prefix-cache entries, then SPILLS the fattest live session's
      private pages to the :class:`~brpc_tpu.kv.pages.HostPagePool`
      (one memcpy per page) and parks it; parked sessions resume —
      bit-exact — when pages free up.  Exhaustion beyond that closes
      the admitting stream under a NAMED ``KV_EVICT_REASONS`` member;
    - mid-spill pages are drain-visible: ``Server.drain`` counts them
      (``kv.pages.host_inflight_spills``) and expiry closes parked
      sessions under ``kv_spill_drain_aborted`` instead of leaking.

    **Two kinds of state** (a layer schedule with state layers,
    ``LMConfig.mixers`` ``"ssm"``, ``"kda"`` or ``"mamba2"``): the
    attention and latent layers keep pages per TOKEN as above; each
    state layer keeps one fixed block per SLOT in the state pool
    (``sh<i>``/``sc<i>`` of the cache, its size follows ``slots``;
    ``kv_stats()["state"]`` says which kinds it holds).  Admission
    writes the prefill's state at the prompt's true length over
    whatever the slot's last session left; the step moves it only where
    the slot is active; eviction just lets the slot go.  A page of keys restores no recurrent
    state, so for such a model the prefix cache declines every lookup
    (counted, ``kv_stats()["prefix"]["declined_state"]``) and the
    options that would park, catch up or import are refused
    at construction (:class:`UnsupportedBlock`).

    **Two page classes** (a schedule with window layers,
    ``LMConfig.windows``): a global layer's pages live as long as their
    session, as above; a window layer's are a class of their own
    (:class:`~brpc_tpu.kv.pages.WindowTable`: pools, allocator and block
    table apart), taken as the context grows and GIVEN BACK once every
    position in them lies behind the window, so a session holds
    ``window // page + 2`` of them however long it runs.  The step is
    handed both tables; before a step that writes a slot's position
    ``p`` the slot's row is made to cover ``p - window + 1 .. p``
    (inside ``page_alloc``).  Such a model's prompt is not prefilled
    into a ``max_seq`` cache and inserted: it is written straight into
    the pages in spans of ``LMConfig.fill_span`` rows
    (``make_paged_span_fill``, one program whatever the prompt's
    length, each span a ``prefill_dispatch``), all of them at
    admission, between two steps.  The prefix cache declines it (a
    page it aliased would have to know its class), as do park/resume,
    slices and KV import (refused at construction).
    ``kv_stats()["window"]`` counts pages held against what whole
    contexts would hold, over steps, and pages given back.

    **Pages a pass** (a looped schedule, ``LMConfig.passes`` > 1: the
    layers run several times a token, each pass attending keys and
    values of its own): one block table and one allocator, a logical
    page standing for its rows in EVERY pass (``paged_page_bytes``
    counts them all), so admission, eviction and the page accounting
    are the first block's.  Its prompts too go through the pages in
    spans of ``fill_span`` rows (a ``max_seq`` stripe a (pass, layer)
    would be gigabytes a join), but not between steps: the session
    holds its slot inactive and each round's step carries ONE span on
    board, as a catch-up slice rides (the span completing the prompt
    activates it, so its first token comes out of that step); no
    prefix cache is built for it, and park / resume, chunk budgets and
    KV import are refused at construction.  ``kv_stats()["loop"]``
    counts the layer bodies its steps ran, the spans its fills took
    and those that rode a step (``spans_rode``).

    Either kind's spans touch their own pages only: a span's rows go
    into the pools as whole pages and its attention fetches the table
    entries it reaches (``ops/span_attention``).  ``kv_stats()["fill"]``
    counts ``spans``, their true ``rows``, ``pages_written``, and
    ``pages_attended`` against ``pages_table`` (what whole block
    tables would have been), a kind of layer counted once.

    **SLO-tiered scheduling** (ROADMAP item 4): the step loop is a
    latency-SLO scheduler over three per-tenant tiers resolved from
    the TLV-22 identity via a :class:`TierRegistry`:

    - **chunked prefill** (``prefill_chunk_tokens``, Sarathi-style):
      each loop round runs ONE decode step plus a bounded budget of
      prefill slices (the last of them on board the step), so a long
      prompt never head-of-line-blocks live
      sessions' next token.  A joining session occupies its slot
      immediately but stays INACTIVE (``fill < ctx_len``) while chunk
      rounds scatter its context; its first generated token is
      teacher-forced identically to a whole-prompt prefill.  The
      interactive tier spends the budget first;
    - **priority preemption**: pending joins drain interactive-first,
      and under pool pressure the spill victim is chosen
      tier-then-footprint (batch-tier sessions park before standard,
      interactive last) with batch victims taken even BEFORE
      prefix-cache holds when the requester outranks them.  Every
      decision counts under the closed ``SLO_SCHED_EVENTS`` enum.
    """

    def __init__(self, cfg: LMConfig, params, slots: int = 8,
                 idle_linger_s: float = 5.0, paged: bool = True,
                 page: int = 16, pages: Optional[int] = None,
                 host_slots: int = 0, prefix: bool = True,
                 prefix_budget: Optional[int] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 tiers: Optional[TierRegistry] = None):
        self.cfg = cfg
        self.params = params
        self.slots = int(slots)
        self.idle_linger_s = idle_linger_s
        if not paged:
            raise ValueError(_PAGED_ONLY)
        self.page = int(page)
        self._pps = cfg.max_seq // self.page
        # +1: page 0 is the allocator's reserved garbage page
        self.num_pages = int(pages) if pages is not None \
            else self.slots * self._pps + 1
        self.host_slots = int(host_slots)
        self.prefix_enabled = bool(prefix)
        self.prefix_budget = prefix_budget
        # SLO scheduler knobs.  chunk_budget == 0 means chunked
        # prefill is OFF for fresh prompts (legacy whole bucketed
        # prefill) — but a chunk program is still built at _chunk_w:
        # partial prefix-cache hits ALWAYS catch up through chunk
        # slices (round-19 REMAINING thread), budget-unbounded when
        # the scheduler is off.
        self.chunk_budget = int(prefill_chunk_tokens) \
            if prefill_chunk_tokens else 0
        self._chunk_w = min(self.chunk_budget, cfg.max_seq) \
            if self.chunk_budget else min(64, cfg.max_seq)
        if cfg.passes > 1:
            # a looped schedule's prompt rides the steps a span of its
            # fill at a time: whole pages from a multiple of fill_span
            self._chunk_w = cfg.fill_span
        if not cfg.plain_block():
            # nothing runs such a model wrong silently: what this
            # engine does not port declines here, by name
            for on, what in (
                    (self.host_slots > 0,
                     "host_slots (park/resume and host spill)"),
                    (self.chunk_budget > 0,
                     "prefill_chunk_tokens (chunked prefill)")):
                if on:
                    raise UnsupportedBlock(
                        f"{what} runs the program's first block only: "
                        "a layer schedule with state layers, latent "
                        "attention, experts, grouped heads or a gated "
                        "FFN serves through the plain paged engine")
        self.tiers = tiers
        # the HEAVY half (jit wrappers + the device KV-pool allocation)
        # is deferred to the batcher thread's first iteration: the
        # first Decode call runs on an engine loop thread inside the
        # batched GIL entry, and allocating a serving-sized pool there
        # would stall every connection the loop owns
        self._prefill = None
        self._step = None
        self._step_riding = None
        self._insert = None
        self._cache = None
        # the step's inputs: host mirrors here, what the device holds
        # below.  ``_active[slot]``: the slot's session takes part in
        # the NEXT step (False again once its last step is dispatched)
        self._tokens = np.zeros((self.slots,), np.int32)
        self._active = np.zeros((self.slots,), bool)
        self._tokens_d = None     # the last argmax, joins poked in
        self._tok_set = set()     # slots whose token the host has set
        # mask and block table on the device, and as uploaded (None:
        # never, which equals no mirror)
        self._active_d = self._active_up = None
        self._bt_d = self._bt_up = None
        self._flight = None       # the _Flight not yet read
        self._ahead = 0           # steps dispatched with one in flight
        self._sync = 0            # ... with nothing in flight
        self._uploads = 0         # of block table, mask, token entries
        self._slices = 0          # chunk slices run ...
        self._slices_rode = 0     # ... of which on board a step
        self._sessions = {}                       # slot -> _Session
        self._pending: deque = deque()
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._thread = None
        self._steps = 0                           # decode steps run
        # paged steps' attention reach, over active slots: pages of
        # live positions against the block table's whole width
        self._attn_pages_read = 0
        self._attn_pages_table = 0
        # the state pool: blocks written by an admission and let go by
        # an eviction (state layers only), and slots held, over steps
        self._state_inserts = 0
        self._state_releases = 0
        self._state_held_steps = 0
        self._state_slot_steps = 0
        # the delta-rule layers' own: steps run and ACTIVE slots
        # stepped (the blocks ``kda_step`` read and wrote, a layer),
        # prompts filled and their true rows (``kda_scan``'s)
        self._kda = {"steps": 0, "slot_steps": 0, "fills": 0,
                     "fill_rows": 0}
        # the Mamba-2 layers' own, as the delta rule's, and the chunks
        # of the prompts' scans (a layer's, the bucket's padding
        # included: what ``ssd_chunk_scan`` ran)
        self._mamba2 = {"steps": 0, "slot_steps": 0, "fills": 0,
                        "fill_rows": 0, "chunks": 0}
        # the expert layers' routing, as the steps' own counts say
        # (read with each step's tokens): steps and rows stepped,
        # (token, expert) pairs on a held expert, held experts touched
        # (both summed over layers and steps), the most rows one took
        self._moe = {"steps": 0, "rows": 0, "local_pairs": 0,
                     "experts_touched": 0, "max_load": 0}
        # a looped schedule's own: steps run and the layer bodies they
        # ran (``passes * depth`` a step), prompts filled through the
        # pages, their true rows, the spans handed out for them and
        # those that went on board a step
        self._loop = {"steps": 0, "layer_passes": 0, "fills": 0,
                      "fill_rows": 0, "fill_spans": 0, "spans_rode": 0}
        # the spans of the fills that go through the pages: their true
        # rows, the pages they wrote, and the table entries their
        # attention had to fetch against whole block tables (a kind of
        # layer counted once, whatever the depth and the passes)
        self._fill = {"spans": 0, "rows": 0, "pages_written": 0,
                      "pages_attended": 0, "pages_table": 0}
        # the window class (kv.pages.WindowTable, built with the
        # engine): pages its sessions held at each step against what
        # their whole contexts would hold, summed over steps
        self._wt = None
        self._span_fill = None
        self._win_held_steps = 0
        self._win_whole_steps = 0
        # the allocator triple (built in _ensure_engine)
        self._alloc = None                        # kv.pages.PageAllocator
        self._prefix = None                       # kv.pages.PrefixCache
        self._host = None                         # kv.pages.HostPagePool
        self._bt = np.zeros((self.slots, self._pps), np.int32)
        self._gather_j = None
        self._scatter_j = None
        self._setlen_j = None
        self._settok_j = None
        self._chunk_j = None                      # chunked prefill slice
        self._parked: list = []                   # spilled sessions
        self.prefills_run = 0
        self.spills = 0
        self.resumes = 0
        # the batcher thread's cursor over the loop's phases, with this
        # batcher's log of its steps; _run hands it the profiler's
        # annotation classes
        self._clock = _lmt.PhaseClock()
        self._rounds_cache = _lmt.LmTelemetryCache(
            build=self._clock.rounds.counters)

    # -- public -----------------------------------------------------------

    def join(self, stream, prompt: np.ndarray, max_new: int,
             tenant=None, span=None) -> None:
        """Queue a session; it enters the live batch between steps.
        ``tenant`` (the request's TLV-22 identity, bytes or str)
        resolves the session's SLO tier through the registry.
        ``span`` (optional rpcz Span) is the session's decode-session
        span — the batcher annotates its step events and finishes it
        at evict."""
        sess = _Session(stream, np.ascontiguousarray(prompt, np.int32),
                        int(max_new))
        self._assign_tier(sess, tenant)
        sess.span = span
        sess.tl = _lmt.open_timeline(sess.tier, tenant, len(prompt),
                                     int(max_new), "fresh")
        if span is not None:
            span.annotate("lm_join")
            if not self.cfg.plain_block():
                # a block beyond the first: which mixer each layer
                # has, and how often the stack is run where it is looped
                # (and where its experts' router reads and scores
                # otherwise than the rows they are fed, by a sigmoid)
                span.annotate("lm_schedule:" + self.cfg.schedule()
                              + (f"*{self.cfg.passes}"
                                 if self.cfg.passes > 1 else "")
                              + _router_note(self.cfg))
        self._enqueue(sess)

    def _assign_tier(self, sess: _Session, tenant) -> None:
        if self.tiers is not None:
            sess.tier = self.tiers.tier_of(tenant)
            sess.tier_rank = _TIER_RANK[sess.tier]

    def join_imported(self, stream, last_token: int, ctx_len: int,
                      max_new: int, cache1, tenant=None,
                      span=None) -> None:
        """Disaggregated serving (kv/): admit a session whose prefill
        ran on ANOTHER tier.  ``cache1`` is the imported per-layer
        cache dict (``decode_cache_from_pages`` layout, batch 1); it
        drops into a free slot between steps exactly like a local
        prefill's, and the imported last prompt token rides the next
        step — so the token stream is identical with the monolithic
        path by the same teacher-forcing argument as `_admit`'s."""
        require_plain_block(self.cfg, "join_imported (KV import / disagg)")
        sess = _Session(stream, None, int(max_new))
        sess.cache1 = cache1
        sess.ctx_len = int(ctx_len)
        sess.last_token = int(last_token)
        self._assign_tier(sess, tenant)
        sess.span = span
        sess.tl = _lmt.open_timeline(sess.tier, tenant,
                                     int(ctx_len) + 1, int(max_new),
                                     "imported")
        if span is not None:
            span.annotate("lm_join")
        self._enqueue(sess)

    def _enqueue(self, sess: _Session) -> None:
        with self._lock:
            self._pending.append(sess)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="lm-decode-batcher",
                    daemon=True)
                self._thread.start()
        self._wake.set()

    def live_slots(self) -> int:
        with self._lock:
            return len(self._sessions)

    def steps_run(self) -> int:
        return self._steps

    def round_log(self, since: int = 0) -> list:
        """The records of the decode steps still in the ring
        (``lm_telemetry.ROUND_RING``), of ordinal ``since`` and later:
        one dict of ``lm_telemetry.ROUND_FIELDS`` a step."""
        return self._clock.rounds.records(since)

    def rounds_window(self):
        """``(prev, cur, dt)`` of ``kv_stats()["rounds"]``, refreshed
        at most once a cache interval: the ``/lm`` page's rates."""
        return self._rounds_cache.window()

    def kv_stats(self) -> dict:
        """Allocator-plane observability — the benchmark and the
        capacity tests read this (``alloc``, ``prefix`` and ``host``
        once the engine is built)."""
        out = {"steps": self._steps,
               "prefills_run": self.prefills_run,
               "spills": self.spills, "resumes": self.resumes,
               "parked": len(self._parked),
               "sched": sched_counters(),
               "phases": self._clock.phase_counters(),
               "phase_ns": self._clock.phase_total_ns(),
               "loop_ns": self._clock.loop_ns,
               "queue": _lmt.queue_counters(),
               # the steps by what stood in front of them, the device's
               # dry and starved time by phase, a first token's stages
               # (this batcher's own, as the phase tables are; the
               # ring: round_log())
               "rounds": self._clock.rounds.counters(),
               "first": self._clock.rounds.first_counters(),
               "lookahead": {"ahead": self._ahead, "sync": self._sync,
                             "uploads": self._uploads,
                             "slices": self._slices,
                             "slices_rode": self._slices_rode},
               "attn": {"pages_read": self._attn_pages_read,
                        "pages_table": self._attn_pages_table,
                        # layers by the form of their decode kernel
                        "forms": attention_forms(self.cfg)},
               # a slot's block of the state pool (no bytes where the
               # schedule has no state layer)
               "state": {"slots": self.slots,
                         "held": len(self._sessions),
                         "held_steps": self._state_held_steps,
                         "slot_steps": self._state_slot_steps,
                         "bytes": self.slots * state_slot_bytes(self.cfg),
                         "inserts": self._state_inserts,
                         "releases": self._state_releases,
                         # by kind of state layer: layers, bytes a slot
                         "kinds": state_kinds(self.cfg)},
               # the layers by their parts: a mixer alone, a
               # feed-forward alone (by kind), or both
               "schedule": self.cfg.layer_parts()}
        cfg = self.cfg
        if cfg.has_kda:
            out["kda"] = {**out["state"]["kinds"]["kda"], **self._kda}
        if cfg.has_mamba2:
            # the gated norm's groups and their width
            out["mamba2"] = {**out["state"]["kinds"]["mamba2"],
                             "groups": cfg.mamba2_groups,
                             "norm_group": cfg.mamba2_heads
                             * cfg.mamba2_head_dim
                             // cfg.mamba2_norm_groups,
                             **self._mamba2}
        if cfg.passes > 1:
            out["loop"] = {"passes": cfg.passes, "layers": cfg.depth,
                           "token_bytes": paged_page_bytes(cfg, self.page)
                           // self.page, **self._loop}
        if cfg.has_window or cfg.passes > 1:
            out["fill"] = dict(self._fill)
        if cfg.has_experts:
            lo, hi = cfg.experts_held
            ecfg = cfg.expert_cfg()
            out["moe"] = {"layers": len(cfg.expert_layers()),
                          "held": hi - lo, "routed": cfg.experts_routed,
                          "top_k": cfg.experts_top_k,
                          "scoring": cfg.router_scoring,
                          "router_at": cfg.router_at,
                          # the experts' form: the activation, whether
                          # it gates, the shared MLP's width
                          "act": ecfg.act, "gated": ecfg.gated,
                          "shared_dim": ecfg.shared_dim if ecfg.shared
                          else 0, **self._moe}
        if cfg.has_window:
            wt = self._wt
            out["window"] = {
                "layers": len(cfg.window_layers()), "window": cfg.window,
                "pages": cfg.window_pages(self.slots, self.page),
                "pages_held": self._win_held_steps,
                "pages_whole": self._win_whole_steps,
                "released": wt.released if wt is not None else 0}
            if wt is not None:
                out["window"]["alloc"] = wt.alloc.stats()
        if cfg.has_latent:
            # one row a token and layer, key and value at once
            out["latent"] = {"row_bytes": cfg.latent_row() * 4,
                             "layers": len(cfg.mla_layers()),
                             "packed_bytes": mla_mixer.packed_bytes(
                                 cfg, self.params),
                             "pool_bytes": self.num_pages * self.page
                             * latent_row_bytes(cfg)}
        if self._alloc is not None:
            out["alloc"] = self._alloc.stats()
        if self._prefix is not None:
            out["prefix"] = self._prefix.stats()
        if self._host is not None:
            out["host"] = self._host.stats()
        return out

    # -- internals (batcher thread only past the pending handoff) ---------

    def _ensure_engine(self) -> None:
        """Build the compiled programs + the device page pools, ON the
        batcher thread (see __init__: the constructor must stay cheap
        enough to run inside an engine loop's batched GIL entry): the
        block-paged step, the page-granular I/O programs, and the
        allocator / prefix-cache / host-tier triple from ``kv.pages``."""
        import jax

        from ..kv.pages import (HostPagePool, PageAllocator,
                                PrefixCache, WindowTable)
        from .transformer_lm import (empty_paged_cache, jit_with_params,
                                     make_paged_io,
                                     make_paged_batch_decode,
                                     make_paged_span_fill)

        if self._prefill is None:
            from ..ops import paged_attention
            from ..ops.device_ops import _on_tpu
            # a latent layer's projections in the layout the step's
            # products read, made ONCE and in this tree alone (the
            # caller's leaves are never written; a tree LMService
            # packed comes back as it is)
            self.params = mla_mixer.pack_params(self.cfg, self.params)
            if _on_tpu():
                # the step's kernel needs Pallas, 1.2 s of import on
                # the chip's host: beside the first prefill programs'
                # loads (which release the GIL), not inside the first
                # step's trace, where set-up would wait for all of it
                threading.Thread(target=paged_attention.import_pallas,
                                 name="lm-pallas-import",
                                 daemon=True).start()
            prefill, step, riding = make_paged_batch_decode(
                self.cfg, self.page, chunk=self._chunk_w)
            # weights are ARGUMENTS of every program, bound outside
            # the jit (jit_with_params) — never closure constants
            self._prefill = jit_with_params(prefill, self.params)
            self._step = jit_with_params(step, self.params,
                                         donate_argnums=(0,))
            # the step with a slice on board: traced at its first
            # call, so never where no session fills by slices
            self._step_riding = jit_with_params(riding, self.params,
                                                donate_argnums=(0,))
            # (for a block beyond the first the spill, resume and
            # catch-up programs decline when traced: __init__ refused
            # what enters them)
            gather, scatter, insert, chunk_prefill = make_paged_io(
                self.cfg, self.page, chunk=self._chunk_w)
            self._insert = jax.jit(insert, donate_argnums=(0,))
            self._gather_j = jax.jit(gather)
            self._scatter_j = jax.jit(scatter, donate_argnums=(0,))
            self._chunk_j = jit_with_params(
                chunk_prefill, self.params, donate_argnums=(0,))
            self._setlen_j = jax.jit(_setlen, donate_argnums=(0,))
            self._settok_j = jax.jit(_settok)
            if self.cfg.has_window:
                # a window schedule's prompts go into the pages in spans
                # of one shape between steps (prefill and insert
                # decline them; a looped schedule's ride the steps)
                self._span_fill = jit_with_params(
                    make_paged_span_fill(self.cfg, self.page),
                    self.params, donate_argnums=(0,))
        if self._cache is None:
            self._cache = empty_paged_cache(self.cfg, self.num_pages,
                                            self.slots, self.page)
            self._bt[:] = 0
        if self._alloc is None:
            pb = paged_page_bytes(self.cfg, self.page)
            self._alloc = PageAllocator(self.num_pages, self.page, pb)
            if self.cfg.has_window:
                self._wt = WindowTable(PageAllocator(
                    self.cfg.window_pages(self.slots, self.page),
                    self.page, paged_page_bytes(self.cfg, self.page,
                                                window_class=True)),
                    self.slots, self._pps)
            # a catch-up slice is the first block's only: grouped
            # heads without state layers serve with no prefix cache; a
            # model with state layers keeps one that declines, counted
            self._prefix = PrefixCache(
                self._alloc, budget_pages=self.prefix_budget,
                state_layers=self.cfg.has_state) \
                if self.prefix_enabled and (self.cfg.plain_block()
                                            or self.cfg.has_state) \
                else None
            if self.host_slots > 0 and self._host is None:
                self._host = HostPagePool(self.host_slots, pb)

    def _pages_for(self, ctx_len: int, max_new: int) -> int:
        """Pages a session needs end-to-end: every position it will
        ever write, ctx-ROUNDED — the whole point of paging."""
        return max(1, -(-(ctx_len + max_new) // self.page))

    # credit wait bound for one step's token writes: a healthy client
    # holds megabytes of window credit per 4-byte token, so a stream
    # that cannot take one token within this is STALLED — and the
    # batcher must never let one stalled client head-of-line-block the
    # whole live batch behind a long write timeout
    EMIT_TIMEOUT_MS = 200

    def _emit(self, pairs) -> list:
        """Write one step's tokens — native-lane streams in ONE
        coalesced engine call per engine (one writev per connection),
        Python-lane ones individually.  Credit waits are bounded by
        EMIT_TIMEOUT_MS so a stalled session costs the batch one short
        stall ONCE and is then evicted — continuous batching must not
        head-of-line-block every live session on one dead client.
        Returns sessions to evict (stream gone or out of credit)."""
        dead = []
        by_engine = {}                 # id(engine) -> (engine, items)
        for sess, tok in pairs:
            s = sess.stream
            if s.closed:
                dead.append((sess, None))
                continue
            data = struct.pack("<i", tok)
            eng = s._native_tx
            if eng is not None:
                # sessions may span servers (multiple engines): group
                # per engine — a sid is only resolvable by its own
                by_engine.setdefault(id(eng), (eng, []))[1].append(
                    (sess, s.id, data))
            else:
                prev = s.options.write_timeout_s
                s.options.write_timeout_s = self.EMIT_TIMEOUT_MS / 1e3
                try:
                    rc = s.write(data)
                finally:
                    s.options.write_timeout_s = prev
                if rc != 0:
                    dead.append((sess, "backpressure" if rc == int(
                        Errno.EOVERCROWDED) else None))
        for eng, items in by_engine.values():
            sts = eng.stream_write_many(
                [(sid, data) for _sess, sid, data in items],
                self.EMIT_TIMEOUT_MS)
            for (sess, _sid, _data), st in zip(items, sts):
                if st == -1:
                    dead.append((sess, "backpressure"))
                elif st == -2:
                    dead.append((sess, None))
        return dead

    # -- admit / spill / park / resume --------------------------------------

    def _alloc_with_reclaim(self, need: int, rank: int = 1):
        """Allocate ``need`` pages, reclaiming under pressure in SLO
        order: when the requester outranks the batch tier, spill a
        BATCH-tier victim first (its pages already ride the host
        tier), then drop LRU prefix-cache entries (cheap — redundant
        with a prefill), then spill whatever the tier-then-footprint
        policy picks.  Returns ``(pages, None)`` or ``(None, reason)``
        with the reason a KV_EVICT_REASONS member."""
        pages = self._alloc.alloc(need)
        if pages is None and self._flight is not None and any(
                s.queued >= s.max_new for _slot, s in self._flight.snap):
            # the step in flight is somebody's last: the pages it
            # frees come before any reclaim, as they did when every
            # step was read in the pass that ran it
            self._land_now()
            pages = self._alloc.alloc(need)
        while pages is None:
            if rank < _RANK_BATCH \
                    and self._spill_one(min_rank=_RANK_BATCH) is None:
                pages = self._alloc.alloc(need)
                continue
            if self._prefix is not None and self._prefix.evict_lru():
                pages = self._alloc.alloc(need)
                continue
            why = self._spill_one()
            if why is not None:
                return None, why
            pages = self._alloc.alloc(need)
        return pages, None

    def _admit(self, sess: _Session) -> None:
        # Prefill the prompt CONTEXT (all but the last token), padded
        # to a power-of-two bucket so distinct prompt lengths share
        # compiled programs — an unbucketed per-length jit would stall
        # EVERY live session for a fresh XLA compile at each new
        # length.  The prompt's LAST token then rides the next batch
        # step (teacher-forced equivalence: step logits at pos s-1 ==
        # full-prefill last-position logits), which both yields the
        # first generated token and overwrites the padded garbage rows
        # before the mask ever admits them.  A session imported from a
        # prefill tier (kv/ handoff) skips the prefill: its caches
        # arrived as pages and insert the same way.
        import jax.numpy as jnp

        clock = self._clock
        ph = clock.switch
        imported = sess.cache1 is not None
        if imported:
            ctx_len = sess.ctx_len
            aliased, covered = [], 0    # imported manifests carry no
            #                             tokens to fingerprint
        else:
            ctx = sess.prompt[:-1]
            ctx_len = len(ctx)
            if self._prefix is not None:
                ph(PH_PREFIX_LOOKUP)
                aliased, covered = self._prefix.lookup(ctx)
            else:
                aliased, covered = [], 0
        ph(PH_PAGE_ALLOC)
        n_total = self._pages_for(ctx_len, sess.max_new)
        priv, why = self._alloc_with_reclaim(n_total - len(aliased),
                                             rank=sess.tier_rank)
        if priv is None:
            self._refuse(sess, why, aliased)
            return
        # free = unOCCUPIED, not merely inactive: a chunk-filling
        # session holds its slot while _active is still False
        ph(PH_INSERT_DISPATCH)
        free = next(i for i in range(self.slots)
                    if i not in self._sessions)
        n_alias = len(aliased)
        row = np.zeros((self._pps,), np.int32)
        row[:n_alias] = aliased
        row[n_alias:n_total] = priv
        filling = False
        last = 0
        if sess.cache1 is not None:
            # disagg import: blockify the imported contiguous cache
            self._cache = self._insert(self._cache, jnp.asarray(row),
                                       sess.cache1, jnp.int32(free))
            clock.filling(1, ctx_len, _written(self._cache))
            sess.cache1 = None
            last = int(sess.last_token)
            start_len = ctx_len
        elif covered == ctx_len and not self.cfg.has_state:
            # full prefix hit (or empty context): the aliased pages
            # ARE the covered context's KV (prefill is deterministic —
            # identical values), no prefill and ZERO copies.  (With
            # state layers even an empty context takes the next branch:
            # its insert is what clears the slot's state.)
            last = int(sess.prompt[-1])
            start_len = ctx_len
        elif self._span_fill is not None:
            if not self._fill_spans(sess, free, row, ctx_len):
                self._wt.release_slot(free)
                self._refuse(sess, "kv_pool_exhausted", priv)
                return
            self.prefills_run += 1
            ph(PH_INSERT_DISPATCH)
            last = int(sess.prompt[-1])
            start_len = ctx_len
        elif covered == 0 and not self.chunk_budget \
                and self.cfg.passes == 1:
            ph(PH_PREFILL_DISPATCH)
            cache1, ctx_len = bucketed_prefill(self._prefill, self.cfg,
                                               sess.prompt)
            clock.filling(1, ctx_len, _written(cache1))
            self.prefills_run += 1
            ph(PH_INSERT_DISPATCH)
            # the insert also takes the slot: in the same program
            # the state layers' blocks are written over whatever the
            # slot's last session left there
            self._cache = self._insert(self._cache, jnp.asarray(row),
                                       cache1, jnp.int32(free))
            clock.filling(1, 0, _written(self._cache))
            self._state_inserts += int(self.cfg.has_state)
            if self.cfg.has_kda:
                self._kda["fills"] += 1
                self._kda["fill_rows"] += ctx_len
            if self.cfg.has_mamba2:
                self._mamba2["fills"] += 1
                self._mamba2["fill_rows"] += ctx_len
                self._mamba2["chunks"] += -(-_bucket(self.cfg, ctx_len)
                                            // self.cfg.mamba2_chunk)
            last = int(sess.prompt[-1])
            start_len = ctx_len
            if self._prefix is not None:
                # the context's FULL pages are immutable from here on
                # (decode writes land at pos >= ctx_len) — cache them
                self._prefix.insert(sess.prompt[:-1], priv)
        else:
            # chunked fill: a fresh prompt under the chunk budget, or
            # a PARTIAL prefix hit whose remainder catches up through
            # chunk slices (covered rows are aliased and immutable;
            # slices scatter only private pages from fill onward), or a
            # looped schedule's prompt, a span of its fill a round —
            # the session holds its slot but stays inactive until
            # _chunk_round completes the context
            filling = True
            sess.fill = covered
            start_len = covered
        self._cache = self._setlen_j(self._cache, jnp.int32(free),
                                     jnp.int32(start_len))
        clock.follow(self._cache["len"])
        sess.pages = list(aliased) + list(priv)
        sess.n_alias = n_alias
        sess.n_priv = len(priv)
        sess.ctx_len = ctx_len
        tl = sess.tl
        if tl is not None:
            if not imported:
                tl.prefix = "prefix_hit" if (n_alias and
                                             covered == ctx_len) \
                    else "prefix_partial" if covered > 0 \
                    else "prefix_miss"
            if len(sess.pages) > tl.pages_peak:
                tl.pages_peak = len(sess.pages)
        self._bt[free] = row
        sess.slot = free
        sess.sent = 0
        self._sessions[free] = sess
        if filling:
            return
        sess.fill = ctx_len
        self._set_token(free, last)
        self._active[free] = True

    def _refuse(self, sess: _Session, why: str, pages) -> None:
        """An admission the pool cannot cover: the pages it held go
        back and its stream closes under the NAMED reason."""
        from ..kv.pages import count_evict
        self._clock.switch(PH_EVICT)
        self._alloc.release_all(pages)
        count_evict(why)
        if not sess.stream.closed:
            sess.stream.close(reason=why)
        self._finalize_obs(sess, why)

    def _fill_spans(self, sess: _Session, slot: int, row, ctx_len: int
                    ) -> bool:
        """A window schedule's prompt written into the slot's pages in
        spans (``make_paged_span_fill``), all queued here: each span a
        ``prefill_dispatch`` of its own, the window class's pages taken
        and given back around it inside ``page_alloc``.  False where
        the window class ran out of pages.  (A looped schedule's spans
        ride the steps instead: ``_chunk_round``.)"""
        import jax.numpy as jnp
        ph = self._clock.switch
        w, win, wt = self.cfg.fill_span, self.cfg.window, self._wt
        ctx = sess.prompt[:-1]
        row_d = jnp.asarray(row)
        for start in range(0, ctx_len, w):
            n = min(w, ctx_len - start)
            ids = np.zeros((w,), np.int32)
            ids[:n] = ctx[start:start + n]
            ph(PH_PAGE_ALLOC)
            if not wt.cover(slot, max(0, start - win + 1), start + n - 1):
                return False
            ph(PH_PREFILL_DISPATCH)
            # (a private copy of the row: the next span's ``cover``
            # changes it under a program that may not have read it yet)
            self._cache = self._span_fill(
                self._cache, row_d, jnp.asarray(wt.bt[slot].copy()),
                np.int32(slot), np.int32(start), np.int32(n), ids)
            self._clock.filling(1, n, _written(self._cache))
            self._count_span(start, n)
        return True

    def _count_span(self, start: int, n: int) -> None:
        """One span's share of ``kv_stats()["fill"]``: what
        ``ops/span_attention`` writes and fetches for it in a layer of
        each kind the schedule has (whole contexts; a window)."""
        from ..ops.span_attention import pages_fetched
        f, cfg, page = self._fill, self.cfg, self.page
        pps = cfg.max_seq // page
        f["spans"] += 1
        f["rows"] += n
        f["pages_written"] += -(-n // page)
        for win in sorted(set(cfg.windows)):
            f["pages_attended"] += pages_fetched(
                start, cfg.fill_span, page, pps, cfg.heads // cfg.kv_heads,
                win)
            f["pages_table"] += pps

    def _cover_windows(self) -> bool:
        """Before a step is queued: every active slot's row of the
        window class covers the window of the position the step writes
        (pages wholly behind it go back), and the step's share of
        ``kv_stats()["window"]`` is counted.  A page boundary a slot
        crosses every ``page`` steps; between them nothing moves."""
        wt, win, page = self._wt, self.cfg.window, self.page
        held = whole = 0
        for slot, sess in self._sessions.items():
            if not self._active[slot]:
                continue
            p = min(sess.ctx_len + sess.queued, self.cfg.max_seq - 1)
            if p // page >= wt.hi[slot] or \
                    max(0, p - win + 1) // page > wt.lo[slot]:
                if not wt.cover(slot, max(0, p - win + 1), p):
                    return False
            held += wt.held(slot)
            whole += p // page + 1
        self._win_held_steps += held
        self._win_whole_steps += whole
        return True

    def _spill_one(self, min_rank: int = 0) -> Optional[str]:
        """Park ONE live session's private pages in the host tier.
        Victim choice is TIER-then-footprint: the worst SLO rank
        spills first (batch before standard before interactive — an
        interactive session is never parked while any batch-tier
        victim exists), fattest private footprint within a tier (frees
        the most pages per D2H), deterministic tie-break on slot.
        ``min_rank`` restricts candidates to ranks >= it (used to take
        batch victims before prefix-cache holds).  Returns None on
        success, else the KV_EVICT_REASONS member naming why nothing
        could spill."""
        if self._host is None:
            return "kv_pool_exhausted"
        # a park reads the victim's last token from the host's mirror,
        # which is whole only with nothing in flight
        self._land_now()
        ab = self._host.abort_reason()
        if ab is not None:
            return ab
        victims = [s for s in self._sessions.values()
                   if s.n_priv > 0 and s.tier_rank >= min_rank]
        if not victims:
            return "kv_pool_exhausted"
        victim = max(victims,
                     key=lambda s: (s.tier_rank, s.n_priv, -s.slot))
        if victim.tier_rank >= _RANK_BATCH:
            count_sched("sched_preempt_batch")
            if victim.tl is not None:
                victim.tl.preempts += 1
        # a phase of its own inside the caller's (a page allocation):
        # the caller's resumes as a second sample when the park is done
        outer = self._clock.switch(PH_HOST_SPILL)
        try:
            return self._park(victim)
        finally:
            self._clock.switch(outer)

    def _park(self, sess: _Session) -> Optional[str]:
        """Move a live session's private pages device → host and free
        its slot.  Bit-exact resume: everything the step depends on —
        page contents, len, the last fed token, the chunk-fill
        watermark — survives in the session object + host tier."""
        import jax.numpy as jnp
        if not self._host.begin_spill():
            return self._host.abort_reason() or "kv_host_tier_full"
        handles = []
        try:
            blk = self._gather_j(self._cache,
                                 jnp.asarray(self._bt[sess.slot]))
            self._clock.filling(1, 0, blk)
            blk = np.asarray(blk)
            for j in range(sess.n_alias, sess.n_alias + sess.n_priv):
                h = self._host.stage(
                    blk[j].reshape(-1).view(np.uint8))
                if h is None:
                    for hh in handles:
                        self._host.free(hh)
                    return "kv_host_tier_full"
                handles.append(h)
        finally:
            self._host.end_spill()
        sess.host_handles = handles
        sess.saved_len = int(np.asarray(self._cache["len"])[sess.slot])
        sess.last_token = int(self._tokens[sess.slot])
        self._alloc.release_all(sess.pages[sess.n_alias:])
        sess.pages = sess.pages[:sess.n_alias]   # alias holds remain
        self._sessions.pop(sess.slot, None)
        self._active[sess.slot] = False
        self._bt[sess.slot] = 0
        sess.slot = -1
        self._parked.append(sess)
        self.spills += 1
        try:
            from .. import fleet
            fleet.record_event("fleet_host_spill",
                               f"tier={getattr(sess, 'tier', '?')}")
        except Exception:
            pass
        if sess.tl is not None:
            sess.tl.spills += 1
        if sess.span is not None:
            sess.span.annotate("lm_spill")
        return None

    def _resume(self, sess: _Session) -> bool:
        """Un-park: re-alloc private pages, land the host bytes back
        (one H2D scatter), rebuild the block-table row, restore len and
        the last fed token.  False = stay parked (no slot or no pages
        yet — never an error)."""
        import jax.numpy as jnp
        free = next((i for i in range(self.slots)
                     if i not in self._sessions), None)
        if free is None:
            return False
        outer = self._clock.switch(PH_HOST_RESUME)
        priv = self._alloc.alloc(sess.n_priv)
        while priv is None:
            # prefix-cache holds are reclaimable — a parked session
            # must never starve behind redundant cached pages
            if self._prefix is not None and self._prefix.evict_lru():
                priv = self._alloc.alloc(sess.n_priv)
                continue
            self._clock.switch(outer)
            return False
        hd = self.cfg.dim // self.cfg.heads
        n_alias = sess.n_alias
        n_used = n_alias + sess.n_priv
        # scatter ids: private entries land in their new pages; alias
        # and pad entries point at the garbage page (their contents
        # are already live on device / don't exist)
        ids = np.zeros((self._pps,), np.int32)
        ids[n_alias:n_used] = priv
        blk = np.zeros((self._pps, 2 * self.cfg.depth, self.page,
                        self.cfg.heads, hd), np.float32)
        for j, h in enumerate(sess.host_handles):
            blk[n_alias + j] = self._host.fetch(h).view(
                np.float32).reshape(blk.shape[1:])
            self._host.free(h)
        sess.host_handles = None
        self._cache = self._scatter_j(self._cache, jnp.asarray(ids),
                                      jnp.asarray(blk))
        self._clock.filling(1, sess.saved_len, _written(self._cache))
        self._cache = self._setlen_j(self._cache, jnp.int32(free),
                                     jnp.int32(sess.saved_len))
        self._clock.follow(self._cache["len"])
        row = np.zeros((self._pps,), np.int32)
        row[:n_alias] = sess.pages
        row[n_alias:n_used] = priv
        sess.pages = list(sess.pages) + list(priv)
        self._bt[free] = row
        self._set_token(free, sess.last_token)
        # a session parked MID-FILL resumes still inactive and the
        # chunk rounds finish its context; an active one re-enters the
        # decode batch directly
        self._active[free] = sess.fill >= sess.ctx_len
        sess.slot = free
        self._sessions[free] = sess
        self.resumes += 1
        tl = sess.tl
        if tl is not None:
            tl.resumes += 1
            if len(sess.pages) > tl.pages_peak:
                tl.pages_peak = len(sess.pages)
        if sess.span is not None:
            sess.span.annotate("lm_resume")
        self._clock.switch(outer)
        return True

    def _drop_parked(self, sess: _Session,
                     reason: Optional[str]) -> None:
        """A parked session that will never resume (stream gone, or
        drain aborted the host tier): free its host slots and alias
        holds, close under the named reason."""
        from ..kv.pages import count_evict
        for h in (sess.host_handles or []):
            try:
                self._host.free(h)
            except Exception:
                pass
        sess.host_handles = None
        self._alloc.release_all(sess.pages)
        sess.pages = []
        if reason is not None:
            count_evict(reason)
        if not sess.stream.closed:
            sess.stream.close(reason=reason or "finished")
        self._finalize_obs(sess, reason or "finished")

    def _service_parked(self) -> None:
        """Between steps: resume whatever fits, discard the dead, and
        — after a drain abort — close everything still parked under
        the named reason."""
        if not self._parked:
            return
        ab = self._host.abort_reason() if self._host is not None \
            else None
        still = []
        # SLO order: interactive parkees resume first (stable within a
        # tier — spill order)
        self._parked.sort(key=lambda s: s.tier_rank)
        for sess in self._parked:
            if sess.stream.closed:
                self._drop_parked(sess, None)
            elif ab is not None:
                self._drop_parked(sess, ab)
            elif not self._resume(sess):
                still.append(sess)
        self._parked = still

    # -- SLO scheduler: chunk rounds, plain rounds -------------------------

    def _activate(self, sess: _Session) -> None:
        """A fully chunk-filled session goes live: the prompt's LAST
        token rides the next batch step — the same teacher-forcing as
        a whole-prompt prefill, so the emitted stream is identical by
        construction — and a fresh chunked context enters the prefix
        cache exactly like a prefilled one would."""
        slot = sess.slot
        sess.fill = sess.ctx_len
        self._set_token(slot, int(sess.prompt[-1]))
        self._active[slot] = True
        if sess.n_alias == 0 and sess.ctx_len > 0:
            # a chunk-filled context counts as one prefill (capacity
            # accounting); prefix-hit catch-up does NOT — the hit
            # avoided it
            self.prefills_run += 1
            if self._prefix is not None:
                self._prefix.insert(sess.prompt[:-1],
                                    sess.pages[sess.n_alias:])

    def _chunk_round(self):
        """Spend this round's chunk budget: bounded prefill slices
        over the chunk-filling sessions, INTERACTIVE tier first — the
        Sarathi-style half of the step loop (each round = one decode
        step + at most ``prefill_chunk_tokens`` of prefill work), so a
        long prompt costs live sessions one bounded slice per token
        instead of a whole prefill.  Safe interleaving is the pooled
        garbage-beyond-mask argument: a filling slot's rows beyond
        ``fill`` are junk, but the attention mask admits a row only
        once ``len`` passes it, and every admissible row has been
        rewritten by a slice first.

        The round's LAST slice is not run here: it is returned, as
        ``_dispatch``'s ``ride``, and goes on board the round's step
        (one pass over the weights for both); a session it completes
        is activated here all the same, so that its first token comes
        out of that step.  With no budget set (partial prefix hits
        only) that is the round's one slice; a budget's slices before
        the last run here, each a program of its own.  A looped
        schedule's prompt fills this way too, one span of
        ``fill_span`` rows a round (``kv_stats()["loop"]`` and
        ``["fill"]`` count it here)."""
        filling = [s for s in self._sessions.values()
                   if s.fill < s.ctx_len]
        if not filling:
            return None
        import jax.numpy as jnp
        ph = self._clock.switch
        filling.sort(key=lambda s: (s.tier_rank, s.slot))
        if filling[0].tier_rank == _TIER_RANK["interactive"] \
                and any(s.tier_rank > filling[0].tier_rank
                        for s in filling):
            count_sched("sched_interactive_first")
        budget = self.chunk_budget if self.chunk_budget else (1 << 30)
        most = (1 << 30) if self.chunk_budget else 1
        plan = []                       # (session, rows), in order
        for sess in filling:
            if budget <= 0 or len(plan) >= most:
                break
            if sess.stream.closed:
                ph(PH_EVICT)
                self._evict(sess, None)
                ph(PH_SCHED)
                continue
            fill = sess.fill
            while budget > 0 and fill < sess.ctx_len \
                    and len(plan) < most:
                n = int(min(self._chunk_w, sess.ctx_len - fill, budget))
                plan.append((sess, n))
                fill += n
                budget -= n
        ride = None
        for i, (sess, n) in enumerate(plan):
            catchup = sess.n_alias > 0
            ph(PH_CATCHUP_SLICE if catchup else PH_CHUNK_SLICE)
            ids = np.zeros((self._chunk_w,), np.int32)
            ids[:n] = sess.prompt[sess.fill:sess.fill + n]
            span = (np.int32(sess.slot), np.int32(sess.fill), np.int32(n),
                    ids)
            if i == len(plan) - 1:
                ride = span
                self._slices_rode += 1
            else:
                self._cache = self._chunk_j(
                    self._cache, jnp.asarray(self._bt[sess.slot]), *span)
                self._clock.filling(1, n, _written(self._cache))
            self._slices += 1
            if self.cfg.passes > 1:
                self._count_span(sess.fill, n)
                self._loop["fill_spans"] += 1
                self._loop["fill_rows"] += n
                self._loop["fills"] += sess.fill + n >= sess.ctx_len
            sess.fill += n
            count_sched("sched_catchup_slice" if catchup
                        else "sched_chunk_slice")
            if sess.span is not None:
                sess.span.annotate("lm_chunk_slice")
            ph(PH_SCHED)
            if sess.fill >= sess.ctx_len:
                self._clock.filled(sess.tl)
                self._activate(sess)
        return ride

    def _set_token(self, slot: int, tok: int) -> None:
        """The host names the token a slot feeds the next step (a
        prompt's last, or a parked session's): into the mirror, and
        marked so that the next dispatch pokes it into the device's
        vector."""
        self._tokens[slot] = tok
        self._tok_set.add(slot)

    def _step_inputs(self):
        """The step's inputs ON the device.  The token vector is the
        last step's argmax with the entries the host has set since
        poked in one by one (never the host's vector, which is a step
        stale for every other slot while a step is in flight); mask and
        block table are uploaded where their mirror differs from what
        was uploaded last: at admission, eviction, park, resume and a
        session's last step.  Uploads are of private copies: the
        mirrors go on changing under a step that is still running."""
        import jax.numpy as jnp
        if self._tokens_d is None:
            self._tokens_d = jnp.asarray(self._tokens.copy())
            self._uploads += 1
        else:
            for slot in self._tok_set:
                self._tokens_d = self._settok_j(
                    self._tokens_d, np.int32(slot),
                    np.int32(self._tokens[slot]))
            self._uploads += len(self._tok_set)
        self._tok_set.clear()
        if not np.array_equal(self._active, self._active_up):
            self._active_up = self._active.copy()
            self._active_d = jnp.asarray(self._active_up)
            self._uploads += 1
        # (a window schedule's step takes both classes' tables as one)
        bt = self._bt if self._wt is None \
            else np.stack([self._bt, self._wt.bt])
        if not np.array_equal(bt, self._bt_up):
            self._bt_up = bt.copy()
            self._bt_d = jnp.asarray(self._bt_up)
            self._uploads += 1
        return self._bt_d, self._tokens_d, self._active_d

    def _dispatch(self, ahead: bool, ride=None) -> _Flight:
        """Queue one plain decode step over the active slots and the
        copy of its tokens to the host; nothing here waits for the
        device.  ``ahead``: the step before it has not been read.
        ``ride``: the slice that goes on board (``_chunk_round``'s),
        ``(slot, start, n, ids)``."""
        import jax.numpy as jnp
        clock = self._clock
        ph = clock.switch
        if self._wt is not None:
            ph(PH_PAGE_ALLOC)
            if not self._cover_windows():
                # cannot be where the pool is ``LMConfig.window_pages``
                raise RuntimeError("the window page class ran dry "
                                   "under live sessions")
        ph(PH_STEP_DISPATCH)
        counts = None
        if ride is not None:
            self._cache, logits = self._step_riding(
                self._cache, *self._step_inputs(), *ride)
        elif self.cfg.has_experts:
            # the routing counts leave the device beside the tokens
            self._cache, logits, counts = self._step(
                self._cache, *self._step_inputs())
            counts.copy_to_host_async()
        else:
            self._cache, logits = self._step(self._cache,
                                             *self._step_inputs())
        queued_at = clock.stamp()
        # greedy, a program of its own: its result feeds the next step
        # as it lies, and starts on its way to the host for the walk
        self._tokens_d = toks = jnp.argmax(logits, axis=-1)
        toks.copy_to_host_async()
        step = self._steps
        self._steps += 1
        if ahead:
            self._ahead += 1
        else:
            self._sync += 1
        self._state_held_steps += len(self._sessions)
        self._state_slot_steps += self.slots
        snap = []
        for slot, sess in self._sessions.items():
            if not self._active[slot]:
                continue
            snap.append((slot, sess))
            sess.queued += 1
            if sess.queued >= sess.max_new:
                # its last step: the next one's mask leaves it out (a
                # state layer's block must not move a position past
                # the session's end), whenever this one is read
                self._active[slot] = False
        if self.cfg.has_kda:
            self._kda["steps"] += 1
            self._kda["slot_steps"] += len(snap)
        if self.cfg.has_mamba2:
            self._mamba2["steps"] += 1
            self._mamba2["slot_steps"] += len(snap)
        if self.cfg.passes > 1:
            self._loop["steps"] += 1
            self._loop["layer_passes"] += self.cfg.passes * self.cfg.depth
            self._loop["spans_rode"] += ride is not None
        return _Flight(toks, snap, counts, clock.queued(
            queued_at, step, len(snap), ahead,
            int(ride[2]) if ride is not None else 0, toks))

    def _land(self, flight: _Flight) -> int:
        """Block on a dispatched step's tokens, walk, emit, evict.
        Returns the phase the loop was in, for a caller that was in
        the middle of one."""
        clock = self._clock
        ph = clock.switch
        # the round's one sync, in a phase of its own: one sample a step
        outer = ph(PH_DEVICE_WAIT)
        t_wait = clock.t
        toks = self._read_tokens(flight.toks)
        touched = 0
        if flight.counts is not None:
            # the same program made them: no second wait
            pairs_, touched, load = (int(c) for c in
                                     np.asarray(flight.counts))
            moe = self._moe
            moe["steps"] += 1
            moe["rows"] += len(flight.snap)
            moe["local_pairs"] += pairs_
            moe["experts_touched"] += touched
            moe["max_load"] = max(moe["max_load"], load)
        ph(PH_TOKEN_WALK)
        pairs, finished = [], []
        last, pages_read = self.cfg.max_seq - 1, 0
        # (a window layer reads the window's pages: the mean over the
        # schedule's layers stands for the slot)
        win, n_win = self.cfg.window, len(self.cfg.window_layers())
        for slot, sess in flight.snap:
            if self._sessions.get(slot) is not sess:
                # evicted with this step in flight (its client hung
                # up): nobody reads the token, and whoever holds the
                # slot now must not get it
                continue
            tok = int(toks[slot])
            self._tokens[slot] = tok
            # the step attended over positions 0..ctx_len + sent
            p = min(sess.ctx_len + sess.sent, last)
            whole = p // self.page + 1
            in_window = whole - max(0, p - win + 1) // self.page
            pages_read += whole + (in_window - whole) * n_win \
                / self.cfg.depth
            sess.sent += 1
            pairs.append((sess, tok))
            if sess.sent >= sess.max_new:
                finished.append(sess)
        pages_read = int(round(pages_read))
        self._attn_pages_read += pages_read
        self._attn_pages_table += len(pairs) * self._pps
        clock.landed(t_wait, flight.ordinal, self._flight is not None,
                     pages_read, touched)
        self._deliver(pairs, finished)
        return outer

    def _land_now(self) -> None:
        """Read the step in flight from the middle of a pass (an
        admission short of pages), and go on in the phase it was in."""
        flight, self._flight = self._flight, None
        if flight is not None:
            self._clock.switch(self._land(flight))

    @staticmethod
    def _read_tokens(toks) -> np.ndarray:
        """The round's one sync (by name, so that a test can put a
        slow device here: the array itself feeds the next step)."""
        return np.asarray(toks)

    def _deliver(self, pairs, finished) -> None:
        """A round's epilogue: write its tokens to their streams, evict
        the sessions that ended and the ones whose stream is gone."""
        ph = self._clock.switch
        ph(PH_STREAM_EMIT)
        dead = self._emit(pairs)
        _lmt.on_emit(pairs, self._clock.rounds)
        if dead or finished:
            ph(PH_EVICT)
        for sess, reason in dead:
            self._evict(sess, reason)
        for sess in finished:
            if self._sessions.get(sess.slot) is sess:
                self._evict(sess, "finished")
        self._clock.delivered()

    def _finalize_obs(self, sess: _Session, reason: str) -> None:
        """Session-close observability (batcher thread): judge and
        count the SLO verdict, move the timeline into the ring, close
        out the decode-session span.  Lock-free — runs inside the step
        loop's evict epilogue."""
        tl = sess.tl
        if tl is not None:
            sess.tl = None
            ttft_t, itl_t = self.tiers.slo_of(sess.tier) \
                if self.tiers is not None else (None, None)
            _lmt.close_timeline(tl, reason, ttft_t, itl_t)
        sp = sess.span
        if sp is not None:
            sess.span = None
            sp.annotate(_lmt.round_note("lm_evict:" + reason,
                                        self._clock.round_now()))
            sp.finish(0)

    def _evict(self, sess: _Session, reason: Optional[str]) -> None:
        self._sessions.pop(sess.slot, None)
        self._active[sess.slot] = False
        # (the slot's block of the state pool is simply let go: the
        # next admission writes over it)
        self._state_releases += int(self.cfg.has_state)
        if sess.pages:
            self._alloc.release_all(sess.pages)
            sess.pages = []
            self._bt[sess.slot] = 0
        if self._wt is not None:
            self._wt.release_slot(sess.slot)
        if not sess.stream.closed:
            sess.stream.close(reason=reason or "finished")
        self._finalize_obs(sess, reason or "finished")

    def _run(self) -> None:
        # the loop's phases PARTITION it: ``ph`` moves the clock's
        # cursor from one leaf of LM_STEP_PHASES to the next, so from
        # the top of the ``while`` to the top of the next pass every
        # nanosecond belongs to exactly one of them, under the same
        # name on the profiler's clock
        clock = self._clock
        try:
            self._ensure_engine()
            import jax.profiler as _prof
            clock.bind(_prof.TraceAnnotation, _prof.StepTraceAnnotation)
            ph = clock.switch
            while True:
                clock.round_end()
                ph(PH_SCHED)
                clock.tick()
                # parked sessions re-enter BEFORE new admits (they
                # were serving first), and a drain-aborted host tier
                # closes them under its named reason here
                self._service_parked()
                with self._lock:
                    if len(self._pending) > 1:
                        # SLO order: interactive joins drain first
                        # (stable within a tier — FIFO)
                        self._pending = deque(sorted(
                            self._pending,
                            key=lambda s: s.tier_rank))
                    pending = []
                    while self._pending and \
                            len(self._sessions) + len(pending) \
                            < self.slots:
                        pending.append(self._pending.popleft())
                    idle = not self._sessions and not pending \
                        and not self._pending and not self._parked \
                        and self._flight is None
                if idle:
                    self._wake.clear()
                    # re-check AFTER the clear: a join landing between
                    # the idle check and the clear set the event we
                    # just cleared — its session must not wait out the
                    # whole linger for its first token
                    with self._lock:
                        if self._pending:
                            continue
                    ph(PH_IDLE_WAIT)
                    clock.tick()    # level before the loop blocks
                    if not self._wake.wait(self.idle_linger_s):
                        with self._lock:
                            if not self._pending \
                                    and not self._sessions:
                                self._thread = None
                                clock.close()
                                return
                    continue
                if pending or self._sessions:
                    # a pass with sessions to serve: it dispatches a
                    # step unless every admission is refused or
                    # every session's last step is already in flight
                    clock.round_begin(self._steps)
                # queue wait ends here, before the admission's work
                _lmt.on_admit(pending)
                for sess in pending:
                    # join-mid-batch: bucketed prefill + slot insert,
                    # BETWEEN steps (bucketing keeps a fresh prompt
                    # length from stalling live sessions on an XLA
                    # compile; the next step emits the first token) —
                    # or, chunked, just the slot grab: _chunk_round
                    # below scatters the context under the budget.
                    # The host's share (prefix lookup, page alloc)
                    # runs beside the step in flight and the programs
                    # queue behind it
                    self._admit(sess)
                    ph(PH_SCHED)
                    if sess.slot >= 0:
                        clock.joined()
                        if sess.fill >= sess.ctx_len:
                            # every program of its context is queued
                            clock.filled(sess.tl)
                # the Sarathi half BEFORE the decode round: a fill
                # completed this round teacher-forces its first token
                # on THIS round's step, which also carries the
                # round's last slice
                ride = self._chunk_round()
                if not self._sessions and self._flight is None:
                    if self._parked:
                        # only parked sessions left and none could
                        # resume yet (another holder must release
                        # first): timed poll, never a busy spin
                        import time as _time
                        ph(PH_IDLE_WAIT)
                        _time.sleep(0.005)
                    continue
                # the step in flight is read AFTER the next is queued:
                # the device goes from one to the other while the host
                # walks, emits and evicts
                landing, self._flight = self._flight, None
                if ride is not None or self._active.any():
                    # a slice alone is work too; with neither, every
                    # occupied slot has its last step in flight
                    self._flight = self._dispatch(landing is not None,
                                                  ride)
                if landing is not None:
                    self._land(landing)
        except Exception:
            LOG.exception("continuous batcher crashed; closing "
                          "sessions")
            clock.close()
            with self._lock:
                sessions = list(self._sessions.values()) \
                    + list(self._pending) + list(self._parked)
                self._sessions.clear()
                self._pending.clear()
                self._parked = []
                # free every slot: a leaked _active bit would make the
                # next incarnation's _admit run out of slots forever
                self._active[:] = False
                self._tokens[:] = 0
                # nothing in flight survives, and the device's copies
                # of the step's inputs are made again from the mirrors
                self._flight = None
                self._tok_set.clear()
                self._tokens_d = self._active_up = self._bt_up = None
                # the crashed _step DONATED self._cache — on donating
                # backends those buffers are gone; drop the pool so
                # the next incarnation's _ensure_engine rebuilds it.
                # State reset (incl. _thread) happens BEFORE any
                # fallible allocation: a rebuild failure under the
                # same pressure must not wedge join() forever.  The
                # allocator triple goes with the pool: its refcounts
                # describe rows that no longer exist.
                self._cache = None
                self._bt[:] = 0
                self._alloc = None
                self._wt = None
                self._prefix = None
                self._host = None
                self._thread = None
            for sess in sessions:
                try:
                    sess.stream.close(reason="decode_error")
                except Exception:
                    pass
                try:
                    self._finalize_obs(sess, "decode_error")
                except Exception:
                    pass


class LMService(Service):
    """``Generate`` — greedy completion; ``Decode`` — server-streaming
    completion with continuous batching (one token chunk per step per
    session); ``Info`` — model config JSON."""

    def __init__(self, cfg: Optional[LMConfig] = None, params=None,
                 max_new_cap: int = 128, quantize: bool = False,
                 decode_slots: int = 8, paged: bool = True,
                 page: int = 16, kv_pages: Optional[int] = None,
                 kv_host_slots: int = 0, prefix: bool = True,
                 prefill_chunk_tokens: Optional[int] = None,
                 tiers: Optional[TierRegistry] = None):
        import jax

        self.cfg = cfg or LMConfig(vocab=256, dim=64, heads=4, depth=2,
                                   max_seq=128, remat=False)
        self.params = params if params is not None else init_params(
            jax.random.PRNGKey(0), self.cfg)
        self.quantized = quantize
        if quantize:
            # weight-only int8 for serving: decode streams every weight
            # per token, so halving the bytes ≈ halves the step time
            # (ops/quant.py); training params stay untouched upstream
            from ..ops.quant import quantize_lm_params
            self.params = quantize_lm_params(self.params)
        self.max_new_cap = max_new_cap
        from ..ops.quant import quantized_nbytes
        self._param_bytes = quantized_nbytes(self.params)  # immutable
        # a latent layer's projections packed for the step's products
        # (models/mla_mixer.py): the same values, so the bytes above
        # and the fingerprint stand; this tree is the service's own
        self.params = mla_mixer.pack_params(self.cfg, self.params)
        # whole-completion scan generator: one device program per
        # request instead of one per token (per-token dispatch dominates
        # single-stream decode).  Programs compile per
        # (batch, prompt_len, bucketed max_new) and are reused.
        from .transformer_lm import make_scan_generator
        self._gen = make_scan_generator(self.cfg, self.params) \
            if self.cfg.plain_block() else None
        # continuous-batching decode engine, built lazily at the first
        # Decode call (Generate-only deployments never pay the batch
        # step compile).  scan_layers configs serve Generate only.
        self.decode_slots = int(decode_slots)
        # paged-KV serving knobs (kv/pages allocator)
        if not paged:
            raise ValueError(_PAGED_ONLY)
        self.page = int(page)
        self.kv_pages = kv_pages
        self.kv_host_slots = int(kv_host_slots)
        self.prefix = bool(prefix)
        # SLO-scheduler knobs (ContinuousBatcher docstring)
        self.prefill_chunk_tokens = prefill_chunk_tokens
        self.tiers = tiers
        self._batcher: Optional[ContinuousBatcher] = None
        self._batcher_lock = threading.Lock()

    def batcher(self) -> ContinuousBatcher:
        with self._batcher_lock:
            if self._batcher is None:
                self._batcher = ContinuousBatcher(
                    self.cfg, self.params, slots=self.decode_slots,
                    page=self.page, pages=self.kv_pages,
                    host_slots=self.kv_host_slots,
                    prefix=self.prefix,
                    prefill_chunk_tokens=self.prefill_chunk_tokens,
                    tiers=self.tiers)
            return self._batcher

    def Generate(self, cntl, request):
        try:
            b, s, max_new = struct.unpack_from("<III", request)
            prompt = np.frombuffer(request, dtype=np.int32,
                                   offset=12).reshape(b, s)
        except (struct.error, ValueError) as e:
            cntl.set_failed(Errno.EREQUEST, f"bad generate request: {e}")
            return None
        if self._gen is None:
            cntl.set_failed(
                Errno.EREQUEST,
                "Generate (the contiguous cache) serves the program's "
                "first block only: use Decode")
            return None
        if b == 0 or s == 0:
            cntl.set_failed(Errno.EREQUEST, "empty prompt")
            return None
        if max_new <= 0 or max_new > self.max_new_cap:
            cntl.set_failed(Errno.EREQUEST,
                            f"max_new must be in [1, {self.max_new_cap}]")
            return None
        if s + max_new > self.cfg.max_seq:
            cntl.set_failed(
                Errno.EREQUEST,
                f"prompt {s} + max_new {max_new} exceeds max_seq "
                f"{self.cfg.max_seq}")
            return None
        if (prompt < 0).any() or (prompt >= self.cfg.vocab).any():
            cntl.set_failed(Errno.EREQUEST, "prompt ids out of vocab")
            return None
        # bucket max_new to the next power of two so distinct requests
        # share compiled programs; slice the surplus off
        bucket = 1
        while bucket < max_new:
            bucket <<= 1
        bucket = min(bucket, self.max_new_cap,
                     self.cfg.max_seq - s)
        out = np.asarray(self._gen(prompt, int(bucket)),
                         dtype=np.int32)[:, :max_new]
        return struct.pack("<II", *out.shape) + out.tobytes()

    def _check_decode_request(self, cntl, request):
        """Shared ``Decode`` validation + stream accept (the monolithic
        service and the kv/ prefill tier serve the SAME wire contract).
        Returns ``(prompt[1, s], max_new, stream)`` or None with the
        controller already failed."""
        from ..streaming import StreamOptions, stream_accept

        try:
            b, s, max_new = struct.unpack_from("<III", request)
            prompt = np.frombuffer(request, dtype=np.int32,
                                   offset=12).reshape(b, s)
        except (struct.error, ValueError) as e:
            cntl.set_failed(Errno.EREQUEST, f"bad decode request: {e}")
            return None
        if b != 1 or s == 0:
            cntl.set_failed(Errno.EREQUEST,
                            "Decode streams one session per call")
            return None
        if max_new <= 0 or max_new > self.max_new_cap:
            cntl.set_failed(Errno.EREQUEST,
                            f"max_new must be in [1, {self.max_new_cap}]")
            return None
        if s + max_new > self.cfg.max_seq:
            cntl.set_failed(
                Errno.EREQUEST,
                f"prompt {s} + max_new {max_new} exceeds max_seq "
                f"{self.cfg.max_seq}")
            return None
        if (prompt < 0).any() or (prompt >= self.cfg.vocab).any():
            cntl.set_failed(Errno.EREQUEST, "prompt ids out of vocab")
            return None
        if self.cfg.scan_layers:
            cntl.set_failed(Errno.EREQUEST,
                            "Decode serves unrolled configs only")
            return None
        stream = stream_accept(cntl, StreamOptions())
        if stream is None:
            cntl.set_failed(Errno.EREQUEST,
                            "Decode requires a client stream "
                            "(stream_create before the call)")
            return None
        return prompt, int(max_new), stream

    def model_fingerprint(self) -> bytes:
        """Identity the kv/ handoff handshake compares: two tiers may
        exchange KV pages only when they serve the same architecture
        and weight image (a page layout is meaningless under any other
        model).  ``param_bytes`` stands in for a weight hash — cheap,
        and wrong only for same-shape different-weight deployments,
        which a fleet rollout should version explicitly anyway."""
        c = self.cfg
        fp = (f"{c.vocab}:{c.dim}:{c.heads}:{c.depth}:{c.max_seq}:"
              f"{self._param_bytes}:{int(self.quantized)}")
        if not c.plain_block():
            # what else decides the layout of pages and state blocks
            fp += (f":{c.kv_heads}:{int(c.rope)}:{c.ffn}:{c.ffn_dim}:"
                   f"{c.schedule()}:"
                   f"{c.ssm_inner}x{c.ssm_state}x{c.ssm_conv}")
        if c.has_kda:
            fp += f":{c.kda_heads}x{c.kda_head_dim}x{c.kda_conv}"
        if c.has_mamba2:
            fp += (f":{c.mamba2_heads}x{c.mamba2_head_dim}x{c.mamba2_state}"
                   f"x{c.mamba2_groups}x{c.mamba2_conv}x{c.mamba2_chunk}")
            if c.mamba2_norm_groups != 1:
                fp += f"n{c.mamba2_norm_groups}"        # the norm's groups
        fp += _scale_note(c)
        if c.passes > 1 or c.post_norms:
            # pages a pass; the norms behind the branches
            fp += f":{c.passes}p{int(c.post_norms)}:{c.rope_theta}"
        if c.has_window or c.parallel_block:
            fp += (f":{c.head_dim}:{c.norm}:{int(c.parallel_block)}:"
                   f"{c.window}:" + "".join(
                       "gw"[bool(w)] for w in c.windows) + ":"
                   + "".join("nr"[r] for r in c.ropes)
                   + f":{c.rope_pairs}:{c.rope_theta}")
        if c.has_latent or c.has_experts:
            fp += (f":{c.q_lora_rank}x{c.kv_lora_rank}x{c.qk_nope_dim}x"
                   f"{c.qk_rope_dim}x{c.v_head_dim}:{c.ffn_schedule()}:"
                   f"{c.expert_dim}x{c.experts_routed}x{c.experts_top_k}:"
                   f"{c.experts_held[0]}-{c.experts_held[1]}")
        return (fp + _router_note(c)).encode()

    def Decode(self, cntl, request):
        """Server-streaming decode: same request wire format as
        ``Generate`` at batch 1, but the caller attaches a stream
        (``stream_create`` before the call) and tokens arrive as int32
        chunks — one per decode step — while the session rides the
        continuous batch (new sessions join between steps, finished
        ones evict; the stream closes with reason ``finished``).  The
        unary response is ``<u32 max_new>`` (the token count the
        stream will carry)."""
        parsed = self._check_decode_request(cntl, request)
        if parsed is None:
            return None
        prompt, max_new, stream = parsed
        # the request's TLV-22 identity picks the session's SLO tier
        meta = getattr(cntl, "request_meta", None)
        tenant = getattr(meta, "tenant", b"") if meta is not None \
            else b""
        self.batcher().join(stream, prompt[0].copy(), max_new,
                            tenant=tenant,
                            span=self._session_span(cntl))
        return struct.pack("<I", max_new)

    def _session_span(self, cntl):
        """Decode-session rpcz span: when the Decode RPC itself is
        traced (its server span exists — forced for a propagated trace
        id, or passively sampled), the session outliving the RPC gets
        its own FORCED child span under the SAME trace id, so the
        batcher's step events (join / chunk slices / first token /
        evict) land in the request's trace — across a disagg handoff
        too, both halves stitch under one id with no new wire format
        (the handoff Controller propagates the trace TLVs any request
        carries)."""
        req_span = getattr(cntl, "span", None)
        if req_span is None:
            return None
        from ..rpcz import Span
        span = Span("LMService.DecodeSession",
                    trace_id=req_span.trace_id,
                    parent_span_id=req_span.span_id)
        span.remote_side = req_span.remote_side
        return span

    def Info(self, cntl, request):
        import json
        c = self.cfg
        info = {"vocab": c.vocab, "dim": c.dim,
                "heads": c.heads, "depth": c.depth,
                "max_seq": c.max_seq,
                "quantized": self.quantized,
                "param_bytes": self._param_bytes,
                "attention": {"forms": attention_forms(c)}}
        if not c.plain_block():
            info.update(
                kv_heads=c.kv_heads, ffn=c.ffn, ffn_dim=c.ffn_dim,
                mixers=c.schedule(),
                state_pool={"slots": self.decode_slots,
                            "bytes": self.decode_slots
                            * state_slot_bytes(c),
                            "kinds": state_kinds(c)})
        if c.passes > 1:
            # the layers run several times a token, pages a pass
            info["loop"] = {
                "passes": c.passes, "layers": c.depth,
                "post_norms": c.post_norms,
                "token_bytes": paged_page_bytes(c, self.page) // self.page,
                "fill_span": c.fill_span,
                # the spans of prompts that rode a step (the batcher's)
                "spans_rode": self._batcher._loop["spans_rode"]
                if self._batcher is not None else 0}
        if c.has_window:
            # two page classes: whole contexts, and windows
            info["window_pool"] = {
                "layers": list(c.window_layers()), "window": c.window,
                "pages": c.window_pages(self.decode_slots, self.page),
                "fill_span": c.fill_span}
        if c.has_window or c.passes > 1:
            # prompts go through the pages in spans of whole pages
            info["fill"] = {"fill_span": c.fill_span,
                            "span_pages": c.fill_span // self.page,
                            "table_pages": c.max_seq // self.page}
        if c.has_latent:
            # one pool a latent layer, a row a token: key and value
            info["packed_bytes"] = mla_mixer.packed_bytes(c, self.params)
            info["latent_pool"] = {
                "layers": len(c.mla_layers()), "row": c.latent_row(),
                "row_bytes": c.latent_row() * 4,
                "token_bytes": latent_row_bytes(c)}
        if c.has_experts:
            info.update(
                ffns=c.ffn_schedule(),
                experts={"routed": c.experts_routed,
                         "held": list(c.experts_held),
                         "top_k": c.experts_top_k, "dim": c.expert_dim,
                         "shared": c.shared_experts,
                         "route_scale": c.route_scale,
                         "scoring": c.router_scoring,
                         "router_at": c.router_at})
        if c.has_mamba2:
            # the Mamba-2 layers' widths and the batcher's counts
            # (``kv_stats()["mamba2"]``)
            bat = self._batcher
            info["mamba2"] = {
                "heads": c.mamba2_heads, "head_dim": c.mamba2_head_dim,
                "state": c.mamba2_state, "groups": c.mamba2_groups,
                "conv": c.mamba2_conv, "chunk": c.mamba2_chunk,
                **state_kinds(c)["mamba2"],
                **(bat._mamba2 if bat is not None else {})}
        if _scale_note(c):
            info["scales"] = {"embed": c.embed_scale,
                              "residual": c.residual_scale,
                              "attn": c.attn_scale, "logit": c.logit_scale}
        return json.dumps(info).encode()
