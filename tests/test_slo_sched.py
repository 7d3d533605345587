"""SLO-tiered batch scheduler (ISSUE 17): chunked prefill, priority
preemption.

Three planes:

- IDENTITY: every scheduler mode must emit the exact tokens of the
  monolithic greedy path — chunked prefill (slices of 4 and of 16),
  partial prefix-hit catch-up, and a batch-tier session across
  park/resume;
- POLICY: interactive sessions get chunk budget first, and under pool
  pressure the spill victim is tier-then-footprint — an interactive
  session is NEVER parked while a batch-tier victim exists;
- TELEMETRY: the closed ``SLO_SCHED_EVENTS`` enum is pinned
  member-by-member (the static enum checker requires every name
  anchored here) and an unregistered event asserts loudly at the first
  count.
"""

import struct
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.models.lm_service import (ContinuousBatcher, TierRegistry,
                                        _Session, _reset_sched_for_tests,
                                        count_sched, sched_counters)
from brpc_tpu.models.transformer_lm import (LMConfig, generate,
                                            init_params)
from brpc_tpu.streaming import StreamOptions

# ---------------------------------------------------------------------------
# Closed-event pins (tools/check/enums.py requires every member of the
# scheduler enums anchored under tests/ — this is the anchor)
# ---------------------------------------------------------------------------

SLO_SCHED_PINS = ("sched_chunk_slice", "sched_catchup_slice",
                  "sched_interactive_first", "sched_preempt_batch")


def test_sched_enums_match_pins():
    from brpc_tpu.models.lm_service import SLO_SCHED_EVENTS
    assert SLO_SCHED_EVENTS == SLO_SCHED_PINS
    assert set(sched_counters()) == set(SLO_SCHED_PINS)
    with pytest.raises(AssertionError):
        count_sched("sched_some_new_event")


def test_tier_registry():
    reg = TierRegistry()
    assert reg.tier_of(b"nobody") == "standard"      # default tier
    reg.set_tier(b"alice", "interactive")
    reg.set_tier("bob", "batch")
    # keyed on the NORMALIZED TLV-22 identity: bytes and str agree
    assert reg.tier_of("alice") == "interactive"
    assert reg.tier_of(b"bob") == "batch"
    assert reg.rank_of(b"alice") < reg.rank_of(b"nobody") \
        < reg.rank_of("bob")
    with pytest.raises(ValueError, match="unknown SLO tier"):
        reg.set_tier(b"x", "platinum")
    with pytest.raises(ValueError, match="unknown SLO tier"):
        TierRegistry(default="gold")
    # bounded at the admission plane's tenant cardinality cap
    from brpc_tpu.server.admission import _MAX_TENANTS
    full = TierRegistry()
    for i in range(_MAX_TENANTS):
        full.set_tier(f"t{i}", "batch")
    with pytest.raises(ValueError, match="registry full"):
        full.set_tier("one-too-many", "batch")
    full.set_tier("t0", "interactive")               # updates still land


def test_join_resolves_tier_from_registry():
    reg = TierRegistry()
    reg.set_tier(b"alice", "interactive")
    cfg = LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=32,
                   remat=False)
    bat = ContinuousBatcher(cfg, params=None, tiers=reg)
    sess = _Session(None, np.zeros((3,), np.int32), 4)
    assert sess.tier == "standard"                   # registry-less default
    bat._assign_tier(sess, b"alice")
    assert sess.tier == "interactive" and sess.tier_rank == 0
    bat._assign_tier(sess, b"unknown-tenant")
    assert sess.tier == "standard"


# ---------------------------------------------------------------------------
# harness (mirrors test_kv_disagg's direct-batcher idiom)
# ---------------------------------------------------------------------------

def _setup(seed=0, **kw):
    cfg = LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=32,
                   remat=False, **kw)
    return cfg, init_params(jax.random.PRNGKey(seed), cfg)


def _reset():
    from brpc_tpu.kv import pages as kv_pages
    from brpc_tpu.kv import transport as kv_transport
    kv_pages._reset_for_tests()
    kv_transport._reset_for_tests()
    _reset_sched_for_tests()


class _FakeStream:
    """Batcher-facing stream stub on the Python write lane (the
    batcher only touches closed/options/write/close/id/_native_tx)."""

    def __init__(self):
        self.closed = False
        self.close_reason = None
        self.tokens = []
        self.id = 0
        self._native_tx = None
        self.options = StreamOptions()

    def write(self, data):
        self.tokens.append(struct.unpack("<i", bytes(data))[0])
        return 0

    def close(self, reason=None):
        self.closed = True
        self.close_reason = reason


def _join(bat, prompt, max_new, tenant=None):
    st = _FakeStream()
    bat.join(st, prompt, max_new, tenant=tenant)
    return st


def _finish(*streams, timeout=120.0):
    deadline = time.monotonic() + timeout
    while not all(s.closed for s in streams) \
            and time.monotonic() < deadline:
        time.sleep(0.002)
    assert all(s.closed for s in streams), "decode session never closed"


def _prompt(seed, n, vocab=64):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (n,), 0, vocab, jnp.int32))


# ---------------------------------------------------------------------------
# chunked prefill: identity + budget priority
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [4, 16])
def test_chunked_prefill_identity(chunk):
    """A chunk-filled session (ctx 16 in slices of 4, or in one of 16)
    emits the exact tokens of whole-prompt prefill — the
    garbage-beyond-mask argument made checkable: chunk slices scatter
    through the block table and the stream is bit-identical with the
    monolithic path; the chunk-filled context enters the prefix cache
    exactly like a prefilled one (second session full-hits it)."""
    from brpc_tpu.kv import pages as kv_pages
    _reset()
    cfg, params = _setup()
    prompt = _prompt(3, 17)
    want = np.asarray(generate(params, cfg, prompt[None, :], 6))[0]
    bat = ContinuousBatcher(cfg, params, slots=4,
                            prefill_chunk_tokens=chunk)
    st = _join(bat, prompt, 6)
    _finish(st)
    assert st.tokens == want.tolist()
    assert st.close_reason == "finished"
    assert bat.prefills_run == 1
    assert sched_counters()["sched_chunk_slice"] >= 16 // chunk
    st2 = _join(bat, prompt, 6)
    _finish(st2)
    assert st2.tokens == want.tolist()
    assert bat.prefills_run == 1                 # full prefix hit
    assert kv_pages.prefix_event_counters()["prefix_hit"] == 1


def test_interactive_gets_chunk_budget_first():
    """Two long prompts filling concurrently: the interactive join's
    slices outrank the standard one's for the per-round budget (the
    named decision is counted), and both streams stay exact."""
    _reset()
    cfg, params = _setup()
    reg = TierRegistry()
    reg.set_tier(b"alice", "interactive")
    pa, pb = _prompt(11, 29), _prompt(12, 29)
    want_a = np.asarray(generate(params, cfg, pa[None, :], 3))[0]
    want_b = np.asarray(generate(params, cfg, pb[None, :], 3))[0]
    bat = ContinuousBatcher(cfg, params, slots=2,
                            prefill_chunk_tokens=2, tiers=reg)
    # both joins land before the batcher's first admit round (the
    # engine compile on the batcher thread gates it), so both sessions
    # chunk-fill in the same rounds
    st_b = _join(bat, pb, 3, tenant=b"bob")
    st_a = _join(bat, pa, 3, tenant=b"alice")
    _finish(st_a, st_b)
    assert st_a.tokens == want_a.tolist()
    assert st_b.tokens == want_b.tolist()
    assert sched_counters()["sched_interactive_first"] >= 1


def test_partial_prefix_hit_catches_up_via_chunks():
    """Round-19 REMAINING thread closed: a context sharing only its
    first full page with the cache aliases that page and the remainder
    catches up through chunk slices (counted as catch-up, NOT as a
    prefill) — stream identical with the uncached path."""
    from brpc_tpu.kv import pages as kv_pages
    _reset()
    cfg = LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=48,
                   remat=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    base = _prompt(6, 16)
    pa = np.concatenate([base, _prompt(7, 17)])   # two full pages cached
    pb = np.concatenate([base, _prompt(8, 17)])   # only page 1 matches
    want_b = np.asarray(generate(params, cfg, pb[None, :], 4))[0]
    bat = ContinuousBatcher(cfg, params, slots=2, page=16)
    st_a = _join(bat, pa, 4)
    _finish(st_a)
    pf = bat.prefills_run
    st_b = _join(bat, pb, 4)
    _finish(st_b)
    assert st_b.tokens == want_b.tolist()
    assert st_b.close_reason == "finished"
    assert bat.prefills_run == pf                # the hit avoided one
    assert kv_pages.prefix_event_counters()["prefix_partial_hit"] == 1
    assert sched_counters()["sched_catchup_slice"] >= 1


@pytest.mark.parametrize("cls", ["ContinuousBatcher", "LMService"])
def test_paged_false_raises_by_name(cls):
    """The contiguous slot cache is gone (PR 29): the keyword that
    once chose it stays only for the benchmark's files, and ``False``
    is refused, naming the PR."""
    from brpc_tpu.models import lm_service
    cfg, params = _setup()
    with pytest.raises(ValueError, match="paged=False.*PR 29"):
        getattr(lm_service, cls)(cfg, params, paged=False)


# ---------------------------------------------------------------------------
# tier-aware preemption: batch spills first, interactive never does
# ---------------------------------------------------------------------------

def test_interactive_never_spilled_while_batch_victim_exists(monkeypatch):
    """Pool pressure from an interactive join: the spill victim is the
    BATCH session (tier-then-footprint), never the interactive one —
    every _park call in the run is spied on — and the preempted batch
    session resumes bit-exact."""
    _reset()
    cfg, params = _setup()
    reg = TierRegistry()
    reg.set_tier(b"alice", "interactive")
    reg.set_tier(b"bob", "batch")
    parked_tiers = []
    orig_park = ContinuousBatcher._park

    def spy(self, sess):
        parked_tiers.append(sess.tier)
        return orig_park(self, sess)

    monkeypatch.setattr(ContinuousBatcher, "_park", spy)
    prompt = _prompt(9, 14)
    want_bob = np.asarray(generate(params, cfg, prompt[None, :], 16))[0]
    want_alice = np.asarray(generate(params, cfg, prompt[None, :], 8))[0]
    # 10 usable pages of 4: bob (ctx 13 + 16 new -> 8 pages) fits
    # alone; alice (6 pages) only if bob spills
    bat = ContinuousBatcher(cfg, params, slots=3, page=4,
                            pages=11, host_slots=32, prefix=False,
                            tiers=reg)
    st_bob = _join(bat, prompt, 16, tenant=b"bob")
    deadline = time.monotonic() + 120
    while not st_bob.tokens and time.monotonic() < deadline:
        time.sleep(0.002)                # bob live before alice asks
    assert st_bob.tokens, "batch session never started"
    st_alice = _join(bat, prompt, 8, tenant=b"alice")
    _finish(st_alice, st_bob)
    assert st_alice.tokens == want_alice.tolist()
    assert st_bob.tokens == want_bob.tolist()    # park/resume bit-exact
    assert st_alice.close_reason == st_bob.close_reason == "finished"
    assert bat.spills >= 1 and bat.resumes >= 1
    assert parked_tiers and set(parked_tiers) == {"batch"}, parked_tiers
    assert sched_counters()["sched_preempt_batch"] >= 1
