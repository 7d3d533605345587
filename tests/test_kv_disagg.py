"""Disaggregated prefill/decode serving + the KV-cache transfer
subsystem (ISSUE 15, brpc_tpu/kv/).

Four planes, mirroring test_data_plane's discipline:

- END-TO-END: a prefill tier exports a session's KV pages, the decode
  tier imports them MID-REQUEST into the continuous batch, tokens
  stream to the original client — and the decoded tokens are
  bit-identical with the monolithic path on every lane (ici/shm/copy);
- ZERO-COPY: the same-host (ici-lane) handoff moves zero payload bytes
  through the message path — BOTH copy ledgers (engine
  ``data_plane_copies`` + Python ``copy_audit``) pinned at exactly 0,
  while the forced shm lane admits exactly its per-page staging memcpy
  (the ledger is proven live, not merely quiet);
- LIFECYCLE: generation-checked double-free/stale-import rejected
  loudly (client ERESPONSE, never "success with an empty cache"), leak
  pin after 1k handoffs, owner-sweep on socket death, drain settles
  outstanding exported pages;
- FALLBACKS: every ineligible shape falls back under a NAMED reason
  from the closed enum (no "unknown" bucket), each pinned here.
"""

import struct
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.butil.flags import get_flag, set_flag
from brpc_tpu.butil.status import Errno
from brpc_tpu.client import Channel, Controller
from brpc_tpu.models.lm_service import LMService, pack_generate_request, \
    unpack_token
from brpc_tpu.models.transformer_lm import LMConfig, generate, init_params
from brpc_tpu.server import Server, ServerOptions
from brpc_tpu.streaming import Stream, StreamOptions, stream_create

from conftest import require_native  # noqa: E402

# ---------------------------------------------------------------------------
# Closed-reason pins (the static enum checker requires every member to
# be anchored under tests/ — this is the anchor; renaming/adding a
# reason fails here until acknowledged on both sides)
# ---------------------------------------------------------------------------

KV_FALLBACK_PINS = (
    "kv_disabled", "kv_probe_failed", "kv_model_mismatch",
    "kv_shm_unavailable", "kv_page_over_slot", "kv_ring_exhausted",
    "kv_pages_exhausted", "kv_peer_remote", "kv_stream_not_local",
    "kv_import_rejected", "kv_no_decode_tier",
)
KV_CLOSE_PINS = ("kv_handoff_failed",)


def test_kv_reason_enums_match_pins():
    from brpc_tpu.kv import KV_CLOSE_REASONS, KV_FALLBACK_REASONS
    assert KV_FALLBACK_REASONS == KV_FALLBACK_PINS
    assert KV_CLOSE_REASONS == KV_CLOSE_PINS


def test_no_unknown_kv_bucket():
    from brpc_tpu.kv import count_fallback, kv_fallback_counters
    assert set(kv_fallback_counters()) == set(KV_FALLBACK_PINS)
    with pytest.raises(AssertionError):
        count_fallback("kv_some_new_reason")


# ---------------------------------------------------------------------------
# Harness
# ---------------------------------------------------------------------------

def _setup(seed=0, **kw):
    cfg = LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=32,
                   remat=False, **kw)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1),
                                           (1, 8), 0, cfg.vocab,
                                           jnp.int32))
    return cfg, params, prompt


def _reset_kv():
    from brpc_tpu.kv import pages as kv_pages
    from brpc_tpu.kv import transport as kv_transport
    kv_pages._reset_for_tests()
    kv_transport._reset_for_tests()


def _two_tier(cfg, params, force_lane=None, decode_slots=4,
              native=False, decode_cfg=None, decode_params=None,
              decode_lm_kw=None, **prefill_kw):
    """Build a decode tier (LM + KV services) and a prefill tier
    pointed at it; returns (pre_srv, dec_srv, dec_lm, pre_svc, dch)."""
    from brpc_tpu.kv import DecodeTierService, KvTransport, \
        PrefillService

    def opts():
        o = ServerOptions()
        if native:
            o.native = True
            o.usercode_inline = False    # handlers run nested RPCs
        return o

    dec_lm = LMService(cfg=decode_cfg or cfg,
                       params=params if decode_params is None
                       else decode_params,
                       decode_slots=decode_slots,
                       **(decode_lm_kw or {}))
    dec_srv = Server(opts())
    dec_srv.add_service(dec_lm, name="LM")
    dec_srv.add_service(DecodeTierService(dec_lm), name="KV")
    assert dec_srv.start("127.0.0.1:0") == 0
    dch = Channel()
    dch.init(str(dec_srv.listen_endpoint))
    pre_svc = PrefillService(
        cfg=cfg, params=params, decode_channel=dch,
        transport=KvTransport(force_lane=force_lane),
        decode_slots=decode_slots, **prefill_kw)
    pre_srv = Server(opts())
    pre_srv.add_service(pre_svc, name="LM")
    assert pre_srv.start("127.0.0.1:0") == 0
    return pre_srv, dec_srv, dec_lm, pre_svc, dch


def _stream_decode(srv, prompt, max_new, timeout=120.0):
    """One streamed decode session -> (tokens, close_reason, ttft_s)."""
    toks, closed, first = [], [], []

    def on_received(st, msgs):
        if not first:
            first.append(time.monotonic())
        toks.extend(unpack_token(m) for m in msgs)

    ch = Channel()
    ch.init(str(srv.listen_endpoint))
    cntl = Controller()
    cntl.timeout_ms = int(timeout * 1000)
    stream_create(cntl, StreamOptions(
        on_received=on_received,
        on_closed=lambda st: closed.append(st.close_reason)))
    t0 = time.monotonic()
    c = ch.call_method("LM.Decode",
                       pack_generate_request(prompt, max_new),
                       cntl=cntl)
    assert not c.failed, (c.error_code, c.error_text)
    deadline = time.monotonic() + timeout
    while not closed and time.monotonic() < deadline:
        time.sleep(0.005)
    assert closed, "decode stream never closed"
    return toks, closed[0], (first[0] - t0 if first else None)


# ---------------------------------------------------------------------------
# End-to-end: two-tier == monolithic, on every lane
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lane", [None, "shm", "copy"],
                         ids=["auto-ici", "shm", "copy"])
def test_two_tier_tokens_identical_to_monolithic(lane):
    """The acceptance demo: prefill worker exports the session's KV
    pages, the decode worker imports them mid-request and joins the
    continuous batch, tokens stream to the ORIGINAL client — and the
    token stream is identical with the monolithic path (greedy
    ``generate``) on the auto-picked ici lane AND the forced shm/copy
    lanes."""
    from brpc_tpu.kv import kv_stats, outstanding_pages
    if lane == "shm":
        from brpc_tpu.transport import shm_ring
        if not shm_ring.shm_supported():
            pytest.skip("no shm support in sandbox")
    _reset_kv()
    cfg, params, prompt = _setup()
    pre_srv, dec_srv, dec_lm, _pre, _dch = _two_tier(
        cfg, params, force_lane=lane)
    try:
        toks, reason, ttft = _stream_decode(pre_srv, prompt, 6)
        want = np.asarray(generate(params, cfg, prompt, 6))[0]
        assert toks == want.tolist()
        assert reason == "finished"
        assert ttft is not None
        st = kv_stats()
        assert st["sessions"] == 1
        assert st[f"{lane or 'ici'}_sessions"] == 1
        assert st["local_fallbacks"] == 0
        # the decode ran on the DECODE tier's batcher, not locally
        assert dec_lm.batcher().steps_run() >= 6
        # every exported page settled once the handoff completed
        assert outstanding_pages() == 0
    finally:
        pre_srv.stop()
        dec_srv.stop()


def test_handed_off_session_joins_live_batch():
    """Continuous batching across tiers: a session decoding DIRECTLY
    on the decode tier and a handed-off session share one live batch;
    both finish with their solo-greedy tokens."""
    _reset_kv()
    cfg, params, prompt = _setup()
    p2 = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (1, 5),
                                       0, cfg.vocab, jnp.int32))
    pre_srv, dec_srv, dec_lm, _pre, _dch = _two_tier(cfg, params)
    try:
        res = {}
        t1 = threading.Thread(target=lambda: res.__setitem__(
            "direct", _stream_decode(dec_srv, prompt, 10)))
        t1.start()
        time.sleep(0.3)          # direct session is mid-generation
        res["handoff"] = _stream_decode(pre_srv, p2, 4)
        t1.join(120)
        wa = np.asarray(generate(params, cfg, prompt, 10))[0]
        wb = np.asarray(generate(params, cfg, p2, 4))[0]
        assert res["direct"][0] == wa.tolist()
        assert res["handoff"][0] == wb.tolist()
        assert res["direct"][1] == res["handoff"][1] == "finished"
    finally:
        pre_srv.stop()
        dec_srv.stop()


def test_same_host_handoff_zero_copies_both_ledgers():
    """THE zero-copy pin: a same-host (shared-runtime) handoff of a
    512KB session cache moves ZERO payload bytes through the message
    path — the engine ``data_plane_copies`` ledger of BOTH tiers and
    the Python ``copy_audit`` both read exactly 0 across the whole
    session.  The forced-shm control run then admits exactly its
    per-page ``stage_shm`` memcpy, proving the ledger is live."""
    require_native()
    from brpc_tpu.butil import copy_audit
    from brpc_tpu.kv import kv_stats
    from brpc_tpu.transport import shm_ring
    _reset_kv()
    # page size 256KB > AUDIT_FLOOR: a staged/serialized page would
    # be visible to the audit — silence means zero-copy, not smallness
    cfg = LMConfig(vocab=128, dim=128, heads=4, depth=2, max_seq=512,
                   remat=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.arange(8, dtype=np.int32)[None, :] % cfg.vocab
    pre_srv, dec_srv, _dec_lm, _pre, _dch = _two_tier(
        cfg, params, native=True)
    engines = [s._native_bridge.engine for s in (pre_srv, dec_srv)]
    try:
        want = np.asarray(generate(params, cfg, prompt, 4))[0]
        _stream_decode(pre_srv, prompt, 4)       # warm compiles first

        def ledgers():
            total = 0
            for eng in engines:
                total += sum(eng.telemetry()["data_plane_copies"]
                             .values())
            return total

        base = ledgers()
        with copy_audit.audit() as snap:
            toks, reason, _ = _stream_decode(pre_srv, prompt, 4)
            counts, _nb = snap()
        assert toks == want.tolist()
        assert reason == "finished"
        assert kv_stats()["ici_sessions"] >= 1
        assert sum(counts.values()) == 0, counts       # Python ledger
        assert ledgers() - base == 0                   # engine ledgers
    finally:
        pre_srv.stop()
        dec_srv.stop()

    # control arm: the forced shm lane admits exactly ONE staging
    # memcpy per page (2 layers x k/v = 4 pages) and nothing else
    if not shm_ring.shm_supported():
        return
    _reset_kv()
    shm_ring._reset_for_tests()
    pre_srv, dec_srv, _dec_lm, _pre, _dch = _two_tier(
        cfg, params, force_lane="shm", native=True)
    try:
        _stream_decode(pre_srv, prompt, 4)       # handshake + compiles
        with copy_audit.audit() as snap:
            toks, _reason, _ = _stream_decode(pre_srv, prompt, 4)
            counts, _nb = snap()
        assert toks == want.tolist()
        assert counts["stage_shm"] == 2 * cfg.depth, counts
        assert counts["ingest"] == counts["materialize"] == 0, counts
    finally:
        pre_srv.stop()
        dec_srv.stop()
        shm_ring._reset_for_tests()


def test_two_tier_over_native_stream_lane():
    """Handed-off sessions stream their tokens over the engine's
    kind-5 lane: the client's stream on the PREFILL tier is adopted
    natively, and the decode tier's batcher writes ride it."""
    require_native()
    _reset_kv()
    cfg, params, prompt = _setup()
    from brpc_tpu.kv import DecodeTierService, KvTransport, \
        PrefillService

    def native_opts(inline):
        o = ServerOptions()
        o.native = True
        o.usercode_inline = inline
        return o

    dec_lm = LMService(cfg=cfg, params=params, decode_slots=4)
    dec_srv = Server(native_opts(False))
    dec_srv.add_service(dec_lm, name="LM")
    dec_srv.add_service(DecodeTierService(dec_lm), name="KV")
    assert dec_srv.start("127.0.0.1:0") == 0
    dch = Channel()
    dch.init(str(dec_srv.listen_endpoint))
    # the prefill tier runs inline (kind-5 adoption requires the slim
    # lane) — its Decode handler's nested handoff RPC targets the
    # OTHER server's loops, so the nested wait cannot deadlock
    pre_svc = PrefillService(cfg=cfg, params=params, decode_channel=dch,
                             transport=KvTransport())
    pre_srv = Server(native_opts(True))
    pre_srv.add_service(pre_svc, name="LM")
    assert pre_srv.start("127.0.0.1:0") == 0
    try:
        _stream_decode(pre_srv, prompt, 4)          # compile warmup
        toks, reason, _ = _stream_decode(pre_srv, prompt, 6)
        want = np.asarray(generate(params, cfg, prompt, 6))[0]
        assert toks == want.tolist()
        assert reason == "finished"
        tele = pre_srv._native_bridge.engine.telemetry()
        # the handed-off session's tokens left through the PREFILL
        # engine's kind-5 chunk path (the decode tier's batcher writes
        # ride the adopted stream)
        assert tele["streams"]["chunks_out"] >= 6, tele["streams"]
    finally:
        pre_srv.stop()
        dec_srv.stop()


# ---------------------------------------------------------------------------
# Page lifecycle: leaks, generations, sweeps, drain
# ---------------------------------------------------------------------------

def test_page_leak_pin_after_1k_handoffs():
    """1000 export→describe→import→release cycles leave the page table
    exactly as found: zero outstanding pages, zero live fabric
    descriptors — the leak pin (bounded table = leaks surface fast)."""
    from brpc_tpu.ici.fabric import in_process_fabric
    from brpc_tpu.kv import process_kv_store
    from brpc_tpu.kv.pages import decode_desc
    _reset_kv()
    store = process_kv_store()
    fabric = in_process_fabric()
    base_desc = fabric.live_descriptors
    page = jnp.arange(1024, dtype=jnp.float32)
    for i in range(1000):
        handles = [store.export_array(page, 4096, owner=("kv", i))
                   for _ in range(4)]
        assert all(h is not None for h in handles)
        for h in handles[:2]:
            # imported half: the importer consumed the registration
            pid, gen, n = decode_desc(h.describe())
            got = store.import_page(pid, gen, n)
            assert got is page
        store.settle_handles(handles)
    assert store.outstanding() == 0
    assert fabric.live_descriptors == base_desc
    st = store.stats()
    assert st["exported"] == 4000 and st["imported"] == 2000


def test_generation_checked_double_free_and_stale_import():
    """The loud-failure matrix: double release raises; import after
    release raises; a RECYCLED page id under a new generation rejects
    the old descriptor (the shm_ring generation discipline)."""
    from brpc_tpu.kv import KvPageError, process_kv_store
    _reset_kv()
    store = process_kv_store()
    page = jnp.ones((8,), jnp.float32)
    h = store.export_array(page, 32)
    store.release(h.page_id, h.gen)
    with pytest.raises(KvPageError, match="double/stale"):
        store.release(h.page_id, h.gen)              # double free
    with pytest.raises(KvPageError, match="stale"):
        store.import_page(h.page_id, h.gen, 32)      # stale import
    # recycle the id: the OLD generation's descriptor must not resolve
    h2 = store.export_array(page, 32)
    assert h2.page_id == h.page_id and h2.gen != h.gen
    with pytest.raises(KvPageError, match="stale"):
        store.import_page(h.page_id, h.gen, 32)
    # double import of a live page is loud too
    assert store.import_page(h2.page_id, h2.gen, 32) is page
    with pytest.raises(KvPageError, match="already imported"):
        store.import_page(h2.page_id, h2.gen, 32)
    store.release(h2.page_id, h2.gen)
    assert store.outstanding() == 0


def test_stale_import_over_rpc_is_eresponse_never_empty_cache():
    """A handoff manifest naming already-settled pages must FAIL the
    RPC with ERESPONSE — the decode tier never seats a session on an
    empty cache and the batcher never sees it."""
    from brpc_tpu.kv import process_kv_store
    from brpc_tpu.kv.transport import (LANE_ICI, SessionManifest,
                                       encode_manifest, stream_auth)
    from brpc_tpu.models.transformer_lm import export_decode_cache
    _reset_kv()
    cfg, params, prompt = _setup()
    pre_srv, dec_srv, dec_lm, pre_svc, dch = _two_tier(cfg, params)
    try:
        # export a real session cache, then settle it (stale descs)
        from brpc_tpu.models.lm_service import bucketed_prefill
        cache1, ctx_len = bucketed_prefill(pre_svc._ensure_prefill(),
                                           cfg, prompt[0])
        pages = export_decode_cache(cfg, cache1)
        store = process_kv_store()
        handles = [store.export_array(a, n) for a, n in pages]
        descs = [h.describe() for h in handles]
        store.settle_handles(handles)
        steps_before = dec_lm.batcher().steps_run()
        client_stream = Stream()         # adoptable, never written
        try:
            man = SessionManifest(LANE_ICI, client_stream.id,
                                  stream_auth(client_stream.id),
                                  ctx_len, int(prompt[0][-1]), 4,
                                  dec_lm.model_fingerprint(), descs)
            cntl = Controller()
            cntl.timeout_ms = 30_000
            c = dch.call_method("KV.ImportSession",
                                encode_manifest(man), cntl=cntl)
            assert c.failed
            assert c.error_code == int(Errno.ERESPONSE), \
                (c.error_code, c.error_text)
            assert "kv_import_rejected" in c.error_text
            assert dec_lm.batcher().live_slots() == 0
            assert dec_lm.batcher().steps_run() == steps_before
        finally:
            client_stream.close()
    finally:
        pre_srv.stop()
        dec_srv.stop()


def test_forged_stream_adoption_rejected():
    """Stream ids are enumerable — a manifest naming another client's
    LIVE stream without the process-keyed adoption tag must be refused
    before any page resolves (no token injection into someone else's
    session)."""
    from brpc_tpu.kv import process_kv_store
    from brpc_tpu.kv.transport import (LANE_ICI, SessionManifest,
                                       encode_manifest)
    from brpc_tpu.models.lm_service import bucketed_prefill
    from brpc_tpu.models.transformer_lm import export_decode_cache
    _reset_kv()
    cfg, params, prompt = _setup()
    pre_srv, dec_srv, dec_lm, pre_svc, dch = _two_tier(cfg, params)
    try:
        cache1, ctx_len = bucketed_prefill(pre_svc._ensure_prefill(),
                                           cfg, prompt[0])
        store = process_kv_store()
        handles = [store.export_array(a, n)
                   for a, n in export_decode_cache(cfg, cache1)]
        victim = Stream()                # a live, adoptable stream
        try:
            man = SessionManifest(LANE_ICI, victim.id, b"\0" * 8,
                                  ctx_len, int(prompt[0][-1]), 4,
                                  dec_lm.model_fingerprint(),
                                  [h.describe() for h in handles])
            cntl = Controller()
            cntl.timeout_ms = 30_000
            c = dch.call_method("KV.ImportSession",
                                encode_manifest(man), cntl=cntl)
            assert c.failed
            assert "kv_stream_not_local" in c.error_text
            # the refusal ran BEFORE any page import: all still live
            assert store.outstanding() == len(handles)
            assert dec_lm.batcher().live_slots() == 0
        finally:
            victim.close()
            store.settle_handles(handles)
    finally:
        pre_srv.stop()
        dec_srv.stop()


def test_ambiguous_handoff_never_double_decodes():
    """A handoff failure that does NOT prove the decode tier never
    seated the session (timeout / transport death) must not fall back
    to local decode — two batchers on one stream is the at-most-once
    violation.  The session is refused with the named close reason
    even under fallback_local=True."""
    from brpc_tpu.kv import PrefillService
    from brpc_tpu.kv.transport import HandoffResult
    _reset_kv()
    cfg, params, prompt = _setup()
    pre_svc = PrefillService(cfg=cfg, params=params,
                             decode_channel=None, decode_slots=4)

    class _AmbiguousTransport:
        def handoff(self, *a, **kw):
            return HandoffResult(False, None, "kv_import_rejected",
                                 ambiguous=True)

    pre_svc.transport = _AmbiguousTransport()
    srv = Server()
    srv.add_service(pre_svc, name="LM")
    assert srv.start("127.0.0.1:0") == 0
    try:
        closed = []
        ch = Channel()
        ch.init(str(srv.listen_endpoint))
        cntl = Controller()
        cntl.timeout_ms = 60_000
        stream_create(cntl, StreamOptions(
            on_closed=lambda st: closed.append(st.close_reason)))
        c = ch.call_method("LM.Decode",
                           pack_generate_request(prompt, 4), cntl=cntl)
        assert c.failed
        assert c.error_code == int(Errno.EINTERNAL)
        deadline = time.time() + 10
        while not closed and time.time() < deadline:
            time.sleep(0.01)
        assert closed == ["kv_handoff_failed"], closed
        # the local batcher never saw the session
        assert pre_svc.batcher().live_slots() == 0
        assert pre_svc.batcher().steps_run() == 0
    finally:
        srv.stop()


def test_owner_sweep_on_socket_death():
    """Pages exported for a connection's session are swept when the
    socket dies before the handoff settles — and the swept pages'
    descriptors reject imports loudly afterwards."""
    from brpc_tpu.kv import (KvPageError, on_socket_closed,
                             outstanding_pages, process_kv_store)
    _reset_kv()
    store = process_kv_store()
    page = jnp.ones((16,), jnp.float32)
    owner = ("kv", 424242)
    handles = [store.export_array(page, 64, owner=owner)
               for _ in range(3)]
    other = store.export_array(page, 64, owner=("kv", 7))
    assert outstanding_pages() == 4
    on_socket_closed(owner)              # the Socket.release hook
    assert outstanding_pages() == 1      # the other conn's page stays
    for h in handles:
        with pytest.raises(KvPageError):
            store.import_page(h.page_id, h.gen, 64)
    store.release(other.page_id, other.gen)
    assert outstanding_pages() == 0


def test_drain_settles_outstanding_exported_pages():
    """The drain plane waits (deadline-bounded) for exported pages to
    settle: a late settle is seen inside the grace; an expired grace
    reports the residue instead of hanging."""
    from brpc_tpu.kv import drain_settle, process_kv_store
    _reset_kv()
    store = process_kv_store()
    page = jnp.ones((16,), jnp.float32)
    h = store.export_array(page, 64)
    # grace too short, nothing settles: residue reported, no hang
    t0 = time.monotonic()
    left = drain_settle(time.monotonic() + 0.15)
    assert left == 1
    assert time.monotonic() - t0 < 5.0
    # a settle landing inside the grace is observed
    threading.Timer(0.1, lambda: store.release(h.page_id, h.gen)).start()
    assert drain_settle(time.monotonic() + 5.0) == 0


# ---------------------------------------------------------------------------
# Named fallbacks — every ineligible shape, pinned
# ---------------------------------------------------------------------------

def _fallback_session(pre_srv, prompt, cfg, params, reason):
    """Run one session expecting a LOCAL fallback under ``reason``:
    tokens still monolithic-identical (the client never notices)."""
    from brpc_tpu.kv import kv_fallback_counters
    before = kv_fallback_counters()[reason]
    toks, close_reason, _ = _stream_decode(pre_srv, prompt, 5)
    want = np.asarray(generate(params, cfg, prompt, 5))[0]
    assert toks == want.tolist()
    assert close_reason == "finished"
    assert kv_fallback_counters()[reason] == before + 1


def test_fallback_no_decode_tier():
    """No decode channel configured: named local fallback, client
    unaffected."""
    from brpc_tpu.kv import PrefillService
    _reset_kv()
    cfg, params, prompt = _setup()
    pre_svc = PrefillService(cfg=cfg, params=params,
                             decode_channel=None, decode_slots=4)
    srv = Server()
    srv.add_service(pre_svc, name="LM")
    assert srv.start("127.0.0.1:0") == 0
    try:
        _fallback_session(srv, prompt, cfg, params,
                          "kv_no_decode_tier")
        assert pre_svc.batcher().steps_run() >= 5   # decoded LOCALLY
    finally:
        srv.stop()


def test_fallback_probe_failed_against_kv_less_peer():
    """A decode channel pointing at a server with no KV service: the
    probe fails once, the session decodes locally under the named
    reason."""
    from brpc_tpu.kv import PrefillService
    _reset_kv()
    cfg, params, prompt = _setup()
    plain = Server()
    plain.add_service(LMService(cfg=cfg, params=params), name="LM")
    assert plain.start("127.0.0.1:0") == 0
    ch = Channel()
    ch.init(str(plain.listen_endpoint))
    pre_svc = PrefillService(cfg=cfg, params=params, decode_channel=ch,
                             decode_slots=4)
    srv = Server()
    srv.add_service(pre_svc, name="LM")
    assert srv.start("127.0.0.1:0") == 0
    try:
        _fallback_session(srv, prompt, cfg, params,
                          "kv_probe_failed")
    finally:
        srv.stop()
        plain.stop()


def test_fallback_model_mismatch():
    """The decode tier serves a DIFFERENT model: the handoff is refused
    at the fingerprint check and the session decodes locally — pages
    never move under a wrong layout."""
    _reset_kv()
    cfg, params, prompt = _setup()
    cfg2 = LMConfig(vocab=64, dim=32, heads=4, depth=3, max_seq=32,
                    remat=False)
    params2 = init_params(jax.random.PRNGKey(9), cfg2)
    pre_srv, dec_srv, _dec_lm, _pre, _dch = _two_tier(
        cfg, params, decode_cfg=cfg2, decode_params=params2)
    try:
        _fallback_session(pre_srv, prompt, cfg, params,
                          "kv_model_mismatch")
    finally:
        pre_srv.stop()
        dec_srv.stop()


def test_fallback_stream_not_local():
    """A handoff naming a stream the decode tier cannot resolve falls
    back under kv_stream_not_local (the cross-process topology's named
    decline — never a silent empty session)."""
    from brpc_tpu.kv import KvTransport, kv_fallback_counters, \
        process_kv_store
    _reset_kv()
    cfg, params, prompt = _setup()
    pre_srv, dec_srv, _dec_lm, pre_svc, dch = _two_tier(cfg, params)
    try:
        from brpc_tpu.models.lm_service import bucketed_prefill
        from brpc_tpu.models.transformer_lm import export_decode_cache
        cache1, ctx_len = bucketed_prefill(pre_svc._ensure_prefill(),
                                           cfg, prompt[0])
        pages = export_decode_cache(cfg, cache1)
        tr = pre_svc.transport
        res = tr.handoff(dch, 999_999_999_999, ctx_len,
                         int(prompt[0][-1]), 4,
                         pre_svc.model_fingerprint(), pages)
        assert not res.ok
        assert res.reason == "kv_stream_not_local"
        assert kv_fallback_counters()["kv_stream_not_local"] == 1
        # the failed handoff settled its leases
        assert process_kv_store().outstanding() == 0
    finally:
        pre_srv.stop()
        dec_srv.stop()


def test_fallback_shm_unavailable_and_peer_remote():
    """Synthetic peer capabilities (the probe cache is the injection
    point): a same-host peer without shm demotes to the copy lane
    under kv_shm_unavailable; a remote-host peer without a transfer
    fabric demotes under kv_peer_remote — the handoff still completes
    (copy lane), the reason is named."""
    from brpc_tpu.kv import kv_fallback_counters
    from brpc_tpu.transport import shm_ring
    _reset_kv()
    cfg, params, prompt = _setup()
    want = np.asarray(generate(params, cfg, prompt, 5))[0]
    for peer, reason in (
            ((b"\0" * 16, shm_ring._host_token(), False),
             "kv_shm_unavailable"),
            ((b"\0" * 16, b"some-other-host", True),
             "kv_peer_remote")):
        pre_srv, dec_srv, _dec_lm, pre_svc, dch = _two_tier(cfg, params)
        try:
            # seed the probe cache with the synthetic peer capability
            pre_svc.transport._peers[dch] = (peer,
                                             time.monotonic() + 60.0)
            toks, close_reason, _ = _stream_decode(pre_srv, prompt, 5)
            assert toks == want.tolist()
            assert close_reason == "finished"
            assert kv_fallback_counters()[reason] >= 1
            from brpc_tpu.kv import kv_stats
            assert kv_stats()["copy_sessions"] >= 1
        finally:
            pre_srv.stop()
            dec_srv.stop()


def test_fallback_page_over_slot_and_ring_exhausted():
    """shm-lane sizing fallbacks: pages over the ring slot size (or a
    ring with too few slots) demote the handoff to the copy lane under
    their named reasons — tokens identical throughout."""
    from brpc_tpu.kv import kv_fallback_counters, kv_stats
    from brpc_tpu.transport import shm_ring
    if not shm_ring.shm_supported():
        pytest.skip("no shm support in sandbox")
    _reset_kv()
    cfg = LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=64,
                   remat=False)                # 8KB pages
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1),
                                           (1, 8), 0, cfg.vocab,
                                           jnp.int32))
    want = np.asarray(generate(params, cfg, prompt, 4))[0]
    slot0 = get_flag("rpc_shm_slot_bytes")
    nslots0 = get_flag("rpc_shm_slots")
    for flag_kv, reason in ((("rpc_shm_slot_bytes", 4096),
                             "kv_page_over_slot"),
                            (("rpc_shm_slots", 1),
                             "kv_ring_exhausted")):
        shm_ring._reset_for_tests()
        set_flag(*flag_kv)
        pre_srv, dec_srv, _dec_lm, _pre, _dch = _two_tier(
            cfg, params, force_lane="shm")
        try:
            toks, close_reason, _ = _stream_decode(pre_srv, prompt, 4)
            assert toks == want.tolist()
            assert close_reason == "finished"
            assert kv_fallback_counters()[reason] >= 1, reason
            assert kv_stats()["copy_sessions"] >= 1
            assert shm_ring.outstanding_tx_slots() == 0
        finally:
            pre_srv.stop()
            dec_srv.stop()
            set_flag("rpc_shm_slot_bytes", slot0)
            set_flag("rpc_shm_slots", nslots0)
            shm_ring._reset_for_tests()


def test_fallback_pages_exhausted():
    """A full export table demotes to the copy lane under
    kv_pages_exhausted (backpressure, not an error)."""
    from brpc_tpu.kv import kv_fallback_counters, kv_stats
    from brpc_tpu.kv import pages as kv_pages
    _reset_kv()
    flag0 = get_flag("kv_pages")
    set_flag("kv_pages", 2)              # table smaller than one session
    try:
        cfg, params, prompt = _setup()
        want = np.asarray(generate(params, cfg, prompt, 4))[0]
        pre_srv, dec_srv, _dec_lm, _pre, _dch = _two_tier(cfg, params)
        try:
            toks, close_reason, _ = _stream_decode(pre_srv, prompt, 4)
            assert toks == want.tolist()
            assert close_reason == "finished"
            assert kv_fallback_counters()["kv_pages_exhausted"] == 1
            assert kv_stats()["copy_sessions"] == 1
            assert kv_pages.outstanding_pages() == 0   # demotion settled
        finally:
            pre_srv.stop()
            dec_srv.stop()
    finally:
        set_flag("kv_pages", flag0)
        kv_pages._reset_for_tests()


def test_fallback_disabled_flag():
    """kv_transfer_enabled=False: every handoff rides the copy lane
    under kv_disabled — correct, counted, reversible."""
    from brpc_tpu.kv import kv_fallback_counters, kv_stats
    _reset_kv()
    cfg, params, prompt = _setup()
    set_flag("kv_transfer_enabled", False)
    try:
        pre_srv, dec_srv, _dec_lm, _pre, _dch = _two_tier(cfg, params)
        try:
            toks, reason, _ = _stream_decode(pre_srv, prompt, 5)
            want = np.asarray(generate(params, cfg, prompt, 5))[0]
            assert toks == want.tolist()
            assert reason == "finished"
            assert kv_fallback_counters()["kv_disabled"] == 1
            assert kv_stats()["copy_sessions"] == 1
        finally:
            pre_srv.stop()
            dec_srv.stop()
    finally:
        set_flag("kv_transfer_enabled", True)


# ---------------------------------------------------------------------------
# Paged KV allocator (ISSUE 16): block-paged attention, cross-session
# prefix cache, host-tier eviction
# ---------------------------------------------------------------------------

KV_EVICT_PINS = ("kv_pool_exhausted", "kv_host_tier_full",
                 "kv_spill_drain_aborted")
PREFIX_EVENT_PINS = ("prefix_hit", "prefix_partial_hit", "prefix_miss",
                     "prefix_insert", "prefix_evict")


def test_paged_enums_match_pins():
    from brpc_tpu.kv.pages import (KV_EVICT_REASONS, PREFIX_CACHE_EVENTS,
                                   count_evict, count_prefix,
                                   kv_evict_counters,
                                   prefix_event_counters)
    assert KV_EVICT_REASONS == KV_EVICT_PINS
    assert PREFIX_CACHE_EVENTS == PREFIX_EVENT_PINS
    assert set(kv_evict_counters()) == set(KV_EVICT_PINS)
    assert set(prefix_event_counters()) == set(PREFIX_EVENT_PINS)
    with pytest.raises(AssertionError):
        count_evict("kv_some_new_evict_reason")
    with pytest.raises(AssertionError):
        count_prefix("prefix_some_new_event")


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


def _paged_run(bat, prompt, max_new, timeout=90.0):
    """One session through a paged batcher via a fake stream."""
    st = _FakeStream()
    bat.join(st, prompt, max_new)
    deadline = time.monotonic() + timeout
    while not st.closed and time.monotonic() < deadline:
        time.sleep(0.002)
    assert st.closed, "paged decode session never closed"
    return st


def _wait(pred, timeout=30.0, msg="condition never held"):
    deadline = time.monotonic() + timeout
    while not pred() and time.monotonic() < deadline:
        time.sleep(0.002)
    assert pred(), msg


def test_paged_decode_identity_and_prefix_hit_skips_prefill():
    """Block-paged attention is token-identical with the monolithic
    path, and a re-sent context ALIASES the cached pages: the second
    session runs NO prefill, copies ZERO bytes, and streams the same
    tokens."""
    from brpc_tpu.butil import copy_audit
    from brpc_tpu.kv import pages as kv_pages
    from brpc_tpu.models.lm_service import ContinuousBatcher
    _reset_kv()
    cfg, params, _ = _setup()
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (17,),
                                           0, cfg.vocab, jnp.int32))
    want = np.asarray(generate(params, cfg, prompt[None, :], 6))[0]
    bat = ContinuousBatcher(cfg, params, slots=4, page=16)
    st1 = _paged_run(bat, prompt, 6)
    assert st1.tokens == want.tolist()
    assert st1.close_reason == "finished"
    assert bat.prefills_run == 1
    ev = kv_pages.prefix_event_counters()
    assert ev["prefix_miss"] == 1 and ev["prefix_insert"] == 1
    # the SAME context again: full-page prefix hit — prefill skipped,
    # the aliased pages move zero audited bytes
    with copy_audit.audit() as snap:
        st2 = _paged_run(bat, prompt, 6)
        counts, _nb = snap()
    assert st2.tokens == want.tolist()
    assert st2.close_reason == "finished"
    assert bat.prefills_run == 1                  # no new prefill
    assert kv_pages.prefix_event_counters()["prefix_hit"] == 1
    assert sum(counts.values()) == 0, counts      # aliasing copies nothing
    # sessions gone: only the prefix cache still holds pages
    st = bat.kv_stats()
    assert st["alloc"]["in_use"] == st["prefix"]["nodes"] == 1


def test_prefix_hit_partial_page_teacher_forced_identity():
    """A context whose FULL pages are all cached but whose tail spills
    past them: the hit aliases the covered page and the remainder
    catches up with teacher-forced steps — the emitted stream is
    identical with the uncached path (the big numerics risk of
    partial-page coverage)."""
    from brpc_tpu.kv import pages as kv_pages
    from brpc_tpu.models.lm_service import ContinuousBatcher
    _reset_kv()
    cfg, params, _ = _setup()
    base = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (16,),
                                         0, cfg.vocab, jnp.int32))
    pa = np.concatenate([base, np.asarray([3, 9], np.int32)])
    pb = np.concatenate([base, np.asarray([7, 1, 4, 2, 8], np.int32)])
    want_b = np.asarray(generate(params, cfg, pb[None, :], 6))[0]
    bat = ContinuousBatcher(cfg, params, slots=4, page=16)
    _paged_run(bat, pa, 4)            # seeds the shared prefix's page
    pf = bat.prefills_run
    st = _paged_run(bat, pb, 6)       # ctx 20: page cached, 4 forced
    assert st.tokens == want_b.tolist()
    assert st.close_reason == "finished"
    assert bat.prefills_run == pf     # covered prefix: no prefill
    # every FULL page matched -> classified a hit (the tail is never
    # shareable); the true partial classification is the test below
    assert kv_pages.prefix_event_counters()["prefix_hit"] == 1


def test_prefix_partial_hit_teacher_forced_identity():
    """A context sharing only its FIRST of two full pages with the
    cached prefix: partial hit — one page aliased, a full page plus
    tail caught up with teacher-forced steps, stream identical with
    the uncached path."""
    from brpc_tpu.kv import pages as kv_pages
    from brpc_tpu.models.lm_service import ContinuousBatcher
    _reset_kv()
    cfg = LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=48,
                   remat=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    base = np.asarray(jax.random.randint(jax.random.PRNGKey(6), (16,),
                                         0, cfg.vocab, jnp.int32))
    ta = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (17,),
                                       0, cfg.vocab, jnp.int32))
    tb = np.asarray(jax.random.randint(jax.random.PRNGKey(8), (17,),
                                       0, cfg.vocab, jnp.int32))
    pa = np.concatenate([base, ta])   # ctx 32: two full pages cached
    pb = np.concatenate([base, tb])   # ctx 32: only page 1 matches
    want_b = np.asarray(generate(params, cfg, pb[None, :], 4))[0]
    bat = ContinuousBatcher(cfg, params, slots=2, page=16)
    _paged_run(bat, pa, 4)
    pf = bat.prefills_run
    st = _paged_run(bat, pb, 4)
    assert st.tokens == want_b.tolist()
    assert st.close_reason == "finished"
    assert bat.prefills_run == pf     # aliased page: no prefill
    assert kv_pages.prefix_event_counters()["prefix_partial_hit"] == 1


@pytest.mark.parametrize("lane", [None, "shm", "copy"],
                         ids=["auto-ici", "shm", "copy"])
def test_two_tier_into_paged_decode_tier_identical(lane):
    """The disagg handoff lands in a PAGED decode tier: the imported
    contiguous cache blockifies into allocator pages and the token
    stream stays monolithic-identical on every lane."""
    from brpc_tpu.kv import outstanding_pages
    if lane == "shm":
        from brpc_tpu.transport import shm_ring
        if not shm_ring.shm_supported():
            pytest.skip("no shm support in sandbox")
    _reset_kv()
    cfg, params, prompt = _setup()
    pre_srv, dec_srv, dec_lm, _pre, _dch = _two_tier(
        cfg, params, force_lane=lane,
        decode_lm_kw={"page": 16})
    try:
        toks, reason, _ = _stream_decode(pre_srv, prompt, 6)
        want = np.asarray(generate(params, cfg, prompt, 6))[0]
        assert toks == want.tolist()
        assert reason == "finished"
        bst = dec_lm.batcher().kv_stats()
        assert bst["alloc"] and bst["steps"] >= 6
        assert bst["alloc"]["in_use"] == 0   # imported pages settled
        assert outstanding_pages() == 0
    finally:
        pre_srv.stop()
        dec_srv.stop()


def test_evict_resume_roundtrip_token_identity():
    """Host-tier eviction roundtrip: admitting B under a dry pool
    SPILLS A's private pages to host RAM and parks it; A resumes
    bit-exact once B's pages free — both streams monolithic-identical,
    nothing leaks."""
    from brpc_tpu.kv.pages import host_inflight_spills
    from brpc_tpu.models.lm_service import ContinuousBatcher
    _reset_kv()
    cfg, params, prompt = _setup()
    pa = prompt[0]
    pb = np.asarray(jax.random.randint(jax.random.PRNGKey(11), (8,),
                                       0, cfg.vocab, jnp.int32))
    want_a = np.asarray(generate(params, cfg, pa[None, :], 12))[0]
    want_b = np.asarray(generate(params, cfg, pb[None, :], 6))[0]
    # 2 usable pages (page 0 reserved): A's 2-page session fills the
    # pool; B's 1-page admit must spill A
    bat = ContinuousBatcher(cfg, params, slots=2, page=16,
                            pages=3, host_slots=8, prefix=False)
    sta = _FakeStream()
    bat.join(sta, pa, 12)                 # pages_for(7, 12) = 2
    _wait(lambda: bat.live_slots() >= 1, msg="A never admitted")
    stb = _FakeStream()
    bat.join(stb, pb, 6)                  # pages_for(7, 6) = 1
    _wait(lambda: sta.closed and stb.closed, timeout=90.0,
          msg="spill/resume sessions never finished")
    assert sta.tokens == want_a.tolist()
    assert stb.tokens == want_b.tolist()
    assert sta.close_reason == stb.close_reason == "finished"
    assert bat.spills >= 1 and bat.resumes >= 1
    st = bat.kv_stats()
    assert st["alloc"]["in_use"] == 0
    assert st["host"]["free"] == 8        # every host slot returned
    assert host_inflight_spills() == 0


def test_pool_exhausted_closes_with_named_reason():
    """An unsatisfiable admit (no host tier to spill to) closes the
    stream under kv_pool_exhausted — backpressure with a name, never a
    partial grant."""
    from brpc_tpu.kv.pages import kv_evict_counters
    from brpc_tpu.models.lm_service import ContinuousBatcher
    _reset_kv()
    cfg, params, prompt = _setup()
    bat = ContinuousBatcher(cfg, params, slots=2, page=16,
                            pages=2, host_slots=0, prefix=False)
    st = _paged_run(bat, prompt[0], 12)   # needs 2 pages, pool has 1
    assert st.close_reason == "kv_pool_exhausted"
    assert st.tokens == []
    assert kv_evict_counters()["kv_pool_exhausted"] == 1


def test_host_tier_full_closes_with_named_reason():
    """A spill that cannot fit in the host tier closes the ADMITTING
    stream under kv_host_tier_full; the would-be victim keeps decoding
    and stays token-identical."""
    from brpc_tpu.kv.pages import kv_evict_counters
    from brpc_tpu.models.lm_service import ContinuousBatcher
    _reset_kv()
    cfg, params, prompt = _setup()
    pa = prompt[0]
    pb = np.asarray(jax.random.randint(jax.random.PRNGKey(13), (8,),
                                       0, cfg.vocab, jnp.int32))
    want_a = np.asarray(generate(params, cfg, pa[None, :], 12))[0]
    # host tier holds ONE page; spilling A needs two
    bat = ContinuousBatcher(cfg, params, slots=2, page=16,
                            pages=3, host_slots=1, prefix=False)
    sta = _FakeStream()
    bat.join(sta, pa, 12)
    _wait(lambda: bat.live_slots() >= 1, msg="A never admitted")
    stb = _paged_run(bat, pb, 12)         # 2 pages: must spill A, can't
    assert stb.close_reason == "kv_host_tier_full"
    assert kv_evict_counters()["kv_host_tier_full"] == 1
    _wait(lambda: sta.closed, timeout=90.0, msg="A never finished")
    assert sta.tokens == want_a.tolist()
    assert sta.close_reason == "finished"
    assert bat.kv_stats()["host"]["free"] == 1   # staged slot rolled back


def test_drain_counts_inflight_spills_and_aborts_at_expiry():
    """Server.drain's settle gauge: a host-tier spill in flight holds
    the drain open; grace expiry marks the pool aborted (named reason)
    instead of hanging or leaking the mid-evict pages."""
    from brpc_tpu.kv.pages import (HostPagePool, drain_settle,
                                   host_inflight_spills)
    _reset_kv()
    pool = HostPagePool(2, 64)
    assert pool.begin_spill()
    assert host_inflight_spills() == 1
    t0 = time.monotonic()
    left = drain_settle(time.monotonic() + 0.15)
    assert left == 1
    assert time.monotonic() - t0 < 5.0
    assert pool.abort_reason() == "kv_spill_drain_aborted"
    assert not pool.begin_spill()         # aborted pool refuses spills
    pool.end_spill()
    assert drain_settle(time.monotonic() + 1.0) == 0
    # a spill landing INSIDE the grace is observed
    pool2 = HostPagePool(2, 64)
    assert pool2.begin_spill()
    threading.Timer(0.1, pool2.end_spill).start()
    assert drain_settle(time.monotonic() + 5.0) == 0


def test_drain_abort_closes_parked_under_named_reason():
    """A parked (spilled) session at drain-abort time force-closes
    under kv_spill_drain_aborted and frees its host slots; the live
    session is untouched."""
    from brpc_tpu.kv.pages import kv_evict_counters
    from brpc_tpu.models.lm_service import ContinuousBatcher
    _reset_kv()
    cfg, params, prompt = _setup()
    pa = prompt[0]
    pb = np.asarray(jax.random.randint(jax.random.PRNGKey(17), (8,),
                                       0, cfg.vocab, jnp.int32))
    want_b = np.asarray(generate(params, cfg, pb[None, :], 20))[0]
    bat = ContinuousBatcher(cfg, params, slots=2, page=16,
                            pages=3, host_slots=4, prefix=False)
    sta = _FakeStream()
    bat.join(sta, pa, 24)                 # 2 pages
    _wait(lambda: bat.live_slots() >= 1, msg="A never admitted")
    stb = _FakeStream()
    bat.join(stb, pb, 20)                 # 2 pages: spills A
    _wait(lambda: bat.spills >= 1, msg="A never spilled")
    # drain-grace expiry while A sits parked: the pool aborts, the
    # batcher closes A under the named reason between steps
    bat._host.drain_abort("kv_spill_drain_aborted")
    _wait(lambda: sta.closed, msg="parked session never closed")
    assert sta.close_reason == "kv_spill_drain_aborted"
    assert kv_evict_counters()["kv_spill_drain_aborted"] >= 1
    _wait(lambda: stb.closed, timeout=90.0, msg="B never finished")
    assert stb.tokens == want_b.tolist()
    assert stb.close_reason == "finished"
    st = bat.kv_stats()
    assert st["alloc"]["in_use"] == 0
    assert st["host"]["free"] == 4        # parked slots reclaimed


def test_allocator_and_host_pool_loud_double_free():
    """The loud-failure matrix for the allocator planes: double page
    release raises, aliasing a dead page raises, host-slot double free
    and stale fetch raise, an oversized spill raises."""
    from brpc_tpu.kv import KvPageError
    from brpc_tpu.kv.pages import HostPagePool, PageAllocator
    _reset_kv()
    a = PageAllocator(4, 16)
    pages = a.alloc(2)
    assert pages is not None and 0 not in pages   # page 0 reserved
    a.release(pages[0])
    with pytest.raises(KvPageError, match="double/stale"):
        a.release(pages[0])
    with pytest.raises(KvPageError, match="dead"):
        a.ref(pages[0])                   # aliasing a freed page
    # an aliased page survives the first release, frees on the last
    a.ref(pages[1])
    a.release(pages[1])
    assert a.refcount(pages[1]) == 1
    a.release(pages[1])
    assert a.in_use() == 0
    with pytest.raises(ValueError):
        PageAllocator(1, 16)              # garbage page + >= 1 real

    pool = HostPagePool(2, 64)
    h = pool.stage(np.arange(64, dtype=np.uint8))
    assert bytes(pool.fetch(h)) == bytes(range(64))
    pool.free(h)
    with pytest.raises(KvPageError, match="double/stale"):
        pool.free(h)
    with pytest.raises(KvPageError, match="stale"):
        pool.fetch(h)
    with pytest.raises(KvPageError, match="exceeds"):
        pool.stage(np.zeros(65, np.uint8))


def test_prefix_cache_refcounts_aliased_pages():
    """An aliased page never returns to the free list while any holder
    (session or cache) remains, and the last release frees it — the
    invariant the generation check turns into an assertion."""
    from brpc_tpu.kv.pages import PageAllocator, PrefixCache
    _reset_kv()
    a = PageAllocator(4, 4)
    cache = PrefixCache(a)
    toks = list(range(4))
    (pg,) = a.alloc(1)
    cache.insert(toks, [pg])              # the cache takes its own hold
    assert a.refcount(pg) == 2
    a.release(pg)                         # the prefilling session leaves
    assert a.refcount(pg) == 1            # cached page stays live
    pages, covered = cache.lookup(toks)
    assert pages == [pg] and covered == 4
    assert a.refcount(pg) == 2            # the hit session's hold
    a.release(pg)
    assert cache.evict_all() == 1         # last holder: page frees
    assert a.in_use() == 0
    pages, covered = cache.lookup(toks)   # cold again
    assert pages == [] and covered == 0


def test_paged_leak_pin_1k_sessions_alias_and_evict():
    """1000 sessions over two alternating contexts on a paged batcher:
    every stream is monolithic-identical (aliased pages included), and
    afterwards the allocator holds exactly the prefix cache's pages —
    evict_all returns the pool to empty.  The alias/evict leak pin."""
    from brpc_tpu.kv import pages as kv_pages
    from brpc_tpu.models.lm_service import ContinuousBatcher
    _reset_kv()
    cfg, params, _ = _setup()
    pa = np.asarray(jax.random.randint(jax.random.PRNGKey(21), (17,),
                                       0, cfg.vocab, jnp.int32))
    pb = np.asarray(jax.random.randint(jax.random.PRNGKey(22), (17,),
                                       0, cfg.vocab, jnp.int32))
    want = {0: np.asarray(generate(params, cfg, pa[None, :], 2))[0],
            1: np.asarray(generate(params, cfg, pb[None, :], 2))[0]}
    bat = ContinuousBatcher(cfg, params, slots=4, page=16)
    streams = []
    for i in range(1000):
        st = _FakeStream()
        streams.append((i % 2, st))
        bat.join(st, pa if i % 2 == 0 else pb, 2)
    _wait(lambda: all(st.closed for _k, st in streams), timeout=300.0,
          msg="1k paged sessions never drained")
    for k, st in streams:
        assert st.close_reason == "finished"
        assert st.tokens == want[k].tolist()
    ev = kv_pages.prefix_event_counters()
    assert ev["prefix_hit"] + ev["prefix_partial_hit"] >= 990
    st = bat.kv_stats()
    held = st["prefix"]["nodes"]
    assert st["alloc"]["in_use"] == held     # only the cache holds pages
    bat._prefix.evict_all()
    assert bat.kv_stats()["alloc"]["in_use"] == 0
    assert kv_pages.prefix_event_counters()["prefix_evict"] >= held


def test_strict_tier_closes_with_named_reason():
    """fallback_local=False: a failed handoff REFUSES the session —
    stream closed with the named kv_handoff_failed reason, EINTERNAL
    on the RPC (capacity-planned tiers fail loudly, never absorb)."""
    from brpc_tpu.kv import PrefillService
    _reset_kv()
    cfg, params, prompt = _setup()
    pre_svc = PrefillService(cfg=cfg, params=params,
                             decode_channel=None,
                             fallback_local=False, decode_slots=4)
    srv = Server()
    srv.add_service(pre_svc, name="LM")
    assert srv.start("127.0.0.1:0") == 0
    try:
        closed = []
        ch = Channel()
        ch.init(str(srv.listen_endpoint))
        cntl = Controller()
        cntl.timeout_ms = 60_000
        stream_create(cntl, StreamOptions(
            on_closed=lambda st: closed.append(st.close_reason)))
        c = ch.call_method("LM.Decode",
                           pack_generate_request(prompt, 4), cntl=cntl)
        assert c.failed
        assert c.error_code == int(Errno.EINTERNAL)
        assert "kv_no_decode_tier" in c.error_text
        deadline = time.time() + 10
        while not closed and time.time() < deadline:
            time.sleep(0.01)
        assert closed == ["kv_handoff_failed"], closed
        assert pre_svc.batcher().live_slots() == 0
    finally:
        srv.stop()
