"""Disaggregated prefill/decode serving — the two-tier LM service.

The fabric-lib shape (PAPERS.md): LLM serving at scale splits prompt
processing (prefill — compute-bound, long bursts) from token
generation (decode — memory-bound, long-lived sessions), scales the
tiers independently, and moves each session's KV-cache between them as
registered memory.  Here:

- :class:`PrefillService` serves the SAME ``LM.Decode`` wire contract
  as the monolithic service: it accepts the client's stream, runs the
  bucketed prompt prefill, exports the session's cache as KV pages and
  hands the LIVE session to the decode tier mid-request through
  :class:`~brpc_tpu.kv.transport.KvTransport`.  On any named handoff
  fallback it decodes locally (the monolithic path — the client never
  sees the topology), or, in strict mode, closes the stream with the
  named ``kv_handoff_failed`` reason.
- :class:`DecodeTierService` is the decode tier's handoff surface
  (``KV.Probe`` + ``KV.ImportSession``): imports the pages, drops them
  into a continuous-batcher slot between steps
  (:meth:`ContinuousBatcher.join_imported`), and the session's tokens
  stream to the ORIGINAL client over the stream it already holds — on
  a native server, the engine's kind-5 lane.

Token identity with the monolithic path is by construction, not luck:
both tiers run the ONE ``bucketed_prefill`` and the one batch-step
program, so a handed-off session emits bit-identical tokens (pinned by
``tests/test_kv_disagg.py``).

Topology note: stream adoption uses the process-global stream registry,
so the decode tier must be co-resident with the prefill tier's process
to take over the client stream directly (the same-host deployment this
round ships).  A cross-process decode tier answers
``kv_stream_not_local`` and the prefill tier decodes locally — a relay
(prefill forwarding the decode tier's chunks) is the named follow-up in
ROADMAP item 4, not a silent behavior change.
"""

from __future__ import annotations

import struct
import threading
from typing import Any, Optional

from ..butil.logging_util import LOG
from ..butil.status import Errno
from ..models.lm_service import LMService, bucketed_prefill
from ..models.transformer_lm import (decode_cache_from_pages,
                                     export_decode_cache, kv_page_specs)
from ..server.service import Service
from .pages import KvPageError
from .transport import (KvTransport, decode_manifest,
                        encode_probe_response, import_pages,
                        stream_auth)


class DecodeTierService(Service):
    """``KV.Probe`` — lane-capability handshake; ``KV.ImportSession`` —
    adopt a prefilled session into the continuous batch.  Wraps the
    tier's :class:`LMService` (which may also serve ``LM.Decode``
    directly: a decode tier is a superset of a monolithic server)."""

    def __init__(self, lm: LMService):
        self.lm = lm

    @classmethod
    def service_name(cls) -> str:
        return "KV"

    def Probe(self, cntl, request):
        # capability answer + the fleet load-report tail (versioned,
        # ignored by pre-fleet probers): the prefill tier's admission /
        # LB side reads live slot availability from the same handshake
        # it already makes before moving a byte
        try:
            from .. import fleet
            report = fleet.report_cache().get(getattr(cntl, "server",
                                                      None))
        except Exception:
            report = None
        return encode_probe_response(report=report)

    def ImportSession(self, cntl, request):
        from time import monotonic_ns

        from ..streaming import find_stream
        recv_ns = monotonic_ns()
        try:
            man = decode_manifest(bytes(request))
        except (KvPageError, struct.error) as e:
            cntl.set_failed(Errno.EREQUEST,
                            f"kv_import_rejected: bad manifest: {e}")
            return None
        if man.model_fp != self.lm.model_fingerprint():
            cntl.set_failed(
                Errno.EREQUEST,
                "kv_model_mismatch: this tier serves "
                f"{self.lm.model_fingerprint().decode()!r}")
            return None
        if not (0 < man.max_new <= self.lm.max_new_cap) \
                or man.ctx_len + 1 + man.max_new > self.lm.cfg.max_seq \
                or not (0 <= man.last_token < self.lm.cfg.vocab):
            cntl.set_failed(Errno.EREQUEST,
                            "kv_import_rejected: session bounds")
            return None
        if man.auth != stream_auth(man.stream_id):
            # stream ids are enumerable; adopting one requires the
            # process-keyed tag only a co-resident tier can mint — a
            # forged manifest naming another client's live stream is
            # refused here, before any page resolves
            cntl.set_failed(Errno.EREQUEST,
                            "kv_stream_not_local: stream "
                            f"{man.stream_id} is not adoptable here")
            return None
        stream = find_stream(man.stream_id)
        if stream is None or stream.closed:
            # the client stream is not adoptable from this process —
            # the sender falls back to local decode under this reason
            cntl.set_failed(Errno.EREQUEST,
                            "kv_stream_not_local: stream "
                            f"{man.stream_id} is not resolvable here")
            return None
        try:
            arrays = import_pages(man, cntl.request_attachment,
                                  kv_page_specs(self.lm.cfg))
            cache1 = decode_cache_from_pages(self.lm.cfg, arrays)
        except KvPageError as e:
            # LOUD failure is the contract: a stale/double import must
            # fail the handoff RPC (sender keeps the session), never
            # seat a session on an empty cache
            cntl.set_failed(Errno.ERESPONSE,
                            f"kv_import_rejected: {e}")
            return None
        # decode-tier half of the stitched trace: the handoff RPC
        # carried the prefill request's trace id in its ordinary trace
        # TLVs, so cntl.span (when present) is already forced under
        # that id — the session span is its child, backdated to the
        # import's arrival so the transfer+import time it covers is
        # honest (rpcz.backdate_span, the PR 4 stitcher's convention)
        span = None
        req_span = getattr(cntl, "span", None)
        if req_span is not None:
            from ..rpcz import Span, backdate_span
            span = Span("KV.DecodeTierSession",
                        trace_id=req_span.trace_id,
                        parent_span_id=req_span.span_id)
            span.remote_side = req_span.remote_side
            backdate_span(span, recv_ns)
        meta = getattr(cntl, "request_meta", None)
        tenant = getattr(meta, "tenant", b"") if meta is not None \
            else b""
        self.lm.batcher().join_imported(stream, man.last_token,
                                        man.ctx_len, man.max_new,
                                        cache1, tenant=tenant,
                                        span=span)
        return b"ok"


class PrefillService(LMService):
    """The prefill tier: ``LM.Decode``-compatible, but the decode half
    of every session is handed to a decode tier through the KV
    transfer plane.  ``Generate``/``Info`` are inherited unchanged (a
    prefill tier still answers unary completions itself).

    ``fallback_local=True`` (default) keeps the monolithic behavior on
    ANY named handoff fallback — capacity planning can then read the
    ``kv_fallback_counters`` to see what the fleet is declining.
    Strict tiers (``fallback_local=False``) refuse instead: stream
    closed with the named ``kv_handoff_failed`` reason, EINTERNAL on
    the RPC."""

    def __init__(self, *args, decode_channel=None,
                 transport: Optional[KvTransport] = None,
                 fallback_local: bool = True, **kw):
        super().__init__(*args, **kw)
        self.decode_channel = decode_channel
        self.transport = transport or KvTransport()
        self.fallback_local = fallback_local
        self._prefill_j = None
        self._prefill_lock = threading.Lock()

    def _ensure_prefill(self):
        with self._prefill_lock:
            if self._prefill_j is None:
                from ..models.transformer_lm import (jit_with_params,
                                                     make_prefill)
                self._prefill_j = jit_with_params(make_prefill(self.cfg),
                                                  self.params)
            return self._prefill_j

    def Decode(self, cntl, request):
        parsed = self._check_decode_request(cntl, request)
        if parsed is None:
            return None
        prompt, max_new, stream = parsed
        # prefill-tier half of the stitched trace: a traced Decode
        # gets a forced session span whose chunk-slice event covers
        # the whole-prompt prefill this tier runs
        span = self._session_span(cntl)
        if span is not None:
            span.annotate("lm_join")
        cache1, ctx_len = bucketed_prefill(self._ensure_prefill(),
                                           self.cfg, prompt[0])
        if span is not None:
            span.annotate("lm_chunk_slice")
        last_token = int(prompt[0][-1])
        pages = export_decode_cache(self.cfg, cache1)
        res = self.transport.handoff(
            self.decode_channel, stream.id, ctx_len, last_token,
            max_new, self.model_fingerprint(), pages,
            owner=("kv", cntl.socket_id),
            trace=(span.trace_id, span.span_id)
            if span is not None else None)
        if res.ok:
            if span is not None:
                span.annotate("lm_handoff")
                span.finish(0)
            return struct.pack("<I", max_new)
        if self.fallback_local and not res.ambiguous:
            # monolithic fallback: the SAME cache1 joins the local
            # batch, so the fallback is token-identical too (and free —
            # the prefill is never recomputed).  Only for failures that
            # PROVE the decode tier never seated the session: an
            # ambiguous one (timeout / transport death mid-import) may
            # have landed, and two batchers decoding onto one client
            # stream is the at-most-once violation — those close with
            # the named reason instead and the client retries
            LOG.info("kv handoff fell back to local decode (%s)",
                     res.reason)
            meta = getattr(cntl, "request_meta", None)
            tenant = getattr(meta, "tenant", b"") \
                if meta is not None else b""
            self.batcher().join_imported(stream, last_token, ctx_len,
                                         max_new, cache1,
                                         tenant=tenant, span=span)
            return struct.pack("<I", max_new)
        stream.close(reason="kv_handoff_failed")
        try:
            from .. import fleet
            fleet.record_event("fleet_kv_handoff_failed",
                               str(res.reason))
        except Exception:
            pass
        if span is not None:
            span.annotate("lm_evict:kv_handoff_failed")
            span.finish(int(Errno.EINTERNAL))
        cntl.set_failed(Errno.EINTERNAL,
                        f"kv handoff failed: {res.reason}")
        return None
