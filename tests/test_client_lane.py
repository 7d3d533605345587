"""Client completion lane (ISSUE 8) — adversarial wire/state comparison.

Pins three contracts:

1. **Native demux vs Python demux are observably identical**: the same
   call matrix (success, errors, attachments, deadlines, traces,
   tenants, retries/backups) runs with the lane force-enabled and
   force-disabled (``rpc_native_client_lane``), and every Controller
   observable — error codes/texts, responses, attachments, span pairs,
   breaker feed — must match.
2. **The eligible matrix stays native**: trace-on, deadline-on and
   tenant-stamped traffic completes through the lane with ZERO new
   fallbacks; every ineligible shape lands in exactly its NAMED
   fallback reason (closed enum — no "unknown" bucket).
3. **Pooled reuse leaks nothing**: client Controllers and the slim
   lane's pooled ServerControllers come back from their free lists with
   every observable field reset.
"""

import socket as pysock
import struct
import threading
import time

import pytest

from conftest import require_native  # noqa: E402

from brpc_tpu.butil.flags import set_flag
from brpc_tpu.butil.iobuf import IOBuf
from brpc_tpu.butil.status import Errno
from brpc_tpu.client import Channel, ChannelOptions, Controller
from brpc_tpu.transport.client_lane import (REASONS,
                                            client_lane_telemetry,
                                            global_client_lane)


def _lane_counts():
    t = client_lane_telemetry()
    fb = t.get("fallbacks", {}) or {r: 0 for r in REASONS}
    return t.get("completions", 0), dict(fb)


def _fb_delta(before, after):
    return {r: after.get(r, 0) - before.get(r, 0) for r in REASONS
            if after.get(r, 0) != before.get(r, 0)}


class _Svc:
    """Service under test (built as a plain Service subclass inside the
    fixture to keep brpc_tpu imports lazy for the skip path)."""


def _mk_server(**opt):
    from brpc_tpu.server import Server, ServerOptions, Service

    class Probe(Service):
        def __init__(self):
            super().__init__()
            self.seen = []           # per-call state snapshots
            self.park = threading.Event()

        def Echo(self, cntl, request):
            cntl.response_attachment.append_iobuf(
                cntl.request_attachment)
            return request

        def Err(self, cntl, request):
            cntl.set_failed(1234, "boom")
            return b""

        def Slow(self, cntl, request):
            time.sleep(float(request or b"0.05"))
            return b"slow"

        def Snap(self, cntl, request):
            # observable server-controller state: pooled reuse must
            # reset every one of these between calls
            self.seen.append({
                "att": cntl.request_attachment.to_bytes(),
                "deadline": cntl.deadline_remaining_ms(),
                "tenant": bytes(cntl.request_meta.tenant or b""),
                "trace": cntl.trace_id,
                "failed": cntl.failed,
                "resp_att": len(cntl.response_attachment),
            })
            return b"snap"

    opts = ServerOptions()
    opts.native = True
    opts.usercode_inline = True
    opts.native_loops = 1
    for k, v in opt.items():
        setattr(opts, k, v)
    svc = Probe()
    srv = Server(opts)
    srv.add_service(svc, name="CL")
    assert srv.start("127.0.0.1:0") == 0
    return srv, svc


def _single_channel(srv, **copt):
    o = ChannelOptions()
    o.connection_type = "single"      # the lane's home: multiplexed demux
    for k, v in copt.items():
        setattr(o, k, v)
    ch = Channel(o)
    ch.init(str(srv.listen_endpoint))
    return ch


@pytest.fixture()
def lane_server():
    require_native()
    srv, svc = _mk_server()
    yield srv, svc
    srv.stop()


# ---------------------------------------------------------------------------
# 1. the eligible matrix stays native (zero new fallbacks)
# ---------------------------------------------------------------------------

def test_eligible_matrix_stays_native(lane_server):
    srv, _svc = lane_server
    ch = _single_channel(srv, tenant="acme")
    comp0, fb0 = _lane_counts()

    # plain
    c = ch.call_method("CL.Echo", b"plain")
    assert not c.failed and c.response == b"plain"
    # deadline-on
    cntl = Controller()
    cntl.timeout_ms = 5000
    c = ch.call_method("CL.Echo", b"deadline", cntl=cntl)
    assert not c.failed and c.response == b"deadline"
    # trace-on (explicitly traced: client+server span pair must record)
    cntl = Controller()
    cntl.trace_id = 0xBEEF01
    c = ch.call_method("CL.Echo", b"traced", cntl=cntl)
    assert not c.failed and c.response == b"traced"
    # attachment response
    cntl = Controller()
    cntl.request_attachment = IOBuf(b"A" * 512)
    c = ch.call_method("CL.Echo", b"att", cntl=cntl)
    assert not c.failed
    assert c.response_attachment.to_bytes() == b"A" * 512
    # async done
    ev = threading.Event()
    out = {}

    def done(cc):
        out["resp"] = cc.response
        ev.set()

    ch.call_method("CL.Echo", b"async", done=done)
    assert ev.wait(5) and out["resp"] == b"async"

    comp1, fb1 = _lane_counts()
    assert comp1 - comp0 == 5, "eligible traffic must demux natively"
    assert _fb_delta(fb0, fb1) == {}, "zero new fallbacks on the matrix"

    # the traced call recorded the client/server span pair
    from brpc_tpu.rpcz import global_span_store
    spans = global_span_store().by_trace(0xBEEF01)
    kinds = {s.is_server for s in spans}
    assert kinds == {True, False}, \
        f"traced lane call must record both span halves, got {spans}"


def test_error_response_falls_back_named(lane_server):
    srv, _svc = lane_server
    ch = _single_channel(srv)
    ch.call_method("CL.Echo", b"warm")        # socket + lane attach
    comp0, fb0 = _lane_counts()
    c = ch.call_method("CL.Err", b"x")
    assert c.error_code == 1234 and c.error_text == "boom"
    _comp1, fb1 = _lane_counts()
    assert _fb_delta(fb0, fb1) == {"cli_meta_tags": 1}


def test_stream_frames_fall_back_named(lane_server):
    srv, _svc = lane_server
    from brpc_tpu.server import Server, ServerOptions, Service
    from brpc_tpu.streaming import (StreamOptions, stream_accept,
                                    stream_create)

    got = []
    done = threading.Event()

    class Sink(Service):
        def Start(self, cntl, request):
            def on_received(stream, msgs):
                got.extend(bytes(m) for m in msgs)
                done.set()
            stream_accept(cntl, StreamOptions(on_received=on_received))
            return b"ok"

    o = ServerOptions()
    o.native = True
    o.usercode_inline = True
    srv2 = Server(o)
    srv2.add_service(Sink(), name="SK")
    assert srv2.start("127.0.0.1:0") == 0
    try:
        ch = _single_channel(srv2)
        # a PLAIN call first pins the shared single socket to the lane;
        # the stream then rides the same lane-attached connection
        with pytest.raises(Exception):
            ch.call("SK.Nope", b"")           # warms the conn (error)
        comp0, fb0 = _lane_counts()
        cntl = Controller()
        cntl.timeout_ms = 5000
        stream = stream_create(cntl, StreamOptions())
        c = ch.call_method("SK.Start", b"", cntl=cntl)
        assert not c.failed, c.error_text
        # server->client stream traffic arrives as TSTR frames on the
        # lane socket: each must fall back under its NAMED reason; the
        # stream itself works end-to-end (byte-identical demux)
        assert stream.write(b"chunk-1") == 0
        assert stream.write(b"chunk-2") == 0
        assert done.wait(5)
        assert got and got[0] == b"chunk-1"
        _comp, fb1 = _lane_counts()
        d = _fb_delta(fb0, fb1)
        assert set(d) <= {"cli_meta_tags", "cli_stream_frame"}, d
        assert d.get("cli_meta_tags", 0) >= 1   # the stream grant
        stream.close()
    finally:
        srv2.stop()


def test_backup_request_stale_response_handled(lane_server):
    """A backup request's losing response must be consumed without
    corrupting anything: same-burst arrivals demux natively and drop at
    the versioned-id rendezvous (the classic stale discipline);
    later-burst arrivals fall back under cli_unknown_cid (the entry was
    cancelled at call end).  Either way the call succeeds exactly once
    and the connection keeps working."""
    srv, _svc = lane_server
    ch = _single_channel(srv)
    ch.call_method("CL.Echo", b"warm")
    comp0, fb0 = _lane_counts()
    cntl = Controller()
    cntl.timeout_ms = 5000
    cntl.backup_request_ms = 20           # fires during the 100ms sleep
    cntl.max_retry = 1
    c = ch.call_method("CL.Slow", b"0.1", cntl=cntl)
    assert not c.failed and c.response == b"slow"
    assert c.has_backup_request
    # both attempts' responses drain (winner + loser), one way or the
    # other — and the stale one never lands on a later call
    deadline = time.time() + 5
    while time.time() < deadline:
        comp1, fb1 = _lane_counts()
        consumed = (comp1 - comp0) + (fb1.get("cli_unknown_cid", 0)
                                      - fb0.get("cli_unknown_cid", 0))
        if consumed >= 2:
            break
        time.sleep(0.01)
    assert consumed >= 2, "loser's response must be consumed"
    c2 = ch.call_method("CL.Echo", b"after")
    assert not c2.failed and c2.response == b"after"


# ---------------------------------------------------------------------------
# 2. force-disabled vs enabled: identical Controller observables
# ---------------------------------------------------------------------------

def _run_matrix(srv):
    """One pass of the comparison matrix against ``srv``; returns the
    list of observable outcomes."""
    out = []
    ch = _single_channel(srv, tenant="cmp")
    # success
    c = ch.call_method("CL.Echo", b"ok")
    out.append(("ok", c.error_code, c.response,
                c.response_attachment.to_bytes()))
    # error
    c = ch.call_method("CL.Err", b"x")
    out.append(("err", c.error_code, c.error_text))
    # attachment + deadline
    cntl = Controller()
    cntl.timeout_ms = 5000
    cntl.request_attachment = IOBuf(b"B" * 300)
    c = ch.call_method("CL.Echo", b"a", cntl=cntl)
    out.append(("att", c.error_code, c.response,
                c.response_attachment.to_bytes()))
    # client-side timeout (doomed work)
    cntl = Controller()
    cntl.timeout_ms = 30
    cntl.max_retry = 0
    c = ch.call_method("CL.Slow", b"0.5", cntl=cntl)
    out.append(("timeout", c.error_code))
    # traced
    cntl = Controller()
    cntl.trace_id = 0xCAFE
    c = ch.call_method("CL.Echo", b"t", cntl=cntl)
    out.append(("traced", c.error_code, c.response))
    return out


def test_lane_on_off_state_comparison():
    """The whole matrix, lane force-disabled vs enabled, on separate
    servers (a 'single' socket keeps its demux mode for life): every
    Controller observable must match."""
    require_native()
    results = {}
    for lane_on in (True, False):
        set_flag("rpc_native_client_lane", lane_on)
        try:
            srv, _svc = _mk_server()
            try:
                results[lane_on] = _run_matrix(srv)
            finally:
                srv.stop()
        finally:
            set_flag("rpc_native_client_lane", True)
    assert results[True] == results[False]


def test_breaker_feed_identical_on_lane():
    """Single-server channels route completion health into the GLOBAL
    breaker map from _finish_locked — lane completions must feed it
    exactly like dispatcher completions."""
    require_native()
    from brpc_tpu.client.circuit_breaker import global_circuit_breaker_map

    def feed_count(lane_on):
        set_flag("rpc_native_client_lane", lane_on)
        try:
            srv, _svc = _mk_server()
            try:
                ch = _single_channel(srv, enable_circuit_breaker=True)
                for _ in range(4):
                    assert ch.call("CL.Echo", b"x") == b"x"
                node = global_circuit_breaker_map()._node(
                    srv.listen_endpoint)
                return node is not None
            finally:
                srv.stop()
        finally:
            set_flag("rpc_native_client_lane", True)

    assert feed_count(True) == feed_count(False)


# ---------------------------------------------------------------------------
# 3. demux unit surface: crafted wire bytes -> named reasons
# ---------------------------------------------------------------------------

def _tlv(tag, data):
    return bytes([tag]) + struct.pack("<I", len(data)) + data


def _resp_frame(cid, payload=b"", extra_meta=b""):
    meta = _tlv(1, struct.pack("<Q", cid)) + extra_meta
    return (b"TRPC" + struct.pack("<II", len(meta) + len(payload),
                                  len(meta)) + meta + payload)


class _DemuxHarness:
    def __init__(self):
        from brpc_tpu.native import load
        self.m = load()
        self.events = []
        self.cv = threading.Condition()
        self.demux = self.m.ClientDemux(self._cb)
        self.thread = threading.Thread(target=self.demux.run_loop,
                                       daemon=True)
        self.thread.start()
        self.a, self.b = pysock.socketpair()
        self.a.setblocking(False)
        self.token = self.demux.attach(self.a.fileno())
        assert self.demux.arm(self.token)

    def _cb(self, *args):
        with self.cv:
            self.events.append(args)
            self.cv.notify_all()

    def wait_events(self, n, timeout=5.0):
        with self.cv:
            self.cv.wait_for(lambda: len(self.events) >= n, timeout)
            return list(self.events)

    def close(self):
        self.demux.stop()
        self.thread.join(timeout=5)
        self.a.close()
        self.b.close()


def test_demux_unit_reasons_and_completions():
    require_native()
    h = _DemuxHarness()
    try:
        m = h.m
        assert h.demux.expect(h.token, 7)
        # burst: one plain completion + one unknown cid + one TICI ack
        h.b.sendall(_resp_frame(7, b"PAY")
                    + _resp_frame(99, b"zz")
                    + b"TICI" + struct.pack("<I", 1)
                    + struct.pack("<Q", 4242))
        evs = h.wait_events(1)
        token, status, comps, fbs, acks = evs[0]
        assert status == 0
        assert [(c[0], bytes(c[1]), c[2]) for c in comps] \
            == [(7, b"PAY", 0)]
        assert [f[0] for f in fbs] == [m.CFB_UNKNOWN_CID]
        assert bytes(fbs[0][1]) == _resp_frame(99, b"zz")
        assert list(acks) == [4242]
        # error-meta response on a registered cid: falls back WHOLE,
        # entry kept (classic demux owns completion)
        assert h.demux.expect(h.token, 8)
        h.b.sendall(_resp_frame(8, b"", _tlv(6, struct.pack("<i", 1003))))
        evs = h.wait_events(2)
        _t, _s, comps, fbs, _a = evs[1]
        assert comps is None and [f[0] for f in fbs] == [m.CFB_META_TAGS]
        assert h.demux.cancel(h.token, 8)      # entry survived
        # malformed meta: no cid tag at all
        h.b.sendall(b"TRPC" + struct.pack("<II", 4, 4) + b"\x00" * 4)
        evs = h.wait_events(3)
        assert [f[0] for f in evs[2][3]] == [m.CFB_META_UNPARSED]
        # unknown magic: sticky passthrough forwards everything
        h.b.sendall(b"*1\r\nPING\r\n")
        evs = h.wait_events(4)
        assert [f[0] for f in evs[3][3]] == [m.CFB_UNKNOWN_MAGIC]
        h.b.sendall(b"more-bytes")
        evs = h.wait_events(5)
        assert [f[0] for f in evs[4][3]] == [m.CFB_UNKNOWN_MAGIC]
        # telemetry reasons form the closed enum exactly
        tel = h.demux.telemetry()
        assert set(tel["fallbacks"]) == set(REASONS)
        assert "unknown" not in tel["fallbacks"]
    finally:
        h.close()


def test_demux_unit_stream_frame_and_eof():
    require_native()
    h = _DemuxHarness()
    try:
        m = h.m
        payload = b"S" * 10
        tstr = (b"TSTR" + bytes([0]) + struct.pack("<Q", 5)
                + struct.pack("<I", len(payload)) + payload)
        h.b.sendall(tstr)
        evs = h.wait_events(1)
        assert [f[0] for f in evs[0][3]] == [m.CFB_STREAM_FRAME]
        assert bytes(evs[0][3][0][1]) == tstr
        # EOF after a final completion: the response wins, status=1 rides
        assert h.demux.expect(h.token, 11)
        h.b.sendall(_resp_frame(11, b"last"))
        h.b.close()
        evs = h.wait_events(2)
        flat_comps = [c for e in evs[1:] if e[2] for c in e[2]]
        assert [(c[0], bytes(c[1])) for c in flat_comps] == [(11, b"last")]
        assert any(e[1] == 1 for e in evs[1:])
    finally:
        h.demux.stop()
        h.thread.join(timeout=5)
        h.a.close()


# ---------------------------------------------------------------------------
# 4. pooled reuse leaks nothing
# ---------------------------------------------------------------------------

def test_pooled_client_controller_resets():
    # the free list is FIFO: with two or more controllers already pooled
    # by earlier tests of this process, obtain() after recycle() hands
    # back another instance — start from an empty list
    from brpc_tpu.client.controller import _cntl_pool
    _cntl_pool.clear()
    c = Controller.obtain()
    c.timeout_ms = 123
    c.trace_id = 0xDEAD
    c.span_id = 7
    c.max_retry = 9
    c.request_attachment = IOBuf(b"leak?")
    c.excluded_servers.add(("1.2.3.4", 5))
    c.response = b"old-response"
    c.set_failed(42, "old")
    c.remote_side = ("9.9.9.9", 1)
    c.retried_count = 3
    c.recycle()
    c2 = Controller.obtain()
    assert c2 is c, "free list must hand the instance back"
    assert c2.timeout_ms is None and c2.max_retry is None
    assert c2.trace_id == 0 and c2.span_id == 0
    assert c2._req_att is None and len(c2.request_attachment) == 0
    assert not c2.excluded_servers
    assert c2.response is None and not c2.failed
    assert c2.error_code == 0 and c2.error_text == ""
    assert c2.remote_side is None and c2.retried_count == 0
    assert c2._done is None and c2._inflight_marks == []


def test_pooled_server_controller_no_cross_call_leak(lane_server):
    """Request 1 stamps tenant + deadline + attachment + trace; request
    2 is bare.  The slim lane's pooled ServerController must show the
    handler pristine state on request 2."""
    srv, svc = lane_server
    ch_rich = _single_channel(srv, tenant="leaky")
    cntl = Controller()
    cntl.timeout_ms = 5000
    cntl.trace_id = 0xF00D
    cntl.request_attachment = IOBuf(b"STICKY")
    assert not ch_rich.call_method("CL.Snap", b"", cntl=cntl).failed
    ch_bare = _single_channel(srv)
    bare_cntl = Controller()
    bare_cntl.timeout_ms = -1            # no TLV 13 on the wire at all
    assert not ch_bare.call_method("CL.Snap", b"", cntl=bare_cntl).failed
    rich, bare = svc.seen[-2], svc.seen[-1]
    assert rich["att"] == b"STICKY" and rich["tenant"] == b"leaky"
    assert rich["deadline"] is not None and rich["trace"] == 0xF00D
    assert bare["att"] == b""
    assert bare["tenant"] == b""
    assert bare["deadline"] is None
    assert bare["trace"] == 0
    assert not bare["failed"] and bare["resp_att"] == 0


def test_parallel_legs_recycled_without_leak():
    """Fan-out legs come from the pool; a traced fan-out followed by an
    untraced one must not leak trace context into the second's legs
    (observable: the second fan-out's sub-servers record no spans)."""
    require_native()
    from brpc_tpu.client.parallel_channel import ParallelChannel
    srvs = []
    pc = ParallelChannel()
    for _ in range(2):
        srv, _svc = _mk_server()
        srvs.append(srv)
        o = ChannelOptions()
        sub = Channel(o)
        sub.init(str(srv.listen_endpoint))
        pc.add_channel(sub)
    try:
        cntl = Controller()
        cntl.trace_id = 0xFA90
        c = pc.call_method("CL.Echo", b"one", cntl=cntl)
        assert not c.failed
        c = pc.call_method("CL.Echo", b"two")
        assert not c.failed and c.response == [b"two", b"two"]
        from brpc_tpu.rpcz import global_span_store
        traced = global_span_store().by_trace(0xFA90)
        assert traced, "traced fan-out must record spans"
        # the untraced fan-out inherited nothing: no span carries a
        # zero/foreign trace id from the recycled legs
        for s in traced:
            assert s.trace_id == 0xFA90
    finally:
        for srv in srvs:
            srv.stop()


def test_lane_flag_off_uses_dispatcher():
    """Force-disabled lane: a fresh single connection must route through
    the classic dispatcher (no completions counted) and still work."""
    require_native()
    set_flag("rpc_native_client_lane", False)
    try:
        srv, _svc = _mk_server()
        try:
            comp0, _ = _lane_counts()
            ch = _single_channel(srv)
            assert ch.call("CL.Echo", b"classic") == b"classic"
            comp1, _ = _lane_counts()
            assert comp1 == comp0
        finally:
            srv.stop()
    finally:
        set_flag("rpc_native_client_lane", True)
