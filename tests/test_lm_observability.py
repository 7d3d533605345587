"""Inference-plane observability (ISSUE 18): batcher step profiler,
per-session token timelines, SLO attainment, the /lm portal, and the
stitched decode-session rpcz trace.

Five planes:

- CLOSED ENUMS: ``LM_STEP_PHASES`` / ``LM_SLO_VERDICTS`` pinned
  member-by-member (the static enum checker requires every name
  anchored here); an unregistered verdict asserts loudly at the first
  count;
- PROFILER INVARIANTS: per-phase histogram mass equals the phase
  count, counts are monotonic across sessions, and the count of the
  phase that holds the round's sync (``device_wait``) equals the
  batcher's step counter exactly — the profiler is wired to the loop,
  not near it;
- THE LOOP, ACCOUNTED FOR (ISSUE 25): the phases partition the loop
  (they add up to ``loop_ns`` and never nest), the sync falls in
  ``device_wait`` and not in ``step_dispatch``, the same names reach
  the profiler's annotations, queue wait is stamped per session, the
  engine times its write batches, and the kill switch stops all of it;
- SLO ATTAINMENT: per-tier verdict deltas against
  ``TierRegistry.set_slo`` targets (ok / ttft-miss / itl-miss /
  untargeted), judged at session close;
- STITCHED TRACE: one traced ``LM.Decode`` through the disaggregated
  prefill→decode handoff produces ONE trace id carrying both tiers'
  session spans — chunk-slice on the prefill side, first-token on the
  decode side — with no new wire format (the handoff RPC's ordinary
  trace TLVs);
- SURFACES: /lm + Prometheus exposition smoke, the
  one-snapshot-per-interval cache pin, windowed-vs-lifetime ratio
  semantics, bounded-ring eviction.
"""

import http.client
import json
import struct
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.client import Channel, Controller
from brpc_tpu.models import lm_telemetry as lmt
from brpc_tpu.models.lm_service import (ContinuousBatcher, LMService,
                                        TierRegistry,
                                        _reset_sched_for_tests,
                                        pack_generate_request,
                                        unpack_token)
from brpc_tpu.models.transformer_lm import LMConfig, init_params
from brpc_tpu.rpcz import global_span_store
from brpc_tpu.server import Server
from brpc_tpu.streaming import StreamOptions, stream_create

# ---------------------------------------------------------------------------
# Closed-enum pins (tools/check/enums.py requires every member of the
# observability enums anchored under tests/ — this is the anchor)
# ---------------------------------------------------------------------------

LM_STEP_PHASE_PINS = (
    "sched", "idle_wait", "prefix_lookup", "page_alloc",
    "prefill_dispatch", "insert_dispatch", "chunk_slice",
    "catchup_slice", "step_dispatch", "device_wait", "token_walk",
    "stream_emit", "evict", "host_spill", "host_resume",
)
LM_SLO_VERDICT_PINS = ("slo_ok", "slo_ttft_miss", "slo_itl_miss",
                       "slo_untargeted")


def test_lm_obs_enums_match_pins():
    assert lmt.LM_STEP_PHASES == LM_STEP_PHASE_PINS
    assert lmt.LM_SLO_VERDICTS == LM_SLO_VERDICT_PINS
    assert set(lmt.phase_counters()) == set(LM_STEP_PHASE_PINS)
    # the index constants ARE the write-side API: drift fails here
    for i, name in enumerate(LM_STEP_PHASE_PINS):
        assert getattr(lmt, "PH_" + name.upper()) == i
        assert lmt.phase_index(name) == i
    with pytest.raises(AssertionError):
        lmt.phase_index("some_new_phase")
    with pytest.raises(AssertionError):
        lmt.count_slo("standard", "slo_some_new_verdict")
    with pytest.raises(AssertionError):
        lmt.count_slo("platinum", "slo_ok")


def test_tier_registry_slo_targets():
    reg = TierRegistry()
    assert reg.slo_of("interactive") == (None, None)
    reg.set_slo("interactive", ttft_ms=250.0, itl_ms=50.0)
    reg.set_slo("batch", itl_ms=1000.0)
    assert reg.slo_of("interactive") == (250.0, 50.0)
    assert reg.slo_of("batch") == (None, 1000.0)
    with pytest.raises(ValueError, match="unknown SLO tier"):
        reg.set_slo("platinum", ttft_ms=1.0)


# ---------------------------------------------------------------------------
# Harness (the direct-batcher idiom from test_slo_sched)
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
    lmt._reset_for_tests()


class _FakeStream:
    def __init__(self):
        self.closed = False
        self.closed_at = None
        self.close_reason = None
        self.tokens = []
        self.id = 0
        self._native_tx = None
        self.options = StreamOptions()

    def write(self, data):
        self.tokens.append(struct.unpack("<i", bytes(data))[0])
        return 0

    def close(self, reason=None):
        self.closed_at = time.monotonic()
        self.closed = True
        self.close_reason = reason


def _join(bat, prompt, max_new, tenant=None, span=None):
    st = _FakeStream()
    bat.join(st, prompt, max_new, tenant=tenant, span=span)
    return st


def _quiet(bat, timeout=30.0):
    """A stream closes INSIDE the loop's evict phase; the samples of
    that pass's tail land after it.  Wait until the batcher sits in
    its idle wait (or its thread has left), so counters read level."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if bat._thread is None or bat._clock.cur == lmt.PH_IDLE_WAIT:
            return
        time.sleep(0.002)
    raise AssertionError("the batcher never went idle")


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
# Step profiler: histogram/count invariants, count == steps
# ---------------------------------------------------------------------------

def test_phase_profiler_invariants():
    """Histogram mass == phase count for every phase; the count of
    ``device_wait`` (the phase that holds the round's sync) equals the
    batcher's own step counter EXACTLY (one sample per round); counts
    are monotonic across sessions; total_ns is consistent with the
    counts."""
    _reset()
    cfg, params = _setup()
    bat = ContinuousBatcher(cfg, params, slots=2, page=16,
                            prefill_chunk_tokens=4)
    st = _join(bat, _prompt(3, 17), 6)
    _finish(st)
    _quiet(bat)
    c1 = lmt.phase_counters()
    assert c1["device_wait"] == bat.steps_run()
    assert c1["step_dispatch"] == c1["token_walk"] == bat.steps_run()
    assert c1["chunk_slice"] >= 4                # ceil(16/4) slices
    assert c1["prefix_lookup"] >= 1
    assert c1["page_alloc"] >= 1
    assert c1["stream_emit"] >= 1
    for name in lmt.LM_STEP_PHASES:
        hist = lmt.phase_histogram(name)
        assert len(hist) == lmt.NBUCKETS
        assert sum(hist) == c1[name], name
        assert all(v >= 0 for v in hist)
    totals = lmt.phase_total_ns()
    assert totals["device_wait"] > 0 and totals["sched"] > 0
    assert totals["host_spill"] == 0             # nothing spilled here
    # monotonic across a second session, and still step-exact
    st2 = _join(bat, _prompt(4, 9), 4)
    _finish(st2)
    _quiet(bat)
    c2 = lmt.phase_counters()
    assert all(c2[p] >= c1[p] for p in lmt.LM_STEP_PHASES)
    assert c2["idle_wait"] > c1["idle_wait"]     # the wait between them
    assert c2["device_wait"] == bat.steps_run()
    assert sum(lmt.phase_histogram("device_wait")) \
        == c2["device_wait"]


def test_profiler_disable_flag_stops_sampling():
    from brpc_tpu.butil.flags import set_flag
    _reset()
    cfg, params = _setup()
    bat = ContinuousBatcher(cfg, params, slots=2)
    assert set_flag("lm_telemetry", "false")
    try:
        assert not lmt.telemetry_enabled()
        st = _join(bat, _prompt(5, 6), 3)
        _finish(st)
        assert lmt.phase_counters()["device_wait"] == 0
        assert lmt.live_sessions() == [] and lmt.ring_len() == 0
    finally:
        assert set_flag("lm_telemetry", "true")
    assert lmt.telemetry_enabled()


# ---------------------------------------------------------------------------
# The loop, accounted for (ISSUE 25)
# ---------------------------------------------------------------------------

def test_sync_falls_in_device_wait_not_dispatch(monkeypatch):
    """The S2 repair: the barrier of a round lies inside a phase of its
    own.  With tokens that take 20 ms to come back (the argmax's result
    feeds the next step as it lies, so the delay sits where the batcher
    reads it, as a device that is still computing puts it),
    ``device_wait`` grows by >= 20 ms a step and ``step_dispatch`` does
    not."""
    _reset()
    cfg, params = _setup()
    real = ContinuousBatcher._read_tokens

    def slow_read(toks):
        time.sleep(0.020)
        return real(toks)

    bat = ContinuousBatcher(cfg, params, slots=2)
    st = _join(bat, _prompt(5, 6), 2)            # compiles, unpatched
    _finish(st)
    _quiet(bat)
    steps0 = bat.steps_run()
    ns0 = lmt.phase_total_ns()
    monkeypatch.setattr(ContinuousBatcher, "_read_tokens",
                        staticmethod(slow_read))
    st = _join(bat, _prompt(6, 6), 5)
    _finish(st)
    _quiet(bat)
    monkeypatch.undo()
    steps = bat.steps_run() - steps0
    ns1 = lmt.phase_total_ns()
    assert steps == 5
    assert ns1["device_wait"] - ns0["device_wait"] >= steps * 20e6
    assert ns1["step_dispatch"] - ns0["step_dispatch"] < steps * 10e6


def test_phases_partition_the_loop():
    """Over 50 steps and more, with joins into a running batch and
    evictions: the phases' totals add up to the loop's wall time (never
    more than ``loop_ns``, at least 0.9 of it), every sample is in a
    histogram, and ``kv_stats`` carries all three counters."""
    _reset()
    cfg, params = _setup()
    bat = ContinuousBatcher(cfg, params, slots=2, page=16)
    streams = []
    for i in range(8):
        streams.append(_join(bat, _prompt(10 + i, 5 + i), 13 + i % 3))
        if i % 3 == 2:
            _finish(streams[-2])                 # a join into a running batch
    _finish(*streams)
    _quiet(bat)
    assert bat.steps_run() >= 50
    kv = bat.kv_stats()
    assert kv["phase_ns"] == lmt.phase_total_ns()
    accounted, loop = sum(kv["phase_ns"].values()), kv["loop_ns"]
    assert loop > 0 and 0.9 * loop <= accounted <= loop
    c = kv["phases"]
    assert c["evict"] >= 1 and c["prefill_dispatch"] >= 1
    assert c["insert_dispatch"] >= c["prefill_dispatch"]
    assert c["sched"] >= bat.steps_run()         # once a pass, and more
    assert kv["queue"]["admitted"] == 8
    for name in lmt.LM_STEP_PHASES:
        assert sum(lmt.phase_histogram(name)) == c[name], name


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation`` and
    ``StepTraceAnnotation``: notes what is built, entered and left."""

    log: list = []

    def __init__(self, name, **kw):
        self.name = name
        _Recorder.log.append(("new", name, kw))

    def __enter__(self):
        _Recorder.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _Recorder.log.append(("exit", self.name))


def test_phases_reach_the_profiler_under_their_names(monkeypatch):
    """The same names on the profiler's clock: every phase whose count
    grew was annotated ``lm/<phase>`` and no other name was, one
    annotation is open at a time (a phase is left before the next is
    entered), and each step has its ``lm_round``: the pass that
    dispatched it, under the step's number; one pass more lands the
    last step and dispatches none."""
    # the phase table is the process's: an earlier test's batcher that
    # lingers out (5 s) during this one would add a sample whose
    # annotation was opened before the recorder stood in
    deadline = time.monotonic() + 30
    while any(t.name == "lm-decode-batcher" for t in threading.enumerate()) \
            and time.monotonic() < deadline:
        time.sleep(0.02)
    _reset()
    monkeypatch.setattr(_Recorder, "log", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", _Recorder)
    cfg, params = _setup()
    bat = ContinuousBatcher(cfg, params, slots=2, page=16,
                            idle_linger_s=0.05)
    st1 = _join(bat, _prompt(3, 9), 5)
    st2 = _join(bat, _prompt(4, 7), 3)
    _finish(st1, st2)
    deadline = time.monotonic() + 30
    while bat._thread is not None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert bat._thread is None                   # lingered out: all closed
    log = list(_Recorder.log)
    grew = {p for p, n in lmt.phase_counters().items() if n}
    phases = [e for e in log if e[1] != lmt.ROUND_TRACE_NAME]
    assert {e[1] for e in phases} == {"lm/" + p for p in grew}
    assert {"lm/step_dispatch", "lm/device_wait", "lm/token_walk",
            "lm/stream_emit", "lm/evict", "lm/sched", "lm/idle_wait",
            "lm/prefill_dispatch", "lm/insert_dispatch"} \
        <= {e[1] for e in phases}
    # never two open: new, enter, exit, new, enter, exit, ...
    assert [e[0] for e in phases] \
        == ["new", "enter", "exit"] * (len(phases) // 3)
    assert len(phases) // 3 == sum(lmt.phase_counters().values())
    rounds = [e for e in log if e[1] == lmt.ROUND_TRACE_NAME]
    new_rounds = [e for e in rounds if e[0] == "new"]
    assert len(new_rounds) == bat.steps_run() + 1 >= 6
    assert [e[2]["step_num"] for e in new_rounds] \
        == list(range(bat.steps_run() + 1))
    assert [e[0] for e in rounds] \
        == ["new", "enter", "exit"] * len(new_rounds)


class _FakeSpan:
    def __init__(self):
        self.notes = []
        self.finished = False

    def annotate(self, text):
        self.notes.append(text)

    def finish(self, _code):
        self.finished = True


def test_queue_wait_is_stamped_when_the_batcher_takes_the_session():
    """A session joined while every slot is taken waits in the pending
    queue until one frees: its ``queue_ms`` is at least that long, its
    span carries ``lm_admit`` between ``lm_join`` and the first token,
    and the queue counters grew by one a session."""
    _reset()
    cfg, params = _setup()
    bat = ContinuousBatcher(cfg, params, slots=1)
    q0 = bat.kv_stats()["queue"]
    assert q0 == {"wait_ns": 0, "admitted": 0}
    first = _join(bat, _prompt(7, 6), 10)
    span = _FakeSpan()
    second = _join(bat, _prompt(8, 6), 2, span=span)
    joined = time.monotonic()                    # after its join_ns
    _finish(first, second)
    _quiet(bat)
    recs = {r["max_new"]: r for r in lmt.timeline_records()}
    freed_after_ms = (first.closed_at - joined) * 1e3
    assert freed_after_ms > 0
    assert recs[2]["queue_ms"] >= freed_after_ms
    assert recs[2]["queue_ms"] <= recs[2]["ttft_ms"]
    assert recs[10]["queue_ms"] < recs[2]["queue_ms"]
    assert span.notes[:2] == ["lm_join", "lm_admit"]
    # each with the step it belongs to: the first session took steps
    # 0-9, this one 10 and 11
    assert span.notes[2:] == ["lm_first_token round=10",
                              "lm_evict:finished round=11"]
    assert span.finished
    q1 = bat.kv_stats()["queue"]
    assert q1["admitted"] == 2
    assert q1["wait_ns"] >= recs[2]["queue_ms"] * 1e6 * 0.999
    rows = lmt._queue_rows()
    assert rows[("standard", "p99")] >= recs[2]["queue_ms"]


def test_engine_times_its_write_batches():
    """``streams.write_ns``: 0 before any write batch, then growing
    with ``write_batches`` (two clock reads a ``stream_write_many``)."""
    from brpc_tpu import native
    from brpc_tpu.server import ServerOptions
    if native.load() is None:
        pytest.skip("the native engine does not build here")
    _reset()
    cfg, params = _setup()
    lm = LMService(cfg=cfg, params=params, decode_slots=2)
    opts = ServerOptions()
    opts.native = True
    opts.usercode_inline = True
    srv = Server(opts)
    srv.add_service(lm, name="LM")
    assert srv.start("127.0.0.1:0") == 0
    try:
        assert srv._native_bridge is not None
        tele = srv._native_bridge.engine.telemetry
        st0 = tele()["streams"]
        assert st0["write_batches"] == 0 and st0["write_ns"] == 0
        toks, reason = _stream_decode_traced(
            srv, _prompt(2, 8)[None, :], 4, 0)
        assert reason == "finished" and len(toks) == 4
        st1 = tele()["streams"]
        assert st1["write_batches"] >= 4 and st1["write_ns"] > 0
        # a write batch is microseconds, not the step it follows
        assert st1["write_ns"] / st1["write_batches"] < 50e6
        toks, reason = _stream_decode_traced(
            srv, _prompt(3, 8)[None, :], 3, 0)
        assert len(toks) == 3
        st2 = tele()["streams"]
        assert st2["write_batches"] > st1["write_batches"]
        assert st2["write_ns"] > st1["write_ns"]
    finally:
        srv.stop()


def test_kill_switch_stops_phases_annotations_and_queue(monkeypatch):
    """With ``lm_telemetry`` off nothing grows and no annotation is so
    much as constructed; switched back on, the clock starts from a
    fresh stamp (the off spell is credited to no phase)."""
    from brpc_tpu.butil.flags import set_flag
    _reset()
    monkeypatch.setattr(_Recorder, "log", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", _Recorder)
    cfg, params = _setup()
    bat = ContinuousBatcher(cfg, params, slots=2, page=16)
    assert set_flag("lm_telemetry", "false")
    t_off = time.monotonic_ns()
    try:
        st = _join(bat, _prompt(5, 6), 3)        # compiles: a long spell
        _finish(st)
        assert _Recorder.log == []
        assert not any(lmt.phase_counters().values())
        assert not any(lmt.phase_total_ns().values())
        kv = bat.kv_stats()
        assert kv["loop_ns"] == 0
        assert kv["queue"] == {"wait_ns": 0, "admitted": 0}
    finally:
        assert set_flag("lm_telemetry", "true")
    t_on = time.monotonic_ns()
    st = _join(bat, _prompt(6, 6), 3)
    _finish(st)
    _quiet(bat)
    kv = bat.kv_stats()
    assert kv["phases"]["device_wait"] == 3
    assert _Recorder.log
    # the spell spent off is in no phase and not in loop_ns
    assert sum(kv["phase_ns"].values()) <= kv["loop_ns"] \
        <= time.monotonic_ns() - t_on
    assert t_on - t_off > 0


# ---------------------------------------------------------------------------
# SLO attainment: per-tier verdict deltas at session close
# ---------------------------------------------------------------------------

def test_slo_verdicts_per_tier():
    _reset()
    cfg, params = _setup()
    reg = TierRegistry()
    reg.set_tier(b"alice", "interactive")
    reg.set_tier(b"bob", "batch")
    # generous targets: a toy decode on CPU finishes well inside 10 min
    reg.set_slo("interactive", ttft_ms=600_000.0, itl_ms=600_000.0)
    # impossible targets: a negative bound no real session can meet
    reg.set_slo("batch", ttft_ms=-1.0)
    # the default tier ("standard") configures no targets
    bat = ContinuousBatcher(cfg, params, slots=3, tiers=reg)
    st_a = _join(bat, _prompt(6, 6), 3, tenant=b"alice")
    st_b = _join(bat, _prompt(7, 6), 3, tenant=b"bob")
    st_c = _join(bat, _prompt(8, 6), 3, tenant=b"carol")
    _finish(st_a, st_b, st_c)
    slo = lmt.slo_counters()
    assert slo[("interactive", "slo_ok")] == 1
    assert slo[("batch", "slo_ttft_miss")] == 1
    assert slo[("standard", "slo_untargeted")] == 1
    # itl-miss: ttft untargeted, itl target impossible — a session
    # with a second token always exceeds it
    reg.set_slo("batch", itl_ms=-1.0)
    st_d = _join(bat, _prompt(9, 6), 3, tenant=b"bob")
    _finish(st_d)
    assert lmt.slo_counters()[("batch", "slo_itl_miss")] == 1
    # the finished sessions moved into the ring with their verdicts
    recs = lmt.timeline_records()
    assert len(recs) == 4 and lmt.live_sessions() == []
    by_tier = {r["tier"]: r for r in recs}
    assert by_tier["interactive"]["verdict"] == "slo_ok"
    assert by_tier["standard"]["verdict"] == "slo_untargeted"
    assert all(r["close_reason"] == "finished" for r in recs)
    assert all(r["tokens"] == 3 for r in recs)
    assert by_tier["interactive"]["ttft_ms"] is not None


def test_timeline_ring_bounded():
    _reset()
    lmt._reset_for_tests(ring=4)
    try:
        seqs = []
        for i in range(6):
            tl = lmt.open_timeline("standard", f"t{i}", 8, 2, "fresh")
            seqs.append(tl.seq)
            lmt.close_timeline(tl, "finished")
        assert lmt.ring_len() == 4 and lmt.ring_maxlen() == 4
        kept = [r["seq"] for r in lmt.timeline_records()]
        assert kept == seqs[-4:]             # oldest two evicted
        assert lmt.live_sessions() == []
    finally:
        lmt._reset_for_tests()


# ---------------------------------------------------------------------------
# Snapshot cache: one build per interval; windowed vs lifetime ratios
# ---------------------------------------------------------------------------

def test_one_snapshot_per_interval():
    _reset()
    cache = lmt.LmTelemetryCache(ttl_s=60.0)
    for _ in range(25):
        cache.get()
        cache.window()
    assert cache.builds == 1


def test_windowed_ratios_reflect_current_window():
    """Lifetime counters carry history; the windowed ratios are deltas
    between consecutive snapshots — stale history cannot dilute them."""
    from brpc_tpu.kv.pages import count_prefix
    _reset()
    # seed old history: 9 hits, 1 miss (lifetime ratio 0.9)
    for _ in range(9):
        count_prefix("prefix_hit")
    count_prefix("prefix_miss")
    assert lmt.lifetime_prefix_hit_ratio() == pytest.approx(0.9)
    cache = lmt.LmTelemetryCache(ttl_s=0.0)      # every call refreshes
    cache.get()                                  # baseline snapshot
    # the current window: 1 hit, 3 misses
    count_prefix("prefix_hit")
    for _ in range(3):
        count_prefix("prefix_miss")
    assert lmt.windowed_prefix_hit_ratio(cache) == pytest.approx(0.25)
    # lifetime is untouched by the windowing
    assert lmt.lifetime_prefix_hit_ratio() == pytest.approx(10 / 14)


def test_windowed_prefix_ratio():
    from brpc_tpu.kv.pages import count_prefix
    _reset()
    count_prefix("prefix_miss")                  # history
    cache = lmt.LmTelemetryCache(ttl_s=0.0)
    cache.get()
    count_prefix("prefix_hit")
    count_prefix("prefix_partial_hit")
    count_prefix("prefix_miss")
    count_prefix("prefix_hit")
    assert lmt.windowed_prefix_hit_ratio(cache) == pytest.approx(0.75)


# ---------------------------------------------------------------------------
# Stitched disagg trace: ONE trace id across prefill + decode tiers
# ---------------------------------------------------------------------------

def _stream_decode_traced(srv, prompt, max_new, trace_id,
                          timeout=120.0):
    toks, closed = [], []

    def on_received(st, msgs):
        toks.extend(unpack_token(m) for m in msgs)

    ch = Channel()
    ch.init(str(srv.listen_endpoint))
    cntl = Controller()
    cntl.timeout_ms = int(timeout * 1000)
    cntl.trace_id = trace_id
    stream_create(cntl, StreamOptions(
        on_received=on_received,
        on_closed=lambda st: closed.append(st.close_reason)))
    c = ch.call_method("LM.Decode",
                       pack_generate_request(prompt, max_new),
                       cntl=cntl)
    assert not c.failed, (c.error_code, c.error_text)
    deadline = time.monotonic() + timeout
    while not closed and time.monotonic() < deadline:
        time.sleep(0.005)
    assert closed, "decode stream never closed"
    return toks, closed[0]


def _spans_by_method(trace_id, want, timeout=10.0):
    """The decode-tier session span finishes on the batcher thread at
    evict — poll briefly so the assert races nothing."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        spans = global_span_store().by_trace(trace_id)
        have = {s.full_method for s in spans}
        if want <= have:
            return {m: [s for s in spans if s.full_method == m]
                    for m in have}
        time.sleep(0.01)
    raise AssertionError(
        f"trace {trace_id:x} never collected {want - have}; "
        f"has {sorted(have)}")


def test_disagg_decode_session_trace_stitched():
    """The acceptance pin: a single traced LM.Decode through the
    disaggregated prefill→decode handoff yields ONE trace id holding
    both tiers' session spans — the prefill side's chunk-slice and
    handoff events, the decode side's first-token and evict events —
    parented to their tiers' server spans.  The trace context crossed
    tiers on the handoff RPC's EXISTING trace TLVs (no new wire
    format)."""
    from test_kv_disagg import _setup as _kv_setup
    from test_kv_disagg import _two_tier
    _reset()
    global_span_store().clear()
    cfg, params, prompt = _kv_setup()
    trace_id = 0x1517_0018
    pre_srv, dec_srv, dec_lm, _pre, _dch = _two_tier(cfg, params)
    try:
        toks, reason = _stream_decode_traced(pre_srv, prompt, 6,
                                             trace_id)
        assert reason == "finished" and len(toks) == 6
        by = _spans_by_method(trace_id, {
            "LMService.DecodeSession", "KV.DecodeTierSession",
            "LM.Decode", "KV.ImportSession"})
        # prefill tier: the session span parents to the Decode server
        # span and carries the join/chunk-slice/handoff events
        (pre_sess,) = by["LMService.DecodeSession"]
        dec_server = [s for s in by["LM.Decode"] if s.is_server]
        assert pre_sess.parent_span_id in {s.span_id
                                           for s in dec_server}
        pre_notes = [t for _, t in pre_sess.annotations]
        assert pre_notes[0] == "lm_join"
        assert "lm_chunk_slice" in pre_notes
        assert pre_notes[-1] == "lm_handoff"
        # decode tier: the session span parents to the ImportSession
        # server span (which is forced under the SAME trace id because
        # the handoff controller carried it) and sees the first token
        (dec_sess,) = by["KV.DecodeTierSession"]
        imp_server = [s for s in by["KV.ImportSession"] if s.is_server]
        assert dec_sess.parent_span_id in {s.span_id
                                           for s in imp_server}
        dec_notes = [t for _, t in dec_sess.annotations]
        assert any(n.startswith("lm_first_token round=")
                   for n in dec_notes)
        assert dec_notes[-1].startswith("lm_evict:finished round=")
        assert dec_sess.trace_id == pre_sess.trace_id == trace_id
    finally:
        pre_srv.stop()
        dec_srv.stop()
        global_span_store().clear()


def test_monolithic_decode_session_span():
    """Single-tier shape: a traced Decode gets one session span with
    join → first-token → evict, child of the Decode server span."""
    _reset()
    global_span_store().clear()
    cfg, params = _setup()
    lm = LMService(cfg=cfg, params=params, decode_slots=2)
    srv = Server()
    srv.add_service(lm, name="LM")
    assert srv.start("127.0.0.1:0") == 0
    try:
        trace_id = 0xA11CE
        toks, reason = _stream_decode_traced(
            srv, _prompt(2, 8)[None, :], 4, trace_id)
        assert reason == "finished" and len(toks) == 4
        by = _spans_by_method(trace_id, {"LMService.DecodeSession",
                                         "LM.Decode"})
        (sess,) = by["LMService.DecodeSession"]
        notes = [t for _, t in sess.annotations]
        assert notes[0] == "lm_join"
        assert "lm_first_token round=0" in notes
        assert notes[-1] == "lm_evict:finished round=3"
        server_ids = {s.span_id for s in by["LM.Decode"]
                      if s.is_server}
        assert sess.parent_span_id in server_ids
    finally:
        srv.stop()
        global_span_store().clear()


# ---------------------------------------------------------------------------
# Surfaces: /lm portal page + Prometheus exposition
# ---------------------------------------------------------------------------

def _http_get(ep, path):
    conn = http.client.HTTPConnection(ep.host, ep.port, timeout=10)
    conn.request("GET", path)
    r = conn.getresponse()
    body = r.read()
    conn.close()
    return r.status, body


def test_lm_portal_and_metrics_exposition():
    _reset()
    cfg, params = _setup()
    lm = LMService(cfg=cfg, params=params, decode_slots=2)
    srv = Server()
    srv.add_service(lm, name="LM")
    assert srv.start("127.0.0.1:0") == 0
    try:
        st = _FakeStream()
        lm.batcher().join(st, _prompt(2, 8), 4)
        _finish(st)
        _quiet(lm.batcher())
        ep = srv.listen_endpoint
        status, body = _http_get(ep, "/lm")
        assert status == 200
        page = json.loads(body)
        assert page["enabled"] is True
        assert page["phases"]["device_wait"]["count"] \
            == lm.batcher().steps_run()
        assert page["phases"]["device_wait"]["buckets_ns"]
        assert set(page["phases"]) == set(LM_STEP_PHASE_PINS)
        assert 0 < page["loop"]["accounted_ns"] <= page["loop"]["loop_ns"]
        assert page["queue"]["admitted"] == 1
        assert "standard|p50" in page["queue_ms"]
        recent = page["recent_sessions"]
        assert len(recent) == 1 and recent[0]["tokens"] == 4
        assert recent[0]["verdict"] == "slo_untargeted"
        assert 0 <= recent[0]["queue_ms"] <= recent[0]["ttft_ms"]
        assert page["live_sessions"] == []
        assert "prefix_cache_hit_ratio" in page["windowed"]
        assert page["lifetime"]["prefix_cache_hit_ratio"] == 0.0
        assert page["timeline_ring"]["len"] == 1
        assert page["kv"]["phases"]["device_wait"] \
            == lm.batcher().steps_run()
        assert page["kv"]["queue"]["admitted"] == 1
        # the steps as spans: from the session's widest gap to the step
        # that ended it and what stood in front of it
        rounds = page["rounds"]
        assert [r["ordinal"] for r in rounds["last"]] == [0, 1, 2, 3]
        assert rounds["last"][0]["cls"] == "restart"
        assert rounds["last"][0]["fill_programs"] == 2
        assert rounds["total"]["restart"]["n"] == 1
        assert rounds["total"]["plain"]["n"] == 3
        assert set(rounds["total"]) == {"restart", "fill", "ride", "plain",
                                        "late", "max_gap_ns", "max_ordinal"}
        # a second read inside the cache's interval has no new window
        assert set(rounds["window"]) <= set(rounds["total"])
        assert set(rounds["dry_ns"]) == set(LM_STEP_PHASE_PINS)
        assert recent[0]["first_round"] == 0
        assert recent[0]["worst_round"] in (1, 2, 3)
        assert recent[0]["worst_round"] \
            in [r["ordinal"] for r in rounds["widest"]]
        assert rounds["total"]["max_ordinal"] == rounds["widest"][0]["ordinal"]
        assert page["kv"]["first"]["n"] == 1
        # the same counters ride the Prometheus exposition
        status, body = _http_get(ep, "/metrics")
        assert status == 200
        text = body.decode()
        assert 'lm_step_phase_total{phase="device_wait"}' in text
        assert 'lm_queue_ms{tier="standard",quantile="p50"}' in text
        assert 'lm_slo_attained_total{tier="standard",' \
            'verdict="slo_untargeted"}' in text
        assert 'lm_ttft_ms{tier="standard",quantile="p50"}' in text
        assert 'lm_windowed{ratio="prefix_cache_hit_ratio"}' in text
        assert 'lm_step_phase_ns{phase="device_wait",bin=' in text
    finally:
        srv.stop()
