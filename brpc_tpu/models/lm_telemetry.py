"""Inference-plane observability (ISSUE 18): the serving-side analogue
of the native engine's telemetry table.

Three planes, all fed by the ONE batcher thread and read passively:

- **step profiler** — per-phase monotonic-ns log2 histograms that
  PARTITION the continuous batcher's loop: the batcher thread moves a
  cursor (:class:`PhaseClock`) from one member of ``LM_STEP_PHASES`` to
  the next, so phases never nest and every nanosecond between the top
  of one pass and the top of the next belongs to exactly one of them
  (``loop_ns``, read on its own, is what they have to add up to).  The
  same cursor wraps each phase in ``jax.profiler.TraceAnnotation
  ("lm/<phase>")`` and each pass that runs a step in
  ``StepTraceAnnotation("lm_round")``, so a profile taken with the host
  tracer on shows the loop under these names beside the device's
  programs.  The tables are each batcher's own, on its clock; the
  process-wide rows sum the live clocks.  The write side is the
  engine-telemetry pattern: plain
  per-thread counters bumped by the batcher thread ONLY — never a lock
  in the step loop (the histograms are preallocated lists;
  ``PhaseClock.switch`` is entry-listed in the blocking linter).
  Readers see racy-but-monotonic values, exactly like
  ``engine.telemetry()`` readers do;
- **the step as a span** (ISSUE 35) — the same clock keeps, per batcher,
  one record for each decode step it queued (:class:`RoundLog`: a ring
  of ``ROUND_RING``, the ordinal the ``lm_round`` annotation's
  ``step_num``): what was queued in front of it, when it landed and
  when the host first knew it finished, whether the device had run
  dry or been starved by a late host; counters by that class, the
  dry and starved time by the phase the host was in, and the stages
  of a first token;
- **session timelines** — a bounded ring of per-session records
  (tier/tenant, prompt length, queue wait, TTFT, the widest gap and
  the step that ended it, prefix hit class, peak pages held, spill/resume/preempt counts, close
  reason) that feeds per-tier ``lm_ttft_ms``/``lm_itl_ms`` percentile
  rows and the CLOSED ``LM_SLO_VERDICTS`` attainment counters
  (``lm_slo_attained_total{tier,verdict}``) judged against the
  :class:`~brpc_tpu.models.lm_service.TierRegistry`'s per-tier targets;
- **snapshot cache** — a ``_TelemetryCache``-style short-TTL cache so
  /vars, /metrics and the ``/lm`` portal page all share ONE snapshot
  per interval (``window()`` additionally retains the previous snapshot
  so the windowed ``prefix_cache_hit_ratio`` reflects CURRENT
  behavior instead of a lifetime average — the lifetime key stays
  where perf_guard reads it).

Everything here must stay importable without the native engine and
without jax — the module is pure-Python bookkeeping; the batcher hands
the profiler's annotation classes to its :class:`PhaseClock`.
"""

from __future__ import annotations

import itertools
import threading
import weakref
from collections import deque
from time import monotonic as _mono_s
from time import monotonic_ns as _mono_ns
# the profiler's clock: the TSL profiler stamps a trace's host events
# with absl::GetCurrentTimeNanos() and converts the device's onto it
from time import time_ns as _trace_ns
from typing import Optional

from ..butil.flags import define_flag, get_flag, watch_flag
from ..bvar.multi_dimension import PassiveDimension

define_flag("lm_telemetry", True,
            "serving-plane observability master switch: step-phase "
            "histograms, per-session token timelines, SLO attainment "
            "(flippable live; the step loop reads a flag-cache, not "
            "the flags table)",
            validator=lambda v: isinstance(v, bool))
define_flag("lm_timeline_ring", 256,
            "bounded ring of closed per-session token timelines kept "
            "for the /lm portal's recent-sessions table",
            validator=lambda v: isinstance(v, int) and 0 < v <= 65536)

# ---------------------------------------------------------------------------
# Step profiler: per-phase log2 ns histograms (batcher-thread writes)
# ---------------------------------------------------------------------------

# CLOSED enum (tools/check/enums.py pins every member to a test): the
# leaves that partition the batcher thread's loop, in the order a pass
# meets them.  Indexes are the write-side API — the batcher binds the
# PH_* constants and its clock's ``switch`` as locals.  A phase marked
# "enqueue" returns when the work is QUEUED for the device: its device
# time is the trace's, not the phase's.
LM_STEP_PHASES = (
    "sched",             # parked sessions, the lock, pending sort+pop, activate
    "idle_wait",         # nothing to do: the wake event / the parked poll
    "prefix_lookup",     # prefix-cache probe at admit
    "page_alloc",        # page allocation incl. the reclaim walk
    "prefill_dispatch",  # host padding + the bucketed prefill (enqueue)
    "insert_dispatch",   # insert + setlen + the block-table row (enqueue)
    "chunk_slice",       # one bounded prefill slice, fresh prompt (enqueue)
    "catchup_slice",     # slice replaying past a partial prefix hit (enqueue)
    "step_dispatch",     # the step's inputs, _step and argmax (enqueue)
    "device_wait",       # the tokens come back: the round's one sync
    "token_walk",        # tokens -> (session, token) pairs
    "stream_emit",       # one step's token writes + the timelines' stamps
    "evict",             # dead and finished sessions leave
    "host_spill",        # one session's D2H park
    "host_resume",       # one session's H2D un-park
)

PH_SCHED = 0
PH_IDLE_WAIT = 1
PH_PREFIX_LOOKUP = 2
PH_PAGE_ALLOC = 3
PH_PREFILL_DISPATCH = 4
PH_INSERT_DISPATCH = 5
PH_CHUNK_SLICE = 6
PH_CATCHUP_SLICE = 7
PH_STEP_DISPATCH = 8
PH_DEVICE_WAIT = 9
PH_TOKEN_WALK = 10
PH_STREAM_EMIT = 11
PH_EVICT = 12
PH_HOST_SPILL = 13
PH_HOST_RESUME = 14

_NPHASES = len(LM_STEP_PHASES)
# the ONE source of the names on the profiler's clock
_TRACE_NAMES = tuple("lm/" + p for p in LM_STEP_PHASES)
ROUND_TRACE_NAME = "lm_round"

# engine Hist layout: bucket 0 holds zeros, bucket i covers
# [2^(i-1), 2^i) ns; 40 buckets reach ~9 minutes — beyond any phase
NBUCKETS = 40

# every batcher's clock (its own phase tables); the process-wide rows
# (/vars, /metrics, the /lm page) are their sums
_clocks: "weakref.WeakSet" = weakref.WeakSet()
_clocks_lock = threading.Lock()

# flag-cached enable gate (the rpcz _rpcz_live idiom): one list read on
# the hot path instead of a flags-table lookup per phase sample.  0 is
# off; on, it holds the count of times the flag came on, so a cursor
# that slept through an off spell knows its last stamp is stale
_live = [1 if get_flag("lm_telemetry", True) else 0]
_on_spells = itertools.count(2)
watch_flag("lm_telemetry", lambda v: _live.__setitem__(
    0, next(_on_spells) if v else 0))


def telemetry_enabled() -> bool:
    return bool(_live[0])


def _log2_bucket(ns: int) -> int:
    b = ns.bit_length() if ns > 0 else 0
    return b if b < NBUCKETS else NBUCKETS - 1


def phase_index(name: str) -> int:
    assert name in LM_STEP_PHASES, f"unregistered step phase: {name}"
    return LM_STEP_PHASES.index(name)


# ---------------------------------------------------------------------------
# The step as a span: one record a decode step, per batcher (ISSUE 35)
# ---------------------------------------------------------------------------

# CLOSED enum (tools/check/enums.py pins every member to a test): what
# stood in front of a step in the device's queue.  Each step falls in
# ONE class, tested in this order.
LM_ROUND_CLASSES = (
    "restart",   # the step before it had landed with nothing queued
    #              behind it: the gap holds a lull, not work
    "fill",      # filling programs were queued since the step before
    "ride",      # a catch-up slice on board
    "plain",     # the step before it, and nothing else
)
RC_RESTART, RC_FILL, RC_RIDE, RC_PLAIN = range(4)

ROUND_RING = 4096
ROUND_FIELDS = (
    "ordinal",        # the ``lm_round`` annotation's ``step_num``
    "pass_ns",        # top of the pass that dispatched it: the span's start
    "dispatch_ns",    # ``_step`` has returned: queued for the device
    "seen_ns",        # the first clock read that knew its tokens ready:
    #                   an upper bound of its end on the device
    "wait_ns",        # its ``device_wait``: how long the host stood waiting
    "land_ns",        # the tokens are on the host: the span's end
    "done_ns",        # tokens written, sessions evicted
    "rows",           # active slots it decodes
    "ahead",          # 1: the step before it was unread when it left
    "ride_rows",      # rows of the slice on board
    "fill_programs",  # filling programs queued since the step before it
    "fill_rows",      # context rows they fill (true lengths)
    "joins",          # sessions admitted since the step before it
    "pages",          # pages it attended, by the batcher's count
    "touched",        # held experts it touched
    "dry_ns",         # the device stood dry this long before it
    "starved_ns",     # ... or starved (seen drained) this long
    "cls",            # index into LM_ROUND_CLASSES
    "gap_ns",         # its ``land_ns`` minus the one before it
)


class RoundLog:
    """One batcher's decode steps as spans: a ring of ``ROUND_RING``
    records (a preallocated column of ints a field, indexed by ordinal
    modulo the ring), counters by what stood in front of the step, the
    device's idle time by the phase the host was in (dry: nothing was
    queued; starved: the last program queued was seen finished, and
    the bracket of that finish), and the stages of a first token.
    Plain data: the batcher thread writes it through its
    :class:`PhaseClock`, readers see racy-but-monotonic values."""

    __slots__ = ROUND_FIELDS + (
        "cls_n", "cls_gap_ns", "cls_rows", "cls_programs", "late",
        "max_gap", "dry_by_phase", "starved_by_phase", "bracket_by_phase",
        "starved_n", "first", "pend", "lull", "prev_land", "prev_ord",
        "dry_sum", "dry_mark", "starved_sum", "starved_mark", "landed_idx")

    def __init__(self):
        for f in ROUND_FIELDS:
            setattr(self, f, [0] * ROUND_RING)
        self.ordinal = [-1] * ROUND_RING
        n = len(LM_ROUND_CLASSES)
        self.cls_n = [0] * n
        self.cls_gap_ns = [0] * n
        self.cls_rows = [0] * n         # fill: context rows; ride: the slice's
        self.cls_programs = [0] * n
        self.late = [0, 0]              # n, gap_ns
        self.max_gap = [0, -1]          # ns, ordinal (restarts left out)
        self.dry_by_phase = [0] * _NPHASES
        self.starved_by_phase = [0] * _NPHASES
        self.bracket_by_phase = [0] * _NPHASES
        self.starved_n = 0
        # n, queue_ns, admit_ns, device_ns, emit_ns, ttft_ns
        self.first = [0] * 6
        # filling programs, their rows and joins since the last step
        self.pend = [0, 0, 0]
        self.lull = True                # nothing has been queued yet
        self.prev_land = 0
        self.prev_ord = -2
        self.dry_sum = 0
        self.dry_mark = 0
        self.starved_sum = 0
        self.starved_mark = 0
        self.landed_idx = -1            # the record being delivered

    def counters(self) -> dict:
        """``kv_stats()["rounds"]``: monotonic, cheap to read.
        ``clock`` is the host's monotonic clock (the records' and the
        tables') and the profiler's, read back to back: a record's
        time on a trace is its own plus ``trace_ns - mono_ns``."""
        out = {}
        for c, name in enumerate(LM_ROUND_CLASSES):
            out[name] = {"n": self.cls_n[c], "gap_ns": self.cls_gap_ns[c]}
            if c in (RC_FILL, RC_RIDE):
                out[name]["rows"] = self.cls_rows[c]
                out[name]["programs"] = self.cls_programs[c]
        out["late"] = {"n": self.late[0], "gap_ns": self.late[1]}
        out["max_gap_ns"], out["max_ordinal"] = self.max_gap
        out["dry_ns"] = dict(zip(LM_STEP_PHASES, self.dry_by_phase))
        out["starved_ns"] = dict(zip(LM_STEP_PHASES, self.starved_by_phase))
        out["starved_bracket_ns"] = dict(zip(LM_STEP_PHASES,
                                             self.bracket_by_phase))
        out["starved"] = {"n": self.starved_n}
        out["clock"] = {"mono_ns": _mono_ns(), "trace_ns": _trace_ns()}
        return out

    def first_counters(self) -> dict:
        """``kv_stats()["first"]``: join -> first token in four stages
        that add up to ``ttft_ns`` over the same ``n`` sessions."""
        return dict(zip(("n", "queue_ns", "admit_ns", "device_ns",
                         "emit_ns", "ttft_ns"), self.first))

    def records(self, since: int = 0) -> list:
        """The ring's records of ordinal ``since`` and later, oldest
        first, each a dict of ``ROUND_FIELDS`` with ``cls`` by name."""
        cols = [getattr(self, f) for f in ROUND_FIELDS]
        out = []
        for i in range(ROUND_RING):
            if self.ordinal[i] >= since:
                rec = {f: col[i] for f, col in zip(ROUND_FIELDS, cols)}
                rec["cls"] = LM_ROUND_CLASSES[rec["cls"]]
                out.append(rec)
        out.sort(key=lambda r: r["ordinal"])
        return out


class PhaseClock:
    """The batcher thread's cursor over ``LM_STEP_PHASES``: ``switch``
    ends the phase the loop was in and starts the next on ONE clock
    read, so the phases partition the loop and cannot nest.  A callee
    that is a phase of its own inside another (a spill inside a page
    allocation) switches back to what ``switch`` returned.

    The batcher's OWN state hangs on it (a second batcher has a second
    clock): the phase tables (``count``, ``total_ns``, ``buckets``,
    ``loop_ns``), ``rounds``, the :class:`RoundLog` its step hooks
    write (``filling`` ... ``delivered``), and the device's idle time
    as the host can know it:

    - ``dry``: nothing is queued for the device (from a step landing
      with nothing behind it to the next program's queueing);
    - ``starved``: the last program queued was SEEN finished.  Each
      hook that queues a program hands in one of its outputs (``ref``:
      one no later program donates); while it is held and unseen,
      every ``switch`` tests it once (``_ready``).  The first that
      sees it ready, with nothing queued behind it, marks the device
      starved from that clock read until the next program is queued.
      The phase in which it finished, from the last read that saw it
      running, is the finish's uncertainty: ``bracket_by_phase``.

    ``switch`` credits an ended phase to the dry or the starved table
    as well; dry + starved is a lower bound of the device's idle time
    between programs, and the bracket added an upper bound.

    ``bind`` hands in ``jax.profiler.TraceAnnotation`` /
    ``StepTraceAnnotation`` (this module imports no jax);
    with no profile session, or one whose host tracer is off, building
    one is a flag test inside the profiler.  With ``lm_telemetry`` off
    nothing is built, written or tested and every hook is the gate's
    single list read."""

    __slots__ = ("cur", "t", "_gen", "_loop_t", "_pass_t", "_ann",
                 "_round", "_trace_cls", "_step_cls", "rounds", "dry",
                 "starved", "count", "total_ns", "buckets", "loop_ns",
                 "_ref", "_older", "_busy_t", "_seen_lo", "_step_hi",
                 "__weakref__")

    def __init__(self, trace_cls=None, step_cls=None):
        self.cur = -1               # the open phase; -1: none
        self.t = 0                  # the last switch's clock read
        self._gen = -1              # the on-spell (_live[0]) of t, _loop_t
        self._loop_t = 0
        self._pass_t = 0            # the pass's first switch
        self._ann = None
        self._round = None
        self._trace_cls = trace_cls
        self._step_cls = step_cls
        self.rounds = RoundLog()
        self.dry = True             # nothing has been queued yet
        self.starved = False
        self.count = [0] * _NPHASES
        self.total_ns = [0] * _NPHASES
        self.buckets = [[0] * NBUCKETS for _ in LM_STEP_PHASES]
        # wall time of the loop, advanced once a pass by a clock read
        # of its own: what the phases' totals have to add up to
        self.loop_ns = 0
        self._ref = None            # the last program's output, unseen
        self._older = None          # an earlier one nothing consumed
        self._busy_t = 0            # the last read that knew it running
        self._seen_lo = 0           # the oldest step that may be unseen
        self._step_hi = -1          # the last step queued
        with _clocks_lock:
            _clocks.add(self)

    def bind(self, trace_cls, step_cls) -> None:
        self._trace_cls = trace_cls
        self._step_cls = step_cls

    def phase_counters(self) -> dict:
        return dict(zip(LM_STEP_PHASES, self.count))

    def phase_total_ns(self) -> dict:
        return dict(zip(LM_STEP_PHASES, self.total_ns))

    def switch(self, idx: int) -> int:
        """Enter phase ``idx`` (-1: none), crediting the time since the
        last switch to the phase that was open; returns that phase.
        Lock-free: preallocated per-phase lists, an int bit_length for
        the log2 bucket, at most one readiness test."""
        gen = _live[0]
        if not gen:
            return -1
        t = _mono_ns()
        cur = self.cur
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(None, None, None)
        if self._gen != gen:        # first switch, or off and on again:
            self._gen = gen         # phases and loop_ns start level
            self._loop_t = t
            self._ref = self._older = None
            self.starved = False
            cur = -1
        if cur >= 0:
            ns = t - self.t
            self.buckets[cur][_log2_bucket(ns)] += 1
            self.count[cur] += 1
            if ns > 0:
                self.total_ns[cur] += ns
                if self.dry or self.starved:
                    self._idle(cur, ns)
        ref = self._ref
        if ref is not None:
            if self._ready(ref):
                self._finished(t, t, cur)
                if not self.dry:
                    self.starved = True
            else:
                self._busy_t = t
        self.cur = idx
        self.t = t
        if idx >= 0 and self._trace_cls is not None:
            self._ann = ann = self._trace_cls(_TRACE_NAMES[idx])
            ann.__enter__()
        return cur

    def tick(self) -> None:
        """Advance ``loop_ns`` to now: once a pass, after the pass's
        first ``switch`` (so the phases never lead it at that point,
        and that switch's read is the pass's start), and once more
        before the loop blocks."""
        if self._gen != _live[0]:   # off, or on again and no switch yet
            return
        self._pass_t = self.t
        t = _mono_ns()
        self.loop_ns += t - self._loop_t
        self._loop_t = t

    def round_begin(self, step: int) -> None:
        """A pass that has work opens its ``lm_round``; the top of the
        next pass ends it."""
        if _live[0] and self._step_cls is not None:
            self._round = r = self._step_cls(ROUND_TRACE_NAME,
                                             step_num=step)
            r.__enter__()

    def round_end(self) -> None:
        r = self._round
        if r is not None:
            self._round = None
            r.__exit__(None, None, None)

    def close(self) -> None:
        """The loop is leaving (idle exit or crash): credit the open
        phase and bring ``loop_ns`` level with it; the thread's next
        incarnation starts level again."""
        self.round_end()
        self.switch(-1)
        self.tick()
        self._gen = -1

    # -- the step hooks: batcher thread only, ints into preallocated
    # lists, no lock; each entry-listed in tools/check/blocking.py ------

    @staticmethod
    def _ready(ref) -> bool:
        """Has the program that made ``ref`` finished?  (The one
        readiness test, by name: a test's fake device answers here.)"""
        try:
            return ref.is_ready()
        except Exception:           # a later program consumed it
            return False

    def _idle(self, cur: int, ns: int) -> None:
        """``ns`` of phase ``cur`` in which the device stood idle: to
        the dry table while nothing was queued, else the starved one."""
        log = self.rounds
        if self.dry:
            log.dry_by_phase[cur] += ns
            log.dry_sum += ns
        else:
            log.starved_by_phase[cur] += ns
            log.starved_sum += ns

    def _finished(self, t: int, seen: int, cur: int) -> None:
        """The last program queued (or the one a program just queued
        consumed) ended at or before ``t`` and after ``_busy_t``: that
        stretch of phase ``cur`` goes to the bracket, and every step
        queued so far is seen at ``seen`` (0: not known)."""
        self._ref = self._older = None
        log = self.rounds
        ns = t - self._busy_t
        if cur >= 0 and ns > 0 and not self.dry:
            log.bracket_by_phase[cur] += ns
        if seen:
            for o in range(self._seen_lo, self._step_hi + 1):
                i = o % ROUND_RING
                if log.ordinal[i] == o and not log.seen_ns[i]:
                    log.seen_ns[i] = seen
            self._seen_lo = self._step_hi + 1

    def _behind(self, t: int, ref, wet: bool = True) -> None:
        """A program was queued at ``t``: the one it followed is tested
        once more.  Where a program consumed (donated) its output, an
        earlier output still running says the device was busy; with
        none, whether it ran out is not known and the stretch goes to
        the bracket.  The idle stretch it ends is credited (a dry one
        only where ``wet``); ``ref`` is followed from here."""
        old = self._ref
        if old is not None:
            gone = _gone(old)
            if not self.dry:
                if gone:
                    older = self._older
                    if older is None or _gone(older) or self._ready(older):
                        self._finished(t, 0, self.cur)
                elif self._ready(old):
                    self._finished(t, _mono_ns(), self.cur)
            if not gone:
                self._older = old
        if self.starved or (wet and self.dry):
            self._wet(t)
        self._ref = ref
        self._busy_t = t

    def _wet(self, t: int) -> None:
        """A device program was queued at ``t`` with the device idle
        (dry or starved): the open phase's time so far was idle, the
        rest is not."""
        ns = t - self.t
        if self.cur >= 0 and ns > 0:
            self._idle(self.cur, ns)
        if self.starved:
            self.starved = False
            self.rounds.starved_n += 1
        self.dry = False

    def filling(self, programs: int, rows: int, ref=None) -> None:
        """Filling programs (a prefill, an insert, a span, a slice not
        on board) have just been queued, for ``rows`` context rows:
        counted into the NEXT step's record.  ``ref``: an output of
        the last of them."""
        if self._gen != _live[0]:
            return
        pend = self.rounds.pend
        pend[0] += programs
        pend[1] += rows
        self._behind(_mono_ns(), ref)

    def follow(self, ref) -> None:
        """A program that fills nothing (a slot's length) has just been
        queued: the probe follows its output.  It ends a starved
        device, not a dry one (dry counts the filling programs and the
        steps alone)."""
        if self._gen == _live[0]:
            self._behind(_mono_ns(), ref, wet=False)

    def joined(self) -> None:
        """A session was admitted into a slot."""
        if self._gen == _live[0]:
            self.rounds.pend[2] += 1

    def filled(self, tl) -> None:
        """The session's context needs no further filling program: the
        clock read that ended its admission (or its last slice's
        preparation) closes its ``admit`` stage."""
        if tl is not None and self._gen == _live[0]:
            tl.fill_ns = self.t

    def stamp(self) -> int:
        """The clock, for ``queued``; 0 with the gate off."""
        return _mono_ns() if self._gen == _live[0] else 0

    def queued(self, t: int, step: int, rows: int, ahead: bool,
               ride_rows: int, ref=None) -> int:
        """Step ``step`` was queued for the device at ``t``
        (``stamp()``), ``ref`` its tokens: open its record.  Returns
        the record's ordinal for ``landed``, -1 where nothing was
        written."""
        if not t:
            return -1
        self._behind(t, ref)
        log = self.rounds
        i = step % ROUND_RING
        pend = log.pend
        log.ordinal[i] = step
        log.pass_ns[i] = self._pass_t
        log.dispatch_ns[i] = t
        log.seen_ns[i] = log.wait_ns[i] = log.land_ns[i] = log.done_ns[i] = 0
        log.pages[i] = log.touched[i] = log.gap_ns[i] = 0
        log.rows[i] = rows
        log.ahead[i] = int(ahead)
        log.ride_rows[i] = ride_rows
        log.fill_programs[i], log.fill_rows[i], log.joins[i] = pend
        log.dry_ns[i] = log.dry_sum - log.dry_mark
        log.dry_mark = log.dry_sum
        log.starved_ns[i] = log.starved_sum - log.starved_mark
        log.starved_mark = log.starved_sum
        log.cls[i] = RC_RESTART if log.lull else RC_FILL if pend[0] \
            else RC_RIDE if ride_rows else RC_PLAIN
        log.lull = False
        pend[0] = pend[1] = pend[2] = 0
        self._step_hi = step
        if self._seen_lo < step - 1:    # only the step before can be
            self._seen_lo = step - 1    # unseen and in flight
        return step

    def landed(self, t_wait: int, ordinal: int, behind: bool,
               pages: int, touched: int) -> None:
        """The step's tokens are on the host: called in the phase that
        follows its wait, whose start (``t``) is the span's end;
        ``t_wait`` is where the wait began.  ``behind``: a later step
        is already queued.  Closes the record, counts it under its
        class (and as late where it was seen finished before its wait
        began), and finds the device dry where nothing is queued
        behind it."""
        if ordinal < 0 or self._gen != _live[0]:
            return
        log = self.rounds
        i = ordinal % ROUND_RING
        if log.ordinal[i] != ordinal:
            return
        t = self.t
        wait = t - t_wait
        gap = t - log.prev_land if log.prev_ord == ordinal - 1 else 0
        log.prev_land, log.prev_ord = t, ordinal
        seen = log.seen_ns[i]
        if not seen:
            log.seen_ns[i] = t
        if self._seen_lo <= ordinal:
            self._seen_lo = ordinal + 1
        log.wait_ns[i] = wait
        log.land_ns[i] = t
        log.pages[i] = pages
        log.touched[i] = touched
        log.gap_ns[i] = gap
        cls = log.cls[i]
        log.cls_n[cls] += 1
        log.cls_gap_ns[cls] += gap
        if cls == RC_FILL:
            log.cls_rows[cls] += log.fill_rows[i]
            log.cls_programs[cls] += log.fill_programs[i]
        elif cls == RC_RIDE:
            log.cls_rows[cls] += log.ride_rows[i]
            log.cls_programs[cls] += 1
        if seen and seen <= t_wait:
            log.late[0] += 1
            log.late[1] += gap
        if cls != RC_RESTART and gap > log.max_gap[0]:
            log.max_gap[0], log.max_gap[1] = gap, ordinal
        log.landed_idx = i
        if not behind and not log.pend[0]:
            # a starved spell that ends in a lull is no step's to carry
            self.dry = log.lull = True
            self.starved = False
            self._ref = self._older = None
            log.starved_mark = log.starved_sum

    def delivered(self) -> None:
        """The landed step's tokens are written and its sessions
        evicted."""
        log = self.rounds
        i = log.landed_idx
        if i >= 0:
            log.landed_idx = -1
            if self._gen == _live[0]:
                log.done_ns[i] = _mono_ns()

    def round_now(self) -> int:
        """The ordinal of the step being delivered, -1 outside one."""
        log = self.rounds
        i = log.landed_idx
        return log.ordinal[i] if i >= 0 else -1


def _gone(ref) -> bool:
    """A later program consumed (donated) ``ref``: it cannot be
    tested."""
    try:
        return ref.is_deleted()
    except Exception:
        return True


def _live_clocks() -> list:
    with _clocks_lock:
        return list(_clocks)


def bucket_label(i: int, nbuckets: int = NBUCKETS) -> str:
    """Exclusive upper-bound label for log2 bucket i (the engine Hist
    convention — deliberately ``bin``, not Prometheus's cumulative
    ``le``; see transport.native_bridge.bucket_label)."""
    return "+Inf" if i >= nbuckets - 1 else str(1 << i)


def _summed(col: str) -> list:
    out = [0] * _NPHASES
    for c in _live_clocks():
        for i, n in enumerate(getattr(c, col)):
            out[i] += n
    return out


def phase_counters() -> dict:
    """Samples by phase, summed over every live batcher's clock."""
    return dict(zip(LM_STEP_PHASES, _summed("count")))


def phase_total_ns() -> dict:
    return dict(zip(LM_STEP_PHASES, _summed("total_ns")))


def _phase_hists() -> list:
    out = [[0] * NBUCKETS for _ in LM_STEP_PHASES]
    for c in _live_clocks():
        for i, hist in enumerate(c.buckets):
            row = out[i]
            for b, n in enumerate(hist):
                row[b] += n
    return out


def phase_histogram(name: str) -> list:
    return _phase_hists()[phase_index(name)]


def loop_ns() -> int:
    return sum(c.loop_ns for c in _live_clocks())


# ---------------------------------------------------------------------------
# SLO attainment: closed verdicts judged at session close
# ---------------------------------------------------------------------------

# CLOSED enum: one verdict per finished session, judged against the
# session's tier targets (TierRegistry.slo_of).  No "unknown" bucket —
# an unregistered verdict fails the assert at the first count.
LM_SLO_VERDICTS = (
    "slo_ok",            # every configured target met
    "slo_ttft_miss",     # first token later than the tier's TTFT target
    "slo_itl_miss",      # an inter-token gap beyond the tier's ITL target
    "slo_untargeted",    # the session's tier configures no targets
)

_slo: dict = {}          # (tier, verdict) -> count, preseeded lazily


def _slo_table() -> dict:
    if not _slo:
        from .lm_service import SLO_TIERS
        for t in SLO_TIERS:
            for v in LM_SLO_VERDICTS:
                _slo[(t, v)] = 0
    return _slo


def count_slo(tier: str, verdict: str) -> None:
    tab = _slo_table()
    assert (tier, verdict) in tab, \
        f"unregistered SLO verdict: {tier}/{verdict}"
    tab[(tier, verdict)] += 1


def slo_counters() -> dict:
    return dict(_slo_table())


# ---------------------------------------------------------------------------
# Session timelines: bounded ring + per-tier latency histograms
# ---------------------------------------------------------------------------

_tl_seq = itertools.count(1)


class SessionTimeline:
    """One decode session's observable life, written by the batcher
    thread (plus the join-side open stamp), finalized into the ring at
    close.  Slotted: the per-token path touches preallocated fields
    only."""

    __slots__ = ("seq", "tier", "tenant", "prompt_len", "max_new",
                 "join_ns", "admit_ns", "fill_ns", "first_ns", "last_ns",
                 "tokens", "itl_max_ns", "first_round", "worst_round",
                 "prefix", "pages_peak", "spills", "resumes", "preempts",
                 "close_reason", "verdict")

    def __init__(self, tier: str, tenant: str, prompt_len: int,
                 max_new: int, source: str):
        self.seq = next(_tl_seq)
        self.tier = tier
        self.tenant = tenant
        self.prompt_len = prompt_len
        self.max_new = max_new
        self.join_ns = _mono_ns()
        self.admit_ns = 0             # the batcher took it from _pending
        self.fill_ns = 0              # its admission's work was done
        self.first_ns = 0
        self.last_ns = 0
        self.tokens = 0
        self.itl_max_ns = 0
        # the ordinals (RoundLog) of the step that made its first token
        # and of the one that ended its widest gap
        self.first_round = -1
        self.worst_round = -1
        self.prefix = source          # fresh|imported, refined at admit
        self.pages_peak = 0
        self.spills = 0
        self.resumes = 0
        self.preempts = 0
        self.close_reason = None
        self.verdict = None

    def ttft_ms(self) -> Optional[float]:
        if not self.first_ns:
            return None
        return (self.first_ns - self.join_ns) / 1e6

    def queue_ms(self) -> Optional[float]:
        if not self.admit_ns:
            return None
        return (self.admit_ns - self.join_ns) / 1e6

    def describe(self) -> dict:
        return {"seq": self.seq, "tier": self.tier,
                "tenant": self.tenant, "prompt_len": self.prompt_len,
                "max_new": self.max_new, "tokens": self.tokens,
                "queue_ms": self.queue_ms(),
                "ttft_ms": self.ttft_ms(),
                "itl_max_ms": self.itl_max_ns / 1e6,
                "first_round": self.first_round,
                "worst_round": self.worst_round,
                "prefix": self.prefix, "pages_peak": self.pages_peak,
                "spills": self.spills, "resumes": self.resumes,
                "preempts": self.preempts,
                "close_reason": self.close_reason,
                "verdict": self.verdict}


# live registry (open → close) + the bounded finished-session ring.
# deque(maxlen) eviction is lock-free; the live dict is mutated by the
# join thread (open) and the batcher thread (close) — both single
# bytecode dict ops, GIL-atomic like the admission counters.
_live_sessions: dict = {}
_ring_max = int(get_flag("lm_timeline_ring", 256))
_ring: deque = deque(maxlen=_ring_max)

# per-tier latency histograms (batcher-thread writes): queue wait
# observed when the batcher takes the session from its pending queue,
# TTFT at the first emitted token, ITL per subsequent token
_tier_queue: dict = {}
_tier_ttft: dict = {}
_tier_itl: dict = {}
# join -> taken from the pending queue, summed over the sessions taken
_queue_wait_ns = [0]
_admitted = [0]


def open_timeline(tier: str, tenant, prompt_len: int, max_new: int,
                  source: str) -> Optional[SessionTimeline]:
    """Called at join (NOT the step loop): allocates the session's
    record and preseeds its tier's histograms."""
    if not _live[0]:
        return None
    from .lm_service import SLO_TIERS
    assert tier in SLO_TIERS, f"unregistered SLO tier: {tier}"
    if tier not in _tier_ttft:
        _tier_queue[tier] = [0] * NBUCKETS
        _tier_ttft[tier] = [0] * NBUCKETS
        _tier_itl[tier] = [0] * NBUCKETS
    if isinstance(tenant, (bytes, bytearray, memoryview)):
        tenant = bytes(tenant).decode("utf-8", "replace")
    tl = SessionTimeline(tier, str(tenant or "-"), int(prompt_len),
                         int(max_new), source)
    _live_sessions[tl.seq] = tl
    return tl


def on_admit(sessions) -> None:
    """The batcher took these sessions from its pending queue (batcher
    thread only, before it admits them): ONE monotonic read for them
    all closes each one's queue wait.  Lock-free, like ``on_emit``."""
    if not _live[0] or not sessions:
        return
    now = _mono_ns()
    for sess in sessions:
        tl = sess.tl
        if tl is None:
            continue
        tl.admit_ns = now
        d = now - tl.join_ns
        _tier_queue[tl.tier][_log2_bucket(d)] += 1
        _queue_wait_ns[0] += d if d > 0 else 0
        _admitted[0] += 1
        if sess.span is not None:
            sess.span.annotate("lm_admit")


def queue_counters() -> dict:
    return {"wait_ns": _queue_wait_ns[0], "admitted": _admitted[0]}


def round_note(note: str, ordinal: int) -> str:
    """A session span's annotation with the step it belongs to."""
    return note if ordinal < 0 else f"{note} round={ordinal}"


def on_emit(pairs, log: Optional[RoundLog] = None) -> None:
    """Per-step token timing (batcher thread only): ONE monotonic read
    for the whole step, then plain list increments per token — the
    first token closes the session's TTFT, later ones feed the tier's
    ITL histogram.  ``log`` is the batcher's, with the step being
    delivered landed: the first token's stages are summed into it and
    the session notes which step made its first token and which ended
    its widest gap.  Lock-free, allocation-free."""
    if not _live[0] or not pairs:
        return
    now = _mono_ns()
    i = log.landed_idx if log is not None else -1
    ordinal, land = (log.ordinal[i], log.land_ns[i]) if i >= 0 else (-1, 0)
    for sess, _tok in pairs:
        tl = sess.tl
        if tl is None:
            continue
        if tl.tokens == 0:
            tl.first_ns = now
            tl.first_round = ordinal
            d = now - tl.join_ns
            _tier_ttft[tl.tier][_log2_bucket(d)] += 1
            if land and tl.admit_ns and tl.fill_ns:
                # join -> taken -> admission done -> step landed -> now
                first = log.first
                first[0] += 1
                first[1] += tl.admit_ns - tl.join_ns
                first[2] += tl.fill_ns - tl.admit_ns
                first[3] += land - tl.fill_ns
                first[4] += now - land
                first[5] += d
            if sess.span is not None:
                sess.span.annotate(round_note("lm_first_token", ordinal))
        else:
            d = now - tl.last_ns
            if d > tl.itl_max_ns:
                tl.itl_max_ns = d
                tl.worst_round = ordinal
            _tier_itl[tl.tier][_log2_bucket(d)] += 1
        tl.last_ns = now
        tl.tokens += 1


def close_timeline(tl: Optional[SessionTimeline], reason: str,
                   ttft_target_ms=None, itl_target_ms=None) -> None:
    """Finalize a session record (batcher thread): judge the SLO
    verdict against the tier's targets, count it, move the record from
    the live table into the bounded ring."""
    if tl is None:
        return
    _live_sessions.pop(tl.seq, None)
    tl.close_reason = reason or "finished"
    if ttft_target_ms is None and itl_target_ms is None:
        v = "slo_untargeted"
    else:
        ttft = tl.ttft_ms()
        if ttft_target_ms is not None \
                and (ttft is None or ttft > ttft_target_ms):
            v = "slo_ttft_miss"
        elif itl_target_ms is not None \
                and tl.itl_max_ns / 1e6 > itl_target_ms:
            v = "slo_itl_miss"
        else:
            v = "slo_ok"
    tl.verdict = v
    count_slo(tl.tier, v)
    _ring.append(tl)


def live_sessions() -> list:
    """Snapshot of in-flight sessions (the /lm live table)."""
    return [tl.describe() for tl in list(_live_sessions.values())]


def timeline_records(limit: int = 0) -> list:
    recs = list(_ring)
    if limit:
        recs = recs[-limit:]
    return [tl.describe() for tl in recs]


def ring_len() -> int:
    return len(_ring)


def ring_maxlen() -> int:
    return _ring.maxlen or 0


# ---------------------------------------------------------------------------
# Percentiles from the log2 histograms
# ---------------------------------------------------------------------------

_QUANTILES = (("p50", 0.50), ("p95", 0.95), ("p99", 0.99))


def _hist_quantile_ms(buckets, q: float) -> float:
    """Approximate quantile from a log2 ns histogram: the upper bound
    of the bucket where the cumulative count crosses q (conservative —
    never under-reports a latency)."""
    n = 0
    for c in buckets:
        n += c
    if n == 0:
        return 0.0
    target = q * n
    acc = 0.0
    for i, c in enumerate(buckets):
        acc += c
        if acc >= target:
            return 0.0 if i == 0 else (1 << i) / 1e6
    return (1 << (len(buckets) - 1)) / 1e6


def _quantile_rows(by_tier: dict) -> dict:
    out = {}
    for tier, h in by_tier.items():
        for name, q in _QUANTILES:
            out[(tier, name)] = round(_hist_quantile_ms(h, q), 3)
    return out


def _queue_rows() -> dict:
    return _quantile_rows(_tier_queue)


def _ttft_rows() -> dict:
    return _quantile_rows(_tier_ttft)


def _itl_rows() -> dict:
    return _quantile_rows(_tier_itl)


# ---------------------------------------------------------------------------
# Snapshot cache (the _TelemetryCache pattern): one build per interval
# ---------------------------------------------------------------------------

class LmTelemetryCache:
    """Short-TTL cache over the full serving-plane snapshot.  ``get()``
    refreshes at most once per TTL; ``window()`` returns
    ``(prev, cur, dt)`` under ONE lock hold so windowed ratios never
    pair a snapshot with the wrong interval.  ``builds`` counts actual
    snapshot constructions — the one-snapshot-per-interval test pin."""

    def __init__(self, ttl_s: float = 0.25, build=None):
        self._ttl = ttl_s
        if build is not None:       # a snapshot of the caller's own
            self._build = build
        self._lock = threading.Lock()
        self._snap = None
        self._t = 0.0
        self._prev = None
        self._prev_t = 0.0
        self.builds = 0

    def _build(self) -> dict:
        from .lm_service import sched_counters
        try:
            from ..kv.pages import prefix_event_counters
            prefix = prefix_event_counters()
        except Exception:
            prefix = {}
        return {
            "phases": phase_counters(),
            "phase_ns": phase_total_ns(),
            "loop_ns": loop_ns(),
            "queue": queue_counters(),
            "phase_hists": dict(zip(LM_STEP_PHASES, _phase_hists())),
            "sched": sched_counters(),
            "prefix_events": prefix,
            "slo": slo_counters(),
            "queue_ms": _queue_rows(),
            "ttft_ms": _ttft_rows(),
            "itl_ms": _itl_rows(),
            "live": live_sessions(),
            "ring": timeline_records(),
        }

    def _refresh_locked(self) -> None:
        now = _mono_s()
        if self._snap is None or now - self._t >= self._ttl:
            snap = self._build()
            self.builds += 1
            self._prev, self._prev_t = self._snap, self._t
            self._snap, self._t = snap, now

    def get(self) -> dict:
        with self._lock:
            self._refresh_locked()
            return self._snap

    def window(self):
        with self._lock:
            self._refresh_locked()
            return (self._prev, self._snap,
                    max(self._t - self._prev_t, 1e-9))


_cache: Optional[LmTelemetryCache] = None
_cache_lock = threading.Lock()


def telemetry_cache() -> LmTelemetryCache:
    global _cache
    with _cache_lock:
        if _cache is None:
            _cache = LmTelemetryCache()
        return _cache


def _delta(cur: dict, prev, key: str) -> int:
    c = cur.get(key, 0)
    return c - prev.get(key, 0) if prev is not None else c


def windowed_prefix_hit_ratio(cache=None) -> float:
    """(hit + partial) / lookups over the LAST snapshot window."""
    prev, cur, _dt = (cache or telemetry_cache()).window()
    p = prev["prefix_events"] if prev is not None else None
    hit = _delta(cur["prefix_events"], p, "prefix_hit")
    part = _delta(cur["prefix_events"], p, "prefix_partial_hit")
    miss = _delta(cur["prefix_events"], p, "prefix_miss")
    denom = hit + part + miss
    return (hit + part) / denom if denom > 0 else 0.0


def windowed_slo_deltas(cache=None) -> dict:
    """Per-tier SLO attainment DELTAS over the last snapshot window,
    as ``{tier: {verdict: count}}`` — the fleet load report's answer
    to 'how is this node attaining NOW' (lifetime counters drift
    toward their historical mean and stop moving under incidents)."""
    prev, cur, _dt = (cache or telemetry_cache()).window()
    p = prev["slo"] if prev is not None else None
    out: dict = {}
    for (tier, verdict), n in cur["slo"].items():
        d = n - p.get((tier, verdict), 0) if p is not None else n
        if d:
            out.setdefault(tier, {})[verdict] = d
    return out


def lifetime_prefix_hit_ratio() -> float:
    try:
        from ..kv.pages import prefix_event_counters
        c = prefix_event_counters()
    except Exception:
        return 0.0
    denom = c.get("prefix_hit", 0) + c.get("prefix_partial_hit", 0) \
        + c.get("prefix_miss", 0)
    return (c.get("prefix_hit", 0) + c.get("prefix_partial_hit", 0)) \
        / denom if denom > 0 else 0.0


# ---------------------------------------------------------------------------
# /vars + /metrics exposure (PassiveDimension rows share the module's
# plain counters; the portal page additionally reads the cache)
# ---------------------------------------------------------------------------

_phase_var = PassiveDimension(("phase",), phase_counters,
                              name="lm_step_phase_total")


def _phase_bucket_rows() -> dict:
    out = {}
    for p, hist in zip(LM_STEP_PHASES, _phase_hists()):
        for b, c in enumerate(hist):
            if c:
                out[(p, bucket_label(b))] = c
    return out


_phase_hist_var = PassiveDimension(("phase", "bin"), _phase_bucket_rows,
                                   name="lm_step_phase_ns")
_slo_var = PassiveDimension(("tier", "verdict"), slo_counters,
                            name="lm_slo_attained_total")
_queue_var = PassiveDimension(("tier", "quantile"), _queue_rows,
                              name="lm_queue_ms")
_ttft_var = PassiveDimension(("tier", "quantile"), _ttft_rows,
                             name="lm_ttft_ms")
_itl_var = PassiveDimension(("tier", "quantile"), _itl_rows,
                            name="lm_itl_ms")
_windowed_var = PassiveDimension(
    ("ratio",),
    lambda: {"prefix_cache_hit_ratio":
             round(windowed_prefix_hit_ratio(), 4)},
    name="lm_windowed")

_LM_VARS = (
    (_phase_var, "lm_step_phase_total"),
    (_phase_hist_var, "lm_step_phase_ns"),
    (_slo_var, "lm_slo_attained_total"),
    (_queue_var, "lm_queue_ms"),
    (_ttft_var, "lm_ttft_ms"),
    (_itl_var, "lm_itl_ms"),
    (_windowed_var, "lm_windowed"),
)


def expose_lm_variables() -> None:
    """(Re-)expose the serving-plane families — the
    ``expose_default_variables`` discipline: a test registry reset
    must not silently drop the /metrics rows for the rest of the
    process lifetime (``Variable.expose`` is a no-op while the name
    is still registered)."""
    for var, name in _LM_VARS:
        var.expose(name)


def _reset_for_tests(ring: Optional[int] = None) -> None:
    global _ring, _cache
    for c in _live_clocks():
        for i in range(_NPHASES):
            c.count[i] = 0
            c.total_ns[i] = 0
            for b in range(NBUCKETS):
                c.buckets[i][b] = 0
        c.loop_ns = 0
    _queue_wait_ns[0] = 0
    _admitted[0] = 0
    _slo_table()
    for k in _slo:
        _slo[k] = 0
    _tier_queue.clear()
    _tier_ttft.clear()
    _tier_itl.clear()
    _live_sessions.clear()
    _ring = deque(maxlen=int(ring) if ring else _ring_max)
    _cache = None
    expose_lm_variables()
