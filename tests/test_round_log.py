"""The decode step as a span (ISSUE 35): each step the batcher queues
keeps a record of what was queued in front of it, when it landed and
whether the device had run dry; counters by that class, the dry time
by phase and the stages of a first token are read from them.

The device here is a fake that runs what the batcher queues in order,
each program for a scripted time, and hands a step's tokens back when
it is done (``_read_tokens``, as ``test_lm_observability`` puts a slow
device there): the gaps the log reads are then the ones it dictated.
"""

import threading
import time
from collections import deque

import jax
import numpy as np
import pytest
from test_lookahead import (_finish, _join, _prompt, _quiet, _Stream,
                            _wait)

from brpc_tpu.butil.flags import set_flag
from brpc_tpu.models import lm_telemetry as lmt
from brpc_tpu.models.lm_service import ContinuousBatcher
from brpc_tpu.models.transformer_lm import LMConfig, init_params

PAGE = 16
BASE = 2 * PAGE             # the shared document: two full pages
STEP_S, FILL_S, RIDE_S = 0.030, 0.025, 0.020
# the host's own work on a busy machine: over a class's mean, and over
# the one step of a class that has one
SLACK_S, SLACK_ONE_S = 0.012, 0.028


@pytest.fixture(scope="module")
def model():
    cfg = LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=256,
                   remat=False)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture()
def level():
    """The phase tables are the process's: start level, with no
    earlier batcher still lingering."""
    _wait(lambda: not any(t.name == "lm-decode-batcher"
                          for t in threading.enumerate()),
          "an earlier test's batcher never lingered out", 30.0)
    lmt._reset_for_tests()


class FakeDevice:
    """Programs run in the order they were queued: a step for
    ``STEP_S`` (``RIDE_S`` more with a slice on board), a filling
    program for ``FILL_S``.  It listens where the batcher says it has
    queued one, and ``read`` returns a step's tokens when it is done."""

    def __init__(self, monkeypatch, step_s=STEP_S):
        self.step_s = step_s
        self.free_at = 0.0          # when everything queued is done
        self.done = deque()         # of the steps not yet read
        filling, queued = lmt.PhaseClock.filling, lmt.PhaseClock.queued
        real = ContinuousBatcher._read_tokens

        def on_filling(clock, programs, rows):
            self.run(FILL_S * programs)
            filling(clock, programs, rows)

        def on_queued(clock, t, step, rows, ahead, ride_rows):
            self.done.append(self.run(
                self.step_s + (RIDE_S if ride_rows else 0.0)))
            return queued(clock, t, step, rows, ahead, ride_rows)

        def read(toks):
            wait = self.done.popleft() - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            return real(toks)

        monkeypatch.setattr(lmt.PhaseClock, "filling", on_filling)
        monkeypatch.setattr(lmt.PhaseClock, "queued", on_queued)
        monkeypatch.setattr(ContinuousBatcher, "_read_tokens",
                            staticmethod(read))

    def run(self, seconds):
        self.free_at = max(self.free_at, time.monotonic()) + seconds
        return self.free_at


class Watching(_Stream):
    """Notes the dry table as each of its tokens is written, and lets
    a test wait for its n-th token."""

    def __init__(self, bat, **kw):
        super().__init__(**kw)
        self.bat, self.dry = bat, []

    def write(self, data):
        self.dry.append(self.bat.kv_stats()["rounds"]["dry_ns"])
        return super().write(data)

    def wait_for(self, n):
        _wait(lambda: len(self.tokens) >= n, f"token {n} never came")


def _warm(bat):
    """Compile every program the script uses; the loop is left in its
    idle wait, the tables level."""
    first = np.concatenate([_prompt(5, BASE), _prompt(6, 9)])
    _finish(_join(bat, first, 2))
    _finish(_join(bat, np.concatenate([_prompt(5, BASE), _prompt(7, 41)]),
                  2))
    _quiet(bat)
    lmt._reset_for_tests()


def test_scripted_run_lands_each_step_in_its_class(model, level,
                                                   monkeypatch):
    """A lull, a join by prefill, a hit whose slice rides, plain steps:
    each step is counted under what stood in front of it, with the gap
    the device dictated; the classes' ``n`` add up to the steps; the
    device is dry for the lull and not while a step is always ahead;
    the first tokens' stages add up to their sessions' own ``ttft``."""
    cfg, params = model
    bat = ContinuousBatcher(cfg, params, slots=3, page=PAGE,
                            idle_linger_s=1.0)
    _warm(bat)                          # leaves BASE in the prefix cache
    FakeDevice(monkeypatch)
    kv0 = bat.kv_stats()
    steps0, r0 = kv0["steps"], kv0["rounds"]
    lull_from = time.monotonic()
    time.sleep(0.3)                     # the lull: no request is there
    c = Watching(bat)
    bat.join(c, _prompt(11, 50), 60)    # a miss: prefill + insert
    joined = time.monotonic()
    c.wait_for(10)
    b = _join(bat, np.concatenate([_prompt(5, BASE), _prompt(8, 41)]), 3)
    c.wait_for(25)                      # 40 rows to catch up: they ride
    d = _join(bat, _prompt(12, 60), 3)  # a miss beside a running stream
    _finish(b, c, d)
    _wait(lambda: bat._thread is None, "the batcher never lingered out")
    kv = bat.kv_stats()
    r, steps = kv["rounds"], kv["steps"] - steps0

    def grew(cls, key):
        return r[cls][key] - r0[cls][key]

    n = {cls: grew(cls, "n") for cls in lmt.LM_ROUND_CLASSES}
    assert sum(n.values()) == steps == 60
    assert n == {"restart": 1, "fill": 1, "ride": 1, "plain": 57}
    assert (grew("fill", "programs"), grew("fill", "rows")) == (2, 59)
    assert (grew("ride", "programs"), grew("ride", "rows")) == (1, 40)
    # the gap of each class is what the device took for it
    for cls, want, slack in (
            ("plain", STEP_S, SLACK_S),
            ("ride", STEP_S + RIDE_S, SLACK_ONE_S),
            ("fill", STEP_S + 2 * FILL_S, SLACK_ONE_S)):
        mean = grew(cls, "gap_ns") / n[cls] / 1e9
        assert want - 0.002 <= mean <= want + slack, (cls, mean)
    assert r["max_gap_ns"] == max(x["gap_ns"] for x in bat.round_log()
                                  if x["cls"] != "restart")
    assert r["late"]["n"] - r0["late"]["n"] <= 5    # the device is slow
    # dry: the lull, and nothing from c's first token to its ninth
    # (as c's first token is written; the linger at the end is dry too)
    dry = {p: c.dry[0][p] - r0["dry_ns"][p] for p in r["dry_ns"]}
    assert set(dry) == set(lmt.LM_STEP_PHASES)
    assert c.dry[8] == c.dry[0]
    lull = joined - lull_from
    assert (lull - 0.05) * 1e9 <= dry["idle_wait"] <= (lull + 0.05) * 1e9
    # ... the admission of c, with the device dry until its prefill was
    # queued, is the host standing between a request and the chip
    assert dry["sched"] > 0 and dry["page_alloc"] > 0
    assert 0 < dry["prefill_dispatch"] < kv["phase_ns"]["prefill_dispatch"]
    # (the slot's row is made up under insert_dispatch, before the
    # prefill; the insert itself is queued behind it)
    assert dry["insert_dispatch"] < kv["phase_ns"]["insert_dispatch"] / 2
    assert dry["step_dispatch"] == dry["device_wait"] == 0
    assert sum(dry.values()) <= (lull + 0.25) * 1e9
    dry = {p: r["dry_ns"][p] - r0["dry_ns"][p] for p in r["dry_ns"]}
    assert sum(dry.values()) <= kv["loop_ns"]
    for p, ns in dry.items():
        assert ns <= kv["phase_ns"][p], p
    # the records: c's first step restarted the device, b's slice rode
    # the step that made c's token 11 or 12, d's prefill stood in front
    # of one of c's
    log = {x["ordinal"]: x for x in bat.round_log(since=steps0)}
    assert sorted(log) == list(range(steps0, steps0 + steps))
    by_cls = {cls: [x for x in log.values() if x["cls"] == cls]
              for cls in lmt.LM_ROUND_CLASSES}
    (restart,), (fill,), (ride,) = (by_cls[k] for k in
                                    ("restart", "fill", "ride"))
    assert restart["ordinal"] == steps0 and restart["ahead"] == 0
    assert (restart["fill_programs"], restart["fill_rows"],
            restart["joins"]) == (2, 49, 1)
    assert restart["dry_ns"] >= (lull - 0.05) * 1e9
    assert (ride["ride_rows"], ride["joins"], ride["rows"]) == (40, 1, 2)
    assert (fill["fill_rows"], fill["joins"], fill["ahead"]) == (59, 1, 1)
    for x in log.values():
        assert x["pass_ns"] < x["dispatch_ns"] < x["land_ns"] <= x["done_ns"]
        assert 0 <= x["wait_ns"] <= x["land_ns"] - x["dispatch_ns"] + 1e6
        assert x["pages"] > 0 and x["touched"] == 0
    assert all(x["dry_ns"] == 0 for x in by_cls["plain"])
    # the sessions: which step made the first token, which the widest gap
    recs = {x["max_new"]: x for x in lmt.timeline_records()}
    assert recs[60]["first_round"] == steps0
    assert recs[60]["worst_round"] in log
    assert log[recs[3]["first_round"]]["cls"] in ("ride", "fill")
    # the stages of a first token partition join -> first token
    first = {k: v - kv0["first"][k] for k, v in kv["first"].items()}
    ring = list(lmt._ring)
    assert first["n"] == len(ring) == 3
    assert first["ttft_ns"] == sum(tl.first_ns - tl.join_ns for tl in ring)
    assert first["queue_ns"] + first["admit_ns"] + first["device_ns"] \
        + first["emit_ns"] == first["ttft_ns"]
    assert first["queue_ns"] == kv["queue"]["wait_ns"]
    assert min(first.values()) > 0
    # c's first token waited for its prefill, its insert and its step
    assert first["device_ns"] >= (3 * (STEP_S + 2 * FILL_S)
                                  - 2 * FILL_S - STEP_S) * 1e9 * 0.5


def test_a_step_the_device_finished_before_the_host_asked_is_late(
        model, level, monkeypatch):
    """A host that takes 30 ms to write a step's tokens beside a device
    that needs 2 ms a step: from the second step on each step has
    landed before the host asks for it, its wait is under
    ``LATE_WAIT_NS``, and the gap it ended is the host's."""
    cfg, params = model
    bat = ContinuousBatcher(cfg, params, slots=2, page=PAGE,
                            idle_linger_s=0.2)
    _warm(bat)
    FakeDevice(monkeypatch, step_s=0.002)

    class Slow(_Stream):
        def write(self, data):
            time.sleep(0.030)
            return super().write(data)

    r0 = bat.kv_stats()["rounds"]
    st = Slow()
    bat.join(st, _prompt(21, 10), 12)
    _finish(st)
    _wait(lambda: bat._thread is None, "the batcher never lingered out")
    r = bat.kv_stats()["rounds"]
    late = r["late"]["n"] - r0["late"]["n"]
    assert late >= 9                            # of 12: not the first
    assert r["late"]["gap_ns"] - r0["late"]["gap_ns"] >= late * 0.029e9
    waits = [x["wait_ns"] for x in bat.round_log()[-10:]]
    assert sorted(waits)[len(waits) // 2] < lmt.LATE_WAIT_NS
    # a late host is no dry device: the next step was queued
    assert all(x["dry_ns"] == 0 for x in bat.round_log()[-10:])


# -- the clock alone ----------------------------------------------------------

def _one_step(clock, step, behind=True, ride_rows=0, fills=()):
    """One pass of the loop's shape around the clock: top, the fills,
    dispatch, land, deliver."""
    clock.switch(lmt.PH_SCHED)
    clock.tick()
    for programs, rows in fills:
        clock.switch(lmt.PH_PREFILL_DISPATCH)
        clock.filling(programs, rows)
    clock.switch(lmt.PH_STEP_DISPATCH)
    ordinal = clock.queued(clock.stamp(), step, 1, step > 0, ride_rows)
    clock.switch(lmt.PH_DEVICE_WAIT)
    t_wait = clock.t
    clock.switch(lmt.PH_TOKEN_WALK)
    clock.landed(t_wait, ordinal, behind, 1, 0)
    clock.switch(lmt.PH_STREAM_EMIT)
    clock.delivered()
    return ordinal


@pytest.mark.parametrize("before,kw,want", [
    ("lull", {}, "restart"),
    ("lull", {"fills": [(2, 100)], "ride_rows": 7}, "restart"),
    ("step", {"fills": [(2, 100)], "ride_rows": 7}, "fill"),
    ("step", {"fills": [(1, 64), (1, 3)]}, "fill"),
    ("step", {"ride_rows": 7}, "ride"),
    ("step", {}, "plain"),
])
def test_each_step_falls_in_one_class(level, before, kw, want):
    """``restart`` before ``fill`` before ``ride`` before ``plain``;
    ``rows`` and ``programs`` are the class's own."""
    clock = lmt.PhaseClock()
    _one_step(clock, 0, behind=before == "step")
    r0 = clock.rounds.counters()
    _one_step(clock, 1, **kw)
    r = clock.rounds.counters()
    grew = {cls: r[cls]["n"] - r0[cls]["n"] for cls in lmt.LM_ROUND_CLASSES}
    assert grew == {cls: int(cls == want) for cls in lmt.LM_ROUND_CLASSES}
    rec = clock.rounds.records(since=1)[0]
    assert rec["cls"] == want
    assert rec["fill_programs"] == sum(p for p, _ in kw.get("fills", ()))
    assert rec["fill_rows"] == sum(n for _, n in kw.get("fills", ()))
    if want == "fill":
        assert (r["fill"]["programs"], r["fill"]["rows"]) \
            == (rec["fill_programs"], rec["fill_rows"])
    if want == "ride":
        assert (r["ride"]["programs"], r["ride"]["rows"]) == (1, 7)
    assert clock.dry is False and clock.rounds.lull is False


def test_the_ring_wraps(level):
    clock = lmt.PhaseClock()
    n = lmt.ROUND_RING + 10
    for step in range(n):
        assert _one_step(clock, step) == step
    recs = clock.rounds.records()
    assert [x["ordinal"] for x in recs] == list(range(10, n))
    assert [x["ordinal"] for x in clock.rounds.records(since=n - 3)] \
        == [n - 3, n - 2, n - 1]
    c = clock.rounds.counters()
    assert sum(c[cls]["n"] for cls in lmt.LM_ROUND_CLASSES) == n
    assert c["restart"]["n"] == 1 and c["plain"]["n"] == n - 1
    # a record that was overwritten before it landed is left alone
    stale = clock.queued(clock.stamp(), n, 1, True, 0)
    clock.queued(clock.stamp(), n + lmt.ROUND_RING, 1, True, 0)
    clock.switch(lmt.PH_DEVICE_WAIT)
    clock.switch(lmt.PH_TOKEN_WALK)
    clock.landed(clock.t, stale, True, 1, 0)
    assert sum(clock.rounds.counters()[cls]["n"]
               for cls in lmt.LM_ROUND_CLASSES) == n


def test_dry_time_is_credited_by_phase_until_a_program_is_queued(level):
    """Dry from the start: whole phases go to the table; the phase in
    which the first program is queued goes there up to that moment and
    no further; a step that lands with nothing behind it sets it
    again, from the moment it landed."""
    clock = lmt.PhaseClock()
    clock.switch(lmt.PH_IDLE_WAIT)
    time.sleep(0.02)
    clock.switch(lmt.PH_SCHED)
    clock.tick()
    clock.switch(lmt.PH_PREFILL_DISPATCH)
    time.sleep(0.01)
    clock.filling(1, 5)                 # queued: dry no more
    time.sleep(0.02)
    clock.switch(lmt.PH_STEP_DISPATCH)
    ordinal = clock.queued(clock.stamp(), 0, 1, False, 0)
    clock.switch(lmt.PH_DEVICE_WAIT)
    t_wait = clock.t
    time.sleep(0.01)
    clock.switch(lmt.PH_TOKEN_WALK)
    clock.landed(t_wait, ordinal, False, 1, 0)      # nothing behind it
    time.sleep(0.01)
    clock.switch(lmt.PH_STREAM_EMIT)
    clock.delivered()
    clock.switch(lmt.PH_IDLE_WAIT)
    dry = clock.rounds.counters()["dry_ns"]
    ns = lmt.phase_total_ns()
    assert dry["idle_wait"] == ns["idle_wait"] >= 20e6
    assert dry["sched"] == ns["sched"]
    assert 10e6 <= dry["prefill_dispatch"] <= ns["prefill_dispatch"] - 20e6
    assert dry["step_dispatch"] == dry["device_wait"] == 0
    assert dry["token_walk"] == ns["token_walk"] >= 10e6
    assert dry["stream_emit"] == ns["stream_emit"]
    rec = clock.rounds.records()[0]
    assert rec["cls"] == "restart"
    assert rec["dry_ns"] == dry["idle_wait"] + dry["sched"] \
        + dry["prefill_dispatch"]
    assert clock.dry and clock.rounds.lull


def test_nothing_is_written_with_telemetry_off(model, level):
    cfg, params = model
    bat = ContinuousBatcher(cfg, params, slots=2, page=PAGE,
                            idle_linger_s=0.2)
    assert set_flag("lm_telemetry", "false")
    try:
        st = _join(bat, _prompt(31, 9), 5)
        _finish(st)
        _wait(lambda: bat._thread is None, "the batcher never lingered out")
        assert bat.steps_run() == 5
        kv = bat.kv_stats()
        zero = lmt.RoundLog()
        assert kv["rounds"] == zero.counters()
        assert kv["first"] == zero.first_counters()
        assert bat.round_log() == []
        assert bat._clock.t == 0 and bat._clock.rounds.pend == [0, 0, 0]
    finally:
        assert set_flag("lm_telemetry", "true")
    # on again: the next step opens the log, at the batcher's own count
    st = _join(bat, _prompt(32, 9), 4)
    _finish(st)
    _wait(lambda: bat._thread is None, "the batcher never lingered out")
    assert [x["ordinal"] for x in bat.round_log()] == [5, 6, 7, 8]
    assert bat.kv_stats()["first"]["n"] == 1


def test_two_batchers_keep_two_logs(model, level):
    cfg, params = model
    a = ContinuousBatcher(cfg, params, slots=2, page=PAGE,
                          idle_linger_s=0.2)
    b = ContinuousBatcher(cfg, params, slots=2, page=PAGE,
                          idle_linger_s=0.2)
    _finish(_join(a, _prompt(41, 9), 6), _join(b, _prompt(42, 9), 3))
    _wait(lambda: a._thread is None and b._thread is None,
          "a batcher never lingered out")
    for bat, steps in ((a, 6), (b, 3)):
        r = bat.kv_stats()["rounds"]
        assert sum(r[cls]["n"] for cls in lmt.LM_ROUND_CLASSES) == steps
        assert len(bat.round_log()) == steps
        assert bat.kv_stats()["first"]["n"] == 1


# -- the real profiler, on the CPU --------------------------------------------

def test_a_records_ordinal_is_its_rounds_step_num_in_a_trace(
        model, level, tmp_path):
    """Under a profile with the host tracer on, the batcher's
    annotations reach the trace: one ``lm_round`` a pass with sessions,
    whose ``step_num`` is the ordinal of the record of the step that
    pass dispatched, and whose phases lie in the order of a pass
    (``test_lookahead``: the next step is dispatched before the last
    one is read)."""
    from benchmarks.harness import hostspans, xplane

    cfg, params = model
    bat = ContinuousBatcher(cfg, params, slots=2, page=PAGE,
                            idle_linger_s=0.2)
    _finish(_join(bat, _prompt(61, 6), 2))              # compiles
    _wait(lambda: bat._thread is None, "the batcher never lingered out")
    steps0 = bat.steps_run()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _finish(_join(bat, _prompt(62, 6), 6))
        _wait(lambda: bat._thread is None,
              "the batcher never lingered out")
    finally:
        jax.profiler.stop_trace()
    data = jax.profiler.ProfileData.from_file(
        xplane.find_xplane(str(tmp_path)))
    rounds = sorted(
        (e.start_ns, e.start_ns + e.duration_ns,
         int(dict(e.stats)["step_num"]))
        for plane in data.planes if plane.name == hostspans.HOST_PLANE
        for line in plane.lines for e in line.events
        if e.name == lmt.ROUND_TRACE_NAME)
    log = bat.round_log(since=steps0)
    assert [x["ordinal"] for x in log] == list(range(steps0, steps0 + 6))
    # six passes dispatch a step; the seventh lands the last and
    # carries the number the next step would have had
    assert [r[2] for r in rounds] == [x["ordinal"] for x in log] \
        + [steps0 + 6]
    phases = hostspans.batcher_phases(data)
    assert {p[2] for p in phases} <= set(lmt.LM_STEP_PHASES)
    keep = ("step_dispatch", "device_wait", "token_walk", "stream_emit",
            "evict")
    passes = [[name for s, _e, name in phases
               if r0 <= s < r1 and name in keep] for r0, r1, _n in rounds]
    land = ["device_wait", "token_walk", "stream_emit"]
    assert passes == [["step_dispatch"]] + [["step_dispatch"] + land] * 5 \
        + [land + ["evict"]]


# -- the benchmark's reader ---------------------------------------------------

class _Run:
    """A run's two counter snapshots, as ``RunRecord`` hands them to a
    reader."""

    def __init__(self, c0, c1):
        self.c0, self.c1 = {"kv": c0}, {"kv": c1}

    def counter(self, snap, path):
        for key in path:
            snap = snap[key]
        return snap


def _rounds(plain, fill, ride):
    return {"rounds": {
        "plain": {"n": plain[0], "gap_ns": plain[1]},
        "fill": {"n": fill[0], "gap_ns": fill[1], "rows": 0, "programs": 0},
        "ride": {"n": ride[0], "gap_ns": ride[1], "rows": 0, "programs": 0}}}


@pytest.mark.parametrize("cls,c0,c1,want", [
    # 10 fill steps of 46 ms beside 100 plain ones of 11 ms: 35 ms a join
    ("fill", _rounds((5, 50e6), (1, 40e6), (0, 0)),
     _rounds((105, 1150e6), (11, 500e6), (0, 0)), 35.0),
    ("ride", _rounds((0, 0), (0, 0), (2, 25e6)),
     _rounds((200, 2200e6), (0, 0), (12, 150e6)), 1.5),
    # the class stood still, or the plain steps did: nothing
    ("ride", _rounds((0, 0), (0, 0), (2, 25e6)),
     _rounds((200, 2200e6), (3, 90e6), (2, 25e6)), None),
    ("fill", _rounds((7, 70e6), (1, 40e6), (0, 0)),
     _rounds((7, 70e6), (11, 500e6), (0, 0)), None),
    # the parent: no such counters, and the run goes on
    ("fill", {"steps": 3}, {"steps": 90}, None),
])
def test_round_excess_reader(cls, c0, c1, want):
    from benchmarks.harness import spec

    read = spec.load_module("readers", "round_excess").read
    got = read(_Run(c0, c1), {"class": cls})
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_new_metrics_name_counters_the_program_has(model):
    """Every metric file this PR adds reads, through the reader it
    names, a counter ``kv_stats()`` really has: the names in the files
    and the program's cannot drift apart unseen."""
    import glob
    import os

    from benchmarks.harness import spec

    cfg, params = model
    bat = ContinuousBatcher(cfg, params, slots=2, page=PAGE)
    kv = bat.kv_stats()
    run = _Run(kv, kv)
    run.due_in_window = lambda: []
    files = [f for stem in ("join_stall_ms", "join_round_share",
                            "ride_stall_ms", "dry_share", "dry_host_share",
                            "late_step_share", "first_device_ms")
             for f in glob.glob(os.path.join(
                 spec.BENCH_DIR, "metrics", f"batcher.*_{stem}.json"))]
    assert len(files) == 27
    # two phases left with speculative decoding (PR 42).  One file that
    # no entry of BENCHMARK.json registers still sums them by name; the
    # `benchmark` PR that registers it takes them out (ROADMAP S0)
    gone = {"spec_draft", "spec_verify"}
    for path in files:
        m = spec.load_json(path)
        if m["reader"] == "round_excess":
            assert m["class"] in ("fill", "ride")
            continue
        assert m["reader"] == "counter_ratio_if_present"
        for p in m["num"] + m.get("den", []):
            if p[-1] in gone:
                assert path.endswith("batcher.chat_dry_host_share.json")
                continue
            assert run.counter(run.c1, p) is not None, (path, p)
    host = spec.load_json(os.path.join(
        spec.BENCH_DIR, "metrics", "batcher.chat_dry_host_share.json"))
    assert sorted(set(p[-1] for p in host["num"]) - gone) \
        == sorted(set(lmt.LM_STEP_PHASES) - {"idle_wait"})


# -- the linters know the new writers and the new enum ------------------------

LM_TEL = "brpc_tpu/models/lm_telemetry.py"


@pytest.mark.parametrize("hook,anchor", [
    ("filling", "        pend = self.rounds.pend\n"),
    ("filling", "        self.dry = False\n"),           # through _wet
    ("joined", "            self.rounds.pend[2] += 1\n"),
    ("filled", "            tl.fill_ns = self.t\n"),
    ("queued", "        log.dispatch_ns[i] = t\n"),
    ("landed", "        log.landed_idx = i\n"),
    ("delivered", "                log.done_ns[i] = _mono_ns()\n"),
])
def test_a_lock_in_a_step_hook_is_caught(hook, anchor):
    """Every hook that writes the step log runs inside the batcher's
    loop and is entry-listed beside ``PhaseClock.switch``: a blocking
    primitive grown into one is a finding."""
    from test_static_checks import _mutate

    from brpc_tpu.tools.check import Tree, check_blocking

    indent = anchor[:len(anchor) - len(anchor.lstrip())]
    ov = _mutate(LM_TEL, anchor, f"{indent}_obs_lock.acquire()\n{anchor}")
    findings = check_blocking(Tree(overrides=ov))
    assert any(hook in f.message and "acquire" in f.message
               for f in findings), findings


def test_an_unpinned_round_class_is_caught():
    """``LM_ROUND_CLASSES`` is closed: a member no test names is a
    finding (the name is put together here so that this file does not
    pin it)."""
    from test_static_checks import _mutate

    from brpc_tpu.tools.check import Tree, check_enums

    unpinned = "stood_" + "nowhere"
    ov = _mutate(LM_TEL, '    "plain",     # the step before it',
                 f'    "{unpinned}",\n    "plain",     # the step before it')
    findings = check_enums(Tree(overrides=ov))
    assert any(unpinned in f.message for f in findings), findings
    assert lmt.LM_ROUND_CLASSES == ("restart", "fill", "ride", "plain")
