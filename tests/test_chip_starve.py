"""``chip_starve.py``: the records put on a trace's clock, the gaps
between program executions, and the bounds the program's idle tables
set on them, at toy size on the CPU."""

import jax
import pytest
from test_lookahead import _finish, _join, _prompt, _wait
from test_round_log import PAGE, _Run

import chip_starve as cs
from brpc_tpu.models import lm_telemetry as lmt
from brpc_tpu.models.lm_service import ContinuousBatcher
from brpc_tpu.models.transformer_lm import LMConfig, init_params

MS = 1_000_000


@pytest.fixture(scope="module")
def model():
    cfg = LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=256,
                   remat=False)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _rec(ordinal, pass_ns, dispatch_ns, land_ns, wait_ns=0, dry=0,
         starved=0):
    return {"ordinal": ordinal, "pass_ns": pass_ns,
            "dispatch_ns": dispatch_ns, "land_ns": land_ns,
            "wait_ns": wait_ns, "done_ns": land_ns + MS // 10 if land_ns
            else 0, "dry_ns": dry, "starved_ns": starved}


def _snap(mono, bracket=0, starved=0, dry=0, loop=0):
    zero = {p: 0 for p in lmt.LM_STEP_PHASES}
    return {"loop_ns": loop, "steps": 0, "rounds": {
        "clock": {"mono_ns": mono, "trace_ns": mono},
        "dry_ns": {**zero, "idle_wait": dry},
        "starved_ns": {**zero, "sched": starved},
        "starved_bracket_ns": {**zero, "stream_emit": bracket}}}


def test_gaps_between_programs_merge_what_overlaps():
    mods = [(0, 10, "jit_step"), (10, 12, "jit__argmax"),
            (15, 30, "jit_prefill"), (20, 25, "jit_insert"),
            (32, 40, "jit_step")]
    gaps = cs.program_gaps(mods)
    assert gaps == [(12, 15, "jit__argmax", "jit_prefill"),
                    (30, 32, "jit_prefill", "jit_step")]
    assert cs.idle_within(gaps, 0, 40) == 5
    assert cs.idle_within(gaps, 13, 31) == 3
    assert cs.idle_within(gaps, 0, 40, shorter=3) == 2


@pytest.mark.parametrize("bracket_ms,upper", [(3, True), (0.5, False)])
def test_the_sandwich_over_the_steps_queued_in_the_span(bracket_ms, upper):
    """The records' dry + starved time of the steps after the first,
    the bracket's growth, and the idle time between programs from the
    first step's queueing to the last's (on the trace: the clock pair,
    the device's lead)."""
    log = [_rec(0, 0, 1 * MS, 9 * MS),
           _rec(1, 10 * MS, 12 * MS, 20 * MS, starved=2 * MS),
           _rec(2, 21 * MS, 22 * MS, 30 * MS)]
    gaps = [(9 * MS, 12 * MS, "jit__argmax", "jit_step")]
    out = cs.sandwich(log, _snap(0), _snap(40 * MS, bracket=bracket_ms * MS),
                      gaps, 0, 0)
    assert out["steps"] == 3 and out["trace_idle_ms"] == 3
    assert (out["starved_ms"], out["dry_ms"]) == (2, 0)
    assert out["lower_holds"] and out["upper_holds"] == upper
    assert out["launch_ms"] == 0
    # the launch floor: a step queued behind its argmax begins a few us
    # after it, and no host account holds that
    launch = gaps + [(21 * MS, 21 * MS + 5000, "jit_step", "jit__argmax")]
    out = cs.sandwich(log, _snap(0), _snap(40 * MS, bracket=MS), launch, 0,
                      0)
    assert out["launch_ms"] == 0.005 and not out["upper_holds"]
    assert out["upper_holds_past_launch"]
    # a lead moves the span on the device's clock
    out = cs.sandwich(log, _snap(0), _snap(40 * MS), gaps, 0, 2 * MS)
    assert out["trace_idle_ms"] == 3
    assert cs.sandwich(log[:1], _snap(0), _snap(40 * MS), gaps, 0, 0) \
        == {"steps": 1}


def test_the_shares_are_the_metric_files():
    from benchmarks.harness import spec

    c0 = _snap(0, bracket=MS, starved=2 * MS, dry=MS, loop=10 * MS)
    c1 = _snap(0, bracket=3 * MS, starved=5 * MS, dry=2 * MS, loop=40 * MS)
    got = cs.shares(c0, c1)
    assert got == {"dry_share": pytest.approx(100 / 30),
                   "starved_share": pytest.approx(100 * 3 / 30),
                   "starved_bracket_share": pytest.approx(100 * 2 / 30)}
    m = spec.load_json(f"{spec.BENCH_DIR}/metrics/"
                       "batcher.chat_starved_share.json")
    read = spec.load_module("readers", m["reader"]).read
    assert read(_Run(c0, c1), m) == pytest.approx(got["starved_share"])
    # a program without the table: nothing
    old = {"loop_ns": 40, "rounds": {"dry_ns": {}}}
    assert cs.shares(old, old) == {}


def test_the_window_is_cut_where_the_profiler_starts_and_stops():
    log = [{**_rec(0, 0, 1 * MS, 9 * MS, wait_ns=MS, starved=MS),
            "seen_ns": 7 * MS},
           {**_rec(1, 10 * MS, 11 * MS, 19 * MS, wait_ns=MS, dry=2 * MS),
            "seen_ns": 19 * MS},
           {**_rec(2, 20 * MS, 21 * MS, 0), "seen_ns": 0}]
    out = cs.segments(log, [("a", 0), ("b", 10 * MS), ("c", 20 * MS),
                            ("end", 40 * MS)])
    assert list(out) == ["a", "b", "c"]
    assert out["a"] == {"ms": 10, "steps": 1, "dry_share": 0,
                        "starved_share": 10, "late": 1}
    assert out["b"]["dry_share"] == 20 and out["b"]["late"] == 0
    assert out["c"]["steps"] == 1 and out["c"]["late"] == 0


def test_a_gap_is_named_by_the_stretch_of_the_pass_it_began_in():
    log = [_rec(0, 0, 2 * MS, 9 * MS, wait_ns=3 * MS),
           _rec(1, 10 * MS, 12 * MS, 0)]
    marks = cs.host_marks(log)
    assert cs.stretch_at(marks, -1) == "before the records"
    assert cs.stretch_at(marks, 1 * MS).startswith("pass top")
    assert cs.stretch_at(marks, 3 * MS).startswith("step queued")
    assert cs.stretch_at(marks, 7 * MS) == "device_wait"
    assert cs.stretch_at(marks, 9 * MS).startswith("landed")
    assert cs.stretch_at(marks, 9.5 * MS).startswith("delivered")
    assert cs.stretch_at(marks, 11 * MS).startswith("pass top")
    table = cs.gap_table([(1 * MS, 3 * MS, "jit__argmax", "jit_step")],
                         lambda g0: cs.stretch_at(marks, g0), 0, 20 * MS)
    (kind, (n, ms)), = table["by_kind"].items()
    assert kind.startswith("jit__argmax -> jit_step | pass top")
    assert (n, ms) == (1, 2.0)
    assert table["longest"][0][:3] == [1.0, 2.0, "jit__argmax -> jit_step"]


def test_the_lead_is_read_off_steps_that_began_on_an_idle_device():
    """A step queued onto an idle device begins when it is enqueued,
    between its pass's top and ``dispatch_ns``: a device clock that
    reads 1.5 ms early puts its start that much before."""
    log = [_rec(0, 10 * MS, 11 * MS, 20 * MS),
           _rec(1, 40 * MS, 41 * MS, 50 * MS)]
    gaps = [(2 * MS, 9 * MS, "jit__argmax", "jit_step"),
            (30 * MS, int(39.2 * MS), "jit__argmax", "jit_step"),
            (25 * MS, int(25.1 * MS), "jit_step", "jit__argmax")]
    lo, hi = cs.restart_lead(gaps, log, 0)
    assert lo <= 1.5 * MS <= hi
    assert cs.restart_lead(gaps[2:], log, 0) is None


def test_the_clock_pair_puts_the_records_under_their_rounds(model, tmp_path):
    """The host tracer's ``lm_round`` begins where the record's pass
    began, once the clock pair and the session's start put the record
    on the trace's clock."""
    from benchmarks.harness import xplane

    cfg, params = model
    bat = ContinuousBatcher(cfg, params, slots=2, page=PAGE,
                            idle_linger_s=0.2)
    _finish(_join(bat, _prompt(71, 6), 2))
    _wait(lambda: bat._thread is None, "the batcher never lingered out")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _finish(_join(bat, _prompt(72, 6), 8))
        _wait(lambda: bat._thread is None, "the batcher never lingered out")
    finally:
        jax.profiler.stop_trace()
    data = jax.profiler.ProfileData.from_file(
        xplane.find_xplane(str(tmp_path)))
    clock = bat.kv_stats()["rounds"]["clock"]
    start = cs.profile_start(data)
    assert start > 0
    shift = clock["trace_ns"] - clock["mono_ns"] - start
    got = cs.residual(data, bat.round_log(), shift)
    assert got["records"] == 8
    assert -2000 < got["median_us"] < 2000, got
    assert cs.residual(data, [], shift) == {"records": 0}


def test_the_probe_is_timed_inside_the_window(monkeypatch):
    from benchmarks.harness import serving

    monkeypatch.setattr(lmt.PhaseClock, "_ready", lmt.PhaseClock._ready)
    monkeypatch.setattr(serving.Served, "counters", lambda served: {})
    probe = cs.Probe()
    assert lmt.PhaseClock._ready(Ready()) is True      # set-up: not counted
    served = object.__new__(serving.Served)
    assert served.counters() == {}
    for _ in range(4):
        lmt.PhaseClock._ready(Ready())
    served.counters()
    lmt.PhaseClock._ready(Ready())                     # after: not counted
    got = probe.summary(steps=2)
    assert got["calls"] == 4 and got["calls_per_step"] == 2
    assert 0 < got["median_ns"] <= got["p90_ns"]
    assert cs.Probe().summary(1) == {"calls": 0}


class Ready:
    def is_ready(self):
        return True


def test_an_unknown_mode_is_refused():
    with pytest.raises(SystemExit):
        cs.main(["sweep", "neox-1b4.batch", "20", "7"])
