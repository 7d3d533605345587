"""The split of device idle gaps over the batcher's annotated phases
(``harness/hostspans.py``), on intervals made by hand, and the reader
of the phase table (``readers/phase_ms.py``) on a record made by hand."""
from types import SimpleNamespace

import pytest

from benchmarks.harness import hostspans, spec
from benchmarks.harness.series import RunRecord

OP = "%x = f32[2]{0} add(...)"


def ns(seconds: float) -> float:
    return pytest.approx(seconds * 1e-9)


def test_gap_covered_by_two_phases():
    mods = [(0, 100, "jit_a"), (200, 300, "jit_b")]
    ops = [(0, 100, OP), (200, 300, OP)]
    phases = [(50, 150, "token_walk"), (150, 260, "stream_emit")]
    r = hostspans.split_gaps(mods, ops, phases)
    assert list(r["gaps"]) == ["jit_a -> jit_b"]
    g = r["gaps"]["jit_a -> jit_b"]
    assert g["n"] == 1 and g["total_s"] == ns(100)
    assert g["by"] == {"token_walk": ns(50), "stream_emit": ns(50)}
    assert r["busy_s"] == ns(200) and r["window_s"] == ns(300)


def test_gap_with_a_d2h_head_and_a_launch_tail():
    """The device ends its argmax at 100 inside a wait that returns at
    130; the step is enqueued by 350 and begins at 400, while the host
    already sits in the next wait: that wait gets none of it."""
    mods = [(0, 100, "jit__argmax"), (400, 500, "jit_step")]
    ops = [(0, 100, OP), (400, 500, OP)]
    phases = [(20, 130, "device_wait"), (130, 200, "token_walk"),
              (200, 250, "sched"), (250, 350, "step_dispatch"),
              (350, 600, "device_wait")]
    g = hostspans.split_gaps(mods, ops, phases)["gaps"][
        "jit__argmax -> jit_step"]
    assert g["by"] == {"d2h_return": ns(30), "token_walk": ns(70),
                       "sched": ns(50), "step_dispatch": ns(100),
                       "launch": ns(50)}
    assert sum(g["by"].values()) == pytest.approx(g["total_s"])


def test_device_starts_before_the_dispatch_returns():
    """No launch remainder where the first operation begins while the
    phase that enqueued it is still open (it goes on to the argmax)."""
    mods = [(0, 100, "jit__argmax"), (300, 400, "jit_step")]
    ops = [(0, 100, OP), (300, 400, OP)]
    phases = [(0, 90, "device_wait"), (90, 250, "stream_emit"),
              (250, 320, "step_dispatch")]
    g = hostspans.split_gaps(mods, ops, phases)["gaps"][
        "jit__argmax -> jit_step"]
    assert g["by"] == {"stream_emit": ns(150), "step_dispatch": ns(50)}


def test_gap_no_annotation_covers_is_unattributed():
    mods = [(0, 100, "jit_a"), (200, 300, "jit_b"), (500, 600, "jit_step")]
    ops = [(0, 100, OP), (200, 300, OP), (500, 600, OP)]
    phases = [(220, 240, "sched"), (320, 420, "evict")]
    r = hostspans.split_gaps(mods, ops, phases)
    assert r["gaps"]["jit_a -> jit_b"]["by"] == {"unattributed": ns(100)}
    # half covered: the rest is nobody's, and no dispatch, so no launch
    assert r["gaps"]["jit_b -> jit_step"]["by"] == {
        "evict": ns(100), "unattributed": ns(100)}
    # busy + gaps = window
    gaps = sum(g["total_s"] for g in r["gaps"].values())
    assert r["busy_s"] + gaps == pytest.approx(r["window_s"])
    assert r["window_s"] == ns(600)


def test_overlapping_operations_are_one_busy_stretch():
    mods = [(0, 100, "jit_a"), (200, 260, "jit_b")]
    ops = [(0, 40, OP), (30, 100, OP), (200, 250, OP), (255, 260, OP)]
    r = hostspans.split_gaps(mods, ops, [(0, 300, "sched")])
    assert r["busy_s"] == ns(155)
    assert r["gaps"]["jit_a -> jit_b"]["by"] == {"sched": ns(100)}
    assert r["gaps"]["jit_b -> jit_b"]["by"] == {"sched": ns(5)}
    assert "jit_a -> jit_b x1" in hostspans.table(r)


def test_clock_lead_by_hand():
    """A program cannot begin before it is enqueued: the device's
    events lead the host's clock by the largest (enqueue - begin) over
    the executions both name; a backed-up queue only lowers others."""
    enqueued = {641: 2_590, 642: 2_800, 643: 30_710, 999: 5}
    began = {641: 918, 642: 25_278, 643: 29_137, 7: 1}
    assert hostspans.clock_lead(enqueued, began) == 1_672
    assert hostspans.clock_lead(enqueued, {7: 1}) is None
    assert hostspans.clock_lead({}, {}) is None


# -- readers/phase_ms.py ------------------------------------------------------

def record(c0: dict, c1: dict, due: int = 4) -> RunRecord:
    cell = SimpleNamespace(config={}, model=None, traffic={})
    reqs = [SimpleNamespace(due=1.0 + i, stamps=[]) for i in range(due)]
    return RunRecord(cell=cell, seconds=10.0, t0=0.0, t1=10.0,
                     requests=reqs, c0={"kv": c0}, c1={"kv": c1},
                     setup_s=1.0)


PHASES0 = {"sched": 1_000_000, "device_wait": 50_000_000,
           "stream_emit": 2_000_000, "idle_wait": 7_000_000}
PHASES1 = {"sched": 3_000_000, "device_wait": 300_000_000,
           "stream_emit": 8_000_000, "idle_wait": 9_000_000}


def test_phase_ms_by_hand():
    read = spec.load_module("readers", "phase_ms").read
    run = record({"steps": 10, "phase_ns": PHASES0},
                 {"steps": 20, "phase_ns": PHASES1})
    per = ["kv", "steps"]
    assert read(run, {"phases": ["device_wait"], "per": per}) \
        == pytest.approx(25.0)
    assert read(run, {"phases": ["sched", "stream_emit"], "per": per}) \
        == pytest.approx(0.8)
    assert read(run, {"all_but": ["device_wait", "idle_wait"],
                      "per": per}) == pytest.approx(0.8)
    assert read(run, {"phases": ["stream_emit"], "per": "requests"}) \
        == pytest.approx(1.5)


def test_phase_ms_finds_nothing_to_read():
    read = spec.load_module("readers", "phase_ms").read
    per = ["kv", "steps"]
    older = record({"steps": 10}, {"steps": 20})       # no phase table
    assert read(older, {"phases": ["device_wait"], "per": per}) is None
    run = record({"steps": 10, "phase_ns": PHASES0},
                 {"steps": 10, "phase_ns": PHASES1})   # no step ran
    assert read(run, {"phases": ["device_wait"], "per": per}) is None
    run = record({"steps": 10, "phase_ns": PHASES0},
                 {"steps": 20, "phase_ns": PHASES1})
    assert read(run, {"phases": ["no_such_phase"], "per": per}) is None
    still = record({"steps": 10, "phase_ns": PHASES0},
                   {"steps": 20, "phase_ns": PHASES0})
    assert read(still, {"phases": ["sched"], "per": per}) is None


def test_counter_ratio_if_present():
    read = spec.load_module("readers", "counter_ratio_if_present").read
    sp = {"num": [["kv", "queue", "wait_ns"]],
          "den": [["kv", "queue", "admitted"]], "scale": 1e-6}
    run = record({"queue": {"wait_ns": 0, "admitted": 0}},
                 {"queue": {"wait_ns": 30_000_000, "admitted": 2}})
    assert read(run, sp) == pytest.approx(15.0)
    assert read(record({}, {}), sp) is None             # an older program
    # a dict of counts stands for its sum, as in counter_ratio
    run = record({"phase_ns": PHASES0, "loop_ns": 60_000_000},
                 {"phase_ns": PHASES1, "loop_ns": 320_000_000})
    assert read(run, {"num": [["kv", "phase_ns"]], "den": [["kv", "loop_ns"]],
                      "scale": 100}) == pytest.approx(100.0)


# -- the real profiler, on the CPU -------------------------------------------

def test_batcher_annotations_reach_a_trace(tmp_path):
    """A toy batcher decoding under a profile with the host tracer at 1:
    the trace holds its ``lm/<phase>`` events, one open at a time, in
    the order the loop runs them, and one ``lm_round`` a step."""
    import struct
    import time

    import jax
    import numpy as np

    from benchmarks.harness import xplane
    from brpc_tpu.models import lm_telemetry as lmt
    from brpc_tpu.models.lm_service import ContinuousBatcher
    from brpc_tpu.models.transformer_lm import LMConfig, init_params
    from brpc_tpu.streaming import StreamOptions

    class Stream:
        closed, id, _native_tx = False, 0, None

        def __init__(self):
            self.tokens, self.options = [], StreamOptions()

        def write(self, data):
            self.tokens.append(struct.unpack("<i", bytes(data))[0])
            return 0

        def close(self, reason=None):
            self.closed = True

    def decode(bat, n):
        st = Stream()
        bat.join(st, np.arange(1, 7, dtype=np.int32), n)
        deadline = time.monotonic() + 120
        while not st.closed and time.monotonic() < deadline:
            time.sleep(0.002)
        assert st.closed and len(st.tokens) == n

    cfg = LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=32,
                   remat=False)
    bat = ContinuousBatcher(cfg, init_params(jax.random.PRNGKey(0), cfg),
                            slots=2, paged=True, page=16)
    decode(bat, 2)                                  # compiles
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    steps0 = bat.steps_run()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    decode(bat, 6)
    while bat._clock.cur != lmt.PH_IDLE_WAIT:       # the pass's tail
        time.sleep(0.002)
    jax.profiler.stop_trace()
    data = jax.profiler.ProfileData.from_file(
        xplane.find_xplane(str(tmp_path)))
    phases = hostspans.batcher_phases(data)
    names = [p[2] for p in phases]
    assert set(names) <= set(lmt.LM_STEP_PHASES)
    steps = bat.steps_run() - steps0
    assert steps == 6
    assert names.count("device_wait") == names.count("step_dispatch") \
        == names.count("token_walk") == steps
    assert names.count("prefill_dispatch") == 1 and "evict" in names
    i = names.index("step_dispatch")
    assert names[i:i + 4] == ["step_dispatch", "device_wait", "token_walk",
                              "stream_emit"]
    for (_s0, e0, _n0), (s1, _e1, _n1) in zip(phases, phases[1:]):
        assert e0 <= s1                             # never two open
    rounds = [e for plane in data.planes if plane.name == hostspans.HOST_PLANE
              for line in plane.lines for e in line.events
              if e.name == lmt.ROUND_TRACE_NAME]
    assert len(rounds) == steps
