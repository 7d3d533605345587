"""The batcher keeps one decode step in flight (ISSUE 28): a pass
dispatches the next step before it reads the last one's tokens, the
step's inputs stay on the device, and the tokens it serves are the ones
a strictly one-at-a-time decode serves.

The yardstick is ``transformer_lm.generate``: one session alone, every
token read before the next step is built.  (The same pins for a layer
schedule with state layers are in ``test_hybrid_lm.py``.)
"""

import struct
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.models import lm_telemetry as lmt
from brpc_tpu.models.lm_service import ContinuousBatcher
from brpc_tpu.models.transformer_lm import LMConfig, generate, init_params
from brpc_tpu.streaming import StreamOptions

PAGE = 8
PAGES = (PAGE, 16)


@pytest.fixture(scope="module")
def model():
    cfg = LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=64,
                   remat=False)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


class _Stream:
    """A client's end of a decode stream; ``hang_up_after`` closes it
    from the client's side once that many tokens have arrived."""

    def __init__(self, hang_up_after=None):
        self.closed, self.close_reason, self.tokens = False, None, []
        self.id, self._native_tx = 0, None
        self.options = StreamOptions()
        self.hang_up_after = hang_up_after

    def write(self, data):
        self.tokens.append(struct.unpack("<i", bytes(data))[0])
        if len(self.tokens) == self.hang_up_after:
            self.closed = True
        return 0

    def close(self, reason=None):
        self.closed, self.close_reason = True, reason


def _prompt(seed, n, vocab=64):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (n,), 0, vocab, jnp.int32))


def _join(bat, prompt, max_new, **kw):
    st = _Stream(**kw)
    bat.join(st, prompt, max_new)
    return st


def _wait(cond, what, timeout=120.0):
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.002)
    assert cond(), what


def _finish(*streams):
    _wait(lambda: all(s.closed for s in streams),
          "a decode session never closed")


def _quiet(bat):
    """The pass that closes the last stream still has its tail to run:
    wait for the idle wait, where every counter reads level."""
    _wait(lambda: bat._thread is None
          or bat._clock.cur == lmt.PH_IDLE_WAIT,
          "the batcher never went idle")


def _want(model, prompt, max_new):
    cfg, params = model
    return np.asarray(generate(params, cfg, prompt[None, :],
                               max_new))[0].tolist()


# -- (a) the tokens of the synchronous order ---------------------------------

@pytest.mark.parametrize("page", PAGES)
def test_sessions_joining_and_ending_mid_batch_get_their_own_tokens(
        model, page):
    """Six sessions of different ``max_new`` (1 and 2 among them: a
    session whose first step is its last) over three slots, so that
    sessions end while others decode and the queued ones join into
    slots that have just changed hands: each is served what it is
    served alone."""
    cfg, params = model
    bat = ContinuousBatcher(cfg, params, slots=3, idle_linger_s=0.2,
                            page=page)
    asks = [(_prompt(20 + i, n), m) for i, (n, m) in enumerate(
        [(9, 12), (5, 1), (17, 7), (3, 2), (12, 9), (1, 5)])]
    streams = [_join(bat, p, m) for p, m in asks]
    _finish(*streams)
    _quiet(bat)
    for (p, m), st in zip(asks, streams):
        assert st.close_reason == "finished"
        assert st.tokens == _want(model, p, m), (len(p), m)
    look = bat.kv_stats()["lookahead"]
    assert look["ahead"] + look["sync"] == bat.steps_run()
    assert look["ahead"] > look["sync"] >= 1
    # whatever feeds it (an upload, an argmax, a poked-in token), the
    # step is one program: nothing new compiles when a step runs ahead
    assert bat._step.func._cache_size() == 1


# -- (b) a client that hangs up with a step in flight -------------------------

def test_hung_up_session_leaves_nothing_to_the_slots_next_holder(model):
    """One slot.  A's client hangs up after three tokens: the batcher
    learns of it at the next emit, with one more step of A's already
    queued.  B, waiting, takes the slot in the next pass and is served
    its own tokens only; C re-sends A's prompt, aliases the pages A's
    prefill cached, and decodes as from a cold cache: the step nobody
    read wrote a row of A's own, not into a page it shared."""
    cfg, params = model
    # the phase table is the process's: level it, whatever ran before
    _wait(lambda: not any(t.name == "lm-decode-batcher"
                          for t in threading.enumerate()),
          "an earlier test's batcher never lingered out", 30.0)
    lmt._reset_for_tests()
    bat = ContinuousBatcher(cfg, params, slots=1, page=PAGE,
                            idle_linger_s=0.2)
    pa, pb = _prompt(31, 20), _prompt(32, 11)
    a = _join(bat, pa, 30, hang_up_after=3)
    b = _join(bat, pb, 6)
    _finish(b)
    assert a.tokens == _want(model, pa, 3)
    assert a.close_reason is None               # it left; nobody closed it
    assert b.tokens == _want(model, pb, 6)
    steps = bat.steps_run()
    assert steps == 3 + 2 + 6                   # A's: read, found out, unread
    c = _join(bat, pa, 6)
    _finish(c)
    _quiet(bat)
    assert c.tokens == _want(model, pa, 6)
    assert bat.kv_stats()["prefix"]["hits"] >= 1
    assert bat.prefills_run == 2                # C's context was A's pages
    look = bat.kv_stats()["lookahead"]
    assert look["ahead"] + look["sync"] == bat.steps_run() == steps + 6
    # every step was walked, the one nobody read too
    c_ = lmt.phase_counters()
    assert c_["device_wait"] == c_["step_dispatch"] == c_["token_walk"]


# -- (d) the counter ----------------------------------------------------------

@pytest.mark.parametrize("page", PAGES)
def test_uploads_only_where_membership_changes(model, page):
    """A session decoding alone: between its admission and its last
    step nothing is uploaded, however many steps run; every step but
    the first leaves with the one before it unread."""
    cfg, params = model
    lmt._reset_for_tests()
    bat = ContinuousBatcher(cfg, params, slots=2, idle_linger_s=0.2,
                            page=page)
    seen = []                   # the counter as each token is emitted

    class Watching(_Stream):
        def write(self, data):
            seen.append(bat.kv_stats()["lookahead"]["uploads"])
            return super().write(data)

    st = Watching()
    bat.join(st, _prompt(41, 7), 50)
    _finish(st)
    assert len(seen) == 50 and len(set(seen)) == 1
    _quiet(bat)
    end = bat.kv_stats()
    look = end["lookahead"]
    assert end["steps"] == 50
    assert (look["sync"], look["ahead"]) == (1, 49)
    # the admission: token vector, mask and block table.  Its last
    # step and its eviction change the mirrors too, and no step
    # followed that would have needed them on the device
    assert look["uploads"] == 3
    # a second session into the same slot: its first token is poked
    # into the vector the device holds, its pages go up with the block
    # table, and the mask on the device is already the one it needs
    st2 = _join(bat, _prompt(42, 9), 20)
    _finish(st2)
    _quiet(bat)
    look2 = bat.kv_stats()["lookahead"]
    assert look2["uploads"] - look["uploads"] == 2
    assert look2["ahead"] + look2["sync"] == bat.steps_run() == 70
    assert look2["sync"] == 2


# -- the order of a pass ------------------------------------------------------

class _Recorder:
    log: list = []

    def __init__(self, name, **kw):
        self.name = name

    def __enter__(self):
        _Recorder.log.append(self.name)
        return self

    def __exit__(self, *exc):
        pass


@pytest.mark.parametrize("riding", [False, True])
def test_a_pass_dispatches_the_next_step_before_it_reads_the_last(
        model, monkeypatch, riding):
    """The phases of one session's decode, in the order the loop ran
    them: the first step leaves alone; from then on every
    ``device_wait`` has a ``step_dispatch`` before it in its own pass,
    until the last step is out and there is nothing left to queue.
    ``riding``: the session is a partial prefix hit with 70 rows to
    catch up: two slices, each in the pass of the step it boards (the
    first of which has no decode row, and is read like any other); the
    second activates the session and the rest is as before."""
    cfg, params = model
    _wait(lambda: not any(t.name == "lm-decode-batcher"
                          for t in threading.enumerate()),
          "an earlier test's batcher never lingered out", 30.0)
    lmt._reset_for_tests()
    monkeypatch.setattr(_Recorder, "log", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", _Recorder)
    if riding:
        # (a config of its own: 70 rows do not fit the module's 64)
        cfg = LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=128,
                       remat=False)
        params = init_params(jax.random.PRNGKey(0), cfg)
    bat = ContinuousBatcher(cfg, params, slots=2, page=PAGE,
                            idle_linger_s=0.05)
    prompt = _prompt(61, 6)
    if riding:
        base = _prompt(62, 2 * PAGE)
        _finish(_join(bat, np.concatenate([base, _prompt(63, 5)]), 2))
        _wait(lambda: bat._thread is None,
              "the batcher never lingered out")
        _Recorder.log.clear()
        prompt = np.concatenate([base, _prompt(64, 70 + 1)])
    st = _join(bat, prompt, 4)
    _finish(st)
    _wait(lambda: bat._thread is None, "the batcher never lingered out")
    passes, cur = [], None
    for name in _Recorder.log:
        if name == lmt.ROUND_TRACE_NAME:
            cur = []
            passes.append(cur)
        elif cur is not None and name in (
                "lm/catchup_slice", "lm/step_dispatch", "lm/device_wait",
                "lm/token_walk", "lm/stream_emit", "lm/evict"):
            cur.append(name[3:])
    land = ["device_wait", "token_walk", "stream_emit"]
    first = [["catchup_slice", "step_dispatch"],
             ["catchup_slice", "step_dispatch"] + land] if riding \
        else [["step_dispatch"]]
    assert passes == first + [["step_dispatch"] + land,
                              ["step_dispatch"] + land,
                              ["step_dispatch"] + land,
                              land + ["evict"]]
    assert bat.kv_stats()["lookahead"]["slices_rode"] == 2 * riding
