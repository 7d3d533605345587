"""A catch-up slice rides the decode step (ISSUE 30): where a session
fills its context by chunk slices, the batcher puts its next slice on
board the step it dispatches, in one program whose decode rows and
span rows cross every weight together.

The yardsticks: for the program, ``chunk_prefill`` followed by ``step``
on the same cache; for the batcher, ``transformer_lm.generate`` (one
session alone, its whole prompt prefilled at once).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_lookahead import (_finish, _join, _prompt, _quiet, _Stream,
                            _wait, _want)

from brpc_tpu.models import transformer_lm as T
from brpc_tpu.models.lm_service import (ContinuousBatcher,
                                        _reset_sched_for_tests,
                                        sched_counters)
from brpc_tpu.models.transformer_lm import LMConfig, init_params

PAGE = 16
CHUNK = 64          # the batcher's slice width where no budget is set


@pytest.fixture(scope="module")
def model():
    cfg = LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=256,
                   remat=False)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _look(bat):
    return bat.kv_stats()["lookahead"]


# -- (a) the program against chunk_prefill followed by step -------------------

@pytest.mark.parametrize("n,completes", [(CHUNK, False), (37, True),
                                         (1, True)])
def test_riding_step_is_the_slice_then_the_step(model, n, completes):
    """Four slots over a pool of random rows: two decode at their own
    depths, one is empty, one is the joiner: a page it aliases from a
    neighbour, then private pages, ``n`` rows of its context to write
    from the first private row on.  Where the slice completes the
    context the joiner is active in the same call, its row the prompt's
    last token."""
    cfg, params = model
    slots, pages, start = 4, 24, PAGE
    _prefill, step, riding = T.make_paged_batch_decode(cfg, PAGE,
                                                       chunk=CHUNK)
    *_io, chunk_prefill = T.make_paged_io(cfg, PAGE, chunk=CHUNK)
    rng = np.random.default_rng(n)
    cache = T.empty_paged_cache(cfg, pages, slots, PAGE)
    for i in range(cfg.depth):
        for kv in ("pk", "pv"):
            cache[kv + str(i)] = jnp.asarray(rng.standard_normal(
                cache[kv + str(i)].shape).astype(np.float32))
    pps = cfg.max_seq // PAGE
    bt = np.zeros((slots, pps), np.int32)
    bt[0, :3] = [1, 2, 3]               # 40 positions live
    bt[1, :2] = [4, 5]                  # 17
    joiner, aliased, private = 3, [1], [6, 7, 8, 9, 10, 11]
    bt[joiner, :7] = aliased + private
    cache["len"] = jnp.asarray([40, 17, 99, start], jnp.int32)
    active = np.array([True, True, False, completes])
    token = jnp.asarray(rng.integers(0, cfg.vocab, slots), jnp.int32)
    ids = np.zeros((CHUNK,), np.int32)
    ids[:n] = rng.integers(0, cfg.vocab, n)
    span = (np.int32(joiner), np.int32(start), np.int32(n), ids)
    args = (jnp.asarray(bt), token, jnp.asarray(active))

    want = jax.jit(chunk_prefill)(params, cache, jnp.asarray(bt[joiner]),
                                  *span)
    want, want_logits = jax.jit(step)(params, want, *args)
    got, got_logits = jax.jit(riding)(params, cache, *args, *span)

    np.testing.assert_array_equal(got["len"], want["len"])
    assert int(got["len"][joiner]) == start + n + int(completes)
    np.testing.assert_allclose(np.asarray(got_logits)[active],
                               np.asarray(want_logits)[active],
                               rtol=2e-2, atol=2e-2)
    # rows written: the span's n, and one for each decode row (the
    # joiner's, active or not, at start + n)
    written = {(bt[0, 2], 8), (bt[1, 1], 1),
               (bt[joiner, (start + n) // PAGE], (start + n) % PAGE)}
    written |= {(bt[joiner, p // PAGE], p % PAGE)
                for p in range(start, start + n)}
    for key in (f"{kv}{i}" for i in range(cfg.depth) for kv in ("pk", "pv")):
        before, g, w = (np.asarray(c[key]) for c in (cache, got, want))
        # page 0 takes the padding rows and the empty slot's: garbage
        np.testing.assert_allclose(g[1:], w[1:], rtol=2e-2, atol=2e-2)
        for p in aliased:
            np.testing.assert_array_equal(g[p], before[p])
        changed = {(int(p), int(r)) for p, r in
                   zip(*np.nonzero((g != before).any(axis=(2, 3))))
                   if p != 0}
        assert changed == {(int(p), int(r)) for p, r in written}, key


def test_riding_step_declines_a_block_beyond_the_first():
    cfg = LMConfig(vocab=64, dim=32, heads=4, kv_heads=2, depth=2,
                   max_seq=64, remat=False)
    _prefill, _step, riding = T.make_paged_batch_decode(cfg, PAGE,
                                                        chunk=CHUNK)
    with pytest.raises(T.UnsupportedBlock, match="riding step"):
        riding()


# -- (b) through the batcher --------------------------------------------------

BASE = 2 * PAGE     # the shared document: two full pages


def _docs(rows):
    """A first question that leaves ``BASE`` tokens in the prefix
    cache, and a second over the same document whose context has
    ``rows`` more."""
    base = _prompt(5, BASE)
    return (np.concatenate([base, _prompt(6, 9)]),
            np.concatenate([base, _prompt(7 + rows, rows + 1)]))


@pytest.mark.parametrize("rows", [1, 63, 64, 65, 130])
def test_partial_hit_and_its_neighbours_get_their_own_tokens(
        model, rows, monkeypatch):
    """A session whose context shares two pages with the cache fills
    the other ``rows`` by slices of 64, each on board a step that a
    neighbour decodes in; only the last activates it.  Both are served
    what ``generate`` serves them alone."""
    cfg, params = model
    _reset_sched_for_tests()
    bat = ContinuousBatcher(cfg, params, slots=3, page=PAGE,
                            idle_linger_s=0.2)
    first, second = _docs(rows)
    _finish(_join(bat, first, 2))
    activated = []              # slices run when a session went live
    orig = ContinuousBatcher._activate
    monkeypatch.setattr(
        ContinuousBatcher, "_activate",
        lambda self, sess: (activated.append(self._slices),
                            orig(self, sess))[1])
    before = _look(bat)
    beside = _prompt(40, 11)
    a = _join(bat, beside, 24)
    _wait(lambda: len(a.tokens) >= 2, "the neighbour never decoded")
    b = _join(bat, second, 5)
    _finish(a, b)
    _quiet(bat)
    assert a.tokens == _want(model, beside, 24)
    assert b.tokens == _want(model, second, 5)
    assert a.close_reason == b.close_reason == "finished"
    slices = -(-rows // CHUNK)
    look = _look(bat)
    assert look["slices"] - before["slices"] == slices
    assert look["slices_rode"] - before["slices_rode"] == slices
    assert sched_counters()["sched_catchup_slice"] == slices
    assert activated == [look["slices"]]
    assert bat.prefills_run == 2            # the hit avoided a third
    # a step a slice, none of its own: the neighbour's 24, and
    # whatever of the joiner's 5 ran after the neighbour's last
    assert look["ahead"] + look["sync"] == bat.steps_run()
    # one riding program whatever the slice's length, and the slice's
    # own program never traced
    assert bat._step_riding.func._cache_size() == 1
    assert bat._chunk_j.func._cache_size() == 0
    assert bat._step.func._cache_size() == 1


# -- (c) a hang-up with the slice in flight -----------------------------------

def test_hang_up_with_a_slice_in_flight_and_a_neighbour_finishing(
        model):
    """Under a budget of four rows a round a partial hit fills by
    fifty slices, each on board a step.  Its client hangs up while the
    step that is its neighbour's LAST carries one: the next pass evicts
    the joiner (its pages go back with that step unread) and, landing
    the step, the neighbour.  What came of the joiner's pages serves
    the next sessions their own tokens."""
    cfg, params = model
    bat = ContinuousBatcher(cfg, params, slots=2, page=PAGE,
                            idle_linger_s=0.2, prefill_chunk_tokens=4)
    first, second = _docs(200)
    _finish(_join(bat, first, 2))
    _quiet(bat)
    held = bat.kv_stats()["alloc"]["in_use"]    # the prefix cache's
    beside = _prompt(41, 9)
    a, b = _Stream(), _Stream()
    hung = []
    riding = bat._step_riding

    def spy(*args):
        out = riding(*args)
        sa = next((s for s in bat._sessions.values()
                   if s.stream is a), None)
        sb = next((s for s in bat._sessions.values()
                   if s.stream is b), None)
        if not hung and sa is not None and sb is not None \
                and sa.queued + 1 == sa.max_new \
                and int(args[-4]) == sb.slot:
            b.closed = True     # its slice is on the step just queued
            hung.append(bat.steps_run())
        return out

    bat._step_riding = spy
    bat.join(a, beside, 12)
    bat.join(b, second, 5)
    _finish(a)
    _quiet(bat)
    assert hung, "the joiner was not filling at the neighbour's last step"
    assert a.tokens == _want(model, beside, 12)
    assert a.close_reason == "finished"
    assert b.tokens == [] and b.close_reason is None
    # both left in the pass after the hang-up: no step was queued in it
    assert bat.steps_run() == hung[0] + 1
    assert not bat._sessions
    assert bat.kv_stats()["alloc"]["in_use"] == held
    c, d = _join(bat, second, 5), _join(bat, beside, 6)
    _finish(c, d)
    assert c.tokens == _want(model, second, 5)
    assert d.tokens == _want(model, beside, 6)


# -- (d) the counters, and what is never traced -------------------------------

def test_no_filling_slot_never_traces_the_riding_step(model):
    cfg, params = model
    bat = ContinuousBatcher(cfg, params, slots=2, page=PAGE,
                            idle_linger_s=0.2)
    asks = [(_prompt(50 + i, n), 6) for i, n in enumerate((40, 7, 90))]
    streams = [_join(bat, p, m) for p, m in asks]
    _finish(*streams)
    _quiet(bat)
    for (p, m), st in zip(asks, streams):
        assert st.tokens == _want(model, p, m)
    look = _look(bat)
    assert look["slices"] == look["slices_rode"] == 0
    assert bat._step_riding.func._cache_size() == 0
    assert bat._step.func._cache_size() == 1
    # every slice that follows finds a step to board
    first, second = _docs(70)
    _finish(_join(bat, first, 2))
    _quiet(bat)
    steps = bat.steps_run()
    _finish(_join(bat, second, 3))
    _quiet(bat)
    look = _look(bat)
    assert look["slices"] == 2 and look["slices_rode"] / look["slices"] == 1.0
    # the first of the two boarded a step with no decode row on it
    assert bat.steps_run() - steps == 1 + 3
    assert look["ahead"] + look["sync"] == bat.steps_run()


# -- (e) what cannot ride -----------------------------------------------------

def test_a_budget_of_two_slices_a_round_rides_the_second(model):
    """``prefill_chunk_tokens`` 16: a long prompt takes whole rounds
    until its last five rows leave eleven of a round to the prompt
    that waits behind it: that round's first slice is a program of its
    own, its second boards the step."""
    cfg, params = model
    bat = ContinuousBatcher(cfg, params, slots=2, page=PAGE,
                            prefill_chunk_tokens=16, idle_linger_s=0.2)
    x, y = _prompt(61, 6 * 16 + 5 + 1), _prompt(62, 60)
    sx, sy = _join(bat, x, 4), _join(bat, y, 4)
    _finish(sx, sy)
    _quiet(bat)
    assert sx.tokens == _want(model, x, 4)
    assert sy.tokens == _want(model, y, 4)
    look = _look(bat)
    # x: six whole slices and one of five; y: 11 + 16 + 16 + 16
    assert look["slices"] == 7 + 4
    assert look["slices_rode"] == look["slices"] - 1
    assert bat._chunk_j.func._cache_size() == 1
