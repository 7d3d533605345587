"""Every counter path a registered benchmark metric names resolves
(ISSUE 42): the readers ``counter_ratio`` / ``counter_ratio_if_present``
walk lists of keys into ``{"engine": engine.telemetry(), "kv":
kv_stats()}`` (``benchmarks/harness/serving.py``,
``benchmarks/harness/series.py``), and the second turns a key that is
gone into a silent ``None``.  Here each ``kv`` path of each metric file
that ``BENCHMARK.json``'s ``per_layer`` registers is walked the same way
into the ``kv_stats()`` of a toy batcher that has served one short
request: it has to end at a number, or at a dict of numbers (which the
reader sums).  ``engine`` paths are the native side's table, not walked
here; a file with none but those is not collected, nor is a file no
entry registers (nobody reads it until one does).
"""

import functools
import json
import numbers
import os

import jax
import jax.numpy as jnp
import pytest
from test_lookahead import _finish, _join, _prompt, _quiet

from brpc_tpu.models import transformer_lm as T
from brpc_tpu.models.lm_service import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("counter_ratio", "counter_ratio_if_present")

def _registered():
    """``(metric, [kv paths])`` of every registered counter metric that
    names a ``kv`` path."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = []
    for entry in bench["per_layer"]:
        path = os.path.join(ROOT, "benchmarks", "metrics",
                            entry["name"] + ".json")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            m = json.load(f)
        if m.get("reader") not in READERS:
            continue
        den = m.get("den")
        paths = [p for p in m["num"] + (den if isinstance(den, list) else [])
                 if p[0] == "kv"]
        if paths:
            out.append((entry["name"], paths))
    return out


def _first_block():
    cfg = T.LMConfig(vocab=64, dim=32, heads=4, depth=2, max_seq=64,
                     remat=False)
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg), 8


def _state_block():
    from test_hybrid_lm import PAGE, _cfg
    cfg = _cfg()
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg), PAGE


def _window_block():
    from test_window_experts import PAGE, _bench
    cfg, m = _bench()
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    m.make_params(cfg, 3))
    return T.LMConfig(remat=False, **m.lm_kwargs(cfg)), params, PAGE


def _early_routed_block():
    from test_early_routed_experts import TOY
    from test_window_experts import PAGE, _bench
    cfg, m = _bench(TOY)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    m.make_params(cfg, 3))
    return T.LMConfig(remat=False, **m.lm_kwargs(cfg)), params, PAGE


# the toy whose ``kv_stats()`` has a section: the first block's, unless
# the section is another block's own.  (Every section the registered
# files name on this tree is reached by one of the three; a file whose
# section none can reach gets a line here saying why, not a silent drop.)
TOY_OF_SECTION = {"state": _state_block, "window": _window_block}


@functools.lru_cache(maxsize=None)
def _kv_stats(toy) -> dict:
    """What the harness snapshots under ``"kv"``, of a toy batcher that
    has served one short request and gone quiet."""
    cfg, params, page = toy()
    bat = ContinuousBatcher(cfg, params, slots=2, page=page,
                            idle_linger_s=0.2)
    st = _join(bat, _prompt(7, 9, vocab=cfg.vocab), 3)
    _finish(st)
    _quiet(bat)
    assert st.close_reason == "finished" and len(st.tokens) == 3
    return bat.kv_stats()


def _is_count(v) -> bool:
    return isinstance(v, numbers.Real) and not isinstance(v, bool)


CASES = _registered()
assert CASES, "BENCHMARK.json registers no counter metric with a kv path"


def _walk(metric, paths, toy_of):
    for path in paths:
        snap = {"kv": _kv_stats(toy_of(path[1]))}
        for i, key in enumerate(path):
            assert isinstance(snap, dict) and key in snap, \
                (metric, path, f"no {key!r} at {path[:i]}")
            snap = snap[key]
        if isinstance(snap, dict):
            assert snap and all(_is_count(v) for v in snap.values()), \
                (metric, path, snap)
        else:
            assert _is_count(snap), (metric, path, snap)


@pytest.mark.parametrize("metric,paths", CASES,
                         ids=[name for name, _ in CASES])
def test_a_registered_metrics_kv_paths_end_at_counters(metric, paths):
    _walk(metric, paths, lambda sec: TOY_OF_SECTION.get(sec, _first_block))


# the cell ``smallthinker-21b.longdoc`` reads every ``*.longdoc_*``
# metric: each of their paths, on a toy of ITS block (a router at the
# layer's input over two page classes), whatever the section
LONGDOC = [c for c in CASES if ".longdoc_" in c[0]]
assert LONGDOC


@pytest.mark.parametrize("metric,paths", LONGDOC,
                         ids=[name for name, _ in LONGDOC])
def test_a_longdoc_metrics_kv_paths_on_the_early_routed_block(metric, paths):
    _walk(metric, paths, lambda _sec: _early_routed_block)
