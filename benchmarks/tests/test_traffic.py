"""Every seed offers the same work: identical multisets of shared,
prompt and output lengths and of gaps, for every mix the benchmark has."""
import glob
import os
from collections import Counter

import pytest

from benchmarks.harness import spec, traffic

MIXES = sorted(glob.glob(os.path.join(spec.BENCH_DIR, "traffic", "*.json")))
SEEDS = [0, 1, 2, 3, 7, 11, 42, 1234, 99991, 2 ** 31 - 1, 2 ** 31 + 5,
         4294967301]


def offered(mix: dict, seed: int, blocks: int = 3):
    plan = traffic.Plan(mix, seed, vocab=1000)
    per_block = max(len(s.grid) for s in (plan._shared, plan._prompt,
                                          plan._output) if s is not None)
    # whole blocks of the per-turn quantities too
    n = blocks * per_block * max(1, len(plan._prompt.grid))
    shared, prompt, out, gaps = Counter(), Counter(), Counter(), Counter()
    for _ in range(n):
        for r in plan.next_session():
            shared[r.shared] += 1
            prompt[len(r.prompt) - r.shared] += 1
            out[r.max_new] += 1
        if plan.loop == "open":
            gaps[round(plan.next_gap() * mix["rate_rps"], 9)] += 1
    return shared, prompt, out, gaps


@pytest.mark.parametrize("path", MIXES, ids=[os.path.basename(p) for p in MIXES])
def test_same_multisets_for_a_dozen_seeds(path):
    mix = spec.load_json(path)
    mix.setdefault("rate_rps", 1.0)
    first = offered(mix, SEEDS[0])
    assert sum(first[1].values()) > 0
    for seed in SEEDS[1:]:
        assert offered(mix, seed) == first, f"seed {seed} offers other work"


@pytest.mark.parametrize("path", MIXES, ids=[os.path.basename(p) for p in MIXES])
def test_seed_changes_tokens_and_same_seed_repeats(path):
    mix = spec.load_json(path)
    mix.setdefault("rate_rps", 1.0)
    a = traffic.Plan(mix, 5, 50000).next_session()
    b = traffic.Plan(mix, 5, 50000).next_session()
    c = traffic.Plan(mix, 6, 50000).next_session()
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert any(len(x.prompt) != len(y.prompt) or (x.prompt != y.prompt).any()
               for x, y in zip(a, c))


def test_fixed_order_is_the_file_s_order():
    mix = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic", "batch.json"))
    for seed in (1, 2):
        plan = traffic.Plan(mix, seed, 1000)
        got = [(len(r.prompt), r.max_new) for _ in range(16)
               for r in plan.next_session()]
        want = list(zip(mix["session"]["prompt_len"]["values"],
                        mix["session"]["output_len"]["values"])) * 2
        assert got == want


def test_later_turns_share_the_document():
    mix = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic", "docqa.json"))
    reqs = traffic.Plan(mix, 3, 1000).next_session()
    assert len(reqs) == mix["session"]["turns"]
    doc = reqs[0].prompt[:reqs[0].shared]
    for r in reqs:
        assert r.shared == reqs[0].shared
        assert (r.prompt[:r.shared] == doc).all()
        assert len(r.prompt) + r.max_new <= 2048


def test_grids():
    assert traffic.grid({"dist": "uniform", "min": 0, "max": 100,
                         "strata": 4}) == [12, 38, 62, 88]
    g = traffic.grid({"dist": "exponential", "mean": 1.0, "strata": 16,
                      "real": True})
    assert sum(g) == pytest.approx(16.0)          # the rate offered is the rate stated
    g = traffic.grid({"dist": "lognormal", "median": 200, "sigma": 1.0,
                      "min": 32, "max": 1024, "strata": 16})
    assert g[0] >= 32 and g[-1] == 1024 and g == sorted(g)
    assert 150 <= (g[7] + g[8]) / 2 <= 250


def test_block_covers_every_stratum():
    for path in MIXES:
        mix = spec.load_json(path)
        mix.setdefault("rate_rps", 1.0)
        plan = traffic.Plan(mix, 9, 1000)
        block = plan.block()
        lens = {len(r.prompt) - r.shared for s in block for r in s}
        assert lens == set(plan._prompt.grid)
        if plan._shared is not None:
            assert {s[0].shared for s in block} == set(plan._shared.grid)
