"""The one traffic generator.  A mix is a data file; this reads it.

A mix fixes the MULTISET of every quantity it draws (shared-prefix
lengths, prompt lengths, output lengths, arrival gaps) as a grid of
strata; ``--seed`` chooses token ids and the ORDER inside each block of
strata (or not even that, with ``"order": "fixed"``).  So any two seeds
offer the same work per block: the same tokens to prefill, the same
tokens to generate, the same gaps.

The file's keys::

    loop         "closed": ``clients`` callers, each starts its next
                 session when the last one closed;
                 "open": a session starts every gap, at ``rate_rps``
                 sessions a second, whatever the system does
    clients      closed loop: number of callers
    rate_rps     open loop: mean arrival rate (a cell's own file under
                 cells/ may set it)
    gap          open loop: strata of the gap, in units of the mean gap
    order        "shuffled" (default) or "fixed"
    session      turns        requests of one session, sent in turn
                 shared_len   tokens every turn of a session starts with
                              (a document); optional
                 prompt_len   a turn's own tokens, after the shared ones
                 output_len   tokens asked for (max_new)
    warmup       sessions, requests or seconds of the traffic itself
                 that run before the window opens
    who, why     one line each

A quantity is ``{"dist": ..., "strata": n, ...}``: ``list`` (values),
``uniform`` (min, max), ``lognormal`` (median, sigma, min, max),
``exponential`` (mean): the grid is the distribution's quantiles at
(i + 0.5) / n, rounded to whole numbers unless ``"real": true``.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def grid(q: dict) -> list:
    """The strata of one quantity: a quantile grid of its distribution."""
    dist = q["dist"]
    if dist == "list":
        vals = list(q["values"])
    else:
        n = int(q["strata"])
        us = [(i + 0.5) / n for i in range(n)]
        if dist == "uniform":
            vals = [q["min"] + u * (q["max"] - q["min"]) for u in us]
        elif dist == "lognormal":
            nd = NormalDist()
            vals = [q["median"] * math.exp(q["sigma"] * nd.inv_cdf(u))
                    for u in us]
        elif dist == "exponential":
            vals = [-q["mean"] * math.log(1.0 - u) for u in us]
            # the grid's mean falls short of the distribution's (the
            # tail's stratum is cut at its median): scale it back, so
            # that ``rate_rps`` is the rate offered
            scale = q["mean"] * n / sum(vals)
            vals = [v * scale for v in vals]
        else:
            raise ValueError(f"unknown dist {dist!r}")
        lo, hi = q.get("min"), q.get("max")
        if lo is not None:
            vals = [max(lo, v) for v in vals]
        if hi is not None:
            vals = [min(hi, v) for v in vals]
    if not q.get("real"):
        vals = [int(round(v)) for v in vals]
    return vals


class Strata:
    """Endless draws of one quantity: every ``len(grid)`` consecutive
    draws are one permutation of the grid."""

    def __init__(self, q: dict, seed: int, lane: int, fixed: bool):
        self.grid = grid(q)
        self._seed, self._lane, self._fixed = seed, lane, fixed
        self._block, self._left = 0, []

    def draw(self):
        if not self._left:
            vals = list(self.grid)
            if not self._fixed:
                rng = np.random.default_rng(
                    [self._seed, self._lane, self._block])
                vals = [vals[i] for i in rng.permutation(len(vals))]
            self._block += 1
            self._left = vals[::-1]
        return self._left.pop()


class Request:
    __slots__ = ("session", "turn", "prompt", "max_new", "shared",
                 "due", "sent", "stamps", "tokens", "closed", "reason",
                 "error", "released")

    def __init__(self, session: int, turn: int, prompt, max_new: int,
                 shared: int):
        self.session, self.turn = session, turn
        self.prompt, self.max_new, self.shared = prompt, max_new, shared
        self.due = self.sent = self.closed = self.released = None
        self.stamps: list = []          # receipt time of every token
        self.tokens: list = []          # the tokens, as received
        self.reason = self.error = None


class Plan:
    """The sessions of one run, made on demand from the seed."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        fixed = mix.get("order", "shuffled") == "fixed"
        s = mix["session"]
        self.mix = mix
        self.loop = mix["loop"]
        self.turns = int(s.get("turns", 1))
        self._shared = Strata(s["shared_len"], seed, 1, fixed) \
            if "shared_len" in s else None
        self._prompt = Strata(s["prompt_len"], seed, 2, fixed)
        self._output = Strata(s["output_len"], seed, 3, fixed)
        self._gap = Strata({**mix["gap"], "real": True}, seed, 4, fixed) \
            if self.loop == "open" else None
        self._vocab = vocab
        self._seed = seed
        self._n = 0

    def next_gap(self) -> float:
        """Seconds to the next arrival (open loop)."""
        return self._gap.draw() / float(self.mix["rate_rps"])

    def next_session(self) -> list:
        """The next session's requests, in the order they are sent."""
        sid = self._n
        self._n += 1
        rng = np.random.default_rng([self._seed, 5, sid])
        n_shared = self._shared.draw() if self._shared else 0
        shared = rng.integers(0, self._vocab, (n_shared,), dtype=np.int32)
        reqs = []
        for turn in range(self.turns):
            own = rng.integers(0, self._vocab, (self._prompt.draw(),),
                               dtype=np.int32)
            reqs.append(Request(sid, turn, np.concatenate([shared, own]),
                                int(self._output.draw()), n_shared))
        return reqs

    def block(self) -> list:
        """One session for every stratum of the longest-gridded
        quantity: every length the mix can offer, once.  Set-up plays
        it (with one token asked of each request) to compile every
        shape before the window opens."""
        n = max(len(s.grid) for s in (self._shared, self._prompt,
                                      self._output) if s is not None)
        twin = Plan(self.mix, self._seed ^ 0x5eed, self._vocab)
        return [twin.next_session() for _ in range(n)]
