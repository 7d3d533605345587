"""What one run recorded, and the series the readers take from it."""

from __future__ import annotations


class RunRecord:
    """Everything a reader may read.  ``trace`` is None in an untraced
    run; ``peaks`` and ``mem_peak_bytes`` are None off the chip."""

    def __init__(self, **kw):
        self.cell = kw["cell"]
        self.cfg = kw["cell"].config
        self.model = kw["cell"].model
        self.mix = kw["cell"].traffic
        self.seconds = kw["seconds"]
        self.t0, self.t1 = kw["t0"], kw["t1"]
        self.requests = kw["requests"]
        self.c0, self.c1 = kw["c0"], kw["c1"]
        self.trace = kw.get("trace")
        self.peaks = kw.get("peaks")
        self.mem_peak_bytes = kw.get("mem_peak_bytes")
        self.setup_s = kw["setup_s"]

    # -- requests and tokens of the window --------------------------------

    def due_in_window(self) -> list:
        return [r for r in self.requests if self.t0 <= r.due < self.t1]

    def tokens_between(self, a: float, b: float) -> int:
        return sum(1 for r in self.requests for t in r.stamps if a <= t <= b)

    def decoded_between(self, a: float, b: float) -> list:
        """``(live_positions,)`` of every token received in [a, b]: the
        j-th served token of a request attended over its prompt and
        the j tokens before it."""
        return [len(r.prompt) + j for r in self.requests
                for j, t in enumerate(r.stamps) if a <= t <= b]

    def admitted_between(self, a: float, b: float) -> list:
        """Requests whose FIRST token was received in [a, b]: their
        context was filled (prefill, prefix hit, catch-up) just before."""
        return [r for r in self.requests
                if r.stamps and a <= r.stamps[0] <= b]

    def program_durations(self, names: list) -> list:
        """Device seconds of every traced execution of the named
        compiled programs; nothing in an untraced run."""
        if self.trace is None:
            return []
        progs = self.trace["reduced"]["programs"]
        return [d for n in names for d in progs.get(n, [])]

    # -- series, in milliseconds -------------------------------------------

    def series(self, name: str) -> list:
        if name == "itl_ms":
            return [(r.stamps[i] - r.stamps[i - 1]) * 1e3
                    for r in self.requests for i in range(1, len(r.stamps))
                    if self.t0 <= r.stamps[i] <= self.t1]
        reqs = self.due_in_window()
        if name == "late_ms":
            return [(r.sent - r.released) * 1e3 for r in reqs]
        if name == "ttft_ms":
            return [(r.stamps[0] - r.due) * 1e3 for r in reqs if r.stamps]
        if name == "ttft_first_turn_ms":
            return [(r.stamps[0] - r.due) * 1e3 for r in reqs
                    if r.stamps and r.turn == 0]
        raise ValueError(f"unknown series {name!r}")

    def counter(self, snap: dict, path: list):
        for key in path:
            snap = snap[key]
        return snap

    def delta(self, path: list) -> float:
        return self.counter(self.c1, path) - self.counter(self.c0, path)


def percentile(values: list, q: float) -> float:
    """Linear interpolation between order statistics (numpy's default),
    in plain Python so the arithmetic is here to read."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
