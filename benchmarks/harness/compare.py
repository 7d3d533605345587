"""The comparison that decides ``correct``.

The configuration's plain reference (``models/<model>.py Reference``)
runs ONCE over a served request's prompt followed by the tokens that
were served (teacher forced), and reads, at every served position, how
far the served token's logit lies below the reference's best, in units
of that position's logit standard deviation.  Two numbers come of it:
the widest such gap over the sample, and the mean gap over its tokens.
A greedy server that computes what the configuration states misses the
best token only on near-ties; a lower precision misses it by more and
more often.  ``judge`` holds them, with the streams that did not finish
and the programs compiled inside the window, to the limits the
configuration's file states.

The control (``Reference(..., int8=True)``) is put in the program's
place through the same two functions: it does not decode, but at each
position of the same prompts and tokens it puts some token first, and
those tokens are compared and judged as the served ones are
(``compare(..., tokens_of=control)``).  It has to come out not correct.
"""

from __future__ import annotations

import numpy as np


def gaps_below_best(logits: np.ndarray, tokens) -> np.ndarray:
    """For each row, how far ``tokens[row]``'s logit lies below the
    row's best, in units of the row's standard deviation."""
    rows = np.arange(len(tokens))
    return (logits.max(axis=-1) - logits[rows, np.asarray(tokens)]) \
        / logits.std(axis=-1)


def pick_sample(finished: list, seed: int, n: int) -> list:
    """``n`` of the finished requests, drawn from the seed, with the
    longest among them and, where the mix has later turns (prefix
    hits), a first turn and a later turn."""
    if not finished:
        return []
    rng = np.random.default_rng([seed, 6])
    order = [finished[i] for i in rng.permutation(len(finished))]
    longest = max(finished, key=lambda r: len(r.prompt) + len(r.tokens))
    picked = [longest]
    for want_later in (False, True):
        for r in order:
            if (r.turn > 0) == want_later and r not in picked:
                picked.append(r)
                break
    for r in order:
        if len(picked) >= n:
            break
        if r not in picked:
            picked.append(r)
    return picked[:max(n, 1)]


def compare(ref, sample: list, tokens_of=None) -> dict:
    """The reference's readings of the served tokens over ``sample``;
    with ``tokens_of`` (the control, in the program's place), of the
    tokens IT puts first at the same positions."""
    widest, total, n_tokens, n_off, finite = 0.0, 0.0, 0, 0, True
    for r in sample:
        served = np.asarray(r.tokens, np.int32)
        logits = ref.served_logits(r.prompt, served)
        finite = finite and bool(np.isfinite(logits).all())
        tokens = served if tokens_of is None else \
            tokens_of.served_logits(r.prompt, served).argmax(axis=-1)
        gaps = gaps_below_best(logits, tokens)
        widest = max(widest, float(gaps.max()))
        total += float(gaps.sum())
        n_tokens += len(served)
        n_off += int((gaps > 0).sum())
    return {"logit_gap_std": widest, "tokens_compared": n_tokens,
            "tokens_off_best": n_off, "requests_compared": len(sample),
            "mean_gap_std": total / max(n_tokens, 1), "finite": finite}


def judge(got: dict, limits: dict, unfinished: int,
          compiled_in_window: int) -> tuple:
    """``(correct, compared)``: every number compared beside its limit.
    ``limits`` is the configuration file's ``correct`` group: it says
    which of the reference's readings are held, and to what; the others
    are printed without one."""
    compared = {name: {"value": got[name], "limit": limit}
                for name, limit in limits.items()
                if isinstance(limit, (int, float))}
    for name in ("logit_gap_std", "mean_gap_std", "tokens_off_best",
                 "tokens_compared"):
        compared.setdefault(name, {"value": got[name]})
    compared["requests_compared"] = {"value": got["requests_compared"],
                                     "at_least": 1}
    compared["streams_unfinished"] = {"value": unfinished, "limit": 0}
    compared["compiled_in_window"] = {"value": compiled_in_window,
                                      "limit": 0}
    correct = bool(got["finite"] and got["requests_compared"] >= 1
                   and all(c["value"] <= c["limit"]
                           for c in compared.values() if "limit" in c))
    return correct, compared
