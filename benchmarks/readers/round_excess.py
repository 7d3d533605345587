"""What a class of decode steps costs the live streams beyond a plain
step, in milliseconds.

spec: ``{"class": "fill" | "ride"}``.  The program counts each decode
step under what stood in front of it in the device's queue
(``kv_stats()["rounds"]``, ``benchmarks/ROUNDS.md``): ``n`` steps of a
class and ``gap_ns``, the time from the step before it landing to its
own landing, which is what every live stream waited for that token.

Returns (growth of the class's ``gap_ns`` / growth of its ``n``) minus
the same of ``plain``, in ms: the stall a join (``fill``) or a riding
slice (``ride``) adds to one gap of every live stream.  A program
without the counters, or a window in which either class stood still,
gives nothing."""

ROUNDS = ["kv", "rounds"]


def read(run, spec):
    try:
        r0 = run.counter(run.c0, ROUNDS)
        r1 = run.counter(run.c1, ROUNDS)
        mean = {}
        for cls in (spec["class"], "plain"):
            n = r1[cls]["n"] - r0[cls]["n"]
            if not n:
                return None
            mean[cls] = (r1[cls]["gap_ns"] - r0[cls]["gap_ns"]) / n
    except KeyError:
        return None
    return (mean[spec["class"]] - mean["plain"]) / 1e6
