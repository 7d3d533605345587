"""A share of the expert layers' routing counts over the window.

spec: ``{"num": "<count>", "den": ["<count or constant>", ...]}``: keys
of ``kv_stats()["moe"]``.  The numerator's growth over the window
against the PRODUCT of the denominator's entries: a count that grows
(``steps``, ``rows``) by its growth, a constant of the schedule
(``held``, ``layers``, ``top_k``) as it stands; in percent.  Nothing
where the program keeps no such counts or no step ran."""

GROWS = ("steps", "rows", "local_pairs", "experts_touched")


def read(run, spec):
    try:
        c0, c1 = run.c0["kv"]["moe"], run.c1["kv"]["moe"]
    except (KeyError, TypeError):
        return None
    den = 1.0
    for key in spec["den"]:
        den *= (c1[key] - c0[key]) if key in GROWS else c1[key]
    if not den:
        return None
    return 100.0 * (c1[spec["num"]] - c0[spec["num"]]) / den
