"""A statistic over the program's spans that lie inside the window.

``span``: the span's name. ``attr``: the attribute to take (default: the
span's duration in seconds). ``weight``: an attribute to weight a mean by.
``stat``: ``mean`` or ``p<q>``.
"""

from lib import window


def read(run, span, attr=None, weight=None, stat="mean"):
    rows = []
    for s in run.spans:
        if s["name"] != span:
            continue
        v = s["t1"] - s["t0"] if attr is None else s["attrs"].get(attr)
        w = 1.0 if weight is None else s["attrs"].get(weight)
        if v is not None and w is not None:
            rows.append((float(v), float(w)))
    if not rows:
        return None
    if stat == "mean":
        return sum(v * w for v, w in rows) / sum(w for _, w in rows)
    return window.percentile([v for v, _ in rows], float(stat[1:]))
