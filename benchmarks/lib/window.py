"""The measured window: rates between commit instants, tails over requests.

Rule 2 of the benchmark (README.md): a rate is never "what completed between
two clock edges". The window opens at the first commit at or after ``t_open``
and closes at the last commit at or before ``seconds`` after that opening;
the work counted is what was committed after the first and up to the last of
those instants, and the divisor is the time between the two.
"""

from __future__ import annotations


def commit_window(commits, t_open: float, seconds: float):
    """``commits``: ``[(t, cumulative_count), ...]`` in time order, one entry
    per commit instant. Returns ``(t_first, t_last, count)`` or ``None`` when
    fewer than two commits fall inside."""
    inside = [(t, c) for t, c in commits if t >= t_open]
    if not inside:
        return None
    t_first, c_first = inside[0]
    inside = [(t, c) for t, c in inside if t <= t_first + seconds]
    t_last, c_last = inside[-1]
    if t_last <= t_first:
        return None
    return t_first, t_last, c_last - c_first


def commit_rate(commits, t_open: float, seconds: float):
    w = commit_window(commits, t_open, seconds)
    return None if w is None else w[2] / (w[1] - w[0])


def percentile(values, q: float):
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        return None
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def lost_time(commits, t_open: float, seconds: float, top: int = 4):
    """Where a rate lost time: every gap between commits in the window held
    against the median pace (seconds per unit of count). Returns the seconds
    lost beyond that pace in all gaps together and the ``top`` gaps that lost
    most, as ``(seconds_lost, offset_in_window)``. A stall the machine caused
    shows here as one gap of tenths of a second or more; logged, read by no
    metric."""
    rows = [(t1 - t_open, t1 - t0, c1 - c0)
            for (t0, c0), (t1, c1) in zip(commits, commits[1:])
            if t0 >= t_open and t1 <= t_open + seconds and c1 > c0]
    if len(rows) < 4:
        return None
    pace = percentile([gap / n for _, gap, n in rows], 50)
    lost = sorted(((gap - pace * n, off) for off, gap, n in rows),
                  reverse=True)
    return {"lost_s": round(sum(max(0.0, x) for x, _ in lost), 3),
            "worst": [(round(x, 3), round(off, 2)) for x, off in lost[:top]]}
