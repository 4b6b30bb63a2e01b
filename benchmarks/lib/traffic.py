"""Traffic: one general generator over a data file of frozen lists.

A traffic file (``benchmarks/traffic/<name>.json``) holds the *frozen* work of
a cell: ``pairs`` — a list of ``[prompt_len, output_len]`` — and, for an open
loop, ``gaps_unit`` — inter-arrival gaps of mean 1.0, one per pair, divided by
``rate_rps`` at run time. ``--seed`` never changes the work of a run: it makes
the weights and the token ids, and every run replays the frozen sequence
cyclically from its first entry. (PR 24 first let the seed rotate the
sequence; the phase at which the replay started was then the largest source of
spread, four times what two runs of one seed differ by. PERF.md section 6.)
The lists are written by ``freeze`` below, once, and
committed as numbers; ``python3 benchmarks/lib/traffic.py`` prints a fresh
pair of lists for a new file.
"""

from __future__ import annotations

import json
import math
import os
import random
import statistics

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def requests(traffic: dict):
    """Endless cyclic replay of the frozen sequence: yields ``(index,
    due_offset_s, prompt_len, output_len)``. ``due_offset_s`` is 0.0 for
    every request of a backlog (no ``gaps_unit``)."""
    pairs = traffic["pairs"]
    gaps = traffic.get("gaps_unit")
    rate = float(traffic.get("rate_rps", 0.0))
    due = 0.0
    i = 0
    while True:
        j = i % len(pairs)
        if gaps:
            due += gaps[j] / rate
        yield i, due, int(pairs[j][0]), int(pairs[j][1])
        i += 1


def period_s(traffic: dict) -> float:
    """Seconds in which one pass of an open loop's list falls due."""
    return sum(traffic["gaps_unit"]) / float(traffic["rate_rps"])


def token_ids(seed: int, index: int, length: int, vocab: int) -> list[int]:
    """Prompt ``index`` of a run: fresh ids for every request, so that a
    replayed pair never shares a prefix with its earlier self."""
    import numpy as np

    rng = np.random.default_rng([int(seed), int(index)])
    return rng.integers(1, vocab, length, dtype=np.int32).tolist()


# ---------------------------------------------------------------- freezing

def _lognormal_quantiles(n, median, sigma, lo, hi):
    nd = statistics.NormalDist()
    out = []
    for k in range(n):
        z = nd.inv_cdf((k + 0.5) / n)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


def freeze(n, prompt, output, schedule_seed):
    """Stratified quantiles of two clipped log-normals, paired and ordered
    by a seeded shuffle, and ``n`` exponential gaps scaled to mean 1."""
    rng = random.Random(schedule_seed)
    p = _lognormal_quantiles(n, *prompt)
    o = _lognormal_quantiles(n, *output)
    rng.shuffle(p)
    rng.shuffle(o)
    gaps = [rng.expovariate(1.0) for _ in range(n)]
    scale = n / sum(gaps)
    return ([[a, b] for a, b in zip(p, o)],
            [round(g * scale, 4) for g in gaps])


if __name__ == "__main__":
    import sys

    n, pm, ps, plo, phi, om, os_, olo, ohi, sseed = map(float, sys.argv[1:11])
    pairs, gaps = freeze(int(n), (pm, ps, plo, phi), (om, os_, olo, ohi),
                         int(sseed))
    print(json.dumps({"pairs": pairs, "gaps_unit": gaps}))
