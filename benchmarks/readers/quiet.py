"""A statistic over the program's spans in the quiet part of the window: the
part before the profiler was started.

Starting and, above all, stopping the profiler stall the engine thread (0.05
s and 2.2-2.5 s on the chip, PERF.md section 5, PR 26); an open loop's queue
then takes seconds to drain, so a span statistic taken over the whole window
of a traced run reads the stalls, not the program. The harness starts the
profiler between two engine steps and notes the instant it came back
(``run.t_trace[0]``). The quiet part ends where the last ``engine.step`` span
that closed before that instant closed: the first stall lies between the
two, the second after it. Only spans that had ended by then are read. A
program that records no ``engine.step`` span gives no cut, and nothing is
read.

Arguments are ``span_stat``'s (``span``, ``attr``, ``weight``, ``stat``).
"""

import types

from readers import span_stat


def spans(run):
    """The spans that had ended when the last ``engine.step`` before the
    profiler's start closed."""
    if not run.t_trace:
        return []
    t_start = run.t_trace[0]
    cut = max((s["t1"] for s in run.spans
               if s["name"] == "engine.step" and s["t1"] <= t_start),
              default=None)
    if cut is None:
        return []
    return [s for s in run.spans if s["t1"] <= cut]


def read(run, **args):
    return span_stat.read(types.SimpleNamespace(spans=spans(run)), **args)
