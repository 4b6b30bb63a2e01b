"""Parts of the engine thread's host time, per decode step of the device,
from the split timeline of ``LLMEngine.step()``: inside ``step.admit`` the
phases ``admit.paged`` (the block manager: reservations, prompt blocks
published, a cancelled prefill's blocks released) and ``admit.launch`` (the
admission's device calls with their input copies), inside ``step.dispatch``
the phase ``dispatch.launch`` (the decode or verify call with its input
copies); and the ``gc_ms`` attr of each ``engine.step`` (the interpreter's
collections inside it).

``phases``: the names whose durations are summed, children of an
``engine.step`` only. ``attr``: an attr of the ``engine.step`` spans to sum
instead, in milliseconds. Either is divided by the ``device_steps`` of the
``step.wait`` spans. Only the quiet part of the window is read
(``readers/quiet.py``), as ``engine_host.py`` reads it.

A timeline without the split (no ``admit.launch``, ``admit.paged`` or
``dispatch.launch`` span; no ``gc_ms`` attr) gives None: a program from
before it records ``step.admit`` and ``step.dispatch`` whole.
"""

from readers import quiet

SPLIT = {"admit.launch", "admit.paged", "dispatch.launch"}


def read(run, phases=(), attr=None):
    spans = quiet.spans(run)
    steps = {s["span_id"]: s for s in spans if s["name"] == "engine.step"}
    kids = [s for s in spans if s.get("parent_id") in steps]
    bottom = sum(s["attrs"].get("device_steps", 0) for s in kids
                 if s["name"] == "step.wait")
    if attr is not None:
        vals = [s["attrs"][attr] for s in steps.values()
                if attr in s["attrs"]]
        top_ms = sum(vals) if vals else None
    elif any(s["name"] in SPLIT for s in kids):
        top_ms = 1000.0 * sum(s["t1"] - s["t0"] for s in kids
                              if s["name"] in phases)
    else:
        top_ms = None
    if top_ms is None or not bottom:
        return None
    return top_ms / bottom
