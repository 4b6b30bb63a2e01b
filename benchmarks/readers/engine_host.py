"""Host work of the engine thread, from the timeline ``LLMEngine.step()``
records: one ``engine.step`` span a step and, tiling it, phase spans that
name it as parent. Rule of the names: in a phase whose name ends in ``.wait``
(``step.wait``, ``prefill.wait``) the host is blocked on the device; every
other instant of an ``engine.step`` is host work, its self time included.

Host seconds = the durations of the ``engine.step`` spans minus those of
their ``*.wait`` children. ``per``:
  ``decode_step``  over the ``device_steps`` of the ``step.wait`` spans:
                   milliseconds of host work per decode step of the device
  ``wall``         over the time from the first of those ``engine.step``
                   spans to the last, in percent: at 100 the host sets the
                   pace and the device waits for it

Only the quiet part of the window is read (``readers/quiet.py``).
"""

from readers import quiet


def read(run, per):
    spans = quiet.spans(run)
    steps = {s["span_id"]: s for s in spans if s["name"] == "engine.step"}
    if not steps:
        return None
    waits = [s for s in spans if s["name"].endswith(".wait")
             and s.get("parent_id") in steps]
    host_s = sum(s["t1"] - s["t0"] for s in steps.values()) \
        - sum(s["t1"] - s["t0"] for s in waits)
    if per == "decode_step":
        bottom = sum(s["attrs"].get("device_steps", 0) for s in waits
                     if s["name"] == "step.wait")
        scale = 1000.0
    elif per == "wall":
        bottom = max(s["t1"] for s in steps.values()) \
            - min(s["t0"] for s in steps.values())
        scale = 100.0
    else:
        raise ValueError(f"unknown divisor {per!r}")
    if not bottom:
        return None
    return scale * host_s / bottom
