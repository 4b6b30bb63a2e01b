"""Device time of some operations in the traced window, over a divisor.

``program`` / ``op``: regular expressions on the jitted program's name and on
the operation's name; without ``op`` the whole program's executions count.
``per``:
  ``busy``          the device's busy time (a share; use ``scale`` 100)
  ``decode_steps``  decode steps in the trace: executions of the kernel
                    matching ``step_op`` inside ``program`` over the layers
  ``prompt_ktok``   thousands of prompt tokens admitted while tracing
                    (``request.queue`` spans that ended inside the trace)
"""

import re


def seconds(trace, program, op=None):
    if op is None:
        return sum(v["seconds"] for k, v in trace["programs"].items()
                   if re.search(program, k))
    return sum(o["seconds"] for o in trace["ops"]
               if re.search(program, o["program"])
               and re.search(op, o["name"]))


def decode_steps(run, program, step_op):
    n = sum(o["count"] for o in run.trace["ops"]
            if re.search(program, o["program"])
            and re.search(step_op, o["name"]))
    return n / run.config["num_hidden_layers"]


def read(run, program, per, op=None, step_op=None, scale=1.0):
    if run.trace is None:
        return None
    top = seconds(run.trace, program, op)
    if per == "busy":
        bottom = run.trace["busy_s"]
    elif per == "decode_steps":
        bottom = decode_steps(run, program, step_op)
    elif per == "prompt_ktok":
        t0, t1 = run.t_trace
        bottom = sum(s["attrs"].get("prompt_tokens", 0) for s in run.spans
                     if s["name"] == "request.queue"
                     and t0 <= s["t1"] <= t1) / 1000.0
    else:
        raise ValueError(f"unknown divisor {per!r}")
    if not top or not bottom:
        return None
    return scale * top / bottom
