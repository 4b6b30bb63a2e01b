"""Share of its roofline that the routed experts' grouped product reaches in
the decode program, where the memory binds it: a step of some tens of rows
reads the three matrices of every expert that was hit and multiplies little.

Bytes: the program's own counter says how many distinct experts a decode chunk
hit, summed over its steps and expert layers (``experts_hit`` on its
``decode.step`` span, of ``device_steps`` steps). Their mean per step over the
chunks that ended while tracing, times the decode steps in the trace (the
attention kernel's executions over the layers) and the bytes of one expert, is
the least the grouped products moved. Time: the operations matching ``op`` in
``program``. Where the program has no such counter, nothing is read.
"""

from lib import mla_moe, peaks
from readers import device_time


def read(run, program, op, step_op):
    if run.trace is None:
        return None
    t0, t1 = run.t_trace
    chunks = [s["attrs"] for s in run.spans
              if s["name"] == "decode.step" and t0 <= s["t1"] <= t1
              and "experts_hit" in s["attrs"]]
    product_s = device_time.seconds(run.trace, program, op)
    steps = device_time.decode_steps(run, program, step_op)
    device_steps = sum(a.get("device_steps", 0) for a in chunks)
    if not chunks or not product_s or not steps or not device_steps:
        return None
    hit_per_step = sum(a["experts_hit"] for a in chunks) / device_steps
    least_s = steps * hit_per_step * mla_moe.expert_bytes(run.config) \
        / peaks.peaks(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / product_s
