"""The decode program of ``qwen3-next-80b-a3b-l8``: its step, the GDN and
GQA decode kernels' shares of their rooflines, and the routed experts' share
of theirs.

A decode step runs the GDN kernel (``kernel``, ``ops/pallas_gdn.py``) once a
GDN layer, so the decode steps in the trace are the kernel's executions over
``lib/qwen3_next.gdn_layers`` (``device_time.decode_steps`` divides by all
layers, two of which run another kernel). ``what``:

  ``step_ms``          device seconds of ``program`` a decode step, in ms
  ``kernel_roofline``  least bytes of the kernel (each live slot's ``S`` read
                       and written once a GDN layer: ``lib/qwen3_next.
                       gdn_kernel_bytes``, the live slots from the
                       ``state_slots`` of the ``decode.step`` spans that ended
                       while tracing, weighted by their device steps) over
                       the peak bandwidth, against the kernel's seconds
  ``attn_roofline``    K and V of every live token once a full-attention
                       layer (``lib/qwen3_next.attn_kernel_bytes``, the live
                       tokens the mean of the engine's samples while
                       tracing) over the peak bandwidth, against the seconds
                       of the paged GQA kernel (``op``)
  ``expert_roofline``  the three matrices of every HELD expert hit
                       (``experts_hit`` of the same spans, summed over steps
                       and layers) over the peak bandwidth, against the
                       seconds of the grouped products (``op``)

Where the program has no such kernel, spans or counters, nothing is read.
"""

import re

from lib import mla_moe, peaks, qwen3_next
from readers import device_time


def decode_steps(run, program, kernel):
    n = sum(o["count"] for o in run.trace["ops"]
            if re.search(program, o["program"])
            and re.search(kernel, o["name"]))
    return n / qwen3_next.gdn_layers(run.config)


def _chunks(run):
    t0, t1 = run.t_trace
    return [s["attrs"] for s in run.spans
            if s["name"] == "decode.step" and t0 <= s["t1"] <= t1]


def read(run, what, program, kernel, op=None):
    if run.trace is None:
        return None
    steps = decode_steps(run, program, kernel)
    if not steps:
        return None
    bw = peaks.peaks(run.device["kind"])["hbm_bytes_per_s"]
    if what == "step_ms":
        return 1000.0 * device_time.seconds(run.trace, program) / steps
    chunks = _chunks(run)
    device_steps = sum(a.get("device_steps", 0) for a in chunks)
    if what == "kernel_roofline":
        kernel_s = device_time.seconds(run.trace, program, kernel)
        weighted = [(a["state_slots"], a.get("device_steps", 0))
                    for a in chunks if "state_slots" in a]
        n = sum(w for _, w in weighted)
        if not kernel_s or not n:
            return None
        slots = sum(v * w for v, w in weighted) / n
        least_s = qwen3_next.gdn_kernel_bytes(run.config, slots) * steps / bw
        return 100.0 * least_s / kernel_s
    if what == "attn_roofline":
        attn_s = device_time.seconds(run.trace, program, op)
        t0, t1 = run.t_trace
        live = [s[1] for s in run.samples if t0 <= s[0] <= t1]
        if not attn_s or not live:
            return None
        least_s = qwen3_next.attn_kernel_bytes(
            run.config, sum(live) / len(live)) * steps / bw
        return 100.0 * least_s / attn_s
    if what == "expert_roofline":
        product_s = device_time.seconds(run.trace, program, op)
        hit = [a for a in chunks if "experts_hit" in a]
        if not product_s or not hit or not device_steps:
            return None
        hit_per_step = sum(a["experts_hit"] for a in hit) / device_steps
        least_s = steps * hit_per_step * mla_moe.expert_bytes(run.config) / bw
        return 100.0 * least_s / product_s
    raise ValueError(f"unknown reading {what!r}")
