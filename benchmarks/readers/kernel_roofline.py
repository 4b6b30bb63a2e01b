"""Share of its roofline that the paged-attention decode kernel reaches.

The kernel is bound by memory: the least it can move in one decode step is
the K and V of every live token (``lib/roofline.decode_kernel_bytes``). Live
tokens are the mean over the engine steps sampled while tracing; decode steps
in the trace are the kernel's executions over the layers. The share is
(bytes / peak bytes per second) / kernel seconds.
"""

from lib import peaks, roofline
from readers import device_time


def read(run, program, op):
    if run.trace is None:
        return None
    kernel_s = device_time.seconds(run.trace, program, op)
    steps = device_time.decode_steps(run, program, op)
    t0, t1 = run.t_trace
    live = [s[1] for s in run.samples if t0 <= s[0] <= t1]
    if not kernel_s or not steps or not live:
        return None
    least_s = roofline.decode_kernel_bytes(
        run.config, sum(live) / len(live)) * steps \
        / peaks.peaks(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
