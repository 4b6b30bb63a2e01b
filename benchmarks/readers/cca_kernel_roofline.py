"""Share of its roofline that the paged GQA decode kernel reaches on the
CCA model (``zaya1-8b-l20``), where the memory binds it: 2 query heads a KV
head and a key byte, far under the ridge.

The least the kernel can move in one decode step is K and V of every live
token, 512 values a token a layer (``lib/cca_moe.decode_kernel_bytes``; the
dense cells' reader goes through ``lib/roofline._dims``, which wants a dense
FFN width this configuration has not). Live tokens are the mean over the
engine steps sampled while tracing; decode steps in the trace are the
kernel's executions over the layers. The share is (bytes / peak bytes per
second) / kernel seconds.
"""

from lib import cca_moe, peaks
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
    least_s = cca_moe.decode_kernel_bytes(
        run.config, sum(live) / len(live)) * steps \
        / peaks.peaks(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
