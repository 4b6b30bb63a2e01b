"""Share of its roofline that the latent (MLA) decode kernel reaches.

The least time one decode step's kernel calls can take is the greater of the
cached rows of every live token over the memory's peak and the scores and sums
over them over the MXU's (``lib/mla_moe.py``: 61 operations a cached byte,
under the ridge of 240, so the memory binds it). Live tokens are the mean over
the engine steps sampled while tracing; decode steps in the trace are the
kernel's executions over the layers. The share is least seconds / kernel
seconds.
"""

from lib import mla_moe, peaks
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
    chip = peaks.peaks(run.device["kind"])
    tokens = sum(live) / len(live)
    least_s = steps * max(
        mla_moe.decode_kernel_bytes(run.config, tokens)
        / chip["hbm_bytes_per_s"],
        mla_moe.decode_kernel_flops(run.config, tokens)
        / chip["bf16_flops_per_s"])
    return 100.0 * least_s / kernel_s
