"""Share of the MXU's peak that the latent model's prefill chunk kernel
reaches on the products a causal attention must make.

Operations: the program's own ``prefill.chunk`` spans say which chunks ran
(``offset``, ``width``, ``prompt_tokens``: the true rows of a chunk are
``min(width, prompt_tokens - offset)``); ``lib/latent_prefill.py`` counts the
causal pairs of each over all layers. A span is the chunk's dispatch on the
host's clock and the kernel's events are the device's, so the chunks counted
are those whose span ended inside the trace, brought to the number the device
ran there: the kernel's executions over the layers. Time: the operations
matching ``op`` in ``program``. The share is (operations / peak operations per
second) / kernel seconds; it counts useful products only, so it reads low.
Where the program has no such kernel, nothing is read.
"""

from lib import latent_prefill, peaks
from readers import device_time


def read(run, program, op):
    if run.trace is None:
        return None
    kernel_s = device_time.seconds(run.trace, program, op)
    # the kernel's executions over the layers: the chunks the device ran
    ran = device_time.decode_steps(run, program, op)
    t0, t1 = run.t_trace
    chunks = [s["attrs"] for s in run.spans
              if s["name"] == "prefill.chunk" and t0 <= s["t1"] <= t1]
    if not kernel_s or not chunks:
        return None
    flops = sum(latent_prefill.chunk_attention_flops(
        run.config, a["offset"],
        min(a["width"], a["prompt_tokens"] - a["offset"])) for a in chunks)
    least_s = flops * ran / len(chunks) \
        / peaks.peaks(run.device["kind"])["bf16_flops_per_s"]
    return 100.0 * least_s / kernel_s
