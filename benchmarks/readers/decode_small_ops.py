"""Share of the device's busy time that the decode program spends in its
small operations: everything in ``program`` that is neither the attention
kernel (``kernel``), nor a grouped product of the experts (``experts``), nor
the head's product (an operation whose result is ``[rows, vocab_size]``).
On the CCA model that is where the two convolutions, the value shift, the
norms, the router's MLP, the merges and the pool's scatters live: a tail
bound by latency, beside the weight streams bound by bandwidth.
"""

import re


def read(run, program, kernel, experts):
    if run.trace is None or not run.trace["busy_s"]:
        return None
    head = re.compile(r"\[\d+,%d\]" % run.config["vocab_size"])
    ops = [o for o in run.trace["ops"] if re.search(program, o["program"])]
    if not any(re.search(kernel, o["name"]) for o in ops):
        return None                # not this program's trace
    small = sum(o["seconds"] for o in ops
                if not re.search(kernel, o["name"])
                and not re.search(experts, o["name"])
                and not head.search(o["name"]))
    return 100.0 * small / run.trace["busy_s"]
