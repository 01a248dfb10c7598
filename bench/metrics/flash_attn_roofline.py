"""The Pallas flash-attention kernel's share of its roofline, %: the
least time the chip could take for the kernel's calls over their device
time in the trace.  A call's least time is the larger of operations
over the bf16 peak and bytes over HBM bandwidth, from its shapes
(``attention_calls`` of the configuration's architecture module); the
calls in the trace are taken at the mean of one step's calls."""

import math

from bench import trace as tracing

KERNEL = "flash_attention"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    secs, calls = tracing.kernel_time(run.trace, KERNEL)
    if secs <= 0 or calls <= 0:
        return None
    step = run.arch.attention_calls(run.model, run.traffic,
                                    run.cell["layout"])
    least = math.fsum(max(ops / run.peaks["bf16_flops_per_s"],
                          nbytes / run.peaks["hbm_bytes_per_s"])
                      for ops, nbytes in step) / len(step)
    return 100.0 * least * calls / secs
