"""The Pallas flash-attention kernel's share of its roofline, %: the
least time the chip could take for the kernel's calls (the larger of
operations over the bf16 peak and bytes over HBM bandwidth, from the
calls' shapes in ``bench/flops.py``) over their device time in the
trace."""

from bench import flops
from bench import trace as tracing

KERNEL = "flash_attention"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    secs, calls = tracing.kernel_time(run.trace, KERNEL)
    if secs <= 0 or calls <= 0:
        return None
    m, tr = run.model, run.traffic
    layout = run.cell["layout"]
    ops, nbytes = flops.flash_attention(
        tr["batch"] // layout["dp"],
        m["num_attention_heads"] // layout["tp"],
        m["num_key_value_heads"] // layout["tp"], tr["seq"], m["head_dim"])
    least = max(ops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * calls / secs
