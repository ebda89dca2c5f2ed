"""Paged-attention kernel: the least time the chip needs for the kernel's
work in the traced sub-window (the larger of its operations over the bf16
peak and its bytes over the HBM peak, `counts.attention_cost`) over the
kernel's device time in the trace, in %.  Which bound applies goes to
`ctx["notes"]`."""

from counts import attention_cost

# device op names of the Pallas paged-attention kernel in the trace
KERNEL_NAMES = ("paged_flash_attention",)


def read(ctx):
    trace, sub = ctx["trace"], ctx["sub"]
    if trace is None or not sub["batches"]:
        return None
    kernel_s = sum(t for name, t in trace["ops_s"].items()
                   if any(k in name for k in KERNEL_NAMES))
    if kernel_s <= 0:
        return None
    cost = attention_cost(ctx["cfg"], sub["batches"])
    peaks = ctx["peaks"]
    compute_s = cost["flops"] / peaks["bf16_flops_per_s"]
    memory_s = cost["bytes"] / peaks["hbm_bytes_per_s"]
    ctx["notes"]["paged_attention_bound"] = (
        "memory" if memory_s >= compute_s else "compute")
    ctx["notes"]["paged_attention_kernel_s"] = kernel_s
    return 100.0 * max(compute_s, memory_s) / kernel_s
