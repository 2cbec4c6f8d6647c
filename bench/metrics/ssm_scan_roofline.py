"""The prefill chunk's state-space scan's share of its roofline: for every
chunk in the window, the larger of the recurrence's operations over the
bf16 peak and its bytes (the float32 state read and written, each token's
inputs and output) over HBM bandwidth, in every Mamba layer (the
architecture's ``ssm_chunk``), summed, over the device time of the kernel
in the trace. None for an architecture without the count, a program
without the kernel, or a window without chunks."""
from bench import flops

KERNEL = ("ssm_chunk_scan",)


def read(run):
    count = getattr(run.arch, "ssm_chunk", None)
    if run.trace is None or count is None:
        return None
    seconds = run.trace.seconds_matching(KERNEL)
    ideal = sum(flops.roofline_seconds(count(run.sizes, prefix, chunk),
                                       run.peak)
                for step in run.steps for prefix, chunk in step.chunks)
    if not seconds or not ideal:
        return None
    return 100.0 * ideal / seconds
