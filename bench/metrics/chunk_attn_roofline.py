"""The paged prefill-chunk kernel's share of its roofline: for every chunk
in the window, the larger of its operations over the bf16 peak and the
pooled prefix, the chunk's keys and values, queries and output over HBM
bandwidth, over every layer (the architecture's ``chunk_attention``),
summed, over the kernel's device time in the trace."""
from bench import flops

KERNEL = ("paged_prefill", "paged_chunk")


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.seconds_matching(KERNEL)
    ideal = sum(flops.roofline_seconds(
        run.arch.chunk_attention(run.sizes, prefix, chunk), run.peak)
        for step in run.steps for prefix, chunk in step.chunks)
    if not seconds or not ideal:
        return None
    return 100.0 * ideal / seconds
