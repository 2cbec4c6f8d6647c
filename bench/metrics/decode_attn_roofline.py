"""The paged decode kernel's share of its roofline: for every decode step
in the window, the larger of its operations over the bf16 peak and the
live keys, values, queries and output over HBM bandwidth, over every
layer (the architecture's ``decode_attention``), summed, over the kernel's
device time in the trace. Each row's walk stops at its own length, in
whole pool blocks, so the live bytes are at most a block a row short of
what it reads."""
from bench import flops

KERNEL = ("paged_decode",)


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.seconds_matching(KERNEL)
    ideal = sum(flops.roofline_seconds(
        run.arch.decode_attention(run.sizes, step.decode_lens), run.peak)
        for step in run.steps if step.decode_lens)
    if not seconds or not ideal:
        return None
    return 100.0 * ideal / seconds
