"""The decode step's state update's share of its roofline: for every
decode step in the window, the larger of its operations over the bf16 peak
and its bytes (each row's float32 state read and written once, and its
inputs and output) over HBM bandwidth, in every Mamba layer (the
architecture's ``ssm_decode``), summed, over the device time of the
kernel in the trace. None for an architecture without the count or a
program without the kernel."""
from bench import flops

KERNEL = ("ssm_decode",)


def read(run):
    count = getattr(run.arch, "ssm_decode", None)
    if run.trace is None or count is None:
        return None
    seconds = run.trace.seconds_matching(KERNEL)
    ideal = sum(flops.roofline_seconds(count(run.sizes, step.decode_lens),
                                       run.peak)
                for step in run.steps if step.decode_lens)
    if not seconds or not ideal:
        return None
    return 100.0 * ideal / seconds
