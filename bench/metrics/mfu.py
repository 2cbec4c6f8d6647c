"""The whole step's share of the chip's bf16 peak (model step): the model
operations of every prefill chunk and decode token in the window, over the
window's seconds times the peak. Counted from shapes by the cell's
architecture (``bench/arch/<arch>.py``).
"""


def read(run):
    total = 0
    for step in run.steps:
        if step.decode_lens:
            total += run.arch.decode_step_flops(run.sizes, step.decode_lens)
        for prefix, chunk in step.chunks:
            total += run.arch.chunk_flops(run.sizes, prefix, chunk)
    if not total:
        return None
    return 100.0 * total / (run.window_s * run.peak["bf16_flops"])
