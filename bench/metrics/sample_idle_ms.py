"""Device idle milliseconds per window step while the innermost open engine
span is ``engine.sample``, the sampler: the finiteness guard, per-request
key folding, the eager sampler and the transfer of the sampled tokens to
the host. Integrated over the idle intervals, as ``bench/spans.py`` sets
out.
"""
from bench import spans


def read(run):
    return spans.idle_ms_per_step(run, "engine.sample")
