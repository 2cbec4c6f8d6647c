"""Device idle milliseconds per window step while the innermost open engine
span is ``engine.pool``, the KV pool: block tables, prefix gather indices,
the per-worker table walk, token appends, pool writes and KV byte
accounting. Integrated over the idle intervals, as ``bench/spans.py`` sets
out.
"""
from bench import spans


def read(run):
    return spans.idle_ms_per_step(run, "engine.pool")
