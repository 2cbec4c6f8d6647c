"""Device idle milliseconds per window step while the innermost open engine
span is ``engine.sched``, the scheduler: admission and the stall check,
fault bookkeeping, the choice of the decode batch and pool-pressure
preemption, retirement. Integrated over the idle intervals, as
``bench/spans.py`` sets out.
"""
from bench import spans


def read(run):
    return spans.idle_ms_per_step(run, "engine.sched")
