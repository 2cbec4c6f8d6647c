"""Device idle milliseconds per window step while the innermost open engine
span is ``engine.model``, the model step: the dispatch of each jitted model
call, its host-to-device inputs, and the wait for and check of the decode
step's result, around the device's own work. Integrated over the idle
intervals, as ``bench/spans.py`` sets out.
"""
from bench import spans


def read(run):
    return spans.idle_ms_per_step(run, "engine.model")
