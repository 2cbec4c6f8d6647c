"""Device idle milliseconds per window step while the innermost open engine
span is ``engine.state``, the recurrent state slots: taking a slot at
admission, freeing it at retirement, and the slot indices of a step.
Integrated over the idle intervals, as ``bench/spans.py`` sets out; None
for a program that writes no such span.
"""
from bench import spans


def read(run):
    return spans.idle_ms_per_step(run, "engine.state")
