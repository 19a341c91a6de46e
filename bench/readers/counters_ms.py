"""Device milliseconds per tick of the tick's ``counters`` stage: the conflict heat and the per-tick counters
(``bench/stages.py``)."""
from bench import stages


def read(view):
    return stages.stage_ms(view, "counters")
