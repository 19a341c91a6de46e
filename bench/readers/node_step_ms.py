"""Device milliseconds per tick of the tick's ``node_step`` stage: the vmapped node step, apart from its store calls
(``bench/stages.py``)."""
from bench import stages


def read(view):
    return stages.stage_ms(view, "node_step")
