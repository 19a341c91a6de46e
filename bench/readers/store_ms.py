"""Device milliseconds per tick of the tick's ``store`` stage: the store calls the node steps make
(``bench/stages.py``)."""
from bench import stages


def read(view):
    return stages.stage_ms(view, "store")
