"""Device milliseconds per tick of the tick's ``gen`` stage: the open-loop generator's draws and admission
(``bench/stages.py``)."""
from bench import stages


def read(view):
    return stages.stage_ms(view, "gen")
