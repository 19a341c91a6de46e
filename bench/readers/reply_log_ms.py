"""Device milliseconds per tick of the tick's ``reply_log`` stage: the exit mask and the reply log's append
(``bench/stages.py``)."""
from bench import stages


def read(view):
    return stages.stage_ms(view, "reply_log")
