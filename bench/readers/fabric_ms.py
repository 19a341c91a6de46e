"""Device milliseconds per tick of the tick's ``fabric`` stage: outbound lanes, fabric masks, hop accounting, the router and the packet sums
(``bench/stages.py``)."""
from bench import stages


def read(view):
    return stages.stage_ms(view, "fabric")
