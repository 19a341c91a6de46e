"""Device milliseconds per tick of the four-chip scan's ``exchange``
stage: the collectives between chips and the inbox compaction around them
(``bench/dist_stages.py``), averaged over the cell's chips."""
from bench import dist_stages


def read(view):
    return dist_stages.stage_ms(view, "exchange")
