"""Device milliseconds per tick of the four-chip scan
(``ChainDist.run_openloop``): its program executions inside the traced
window, over the ticks they ran, averaged over the cell's chips."""
from bench.readers.tick_ms import ticks_and_seconds


def read(view):
    if "cross_chip_window" not in view["rec"]:
        return None
    ticks, secs = ticks_and_seconds(view)
    return 1e3 * secs / ticks if ticks else None
