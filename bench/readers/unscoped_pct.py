"""Share of the scan's device op time that no stage of the tick names: ops
whose instruction carries no stage, or that the program's stage map does
not hold (``bench/stages.py``)."""
from bench import stages


def read(view):
    return stages.unscoped_pct(view)
