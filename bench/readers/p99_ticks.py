"""99th percentile of ticks from send to reply (``t_done - t_inject``) over
the ops answered in the window: an exact count, free of host-clock noise."""
import numpy as np


def read(view):
    ticks = view["lat"]["ticks"]
    return float(np.percentile(ticks, 99)) if ticks.size else None
