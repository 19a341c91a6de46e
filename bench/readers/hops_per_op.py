"""Mean link traversals (``ReplyLog.hops``) of the ops answered in the
window: 2 for a read answered where it entered, more for a read sent on to
the tail and for a write's trip down the chain."""
import numpy as np


def read(view):
    hops = view["lat"]["hops"]
    return float(np.mean(hops)) if hops.size else None
