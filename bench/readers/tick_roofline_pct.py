"""The tick's share of its HBM roofline: the least bytes the window's work
needs, over the tick's device time times the chip's peak HBM bandwidth.

The least bytes count the work, not the arrays: each answered op passes
``hops - 1`` nodes (``ReplyLog.hops`` counts link traversals, and one of
them is the leg back to the client), and each pass reads one message and
reads or writes one store record (every version cell, its seqs, the dirty
count and the next seq).  The same work reads the same bytes whatever
implements it."""
import numpy as np

from bench.readers.tick_ms import ticks_and_seconds


def least_bytes(hops, msg_bytes: int, record_bytes: int) -> float:
    passes = np.clip(np.asarray(hops, np.int64) - 1, 0, None).sum()
    return float(passes) * (msg_bytes + record_bytes)


def read(view):
    ticks, secs = ticks_and_seconds(view)
    if not ticks or view["peak"] is None:
        return None
    rec, lat = view["rec"], view["lat"]
    t0, t1 = rec["window_ticks"]
    per_tick = least_bytes(lat["hops"], rec["msg_bytes"], rec["record_bytes"]) / (t1 - t0)
    return 100.0 * per_tick / (secs / ticks * view["peak"]["hbm_bytes_per_s"])
