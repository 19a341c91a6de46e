"""The exchange's share of its ICI roofline: the bytes of the messages
that crossed from one chip to another in the window, per chip and tick,
over the ``exchange`` stage's device time per tick times a chip's peak
inter-chip bandwidth (``bench/peaks.json``).

It counts the messages the work needs (``DistState.cross_chip``: the live
messages a chip received from another chip), not the lanes a collective
moves, so any implementation of the same work reads the same bytes."""
from bench.readers.exchange_ms import read as exchange_ms


def read(view):
    rec, peak = view["rec"], view["peak"]
    ms = exchange_ms(view)
    if not ms or peak is None or "cross_chip_window" not in rec:
        return None
    t0, t1 = rec["window_ticks"]
    per_tick = rec["cross_chip_window"] * rec["msg_bytes"] / rec["chips"] / (t1 - t0)
    return 100.0 * per_tick / (ms * 1e-3 * peak["ici_bits_per_s"] / 8)
