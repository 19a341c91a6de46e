"""Device milliseconds per tick of the open-loop scan: the scan's program
executions inside the traced window, over the ticks they ran."""
from bench import tracing


def ticks_and_seconds(view):
    lo, hi = view["summary"]["window"]
    rec = view["rec"]
    execs, secs = tracing.program_time(view["planes"], rec["fn_name"], lo, hi)
    return execs * rec["ticks_per_segment"], secs


def read(view):
    ticks, secs = ticks_and_seconds(view)
    return 1e3 * secs / ticks if ticks else None
