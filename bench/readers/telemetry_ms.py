"""Device milliseconds per tick of the tick's ``telemetry`` stage: the latency histogram, the packet traces and the flight-recorder row
(``bench/stages.py``)."""
from bench import stages


def read(view):
    return stages.stage_ms(view, "telemetry")
