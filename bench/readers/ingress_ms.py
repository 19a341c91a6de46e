"""Device milliseconds per tick of the tick's ``ingress`` stage: entry stamps, dead-node masks, inbound lanes, stale-route admission, lease expiry and the head's lock stage
(``bench/stages.py``)."""
from bench import stages


def read(view):
    return stages.stage_ms(view, "ingress")
