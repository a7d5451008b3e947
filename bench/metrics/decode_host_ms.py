"""From the trace: the device's idle time inside the decode steps' own
``engine.step`` spans, the device clock shifted by its lag behind the
host's, per such step in the traced window (``bench/phases.reduce``)."""


def read(rec):
    return (rec["trace"] or {}).get("decode_host_ms")
