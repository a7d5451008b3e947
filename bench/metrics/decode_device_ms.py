"""From the trace: the decode program's device time (the union of its
``jit_serve_decode`` module intervals) in the traced window, per decode
program started in it (``bench/phases.reduce``)."""


def read(rec):
    return (rec["trace"] or {}).get("decode_device_ms")
