"""From the trace: 1 - (union of device-op intervals) / traced window,
averaged over the cell's devices."""


def read(rec):
    return (rec["trace"] or {}).get("idle_frac")
