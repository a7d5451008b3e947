"""From the trace: the device self time of the decode program's ops whose
HLO ``op_name`` scope is ``decode_attn`` (the decode attention island),
per decode program started in the traced window (``bench/phases.reduce``,
scopes from the step programs' HLO)."""


def read(rec):
    return (rec["trace"] or {}).get("decode_attn_island_ms")
