"""Share of the chips' HBM bandwidth that the decode attention island
reached in the traced window: the KV bytes that the live slots hold (the
model module's ``decode_attn_bytes``), summed over the decode steps that
the traced span holds, over chips x HBM peak x the device self time of
the ops scoped ``decode_attn`` in the ``jit_serve_decode`` program.

The pattern for a kernel's roofline share: a count of the model module
over the steps of the traced span, over the device time of one scope of
one program from ``device_scopes``."""

from bench.counts import total

PROGRAM, SCOPE = "jit_serve_decode", "decode_attn"


def read(rec):
    t = sum(s for p, sc, s in (rec["trace"] or {}).get("device_scopes", [])
            if (p, sc) == (PROGRAM, SCOPE))
    steps = [x for x in rec["traced_steps"] if x.kind == "decode"]
    if not t or not steps or rec["peak"] is None:
        return None
    nbytes = total(rec["model"], "decode_attn_bytes", rec["shapes"], steps)
    if nbytes is None:
        return None
    return 100.0 * nbytes / (rec["chips"] * rec["peak"]["hbm_bytes_per_s"]
                             * t)
