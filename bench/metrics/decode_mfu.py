"""Share of the chips' bf16 peak that the whole decode step reached in the
window: the FLOPs of its live slots (the model module's ``decode_flops``:
every matrix, attention over each slot's current length, the head), over
chips x peak x decode time. It bounds ``decode_hbm_roofline`` from the
compute side."""

from bench.counts import total


def read(rec):
    run = rec["run"]
    steps = [x for x in run.steps if x.kind == "decode" and x.t0 >= run.w0]
    t = sum(x.t1 - x.t0 for x in steps)
    if not t or rec["peak"] is None:
        return None
    flops = total(rec["model"], "decode_flops", rec["shapes"], steps)
    if flops is None:
        return None
    return 100.0 * flops / (rec["chips"] * rec["peak"]["bf16_flops_per_s"]
                            * t)
