"""Share of the chips' bf16 peak that prefill steps reached in the window:
the FLOPs of the real prompt tokens they admitted, attention included (the
model module's ``prefill_flops``), over chips x peak x their summed
host-clocked time. Bucket padding and inert group rows are not counted as
work."""

from bench.counts import total


def read(rec):
    run = rec["run"]
    steps = [x for x in run.steps if x.kind == "prefill" and x.t0 >= run.w0
             and x.prompt_lens]
    t = sum(x.t1 - x.t0 for x in steps)
    if not t or rec["peak"] is None:
        return None
    flops = total(rec["model"], "prefill_flops", rec["shapes"], steps)
    if flops is None:
        return None
    return 100.0 * flops / (rec["chips"] * rec["peak"]["bf16_flops_per_s"]
                            * t)
