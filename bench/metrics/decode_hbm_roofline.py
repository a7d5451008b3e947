"""Share of the chips' HBM bandwidth that decode steps reached in the
window: the bytes a decode step needs (the model module's
``decode_bytes``: every weight but the embedding table, and the KV each
live slot holds now, never the slab's full length), total over total
host-clocked decode time."""

from bench.counts import total


def read(rec):
    run = rec["run"]
    steps = [x for x in run.steps if x.kind == "decode" and x.t0 >= run.w0]
    t = sum(x.t1 - x.t0 for x in steps)
    if not t or rec["peak"] is None:
        return None
    nbytes = total(rec["model"], "decode_bytes", rec["shapes"], steps)
    if nbytes is None:
        return None
    return 100.0 * nbytes / (rec["chips"] * rec["peak"]["hbm_bytes_per_s"]
                             * t)
