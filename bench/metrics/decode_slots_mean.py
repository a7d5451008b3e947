"""Mean number of live slots a decode step in the window served (counted
from ``ServingEngine.slots`` before each step)."""


def read(rec):
    run = rec["run"]
    rows = [len(s.kv_lens) for s in run.steps
            if s.kind == "decode" and s.t0 >= run.w0]
    return sum(rows) / len(rows) if rows else None
