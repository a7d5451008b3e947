"""p90 of the wait from a request's due time to the start of the engine
step that admitted it (the engine's ``admit`` events, on the harness's
clock), over the requests due in the window; one still queued at the
window's end counts at its wait so far."""

import numpy as np


def read(rec):
    run = rec["run"]
    waits = [min(r.admit if r.admit is not None else np.inf, run.w1) - r.due
             for r in run.due_in_window()]
    return float(np.percentile(waits, 90)) * 1e3 if waits else None
