"""From the trace: the share of collective-op device time (all-gather,
reduce-scatter, all-reduce, collective-permute, all-to-all) during which
no other op runs on that device. Nothing to read where no collective ran."""


def read(rec):
    return (rec["trace"] or {}).get("exposed_comm_frac")
