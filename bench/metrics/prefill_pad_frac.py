"""Share of the prefill rows x bucket positions dispatched in the window
that held no prompt token: 1 - the change of the engine's
``prefill_tokens`` over that of its ``prefill_slot_tokens`` between the
window's edges."""

from bench.phases import pad_frac

COUNTERS = ("prefill_tokens", "prefill_slot_tokens")


def read(rec):
    tokens, slots = (rec["run"].counters.get(n) for n in COUNTERS)
    if tokens is None or slots is None:
        return None
    return pad_frac(tokens, slots)
