"""What the counts of every architecture share: the published peaks of the
chip, and the width of a bf16 value.

The operations and bytes of a step belong to the architecture: each model
module under ``bench/models/`` counts its own from shapes alone and from
the ``bench/loop.Step`` it is given (the prompts a prefill admitted, the
positions each live decode slot attends to). A count a module cannot give
is ``None``, and a reader then reports nothing.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
BF16 = 2


def peak(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name} (known: {sorted(table)})")
    return table[device_kind]


def total(model, name: str, shapes: dict, steps) -> int | None:
    """The model module's count ``name`` summed over ``steps``: ``None``
    where the module has no such count or gives ``None`` for a step, never
    another architecture's count in its place."""
    count = getattr(model, name, None)
    if count is None:
        return None
    per_step = [count(shapes, x) for x in steps]
    return None if None in per_step else sum(per_step)
