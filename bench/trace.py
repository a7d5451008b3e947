"""Reduction of a profiler trace (``.xplane.pb``) to device busy time, idle
share, exposed collective time, the top device operations, and the idle
gaps labelled by the harness span the host was in.

``events(path)`` reads the trace into plain tuples; ``reduce(ev)`` does the
arithmetic on them, so it is checked on a small recorded trace and on
hand-made events alike. Device planes are ``/device:TPU:<n>``: busy time
is the union of their ``XLA Ops`` line; collectives are read from it and
from ``Async XLA Ops``, where an overlapped transfer shows its flight.
Host spans are the harness's ``bench.*`` annotations; the traced window is
the ``bench.traced`` span. (On a v5e host the device clock reads about a
millisecond behind the host's, so labels of gaps that short are loose.)
"""

from __future__ import annotations

from collections import defaultdict

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
WINDOW = "bench.traced"
#: idle gaps shorter than this sit between two ops of one program and are
#: summed under one label, not attributed to a host span
SHORT_GAP_NS = 10_000


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def events(path: str) -> dict:
    """{"devices": {plane: [(op, start_ns, end_ns)]}, "async": {plane:
    [...]}, "host": [(span, start_ns, end_ns)]} from one trace file."""
    from jax.profiler import ProfileData

    out: dict = {"devices": {}, "async": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                key = {OP_LINE: "devices", ASYNC_LINE: "async"}.get(
                    line.name)
                if key:
                    out[key].setdefault(plane.name, []).extend(
                        (op_name(e.name), e.start_ns, e.end_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [(e.name, e.start_ns, e.end_ns)
                                for e in line.events
                                if e.name.startswith("bench.")]
    return out


def union(iv):
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def measure(iv) -> float:
    return float(sum(e - s for s, e in iv))


def intersect(a, b):
    """Intersection of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def is_collective(name: str) -> bool:
    n = name.lower()
    return any(c in n for c in COLLECTIVES)


def leaf_times(ops) -> dict:
    """Self time of each op name: an op's duration less the ops nested in
    it on the same line (a loop op contains its body's ops)."""
    tot: dict = defaultdict(float)
    stack: list = []                         # [name, end, child_time]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            n, end, child, dur = stack.pop()
            tot[n] += dur - child
        if stack:
            stack[-1][2] += e - s
        stack.append([name, e, 0.0, e - s])
    for n, _, child, dur in stack:
        tot[n] += dur - child
    return tot


def label_at(host, t: float) -> str:
    """The innermost harness span open at ``t`` (``bench.traced`` only
    when no other is)."""
    best = None
    for name, s, e in host:
        if name != WINDOW and s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "bench.other"


def reduce(ev: dict, top: int = 10) -> dict:
    """Busy and idle time per device inside the traced window, averaged
    over the devices; the exposed share of collective time; and the
    breakdown lists (device ops by self time, idle time by host span)."""
    win = [(s, e) for name, s, e in ev["host"] if name == WINDOW]
    if not win or not ev["devices"]:
        return {}
    w0, w1 = win[0]
    window_s = (w1 - w0) * 1e-9
    host = [h for h in ev["host"] if h[2] > w0 and h[1] < w1]
    busy, comm, exposed = [], 0.0, 0.0
    ops_t: dict = defaultdict(float)
    idle_t: dict = defaultdict(float)
    n_dev = len(ev["devices"])
    def clip(ops):
        return [(n, max(s, w0), min(e, w1)) for n, s, e in ops
                if e > w0 and s < w1]

    for plane, ops in ev["devices"].items():
        ops = clip(ops)
        flight = clip(ev.get("async", {}).get(plane, []))
        all_u = union([(s, e) for _, s, e in ops])
        busy.append(measure(all_u))
        coll = union([(s, e) for n, s, e in ops + flight
                      if is_collective(n)])
        compute = union([(s, e) for n, s, e in ops if not is_collective(n)])
        comm += measure(coll)
        exposed += measure(coll) - measure(intersect(coll, compute))
        for n, t in leaf_times(ops).items():
            ops_t[n] += t / n_dev
        edges = [w0] + [x for iv in all_u for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e - s >= SHORT_GAP_NS:
                idle_t[label_at(host, (s + e) / 2)] += (e - s) / n_dev
            elif e > s:
                idle_t["between_ops"] += (e - s) / n_dev
    busy_s = sum(busy) / n_dev * 1e-9
    rank = sorted(ops_t.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_t.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_frac": 1.0 - busy_s / window_s,
            "comm_s": comm / n_dev * 1e-9,
            "exposed_comm_frac": exposed / comm if comm else None,
            "device_ops": [[n, t * 1e-9] for n, t in rank],
            "idle_gaps": [[n, t * 1e-9] for n, t in gaps]}
