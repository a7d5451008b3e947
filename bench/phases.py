"""Engine phases, device programs and island scopes from one profiler trace:
the reduction that splits a serving step into device time by named program
and scope, and host time by engine phase.

``events(path)`` is ``bench.trace.events`` plus the program's own host
spans (``engine.step`` and its phases, from ``runtime/serving.py``) and the
``XLA Modules`` line of each device, where every jitted program shows under
its module name (``jit_serve_decode(<fingerprint>)``,
``jit_serve_prefill_<bucket>(...)``). ``scope_map(hlo)`` maps the op names
of one compiled program to the innermost ``jax.named_scope`` in their
``op_name`` metadata (an ``Island`` name, ``qkv``, ``norm``, ``head``,
``cache_scan``); the trace's op events carry no scope, so the compiled
HLO is the source. ``reduce(ev, scopes)`` does the arithmetic, so it is
checked on hand-made events.

The device clock reads behind the host's. A program cannot start before
the ``engine.dispatch`` span that launched it opened, so the largest
amount by which one appears to is taken as the lag, and device intervals
are shifted by it for labelling idle gaps and step spans only; busy time,
program and scope times are read unshifted. A trace without ``engine.*``
spans has lag 0, and every key ``bench.trace.reduce`` gives comes out as it
gives it.
"""

from __future__ import annotations

import bisect
import re
from collections import Counter, defaultdict

from bench import trace as TR

MODULE_LINE = "XLA Modules"
ENGINE = "engine."
STEP = "engine.step"
DISPATCH = "engine.dispatch"
DECODE = "jit_serve_decode"
PREFILL = "jit_serve_prefill_"
DECODE_ISLAND = "decode_attn"
UNSCOPED = "unscoped"
#: name-stack entries JAX and XLA add themselves: never a program scope
INTERNAL = {"while", "body", "cond", "closed_call", "checkpoint", "remat",
            "scan", "shard_map", "pjit", "custom_jvp_call",
            "custom_vjp_call", "branch"}
_SCOPE = re.compile(r"[A-Za-z_][A-Za-z0-9_]*$")


def program(module: str) -> str:
    """``jit_serve_decode(1185...)`` -> ``jit_serve_decode``."""
    return module.split("(", 1)[0]


def events(path: str) -> dict:
    """``bench.trace.events(path)`` plus ``"engine"``: [(span, start_ns,
    end_ns)] of the program's ``engine.*`` spans, and ``"modules"``:
    {plane: [(module, start_ns, end_ns)]}."""
    from jax.profiler import ProfileData

    out = TR.events(path)
    out["engine"], out["modules"] = [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    out["modules"].setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.end_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["engine"] += [(e.name, e.start_ns, e.end_ns)
                                  for e in line.events
                                  if e.name.startswith(ENGINE)]
    return out


def scope_of(op_name: str) -> str:
    """Innermost program scope of one ``op_name``:
    ``jit(serve_decode)/cache_scan/while/body/closed_call/mlp/dot_general``
    -> ``mlp``; ``unscoped`` where only JAX's own entries stand."""
    parts = op_name.split(";", 1)[0].split("/")[:-1]
    for p in reversed(parts):
        if _SCOPE.match(p) and p not in INTERNAL:
            return p
    return UNSCOPED


_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INST = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\((%?[\w.\-]+)?")
_META = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def scope_map(hlo: str) -> dict:
    """{instruction name: scope} over one compiled program's HLO text. An
    instruction with no scoped ``op_name`` of its own takes that of the
    computation it calls (a fusion: its root's, else the commonest among
    its instructions), else that of its first operand (a copy XLA inserted
    takes the scope of what it copies)."""
    comps: dict = defaultdict(list)        # computation -> [(root, scope)]
    insts: list = []                       # (name, scope, called, operand)
    comp = None
    for line in hlo.splitlines():
        if not line.startswith(" "):
            m = _COMP.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INST.match(line)
        if m is None or comp is None:
            continue
        meta = _META.search(line)
        scope = scope_of(meta.group(1)) if meta else UNSCOPED
        calls = _CALLS.search(line)
        comps[comp].append((bool(m.group(1)), scope))
        insts.append((m.group(2), scope, calls.group(1) if calls else None,
                      (m.group(4) or "").lstrip("%")))

    def inherited(called):
        body = comps.get(called, [])
        root = [s for r, s in body if r and s != UNSCOPED]
        if root:
            return root[0]
        common = Counter(s for _, s in body if s != UNSCOPED).most_common(1)
        return common[0][0] if common else UNSCOPED

    out: dict = {}
    for name, scope, called, operand in insts:
        if scope == UNSCOPED and called is not None:
            scope = inherited(called)
        if scope == UNSCOPED:
            scope = out.get(operand, UNSCOPED)
        out[name] = scope
    return out


def clock_lag(ev: dict) -> float:
    """Nanoseconds by which the device clock reads behind the host's: the
    largest lead of an ``engine.dispatch`` span's start over the start of
    the step program nearest it (0 where nothing pairs)."""
    starts = sorted(s for n, s, _ in ev.get("engine", []) if n == DISPATCH)
    lag = 0.0
    if not starts:
        return lag
    for mods in ev.get("modules", {}).values():
        for name, s, _ in mods:
            if not program(name).startswith((DECODE, PREFILL)):
                continue
            i = bisect.bisect_left(starts, s)
            near = min(starts[max(i - 1, 0):i + 1], key=lambda d: abs(d - s))
            lag = max(lag, near - s)
    return lag


def step_kinds(ev: dict, lag: float) -> list:
    """[(kind, start_ns, end_ns)] of each ``engine.step`` span: ``decode``
    or ``prefill`` after the step program that started, clock-shifted,
    inside it (None where none did)."""
    mods = next(iter(ev.get("modules", {}).values()), [])
    starts = sorted((s + lag, program(n)) for n, s, _ in mods
                    if program(n).startswith((DECODE, PREFILL)))
    keys = [s for s, _ in starts]
    out = []
    for name, s, e in ev.get("engine", []):
        if name != STEP:
            continue
        i = bisect.bisect_left(keys, s)
        kind = None
        if i < len(keys) and keys[i] < e:
            kind = "decode" if starts[i][1] == DECODE else "prefill"
        out.append((kind, s, e))
    return sorted(out, key=lambda k: k[1])


def _in_module(mods, ops):
    """Each op tagged with the module interval that holds its start:
    [((program, op), start, end)]; ``none`` where no module does."""
    mods = sorted(mods, key=lambda m: m[1])
    starts = [s for _, s, _ in mods]
    out = []
    for n, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = (program(mods[i][0]) if i >= 0 and s < mods[i][2]
                else "none")
        out.append(((prog, n), s, e))
    return out


def reduce(ev: dict, scopes: dict | None = None, top: int = 32) -> dict:
    """``bench.trace.reduce(ev)`` with its ``idle_gaps`` labelled by the
    innermost harness or engine span after the clock shift, its
    ``device_ops`` named ``<program>/<op>`` (``none/<op>`` outside every
    program), so that ops of one name in two programs stay apart, and:
    ``clock_lag_ms``; ``device_programs`` [[program, s]] (union of each
    program's module intervals); ``device_scopes`` [[program, scope, s]]
    (device self time of ops, ``unscoped`` where the program's HLO names
    none or was not given); ``decode_device_ms`` (decode device time per
    decode program started in the window); ``decode_host_ms`` (device-idle
    time inside decode ``engine.step`` spans, per such step);
    ``decode_attn_island_ms`` (self time of ops scoped ``decode_attn`` per
    decode program). All per device, averaged over devices, in the traced
    window; a metric is None where its spans or programs are missing.
    ``scopes`` maps a program name to its ``scope_map``."""
    base = TR.reduce(ev, top=top)
    if not base:
        return {}
    scopes = scopes or {}
    w0, w1 = next((s, e) for n, s, e in ev["host"] if n == TR.WINDOW)
    lag = clock_lag(ev)
    engine = [h for h in ev.get("engine", []) if h[2] > w0 and h[1] < w1]
    if engine:
        def shift(planes):
            return {p: [(n, s + lag, e + lag) for n, s, e in ops]
                    for p, ops in planes.items()}

        shifted = {**ev, "devices": shift(ev["devices"]),
                   "async": shift(ev.get("async", {})),
                   "host": ev["host"] + engine}
        base["idle_gaps"] = TR.reduce(shifted, top=top)["idle_gaps"]
    planes = ev["devices"]
    n_dev = len(planes)
    modules = ev.get("modules", {})
    prog_t: dict = defaultdict(float)
    scope_t: dict = defaultdict(float)
    ops_t: dict = defaultdict(float)
    dec_dev, dec_attn, dec_planes = 0.0, 0.0, 0
    for plane, ops in planes.items():
        mods = [(n, max(s, w0), min(e, w1)) for n, s, e in
                modules.get(plane, []) if e > w0 and s < w1]
        by_prog: dict = defaultdict(list)
        for n, s, e in mods:
            by_prog[program(n)].append((s, e))
        for p, iv in by_prog.items():
            prog_t[p] += TR.measure(TR.union(iv)) / n_dev
        n_dec = sum(1 for n, s, _ in modules.get(plane, [])
                    if program(n) == DECODE and w0 <= s < w1)
        ops = [(n, max(s, w0), min(e, w1)) for n, s, e in ops
               if e > w0 and s < w1]
        attn = 0.0
        for (p, op), t in TR.leaf_times(_in_module(mods, ops)).items():
            sc = scopes.get(p, {}).get(op, UNSCOPED)
            scope_t[(p, sc)] += t / n_dev
            ops_t[f"{p}/{op}"] += t / n_dev
            if p == DECODE and sc == DECODE_ISLAND:
                attn += t
        if n_dec:
            dec_planes += 1
            dec_dev += TR.measure(TR.union(by_prog[DECODE])) / n_dec
            dec_attn += attn / n_dec
    host = _decode_host(ev, lag, w0, w1)
    ms = 1e-6
    base.update(
        device_ops=[[n, t * 1e-9] for n, t in
                    sorted(ops_t.items(), key=lambda kv: -kv[1])[:top]],
        clock_lag_ms=lag * ms,
        device_programs=[[p, t * 1e-9] for p, t in
                         sorted(prog_t.items(), key=lambda kv: -kv[1])],
        device_scopes=[[p, sc, t * 1e-9] for (p, sc), t in
                       sorted(scope_t.items(), key=lambda kv: -kv[1])],
        decode_device_ms=dec_dev / dec_planes * ms if dec_planes else None,
        decode_host_ms=host,
        decode_attn_island_ms=(dec_attn / dec_planes * ms
                               if dec_planes and scopes else None))
    return base


def _decode_host(ev: dict, lag: float, w0, w1):
    """Mean device-idle ms inside the decode ``engine.step`` spans that
    start in the window, the device clock shifted by ``lag``."""
    steps = [(s, e) for k, s, e in step_kinds(ev, lag)
             if k == "decode" and w0 <= s < w1]
    if not steps:
        return None
    idle = 0.0
    for ops in ev["devices"].values():
        busy = TR.union([(s + lag, e + lag) for _, s, e in ops])
        for s, e in steps:
            idle += (e - s) - TR.measure(TR.intersect(busy, [[s, e]]))
    return idle / len(ev["devices"]) / len(steps) * 1e-6


def pad_frac(tokens: int, slot_tokens: int):
    """``prefill_pad_frac`` from the change of the engine's
    ``prefill_tokens`` and ``prefill_slot_tokens`` over a span: the share
    of the dispatched prefill rows x bucket that held no prompt token."""
    return 1.0 - tokens / slot_tokens if slot_tokens else None
