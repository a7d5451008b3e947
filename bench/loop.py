"""The measured loop: drive ``ServingEngine.submit`` and ``step`` with a
mix's requests on the host clock, and record what each request and each
step did. The step is synchronous (its greedy sampling pulls the tokens to
the host), so a token exists when ``step`` returns.

Host spans go into the profiler's trace as ``TraceAnnotation``s named
``bench.*``; the trace reduction labels device idle gaps with them.

Python's cyclic garbage collector is off from the pre-roll's start to the
window's end: a full collection walks every object the set-up left (JAX's
traced programs among them) and pauses the loop, while the loop itself
makes no reference cycles. The set-up's objects are collected and frozen
before the pre-roll.

Each step also records what the host did meanwhile (``host_counters``), so
that a step far longer than its kind can be told apart: the main thread
busy in Python, the machine's CPUs taken by the hypervisor, pages read from
disk, or the thread waiting on the device.

Engine counters: ``serve(counters=names)`` reads each named attribute of
the engine before and after every step (``Step.counters``, the change
over the step) and at the window's edges (``Run.counters``, the change
over the window). An attribute the engine lacks reads ``None``. The names
are those that the cell's readers and model module declare as
``COUNTERS``, so a counter the program gains reaches a reader with no
change here.
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import os
import resource
import time

import numpy as np
from jax.profiler import TraceAnnotation

clock = time.perf_counter


@dataclasses.dataclass
class Req:
    due: float               # seconds from the pre-roll's start
    prompt: np.ndarray
    out_len: int
    client: int = -1
    rid: int = -1
    admit: float | None = None    # start of the step that admitted it
    first: float | None = None    # end of the step that gave token 1
    last: float | None = None
    n: int = 0
    tokens: list | None = None    # served ids, once it completed


@dataclasses.dataclass
class Step:
    kind: str
    t0: float
    t1: float
    kv_lens: list            # decode: positions each live slot attends to
    prompt_lens: list        # prefill: prompts it admitted
    host: tuple = ()         # host_counters() over the step
    counters: dict = dataclasses.field(default_factory=dict)  # engine's


@dataclasses.dataclass
class Run:
    w0: float
    w1: float
    reqs: list
    steps: list
    gaps: list               # inter-token gaps inside the window
    tokens_in_window: int
    compiles_in_window: int  # compiled or read from the cache
    late_max_s: float        # how late the generator submitted (open loop)
    start: float             # clock() at the pre-roll's start
    trace_start_s: float = 0.0   # how long starting the profiler held it
    gc_s: float = 0.0        # the full collection before the pre-roll
    gc_objects: int = 0      # objects it walked
    gc_pauses: list = dataclasses.field(default_factory=list)  # after it
    counters: dict = dataclasses.field(default_factory=dict)  # engine's
    traced: tuple | None = None  # the bench.traced span, on this clock

    def due_in_window(self):
        return [r for r in self.reqs if self.w0 <= r.due < self.w1]


HOST = ("main-thread CPU s", "steal s", "major faults",
        "voluntary switches", "involuntary switches")


def host_counters() -> np.ndarray:
    """The main thread's CPU seconds, the seconds of steal over all the
    machine's CPUs (``/proc/stat``; 0 where it is missing), and the
    process's major page faults and voluntary and involuntary context
    switches, so far."""
    steal = 0.0
    try:
        with open("/proc/stat") as f:
            steal = int(f.readline().split()[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        pass
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return np.array([time.thread_time(), steal, ru.ru_majflt, ru.ru_nvcsw,
                     ru.ru_nivcsw])


def read_counters(engine, names) -> dict:
    return {n: getattr(engine, n, None) for n in names}


def change(before: dict, after: dict) -> dict:
    """Per counter, ``after - before``; ``None`` where either is."""
    return {n: None if v is None or after[n] is None else after[n] - v
            for n, v in before.items()}


def warm_up(engine, vocab: int, seed_rng) -> None:
    """Compile every program the cell's window will run: each bucket's
    prefill, the decode step, and the cache scatter for each group size."""
    serve = engine.serve

    def drain(lens):
        for n in lens:
            engine.submit(seed_rng.integers(0, vocab, size=n), 2)
        while engine.step() is not None:
            pass

    for edge in serve.bucket_edges:
        drain([edge])
    for g in range(2, serve.prefill_batch + 1):
        drain([serve.bucket_edges[0]] * g)


def serve(engine, traffic, *, preroll_s: float, seconds: float,
          compile_clock=None, trace_dir: str | None = None,
          trace_s: float = 3.0, counters: tuple = ()) -> Run:
    """Serve the mix from its pre-roll's start to the window's end.

    Open loop: each request is submitted once the clock reaches its due
    time, whatever the engine is doing. Closed loop: each client submits
    its next request when its last one completes. With ``trace_dir`` the
    profiler records the ``trace_s`` seconds that end a second before the
    window closes (the ``bench.traced`` span), and is stopped only after
    the window, since stopping it holds the loop for seconds.
    """
    gc_objects = len(gc.get_objects())
    t = clock()
    gc.collect()
    gc_s = clock() - t
    gc.freeze()
    pauses: list[float] = []

    def on_gc(phase, _info):
        if phase == "start":
            pauses.append(-clock())
        else:
            pauses[-1] += clock()

    gc.callbacks.append(on_gc)
    gc.disable()
    try:
        run = _serve(engine, traffic, preroll_s, seconds, compile_clock,
                     trace_dir, trace_s, tuple(counters))
    finally:
        gc.enable()
        gc.callbacks.remove(on_gc)
    run.gc_s, run.gc_objects, run.gc_pauses = gc_s, gc_objects, pauses
    return run


def _serve(engine, traffic, preroll_s, seconds, compile_clock, trace_dir,
           trace_s, counters) -> Run:
    import jax

    spec = traffic.spec
    start = clock()
    w0, w1 = preroll_s, preroll_s + seconds
    reqs: list[Req] = []
    by_rid: dict[int, Req] = {}
    steps: list[Step] = []
    gaps: list[float] = []
    tok_in = 0
    late = 0.0
    compiles0 = None
    trace_at = max(w0, w1 - trace_s - 1.0) if trace_dir else None
    traced = None
    traced_span = None
    trace_start_s = 0.0
    at_w0 = None                 # the counters at the window's start

    def submit(r: Req):
        with TraceAnnotation("bench.submit"):
            r.rid = engine.submit(r.prompt, r.out_len)
        by_rid[r.rid] = r
        reqs.append(r)

    def emit(r: Req, n: int, t: float):
        nonlocal tok_in
        if n <= r.n:
            return
        if r.n == 0:
            r.first = t
        elif w0 <= r.last and t < w1:
            gaps.append(t - r.last)
        if w0 <= t < w1:
            tok_in += n - r.n
        r.n, r.last = n, t

    if spec["loop"] == "open":
        nxt = Req(*traffic.next())
    else:
        for c in range(spec["clients"]):
            _, ids, out = traffic.next()
            submit(Req(0.0, ids, out, client=c))
    n_events = len(engine.events)
    n_done = len(engine.completions)
    while True:
        t = clock() - start
        if t >= w1:
            break
        if compiles0 is None and t >= w0 and compile_clock is not None:
            compiles0 = compile_clock.programs
        if at_w0 is None and t >= w0:
            at_w0 = read_counters(engine, counters)
        if trace_at is not None and t >= trace_at and traced is None:
            t0 = clock()
            jax.profiler.start_trace(trace_dir)
            trace_start_s = clock() - t0
            traced = TraceAnnotation("bench.traced")
            traced.__enter__()
            traced_span = (clock() - start, None)
        if traced is not None and trace_at is not None \
                and clock() - start >= trace_at + trace_s:
            traced.__exit__(None, None, None)
            traced_span = (traced_span[0], clock() - start)
            trace_at = None
        if spec["loop"] == "open":
            while nxt.due <= t:
                late = max(late, t - nxt.due) if t >= w0 else late
                submit(nxt)
                nxt = Req(*traffic.next())
        if not engine.pending:
            wait = (nxt.due if spec["loop"] == "open" else w1) - t
            with TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(wait, w1 - t)))
            continue
        live = [s.prompt_len + len(s.tokens) for s in engine.slots
                if s is not None]
        c0 = read_counters(engine, counters)
        h0 = host_counters()
        t0 = clock() - start
        with TraceAnnotation("bench.step"):
            kind = engine.step()
        t1 = clock() - start
        host = tuple((host_counters() - h0).tolist())
        c1 = change(c0, read_counters(engine, counters))
        admitted = []
        for ev in engine.events[n_events:]:
            if ev[0] == "admit":
                r = by_rid.get(ev[2])
                if r is not None:
                    r.admit = t0
                    admitted.append(len(r.prompt))
        n_events = len(engine.events)
        steps.append(Step(kind, t0, t1, live if kind == "decode" else [],
                          admitted, host, c1))
        for s in engine.slots:
            if s is not None and s.rid in by_rid:
                emit(by_rid[s.rid], len(s.tokens), t1)
        done = list(engine.completions)[n_done:]
        n_done = len(engine.completions)
        for rid in done:
            r = by_rid.get(rid)
            if r is None:
                continue
            r.tokens = engine.completions[rid].tokens
            emit(r, len(r.tokens), t1)
            if spec["loop"] == "closed":
                _, ids, out = traffic.next()
                submit(Req(t1, ids, out, client=r.client))
    if traced is not None:
        if trace_at is not None:
            traced.__exit__(None, None, None)
            traced_span = (traced_span[0], clock() - start)
        jax.profiler.stop_trace()
    window = change(at_w0 or dict.fromkeys(counters),
                    read_counters(engine, counters))
    n_compiles = (compile_clock.programs - compiles0
                  if compile_clock is not None and compiles0 is not None
                  else 0)
    return Run(w0, w1, reqs, steps, gaps, tok_in, n_compiles, late, start,
               trace_start_s, counters=window, traced=traced_span)


def trace_file(trace_dir: str) -> str | None:
    found = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return found[0] if found else None
