"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload starcoder2-15b-l10.chat --seed 7 \
        --seconds 30 --trace 0

Set-up (counted in ``setup_s``): find the cell by name, check the chips,
draw the weights from the seed on the device, build the serving engine,
compile and warm every program the window runs, then serve the mix's
pre-roll. The window then serves the mix for ``--seconds``; nothing
compiles in it. Afterwards the device's peak memory is read, the engine's
cache freed, and the float32 reference of the configuration's model module
decides ``correct`` on a sample of the finished requests. The last line of
standard output is one JSON object; ``--trace 0`` reports the end-to-end
metrics. ``--trace 1`` reports the per-layer metrics: it profiles a few
seconds of the window, keeps the engine counters that the readers and the
model module declare (``COUNTERS``), and reduces the trace by program,
scope and engine phase (``bench/phases.py``) with the scopes read from the
step programs' HLO after the window.

It exits 2, printing no result, when JAX finds no TPU, an unknown kind, or
fewer chips than the cell needs.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

BREAKDOWN = 10           # entries in each list of the traced breakdown


def reader(metrics_dir: Path, name: str):
    from bench.cell import load_file
    return load_file(metrics_dir / f"{name}.py")


def read_metric(metrics_dir: Path, name: str, rec: dict):
    """Value of per-layer metric ``name`` from its reader file, or None
    where the reader finds nothing to read."""
    return reader(metrics_dir, name).read(rec)


def counters_of(spec: dict) -> tuple:
    """The engine counters the cell's per-layer readers and its model
    module declare (``COUNTERS``), each once."""
    mods = [reader(spec["metrics_dir"], m["name"])
            for m in spec["per_layer"]] + [spec["model"]]
    names = [n for mod in mods for n in getattr(mod, "COUNTERS", ())]
    return tuple(dict.fromkeys(names))


def reduce_trace(path, eng, top: int = BREAKDOWN) -> dict:
    """The trace reduced by program, scope and engine phase
    (``bench/phases.reduce``), with ``hlo_s``, the seconds it took to read
    the scopes from the HLO of the engine's step programs; read while the
    engine still holds its cache. The executables' fingerprints do not
    appear in the trace's module names, so scopes are matched by program
    name: one executable per name in a cell's window."""
    from bench import phases as PH

    t = time.perf_counter()
    scopes = {name: PH.scope_map(compiled.as_text())
              for name, compiled in eng.step_programs().items()}
    hlo_s = time.perf_counter() - t
    out = PH.reduce(PH.events(path), scopes, top=top)
    return {**out, "hlo_s": hlo_s} if out else {}


def ttfts(run) -> list:
    """Due time to first token of every request due in the window; one
    with no first token by the window's end counts at its wait so far."""
    return [min(r.first if r.first is not None else np.inf, run.w1) - r.due
            for r in run.due_in_window()]


def end_to_end(run, setup_s: float) -> dict:
    """The metrics a user of the served model sees, over the window."""
    w = run.w1 - run.w0
    ttft = ttfts(run)
    return {"ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3,
            "itl_p99_ms": float(np.percentile(run.gaps, 99)) * 1e3,
            "output_tok_s": run.tokens_in_window / w,
            "setup_s": setup_s}


def tails(run) -> str:
    """More percentiles of TTFT and of the inter-token gaps, with their
    sample counts, for the record on standard error."""
    ttft = ttfts(run)
    qs = (50, 85, 87.5, 90, 95)
    out = [f"ttft_ms n={len(ttft)}"] + [
        f"p{q}={np.percentile(ttft, q) * 1e3:.3f}" for q in qs]
    qs = (50, 99, 99.5, 99.8, 99.9)
    out += [f"itl_ms n={len(run.gaps)}"] + [
        f"p{q}={np.percentile(run.gaps, q) * 1e3:.3f}" for q in qs]
    return " ".join(out)


def stalls(run) -> str:
    """The window's longest step, what the host did meanwhile, the steps
    over half a second, and the longest host time between steps."""
    from bench.loop import HOST

    steps = [s for s in run.steps if s.t0 >= run.w0]
    if not steps:
        return "no steps in the window"
    big = max(steps, key=lambda s: s.t1 - s.t0)
    host = ", ".join(f"{n} {v:.3f}" for n, v in zip(HOST, big.host))
    over = sum(s.t1 - s.t0 > 0.5 for s in steps)
    between = [(b.t0 - a.t1, a.t1) for a, b in zip(steps, steps[1:])]
    gap, at = max(between, default=(0.0, run.w0))
    return (f"longest step {big.kind} {(big.t1 - big.t0) * 1e3:.3f} ms at "
            f"{big.t0 - run.w0:.3f} s ({host}); {over} steps over 500 ms; "
            f"longest time between steps "
            f"{gap * 1e3:.3f} ms at {at - run.w0:.3f} s; garbage collection "
            f"before the pre-roll {run.gc_s * 1e3:.3f} ms over "
            f"{run.gc_objects} objects, {len(run.gc_pauses)} after it "
            f"({sum(run.gc_pauses) * 1e3:.3f} ms); profiler start "
            f"{run.trace_start_s * 1e3:.3f} ms")


def record(run, conf: dict, model, kind: str, chips: int,
           compile_s: float, trace: dict, peak_bytes) -> dict:
    """What the per-layer readers read: the run, the steps inside the
    traced span, the model module with the shapes its counts take, the
    peaks, set-up compile seconds, the trace reduction, memory."""
    from bench.counts import peak
    lo, hi = run.traced or (0.0, 0.0)
    # a CPU rehearsal has no peaks: the readers of shares report nothing
    return {"run": run, "traced_steps": [s for s in run.steps
                                         if lo <= s.t0 < hi],
            "model": model, "shapes": model.shapes(conf),
            "peak": peak(kind) if kind != "cpu" else None,
            "chips": chips, "compile_s": compile_s, "trace": trace,
            "memory_peak_bytes": peak_bytes}


def breakdown(trace: dict) -> dict:
    """The traced run's breakdown: device ops by self time, named
    ``<program>/<op>``; idle gaps by the span the host was in; device self
    time by ``<program>/<scope>``."""
    return {"device_ops": trace["device_ops"],
            "idle_gaps": trace["idle_gaps"],
            "device_scopes": [[f"{p}/{sc}", t] for p, sc, t
                              in trace["device_scopes"][:BREAKDOWN]]}


def main(argv=None, *, cell=None, devices=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    from bench import cell as C
    from bench import loop, reference
    from bench.traffic import Traffic

    spec = cell if cell is not None else C.resolve(args.workload)
    if devices is None:
        devices = C.check_devices(spec["chips"])
        C.use_compile_cache()
    clock = C.CompileClock()
    conf, mix = spec["config"], spec["traffic"]

    model = spec["model"]
    built = C.build(model, conf, args.seed, devices)
    eng = built.engine
    vocab = built.cfg.vocab_size
    loop.warm_up(eng, vocab, C.rng(args.seed, 4))
    compile_s = clock.seconds
    traffic = Traffic(mix, args.seed, vocab)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    counters = counters_of(spec) if args.trace else ()
    preroll_s = float(mix["preroll_s"])
    run = loop.serve(eng, traffic, preroll_s=preroll_s, seconds=args.seconds,
                     compile_clock=clock, trace_dir=trace_dir,
                     counters=counters)
    setup_s = run.start + run.w0 - T0      # process start to window start
    kind = devices[0].device_kind
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)
    trace = {}
    if trace_dir:
        path = loop.trace_file(trace_dir)
        trace = reduce_trace(path, eng) if path else {}
        shutil.rmtree(trace_dir, ignore_errors=True)

    eng.cache = None                 # free the slot slab for the reference
    gc.collect()
    check = reference.compare(model, eng.params, conf, run.reqs,
                              C.rng(args.seed, 3))
    served = [r for r in run.reqs if r.tokens is not None]
    short = sum(len(r.tokens) != r.out_len for r in served)
    limit = conf["check"]["logit_gap_limit"]
    checks = {"logit_gap": {"value": check["logit_gap"], "limit": limit},
              "short_answers": {"value": short, "limit": 0},
              "tokens_compared": {"value": check["tokens"], "limit": 1}}
    correct = bool(check["logit_gap"] <= limit and short == 0
                   and check["tokens"] >= 1)

    attempted = run.due_in_window()
    failed = sum(r.rid in eng.quarantined or r.rid in eng.expired
                 for r in attempted)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    out = {"correct": correct, "attempted": len(attempted),
           "failed": failed}
    if args.trace:
        rec = record(run, conf, model, kind, len(devices), compile_s, trace,
                     peak_bytes)
        metrics = {}
        for m in spec["per_layer"]:
            v = read_metric(spec["metrics_dir"], m["name"], rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = trace.get("busy_s", 0.0)
        device["window_s"] = trace.get("window_s", 0.0)
        out.update(metrics=metrics, device=device)
        if trace:
            out["breakdown"] = breakdown(trace)
    else:
        e2e = end_to_end(run, setup_s)
        out.update(metrics={m["name"]: {"value": e2e[m["name"]],
                                        "unit": m["unit"]}
                            for m in spec["end_to_end"]},
                   device=device)
    out["checks"] = checks
    print(f"[bench] {spec['name']} seed {args.seed}: "
          f"{len(run.reqs)} requests submitted, {len(served)} finished, "
          f"{len(run.steps)} steps, {run.tokens_in_window} tokens in the "
          f"window; compile {compile_s:.3f} s ({clock.cache_hits} cache "
          f"hits), {run.compiles_in_window} programs compiled or loaded in "
          f"the window; generator late by up to "
          f"{run.late_max_s * 1e3:.3f} ms; "
          f"{check['requests']} requests compared"
          + (f"; scopes read from the HLO in {trace['hlo_s']:.3f} s"
             if trace else ""), file=sys.stderr)
    print(f"[bench] {tails(run)}", file=sys.stderr)
    print(f"[bench] {stalls(run)}", file=sys.stderr)
    for name, c in checks.items():
        print(f"[check] {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
