"""Where a cell's step time goes: one run of the cell as ``bench/run.py``
serves it, with the engine's phase split kept for every step and, with
``--trace 1``, the profiler trace reduced by ``bench/phases.py`` into device
time per program and per scope, idle time per engine phase, and the clock
lag between host and device.

    python3 bench/profile.py --workload starcoder2-15b-l10.chat --seed 7 \
        --seconds 51 --trace 1 --out profile-7.json

It prints the end-to-end metrics of the window (meaningful with
``--trace 0``: the profiler slows the loop), the mean phase split of each
step kind, the longest step's split and, for every step over half a
second, the phase that held most of it, then the trace's reduction. The
full record goes to ``--out``; the last line of standard output is a
summary. It makes no ``correct`` check and reports no metric to the
benchmark; ``bench/run.py`` does both.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

STALL_S = 0.5
PAD = ("prefill_tokens", "prefill_slot_tokens")


def keep_phases(engine) -> list:
    """Record, after every engine step, (end on the host clock, kind, its
    phase split): the engine's ``step`` is wrapped in place."""
    steps = []
    step = engine.step

    def recorded():
        kind = step()
        if kind is not None:
            steps.append((time.perf_counter(), kind,
                          dict(engine.last_phases)))
        return kind

    engine.step = recorded
    return steps


def split(steps, run) -> dict:
    """Phase split of the window's steps: mean ms per phase of each kind,
    the longest step's split, the steps over ``STALL_S`` with the phase
    that held most of each, and ``prefill_pad_frac`` from the engine's
    counters over the window."""
    from bench.phases import pad_frac

    w0, w1 = run.start + run.w0, run.start + run.w1
    inside = [s for s in steps if w0 <= s[0] < w1]
    by_kind: dict = {}
    for _, kind, ph in inside:
        k = by_kind.setdefault(kind, {"steps": 0, "ms": {}})
        k["steps"] += 1
        for name, sec in ph.items():
            k["ms"][name] = k["ms"].get(name, 0.0) + sec * 1e3
    for k in by_kind.values():
        k["ms"] = {n: v / k["steps"] for n, v in sorted(k["ms"].items())}
    total = [sum(ph.values()) for _, _, ph in inside]
    longest = max(range(len(inside)), key=total.__getitem__, default=None)
    return {
        "by_kind": by_kind,
        "longest": None if longest is None else {
            "kind": inside[longest][1], "ms": total[longest] * 1e3,
            "phases_ms": {n: v * 1e3 for n, v in inside[longest][2].items()}},
        "stalls": [{"kind": kind, "ms": t * 1e3,
                    "held_by": max(ph, key=ph.get)}
                   for (_, kind, ph), t in zip(inside, total)
                   if t > STALL_S],
        "prefill_pad_frac": pad_frac(*(run.counters[n] for n in PAD))}


def main(argv=None, *, cell=None, devices=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench import cell as C
    from bench import loop
    from bench.run import end_to_end, reduce_trace
    from bench.traffic import Traffic

    spec = cell if cell is not None else C.resolve(args.workload)
    if devices is None:
        devices = C.check_devices(spec["chips"])
        C.use_compile_cache()
    conf, mix = spec["config"], spec["traffic"]
    built = C.build(spec["model"], conf, args.seed, devices)
    eng = built.engine
    vocab = built.cfg.vocab_size
    loop.warm_up(eng, vocab, C.rng(args.seed, 4))
    steps = keep_phases(eng)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    run = loop.serve(eng, Traffic(mix, args.seed, vocab),
                     preroll_s=float(mix["preroll_s"]),
                     seconds=args.seconds, trace_dir=trace_dir,
                     counters=PAD)
    out = {"workload": spec["name"], "seed": args.seed,
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind,
                      "count": len(devices)},
           "end_to_end": end_to_end(run, run.start + run.w0 - T0),
           "phases": split(steps, run)}
    if trace_dir:
        path = loop.trace_file(trace_dir)
        if path:
            out["trace"] = reduce_trace(path, eng, top=32)
        shutil.rmtree(trace_dir, ignore_errors=True)
    text = json.dumps(out, indent=1)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text + "\n")
    print(text, file=sys.stderr)
    tr = out.get("trace", {})
    print(json.dumps({k: tr.get(k) for k in (
        "clock_lag_ms", "decode_device_ms", "decode_host_ms",
        "decode_attn_island_ms", "idle_frac")}
        | {"prefill_pad_frac": out["phases"]["prefill_pad_frac"],
           "end_to_end": out["end_to_end"]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
