"""Find an open-loop cell's knee: serve its mix at each of a few rates in
one process (one set-up) and report, per rate, the tokens per second
completed, TTFT, and whether the queue grew through the window.

    python3 bench/sweep.py --workload starcoder2-15b-l10.chat --seed 3 \
        --rates 3 4 5 6 --seconds 20

The knee is the highest rate whose queue stays bounded; the cell's rate is
written into its traffic file as a number (about 0.8 of the knee).
"""

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--preroll", type=float, default=6.0)
    args = ap.parse_args()

    from bench import cell as C
    from bench import loop
    from bench.run import ttfts
    from bench.traffic import Traffic

    spec = C.resolve(args.workload)
    devices = C.check_devices(spec["chips"])
    C.use_compile_cache()
    built = C.build(spec["model"], spec["config"], args.seed, devices)
    eng = built.engine
    vocab = built.cfg.vocab_size
    loop.warm_up(eng, vocab, C.rng(args.seed, 4))
    for rate in args.rates:
        mix = {**spec["traffic"], "rate_per_s": rate}
        run = loop.serve(eng, Traffic(mix, args.seed, vocab),
                         preroll_s=args.preroll, seconds=args.seconds)
        due = run.due_in_window()
        ttft = ttfts(run)
        started = sum(r.first is not None for r in due)
        dec = [len(s.kv_lens) for s in run.steps if s.kind == "decode"
               and s.t0 >= run.w0]
        print(f"[sweep] rate {rate}: {len(due)} due, {started} started, "
              f"queue at end {len(eng.queue)}, output "
              f"{run.tokens_in_window / args.seconds:.1f} tok/s, ttft p50 "
              f"{np.percentile(ttft, 50) * 1e3:.1f} p90 "
              f"{np.percentile(ttft, 90) * 1e3:.1f} ms, itl p99 "
              f"{np.percentile(run.gaps, 99) * 1e3:.1f} ms, decode slots "
              f"{np.mean(dec):.1f}", flush=True)
        eng.take_undone()            # abandon what is left; slots reset
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
