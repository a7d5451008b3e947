"""Readings the comparison's limit is set from, at a cell's own size and
load, in one process: for each seed, fresh weights, one run of the mix
(pre-roll and window), then the widest logit gap of the served tokens
(the program's reading) and, on the first ``--control`` seeds, that of the
float8 control (``reference.gaps(control=True)``).

    python3 bench/control.py --workload starcoder2-15b-l10.chat \
        --seconds 30 --seeds 1 2 3 4 5 6 7 8 9 10 11 12 --control 4

The limit in the configuration file lies between the largest program
reading and the smallest control reading. The benchmark's own runs never
run the control.
"""

import argparse
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args()

    from bench import cell as C
    from bench import loop, reference
    from bench.traffic import Traffic

    spec = C.resolve(args.workload)
    devices = C.check_devices(spec["chips"])
    C.use_compile_cache()
    conf, mix = spec["config"], spec["traffic"]
    model = spec["model"]
    built = C.build(model, conf, args.seeds[0], devices)
    eng = built.engine
    vocab = built.cfg.vocab_size
    loop.warm_up(eng, vocab, C.rng(args.seeds[0], 4))
    for i, seed in enumerate(args.seeds):
        if i:
            eng.params = None
            eng.params = C.make_weights(built.tmpl, seed, built.cfg.d_model,
                                        built.mesh)
        t = time.perf_counter()
        run = loop.serve(eng, Traffic(mix, seed, vocab),
                         preroll_s=float(mix["preroll_s"]),
                         seconds=args.seconds)
        eng.take_undone()
        got = reference.compare(model, eng.params, conf, run.reqs,
                                C.rng(seed, 3), control=i < args.control)
        print(f"[control] seed {seed}: {got} "
              f"({time.perf_counter() - t:.1f} s)", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
