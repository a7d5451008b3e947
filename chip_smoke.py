"""Chip smoke test: serve tinyllama-1.1b at its published widths on a TPU.

    python chip_smoke.py             # one chip: the serving engine, no mesh
    python chip_smoke.py --chips 4   # a (1, 4) data x model mesh, auto
                                     # dispatch vs reference_mode

One chip: ``build_engine(reduced=False)`` -> ``ServingEngine.run`` in
continuous mode over 12 requests whose prompt lengths (32..512) and tokens
come from ``--seed``, with random weights from the same seed. Every request
must complete with its token count and no row may go non-finite. Then one
prompt's last-position prefill logits from the chip are checked against the
same parameters run through the same jitted prefill program on the CPU
backend of this process — a reference, not a fallback.

Four chips (``--chips 4``, only this phase): the same model on a (1, 4)
mesh with policy dispatch, against an engine built the same way with
``reference_mode=True`` (every island on its dense twin). Prefill logits
must agree within tolerance, the plan table must put at least one island on
a ring or fused backend, and every device must hold its share of the
parameters.

The script exits non-zero, printing no result, when JAX finds no TPU or a
TPU kind the repo has no spec for. The last line of standard output is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "tinyllama-1.1b"
#: bf16 tolerance on last-position prefill logits: ||a - b|| / ||b||. One
#: bf16 rounding is 2^-9 relative; 22 layers of differently fused and
#: rounded ops compound it to the 1e-2 range, while a wrong program lands
#: near 1.4 (uncorrelated logits).
LOGIT_REL_TOL = 5e-2


def fail(msg: str) -> None:
    print(f"[smoke] FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


def rel_err(got, want) -> float:
    import numpy as np
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def requests(n: int, vocab: int, seed: int, lo: int = 32, hi: int = 512):
    import numpy as np
    rng = np.random.RandomState(seed)
    return [tuple(int(t) for t in rng.randint(0, vocab, size=int(L)))
            for L in rng.randint(lo, hi + 1, size=n)]


class CompileClock:
    """Seconds JAX spent compiling (persistent-cache reads included) and
    persistent-cache hits, from ``jax.monitoring`` events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def check_device(chips: int):
    """The TPU this run measures, or exit: no fallback to another backend."""
    import jax

    from repro.core import costmodel as cm
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"[smoke] no TPU found: JAX's default backend is "
              f"{d.platform!r} ({d.device_kind})", file=sys.stderr)
        raise SystemExit(2)
    if d.device_kind not in cm.DEVICE_KINDS:
        print(f"[smoke] no HardwareSpec for device kind {d.device_kind!r} "
              f"(known: {sorted(cm.DEVICE_KINDS)})", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"[smoke] --chips {chips} needs {chips} TPUs; JAX sees "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(2)
    return devs


def peak_bytes(dev):
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


def describe(eng) -> None:
    import jax
    cfg = eng.cfg
    leaves = jax.tree.leaves(eng.params)
    n_params = sum(x.size for x in leaves)
    n_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    print(f"[smoke] model {cfg.name}: layers={cfg.n_layers} "
          f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"params={n_params} ({n_bytes / 1e9:.3f} GB)")


def serve_trace(eng, trace, label: str):
    """Run the trace to completion; every request must finish with its full
    token count and no prefill/decode row may be non-finite."""
    t0 = time.perf_counter()
    done = eng.run(trace)
    wall = time.perf_counter() - t0
    st = eng.stats()
    want = eng.serve.max_new_tokens
    short = [c.rid for c in done if len(c.tokens) != want]
    print(f"[smoke] {label}: {len(done)}/{len(trace)} requests, "
          f"{st['tokens_generated']} tokens "
          f"({st['prefill_steps']} prefill + {st['decode_steps']} decode "
          f"steps) in {wall:.3f}s wall; quarantined={st['quarantined']}")
    if len(done) != len(trace) or short:
        fail(f"{label}: {len(done)}/{len(trace)} completed; short: {short}")
    if st["quarantined"] or eng.quarantined:
        fail(f"{label}: non-finite logits quarantined "
             f"{sorted(eng.quarantined)}")
    return done, wall


def one_chip(args, clock) -> None:
    import jax
    import numpy as np

    from repro.configs.base import ServeConfig
    from repro.launch.serve import build_engine
    from repro.runtime.serving import render_serving_plans

    serve = ServeConfig(max_batch=8, prefill_batch=4, bucket_edges=(128, 512),
                        max_new_tokens=32)
    eng = build_engine(ARCH, reduced=False, serve=serve, seed=args.seed)
    describe(eng)
    print("[smoke] serving plan table (no mesh: every island dense)")
    print(render_serving_plans(eng.bucket_plans))
    trace = requests(12, eng.cfg.vocab_size, args.seed)
    print(f"[smoke] prompt lengths {[len(p) for p in trace]}")
    _, wall = serve_trace(eng, trace, "serve")
    print(f"[smoke] compile seconds {clock.seconds:.3f} "
          f"(persistent-cache hits {clock.cache_hits}); wall seconds "
          f"{wall:.3f}")
    dev = jax.devices()[0]
    print(f"[smoke] peak_bytes_in_use "
          f"{peak_bytes(dev)} on {dev.device_kind}")

    # reference: the same prefill program and weights on the CPU backend
    prompt = [trace[0][:128]]
    chip = eng.prefill_logits(prompt)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref = eng.prefill_logits(prompt,
                                 params=jax.device_put(eng.params, cpu))
    if not (np.isfinite(chip).all() and np.isfinite(ref).all()):
        fail("non-finite prefill logits")
    err = rel_err(chip, ref)
    print(f"[smoke] reference check (chip vs CPU backend, prompt "
          f"len {len(prompt[0])}): rel_l2={err:.6f} (tol {LOGIT_REL_TOL}) "
          f"max_abs={float(np.abs(chip - ref).max()):.6f} "
          f"argmax chip={int(chip.argmax())} cpu={int(ref.argmax())}")
    if err > LOGIT_REL_TOL:
        fail(f"chip logits differ from the CPU reference: {err:.4f}")


def four_chips(args, clock) -> None:
    import jax
    import numpy as np

    from repro.configs.base import ServeConfig
    from repro.launch.serve import build_engine
    from repro.runtime.serving import render_serving_plans

    # prefill_batch 8 x bucket 512 puts 4096 tokens through the MLP island,
    # where the policy leaves bulk (smaller GEMMs stay bulk on v5e)
    serve = ServeConfig(max_batch=8, prefill_batch=8, bucket_edges=(512,),
                        max_new_tokens=16)
    kw = dict(reduced=False, mesh_shape=(1, 4), serve=serve, seed=args.seed,
              comm_policy="auto")
    eng = build_engine(ARCH, **kw)
    ref = build_engine(ARCH, **kw, run_overrides={"reference_mode": True})
    describe(eng)
    print("[smoke] serving plan table ((1, 4) data x model mesh, auto "
          "dispatch)")
    print(render_serving_plans(eng.bucket_plans))
    overlapped = [(name, p.island, p.backend)
                  for name, bp in eng.bucket_plans.items() for p in bp.plans
                  if not p.fallback
                  and p.backend in ("ring", "ring_bidir", "fused")]
    print(f"[smoke] islands on ring/fused: {overlapped}")
    if not overlapped:
        fail("no island on a ring or fused backend")

    # every device holds its share of the parameters
    per_dev: dict = {}
    for leaf in jax.tree.leaves(eng.params):
        for sh in leaf.addressable_shards:
            per_dev[sh.device] = per_dev.get(sh.device, 0) + sh.data.nbytes
    for d in jax.devices()[:4]:
        ms = d.memory_stats() or {}
        print(f"[smoke] device {d.id}: param bytes {per_dev.get(d, 0)} "
              f"bytes_in_use {ms.get('bytes_in_use')} "
              f"peak_bytes_in_use {ms.get('peak_bytes_in_use')}")
    shares = [per_dev.get(d, 0) for d in jax.devices()[:4]]
    if min(shares) == 0 or max(shares) > 1.1 * min(shares):
        fail(f"parameters are not spread over the 4 devices: {shares}")

    trace = requests(8, eng.cfg.vocab_size, args.seed)
    got = eng.prefill_logits(trace)
    want = ref.prefill_logits(trace)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        fail("non-finite prefill logits")
    err = max(rel_err(g, w) for g, w in zip(got, want))
    print(f"[smoke] auto dispatch vs reference_mode prefill logits "
          f"(8 prompts, bucket 512): worst rel_l2={err:.6f} "
          f"(tol {LOGIT_REL_TOL}) "
          f"max_abs={float(np.abs(got - want).max()):.6f}")
    if err > LOGIT_REL_TOL:
        fail(f"auto-dispatch logits differ from reference_mode: {err:.4f}")

    done, wall = serve_trace(eng, trace, "serve (auto dispatch)")
    done_ref, _ = serve_trace(ref, trace, "serve (reference_mode)")
    toks = [(a.tokens, b.tokens) for a, b in zip(done, done_ref)]
    same_tok = sum(x == y for a, b in toks for x, y in zip(a, b))
    n_tok = sum(len(a) for a, _ in toks)
    print(f"[smoke] greedy agreement with reference_mode: "
          f"{sum(a == b for a, b in toks)}/{len(toks)} requests identical, "
          f"{same_tok}/{n_tok} tokens equal position-wise")
    print(f"[smoke] compile seconds {clock.seconds:.3f} "
          f"(persistent-cache hits {clock.cache_hits}); wall seconds "
          f"{wall:.3f} (auto dispatch serve)")
    # the allocator itself must see each share (a CPU rehearsal, which
    # reports no memory_stats, stops here)
    held = [(d.memory_stats() or {}).get("bytes_in_use", 0)
            for d in jax.devices()[:4]]
    if any(h < s for h, s in zip(held, shares)):
        fail(f"memory_stats bytes_in_use {held} below the parameter "
             f"shares {shares}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    # the one-chip reference check runs on this process's CPU backend, so a
    # TPU-only platform list gets the CPU appended (never the reverse)
    plats = os.environ.get("JAX_PLATFORMS", "")
    if "tpu" in plats.split(",") and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"

    try:
        from repro import compat
    except ImportError as e:
        print(f"[smoke] no repro package under {ROOT}/src ({e}): run this "
              f"script from a checkout of the repo", file=sys.stderr)
        raise SystemExit(2)
    devs = check_device(args.chips)
    cache = compat.enable_compile_cache()
    print(f"[smoke] {len(devs)} x {devs[0].device_kind}; compile cache "
          f"{cache}")
    clock = CompileClock()
    (four_chips if args.chips == 4 else one_chip)(args, clock)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}))


if __name__ == "__main__":
    main()
