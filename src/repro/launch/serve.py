"""Serving launcher — a thin CLI over the continuous-batching engine
(``repro.runtime.serving``).

    # static batch (the classic throughput run)
    python -m repro.launch.serve --arch tinyllama-1.1b --reduced \
        --mesh-shape 1 8 --batch 8 --prompt-len 8 --tokens 16

    # continuous batching over a synthetic request trace
    python -m repro.launch.serve --arch tinyllama-1.1b --reduced \
        --mesh-shape 1 8 --mode continuous --requests 12 --tokens 16

    # a 2-replica fleet with a scripted kill + rejoin
    python -m repro.launch.serve --arch tinyllama-1.1b --reduced \
        --mode continuous --replicas 2 --router least-loaded \
        --fault-plan "kill:1@4 rejoin:1@8" --requests 12

``--replicas N`` (N > 1) wraps N engine replicas in a
``runtime.fleet.ServingFleet`` behind the chosen ``--router`` policy;
``--fault-plan`` injects scripted kill/delay/drain/rejoin events
(``kind:replica@step[xticks]``).

Both modes print the per-bucket serving plan table (island backend / chunks
/ hidden fraction, measured on a calibrated mesh) before anything traces —
the engine consumes exactly those plans via ``RunConfig.island_overrides``.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import compat
from repro.configs import get_config
from repro.configs.base import RunConfig, ServeConfig
from repro.launch import specs as SP
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.sharding import ShardingRules
from repro.runtime.serving import ServingEngine, render_serving_plans


def build_engine(arch: str, *, reduced: bool = True, mesh_shape=None,
                 mesh_axes=("data", "model"), serve: ServeConfig | None = None,
                 seed: int = 0, comm_policy: str = "analytic",
                 comm_chunks: int | None = None,
                 run_overrides: dict | None = None,
                 comm_faults=None) -> ServingEngine:
    """Config -> params -> ServingEngine, on local devices (CPU-emulated or
    a real slice). The tests and the bench harness build engines through
    this, so there is exactly one construction path. ``comm_faults`` is a
    ``runtime.health.CommFaultPlan`` (or its spec string) of scripted
    comms-level faults."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    mesh = make_mesh(mesh_shape, mesh_axes) if mesh_shape else None
    kw = dict(dp_axes=tuple(a for a in (mesh_axes or ()) if a != "model")
              or ("data",),
              fsdp=False, decode_seq_shard=mesh is not None,
              comm_policy=comm_policy, comm_chunks=comm_chunks)
    kw.update(run_overrides or {})
    run = RunConfig(**kw)
    rules = ShardingRules(mesh, run) if mesh is not None else None
    tmpl = T.param_template(cfg, run, rules)
    params = SP.init_params(tmpl, seed, cfg.d_model, mesh)
    if serve is None:
        ssm = any(sp.mixer == "mamba" for sp in cfg.layer_pattern())
        serve = ServeConfig(exact_buckets=ssm)
    return ServingEngine(cfg, run, rules, params, serve,
                         comm_faults=comm_faults)


def synthetic_trace(n_requests: int, serve: ServeConfig, vocab: int,
                    seed: int = 0):
    """Deterministic mixed-bucket request trace: prompt lengths drawn over
    the bucket range, token ids over the vocab."""
    rng = np.random.RandomState(seed)
    lo = 2
    hi = serve.bucket_edges[-1]
    out = []
    for _ in range(n_requests):
        n = int(rng.randint(lo, hi + 1))
        out.append(tuple(int(t) for t in rng.randint(0, vocab, size=n)))
    return out


def generate(arch: str, *, reduced: bool, batch: int, prompt_len: int,
             gen_tokens: int, mesh_shape=None, mesh_axes=("data", "model"),
             seed: int = 0, greedy: bool = True,
             comm_policy: str = "analytic", comm_chunks: int | None = None,
             comm_wire: str | None = None, kv_dtype: str = "bf16"):
    """Static-batch generation (the legacy entry point, now one engine
    call): `batch` synthetic prompts of `prompt_len` tokens, prefilled as
    one batch and decoded in lockstep. Returns the (batch, gen_tokens)
    generated ids and prints tokens/s."""
    import time

    import jax.numpy as jnp

    # exact_buckets: uniform static prompts never pad, and it keeps the
    # engine's SSM right-padding guard satisfied for mamba archs
    serve = ServeConfig(bucket_edges=(max(prompt_len, 2),),
                        max_new_tokens=gen_tokens,
                        max_batch=batch, prefill_batch=min(batch, 8),
                        exact_buckets=True, kv_dtype=kv_dtype)
    eng = build_engine(arch, reduced=reduced, mesh_shape=mesh_shape,
                       mesh_axes=mesh_axes, serve=serve, seed=seed,
                       comm_policy=comm_policy, comm_chunks=comm_chunks,
                       run_overrides={"comm_wire": comm_wire})
    if eng.rules is not None:
        print(f"[plan] comm_policy={comm_policy}")
        print(render_serving_plans(eng.bucket_plans))
    rng = np.random.RandomState(seed)
    prompts = [tuple(int(t) for t in
                     rng.randint(0, eng.cfg.vocab_size, size=prompt_len))
               for _ in range(batch)]
    t0 = time.perf_counter()
    out = eng.generate_static(prompts, gen_tokens)
    dt = time.perf_counter() - t0
    total = batch * (prompt_len + gen_tokens)
    print(f"[serve] {arch}: {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s, batch={batch})")
    return jnp.asarray(out, jnp.int32)


def serve_fleet(args, serve: ServeConfig) -> None:
    """Continuous mode with ``--replicas > 1``: a ServingFleet over
    identical engine replicas (same arch/serve/seed — data-parallel), with
    optional scripted faults, ending in the fleet + per-replica stats."""
    from repro.configs.base import FleetConfig
    from repro.runtime.fleet import FaultPlan, ServingFleet

    def factory(i: int) -> ServingEngine:
        overrides = {"comm_wire": args.comm_wire,
                     "island_guards": args.island_guards}
        if args.comm_backend:
            overrides["comm_backend"] = args.comm_backend
        return build_engine(args.arch, reduced=args.reduced,
                            mesh_shape=args.mesh_shape, serve=serve,
                            seed=args.seed, comm_policy=args.comm_policy,
                            comm_chunks=args.comm_chunks,
                            run_overrides=overrides)

    plan = FaultPlan.parse(args.fault_plan) if args.fault_plan else None
    fleet = ServingFleet(
        factory, FleetConfig(n_replicas=args.replicas, router=args.router),
        fault_plan=plan, ckpt_dir=args.ckpt_dir)
    trace = synthetic_trace(args.requests, serve,
                            fleet.replicas[0].engine.cfg.vocab_size,
                            seed=args.seed)
    done = fleet.run(trace)
    st = fleet.stats()
    print(f"[fleet] {args.arch} x{st['replicas']} ({st['router']}): "
          f"{len(done)} requests, {st['useful_tokens']} tokens in "
          f"{st['wall_s']:.2f}s ({st['tokens_per_s']:.1f} tok/s; "
          f"{st['fleet_steps']} fleet steps, {st['assignments']} routed, "
          f"{st['steals']} steals, {st['requeued']} requeued, "
          f"{st['live']}/{st['replicas']} live)")
    for idx, fb in sorted(st["per_replica"].items()):
        if not fb["alive"]:
            print(f"[fleet]   r{idx}: dead")
            continue
        print(f"[fleet]   r{idx}: load={fb['load']} "
              f"queue={fb['queue_depth']} "
              f"tok/s={fb['tokens_per_s']:.1f} "
              f"buckets={fb['jitted_buckets']} "
              f"ema={fb['watchdog_ema']:.3f}"
              + (" (draining)" if fb["draining"] else ""))
    if args.fault_plan:
        kinds = [e[0] for e in fleet.events
                 if e[0] in ("kill", "drain", "rejoin", "delay", "stall",
                             "steal", "snapshot", "comm_fault")]
        print(f"[fleet] fault events fired: {kinds}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mode", default="static",
                    choices=["static", "continuous"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8,
                    help="continuous mode: synthetic trace length")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--prefill-batch", type=int, default=4)
    ap.add_argument("--bucket-edges", type=int, nargs="*", default=None)
    ap.add_argument("--queue-policy", default="fcfs",
                    choices=["fcfs", "bucket-greedy"])
    ap.add_argument("--cache-layout", default="slab",
                    choices=["slab", "paged"])
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged layout: tokens per KV page")
    ap.add_argument("--n-pages", type=int, default=0,
                    help="paged layout: pool pages (0 = slab-equivalent)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="paged layout: split prefill into page-aligned "
                         "chunks so decode ticks interleave (0 = off)")
    ap.add_argument("--mesh-shape", type=int, nargs="*", default=None)
    ap.add_argument("--comm-policy", default="analytic",
                    choices=["analytic", "measured", "auto"])
    ap.add_argument("--comm-chunks", type=int, default=None)
    ap.add_argument("--comm-wire", default=None,
                    choices=["bf16", "int8", "int8_sr"],
                    help="GEMM-collective ring wire format (int8 ships "
                         "quantized sub-chunks + f32 scales)")
    ap.add_argument("--kv-dtype", default="bf16", choices=["bf16", "int8"],
                    help="KV-cache storage dtype: int8 quantizes on write "
                         "with per-(token, head) f32 scales, roughly "
                         "halving cache HBM")
    ap.add_argument("--replicas", type=int, default=1,
                    help="continuous mode: >1 runs a ServingFleet of "
                         "data-parallel engine replicas")
    ap.add_argument("--router", default="least-loaded",
                    choices=["fcfs", "least-loaded", "cache-affinity"])
    ap.add_argument("--fault-plan", default=None,
                    help="scripted fleet faults, e.g. 'kill:1@4 rejoin:1@8', "
                         "'delay:0@2x3', or comms-level "
                         "'linkdown:1.mlp@4x3' "
                         "(kind:replica[.island]@step[xticks])")
    ap.add_argument("--comm-fault-plan", default=None,
                    help="single-engine scripted comms faults, e.g. "
                         "'corrupt:mlp@3 stall:mlp@5x6' "
                         "(kind:island@step[xticks])")
    ap.add_argument("--comm-backend", default=None,
                    help="pin every GEMM island's collective backend "
                         "(e.g. ring), bypassing dispatch — mostly for "
                         "fault drills")
    ap.add_argument("--island-guards", action="store_true",
                    help="jit-compatible finite-checks on island "
                         "inputs/outputs; trips feed the health monitor")
    ap.add_argument("--health-monitor", action="store_true",
                    help="per-island EMA health monitor: demote a drifting "
                         "island's backend with hysteresis, re-promote "
                         "after probation")
    ap.add_argument("--ckpt-dir", default=None,
                    help="fleet: snapshot/rejoin checkpoint directory")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compat.enable_compile_cache()

    if args.mode == "static":
        generate(args.arch, reduced=args.reduced, batch=args.batch,
                 prompt_len=args.prompt_len, gen_tokens=args.tokens,
                 mesh_shape=args.mesh_shape, comm_policy=args.comm_policy,
                 comm_chunks=args.comm_chunks, seed=args.seed,
                 comm_wire=args.comm_wire, kv_dtype=args.kv_dtype)
        return

    edges = tuple(args.bucket_edges) if args.bucket_edges else (8, 16, 32)
    serve = ServeConfig(max_batch=args.max_batch,
                        prefill_batch=args.prefill_batch,
                        bucket_edges=edges, max_new_tokens=args.tokens,
                        queue_policy=args.queue_policy,
                        cache_layout=args.cache_layout,
                        page_size=args.page_size, n_pages=args.n_pages,
                        prefill_chunk=args.prefill_chunk,
                        kv_dtype=args.kv_dtype,
                        health_monitor=args.health_monitor)
    if args.replicas > 1:
        serve_fleet(args, serve)
        return
    overrides = {"comm_wire": args.comm_wire,
                 "island_guards": args.island_guards}
    if args.comm_backend:
        overrides["comm_backend"] = args.comm_backend
    eng = build_engine(args.arch, reduced=args.reduced,
                       mesh_shape=args.mesh_shape, serve=serve,
                       seed=args.seed, comm_policy=args.comm_policy,
                       comm_chunks=args.comm_chunks,
                       run_overrides=overrides,
                       comm_faults=args.comm_fault_plan)
    if eng.rules is not None:
        print(f"[plan] comm_policy={args.comm_policy}")
        print(render_serving_plans(eng.bucket_plans))
    if eng.paged:
        g = eng.geom
        print(f"[cache] paged: page={g.page_size} pool={g.n_pages} pages "
              f"x {g.n_partitions} partitions "
              f"(chunk={serve.prefill_chunk or 'off'})")
    trace = synthetic_trace(args.requests, serve, eng.cfg.vocab_size,
                            seed=args.seed)
    done = eng.run(trace)
    st = eng.stats()
    print(f"[serve] {args.arch}: {len(done)} requests, "
          f"{st['tokens_generated']} tokens in {st['wall_s']:.2f}s of "
          f"engine steps ({st['tokens_per_s']:.1f} tok/s over step time; "
          f"{st['prefill_steps']} prefill + {st['decode_steps']} decode "
          f"steps; buckets jitted: {st['compiled_buckets']})")
    print("[serve] step time by phase: " + ", ".join(
        f"{n} {sec:.3f}s" for n, sec in sorted(st["phase_s"].items(),
                                               key=lambda kv: -kv[1])))
    cs = st["cache"]
    line = (f"[cache] layout={cs['layout']} kv={cs['kv_dtype']} "
            f"hbm={cs['hbm_bytes']/1e6:.1f}MB "
            f"(slab-equivalent {cs['slab_bytes']/1e6:.1f}MB) "
            f"peak_slots={cs['peak_resident_slots']}")
    if cs["layout"] == "paged":
        line += (f" peak_pages={cs['peak_resident_pages']}/{cs['n_pages']} "
                 f"prefix_hits={cs['prefix_hits']} "
                 f"shared_pages={cs['shared_pages_reused']} "
                 f"cow={cs['cow_copies']} "
                 f"blocked={cs['admission_blocked']}")
    print(line)
    if args.comm_fault_plan or args.island_guards or args.health_monitor:
        print(f"[health] quarantined={st['quarantined']} "
              f"retries={st['retries']} guard_trips={st['guard_trips']} "
              f"demotions={st['health_demotions']} "
              f"idle_steps={st['idle_steps']}")
        kinds = [e[0] for e in eng.events
                 if e[0] in ("comm_fault", "comm_fault_end", "guard_trip",
                             "retry", "quarantine", "deadline",
                             "health_demote", "health_promote",
                             "health_link_up")]
        print(f"[health] events fired: {kinds}")
        if eng.health is not None and any(
                o[3] == "health" for o in eng.plan_record()
                ["health_overrides"]):
            print("[health] live overrides: "
                  f"{eng.plan_record()['health_overrides']}")


if __name__ == "__main__":
    main()
