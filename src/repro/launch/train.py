"""Training launcher: `python -m repro.launch.train --arch <id> [...]`.

Runs end-to-end on whatever devices exist (CPU for local runs, TPU pod when
launched per-host). `--reduced` selects the smoke-scale config; full configs
are intended for real pods (use dryrun.py to validate them without hardware).
"""

from __future__ import annotations

import argparse

import jax

from repro import compat
from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.core.template import render_plans
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch import specs as SP
from repro.models.layers import island_plans
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.sharding import ShardingRules
from repro.optim.adamw import AdamW, warmup_cosine
from repro.optim.compress import ErrorFeedbackInt8
from repro.runtime.driver import DriverConfig, TrainDriver
from repro.train.step import TrainState, make_train_step


def build_and_train(arch: str, *, steps: int, reduced: bool, mesh_shape,
                    mesh_axes, batch: int, seq: int, ckpt_dir: str,
                    lr: float = 3e-3, microbatches: int = 1,
                    pk_overlap: bool = True, compress_grads: bool = False,
                    fault_hook=None, seed: int = 0, log_every: int = 10,
                    ckpt_every: int = 50, comm_policy: str = "analytic",
                    comm_chunks: int | None = None, ulysses_chunks: int = 1,
                    comm_wire: str | None = None):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    mesh = make_mesh(mesh_shape, mesh_axes) if mesh_shape else None
    run = RunConfig(dp_axes=tuple(a for a in (mesh_axes or ()) if a != "model")
                    or ("data",),
                    pk_overlap=pk_overlap, microbatches=microbatches,
                    fsdp=mesh is not None, comm_policy=comm_policy,
                    comm_chunks=comm_chunks, ulysses_chunks=ulysses_chunks,
                    comm_wire=comm_wire)
    rules = ShardingRules(mesh, run) if mesh is not None else None
    if rules is not None:
        # the overlap schedule every PK island will pick, before tracing —
        # hidden fractions/chunks are measured when a calibration table
        # matches this machine (src=measured), predicted otherwise
        print(f"[plan] comm_policy={run.comm_policy}")
        print(render_plans(island_plans(cfg, run, rules, batch=batch,
                                        seq=seq)))

    tmpl = T.param_template(cfg, run, rules)
    params = SP.init_params(tmpl, seed, cfg.d_model, mesh)

    opt = AdamW(lr=warmup_cosine(lr, max(10, steps // 20), steps),
                weight_decay=0.01)
    state = TrainState(params=params, opt=opt.init(params))

    grad_transform = None
    if compress_grads:
        ef = ErrorFeedbackInt8()
        ef_state = {"s": ef.init(params)}

        def grad_transform(grads):  # noqa: F811
            g, ef_state["s"] = ef.transform(grads, ef_state["s"])
            return g

    step_fn = jax.jit(make_train_step(cfg, run, rules, opt,
                                      grad_transform=grad_transform),
                      donate_argnums=(0,))
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                  global_batch=batch, seed=seed),
                       mesh=mesh, dp_axes=run.dp_axes)
    driver = TrainDriver(
        train_step=step_fn, state=state, data=data, ckpt_dir=ckpt_dir,
        cfg=DriverConfig(total_steps=steps, ckpt_every=ckpt_every,
                         log_every=log_every),
        fault_hook=fault_hook)
    return driver.run()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh-shape", type=int, nargs="*", default=None)
    ap.add_argument("--mesh-axes", type=str, nargs="*",
                    default=["data", "model"])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--no-pk", action="store_true")
    ap.add_argument("--comm-policy", default="analytic",
                    choices=["analytic", "measured", "auto"],
                    help="cost source for comm backend dispatch "
                         "(measured needs a calibration table)")
    ap.add_argument("--comm-chunks", type=int, default=None,
                    help="force the ring GEMM-collective sub-chunk count "
                         "(default: scheduler/measured table)")
    ap.add_argument("--ulysses-chunks", type=int, default=1,
                    help="a2a chunk count for the Ulysses attention island")
    ap.add_argument("--comm-wire", default=None,
                    choices=["bf16", "int8", "int8_sr"],
                    help="GEMM-collective ring wire format: int8 ships "
                         "quantized sub-chunks + f32 scales (int8_sr adds "
                         "stochastic rounding); default full precision")
    args = ap.parse_args()
    compat.enable_compile_cache()
    build_and_train(args.arch, steps=args.steps, reduced=args.reduced,
                    mesh_shape=args.mesh_shape, mesh_axes=args.mesh_axes,
                    batch=args.batch, seq=args.seq, ckpt_dir=args.ckpt_dir,
                    lr=args.lr, microbatches=args.microbatches,
                    pk_overlap=not args.no_pk,
                    compress_grads=args.compress_grads,
                    comm_policy=args.comm_policy,
                    comm_chunks=args.comm_chunks,
                    ulysses_chunks=args.ulysses_chunks,
                    comm_wire=args.comm_wire)


if __name__ == "__main__":
    main()
