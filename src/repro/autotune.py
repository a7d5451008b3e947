"""CLI for the empirical autotuner (``repro.core.autotune``).

    python -m repro.autotune calibrate [--devices N] [--grid tiny|small|full]
                                       [--out PATH] [--notes TEXT] [--reps R]
    python -m repro.autotune show [PATH]
    python -m repro.autotune diff A [B]
    python -m repro.autotune check [PATH] [--max-age-days D] [--drift F]
                                   [--no-probe] [--reps R]

``calibrate`` micro-benchmarks every comm backend on the live mesh and saves
the fitted table (default: the user cache ``CommContext(policy="measured")``
searches, ``~/.cache/repro/autotune-<hw>-<jax>.json``). ``show`` prints a
table (the resolved dispatch table when no path is given). ``diff`` compares
two tables — or, with one argument, a table against the analytic constants —
so a re-calibration's drift is reviewable before it lands in the cache.
``check`` audits a table for staleness: its ``created`` age against a
threshold plus a quick spot re-probe of the machine-local corrections
(launch overhead, GEMM efficiency); exit 1 when the table looks stale.

``--devices`` forces the CPU-emulated mesh size and must be handled before
jax initializes, which is why this module only imports jax inside ``main``.
"""

from __future__ import annotations

import argparse
import os
import sys


def _pct(new: float, old: float) -> str:
    if old == 0:
        return "n/a"
    return f"{(new - old) / old * 100.0:+.1f}%"


def _fmt_corrections(corr: dict, base) -> list[str]:
    analytic = {
        "ici_bandwidth": base.ici_bandwidth,
        "remote_sync_s": base.remote_sync_s,
        "gemm_efficiency": base.gemm_efficiency,
        "kernel_launch_s": base.kernel_launch_s,
    }
    lines = [f"  {'field':<18} {'measured':>12} {'analytic':>12} {'drift':>9}"]
    for k, v in sorted(corr.items()):
        a = analytic.get(k)
        lines.append(f"  {k:<18} {v:>12.4g} "
                     f"{a if a is None else format(a, '>12.4g')} "
                     f"{_pct(v, a) if a is not None else '':>9}")
    return lines


def _show(table, base) -> None:
    fp = table.fingerprint
    print(f"schema v{table.version}  created {table.created or '?'}  "
          f"notes: {table.notes or '-'}")
    print(f"fingerprint: hw={fp.hw} jax={fp.jax_version} "
          f"backend={fp.backend} kind={fp.device_kind!r} "
          f"devices={fp.n_devices}")
    print("corrections:")
    print("\n".join(_fmt_corrections(table.corrections, base)))
    cov = table.ops_covered()
    print(f"measurements: {len(table.measurements)} rows over "
          f"{len(cov)} ops ({', '.join(f'{k}:{v}' for k, v in sorted(cov.items()))})")
    for row in table.measurements:
        extra = ""
        if row.get("n_chunks", 1) not in (None, 1):
            extra += f" chunks={row['n_chunks']}"
        if row.get("island"):
            extra += f" island={row['island']}"
        print(f"  {row['op']}/{row['backend']}"
              f"  axis={row['axis_size']} m={row['m']} n={row['n']} "
              f"k={row['k']}  {row['us']:.1f} us{extra}")


def _island_sweeps(args):
    """IslandSweep specs for ``calibrate --per-island``: build the model's
    island inventory on a (1, n_devices) mesh and keep every active
    GEMM-collective island's declared coordinates."""
    import jax

    from repro.configs import get_config
    from repro.configs.base import RunConfig
    from repro.launch.mesh import make_mesh
    from repro.models.layers import island_comm_sweeps
    from repro.models.sharding import ShardingRules

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_mesh((1, len(jax.devices())), ("data", "model"))
    # enable every GEMM island so each one gets measured rows; a run that
    # keeps attn_out dense simply never queries its key. --sp-attention
    # ulysses additionally sweeps the a2a re-sharding island; --phase
    # prefill|decode sweeps one serving bucket's inventory at its exact
    # coordinates (the serving engine's per-bucket dispatch rows).
    run = RunConfig(dp_axes=("data",), fsdp=False, pk_attn_out_island=True,
                    sp_attention=args.sp_attention)
    rules = ShardingRules(mesh, run)
    sweeps = island_comm_sweeps(cfg, run, rules, batch=args.batch,
                                seq=args.seq, phase=args.phase)
    if not sweeps:
        print("warning: --per-island found no active comm islands "
              f"for {cfg.name} on this mesh", file=sys.stderr)
    return sweeps


def int8_island_sweeps(islands):
    """Re-key GEMM island sweeps at the int8 wire width (the ``--dtype
    both``/``int8`` dtype axis): same declared (m, n, k), rows land under the
    island's ``…|b1`` key so per-island measured dispatch resolves when the
    run sets ``comm_wire="int8"`` — paired with the full-precision ``…|b2``
    rows the original sweeps emit. The b1 sweep itself excludes the fused
    backend (``island_sweep_cases``: fused kernels ship full precision)."""
    import dataclasses

    from repro.core import autotune

    return [
        dataclasses.replace(
            sw, island=sw.island.rsplit("|", 1)[0] + "|b1",
            dtype_bytes=1)
        for sw in islands
        if sw.op in autotune.GEMM_OPS and sw.dtype_bytes != 1]


def cmd_calibrate(args) -> int:
    from repro.core import autotune, costmodel

    hw = costmodel.spec_by_name(args.hw)
    dtypes = {"bf16": (2,), "int8": (1,), "both": (2, 1)}[args.dtype]
    islands = list(_island_sweeps(args)) if args.per_island else []
    if 1 in dtypes and islands:
        islands += int8_island_sweeps(islands)
    table = autotune.calibrate(grid=args.grid, reps=args.reps, hw=hw,
                               notes=args.notes, verbose=True,
                               islands=islands, dtypes=dtypes)
    out = args.out or autotune.cache_path(table.fingerprint)
    path = table.save(out)
    autotune.clear_caches()
    print(f"\nwrote {path}")
    print("CommContext(policy='measured') will now dispatch from it on "
          "this machine.")
    if islands:
        keys = sorted({r["island"] for r in table.measurements
                       if r.get("island")})
        print(f"island-keyed rows: {', '.join(keys) or 'none measured'}")
    return 0


def cmd_show(args) -> int:
    from repro.core import autotune, costmodel

    if args.path:
        table = autotune.CalibrationTable.load(args.path)
    else:
        table = autotune.find_table(costmodel.TPU_V5E.name)
        if table is None:
            print("no calibration table found (searched "
                  f"{autotune.cache_path(autotune.live_fingerprint(costmodel.TPU_V5E.name))} "
                  "and the in-repo seeds); run `python -m repro.autotune "
                  "calibrate`", file=sys.stderr)
            return 1
    _show(table, costmodel.TPU_V5E)
    return 0


def cmd_diff(args) -> int:
    from repro.core import autotune, costmodel

    a = autotune.CalibrationTable.load(args.a)
    base = costmodel.spec_by_name(a.fingerprint.hw)
    if args.b is None:
        # one-sided: measured vs the analytic spec it corrects
        print(f"{args.a} vs analytic {base.name}:")
        print("\n".join(_fmt_corrections(a.corrections, base)))
        return 0
    b = autotune.CalibrationTable.load(args.b)
    if not a.fingerprint.compatible(b.fingerprint):
        print(f"fingerprints are incompatible:\n  A: {a.fingerprint}\n"
              f"  B: {b.fingerprint}", file=sys.stderr)
        return 1
    print(f"{'field':<18} {'A':>12} {'B':>12} {'B vs A':>9}")
    for k in sorted(set(a.corrections) | set(b.corrections)):
        va, vb = a.corrections.get(k), b.corrections.get(k)
        if va is None or vb is None:
            print(f"{k:<18} {'—' if va is None else format(va, '.4g'):>12} "
                  f"{'—' if vb is None else format(vb, '.4g'):>12}")
            continue
        print(f"{k:<18} {va:>12.4g} {vb:>12.4g} {_pct(vb, va):>9}")
    shared = 0
    drifts = []
    for row in a.measurements:
        us_b = b.measured_us(row["op"], row["backend"], row["m"], row["n"],
                             row["k"], axis_size=row["axis_size"],
                             max_ratio=1.01)
        if us_b is None:
            continue
        shared += 1
        drifts.append(abs(us_b - row["us"]) / max(row["us"], 1e-9))
    if shared:
        print(f"measurements: {shared} shared grid points, median drift "
              f"{sorted(drifts)[len(drifts) // 2] * 100:.1f}%")
    return 0


def cmd_check(args) -> int:
    from repro.core import autotune, costmodel

    if args.path:
        table = autotune.CalibrationTable.load(args.path)
        where = args.path
    else:
        table = autotune.find_table(costmodel.TPU_V5E.name)
        if table is None:
            print("no calibration table found to check; run "
                  "`python -m repro.autotune calibrate`", file=sys.stderr)
            return 1
        where = "resolved table"
    age = autotune.table_age_days(table)
    print(f"checking {where}: created {table.created or '?'}"
          f"{f' ({age:.1f} days ago)' if age is not None else ''}")
    msgs = autotune.staleness(table, max_age_days=args.max_age_days,
                              drift_threshold=args.drift,
                              probe=not args.no_probe, reps=args.reps)
    if not msgs:
        print("table looks fresh (age and spot-probe drift within "
              "thresholds)" if not args.no_probe
              else "table age within threshold (spot probe skipped)")
        return 0
    for m in msgs:
        print(f"STALE: {m}", file=sys.stderr)
    print("re-run `python -m repro.autotune calibrate` to refresh",
          file=sys.stderr)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.autotune",
        description="measure, inspect and compare comm calibration tables")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("calibrate", help="micro-benchmark this machine")
    p.add_argument("--devices", type=int, default=None,
                   help="force an emulated CPU mesh of this many devices")
    p.add_argument("--grid", default="small", choices=_grid_names())
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--hw", default="tpu_v5e",
                   help="HardwareSpec constant to correct (tpu_v5e/h100_sxm)")
    p.add_argument("--out", default=None,
                   help="destination (default: the user cache path)")
    p.add_argument("--notes", default="")
    p.add_argument("--dtype", default="bf16",
                   choices=["bf16", "int8", "both"],
                   help="wire-width axis: bf16 sweeps full precision (b2 "
                        "rows); int8 sweeps the quantized ring wire (b1 "
                        "rows: ring backends run wire='int8', the bulk "
                        "baseline is timed unquantized under the same b1 "
                        "key); both runs the grid twice")
    p.add_argument("--per-island", action="store_true",
                   help="additionally sweep backend x chunk count at every "
                        "active GEMM-collective island's declared (m, n, k) "
                        "(rings x {1,2,4}; on TPU also the fused kernels x "
                        "{1,2,4,8}), tagging rows with the island key so "
                        "dispatch and Island.plan() become per-island "
                        "measured")
    p.add_argument("--arch", default="tinyllama-1.1b",
                   help="model whose islands --per-island sweeps")
    p.add_argument("--reduced", action="store_true",
                   help="use the smoke-scale config for --per-island")
    p.add_argument("--batch", type=int, default=8,
                   help="--per-island global batch")
    p.add_argument("--seq", type=int, default=128,
                   help="--per-island sequence length")
    p.add_argument("--sp-attention", default="ring",
                   choices=["ring", "ulysses", "none"],
                   help="--per-island: attention SP mode (ulysses adds the "
                        "a2a re-sharding island to the sweep)")
    p.add_argument("--phase", default="all",
                   choices=["all", "prefill", "decode"],
                   help="--per-island: sweep one serving bucket's island "
                        "inventory (prefill: full-seq shapes at --seq; "
                        "decode: one-token shapes)")
    p.set_defaults(fn=cmd_calibrate)

    p = sub.add_parser("show", help="print a table (default: the resolved one)")
    p.add_argument("path", nargs="?", default=None)
    p.set_defaults(fn=cmd_show)

    p = sub.add_parser("diff", help="compare two tables (or one vs analytic)")
    p.add_argument("a")
    p.add_argument("b", nargs="?", default=None)
    p.set_defaults(fn=cmd_diff)

    p = sub.add_parser("check",
                       help="audit a table for staleness (age + spot probe)")
    p.add_argument("path", nargs="?", default=None)
    p.add_argument("--max-age-days", type=float, default=30.0,
                   help="warn when 'created' is older than this (default 30)")
    p.add_argument("--drift", type=float, default=0.5,
                   help="relative drift vs the spot probe that counts as "
                        "stale (default 0.5)")
    p.add_argument("--no-probe", action="store_true",
                   help="age check only; skip the micro-benchmark probe")
    p.add_argument("--reps", type=int, default=3)
    p.set_defaults(fn=cmd_check)

    args = ap.parse_args(argv)
    if getattr(args, "devices", None):
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count"
                        f"={args.devices}").strip()
        if "jax" in sys.modules:
            import jax
            if len(jax.devices()) != args.devices:
                print(f"warning: jax already initialized with "
                      f"{len(jax.devices())} devices; --devices ignored",
                      file=sys.stderr)
    return args.fn(args)


def _grid_names():
    # repro.core.autotune never imports jax at module level, so pulling the
    # grid names here cannot defeat the --devices XLA_FLAGS handling below.
    from repro.core.autotune import GRIDS
    return sorted(GRIDS)


if __name__ == "__main__":
    raise SystemExit(main())
