"""Unified PK island template — the paper's §3.2 programming template as a
declarative JAX object.

The paper's headline claim is that eight communication primitives plus **one
load/compute/store/comm scaffold** suffice for peak multi-GPU kernels, making
each overlapped workload <50 LoC. ``CommContext`` (repro.core.comms) is the
comm half of that claim; this module is the scaffold half: every overlapped
``shard_map`` island in the model stack — MLP, attention out-projection,
ring/Ulysses attention, MoE, sharded decode, GPipe — is *declared* as an
:class:`Island` instead of hand-rolling spec construction, FSDP weight
gathering, CommContext injection, fallback switching and ``shard_map``
wrapping at each site.

An ``Island`` is declared with:

* **named inputs** (``{"x": P(...), "w": P(...)}``) and **out_specs** — the
  logical shardings, derived from ``ShardingRules`` at the call site, never
  hand-threaded through a ``shard_map`` call;
* an optional **FSDP-gather set** (``gathers={"w1": Gather(dim=0, size=d)}``)
  — weights all-gathered *inside* the island so XLA overlaps the gather with
  the previous chunk's compute (ZeRO-3), one implementation instead of six;
* a **body** ``body(ctx, **inputs)`` that receives a ready
  :class:`~repro.core.comms.CommContext` whose backend pin / policy /
  calibration are threaded from ``RunConfig``;
* a **fallback predicate** (single-device mesh, tp-divisibility constraints,
  ``pk_overlap=False`` / ``reference_mode``) routing to a dense **reference**
  implementation with identical semantics;
* an optional :class:`Comm` descriptor naming the island's dominant
  collective, from which :meth:`Island.plan` derives a trace-free report —
  chosen backend, chunk count, predicted hidden fraction — so a whole forward
  pass's overlap schedule is inspectable before anything runs.

Adding a new overlapped workload is declaring one Island (README has the
walkthrough)::

    island = Island(
        "my_op", rules=rules, run=run,
        inputs={"x": P(bspec, None), "w": rules.w2d(k, n, tp_dim=0)},
        out_specs=P(bspec, None),
        gathers={"w": Gather(dim=1, size=n)},
        body=lambda ctx, x, w: ctx.matmul_all_reduce(x, w),
        reference=lambda x, w: x @ w,
        divisible=((n, rules.tp),),
        comm=Comm("matmul_all_reduce", m=m, n=n, k=k))
    y = island(x=x, w=w)          # shard_map island, or dense fallback
    print(island.plan())          # backend/chunks/hidden fraction, trace-free

This module is the **only** place in the PK-overlap paths allowed to call
``compat.shard_map`` directly (guarded by tests/test_template.py; the
calibration micro-bench harness in core/autotune.py is the one documented
exception).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P  # noqa: F401  (re-export for call sites)

from repro import compat
from repro.core import costmodel as cm
from repro.core.autotune import island_key as _island_key
from repro.core.comms import GEMM_OP_KIND, OP_BACKENDS, CommContext
from repro.core.schedule import a2a_chunk_axis, choose_a2a_chunks

__all__ = ["Island", "Gather", "Comm", "IslandPlan", "comm_context",
           "maybe_allgather", "render_plans", "plan_overrides",
           "island_override", "record_guard_trip", "take_guard_trips"]


# ---------------------------------------------------------------------------
# Island boundary guards (RunConfig.island_guards). The check itself is a
# jit-compatible finite-reduction over the island's float inputs/outputs;
# trips land in this process-wide registry via jax.debug.callback and are
# drained once per engine step (the fleet steps replicas serially, so the
# plain dict needs no locking). re-exported by runtime.health.
# ---------------------------------------------------------------------------

_GUARD_TRIPS: dict[str, int] = {}


def record_guard_trip(island: str, ok) -> None:
    """Guard callback target: count a trip when ``ok`` is False."""
    if not bool(ok):
        _GUARD_TRIPS[island] = _GUARD_TRIPS.get(island, 0) + 1


def take_guard_trips() -> dict[str, int]:
    """Drain the guard-trip registry: {island: trips since last drain}."""
    out = dict(_GUARD_TRIPS)
    _GUARD_TRIPS.clear()
    return out


def _boundary_guard(name: str, args, out):
    """Emit one finite-check over every float leaf of an island's inputs and
    outputs. Cheap (a single fused all-isfinite reduction per leaf) and
    jit-compatible; the verdict leaves the trace through a debug callback."""
    leaves = [a for a in jax.tree.leaves((args, out))
              if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating)]
    if not leaves:
        return out
    ok = jnp.all(jnp.stack([jnp.all(jnp.isfinite(a)) for a in leaves]))
    jax.debug.callback(functools.partial(record_guard_trip, name), ok)
    return out


def _axes_size(mesh, axes) -> int:
    """Product of the named mesh axes (1 for None/empty)."""
    if mesh is None or axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    return math.prod(mesh.shape[a] for a in axes)


def comm_context(run, axis: str, mesh=None, **overrides) -> CommContext:
    """The single CommContext construction point for every island (DESIGN §3):
    ``run.comm_backend`` pins one backend for A/B runs, ``run.comm_policy`` /
    ``run.calibration_path`` select the analytic vs measured cost source.
    ``run=None`` gives the default policy context (benchmarks, tests)."""
    kw: dict[str, Any] = {"axis_name": axis, "mesh": mesh}
    if run is not None:
        kw.update(backend=run.comm_backend, allow_bidir=run.pk_bidirectional,
                  policy=run.comm_policy, calibration=run.calibration_path,
                  chunks=run.comm_chunks,
                  wire=getattr(run, "comm_wire", None))
    kw.update(overrides)
    return CommContext(**kw)


def maybe_allgather(w, axes, dim: int, full_size: int):
    """FSDP (ZeRO-3) weight gather inside an island: all-gather `dim` of `w`
    up to `full_size` over the fsdp axes. No-op for ``axes=None`` / ``w=None``
    / already-full weights — safe to declare unconditionally."""
    if w is None or axes is None:
        return w
    names = (axes,) if isinstance(axes, str) else tuple(axes)
    for a in names:
        if w.ndim > dim and w.shape[dim] < full_size:
            w = jax.lax.all_gather(w, a, axis=dim, tiled=True)
    return w


@dataclasses.dataclass(frozen=True)
class Gather:
    """FSDP all-gather instruction for one island input: gather `dim` back to
    `size` over the rules' fsdp axes before the body runs."""
    dim: int
    size: int


@dataclasses.dataclass(frozen=True)
class Comm:
    """Declaration of an island's dominant collective, for :meth:`Island.plan`.

    GEMM×collective ops carry the global GEMM coordinates (m, n, k) the
    §3.1.1 cost model dispatches on; ``all_to_all`` carries the payload bytes
    the chunk policy needs; ``backend`` records a call-site pin (e.g. the MoE
    ring combine) so the plan reports what actually runs.
    """
    op: str
    m: int = 0
    n: int = 0
    k: int = 0
    payload_bytes: float = 0.0
    dtype_bytes: int = 2
    n_chunks: int | None = None
    chunk_dim: str | None = None
    backend: str | None = None
    downstream_compute_s: float = 0.0
    #: local payload shape + a2a axes, so plan() can fit the chunk count to
    #: the splittable bystander dims exactly like pk_all_to_all will
    shape: tuple[int, ...] | None = None
    split_axis: int | None = None
    concat_axis: int | None = None
    #: where a declared n_chunks/backend came from ("measured" when the
    #: builder resolved it from calibration rows, e.g. the auto Ulysses a2a
    #: chunk count) — plan() reports it instead of re-deriving
    source: str | None = None


@dataclasses.dataclass(frozen=True)
class IslandPlan:
    """Trace-free overlap report for one island (paper §3.1.3 decision).

    ``source`` records where the hidden fraction and chunk count came from:
    ``"analytic"`` (the cost model's prediction) or ``"measured"`` (this
    island's — or the global — calibration rows on a calibrated mesh).
    """
    island: str
    axis: Any
    axis_size: int
    fallback: bool
    reason: str
    op: str | None = None
    backend: str | None = None
    n_chunks: int | None = None
    chunk_dim: str | None = None
    hidden_fraction: float | None = None
    source: str = "analytic"
    #: on-wire element format of the island's ring payloads ("int8"/"int8_sr"
    #: when RunConfig.comm_wire quantizes them, else "bf16"); None for
    #: non-ring backends and non-GEMM ops, where no wire transform applies
    wire: str | None = None

    def asdict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        if self.fallback:
            return f"{self.island:<14} -> dense fallback ({self.reason})"
        hf = ("-" if self.hidden_fraction is None
              else f"{self.hidden_fraction:.2f}")
        return (f"{self.island:<14} op={self.op or '-':<22} "
                f"backend={self.backend or '-':<10} "
                f"chunks={self.n_chunks or 1:<3} hidden={hf:<5} "
                f"wire={self.wire or '-':<8} src={self.source}")


def render_plans(plans: Sequence[IslandPlan]) -> str:
    """One-line-per-island overlap schedule table (launchers print this)."""
    head = "island         overlap schedule (backend / chunks / hidden frac)"
    return "\n".join([head, "-" * len(head)] + [str(p) for p in plans])


def plan_overrides(plans: Sequence[IslandPlan]) -> tuple:
    """Freeze resolved plans into ``RunConfig.island_overrides`` entries.

    Each non-fallback plan with a resolved backend becomes one
    ``(island_name, backend, chunks)`` entry; ``chunks`` is normalized to
    what the consumer expects — *sub-chunks per ring step* for the
    chunk-pipelined GEMM×collectives (``Island.make_context`` threads it
    into ``CommContext.chunks``), the *total* chunk count for ``all_to_all``
    islands (the a2a builders consume it directly). This is the serving
    engine's plan-to-context seam: evaluate ``island_plans()`` once per
    shape bucket, freeze the decisions here, and every island the bucket's
    jitted step builds runs exactly the schedule its plan reported.
    """
    out = []
    for p in plans:
        if p.fallback or p.backend is None:
            continue
        if p.op in GEMM_OP_KIND:
            chunks = None
            if (p.backend in ("ring", "ring_bidir", "fused")
                    and p.n_chunks):
                chunks = max(1, p.n_chunks // max(p.axis_size, 1))
        elif p.op == "all_to_all":
            chunks = p.n_chunks
        else:
            # psum / ring_shift / ...: the backend choice is the whole
            # decision; chunk counts there are structural (axis size)
            chunks = None
        out.append((p.island, p.backend, chunks))
    return tuple(out)


def island_override(run, name: str) -> tuple | None:
    """The ``(backend, chunks, source)`` override
    ``RunConfig.island_overrides`` carries for island ``name``, or None.
    Later entries win (a re-resolved plan appended to an existing tuple
    supersedes the stale one — the seam the runtime HealthMonitor's
    demotions layer through, as 4-tuples whose source is ``"health"``;
    plain plan entries report source ``"plan"``)."""
    entries = getattr(run, "island_overrides", ()) if run is not None else ()
    hit = None
    for entry in entries:
        if entry and entry[0] == name:
            hit = (entry[1], entry[2] if len(entry) > 2 else None,
                   entry[3] if len(entry) > 3 else "plan")
    return hit


class Island:
    """One declarative overlapped shard_map island (see module docstring).

    Construction is cheap and trace-free; ``__call__(**arrays)`` compiles the
    ``shard_map`` (or routes to the dense reference), ``plan()`` reports the
    overlap schedule without tracing anything.
    """

    def __init__(self, name: str, *, body: Callable | None = None,
                 inputs: Mapping[str, Any] | None = None,
                 out_specs: Any = None,
                 rules=None, mesh=None, axis=None, run=None,
                 reference: Callable | None = None,
                 gathers: Mapping[str, Gather] | None = None,
                 enable: bool = True,
                 gather_axes: Any = None,
                 divisible: Sequence[tuple[int, Any]] = (),
                 fallback_axes: Any = None,
                 comm: Comm | None = None,
                 hw: cm.HardwareSpec | None = None,
                 ctx_kwargs: Mapping[str, Any] | None = None):
        self.name = name
        self.rules = rules
        self.mesh = mesh if mesh is not None else (
            rules.mesh if rules is not None else None)
        self.axis = axis if axis is not None else (
            rules.tp if rules is not None else None)
        self.run = run
        self.body = body
        self.inputs = dict(inputs or {})
        self.out_specs = out_specs
        self.reference = reference
        self.gathers = dict(gathers or {})
        self.gather_axes = gather_axes if gather_axes is not None else (
            rules.fsdp_axes if rules is not None else None)
        self.enable = enable
        self.divisible = tuple(divisible)
        self.fallback_axes = fallback_axes if fallback_axes is not None \
            else self.axis
        self.comm = comm
        self.hw = hw
        self.ctx_kwargs = dict(ctx_kwargs or {})

    # -- fallback predicate ------------------------------------------------

    @property
    def axis_size(self) -> int:
        return _axes_size(self.mesh, self.axis)

    def fallback_reason(self) -> str | None:
        """Why this island routes to the dense reference (None = it runs as a
        shard_map island). The paper-template predicate: reference mode,
        single device, and per-island tp-divisibility constraints."""
        if self.mesh is None:
            return "no mesh (single-process reference mode)"
        if self.run is not None and getattr(self.run, "reference_mode", False):
            return "RunConfig.reference_mode"
        if not self.enable:
            return "disabled by RunConfig"
        if self.mesh.devices.size == 1:
            return "single-device mesh"
        if _axes_size(self.mesh, self.fallback_axes) == 1:
            return f"axis {self.fallback_axes!r} has size 1"
        for size, axes in self.divisible:
            n = _axes_size(self.mesh, axes)
            if n and size % n != 0:
                return (f"size {size} not divisible by axis {axes!r} "
                        f"(= {n})")
        return None

    # -- execution ---------------------------------------------------------

    @property
    def island_key(self) -> str | None:
        """The calibration-row key this island dispatches as (None when no
        ``Comm`` is declared): ``autotune.island_key(name, op, dtype)``.
        ``calibrate --per-island`` tags measured rows with it; the context
        built below prefers those rows over the global shape grid."""
        if self.comm is None:
            return None
        return _island_key(self.name, self.comm.op, self.comm.dtype_bytes)

    def make_context(self) -> CommContext:
        kw = dict(self.ctx_kwargs)
        if self.hw is not None:
            kw.setdefault("hw", self.hw)
        kw.setdefault("island", self.island_key)
        # RunConfig.island_overrides: a frozen plan decision for THIS island
        # (serving engine buckets). The backend becomes a context pin and a
        # GEMM sub-chunk count the context default, so the bucket's step
        # runs exactly what its recorded plan reported. Explicit ctx_kwargs
        # at the declaration site still win (setdefault).
        ov = island_override(self.run, self.name)
        if ov is not None:
            be, chunks, _src = ov
            if be is not None:
                kw.setdefault("backend", be)
            if (chunks is not None and self.comm is not None
                    and self.comm.op in GEMM_OP_KIND):
                kw.setdefault("chunks", chunks)
        # scripted comms-level payload fault (RunConfig.comm_fault, set by
        # the serving engine while a CommFaultPlan corrupt/bitflip event is
        # active): thread (kind, hop) to the ring collectives when this
        # island is the target ("*" targets every island)
        ft = getattr(self.run, "comm_fault", None) \
            if self.run is not None else None
        if ft is not None and ft[1] in ("*", self.name):
            kw.setdefault("fault",
                          (ft[0], ft[2] if len(ft) > 2 else 0))
        # a declared Comm.n_chunks becomes the context's chunk default, so
        # the body's GEMM-collective calls run the schedule plan() reports
        # without every call site re-passing n_chunks=. The global A/B knob
        # (RunConfig.comm_chunks) still wins when set.
        if (self.comm is not None and self.comm.n_chunks is not None
                and self.comm.op in GEMM_OP_KIND
                and (self.run is None
                     or getattr(self.run, "comm_chunks", None) is None)):
            kw.setdefault("chunks", self.comm.n_chunks)
        return comm_context(self.run, self.axis, mesh=self.mesh, **kw)

    def __call__(self, **arrays):
        """Run the island: its ``shard_map`` or its dense reference, either
        one under ``jax.named_scope(self.name)``, so every op it lowers to
        carries the island's name in its ``op_name`` metadata."""
        if set(arrays) != set(self.inputs) and self.fallback_reason() is None:
            raise TypeError(
                f"island {self.name!r} declared inputs "
                f"{sorted(self.inputs)}, got {sorted(arrays)}")
        with jax.named_scope(self.name):
            return self._run(**arrays)

    def _run(self, **arrays):
        reason = self.fallback_reason()
        if reason is not None:
            if self.reference is None:
                raise ValueError(
                    f"island {self.name!r} must fall back ({reason}) but "
                    "declares no dense reference")
            return self.reference(**arrays)
        names = list(self.inputs)
        ctx = self.make_context()
        gather_axes = self.gather_axes

        def shard_body(*args):
            kw = dict(zip(names, args))
            for n, g in self.gathers.items():
                kw[n] = maybe_allgather(kw[n], gather_axes, g.dim, g.size)
            return self.body(ctx, **kw)

        f = compat.shard_map(
            shard_body, mesh=self.mesh,
            in_specs=tuple(self.inputs[n] for n in names),
            out_specs=self.out_specs, check_vma=False)
        args = tuple(arrays[n] for n in names)
        out = f(*args)
        if self.run is not None and getattr(self.run, "island_guards", False):
            # guard at the island BOUNDARY — outside the shard_map, inside
            # the enclosing jit — so one check covers the re-assembled
            # logical arrays on every backend
            out = _boundary_guard(self.name, args, out)
        return out

    # -- introspection -----------------------------------------------------

    def _measured_hidden(self, ctx: CommContext, backend: str,
                         kind: str) -> float | None:
        """Measured hidden fraction for the chosen backend, or None.

        On a calibrated mesh the bulk row is the serial GEMM-then-collective
        baseline and the ring row the overlapped schedule, so the time the
        ring saved over bulk IS the hidden communication:
        ``(us_bulk - us_ring) / t_comm`` clamped to [0, 1], with ``t_comm``
        priced on the calibrated (measured-bandwidth) spec. A measured
        *bulk* decision (the table showed bulk winning) reports 0.0 — still
        a measurement, not a prediction. Island-keyed rows are preferred;
        the generic shape grid is the fallback. None — no usable
        measurement — leaves the plan on the analytic prediction. The fused
        backend participates the same way once ``calibrate --per-island``
        has swept fused×chunks rows: its delta over the bulk row is the
        overlap the single-kernel pipeline actually achieved.
        """
        if backend not in ("bulk", "ring", "ring_bidir", "fused"):
            return None
        table = ctx.active_calibration()
        if table is None or self.comm is None:
            return None
        c = self.comm
        n_dev = self.axis_size
        ring_be = backend if backend != "bulk" else "ring"
        # both sides of the delta must come from the SAME tier: an island
        # ring row minus a global-grid bulk row is a cross-layout subtraction
        # (another tier's layout is not evidence about this one)
        tiers: list[dict[str, Any]] = []
        if self.island_key is not None:
            tiers.append({"island": self.island_key, "island_only": True})
        tiers.append({"island": None})
        us_ov = us_bulk = None
        for sel in tiers:
            kw: dict[str, Any] = dict(axis_size=n_dev,
                                      dtype_bytes=c.dtype_bytes, **sel)
            us_ov = table.measured_us(c.op, ring_be, c.m, c.n, c.k, **kw)
            us_bulk = table.measured_us(c.op, "bulk", c.m, c.n, c.k, **kw)
            if us_ov is not None and us_bulk is not None:
                break
        if us_ov is None or us_bulk is None:
            return None
        if backend == "bulk":
            return 0.0          # nothing overlaps, by measurement
        # a quantized wire shrinks the denominator: T_comm is what the ring
        # actually ships (int8 payload + f32 scale planes), not the tensor's
        # own width — the repriced hidden fraction the plan reports
        fmt = ctx.wire_format()
        elem_bytes = (fmt.bytes_per_element if fmt is not None
                      else c.dtype_bytes)
        shard = (cm.collective_tensor_bytes(c.m, c.n, c.k, 1, kind)
                 * elem_bytes / max(n_dev, 1))
        # same T_comm convention as choose_gemm_collective: the
        # bidirectional ring moves the payload over two link-pairs
        t_comm_us = cm.transfer_cost(
            cm.ring_collective_bytes(shard, n_dev, kind),
            ctx.effective_hw(),
            links=2 if backend == "ring_bidir" else 1) * 1e6
        if t_comm_us <= 0:
            return None
        return max(0.0, min(1.0, (us_bulk - us_ov) / t_comm_us))

    def plan(self) -> IslandPlan:
        """The trace-free §3.1.3 schedule decision this island will make:
        which backend the policy (or a pin) resolves to, the chunk count and
        the predicted hidden fraction of T_comm — or the fallback reason."""
        reason = self.fallback_reason()
        base = IslandPlan(self.name, self.axis, self.axis_size,
                          fallback=reason is not None,
                          reason=reason or "",
                          op=self.comm.op if self.comm else None)
        if reason is not None or self.comm is None:
            return base
        c = self.comm
        ctx = self.make_context()
        if c.op in GEMM_OP_KIND:
            n_dev = self.axis_size
            # Mirror the runtime dispatch's shape rules exactly, so the plan
            # can never report a schedule CommContext would refuse to run:
            # ring RS/AR needs m divisible by the axis (auto() returns bulk,
            # context pins degrade via _shape_guard); the bidirectional AG
            # ring additionally needs >= 2 local rows to split across the
            # two directions (odd shards split unevenly); the fused Pallas
            # kernel is auto-picked by the same ``fused_fits`` test dispatch
            # runs (real TPU, compilable shape, scratch inside VMEM).
            ring_ok = c.op == "all_gather_matmul" or c.m % n_dev == 0
            m_loc = c.m // n_dev if c.m % n_dev == 0 else c.m
            fused_ok = ctx.fused_fits(c.op, c.m, c.n, c.k,
                                      dtype_bytes=c.dtype_bytes)
            if c.backend is not None:
                # call-site pin: the body passes backend= explicitly, which
                # the runtime enforces — a shape violation RAISES there
                # rather than degrading, so report the pin and say so.
                backend = c.backend
                reason = f"pinned backend={c.backend}" if ring_ok or \
                    backend == "bulk" else (
                        f"pinned backend={c.backend} violates m % axis == 0 "
                        "— the runtime raises ValueError for this call")
            elif ctx.backend in OP_BACKENDS.get(c.op, ()):
                backend = ctx.backend       # context pin (RunConfig A/B run)
                if backend != "bulk" and not ring_ok:
                    backend = "bulk"        # the _shape_guard degradation
                elif (backend == "ring_bidir" and n_dev % 2 == 0
                        and m_loc < 2):
                    backend = "ring"
                reason = f"context pin -> {backend}"
            elif not ring_ok:
                backend = "bulk"
                reason = f"m={c.m} not divisible by axis size {n_dev} -> bulk"
            else:
                backend = ctx.auto_gemm_backend(
                    c.op, c.m, c.n, c.k, dtype_bytes=c.dtype_bytes,
                    fused_ok=fused_ok, bidir_ok=(m_loc >= 2))
                reason = None
            pol = ctx.gemm_policy(c.m, c.n, c.k, kind=GEMM_OP_KIND[c.op],
                                  dtype_bytes=c.dtype_bytes)
            if backend in ("ring", "ring_bidir", "fused"):
                # chunk-pipeline schedule, resolved through the SAME context
                # the body receives (make_context threads Comm.n_chunks into
                # ctx.chunks, RunConfig.comm_chunks winning): context default
                # > measured chunk sweep (island-keyed rows first) >
                # analytic argmin (fused-pipeline cost term for the fused
                # kernels) — plan and runtime cannot diverge
                sched = ctx.gemm_chunk_schedule(
                    c.op, c.m, c.n, c.k, backend=backend,
                    dtype_bytes=c.dtype_bytes, chunk_dim=c.chunk_dim)
                n_chunks = n_dev * sched.n_chunks   # ring steps × sub-chunks
                chunk_dim = sched.chunk_dim
                hidden = pol.hidden_fraction
                source = "measured" if sched.source == "measured" \
                    else "analytic"
            else:
                n_chunks = c.n_chunks if c.n_chunks is not None else 1
                chunk_dim, hidden, source = None, 0.0, "analytic"
            meas = self._measured_hidden(ctx, backend, GEMM_OP_KIND[c.op])
            if meas is not None:
                hidden, source = meas, "measured"
            ov = island_override(self.run, self.name)
            if ov is not None and ov[2] == "health" and ov[0] == backend:
                # a runtime HealthMonitor demotion is the decision on
                # record, layered above plan/measured dispatch
                source = "health"
                reason = f"health demotion -> {backend}"
            fmt = ctx.wire_format()
            wire = None
            if backend in ("ring", "ring_bidir"):
                # only the ring schedules implement the quantized wire; bulk
                # and fused ship full precision whatever the config says
                wire = fmt.name if fmt is not None else "bf16"
            return dataclasses.replace(
                base, backend=backend, n_chunks=n_chunks,
                chunk_dim=chunk_dim, hidden_fraction=hidden, source=source,
                wire=wire,
                reason=reason if reason is not None else pol.reason)
        if c.op == "all_to_all":
            source = c.source or "analytic"
            if c.n_chunks is not None:
                n_chunks = c.n_chunks
            elif c.shape is not None:
                # measured-first resolution, same method the builders use
                # for the auto chunk count (calibrate --per-island a2a rows)
                sched = ctx.a2a_chunk_schedule(
                    c.shape, c.split_axis, c.concat_axis,
                    dtype_bytes=c.dtype_bytes,
                    downstream_compute_s=c.downstream_compute_s)
                n_chunks, source = sched.n_chunks, sched.source
            else:
                n_chunks = choose_a2a_chunks(
                    c.payload_bytes, axis_size=self.axis_size,
                    downstream_compute_s=c.downstream_compute_s,
                    hw=ctx.effective_hw(), shape=c.shape,
                    split_axis=c.split_axis, concat_axis=c.concat_axis)
            if n_chunks > 1 and c.shape is not None:
                # mirror pk_all_to_all's bystander-dim fitting so the plan
                # never reports a chunking the runtime would bulk away
                fit = a2a_chunk_axis(c.shape, c.split_axis, c.concat_axis,
                                     n_chunks)
                n_chunks = fit[1] if fit is not None else 1
            backend = c.backend if c.backend is not None else \
                ("chunked" if n_chunks > 1 else "bulk")
            if backend == "chunked" and n_chunks <= 1:
                backend = "bulk"    # the declared chunking cannot split
            hidden = 1.0 - 1.0 / n_chunks if n_chunks > 1 else 0.0
            return dataclasses.replace(
                base, backend=backend, n_chunks=n_chunks,
                hidden_fraction=hidden, source=source,
                reason=f"a2a chunk policy -> {n_chunks} chunks")
        # psum / ring_shift / all_gather / reduce_scatter: the backend is
        # either pinned at the call site or bulk; per-hop overlap of the ring
        # schedules is structural (n-1 hops hide under per-step compute).
        backend = c.backend
        if backend is None and ctx.backend in OP_BACKENDS.get(c.op, ()):
            backend = ctx.backend
        backend = backend or "bulk"
        n_chunks = c.n_chunks if c.n_chunks is not None else (
            self.axis_size if backend != "bulk" else 1)
        return dataclasses.replace(
            base, backend=backend, n_chunks=n_chunks,
            reason=f"{c.op} via {backend}")

    def __repr__(self) -> str:
        return (f"Island({self.name!r}, axis={self.axis!r}, "
                f"inputs={list(self.inputs)}, "
                f"fallback={self.fallback_reason()!r})")
