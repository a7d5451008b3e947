"""Unified policy-driven communication API — ``CommContext``.

The paper's core claim is that eight primitives and **one programming
template** suffice for peak multi-GPU kernels. This module is that template's
host-side face: every communication op the repo implements is reachable
through one object, and *which* implementation runs is decided by the §3.1.1
cost model at trace time — not hardcoded at each call site.

    from repro.core.comms import CommContext

    ctx = CommContext(axis_name="model", mesh=mesh)        # construct once
    y = ctx.matmul_reduce_scatter(x, w)                    # policy-routed
    y = ctx.matmul_reduce_scatter(x, w, backend="ring")    # explicit override

Ops (uniform signature: operands, then ``backend=None`` plus op kwargs):

    ==============================  =======================================
    op                              backends
    ==============================  =======================================
    ``all_gather_matmul(x, w)``     bulk | ring | ring_bidir | fused
    ``matmul_reduce_scatter(x, w)`` bulk | ring | fused
    ``matmul_all_reduce(x, w)``     bulk | ring | fused
    ``all_to_all(x)``               bulk | chunked
    ``psum(x)``                     bulk | ring
    ``all_gather(x)``               bulk | fused
    ``reduce_scatter(x)``           bulk | fused
    ``ring_shift(x)``               bulk | fused
    ==============================  =======================================

``bulk``    — the non-overlapped XLA collective (paper's cuBLAS+NCCL analogue)
``ring``    — chunk-pipelined ``ppermute`` ring; every ring step is split
              into ``n_chunks`` double-buffered chunks (send-ahead: step
              i+1's shifts are issued before step i's chunk GEMMs), so
              transfers hide under the MXU at sub-shard granularity
``ring_bidir`` — both ring directions at once (2 link-pairs, halves T_comm);
              multi-chunk per step per direction, uneven shards split
              ceil/floor
``chunked`` — payload split so downstream compute overlaps later chunks
``fused``   — single Pallas kernel with intra-kernel RDMA overlap (LCSC
              template; compiled on a TPU, TPU interpret mode elsewhere). Each
              ring hop is itself chunk-pipelined: per-chunk one-way DMAs
              issued ahead of the chunk GEMM, same ``ChunkSchedule``
              resolution as the jax-level rings but priced with
              ``costmodel.fused_pipeline_cost`` (in-kernel sync is cheap,
              so the argmin sits at finer chunks)

The GEMM×collective ops take ``n_chunks=``/``chunk_dim=`` knobs; left unset,
the chunk count resolves via ``CommContext.gemm_chunk_schedule`` (context
default -> measured chunk sweep -> ``schedule.choose_gemm_chunks``), and any
count is fitted to the chunked sub-shape's largest divisor — chunking never
adds a shape constraint. Under the measured policy, lookups prefer
calibration rows tagged with this context's ``island`` key (see
``repro.core.autotune.island_key``; ``calibrate --per-island`` produces
them), so different islands can dispatch differently at the same shape.

Backend-selection precedence (highest to lowest)
------------------------------------------------

1. **Per-call override** — ``ctx.matmul_reduce_scatter(x, w,
   backend="ring")``. Always wins. If the named backend's shape constraint
   is violated (e.g. ``m`` not divisible by the axis for a ring), this is
   treated as a caller bug and raises ``ValueError`` with the constraint
   spelled out; it never silently measures a different backend.
2. **Context pin** — ``CommContext(backend="ring")`` (what
   ``RunConfig.comm_backend`` sets for A/B runs). Applies to every call on
   the context, with two deliberate softenings: a pinned backend the called
   op does not implement (e.g. ``ring_bidir`` pinned, ``matmul_all_reduce``
   called) falls back to the policy for that op, and a pinned backend whose
   shape constraint fails (decode-shaped GEMMs) degrades to ``bulk`` the
   way the policy would — so one pin cannot crash a whole run. A typo'd
   pin (not a backend of *any* op) still raises.
3. **Policy** (``backend=None``) — the §3.1.1 cost model decides; see below.

Dispatch rules (``backend=None``): GEMM×collective ops go through
``schedule.choose_gemm_collective`` — bulk when the GEMM is too small to
cover the ring's sync overhead, ``ring_bidir`` when the axis is even and
bidirectional rings are allowed, ``ring`` otherwise, ``fused`` on a real TPU
when the kernel compiles at the shape and its scratch fits VMEM
(``CommContext.fused_fits``). ``all_to_all`` picks its chunk count from
``schedule.choose_a2a_chunks``.

Analytic vs measured costs (``policy=``)
----------------------------------------

The policy's cost source is itself a knob. ``policy="analytic"`` (default)
prices schedules from ``hw``'s datasheet constants. ``policy="measured"``
dispatches from a ``repro.core.autotune`` calibration table — micro-bench
measurements of every backend on *this* machine — and only falls back to
the analytic model (with a warning) when no table matches the machine's
fingerprint or the requested shape is too far off the calibrated grid.
``policy="auto"`` is the same fallback, silent. Produce a table with
``python -m repro.autotune calibrate``; see docs/ARCHITECTURE.md for the
full calibration loop.

This module also owns the **collective-id allocator**: every Pallas
communication kernel gets its ``CompilerParams(collective_id=...)`` from
``collective_id(name)`` instead of a hand-numbered constant, so two kernels
can never collide on a barrier-semaphore id.

The jax-level implementations (formerly ``repro.core.collectives``; that
module is now a removed stub raising ImportError) live at the bottom of this
module and are re-exported from ``repro.core``. Whole overlapped workloads
should be declared through the unified island template
(``repro.core.template.Island``), which threads a ready ``CommContext`` into
the island body.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro import compat
from repro.core import costmodel as cm
from repro.core.quant import (WireFormat, dequantize_blocks, quantize_blocks,
                              resolve_wire, wire_dtype_bytes)
from repro.core.schedule import (GEMM_CHUNK_DIM, ChunkSchedule, OverlapPolicy,
                                 a2a_chunk_axis, choose_a2a_chunks,
                                 choose_gemm_chunks, choose_gemm_collective,
                                 fit_chunks)

__all__ = [
    "CommContext", "collective_id", "register_collective", "OP_BACKENDS",
    "GEMM_OP_KIND",
    # jax-level implementations (canonical home since the comms redesign)
    "all_gather_matmul_baseline", "pk_all_gather_matmul",
    "matmul_reduce_scatter_baseline", "pk_matmul_reduce_scatter",
    "matmul_all_reduce_baseline", "pk_matmul_all_reduce",
    "all_to_all_baseline", "pk_all_to_all", "pk_psum_ring", "ring_shift",
]


# ---------------------------------------------------------------------------
# Central collective-id allocator (replaces hand-numbered 0..5 constants).
# ---------------------------------------------------------------------------

_COLLECTIVE_IDS: dict[str, int] = {}

# Registered eagerly, in a fixed order, so every process of an SPMD job
# assigns identical ids no matter which kernel it happens to trace first.
_CANONICAL_KERNELS = (
    "ring_all_gather",
    "ring_reduce_scatter",
    "p2p_ring_shift",
    "ag_matmul_fused",
    "matmul_rs_fused",
    "lcsc_ring_all_gather",
    "matmul_ar_fused",
)


def register_collective(name: str) -> int:
    """Assign the next id to a named collective kernel.

    MUST be called at kernel-definition (module import) time: import order
    is deterministic across the processes of an SPMD job, trace order is
    not — two hosts tracing conditionally-reached kernels in different
    orders would otherwise map the same id to different kernels."""
    if name not in _COLLECTIVE_IDS:
        _COLLECTIVE_IDS[name] = len(_COLLECTIVE_IDS)
    return _COLLECTIVE_IDS[name]


def collective_id(name: str) -> int:
    """Process-wide stable ``collective_id`` for a registered kernel.

    Pallas requires concurrently-running collective kernels to carry distinct
    ids (they select the barrier semaphore). Hand-numbering them across files
    is a collision waiting to happen; kernels call this instead. Unregistered
    names are an error — silently allocating here would hand out
    trace-order-dependent ids, the exact cross-process mismatch this
    allocator exists to prevent."""
    if name not in _COLLECTIVE_IDS:
        raise KeyError(
            f"collective kernel {name!r} is not registered; call "
            "repro.core.comms.register_collective(name) at module import "
            "time (trace-time allocation would give different ids on "
            "different SPMD processes)")
    return _COLLECTIVE_IDS[name]


def registered_collectives() -> dict[str, int]:
    """Snapshot of the current name -> id assignment (diagnostics/tests)."""
    return dict(_COLLECTIVE_IDS)


for _name in _CANONICAL_KERNELS:
    register_collective(_name)


# ---------------------------------------------------------------------------
# The op/backend registry.
# ---------------------------------------------------------------------------

OP_BACKENDS: dict[str, tuple[str, ...]] = {
    "all_gather_matmul": ("bulk", "ring", "ring_bidir", "fused"),
    "matmul_reduce_scatter": ("bulk", "ring", "fused"),
    "matmul_all_reduce": ("bulk", "ring", "fused"),
    "all_to_all": ("bulk", "chunked"),
    "psum": ("bulk", "ring"),
    "all_gather": ("bulk", "fused"),
    "reduce_scatter": ("bulk", "fused"),
    "ring_shift": ("bulk", "fused"),
}

_ALL_BACKENDS = {b for bs in OP_BACKENDS.values() for b in bs}

#: GEMM×collective op -> cost-model "kind" (the §3.1.3 schedule coordinate).
#: Single source for dispatch here, Island.plan() and the benchmarks.
GEMM_OP_KIND = {"all_gather_matmul": "all_gather",
                "matmul_reduce_scatter": "reduce_scatter",
                "matmul_all_reduce": "all_reduce"}


@dataclasses.dataclass(frozen=True)
class CommContext:
    """One handle for every overlapped collective over a mesh axis.

    Construct once per (mesh, axis); methods are safe to call both at the
    jit level and inside ``shard_map`` (with ``axis_name`` bound). When
    ``mesh`` is None the context must be used inside ``shard_map`` so the
    axis size can be read from the binding.

    ``backend`` set here applies to every call (benchmarks pin "bulk" /
    "ring" to measure both sides); per-call ``backend=`` overrides even that.
    ``interpret`` controls Pallas interpret-mode dispatch for the fused
    kernels: None = interpret everywhere but a real TPU.
    """

    axis_name: str
    mesh: Any = None
    hw: cm.HardwareSpec = cm.TPU_V5E
    backend: str | None = None
    interpret: bool | None = None
    allow_bidir: bool = True
    #: "analytic" prices schedules from ``hw``'s datasheet constants;
    #: "measured" dispatches from a ``repro.core.autotune`` calibration
    #: table (falling back to analytic, with a warning, when none matches
    #: this machine); "auto" is "measured when a matching table exists,
    #: analytic otherwise", silently.
    policy: str = "analytic"
    #: a ``CalibrationTable``, a path to one, or None (= search the user
    #: cache then the in-repo seed tables). Ignored under policy="analytic".
    calibration: Any = None
    #: island key (``autotune.island_key(...)``) this context dispatches as.
    #: Measured lookups prefer calibration rows tagged with this key and fall
    #: back to the global (untagged) rows — two islands with different
    #: layouts/dtypes can then resolve to different backends at the same
    #: (m, n, k). None = global rows only.
    island: str | None = None
    #: context-wide default sub-chunk count for the chunk-pipelined ring
    #: GEMM×collectives (``RunConfig.comm_chunks``). None = per-call kwarg,
    #: else measured table, else the analytic chunk scheduler.
    chunks: int | None = None
    #: on-wire element format for the ring GEMM×collectives
    #: (``RunConfig.comm_wire``): None/"bf16" ships payloads in their own
    #: dtype; "int8" quantizes each travelling sub-chunk per-row into int8
    #: blocks + f32 scales (quantize → ring-shift → dequantize-accumulate
    #: in f32); "int8_sr" adds stochastic rounding (GEMM+AR option). Bulk
    #: and fused backends ignore the wire — it is a property of the ring
    #: transfer schedule, and the measured question the dtype axis answers
    #: is precisely "int8-ring vs bf16-bulk".
    wire: Any = None
    #: scripted comms-level payload fault (runtime/health.py): a
    #: ``(kind, hop)`` pair with kind "corrupt" (NaN the whole hop payload)
    #: or "bitflip" (NaN one element), applied to the ring GEMM×collectives'
    #: hop ``hop`` after its ppermute. Trace-time-static test seam — None
    #: everywhere outside scripted fault injection. Bulk and fused backends
    #: ignore it (only ring transfers have hops to poison).
    fault: Any = None

    def wire_format(self, override: Any = None) -> WireFormat | None:
        """Resolved quantized ``WireFormat`` for a call (per-call ``wire=``
        override first, then the context default), or None when the wire is
        full-precision."""
        return resolve_wire(override if override is not None else self.wire)

    # -- introspection -----------------------------------------------------

    @property
    def axis_size(self) -> int:
        if self.mesh is not None:
            return self.mesh.shape[self.axis_name]
        return compat.axis_size(self.axis_name)

    def available_backends(self, op: str) -> tuple[str, ...]:
        """Backends of `op` (fused ones run compiled on a TPU and in TPU
        interpret mode elsewhere).

        Example::

            >>> CommContext(axis_name="x").available_backends("psum")
            ('bulk', 'ring')
        """
        return OP_BACKENDS[op]

    def active_calibration(self):
        """The ``CalibrationTable`` this context's policy dispatches from,
        or None when the policy is analytic (explicitly, or by fallback
        because no table matches this machine's fingerprint)."""
        from repro.core import autotune
        return autotune.resolve_table(self.calibration, self.hw.name,
                                      self.policy)

    def effective_hw(self) -> cm.HardwareSpec:
        """``hw`` with measured correction factors applied when the measured
        policy is active — the spec every cost-model query below runs on."""
        table = self.active_calibration()
        return table.spec(self.hw) if table is not None else self.hw

    # -- dispatch plumbing -------------------------------------------------

    def _interpret_mode(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        return compat.default_interpret()

    def _resolve(self, op: str, override: str | None, auto) -> str:
        be = override if override is not None else self.backend
        if be in (None, "auto"):
            be = auto()
        elif override is None and be not in OP_BACKENDS[op]:
            # A context-wide pin (RunConfig.comm_backend) names a real
            # backend that this particular op doesn't implement (e.g.
            # "ring_bidir" pinned, matmul_all_reduce called): fall back to
            # the policy for this op rather than crashing the whole run.
            # Unknown names are still an error — a typo'd pin must not
            # silently run the policy everywhere.
            if be not in _ALL_BACKENDS:
                raise ValueError(
                    f"unknown backend {be!r}; known backends: "
                    f"{sorted(_ALL_BACKENDS)}")
            be = auto()
        if be not in OP_BACKENDS[op]:
            raise ValueError(
                f"op {op!r} has no backend {be!r}; "
                f"available: {OP_BACKENDS[op]}")
        return be

    def _shape_guard(self, op: str, be: str, override: str | None,
                     ok: bool, constraint: str, fallback: str = "bulk") -> str:
        """Ring/fused schedules have shape divisibility requirements the
        bulk path doesn't. A per-call ``backend=`` that violates one is a
        caller bug — raise with the constraint spelled out (not the bare
        ``assert`` inside the impl). A context-pinned backend (e.g.
        ``RunConfig.comm_backend`` A/B runs) degrades to `fallback` instead,
        the way the policy does, so decode-shaped calls keep working."""
        if ok or be == fallback:
            return be
        if override is not None:
            raise ValueError(
                f"{op}(backend={be!r}) requires {constraint} "
                f"(axis {self.axis_name!r} has size {self.axis_size})")
        return fallback

    def fused_fits(self, op: str, m: int, n: int, k: int, *,
                   dtype_bytes: int = 2) -> bool:
        """May the policy pick the fused Pallas kernel for GEMM×collective
        ``op`` at dispatch coordinates (m, n, k)? Only compiled on a real
        TPU, at a shape the chip's compiler accepts, with the kernel's whole
        VMEM scratch (f32 accumulators included) inside ``hw.vmem_bytes``.
        Dispatch and ``Island.plan()`` both ask this, so they agree."""
        if jax.default_backend() != "tpu" or self._interpret_mode():
            return False
        from repro.kernels import collective_matmul
        return collective_matmul.fused_fits(
            op, m, n, k, self.axis_size, dtype_bytes=dtype_bytes,
            budget=self.hw.vmem_bytes)

    def gemm_policy(self, m: int, n: int, k: int, *, kind: str,
                    dtype_bytes: int = 2, hw: cm.HardwareSpec | None = None,
                    wire: Any = None) -> OverlapPolicy:
        """The §3.1.3 schedule decision for a fused GEMM×collective of global
        GEMM shape (m, n, k) over this context's axis. Pure / trace-free.

        ``hw=None`` prices on ``effective_hw()``; callers that already hold
        the resolved spec (``auto_gemm_backend``) pass it to avoid resolving
        the calibration table twice per dispatch.

        Only the AG+GEMM op implements the bidirectional ring, so only the
        "all_gather" kind may credit the cost model with the second
        link-pair — otherwise hidden_fraction would be 2x optimistic for
        RS/AR and the policy would report a strategy no backend implements.

        A quantized wire reprices only the ring's transfer side: the hiding
        condition is evaluated at the on-wire element width (scales and
        quantize-kernel term included), so the hidden fraction the plan
        reports reflects what the int8 payload actually ships.
        """
        allow_bidir = self.allow_bidir and kind == "all_gather"
        fmt = self.wire_format(wire)
        return choose_gemm_collective(
            m, n, k, axis_size=self.axis_size, kind=kind,
            dtype_bytes=dtype_bytes,
            hw=hw if hw is not None else self.effective_hw(),
            allow_bidir=allow_bidir,
            wire_bytes=fmt.bytes_per_element if fmt is not None else None)

    _GEMM_KIND = GEMM_OP_KIND

    def auto_gemm_backend(self, op: str, m: int, n: int, k: int, *,
                          dtype_bytes: int = 2, fused_ok: bool = False,
                          bidir_ok: bool = True, wire: Any = None) -> str:
        """The backend ``backend=None`` resolves to for a GEMM×collective of
        global shape (m, n, k) — the policy mapping itself, trace-free, so
        dispatch is unit-testable without running the GEMM. ``fused_ok`` /
        ``bidir_ok`` carry the operand-level constraints (VMEM fit, even
        local rows) the real call sites compute from their arrays.

        Under the measured policy, backends with calibration measurements
        near (m, n, k) are compared on *measured* microseconds and the
        analytic model is only consulted when the table has no usable
        coverage (shape too far off the calibrated grid, or fewer than two
        feasible backends measured). A quantized ``wire`` moves the lookup
        to that width's rows (``dtype_bytes=1`` → the ``b1`` island keys a
        ``calibrate --dtype int8`` sweep produced) — the rows there pit the
        int8 ring against the still-full-precision bulk collective, so the
        measured argmin answers exactly "does int8-ring beat bf16-bulk"."""
        fmt = self.wire_format(wire)
        q_bytes = fmt.dtype_bytes if fmt is not None else dtype_bytes
        table = self.active_calibration()
        if table is not None:
            allowed = ["bulk", "ring"]
            if (op == "all_gather_matmul" and bidir_ok and self.allow_bidir
                    and self.axis_size % 2 == 0):
                allowed.append("ring_bidir")
            if fused_ok:
                allowed.append("fused")
            best = table.best_backend(op, m, n, k, allowed=allowed,
                                      axis_size=self.axis_size,
                                      dtype_bytes=q_bytes,
                                      island=self.island)
            if best is not None:
                return best
        pol = self.gemm_policy(
            m, n, k, kind=self._GEMM_KIND[op], dtype_bytes=dtype_bytes,
            hw=table.spec(self.hw) if table is not None else self.hw,
            wire=wire)
        if not pol.enabled:
            return "bulk"
        if fused_ok and fmt is None:
            # the fused Pallas kernels ship full-precision payloads; under a
            # quantized wire the ring schedules are the ones that actually
            # put int8 on the links
            return "fused"
        if (op == "all_gather_matmul" and pol.strategy == "ring_bidir"
                and bidir_ok):
            return "ring_bidir"
        return "ring"

    def gemm_chunk_schedule(self, op: str, m: int, n: int, k: int, *,
                            backend: str, dtype_bytes: int = 2,
                            n_chunks: int | None = None,
                            chunk_dim: str | None = None,
                            wire: Any = None) -> ChunkSchedule:
        """The chunk-pipeline decision for a resolved GEMM×collective call.

        Precedence: explicit per-call ``n_chunks`` > the context-wide
        ``chunks`` default (``RunConfig.comm_chunks``) > chunk counts
        *measured* in the calibration table (island-keyed rows first) > the
        analytic argmin. Bulk takes no sub-chunks — its whole point is the
        monolithic collective. Ring backends price the analytic tier with
        ``schedule.choose_gemm_chunks``; the fused single-kernel pipeline
        prices it with the ``fused=True`` variant
        (``costmodel.fused_pipeline_cost``: one launch, VMEM-resident
        operands, local-sync chunk handoffs), whose argmin usually sits at a
        finer count. The returned count is a request; the impls fit it to
        the chunked sub-shape's largest divisor (never a new shape
        constraint).

        A quantized ``wire`` pins ``chunk_dim="m"``: blocks are quantized
        per row (along the last axis), so row chunks leave every scale group
        intact — the quantized values stay bit-exact across chunk counts —
        while column chunks would re-cut the blocks per chunk. It also moves
        the measured chunk lookup to the wire's ``b{dtype_bytes}`` rows and
        reprices the analytic argmin at the on-wire element width. The fused
        kernels ship full precision, so their schedule ignores ``wire``
        (chunk rows always slice "m" — the payload's row dim).
        """
        kind = self._GEMM_KIND[op]
        fused = backend == "fused"
        fmt = self.wire_format(wire) if not fused else None
        if fmt is not None or fused:
            # per-row scale groups survive only row chunking; the fused
            # kernels likewise chunk the payload's rows (see docstring)
            chunk_dim = "m"
        dim = chunk_dim if chunk_dim is not None else GEMM_CHUNK_DIM[kind]
        if backend not in ("ring", "ring_bidir", "fused"):
            return ChunkSchedule(1, dim, f"{backend} path takes no sub-chunks")
        if n_chunks is not None:
            return ChunkSchedule(max(1, n_chunks), dim, "per-call n_chunks=",
                                 source="explicit")
        if self.chunks is not None:
            return ChunkSchedule(max(1, self.chunks), dim,
                                 "context chunks= (RunConfig.comm_chunks)",
                                 source="explicit")
        q_bytes = fmt.dtype_bytes if fmt is not None else dtype_bytes
        table = self.active_calibration()
        if table is not None:
            c = table.best_chunks(op, backend, m, n, k,
                                  axis_size=self.axis_size,
                                  dtype_bytes=q_bytes,
                                  island=self.island)
            if c is not None:
                return ChunkSchedule(c, dim, "measured chunk sweep argmin",
                                     source="measured")
        sched = choose_gemm_chunks(
            m, n, k, axis_size=self.axis_size, kind=kind,
            dtype_bytes=dtype_bytes, hw=self.effective_hw(),
            wire_bytes=fmt.bytes_per_element if fmt is not None else None,
            fused=fused)
        return sched if chunk_dim is None else dataclasses.replace(
            sched, chunk_dim=chunk_dim)

    @staticmethod
    def a2a_coords(shape, split_axis: int, concat_axis: int
                   ) -> tuple[int, int, int]:
        """The (m, n, k) lookup coordinates an ``all_to_all`` calibration
        row is stored/queried under: (local payload elements, split-dim
        extent, concat-dim extent). One convention shared by the per-island
        calibration sweep and every dispatch query, the same way the GEMM
        rows share ``auto_gemm_backend``'s coordinates — rows stored in any
        other system would never be found."""
        return (int(math.prod(shape)), int(shape[split_axis]),
                int(shape[concat_axis]))

    def a2a_chunk_schedule(self, shape, split_axis: int, concat_axis: int, *,
                           dtype_bytes: int = 2,
                           downstream_compute_s: float = 0.0
                           ) -> ChunkSchedule:
        """Chunk count for an ``all_to_all`` of local payload ``shape``.

        Measured-first: when the calibration table carries a2a rows near
        :meth:`a2a_coords` (``calibrate --per-island`` sweeps the Ulysses /
        MoE dispatch islands; island-keyed rows preferred), the bulk-vs-
        chunked decision and the chunk count are the measured argmin;
        otherwise the analytic ``schedule.choose_a2a_chunks`` policy
        answers. The count is always fitted to the payload's splittable
        bystander dims, exactly like ``pk_all_to_all`` will."""
        m, n, k = self.a2a_coords(shape, split_axis, concat_axis)
        table = self.active_calibration()
        if table is not None:
            be = table.best_backend("all_to_all", m, n, k,
                                    allowed=("bulk", "chunked"),
                                    axis_size=self.axis_size,
                                    dtype_bytes=dtype_bytes,
                                    island=self.island)
            if be == "bulk":
                return ChunkSchedule(1, "a2a", "measured: bulk a2a wins",
                                     source="measured")
            if be == "chunked":
                c = table.best_chunks("all_to_all", "chunked", m, n, k,
                                      axis_size=self.axis_size,
                                      dtype_bytes=dtype_bytes,
                                      island=self.island)
                c = c if c is not None else 2
                fit = a2a_chunk_axis(shape, split_axis, concat_axis, c)
                if fit is not None and fit[1] > 1:
                    return ChunkSchedule(fit[1], "a2a",
                                         "measured chunk sweep argmin",
                                         source="measured")
                return ChunkSchedule(1, "a2a",
                                     "measured chunked win, but no "
                                     "bystander dim splits", source="measured")
        c = choose_a2a_chunks(
            math.prod(shape) * dtype_bytes, axis_size=self.axis_size,
            downstream_compute_s=downstream_compute_s,
            hw=self.effective_hw(), shape=shape, split_axis=split_axis,
            concat_axis=concat_axis)
        return ChunkSchedule(c, "a2a",
                             f"choose_a2a_chunks -> {c}", source="analytic")

    # -- GEMM × collective ops --------------------------------------------

    def all_gather_matmul(self, x, w, *, backend: str | None = None,
                          n_chunks: int | None = None,
                          chunk_dim: str | None = None,
                          wire: Any = None,
                          preferred=jnp.float32):
        """x: (m_loc, k) row-sharded; w: (k, n_loc) local. -> (m, n_loc).

        The tensor-parallel first projection (paper Fig. 7): gather the
        row-sharded activations while the GEMM consumes already-arrived
        shards. ``backend="ring_bidir"`` additionally needs ``m_loc >= 2``
        (the shard is split across the two ring directions — unevenly when
        odd; the guard validates the chunked sub-shape, not full-shard
        parity). ``n_chunks``/``chunk_dim`` select the chunk-pipeline
        granularity of the ring schedules (None = scheduler/measured table).
        ``wire`` (per-call override of the context default) selects the
        on-wire format of the ring payloads: "int8" quantizes each
        travelling shard chunk once per-row and ships (int8, f32-scale)
        pairs around the ring, dequantizing for each arrival's GEMM.

        Example (inside ``shard_map`` with axis ``"model"`` bound)::

            ctx = CommContext(axis_name="model", mesh=mesh)
            # x: (seq/n_dev, d_model) per device; w: (d_model, d_ff/n_dev)
            y = ctx.all_gather_matmul(x, w)          # policy-routed
            y = ctx.all_gather_matmul(x, w, backend="ring", n_chunks=4)
        """
        n_dev = self.axis_size
        m_loc, k = x.shape
        n_out = w.shape[1]
        fmt = self.wire_format(wire)

        def auto() -> str:
            return self.auto_gemm_backend(
                "all_gather_matmul", m_loc * n_dev, n_out, k,
                dtype_bytes=x.dtype.itemsize,
                fused_ok=self.fused_fits("all_gather_matmul", m_loc * n_dev,
                                         n_out, k,
                                         dtype_bytes=x.dtype.itemsize),
                bidir_ok=(m_loc >= 2), wire=fmt)

        be = self._resolve("all_gather_matmul", backend, auto)
        if be == "ring_bidir":
            be = self._shape_guard(
                "all_gather_matmul", be, backend,
                ok=(m_loc >= 2 or n_dev % 2 != 0),
                constraint="at least 2 local rows to split across the two "
                           "ring directions (m_loc >= 2)",
                fallback="ring")
        if be == "bulk":
            return all_gather_matmul_baseline(x, w, self.axis_name,
                                              preferred=preferred)
        if be in ("ring", "ring_bidir"):
            sched = self.gemm_chunk_schedule(
                "all_gather_matmul", m_loc * n_dev, n_out, k, backend=be,
                dtype_bytes=x.dtype.itemsize, n_chunks=n_chunks,
                chunk_dim=chunk_dim, wire=fmt)
            return pk_all_gather_matmul(x, w, self.axis_name,
                                        bidirectional=(be == "ring_bidir"),
                                        n_chunks=sched.n_chunks,
                                        chunk_dim=sched.chunk_dim,
                                        wire=fmt, fault=self.fault,
                                        preferred=preferred)
        from repro.kernels import ops
        sched = self.gemm_chunk_schedule(
            "all_gather_matmul", m_loc * n_dev, n_out, k, backend="fused",
            dtype_bytes=x.dtype.itemsize, n_chunks=n_chunks,
            chunk_dim=chunk_dim)
        return ops.pk_ag_matmul(x, w, self.axis_name,
                                n_chunks=sched.n_chunks,
                                interpret=self._interpret_mode()
                                ).astype(x.dtype)

    def matmul_reduce_scatter(self, x, w, *, backend: str | None = None,
                              n_chunks: int | None = None,
                              chunk_dim: str | None = None,
                              wire: Any = None,
                              preferred=jnp.float32):
        """x: (m, k_loc); w: (k_loc, n). -> (m_loc, n) = RS(x @ w).

        The tensor-parallel second projection (paper Fig. 8): each device
        holds a K-shard, partial products are reduce-scattered. The ring
        backend computes per-destination blocks and accumulates them around
        the ring, hiding each hop under the next block's GEMM; it requires
        ``m`` divisible by the axis size.

        Example::

            ctx = CommContext(axis_name="model", mesh=mesh)
            # x: (seq, d_ff/n_dev); w: (d_ff/n_dev, d_model)
            y = ctx.matmul_reduce_scatter(x, w)      # -> (seq/n_dev, d_model)
        """
        n_dev = self.axis_size
        m, k_loc = x.shape
        n_out = w.shape[1]
        fmt = self.wire_format(wire)

        def auto() -> str:
            if m % n_dev != 0:
                return "bulk"            # ring needs m divisible by the axis
            return self.auto_gemm_backend(
                "matmul_reduce_scatter", m, n_out, k_loc,
                dtype_bytes=x.dtype.itemsize,
                fused_ok=self.fused_fits("matmul_reduce_scatter", m, n_out, k_loc,
                                         dtype_bytes=x.dtype.itemsize),
                wire=fmt)

        be = self._resolve("matmul_reduce_scatter", backend, auto)
        if be != "bulk":
            be = self._shape_guard(
                "matmul_reduce_scatter", be, backend, ok=(m % n_dev == 0),
                constraint="m divisible by the axis size")
        if be == "bulk":
            return matmul_reduce_scatter_baseline(x, w, self.axis_name,
                                                  preferred=preferred)
        if be == "ring":
            sched = self.gemm_chunk_schedule(
                "matmul_reduce_scatter", m, n_out, k_loc, backend=be,
                dtype_bytes=x.dtype.itemsize, n_chunks=n_chunks,
                chunk_dim=chunk_dim, wire=fmt)
            return pk_matmul_reduce_scatter(x, w, self.axis_name,
                                            n_chunks=sched.n_chunks,
                                            chunk_dim=sched.chunk_dim,
                                            wire=fmt, fault=self.fault,
                                            preferred=preferred)
        from repro.kernels import ops
        sched = self.gemm_chunk_schedule(
            "matmul_reduce_scatter", m, n_out, k_loc, backend="fused",
            dtype_bytes=x.dtype.itemsize, n_chunks=n_chunks,
            chunk_dim=chunk_dim)
        return ops.pk_matmul_rs(x, w, self.axis_name,
                                n_chunks=sched.n_chunks,
                                interpret=self._interpret_mode()
                                ).astype(x.dtype)

    def matmul_all_reduce(self, x, w, *, backend: str | None = None,
                          n_chunks: int | None = None,
                          chunk_dim: str | None = None,
                          wire: Any = None,
                          preferred=jnp.float32):
        """x: (m, k_loc); w: (k_loc, n). -> (m, n) = AR(x @ w).

        Paper Fig. 9. ICI has no in-network reduction, so the overlapped
        backends realize AR as RS (hidden under the GEMM) + AG — same
        2(N-1)/N per-device bytes. Ring needs ``m`` divisible by the axis.

        Example::

            ctx = CommContext(axis_name="model", mesh=mesh)
            y = ctx.matmul_all_reduce(x, w)              # replicated (m, n)
            y = ctx.matmul_all_reduce(x, w, backend="bulk")  # A/B baseline
        """
        n_dev = self.axis_size
        m, k_loc = x.shape
        n_out = w.shape[1]
        fmt = self.wire_format(wire)

        def auto() -> str:
            if m % n_dev != 0:
                return "bulk"
            return self.auto_gemm_backend(
                "matmul_all_reduce", m, n_out, k_loc,
                dtype_bytes=x.dtype.itemsize,
                fused_ok=self.fused_fits("matmul_all_reduce", m, n_out, k_loc,
                                         dtype_bytes=x.dtype.itemsize),
                wire=fmt)

        be = self._resolve("matmul_all_reduce", backend, auto)
        if be != "bulk":
            be = self._shape_guard(
                "matmul_all_reduce", be, backend, ok=(m % n_dev == 0),
                constraint="m divisible by the axis size")
        if be == "bulk":
            return matmul_all_reduce_baseline(x, w, self.axis_name,
                                              preferred=preferred)
        if be == "ring":
            sched = self.gemm_chunk_schedule(
                "matmul_all_reduce", m, n_out, k_loc, backend=be,
                dtype_bytes=x.dtype.itemsize, n_chunks=n_chunks,
                chunk_dim=chunk_dim, wire=fmt)
            return pk_matmul_all_reduce(x, w, self.axis_name,
                                        n_chunks=sched.n_chunks,
                                        chunk_dim=sched.chunk_dim,
                                        wire=fmt, fault=self.fault,
                                        preferred=preferred)
        from repro.kernels import ops
        sched = self.gemm_chunk_schedule(
            "matmul_all_reduce", m, n_out, k_loc, backend="fused",
            dtype_bytes=x.dtype.itemsize, n_chunks=n_chunks,
            chunk_dim=chunk_dim)
        # one kernel end to end (RS ring + in-kernel gather) — the old
        # pk_matmul_rs + lax.all_gather composition re-entered XLA for the
        # trailing gather, forfeiting the fused path's single-launch win
        return ops.pk_matmul_ar(x, w, self.axis_name,
                                n_chunks=sched.n_chunks,
                                interpret=self._interpret_mode()
                                ).astype(x.dtype)

    # -- data-movement ops -------------------------------------------------

    def all_to_all(self, x, *, split_axis: int, concat_axis: int,
                   backend: str | None = None, n_chunks: int | None = None,
                   downstream_compute_s: float = 0.0):
        """Re-sharding all-to-all; "chunked" overlaps downstream compute.

        Paper Fig. 11/17 (Ulysses head↔sequence re-sharding, MoE dispatch).
        ``downstream_compute_s`` tells the chunk policy how much compute is
        available to hide later chunks under; ``n_chunks`` forces the count.
        Chunks are cut along a bystander dim, so results are bit-identical
        to bulk.

        Example (Ulysses: seq-sharded -> head-sharded)::

            ctx = CommContext(axis_name="sp", mesh=mesh)
            # q: (b, heads, seq/n_dev, hd) -> (b, heads/n_dev, seq, hd)
            q = ctx.all_to_all(q, split_axis=1, concat_axis=2)
        """

        def auto_chunks() -> int:
            # the policy validates against the chunked sub-shape: counts no
            # bystander dim can split degrade (or drop to bulk) here rather
            # than inside the impl
            return choose_a2a_chunks(
                x.size * x.dtype.itemsize, axis_size=self.axis_size,
                downstream_compute_s=downstream_compute_s,
                hw=self.effective_hw(), shape=x.shape,
                split_axis=split_axis, concat_axis=concat_axis)

        def auto() -> str:
            if n_chunks is not None:
                return "chunked" if n_chunks > 1 else "bulk"
            return "chunked" if auto_chunks() > 1 else "bulk"

        be = self._resolve("all_to_all", backend, auto)
        if be == "bulk":
            return all_to_all_baseline(x, self.axis_name,
                                       split_axis=split_axis,
                                       concat_axis=concat_axis)
        c = n_chunks if n_chunks is not None else auto_chunks()
        return pk_all_to_all(x, self.axis_name, split_axis=split_axis,
                             concat_axis=concat_axis, n_chunks=max(c, 2))

    def psum(self, x, *, backend: str | None = None):
        """All-reduce. "ring" keeps the payload in its dtype (bf16 halves the
        bytes vs XLA's f32-promoted psum) and each hop overlaps compute;
        it requires ``x.shape[0]`` divisible by the axis size.

        Example (MoE combine across experts)::

            ctx = CommContext(axis_name="expert", mesh=mesh)
            y = ctx.psum(partial_outputs)            # policy-routed
            y = ctx.psum(partial_outputs, backend="ring")
        """

        def auto() -> str:
            ring_ok = x.ndim >= 1 and x.shape[0] % self.axis_size == 0
            table = self.active_calibration()
            if table is not None and ring_ok:
                best = table.best_backend(
                    "psum", x.shape[0],
                    max(x.size // max(x.shape[0], 1), 1), 1,
                    allowed=("bulk", "ring"), axis_size=self.axis_size,
                    dtype_bytes=x.dtype.itemsize, island=self.island)
                if best is not None:
                    return best
            if ring_ok and x.dtype == jnp.bfloat16:
                return "ring"
            return "bulk"

        be = self._resolve("psum", backend, auto)
        if be == "ring":
            be = self._shape_guard(
                "psum", be, backend,
                ok=(x.ndim >= 1 and x.shape[0] % self.axis_size == 0),
                constraint="shape[0] divisible by the axis size")
        if be == "bulk":
            return lax.psum(x, self.axis_name)
        return pk_psum_ring(x, self.axis_name)

    def all_gather(self, x, *, axis: int = 0, backend: str | None = None):
        """Tiled all-gather along `axis`.

        Example (FSDP param gather before a block)::

            ctx = CommContext(axis_name="data", mesh=mesh)
            w_full = ctx.all_gather(w_shard)                  # axis 0
            w_full = ctx.all_gather(w_shard, backend="fused") # Pallas kernel
        """
        be = self._resolve("all_gather", backend, lambda: "bulk")
        if be == "bulk":
            return lax.all_gather(x, self.axis_name, axis=axis, tiled=True)
        from repro.kernels import ops
        stacked = ops.pk_all_gather(x, self.axis_name,
                                    interpret=self._interpret_mode())
        return jnp.concatenate([stacked[i] for i in range(self.axis_size)],
                               axis=axis)

    def reduce_scatter(self, x, *, axis: int = 0,
                       backend: str | None = None):
        """Tiled reduce-scatter along `axis`.

        Example (FSDP gradient shard-reduce)::

            ctx = CommContext(axis_name="data", mesh=mesh)
            g_shard = ctx.reduce_scatter(grads)   # (n*d, ...) -> (d, ...)
        """
        be = self._resolve("reduce_scatter", backend, lambda: "bulk")
        if be == "bulk":
            return lax.psum_scatter(x, self.axis_name,
                                    scatter_dimension=axis, tiled=True)
        if axis != 0:
            raise ValueError("fused reduce_scatter supports axis=0 only")
        n_dev = self.axis_size
        from repro.kernels import ops
        parts = x.reshape(n_dev, x.shape[0] // n_dev, *x.shape[1:])
        return ops.pk_reduce_scatter(parts, self.axis_name,
                                     interpret=self._interpret_mode())

    def ring_shift(self, x, *, reverse: bool = False,
                   backend: str | None = None):
        """One-hop ring rotation of a pytree (KV blocks in ring attention,
        SSM boundary states in sequence-parallel Mamba).

        Example (ring attention inner loop)::

            ctx = CommContext(axis_name="sp", mesh=mesh)
            kv = ctx.ring_shift({"k": k, "v": v})    # device d -> d+1
        """
        be = self._resolve("ring_shift", backend, lambda: "bulk")
        if be == "bulk":
            return ring_shift(x, self.axis_name, reverse=reverse)
        if reverse:
            raise ValueError("fused ring_shift sends right only")
        from repro.kernels import ops
        return jax.tree_util.tree_map(
            lambda t: ops.pk_ring_shift(t, self.axis_name,
                                        interpret=self._interpret_mode()), x)


# ---------------------------------------------------------------------------
# jax-level implementations (paper §4.1), moved here from core/collectives.
# Each pk_* function MUST be called inside shard_map with `axis_name` bound.
# Ring direction conventions:
#   "send right": perm (j -> j+1); after i hops device d holds shard (d-i)%n.
#   "send left":  perm (j -> j-1); after i hops device d holds shard (d+i)%n.
# ---------------------------------------------------------------------------


def _perm_right(n: int):
    return [(j, (j + 1) % n) for j in range(n)]


def _perm_left(n: int):
    return [(j, (j - 1) % n) for j in range(n)]


def _axis_info(axis_name):
    n = compat.axis_size(axis_name)
    d = lax.axis_index(axis_name)
    return n, d


# -- chunk plumbing shared by the ring schedules -----------------------------

def _wire_sr_key(wire: WireFormat | None, axis_name: str, salt: int):
    """Deterministic per-device stochastic-rounding key for a quantized
    ring, or None for round-to-nearest wires. Derived from a fixed seed +
    the device's ring position + a per-op salt, so every retrace of the
    same schedule rounds identically (reproducible runs) while no two
    devices or hops share noise."""
    if wire is None or not wire.stochastic_round:
        return None
    key = jax.random.fold_in(jax.random.PRNGKey(1729), salt)
    return jax.random.fold_in(key, lax.axis_index(axis_name))


def _poison_hop(fault, hop: int, t: jax.Array) -> jax.Array:
    """Scripted payload fault (``CommContext.fault``): corrupt ``t`` when
    ``fault`` = (kind, hop') targets ring hop ``hop``. "corrupt" NaNs the
    whole payload, "bitflip" a single element. Float payloads only — a
    quantized wire's int8 payload is poisoned through its f32 scales at
    the call site. Trace-time static: no fault, no extra ops."""
    if fault is None or fault[1] != hop:
        return t
    if not jnp.issubdtype(t.dtype, jnp.floating):
        return t
    if fault[0] == "bitflip":
        return t.at[(0,) * t.ndim].set(jnp.nan)
    return jnp.full_like(t, jnp.nan)


def _row_chunks(t: jax.Array, n_chunks: int) -> list[jax.Array]:
    """Split `t` into `n_chunks` row chunks (fitted to a divisor of the row
    count — the non-divisible fallback validates the chunked sub-shape)."""
    c = fit_chunks(t.shape[0], n_chunks)
    if c == 1:
        return [t]
    return list(jnp.split(t, c, axis=0))


def _col_chunks(t: jax.Array, n_chunks: int) -> list[jax.Array]:
    c = fit_chunks(t.shape[1], n_chunks)
    if c == 1:
        return [t]
    return list(jnp.split(t, c, axis=1))


# -- AG + GEMM (paper Fig. 7) — tensor-parallel first projection. -----------

def all_gather_matmul_baseline(x: jax.Array, w: jax.Array, axis_name: str,
                               *, preferred=jnp.float32) -> jax.Array:
    """x: (m_loc, k) row-sharded over axis; w: (k, n_loc) local TP shard.
    Returns (m, n_loc): bulk all-gather then a single GEMM."""
    x_full = lax.all_gather(x, axis_name, axis=0, tiled=True)
    return jnp.dot(x_full, w, preferred_element_type=preferred).astype(x.dtype)


def _ag_ring_lane(x, w, out, axis_name, *, n, d, row0: int, m_stride: int,
                  reverse: bool, n_chunks: int, chunk_dim: str, preferred,
                  wire: WireFormat | None = None, fault=None):
    """One direction of the chunk-pipelined AG+GEMM ring.

    The travelling shard is split into chunks (rows for chunk_dim="m",
    GEMM output columns for "n"); every step issues the *next* step's
    ppermutes before the current chunk GEMMs consume their operands
    (double-buffered send-ahead), so the per-chunk shifts hide under the
    per-chunk GEMMs at sub-shard granularity.

    With a quantized ``wire``, each travelling chunk is quantized ONCE
    per-row before the first hop and travels the whole ring as an
    (int8 payload, f32 scales) pair; every arrival — the device's own
    chunk included, so ring == bulk-quantized-AG exactly — is dequantized
    to f32 for its GEMM. Because blocks are per-row and chunks slice rows,
    the dequantized values are bit-exact across chunk counts.
    """
    perm = _perm_left(n) if reverse else _perm_right(n)
    if chunk_dim == "n":
        w_chunks = _col_chunks(w, n_chunks)
        cur = [x]
    else:
        w_chunks = [w]
        cur = _row_chunks(x, n_chunks)
    k_cols = x.shape[1]
    if wire is not None:
        key = _wire_sr_key(wire, axis_name, salt=1 if reverse else 0)
        cur = [quantize_blocks(
                   t, block=wire.block,
                   stochastic_key=(None if key is None
                                   else jax.random.fold_in(key, j)))
               for j, t in enumerate(cur)]
    for i in range(n):
        src = (d + i) % n if reverse else (d - i) % n
        # send-ahead: step i+1's shifts are issued before step i's GEMMs,
        # which depend only on the already-held chunks
        if i < n - 1:
            if wire is None:
                nxt = [_poison_hop(fault, i, lax.ppermute(t, axis_name, perm))
                       for t in cur]
            else:
                nxt = [(lax.ppermute(q, axis_name, perm),
                        _poison_hop(fault, i,
                                    lax.ppermute(s, axis_name, perm)))
                       for q, s in cur]
        else:
            nxt = cur
        r = 0
        for t in cur:
            if wire is not None:
                q, s = t
                rows = q.shape[0]
                t = dequantize_blocks(q, s, k_cols)
            else:
                rows = t.shape[0]
            col = 0
            for wc in w_chunks:
                y = jnp.dot(t, wc,
                            preferred_element_type=preferred).astype(x.dtype)
                out = lax.dynamic_update_slice(
                    out, y, (src * m_stride + row0 + r, col))
                col += wc.shape[1]
            r += rows
        cur = nxt
    return out


def pk_all_gather_matmul(x: jax.Array, w: jax.Array, axis_name: str, *,
                         bidirectional: bool = False, n_chunks: int = 1,
                         chunk_dim: str = "m", wire: WireFormat | None = None,
                         fault=None, preferred=jnp.float32) -> jax.Array:
    """Chunk-pipelined AG+GEMM: rotate x shards around the ring; GEMM each
    chunk on arrival. Each ring step is split into `n_chunks` double-buffered
    chunks whose shifts for step i+1 are issued before step i's GEMMs (paper
    §3.1.3 intra-/inter-SM overlap at sub-shard granularity). Chunk counts
    that do not divide the chunked sub-shape degrade to its largest divisor;
    results are bit-identical to the unchunked ring for any count.

    ``bidirectional`` splits the shard across the two ring directions (two
    link-pairs, halving T_comm). The split no longer requires an even
    ``m_loc``: an odd shard splits unevenly (ceil right, floor left) — the
    chunked sub-shapes are what must be sliceable, not the full shard.

    ``wire`` (a quantized ``core.quant.WireFormat``) ships the travelling
    shards as int8 blocks + f32 scales; every consumer — including the
    local device's own shard — sees the dequantized values, so the result
    equals a bulk all-gather of the per-row-quantized input bit-for-bit,
    for any chunk count."""
    n, d = _axis_info(axis_name)
    wire = wire if (wire is not None and wire.quantized) else None
    m_loc, _ = x.shape
    n_out = w.shape[1]
    out = jnp.zeros((n * m_loc, n_out), dtype=x.dtype)

    if not bidirectional or n % 2 != 0 or m_loc < 2:
        return _ag_ring_lane(x, w, out, axis_name, n=n, d=d, row0=0,
                             m_stride=m_loc, reverse=False, n_chunks=n_chunks,
                             chunk_dim=chunk_dim, preferred=preferred,
                             wire=wire, fault=fault)

    # Bidirectional: the shard's top rows travel the right-going ring, the
    # bottom rows the left-going ring — each of the n-1 hops moves part of a
    # shard per direction over two link-pairs, halving T_comm versus the
    # unidirectional ring. Odd m_loc splits ceil/floor (every device uses the
    # same static split, so the ppermute payloads stay uniform).
    h_r = (m_loc + 1) // 2
    x_r, x_l = x[:h_r], x[h_r:]
    out = _ag_ring_lane(x_r, w, out, axis_name, n=n, d=d, row0=0,
                        m_stride=m_loc, reverse=False, n_chunks=n_chunks,
                        chunk_dim=chunk_dim, preferred=preferred, wire=wire,
                        fault=fault)
    return _ag_ring_lane(x_l, w, out, axis_name, n=n, d=d, row0=h_r,
                         m_stride=m_loc, reverse=True, n_chunks=n_chunks,
                         chunk_dim=chunk_dim, preferred=preferred, wire=wire,
                         fault=fault)


# -- GEMM + reduce-scatter (paper Fig. 8 / Table 3) — TP second projection. --

def matmul_reduce_scatter_baseline(x: jax.Array, w: jax.Array, axis_name: str,
                                   *, preferred=jnp.float32) -> jax.Array:
    """x: (m, k_loc); w: (k_loc, n). Returns (m_loc, n) = RS(x @ w).
    Bulk: full partial GEMM then one reduce-scatter."""
    partial = jnp.dot(x, w, preferred_element_type=preferred)
    out = lax.psum_scatter(partial, axis_name, scatter_dimension=0, tiled=True)
    return out.astype(x.dtype)


def pk_matmul_reduce_scatter(x: jax.Array, w: jax.Array, axis_name: str, *,
                             n_chunks: int = 1, chunk_dim: str = "m",
                             wire: WireFormat | None = None,
                             fault=None, preferred=jnp.float32) -> jax.Array:
    """Chunk-pipelined GEMM+RS (accumulate-and-forward ring).

    At step i, device d computes the partial block destined for device
    (d+1+i) % n, adds the accumulator arriving from the right, and forwards
    left. The final step computes d's own block — no trailing permute. The
    per-step GEMM hides the per-step transfer whenever K >= s*R/(2*B)
    (costmodel.hiding_threshold_k).

    With ``n_chunks`` > 1 the per-destination block travels as independent
    chunks (rows for chunk_dim="m", output columns for "n"): chunk j's shift
    is issued before chunk j+1's GEMM is consumed, so the per-chunk hops hide
    under per-chunk compute at sub-block granularity. Chunk counts are fitted
    to the chunked sub-shape (largest divisor), and every count is
    bit-identical to the unchunked ring (GEMM rows/columns are independent
    and the accumulation order around the ring is unchanged).

    A quantized ``wire`` replaces the bf16 accumulator hop with quantize →
    ring-shift (int8 payload + f32 scales) → dequantize-accumulate in f32:
    the accumulator stays f32 between hops locally and only crosses the
    link quantized. ``chunk_dim`` is forced to "m" — blocks are per-row, so
    row chunks keep every scale group intact and the quantized values stay
    bit-exact across chunk counts (column chunks would re-cut the blocks).
    """
    n, d = _axis_info(axis_name)
    wire = wire if (wire is not None and wire.quantized) else None
    if wire is not None:
        chunk_dim = "m"
    m = x.shape[0]
    assert m % n == 0, (m, n)
    m_blk = m // n
    n_out = w.shape[1]

    if chunk_dim == "n":
        c = fit_chunks(n_out, n_chunks)
        w_chunks = _col_chunks(w, c)

        def partial_chunk(b, j):
            xb = lax.dynamic_slice_in_dim(x, b * m_blk, m_blk, axis=0)
            return jnp.dot(xb, w_chunks[j], preferred_element_type=preferred)
    else:
        c = fit_chunks(m_blk, n_chunks)
        sub = m_blk // c

        def partial_chunk(b, j):
            xb = lax.dynamic_slice_in_dim(x, b * m_blk + j * sub, sub, axis=0)
            return jnp.dot(xb, w, preferred_element_type=preferred)

    if wire is not None:
        key = _wire_sr_key(wire, axis_name, salt=2)
        accs = [partial_chunk((d + 1) % n, j).astype(jnp.float32)
                for j in range(c)]
        for i in range(1, n):
            # quantize each chunk accumulator, ship the (int8, scales) pair;
            # send-ahead: all chunk shifts are issued before this step's GEMMs
            qs = [quantize_blocks(
                      a, block=wire.block,
                      stochastic_key=(None if key is None else
                                      jax.random.fold_in(key, i * c + j)))
                  for j, a in enumerate(accs)]
            qs = [(lax.ppermute(q, axis_name, _perm_left(n)),
                   _poison_hop(fault, i - 1,
                               lax.ppermute(s, axis_name, _perm_left(n))))
                  for q, s in qs]
            accs = [dequantize_blocks(q, s, n_out)
                    + partial_chunk((d + 1 + i) % n, j).astype(jnp.float32)
                    for j, (q, s) in enumerate(qs)]
        accs = [a.astype(x.dtype) for a in accs]
        return accs[0] if c == 1 else jnp.concatenate(accs, axis=0)

    # the ring payload travels in the activation dtype (bf16): half the ICI
    # bytes of an f32 accumulator; each hop's add still runs in f32
    accs = [partial_chunk((d + 1) % n, j).astype(x.dtype) for j in range(c)]
    for i in range(1, n):
        # send-ahead: all chunk shifts are issued before this step's GEMMs
        accs = [_poison_hop(fault, i - 1,
                            lax.ppermute(a, axis_name, _perm_left(n)))
                for a in accs]
        accs = [(a.astype(preferred)
                 + partial_chunk((d + 1 + i) % n, j)).astype(x.dtype)
                for j, a in enumerate(accs)]
    if c == 1:
        return accs[0]
    return jnp.concatenate(accs, axis=1 if chunk_dim == "n" else 0)


# -- GEMM + all-reduce (paper Fig. 9). ---------------------------------------

def matmul_all_reduce_baseline(x: jax.Array, w: jax.Array, axis_name: str,
                               *, preferred=jnp.float32) -> jax.Array:
    partial = jnp.dot(x, w, preferred_element_type=preferred)
    return lax.psum(partial, axis_name).astype(x.dtype)


def pk_matmul_all_reduce(x: jax.Array, w: jax.Array, axis_name: str, *,
                         n_chunks: int = 1, chunk_dim: str = "m",
                         wire: WireFormat | None = None,
                         fault=None, preferred=jnp.float32) -> jax.Array:
    """Overlapped GEMM+AR. TPU ICI has no in-network reduction (DESIGN §2.1),
    so the paper's switch-offloaded AR is re-derived as overlapped
    RS(accumulate-on-arrival) + AG: same 2*(N-1)/N per-device traffic, and the
    RS half hides under the GEMM. ``n_chunks``/``chunk_dim`` chunk-pipeline
    the RS half (see ``pk_matmul_reduce_scatter``).

    A quantized ``wire`` applies to BOTH halves: the RS hops ship quantized
    accumulators, and the trailing gather ships each device's reduced shard
    as one more (int8, f32 scales) pair, dequantized after the gather —
    ``wire.stochastic_round`` ("int8_sr") makes every quantize on the path
    unbiased, the GEMM+AR mode where repeated reductions must not drift."""
    n, _ = _axis_info(axis_name)
    wire = wire if (wire is not None and wire.quantized) else None
    rs = pk_matmul_reduce_scatter(x, w, axis_name, n_chunks=n_chunks,
                                  chunk_dim=chunk_dim, wire=wire,
                                  fault=fault, preferred=preferred)
    if wire is None:
        return lax.all_gather(rs, axis_name, axis=0, tiled=True)
    key = _wire_sr_key(wire, axis_name, salt=3)
    q, s = quantize_blocks(rs.astype(jnp.float32), block=wire.block,
                           stochastic_key=key)
    q = lax.all_gather(q, axis_name, axis=0, tiled=True)
    s = lax.all_gather(s, axis_name, axis=0, tiled=True)
    return dequantize_blocks(q, s, rs.shape[-1]).astype(rs.dtype)


# -- Fine-grained all-to-all (paper Fig. 11 / 17). ----------------------------

def all_to_all_baseline(x: jax.Array, axis_name: str, *, split_axis: int,
                        concat_axis: int) -> jax.Array:
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def pk_all_to_all(x: jax.Array, axis_name: str, *, split_axis: int,
                  concat_axis: int, n_chunks: int = 1) -> jax.Array:
    """Chunked a2a: splitting the payload lets downstream compute start on the
    first chunk while later chunks are still in flight (inter-SM analogue).
    With n_chunks=1 this is the native tiled all-to-all, which — unlike NCCL
    (paper §4.2) — already operates on the strided layout with no reshape.

    Chunks are cut along a *bystander* dim (neither split nor concat) so the
    chunked result is bit-identical to the bulk op. The requested count is
    validated against the chunked sub-shape (``schedule.a2a_chunk_axis``): a
    count no bystander dim divides exactly degrades to the largest feasible
    divisor instead of silently bulking the whole transfer."""
    if n_chunks == 1:
        return all_to_all_baseline(x, axis_name, split_axis=split_axis,
                                   concat_axis=concat_axis)
    fit = a2a_chunk_axis(x.shape, split_axis, concat_axis, n_chunks)
    if fit is None:
        return all_to_all_baseline(x, axis_name, split_axis=split_axis,
                                   concat_axis=concat_axis)
    chunk_axis, c = fit
    chunks = jnp.split(x, c, axis=chunk_axis)
    outs = [lax.all_to_all(t, axis_name, split_axis=split_axis,
                           concat_axis=concat_axis, tiled=True) for t in chunks]
    return jnp.concatenate(outs, axis=chunk_axis)


def pk_psum_ring(y: jax.Array, axis_name: str) -> jax.Array:
    """all-reduce as an explicit accumulate-and-forward ring (RS) + ring AG,
    built from ppermutes — the TPU re-derivation of the paper's in-network
    AR (DESIGN §2.1): same 2(N-1)/N per-device traffic, but the payload
    keeps its dtype (XLA:CPU promotes bf16 all-reduce to f32 — 2x bytes)
    and each hop is independently overlappable with compute."""
    n, d = _axis_info(axis_name)
    lead = y.shape[0]
    if n == 1:
        return y
    if lead % n != 0:
        return lax.psum(y, axis_name)
    blk = lead // n
    parts = y.reshape(n, blk, *y.shape[1:])
    acc = parts[(d + 1) % n]
    for i in range(1, n):
        acc = lax.ppermute(acc, axis_name, _perm_left(n))
        acc = acc + parts[(d + 1 + i) % n]
    out = lax.all_gather(acc, axis_name, axis=0, tiled=True)
    return out.reshape(y.shape)


# -- Ring shift — the PK `store_async`-to-neighbor pattern at jax level. -----

def ring_shift(x, axis_name: str, *, reverse: bool = False):
    """One-hop ring rotation of a pytree (KV blocks in ring attention, SSM
    states in sequence-parallel Mamba)."""
    n = compat.axis_size(axis_name)
    perm = _perm_left(n) if reverse else _perm_right(n)
    return jax.tree_util.tree_map(
        lambda t: lax.ppermute(t, axis_name, perm), x)
