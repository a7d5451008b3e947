"""Fused collective×GEMM kernels — the paper's flagship workloads (Fig. 7/8,
Table 3) as single Pallas kernels with **intra-kernel overlap**: the scalar
core issues the next ring RDMA, then the MXU computes the current shard while
the transfer flies. This is the TPU realization of the paper's intra-SM
overlapping (§3.1.3): the "communication warp" is the scalar core + ICI DMA
engine, and it costs zero MXU occupancy.

Chunk pipeline (``core/schedule.ChunkSchedule``, Syncopate's chunk-centric
thesis): every ring hop is additionally split into ``n_chunks`` row
sub-chunks. The scalar core issues chunk c's one-way RDMA *ahead of* the
chunk GEMM it overlaps, so the first output rows are computed (AG×GEMM) or
on the wire (GEMM×RS/AR) while the rest of the hop's payload is still
flying — the pipeline fill shrinks from one shard transfer to one chunk
transfer. Chunks slice the payload's row dim only, so the per-row K
reduction order is untouched and every chunk count is **bit-identical** to
the 1-chunk schedule (enforced by tests/test_fused_chunks.py). Requested
counts that do not divide the payload rows degrade via ``fit_chunks`` —
chunking is never a shape constraint.

Communication code in each kernel is ~12 lines (start / wait / signal),
mirroring the paper's <50-LOC claim; everything else is the same GEMM a
single-device kernel would have.

Synchronization discipline (see kernels/pk_comm.py for the derivation):
per-hop × per-chunk send/recv DMA semaphores order arrivals; cap_sem acks
guard double-buffer reuse and stay per-slot (the consumer frees a whole
slot, so the capacity ack does not chunk). All one-way — no rendezvous
(paper §3.1.4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.core.comms import collective_id
from repro.core.schedule import fit_chunks, row_tile

from repro.kernels.pk_comm import (pk_neighbor_barrier, pk_signal,
                                   pk_store_async, pk_store_chunked, pk_wait)


# ---------------------------------------------------------------------------
# What the TPU compiler accepts: row tiling and VMEM footprint. Dispatch
# (CommContext.fused_fits) and Island.plan() both read ``fused_fits``, so
# `fused` is only ever chosen at a shape the kernel compiles at.
# ---------------------------------------------------------------------------

#: Mosaic's default scoped-VMEM limit on v5e; kernels that need more ask
#: for it explicitly through ``vmem_limit_bytes``
DEFAULT_VMEM_LIMIT = 16 * 2**20
#: what Mosaic keeps beside the declared scratch (dot results, spills)
_VMEM_HEADROOM = 8 * 2**20


def _rs_align(dtype_bytes: int) -> int:
    # the RS/AR chunks slice f32 accumulators and x.dtype blocks alike
    return max(row_tile(4), row_tile(dtype_bytes))


def fused_vmem_bytes(op: str, rows: int, n: int, k: int,
                     dtype_bytes: int = 2) -> int:
    """VMEM scratch of the fused kernel for GEMM×collective ``op`` holding
    ``rows`` rows per device (the x shard for AG, the output block for
    RS/AR), local n (AG) or local k (RS/AR)."""
    if op == "all_gather_matmul":
        # double-buffered x shards + resident w + one output shard
        return (2 * rows * k + k * n + rows * n) * dtype_bytes
    # acc / partial / landing-copy f32 blocks + x block + resident w
    return 3 * rows * n * 4 + (rows * k + k * n) * dtype_bytes


def fused_fits(op: str, m: int, n: int, k: int, n_dev: int, *,
               dtype_bytes: int, budget: float) -> bool:
    """Does the chip's compiler accept the fused kernel for ``op`` at the
    dispatch coordinates (m, n, k) over ``n_dev`` devices, with its VMEM
    scratch inside ``budget``? RS/AR slice the operand in row blocks, which
    must be tile-aligned; AG moves whole shards."""
    if m % n_dev:
        return False
    rows = m // n_dev
    if op != "all_gather_matmul" and rows % _rs_align(dtype_bytes):
        return False
    return fused_vmem_bytes(op, rows, n, k, dtype_bytes) <= budget


def vmem_limit(scratch_bytes: int) -> int:
    """``vmem_limit_bytes`` for a kernel holding ``scratch_bytes`` of
    declared scratch: the default limit unless the scratch plus Mosaic's
    own needs exceed it."""
    return max(DEFAULT_VMEM_LIMIT, scratch_bytes + _VMEM_HEADROOM)


# ---------------------------------------------------------------------------
# Fused all-gather × GEMM (paper Fig. 7)
# ---------------------------------------------------------------------------

def _ag_mm_kernel(x_ref, w_ref, out_ref, buf, w_v, y_v, send_sem, recv_sem,
                  cap_sem, copy_sem, *, axis_name: str, n_dev: int,
                  n_chunks: int, m_chunk: int):
    my = lax.axis_index(axis_name)
    right = lax.rem(my + 1, jnp.int32(n_dev))
    left = lax.rem(my + n_dev - 1, jnp.int32(n_dev))
    pk_neighbor_barrier(axis_name)

    cp_x = pltpu.make_async_copy(x_ref, buf.at[0], copy_sem)
    cp_x.start()
    cp_w = pltpu.make_async_copy(w_ref, w_v, copy_sem)
    cp_w.start()
    cp_x.wait()
    cp_w.wait()

    def step(i, _):
        cur = lax.rem(i, 2)
        nxt = lax.rem(i + 1, 2)

        @pl.when(jnp.logical_and(i < n_dev - 1, i >= 2))
        def _reuse_ack():           # right must have consumed slot `nxt`
            pk_wait(cap_sem.at[nxt], 1)

        # Chunk pipeline: chunk c of the next shard goes on the wire, THEN
        # the MXU computes chunk c of the current shard — each chunk's DMA
        # is issued ahead of the chunk GEMM it overlaps.
        for c in range(n_chunks):
            rows = pl.dslice(c * m_chunk, m_chunk)

            @pl.when(i < n_dev - 1)
            def _send(rows=rows, c=c):
                pk_store_async(buf.at[cur].at[rows], buf.at[nxt].at[rows],
                               send_sem.at[i, c], recv_sem.at[i, c], right)

            sl = slice(c * m_chunk, (c + 1) * m_chunk)
            y_v[sl] = jax.lax.dot(buf.at[cur][sl], w_v[...],
                                  preferred_element_type=jnp.float32
                                  ).astype(y_v.dtype)

        src = lax.rem(my - i + n_dev, jnp.int32(n_dev))
        st = pltpu.make_async_copy(y_v, out_ref.at[src], copy_sem)
        st.start()
        st.wait()

        @pl.when(i < n_dev - 1)
        def _wait():
            # recreate the matching descriptors to wait send+recv of hop i
            for c in range(n_chunks):
                rows = pl.dslice(c * m_chunk, m_chunk)
                pltpu.make_async_remote_copy(
                    src_ref=buf.at[cur].at[rows], dst_ref=buf.at[nxt].at[rows],
                    send_sem=send_sem.at[i, c], recv_sem=recv_sem.at[i, c],
                    device_id=(right,),
                    device_id_type=pltpu.DeviceIdType.MESH).wait()

        @pl.when(jnp.logical_and(i >= 1, i <= n_dev - 3))
        def _consumed():            # buf[cur] free (dot done + send done)
            pk_signal(cap_sem.at[cur], left)
        return 0

    lax.fori_loop(0, n_dev, step, 0)


def ag_matmul_fused(x, w, axis_name: str, *, n_chunks: int = 1,
                    interpret: bool | None = None):
    """x: (m_loc, k) row shard; w: (k, n) local weight. Returns
    (n_dev*m_loc, n) — all-gather fused into the GEMM. Call inside shard_map.
    ``n_chunks`` splits each hop into row sub-chunks (largest-divisor
    ``fit_chunks`` fallback); bit-identical to the 1-chunk schedule.
    Whole-operand VMEM residency: sized for benchmark/validation shapes; the
    production path tiles K via kernels/matmul.py blocking (DESIGN §5)."""
    n_dev = compat.axis_size(axis_name)
    m_loc, k = x.shape
    n = w.shape[1]
    n_chunks = fit_chunks(m_loc, n_chunks, align=row_tile(x.dtype.itemsize))
    m_chunk = m_loc // n_chunks
    vmem = fused_vmem_bytes("all_gather_matmul", m_loc, n, k,
                            x.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_ag_mm_kernel, axis_name=axis_name, n_dev=n_dev,
                          n_chunks=n_chunks, m_chunk=m_chunk),
        in_specs=[pl.BlockSpec(memory_space=compat.ANY),
                  pl.BlockSpec(memory_space=compat.ANY)],
        out_specs=pl.BlockSpec(memory_space=compat.ANY),
        out_shape=jax.ShapeDtypeStruct((n_dev, m_loc, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((2, m_loc, k), x.dtype),
                        pltpu.VMEM((k, n), w.dtype),
                        pltpu.VMEM((m_loc, n), x.dtype),
                        pltpu.SemaphoreType.DMA((n_dev - 1, n_chunks)),
                        pltpu.SemaphoreType.DMA((n_dev - 1, n_chunks)),
                        pltpu.SemaphoreType.REGULAR((2,)),
                        pltpu.SemaphoreType.DMA],
        compiler_params=compat.CompilerParams(
            collective_id=collective_id("ag_matmul_fused"),
            vmem_limit_bytes=vmem_limit(vmem)),
        interpret=compat.kernel_interpret(interpret),
    )(x, w)


# ---------------------------------------------------------------------------
# Fused GEMM × reduce-scatter (paper Fig. 8 / Table 3)
# ---------------------------------------------------------------------------

def _rs_ring(x_ref, landing, acc_v, p_v, l_v, x_v, w_v, send_sem, recv_sem,
             cap_sem, copy_sem, *, axis_name: str, n_dev: int, m_blk: int,
             n_chunks: int, m_chunk: int):
    """The accumulate-and-forward GEMM×RS ring, shared by the RS and AR
    kernels. On return ``acc_v`` holds this device's fully reduced block."""
    my = lax.axis_index(axis_name)
    left = lax.rem(my + n_dev - 1, jnp.int32(n_dev))
    right = lax.rem(my + 1, jnp.int32(n_dev))

    def load_block(b):
        cp = pltpu.make_async_copy(x_ref.at[pl.dslice(b * m_blk, m_blk)],
                                   x_v, copy_sem)
        cp.start()
        cp.wait()

    # step 0: acc = my partial for block (my+1)
    load_block(lax.rem(my + 1, jnp.int32(n_dev)))
    acc_v[...] = jax.lax.dot(x_v[...], w_v[...],
                             preferred_element_type=jnp.float32)

    def step(i, _):
        slot = lax.rem(i, 2)

        @pl.when(i >= 3)
        def _reuse_ack():
            pk_wait(cap_sem.at[slot], 1)

        def send_chunk(c):
            # one-way, into the left neighbor's pre-allocated landing slot
            rows = pl.dslice(c * m_chunk, m_chunk)
            return pk_store_async(acc_v.at[rows], landing.at[slot].at[rows],
                                  send_sem.at[i - 1, c],
                                  recv_sem.at[i - 1, c], left)

        # Chunk 0 of the accumulator is on the wire before anything else;
        # the x-block HBM read and every chunk GEMM then overlap the
        # remaining chunk transfers — chunk c+1's DMA is issued ahead of
        # chunk c's dot. The paper's hiding condition K >= s*R/(2*B)
        # decides if the dots fully cover the transfers
        # (costmodel.hiding_threshold_k).
        rdmas = [send_chunk(0)]
        load_block(lax.rem(my + 1 + i, jnp.int32(n_dev)))
        for c in range(n_chunks):
            if c + 1 < n_chunks:
                rdmas.append(send_chunk(c + 1))
            sl = slice(c * m_chunk, (c + 1) * m_chunk)
            p_v[sl] = jax.lax.dot(x_v[sl], w_v[...],
                                  preferred_element_type=jnp.float32)
        for r in rdmas:
            r.wait()
        cp_l = pltpu.make_async_copy(landing.at[slot], l_v, copy_sem)
        cp_l.start()
        cp_l.wait()
        acc_v[...] = p_v[...] + l_v[...]

        @pl.when(i <= n_dev - 3)
        def _consumed():
            pk_signal(cap_sem.at[slot], right)
        return 0

    lax.fori_loop(1, n_dev, step, 0)


def _mm_rs_kernel(x_ref, w_ref, out_ref, landing, acc_v, p_v, l_v, x_v, w_v,
                  send_sem, recv_sem, cap_sem, copy_sem, *,
                  axis_name: str, n_dev: int, m_blk: int, n_chunks: int,
                  m_chunk: int):
    pk_neighbor_barrier(axis_name)
    cp_w = pltpu.make_async_copy(w_ref, w_v, copy_sem)
    cp_w.start()
    cp_w.wait()
    _rs_ring(x_ref, landing, acc_v, p_v, l_v, x_v, w_v, send_sem, recv_sem,
             cap_sem, copy_sem, axis_name=axis_name, n_dev=n_dev, m_blk=m_blk,
             n_chunks=n_chunks, m_chunk=m_chunk)
    st = pltpu.make_async_copy(acc_v, out_ref, copy_sem)
    st.start()
    st.wait()


def matmul_rs_fused(x, w, axis_name: str, *, n_chunks: int = 1,
                    interpret: bool | None = None):
    """x: (m, k_loc); w: (k_loc, n) (K sharded over the axis). Returns the
    reduce-scattered (m/n_dev, n) fp32 shard. Call inside shard_map.
    ``n_chunks`` splits each hop's accumulator payload into row sub-chunks
    (``fit_chunks`` fallback); bit-identical to the 1-chunk schedule."""
    n_dev = compat.axis_size(axis_name)
    m, k_loc = x.shape
    n = w.shape[1]
    assert m % n_dev == 0
    m_blk = m // n_dev
    n_chunks = fit_chunks(m_blk, n_chunks, align=_rs_align(x.dtype.itemsize))
    m_chunk = m_blk // n_chunks
    vmem = fused_vmem_bytes("matmul_reduce_scatter", m_blk, n, k_loc, x.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_mm_rs_kernel, axis_name=axis_name, n_dev=n_dev,
                          m_blk=m_blk, n_chunks=n_chunks, m_chunk=m_chunk),
        in_specs=[pl.BlockSpec(memory_space=compat.ANY),
                  pl.BlockSpec(memory_space=compat.ANY)],
        # the landing double buffer is a second (discarded) output: remote
        # DMAs need an HBM destination, and Mosaic scratch is VMEM/SMEM only
        out_specs=(pl.BlockSpec(memory_space=compat.ANY),
                   pl.BlockSpec(memory_space=compat.ANY)),
        out_shape=(jax.ShapeDtypeStruct((m_blk, n), jnp.float32),
                   jax.ShapeDtypeStruct((2, m_blk, n), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((m_blk, n), jnp.float32),
                        pltpu.VMEM((m_blk, n), jnp.float32),
                        pltpu.VMEM((m_blk, n), jnp.float32),
                        pltpu.VMEM((m_blk, k_loc), x.dtype),
                        pltpu.VMEM((k_loc, n), w.dtype),
                        pltpu.SemaphoreType.DMA((n_dev - 1, n_chunks)),
                        pltpu.SemaphoreType.DMA((n_dev - 1, n_chunks)),
                        pltpu.SemaphoreType.REGULAR((2,)),
                        pltpu.SemaphoreType.DMA],
        compiler_params=compat.CompilerParams(
            collective_id=collective_id("matmul_rs_fused"),
            vmem_limit_bytes=vmem_limit(vmem)),
        interpret=compat.kernel_interpret(interpret),
    )(x, w)[0]


# ---------------------------------------------------------------------------
# Fused GEMM × all-reduce — the §3.1.3 re-derivation (AR = RS ∘ AG on the
# same ring), still ONE kernel: the reduce-scatter ring above, then a
# chunked all-gather of the reduced blocks without leaving the kernel.
# ---------------------------------------------------------------------------

def _mm_ar_kernel(x_ref, w_ref, out_ref, landing, acc_v, p_v, l_v, x_v, w_v,
                  send_sem, recv_sem, ag_send, ag_recv, cap_sem, copy_sem, *,
                  axis_name: str, n_dev: int, m_blk: int, n_chunks: int,
                  m_chunk: int):
    my = lax.axis_index(axis_name)
    right = lax.rem(my + 1, jnp.int32(n_dev))
    pk_neighbor_barrier(axis_name)
    cp_w = pltpu.make_async_copy(w_ref, w_v, copy_sem)
    cp_w.start()
    cp_w.wait()
    _rs_ring(x_ref, landing, acc_v, p_v, l_v, x_v, w_v, send_sem, recv_sem,
             cap_sem, copy_sem, axis_name=axis_name, n_dev=n_dev, m_blk=m_blk,
             n_chunks=n_chunks, m_chunk=m_chunk)

    # publish my reduced block into my PGL slot, then ring-gather the rest —
    # same hop/chunk discipline as _ag_kernel, no rendezvous: out_ref slots
    # are pre-allocated kernel outputs, live since the opening barrier.
    st = pltpu.make_async_copy(acc_v, out_ref.at[my], copy_sem)
    st.start()
    st.wait()

    def ag_hop(j, _):
        # forward the reduced block received j hops ago (origin my - j)
        slot = lax.rem(my - j + n_dev, jnp.int32(n_dev))
        rdmas = pk_store_chunked(out_ref.at[slot], out_ref.at[slot],
                                 ag_send.at[j], ag_recv.at[j], right,
                                 n_chunks=n_chunks, chunk_rows=m_chunk)
        for r in rdmas:
            r.wait()
        return 0

    lax.fori_loop(0, n_dev - 1, ag_hop, 0)


def matmul_ar_fused(x, w, axis_name: str, *, n_chunks: int = 1,
                    interpret: bool | None = None):
    """x: (m, k_loc); w: (k_loc, n) (K sharded over the axis). Returns the
    all-reduced (n_dev, m/n_dev, n) fp32 blocks (reshape to (m, n) outside).
    Call inside shard_map. One kernel end to end: the GEMM×RS ring followed
    by an in-kernel chunked all-gather of the reduced blocks — the trailing
    gather's hops reuse the chunk pipeline, so no second launch and no bulk
    re-entry into XLA. Bit-identical to the 1-chunk schedule."""
    n_dev = compat.axis_size(axis_name)
    m, k_loc = x.shape
    n = w.shape[1]
    assert m % n_dev == 0
    m_blk = m // n_dev
    n_chunks = fit_chunks(m_blk, n_chunks, align=_rs_align(x.dtype.itemsize))
    m_chunk = m_blk // n_chunks
    vmem = fused_vmem_bytes("matmul_all_reduce", m_blk, n, k_loc, x.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_mm_ar_kernel, axis_name=axis_name, n_dev=n_dev,
                          m_blk=m_blk, n_chunks=n_chunks, m_chunk=m_chunk),
        in_specs=[pl.BlockSpec(memory_space=compat.ANY),
                  pl.BlockSpec(memory_space=compat.ANY)],
        # the landing double buffer is a second (discarded) output: remote
        # DMAs need an HBM destination, and Mosaic scratch is VMEM/SMEM only
        out_specs=(pl.BlockSpec(memory_space=compat.ANY),
                   pl.BlockSpec(memory_space=compat.ANY)),
        out_shape=(jax.ShapeDtypeStruct((n_dev, m_blk, n), jnp.float32),
                   jax.ShapeDtypeStruct((2, m_blk, n), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((m_blk, n), jnp.float32),
                        pltpu.VMEM((m_blk, n), jnp.float32),
                        pltpu.VMEM((m_blk, n), jnp.float32),
                        pltpu.VMEM((m_blk, k_loc), x.dtype),
                        pltpu.VMEM((k_loc, n), w.dtype),
                        pltpu.SemaphoreType.DMA((n_dev - 1, n_chunks)),
                        pltpu.SemaphoreType.DMA((n_dev - 1, n_chunks)),
                        pltpu.SemaphoreType.DMA((n_dev - 1, n_chunks)),
                        pltpu.SemaphoreType.DMA((n_dev - 1, n_chunks)),
                        pltpu.SemaphoreType.REGULAR((2,)),
                        pltpu.SemaphoreType.DMA],
        compiler_params=compat.CompilerParams(
            collective_id=collective_id("matmul_ar_fused"),
            vmem_limit_bytes=vmem_limit(vmem)),
        interpret=compat.kernel_interpret(interpret),
    )(x, w)[0]
