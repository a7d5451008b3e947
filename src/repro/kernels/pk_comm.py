"""ParallelKittens primitives on TPU (paper §3.2.2) + pure-communication ring
kernels built from them (paper Fig. 6/15/16 workloads).

The eight primitives, mapped per DESIGN.md §2:

  GPU (paper)            TPU (here)
  store_async        ->  pk_store_async      (make_async_remote_copy)
  store_add_async    ->  pk_store_async + accumulate-on-arrival (no remote
                         atomics over ICI; the receiver adds — see
                         ring_reduce_scatter)
  reduce             ->  accumulate-on-arrival ring step (no in-network
                         reduction on ICI; DESIGN §2.1)
  all_reduce         ->  composed reduce_scatter + all_gather (ops.py)
  signal             ->  pk_signal           (semaphore_signal w/ device_id)
  signal_all         ->  loop of pk_signal (no multicast fabric)
  wait               ->  pk_wait             (semaphore_wait)
  barrier            ->  pk_neighbor_barrier / pk_global_barrier

Design-overhead principles carried over (paper §3.1.4): destination buffers
are pre-allocated kernel outputs/scratch (PGL slots) — transfers are one-way,
there is no staging copy and no sender/receiver rendezvous beyond the initial
barrier; completion is a DMA-semaphore count, not a two-way handshake.

All kernels run under shard_map and are validated cross-device in TPU
interpret mode (pltpu.InterpretParams), which emulates per-device semaphores
and remote DMAs faithfully on CPU.
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


# ---------------------------------------------------------------------------
# Primitives (used inside Pallas kernels)
# ---------------------------------------------------------------------------

def pk_store_async(src_ref, dst_ref, send_sem, recv_sem, dst_dev):
    """store_async(dst, src, coord): one-way async tile store into a peer's
    pre-allocated PGL slot. Returns the descriptor (call .wait_send()/.wait()).
    Single-issue (one scalar-core instruction), so compute overlaps freely —
    the TMA property the paper builds intra-SM overlap on."""
    rdma = pltpu.make_async_remote_copy(
        src_ref=src_ref, dst_ref=dst_ref, send_sem=send_sem,
        recv_sem=recv_sem, device_id=(dst_dev,),
        device_id_type=pltpu.DeviceIdType.MESH)
    rdma.start()
    return rdma


def pk_store_chunked(src_ref, dst_ref, send_sems, recv_sems, dst_dev, *,
                     n_chunks: int, chunk_rows: int):
    """``store_async`` at sub-chunk granularity: one one-way RDMA per row
    chunk of the payload, each ordered by its own (send, recv) pair from the
    supplied per-chunk semaphore rows (shape ``(n_chunks,)``). The chunk loop
    is static, so the scalar core issues every descriptor back-to-back and
    chunk c is on the wire before the consumer's chunk-c compute runs — the
    seam the fused kernels build their sub-shard overlap on. Still one-way:
    per-chunk semaphores extend the hop discipline, they do not add a
    rendezvous. Returns the descriptors (static list; ``.wait()`` each to
    block on send+recv completion)."""
    if n_chunks <= 1:
        return [pk_store_async(src_ref, dst_ref, send_sems.at[0],
                               recv_sems.at[0], dst_dev)]
    out = []
    for c in range(n_chunks):
        rows = pl.dslice(c * chunk_rows, chunk_rows)
        out.append(pk_store_async(src_ref.at[rows], dst_ref.at[rows],
                                  send_sems.at[c], recv_sems.at[c], dst_dev))
    return out


def pk_signal(sem, dst_dev, inc: int = 1):
    """signal(bar, coord, dev_idx, val)."""
    pltpu.semaphore_signal(sem, inc, device_id=(dst_dev,),
                           device_id_type=pltpu.DeviceIdType.MESH)


def pk_signal_all(sem, n_dev: int, inc: int = 1):
    """signal_all — no NVSwitch multicast on ICI: loop of unicasts."""
    for d in range(n_dev):
        pltpu.semaphore_signal(sem, inc, device_id=(jnp.int32(d),),
                               device_id_type=pltpu.DeviceIdType.MESH)


def pk_wait(sem, expected: int = 1):
    """wait(bar, coord, dev_idx, expected)."""
    pltpu.semaphore_wait(sem, expected)


def pk_neighbor_barrier(axis_name: str, sem=None):
    """barrier with both ring neighbors — required before the first RDMA of a
    ring schedule so landing buffers are live (paper's barrier primitive)."""
    n = compat.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    left = lax.rem(my + n - 1, jnp.int32(n))
    right = lax.rem(my + 1, jnp.int32(n))
    sem = pltpu.get_barrier_semaphore() if sem is None else sem
    pltpu.semaphore_signal(sem, 1, device_id=(left,),
                           device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_signal(sem, 1, device_id=(right,),
                           device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(sem, 2)


# ---------------------------------------------------------------------------
# Ring all-gather kernel (paper Fig. 15 workload)
# ---------------------------------------------------------------------------

def _ag_kernel(x_ref, out_ref, send_sem, recv_sem, copy_sem, *,
               axis_name: str, n_dev: int, n_chunks: int, chunk_rows: int):
    """Per-hop semaphores: a bare DMA-semaphore *count* only proves that SOME
    transfer landed, not the one this hop forwards — under out-of-order
    delivery that is a real data race (caught by InterpretParams
    detect_races). recv_sem[i, c] is signaled exclusively by the hop-i
    chunk-c transfer, so waiting on it orders the ring correctly with zero
    extra messages — the PK one-way-sync principle (paper §3.1.4) preserved
    at sub-chunk granularity."""
    my = lax.axis_index(axis_name)
    right = lax.rem(my + 1, jnp.int32(n_dev))
    pk_neighbor_barrier(axis_name)

    # local shard -> my PGL slot (pre-allocated destination, no staging)
    local = pltpu.make_async_copy(x_ref, out_ref.at[my], copy_sem)
    local.start()
    local.wait()

    def hop(i, _):
        # forward the shard received i hops ago (origin my - i)
        slot = lax.rem(my - i + n_dev, jnp.int32(n_dev))
        rdmas = pk_store_chunked(out_ref.at[slot], out_ref.at[slot],
                                 send_sem.at[i], recv_sem.at[i], right,
                                 n_chunks=n_chunks, chunk_rows=chunk_rows)
        for r in rdmas:
            r.wait()
        return 0

    lax.fori_loop(0, n_dev - 1, hop, 0)


def ring_all_gather(x, axis_name: str, *, n_chunks: int = 1,
                    interpret: bool | None = None):
    """x: (blk, ...) local shard -> (n_dev, blk, ...) full array, via one-way
    RDMA hops into pre-allocated slots. Call inside shard_map. ``n_chunks``
    splits each hop's payload into row sub-chunks (largest-divisor fallback
    via ``fit_chunks`` — never a shape constraint); results are bit-identical
    to the 1-chunk schedule."""
    n_dev = compat.axis_size(axis_name)
    n_chunks = (fit_chunks(x.shape[0], n_chunks,
                           align=row_tile(x.dtype.itemsize))
                if x.ndim else 1)
    chunk_rows = (x.shape[0] // n_chunks) if x.ndim else 0
    out_shape = jax.ShapeDtypeStruct((n_dev, *x.shape), x.dtype)
    return pl.pallas_call(
        functools.partial(_ag_kernel, axis_name=axis_name, n_dev=n_dev,
                          n_chunks=n_chunks, chunk_rows=chunk_rows),
        in_specs=[pl.BlockSpec(memory_space=compat.ANY)],
        out_specs=pl.BlockSpec(memory_space=compat.ANY),
        out_shape=out_shape,
        scratch_shapes=[pltpu.SemaphoreType.DMA((n_dev - 1, n_chunks)),
                        pltpu.SemaphoreType.DMA((n_dev - 1, n_chunks)),
                        pltpu.SemaphoreType.DMA],
        compiler_params=compat.CompilerParams(
            collective_id=collective_id("ring_all_gather")),
        interpret=compat.kernel_interpret(interpret),
    )(x)


# ---------------------------------------------------------------------------
# Ring reduce-scatter kernel — accumulate-on-arrival (the TPU re-derivation
# of in-network reduction; paper Fig. 16 workload + §3.1.3 GEMM+AR analysis)
# ---------------------------------------------------------------------------

def _rs_kernel(x_ref, out_ref, landing, acc_v, tmp_v, send_sem, recv_sem,
               cap_sem, copy_sem, *, axis_name: str, n_dev: int,
               n_chunks: int, chunk_rows: int):
    """Accumulate-and-forward ring. Two sync obligations, both one-way
    (paper §3.1.4 — no rendezvous):
      * per-hop (and per-chunk) recv semaphores order data arrival;
      * cap_sem[slot] is the consumer's ack that a landing slot was read —
        a fast sender may otherwise lap a slow receiver by two hops and
        overwrite an unconsumed slot (WAR hazard). The ack stays per-slot:
        the consumer reads the whole slot at once, so chunking the data
        path does not chunk the capacity ack."""
    my = lax.axis_index(axis_name)
    left = lax.rem(my + n_dev - 1, jnp.int32(n_dev))
    right = lax.rem(my + 1, jnp.int32(n_dev))
    pk_neighbor_barrier(axis_name)

    # acc = my partial for block (my+1)
    first = pltpu.make_async_copy(x_ref.at[lax.rem(my + 1, jnp.int32(n_dev))],
                                  acc_v, copy_sem)
    first.start()
    first.wait()

    def hop(i, _):
        slot = lax.rem(i, 2)
        # Reusing a slot (hop i shares it with hop i-2): wait for the
        # consumer's ack before overwriting.
        @pl.when(i >= 3)
        def _ack():
            pk_wait(cap_sem.at[slot], 1)
        # one-way send of the running accumulator to the left neighbor's
        # pre-allocated landing slot; per-hop/per-chunk semaphores order
        # the ring
        rdmas = pk_store_chunked(acc_v, landing.at[slot], send_sem.at[i - 1],
                                 recv_sem.at[i - 1], left,
                                 n_chunks=n_chunks, chunk_rows=chunk_rows)
        for r in rdmas:
            r.wait()
        # accumulate on arrival: landing + my partial for block (my+1+i)
        blk = lax.rem(my + 1 + i, jnp.int32(n_dev))
        cp_in = pltpu.make_async_copy(landing.at[slot], acc_v, copy_sem)
        cp_in.start()
        cp_l = pltpu.make_async_copy(x_ref.at[blk], tmp_v, copy_sem)
        cp_l.start()
        cp_in.wait()
        cp_l.wait()
        acc_v[...] = acc_v[...] + tmp_v[...]

        # landing[slot] consumed -> ack the producer (my right neighbor);
        # only when some future hop will actually reuse the slot, so all
        # semaphores drain to zero by kernel exit.
        @pl.when(i <= n_dev - 3)
        def _consumed():
            pk_signal(cap_sem.at[slot], right)
        return 0

    lax.fori_loop(1, n_dev, hop, 0, unroll=False)
    done = pltpu.make_async_copy(acc_v, out_ref, copy_sem)
    done.start()
    done.wait()


def ring_reduce_scatter(x, axis_name: str, *, n_chunks: int = 1,
                        interpret: bool | None = None):
    """x: (n_dev, blk, ...) per-destination partials -> (blk, ...) reduced
    shard for this device. Accumulate-and-forward ring; landing buffers are
    double-buffered PGL scratch slots (no staging copies). ``n_chunks``
    splits each hop's payload into row sub-chunks (``fit_chunks`` fallback);
    the accumulation order is untouched, so results stay bit-identical to
    the 1-chunk schedule."""
    n_dev = compat.axis_size(axis_name)
    blk_shape = x.shape[1:]
    n_chunks = (fit_chunks(blk_shape[0], n_chunks,
                           align=row_tile(x.dtype.itemsize))
                if blk_shape else 1)
    chunk_rows = (blk_shape[0] // n_chunks) if blk_shape else 0
    return pl.pallas_call(
        functools.partial(_rs_kernel, axis_name=axis_name, n_dev=n_dev,
                          n_chunks=n_chunks, chunk_rows=chunk_rows),
        in_specs=[pl.BlockSpec(memory_space=compat.ANY)],
        # the landing double buffer is a second (discarded) output: remote
        # DMAs need an HBM destination, and Mosaic scratch is VMEM/SMEM only
        out_specs=(pl.BlockSpec(memory_space=compat.ANY),
                   pl.BlockSpec(memory_space=compat.ANY)),
        out_shape=(jax.ShapeDtypeStruct(blk_shape, x.dtype),
                   jax.ShapeDtypeStruct((2, *blk_shape), x.dtype)),
        scratch_shapes=[pltpu.VMEM(blk_shape, x.dtype),
                        pltpu.VMEM(blk_shape, x.dtype),
                        pltpu.SemaphoreType.DMA((n_dev - 1, n_chunks)),
                        pltpu.SemaphoreType.DMA((n_dev - 1, n_chunks)),
                        pltpu.SemaphoreType.REGULAR((2,)),
                        pltpu.SemaphoreType.DMA],
        compiler_params=compat.CompilerParams(
            collective_id=collective_id("ring_reduce_scatter")),
        interpret=compat.kernel_interpret(interpret),
    )(x)[0]


# ---------------------------------------------------------------------------
# One-shot P2P (paper Fig. 2 microbenchmark granularity study)
# ---------------------------------------------------------------------------

def _p2p_kernel(x_ref, out_ref, send_sem, recv_sem, *, axis_name, n_dev):
    my = lax.axis_index(axis_name)
    right = lax.rem(my + 1, jnp.int32(n_dev))
    pk_neighbor_barrier(axis_name)
    rdma = pk_store_async(x_ref, out_ref, send_sem, recv_sem, right)
    rdma.wait()


def p2p_ring_shift(x, axis_name: str, *, interpret: bool | None = None):
    """Single-hop one-way RDMA (store_async) to the right neighbor."""
    n_dev = compat.axis_size(axis_name)
    return pl.pallas_call(
        functools.partial(_p2p_kernel, axis_name=axis_name, n_dev=n_dev),
        in_specs=[pl.BlockSpec(memory_space=compat.ANY)],
        out_specs=pl.BlockSpec(memory_space=compat.ANY),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        compiler_params=compat.CompilerParams(
            collective_id=collective_id("p2p_ring_shift")),
        interpret=compat.kernel_interpret(interpret),
    )(x)
