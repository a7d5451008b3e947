"""The LCSC program template (paper §3.2.3 / Appendix D), TPU form.

The paper structures every multi-GPU kernel as four specialized workers —
loader / consumer / storer / communicator — wired through semaphores. On TPU
the warpgroup specialization maps to issue streams of one core (DESIGN §2):

  loader        -> async local copies HBM->VMEM (pltpu.make_async_copy)
  consumer      -> MXU/VPU compute on VMEM refs
  storer        -> async copies VMEM->HBM (local or the output PGL slot)
  communicator  -> one-way ICI RDMA + semaphore signaling (pk_comm primitives)

`lcsc_kernel(...)` assembles the steady-state ring schedule the paper's
template automates: per step, the communicator *starts* the next transfer
first, the consumer computes on the current buffer while it flies, the storer
drains results, and the step closes on the per-hop DMA semaphores — the
intra-kernel overlap pattern of kernels/collective_matmul.py, factored out.

Each worker is a callback taking an `LCSCCtx`; users write only per-tile
logic, mirroring the paper's "<50 LOC of device code" claim.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.core.comms import collective_id

from repro.kernels.pk_comm import pk_neighbor_barrier, pk_store_async


@dataclasses.dataclass
class LCSCCtx:
    """Everything a worker callback may touch at step i."""
    step: Any                 # traced loop index
    n_dev: int
    my_id: Any
    left: Any
    right: Any
    in_refs: tuple            # kernel operand refs (ANY/HBM)
    out_ref: Any              # output ref (ANY/HBM)
    bufs: tuple               # VMEM scratch refs
    send_sem: Any             # per-hop DMA semaphore array
    recv_sem: Any
    copy_sem: Any

    def local_copy(self, src, dst):
        cp = pltpu.make_async_copy(src, dst, self.copy_sem)
        cp.start()
        cp.wait()

    def remote_store(self, src, dst):
        """communicator: one-way RDMA to the right neighbor, hop `step`.
        Returns the descriptor — the template waits it at step close."""
        return pk_store_async(src, dst, self.send_sem.at[self.step],
                              self.recv_sem.at[self.step], self.right)


def lcsc_kernel(*, n_steps_from_ndev: Callable[[int], int],
                communicator: Callable[[LCSCCtx], Any] | None,
                loader: Callable[[LCSCCtx], None] | None,
                consumer: Callable[[LCSCCtx], None] | None,
                storer: Callable[[LCSCCtx], None] | None,
                prologue: Callable[[LCSCCtx], None] | None = None):
    """Build a Pallas kernel body from LCSC worker callbacks."""

    def body(axis_name, n_dev, in_refs, out_ref, bufs, send_sem, recv_sem,
             copy_sem):
        my = lax.axis_index(axis_name)
        ctx = LCSCCtx(step=jnp.int32(0), n_dev=n_dev, my_id=my,
                      left=lax.rem(my + n_dev - 1, jnp.int32(n_dev)),
                      right=lax.rem(my + 1, jnp.int32(n_dev)),
                      in_refs=in_refs, out_ref=out_ref, bufs=bufs,
                      send_sem=send_sem, recv_sem=recv_sem,
                      copy_sem=copy_sem)
        pk_neighbor_barrier(axis_name)
        if prologue is not None:
            prologue(ctx)

        def step_fn(i, _):
            c = dataclasses.replace(ctx, step=i)
            rdma = communicator(c) if communicator is not None else None
            if loader is not None:
                loader(c)
            if consumer is not None:
                consumer(c)
            if storer is not None:
                storer(c)
            if rdma is not None:
                rdma.wait()          # close the hop on its own semaphores
            return 0

        lax.fori_loop(0, n_steps_from_ndev(n_dev), step_fn, 0)

    return body


# ---------------------------------------------------------------------------
# Demo: ring all-gather expressed on the template (8 lines of worker logic) —
# equivalent to kernels/pk_comm.ring_all_gather.
# ---------------------------------------------------------------------------

def lcsc_ring_all_gather(x, axis_name: str, *,
                         interpret: bool | None = None):
    n_dev = compat.axis_size(axis_name)

    def prologue(c):             # stage the local shard into my PGL slot
        c.local_copy(c.in_refs[0], c.out_ref.at[c.my_id])

    def communicator(c):         # forward the shard received `step` hops ago
        slot = lax.rem(c.my_id - c.step + n_dev, jnp.int32(n_dev))
        return c.remote_store(c.out_ref.at[slot], c.out_ref.at[slot])

    body = lcsc_kernel(n_steps_from_ndev=lambda n: n - 1,
                       communicator=communicator, loader=None, consumer=None,
                       storer=None, prologue=prologue)

    def kernel(x_ref, out_ref, send_sem, recv_sem, copy_sem):
        body(axis_name, n_dev, (x_ref,), out_ref, (), send_sem, recv_sem,
             copy_sem)

    return pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=compat.ANY)],
        out_specs=pl.BlockSpec(memory_space=compat.ANY),
        out_shape=jax.ShapeDtypeStruct((n_dev, *x.shape), x.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA((n_dev - 1,)),
                        pltpu.SemaphoreType.DMA((n_dev - 1,)),
                        pltpu.SemaphoreType.DMA],
        compiler_params=compat.CompilerParams(
            collective_id=collective_id("lcsc_ring_all_gather")),
        interpret=compat.kernel_interpret(interpret),
    )(x)
