"""PK communication kernels: ring all-gather / reduce-scatter / p2p and the
fused collective-matmul kernels, cross-device in TPU interpret mode under
shard_map — including multi-seed runs with race detection (the interpreter
models out-of-order DMA delivery)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.collective_matmul import (ag_matmul_fused, matmul_ar_fused,
                                             matmul_rs_fused)
from repro.kernels.pk_comm import (p2p_ring_shift, ring_all_gather,
                                   ring_reduce_scatter)

N = 4


@pytest.fixture(scope="module")
def sm(mesh4):
    return partial(compat.shard_map, mesh=mesh4, check_vma=False)


def test_p2p_ring_shift(sm):
    x = jax.random.normal(jax.random.PRNGKey(0), (N, 8, 16), jnp.float32)
    f = jax.jit(sm(lambda x: p2p_ring_shift(x[0], "x")[None],
                   in_specs=P("x"), out_specs=P("x")))
    np.testing.assert_allclose(np.asarray(f(x)),
                               np.asarray(jnp.roll(x, 1, axis=0)))


def test_ring_all_gather(sm):
    x = jax.random.normal(jax.random.PRNGKey(0), (N, 8, 16), jnp.float32)
    f = jax.jit(sm(lambda x: ring_all_gather(x[0], "x")[None],
                   in_specs=P("x"), out_specs=P("x")))
    got = np.asarray(f(x))           # (dev, slot, 8, 16)
    for d in range(N):
        np.testing.assert_allclose(got[d], np.asarray(x))


def test_ring_reduce_scatter(sm):
    xg = jax.random.normal(jax.random.PRNGKey(0), (N, N, 8, 16), jnp.float32)
    f = jax.jit(sm(lambda x: ring_reduce_scatter(x[0], "x")[None],
                   in_specs=P("x"), out_specs=P("x")))
    np.testing.assert_allclose(np.asarray(f(xg)),
                               np.asarray(ref.reduce_scatter_ref(xg)),
                               rtol=1e-5, atol=1e-5)


def test_ag_matmul_fused(sm):
    m_loc, k, n_out = 16, 32, 24
    x = jax.random.normal(jax.random.PRNGKey(0), (N * m_loc, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n_out), jnp.float32)
    f = jax.jit(sm(
        lambda x, w: ag_matmul_fused(x, w, "x").reshape(N * m_loc, n_out)[None],
        in_specs=(P("x"), P()), out_specs=P("x")))
    got = np.asarray(f(x, w)).reshape(N, N * m_loc, n_out)
    want = np.asarray(ref.ag_matmul_ref(x, w))
    for d in range(N):
        np.testing.assert_allclose(got[d], want, rtol=1e-4, atol=1e-4)


def test_matmul_rs_fused(sm):
    m, k_loc, n_out = 16, 8, 24
    x = jax.random.normal(jax.random.PRNGKey(0), (m, N * k_loc), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (N * k_loc, n_out), jnp.float32)
    f = jax.jit(sm(lambda x, w: matmul_rs_fused(x, w, "x"),
                   in_specs=(P(None, "x"), P("x", None)),
                   out_specs=P("x", None)))
    np.testing.assert_allclose(np.asarray(f(x, w)),
                               np.asarray(ref.matmul_rs_ref(x, w)),
                               rtol=1e-4, atol=1e-4)


def test_matmul_ar_fused(sm):
    """Single-kernel GEMM×all-reduce (RS ring + in-kernel gather of the
    reduced blocks) matches the replicated matmul oracle on every device."""
    m, k_loc, n_out = 16, 8, 24
    x = jax.random.normal(jax.random.PRNGKey(0), (m, N * k_loc), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (N * k_loc, n_out), jnp.float32)
    f = jax.jit(sm(
        lambda x, w: matmul_ar_fused(x, w, "x").reshape(m, n_out)[None],
        in_specs=(P(None, "x"), P("x", None)), out_specs=P("x")))
    got = np.asarray(f(x, w))        # (dev, m, n_out): replica per device
    want = np.asarray(ref.matmul_ar_ref(x, w))
    for d in range(N):
        np.testing.assert_allclose(got[d], want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n_chunks", [1, 2])
def test_ring_all_gather_race_free(mesh4, seed, n_chunks):
    """Per-hop (and per-sub-chunk) semaphores must order the ring under
    randomized DMA delivery (this catches the count-only synchronization
    bug — see pk_comm.py)."""
    import functools
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels.pk_comm import _ag_kernel

    def ag(x):
        from repro.core.comms import collective_id
        return pl.pallas_call(
            functools.partial(_ag_kernel, axis_name="x", n_dev=N,
                              n_chunks=n_chunks,
                              chunk_rows=x.shape[0] // n_chunks),
            in_specs=[pl.BlockSpec(memory_space=compat.ANY)],
            out_specs=pl.BlockSpec(memory_space=compat.ANY),
            out_shape=jax.ShapeDtypeStruct((N, *x.shape), x.dtype),
            scratch_shapes=[pltpu.SemaphoreType.DMA((N - 1, n_chunks)),
                            pltpu.SemaphoreType.DMA((N - 1, n_chunks)),
                            pltpu.SemaphoreType.DMA],
            compiler_params=compat.CompilerParams(
                collective_id=collective_id("ring_all_gather")),
            interpret=compat.interpret_params(random_seed=seed,
                                              detect_races=True),
        )(x)

    x = jnp.arange(N, dtype=jnp.float32)[:, None, None] * jnp.ones((N, 4, 8))
    f = jax.jit(partial(compat.shard_map, mesh=mesh4, check_vma=False)(
        lambda x: ag(x[0])[None], in_specs=P("x"), out_specs=P("x")))
    got = np.asarray(f(x))
    for d in range(N):
        np.testing.assert_allclose(got[d, :, 0, 0], np.arange(N))


def test_lcsc_template_ring_all_gather(sm):
    """The LCSC template (paper §3.2.3) expressing ring AG in ~8 worker
    lines must match the hand-written kernel."""
    from repro.kernels.lcsc import lcsc_ring_all_gather
    x = jax.random.normal(jax.random.PRNGKey(5), (N, 8, 16), jnp.float32)
    f = jax.jit(sm(lambda x: lcsc_ring_all_gather(x[0], "x")[None],
                   in_specs=P("x"), out_specs=P("x")))
    got = np.asarray(f(x))
    for d in range(N):
        np.testing.assert_allclose(got[d], np.asarray(x))
