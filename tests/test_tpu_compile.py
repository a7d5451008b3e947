"""Compile the fused PK GEMM x collective kernels for a described TPU v5e
2x2 (no chip needed), at tinyllama-1.1b's tensor-parallel MLP and
attention-out widths over a 4-device mesh; and the slab decode step for one
described chip, to show that it updates its donated cache in place.

Interpret mode accepts kernels the chip's compiler refuses (row slices off
the dtype's tiling, HBM scratch, too much VMEM). These tests run Mosaic
itself, so every shape at which dispatch may pick the ``fused`` backend
(``collective_matmul.fused_fits``) is shown to compile, and the shapes it
refuses are shown to be refused for a reason.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import compat
from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.core import costmodel as cm
from repro.core.schedule import choose_gemm_chunks
from repro.kernels import collective_matmul as cmm
from repro.models import transformer as T
from repro.train.step import make_serve_step

N_DEV = 4
CFG = get_config("tinyllama-1.1b")
D, F, H = CFG.d_model, CFG.d_ff, CFG.n_heads * CFG.hd
HW = cm.TPU_V5E
KIND = {"all_gather_matmul": "all_gather",
        "matmul_reduce_scatter": "reduce_scatter",
        "matmul_all_reduce": "all_reduce"}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a described chip's executables are written to the persistent cache
    # but can never be read back here: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices[:N_DEV]), ("x",))


def _coords(op: str, m: int, k_glob: int, n_glob: int):
    """Dispatch coordinates (m, n, k) of ``op`` on the 4-device axis: AG
    shards the output columns, RS/AR the contraction."""
    if op == "all_gather_matmul":
        return m, n_glob // N_DEV, k_glob
    return m, n_glob, k_glob // N_DEV


def _fits(op, m, k_glob, n_glob):
    m, n, k = _coords(op, m, k_glob, n_glob)
    return cmm.fused_fits(op, m, n, k, N_DEV, dtype_bytes=2,
                          budget=HW.vmem_bytes)


def _compile(mesh, op, m, k_glob, n_glob):
    """Lower + compile the fused kernel for ``op`` at global GEMM (m, k) x
    (k, n), with the chunk count the analytic fused schedule requests."""
    mm, n, k = _coords(op, m, k_glob, n_glob)
    n_chunks = choose_gemm_chunks(mm, n, k, axis_size=N_DEV, kind=KIND[op],
                                  dtype_bytes=2, hw=HW, fused=True).n_chunks
    if op == "all_gather_matmul":
        specs = (P("x", None), P(None, "x"), P(None, "x"))

        def fn(x, w):
            return cmm.ag_matmul_fused(x, w, "x", n_chunks=n_chunks,
                                       interpret=False).reshape(-1, w.shape[1])
    elif op == "matmul_reduce_scatter":
        specs = (P(None, "x"), P("x", None), P("x", None))

        def fn(x, w):
            return cmm.matmul_rs_fused(x, w, "x", n_chunks=n_chunks,
                                       interpret=False)
    else:
        specs = (P(None, "x"), P("x", None), P("x"))

        def fn(x, w):
            out = cmm.matmul_ar_fused(x, w, "x", n_chunks=n_chunks,
                                      interpret=False)
            return out.reshape(-1, w.shape[1])[None]
    f = jax.jit(compat.shard_map(fn, mesh=mesh, in_specs=specs[:2],
                                 out_specs=specs[2], check_vma=False))
    x = jax.ShapeDtypeStruct((m, k_glob), jnp.bfloat16,
                             sharding=NamedSharding(mesh, specs[0]))
    w = jax.ShapeDtypeStruct((k_glob, n_glob), jnp.bfloat16,
                             sharding=NamedSharding(mesh, specs[1]))
    return f.lower(x, w).compile()


# (op, m, global k, global n): a decode-shaped and a prefill-shaped case of
# each kernel, at the analytic fused chunk count for the shape (2 chunks at
# m=64 and m=128, 8 at m=512 — 16-row chunks in every case). RS/AR take the
# smallest decode batch whose row block is tile-aligned (16 bf16 rows per
# device). Compile time grows with the chunk count (each chunk is its own
# unrolled dot), so one 8-chunk case stands for the rest.
COMPILES = [
    ("all_gather_matmul", 8, D, F),           # MLP in, decode batch 8
    ("all_gather_matmul", 128, D, F),         # MLP in, one 128-token prefill
    ("matmul_reduce_scatter", 64, F, D),      # MLP out, decode batch 64
    ("matmul_reduce_scatter", 128, F, D),     # MLP out, one 128-token prefill
    ("matmul_all_reduce", 64, F, D),          # MLP out, decode batch 64
    ("matmul_all_reduce", 512, F, D),         # MLP out, prefill 4 x 128
    ("matmul_all_reduce", 128, H, D),         # attention out, 128-token prefill
]


@pytest.mark.parametrize("op,m,k,n", COMPILES,
                         ids=[f"{c[0]}-m{c[1]}-k{c[2]}" for c in COMPILES])
def test_fused_kernel_compiles_for_v5e(mesh, op, m, k, n):
    assert _fits(op, m, k, n), "dispatch would not send this shape to fused"
    compiled = _compile(mesh, op, m, k, n)
    assert "tpu_custom_call" in compiled.as_text()


# Shapes dispatch refuses: decode batch 8 leaves RS/AR a 2-row block (off
# the 16-row bf16 tiling), and MLP-out prefill at 2048 rows needs ~19.8 MB
# of VMEM scratch (three f32 accumulators + x block + weight) > 16 MiB.
REFUSED = [
    ("matmul_reduce_scatter", 8, F, D),
    ("matmul_all_reduce", 8, F, D),
    ("matmul_all_reduce", 2048, F, D),
]


@pytest.mark.parametrize("op,m,k,n", REFUSED,
                         ids=[f"{c[0]}-m{c[1]}-k{c[2]}" for c in REFUSED])
def test_fused_refused_where_it_cannot_run(op, m, k, n):
    assert not _fits(op, m, k, n)


def test_unaligned_row_block_is_refused_by_the_compiler(mesh):
    """Why the alignment guard exists: Mosaic refuses the RS ring's 2-row
    block at decode batch 8 (interpret mode would have run it)."""
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(mesh, "matmul_all_reduce", 8, F, D)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_decode_updates_the_cache_in_place(topo, kv_dtype):
    """The slab decode step, its cache donated as the serving engine
    donates it, compiles for one chip to a program that writes each cache
    leaf where it lies: every leaf is aliased input to output, and the
    temporaries stay below one layer's K slab. Threading the slabs through
    the layer scan as its outputs made XLA copy every stacked slab once a
    step. Four layers, 32 slots of 16384 positions: slabs too large for the
    chip's fast memory, where such copies would not count as temporaries."""
    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              n_layers=4)
    run = RunConfig(dp_axes=("data",), fsdp=False)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def abstract(tmpl):
        return jax.tree.map(
            lambda pd: jax.ShapeDtypeStruct(pd.shape, pd.dtype,
                                            sharding=one_chip),
            tmpl, is_leaf=lambda x: isinstance(x, T.PD))

    b = 32
    params = abstract(T.param_template(cfg, run, None))
    cache = abstract(T.cache_template(cfg, run, None, batch=b, s_max=16384,
                                      slot_pos=True, kv_dtype=kv_dtype))
    tokens = jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(make_serve_step(cfg, run, None),
                       donate_argnums=(1,)).lower(params, cache,
                                                  tokens).compile()
    first = len(jax.tree.leaves(params))       # the cache's first parameter
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)",
        compiled.as_text())}
    assert set(range(first, first + len(jax.tree.leaves(cache)))) <= aliased
    k = cache["blocks"]["pos0"]["k"]
    layer_k = k.size // k.shape[0] * k.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < layer_k
