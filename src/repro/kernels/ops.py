"""jit'd public wrappers for the Pallas kernels: padding to MXU-aligned
tiles, GQA head handling, interpret-mode dispatch (CPU validation vs TPU
target), and the composed collectives (all_reduce = RS ∘ AG).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import compat
from repro.kernels.collective_matmul import (ag_matmul_fused, matmul_ar_fused,
                                             matmul_rs_fused)
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.grouped_matmul import grouped_matmul as _gmm
from repro.kernels.mamba_scan import mamba_scan as _mscan
from repro.kernels.matmul import matmul as _mm
from repro.kernels.pk_comm import (p2p_ring_shift, ring_all_gather,
                                   ring_reduce_scatter)


def _pad_to(x, mult: int, axis: int):
    s = x.shape[axis]
    pad = (-s) % mult
    if pad == 0:
        return x, s
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), s


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul(x, w, *, bm=128, bn=128, bk=128, interpret=None):
    interpret = compat.default_interpret() if interpret is None else interpret
    x, m0 = _pad_to(x, bm, 0)
    x, _ = _pad_to(x, bk, 1)
    w, _ = _pad_to(w, bk, 0)
    w, n0 = _pad_to(w, bn, 1)
    out = _mm(x, w, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m0, :n0]


@functools.partial(jax.jit, static_argnames=("causal", "window", "blq",
                                             "blk", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, blq=128, blk=128,
                    interpret=None):
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D). GQA: kv heads repeated."""
    interpret = compat.default_interpret() if interpret is None else interpret
    hq, hkv = q.shape[1], k.shape[1]
    if hq != hkv:
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
    d0 = q.shape[-1]
    scale = d0 ** -0.5
    q, s0 = _pad_to(q, blq, 2)
    k, _ = _pad_to(k, blk, 2)
    v, _ = _pad_to(v, blk, 2)
    # kv padding correctness: padded cols are masked by causality for every
    # real q row (col > row); non-causal callers must pass aligned S.
    assert causal or k.shape[2] == s0, "non-causal needs blk-aligned S"
    q, _ = _pad_to(q, 128, 3)
    k, _ = _pad_to(k, 128, 3)
    v, _ = _pad_to(v, 128, 3)
    out = _flash(q, k, v, causal=causal, window=window, scale=scale,
                 blq=blq, blk=blk, interpret=interpret)
    return out[:, :, :s0, :d0]


@functools.partial(jax.jit, static_argnames=("bc", "bf", "bk", "interpret"))
def grouped_matmul(x, w, *, bc=128, bf=128, bk=128, interpret=None):
    interpret = compat.default_interpret() if interpret is None else interpret
    x, c0 = _pad_to(x, bc, 1)
    x, _ = _pad_to(x, bk, 2)
    w, _ = _pad_to(w, bk, 1)
    w, f0 = _pad_to(w, bf, 2)
    out = _gmm(x, w, bc=bc, bf=bf, bk=bk, interpret=interpret)
    return out[:, :c0, :f0]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba_scan(dt, b_ssm, c_ssm, x, a, h0, *, chunk=128, interpret=None):
    interpret = compat.default_interpret() if interpret is None else interpret
    return _mscan(dt, b_ssm, c_ssm, x, a, h0, chunk=chunk,
                  interpret=interpret)


# --- PK collectives (call inside shard_map) ---
#
# ``n_chunks`` on the GEMM×collectives is the ChunkSchedule seam: the count
# resolved by ``CommContext.gemm_chunk_schedule`` (explicit > RunConfig >
# measured table > analytic fused cost term) lands here and is fitted to the
# payload rows by the kernel wrappers (``fit_chunks`` — never a constraint).
# ``interpret=None`` resolves in the kernels (``compat.kernel_interpret``).

def pk_all_gather(x, axis_name, *, n_chunks=1, interpret=None):
    return ring_all_gather(x, axis_name, n_chunks=n_chunks,
                           interpret=interpret)


def pk_reduce_scatter(x, axis_name, *, n_chunks=1, interpret=None):
    return ring_reduce_scatter(x, axis_name, n_chunks=n_chunks,
                               interpret=interpret)


def pk_all_reduce(x, axis_name, *, n_chunks=1, interpret=None):
    """all_reduce = reduce_scatter ∘ all_gather (no in-network reduction on
    ICI — DESIGN §2.1; same 2(N-1)/N per-device traffic as switch-offload)."""
    n = compat.axis_size(axis_name)
    blk, rem = divmod(x.shape[0], n)
    if rem != 0:  # pad leading dim to a multiple of n
        x = jnp.pad(x, [(0, n - rem)] + [(0, 0)] * (x.ndim - 1))
        blk = x.shape[0] // n
    parts = x.reshape(n, blk, *x.shape[1:])
    rs = pk_reduce_scatter(parts, axis_name, n_chunks=n_chunks,
                           interpret=interpret)
    ag = pk_all_gather(rs, axis_name, n_chunks=n_chunks, interpret=interpret)
    out = ag.reshape(n * blk, *x.shape[1:])
    return out[:x.shape[0] - (n - rem if rem else 0)] if rem else out


def pk_ring_shift(x, axis_name, *, interpret=None):
    return p2p_ring_shift(x, axis_name, interpret=interpret)


def pk_ag_matmul(x, w, axis_name, *, n_chunks=1, interpret=None):
    out = ag_matmul_fused(x, w, axis_name, n_chunks=n_chunks,
                          interpret=interpret)
    return out.reshape(-1, w.shape[1])


def pk_matmul_rs(x, w, axis_name, *, n_chunks=1, interpret=None):
    return matmul_rs_fused(x, w, axis_name, n_chunks=n_chunks,
                           interpret=interpret)


def pk_matmul_ar(x, w, axis_name, *, n_chunks=1, interpret=None):
    """Fused GEMM×all-reduce: one kernel (RS ring + in-kernel gather of the
    reduced blocks). Returns (m, n) fp32."""
    out = matmul_ar_fused(x, w, axis_name, n_chunks=n_chunks,
                          interpret=interpret)
    return out.reshape(-1, w.shape[1])
