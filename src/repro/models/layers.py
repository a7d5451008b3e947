"""Model layers: norms, RoPE, GQA attention (causal/SWA/cross/decode), MLP,
MoE wrapper, vocab-parallel embedding and loss.

Everything is functional: `fn(params_subtree, x, ...)`. Activation sharding
is maintained with with_sharding_constraint (XLA Auto skeleton); every
PK-overlapped path is declared as a ``repro.core.template.Island`` — the
paper's unified §3.2 template — switched by RunConfig (DESIGN.md §3). The
``*_island`` builders below are trace-free: constructing one costs nothing,
so ``island_plans()`` can report the whole forward pass's overlap schedule
(backend / chunks / hidden fraction per island) without running the model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, RunConfig
from repro.core import moe as pk_moe
from repro.core import pk_ring_attention, pk_ulysses_attention
from repro.core.autotune import island_key
from repro.core.quant import resolve_wire
from repro.core.template import (Comm, Gather, Island, IslandPlan,
                                 comm_context, island_override)
from repro.models.sharding import ShardingRules

NEG_INF = -1e30


def constrain(x, rules: ShardingRules | None, spec: P):
    if rules is None:
        return x
    return lax.with_sharding_constraint(x, rules.named(spec))


def _dtype_bytes(cfg: ArchConfig) -> int:
    return 2 if cfg.dtype == "bfloat16" else 4


def _wire_bytes(cfg: ArchConfig, run: RunConfig) -> int:
    """Element width a GEMM island's ``Comm`` declares. Under a quantized
    ``RunConfig.comm_wire`` this is the *wire* width (1 for int8) — the
    island key becomes ``...|b1``, so ``calibrate --per-island`` rows and
    measured dispatch both resolve at the width the ring actually ships —
    else the tensor dtype's own width."""
    fmt = resolve_wire(getattr(run, "comm_wire", None))
    return fmt.dtype_bytes if fmt is not None else _dtype_bytes(cfg)


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------

@jax.named_scope("norm")
def rms_norm(w, x, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def get_act(name: str):
    return {"silu": jax.nn.silu, "gelu": functools.partial(jax.nn.gelu, approximate=True),
            "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x, positions, theta: float):
    """x: (B, H, S, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)
    if positions.ndim == 1:
        ang = positions.astype(jnp.float32)[:, None] * inv[None, :]   # (S, hd/2)
        cos, sin = jnp.cos(ang)[None, None], jnp.sin(ang)[None, None]
    else:
        ang = positions.astype(jnp.float32)[..., None] * inv
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _full_attention(q, k, v, *, causal, window, scale=None):
    """q: (B,Hq,Sq,hd); k,v: (B,Hkv,Skv,hd). fp32 softmax, GQA grouped."""
    b, hq, sq, hd = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else hd ** -0.5
    qg = q.reshape(b, hkv, g, sq, hd)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    qi = jnp.arange(sq)[:, None]
    ki = jnp.arange(skv)[None, :]
    keep = jnp.ones((sq, skv), bool)
    if causal:
        keep &= ki <= qi
    if window is not None:
        keep &= ki > qi - window
    s = jnp.where(keep, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksd->bkgqd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, sq, hd).astype(q.dtype)


def _chunked_attention(q, k, v, *, causal, window, scale=None,
                       qc: int = 512, kc: int = 1024):
    """Memory-bounded XLA attention (flash-style online softmax over kv
    chunks inside a scan over q chunks) — the jnp twin of
    kernels/flash_attention.py, used when S·Skv would blow HBM (32k+ prefill).
    Fully-masked kv blocks are skipped via lax.cond (real compute savings,
    same as the kernel's block schedule)."""
    b, hq, s, hd = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else hd ** -0.5
    qc = min(qc, s)
    kc = min(kc, skv)
    assert s % qc == 0 and skv % kc == 0, (s, qc, skv, kc)
    nq, nk = s // qc, skv // kc
    qg = q.reshape(b, hkv, g, nq, qc, hd).transpose(3, 0, 1, 2, 4, 5)
    kb = k.reshape(b, hkv, nk, kc, hd)
    vb = v.reshape(b, hkv, nk, kc, hd)

    def one_q_block(args):
        qi, qblk = args                                  # (b,hkv,g,qc,hd)
        m0 = jnp.full((b, hkv, g, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, qc), jnp.float32)
        o0 = jnp.zeros((b, hkv, g, qc, hd), jnp.float32)

        def kv_step(carry, ki):
            m, l, o = carry
            k_i = lax.dynamic_index_in_dim(kb, ki, 2, keepdims=False)
            v_i = lax.dynamic_index_in_dim(vb, ki, 2, keepdims=False)
            q_lo = qi * qc
            k_lo = ki * kc
            run_blk = jnp.bool_(True)
            if causal:
                run_blk &= k_lo <= q_lo + qc - 1
            if window is not None:
                run_blk &= k_lo + kc - 1 > q_lo - window

            def do(args):
                m_, l_, o_ = args
                sc = jnp.einsum("bkgqd,bksd->bkgqs", qblk, k_i,
                                preferred_element_type=jnp.float32) * scale
                rows = q_lo + jnp.arange(qc)[:, None]
                cols = k_lo + jnp.arange(kc)[None, :]
                keep = jnp.ones((qc, kc), bool)
                if causal:
                    keep &= cols <= rows
                if window is not None:
                    keep &= cols > rows - window
                sc = jnp.where(keep, sc, NEG_INF)
                m_new = jnp.maximum(m_, sc.max(axis=-1))
                p_ = jnp.exp(sc - m_new[..., None])
                alpha = jnp.exp(m_ - m_new)
                l_new = l_ * alpha + p_.sum(axis=-1)
                o_new = o_ * alpha[..., None] + jnp.einsum(
                    "bkgqs,bksd->bkgqd", p_, v_i.astype(jnp.float32))
                return m_new, l_new, o_new

            return lax.cond(run_blk, do, lambda a: a, (m, l, o)), None

        (m, l, o), _ = lax.scan(kv_step, (m0, l0, o0), jnp.arange(nk))
        return o / jnp.maximum(l, 1e-30)[..., None]

    out = lax.map(one_q_block, (jnp.arange(nq), qg))     # (nq,b,hkv,g,qc,hd)
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, hq, s, hd)
    return out.astype(q.dtype)


# kv lengths at/above this use the chunked path (HBM budget, DESIGN §5)
XLA_ATTN_CHUNK_THRESHOLD = 8192


def sp_attention_island(cfg: ArchConfig, run: RunConfig,
                        rules: ShardingRules | None, b: int, s: int, *,
                        causal: bool = True, reference=None) -> Island:
    """Sequence-parallel attention island: ring attention (paper §4.2) or
    Ulysses a2a attention over the tp axis, q/k/v seq-sharded on dim 2."""
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    if rules is None:
        return Island("attn_sp", run=run, reference=reference)
    axis = rules.tp
    tp_size = rules.mesh.shape[axis]
    ulysses = run.sp_attention == "ulysses"
    fn = pk_ulysses_attention if ulysses else pk_ring_attention
    bspec = rules.dim(b, rules.dp)
    spec = P(bspec, None, axis, None)
    b_loc = rules.local_batch(b)
    s_loc = max(s // tp_size, 1)
    dtb = _dtype_bytes(cfg)
    divisible = [(s, axis)] + ([(hq, axis)] if ulysses else [])
    if ulysses:
        # RunConfig.ulysses_chunks reaches the paper-Fig. 11 chunked-a2a
        # overlap: attention on early head chunks hides later chunks' a2a.
        # The local payload shape lets plan() fit the count to the
        # splittable bystander dims exactly like the runtime a2a will.
        # ulysses_chunks=0 means AUTO: a frozen serving-bucket plan
        # (RunConfig.island_overrides) wins, then measured a2a rows from
        # `calibrate --per-island` (island-keyed first), then the analytic
        # chunk policy — the a2a twin of the GEMM chunk-schedule precedence.
        shape = (b_loc, hq, s_loc, hd)
        ov = island_override(run, "attn_ulysses")
        source = None
        if ov is not None and ov[1] is not None:
            a2a_chunks = max(1, ov[1])
            source = ov[2]          # "plan", or "health" for a demotion
        elif run.ulysses_chunks > 0:
            a2a_chunks = run.ulysses_chunks
        else:
            ctx = comm_context(run, axis, mesh=rules.mesh,
                               island=island_key("attn_ulysses",
                                                 "all_to_all", dtb))
            sched = ctx.a2a_chunk_schedule(shape, 1, 2, dtype_bytes=dtb)
            a2a_chunks, source = sched.n_chunks, sched.source
        comm = Comm("all_to_all", n_chunks=a2a_chunks,
                    backend="chunked" if a2a_chunks > 1 else "bulk",
                    payload_bytes=b_loc * hq * s_loc * hd * dtb,
                    shape=shape, split_axis=1, concat_axis=2,
                    source=source)
    else:
        comm = Comm("ring_shift", backend="bulk", n_chunks=tp_size,
                    payload_bytes=2 * b_loc * hkv * s_loc * hd * dtb)

    def body(ctx, q, k, v):
        kw = {"n_chunks": comm.n_chunks} if ulysses else {}
        return fn(q, k, v, axis, causal=causal, window=cfg.sliding_window,
                  ctx=ctx, **kw)

    return Island(f"attn_{run.sp_attention}", rules=rules, run=run,
                  inputs={"q": spec, "k": spec, "v": spec}, out_specs=spec,
                  body=body, reference=reference, divisible=divisible,
                  comm=comm)


def attn_out_island(cfg: ArchConfig, run: RunConfig,
                    rules: ShardingRules | None, b: int, s: int) -> Island:
    """Attention out-projection as the PK GEMM+AR island (paper Fig. 9): the
    head-sharded context × row-sharded wo, ring-overlapped all-reduce."""
    hq, hd, d = cfg.n_heads, cfg.hd, cfg.d_model
    h_full = hq * hd

    def reference(o, wo):
        return jnp.einsum("bsh,hd->bsd", o, wo)

    if rules is None:
        return Island("attn_out", run=run, reference=reference)
    tp = rules.tp
    tp_size = rules.mesh.shape[tp]
    bspec = rules.dim(b, rules.dp)
    b_loc = rules.local_batch(b)

    def body(ctx, o, wo):
        t = o.reshape(-1, o.shape[-1])
        out = ctx.matmul_all_reduce(t, wo)
        return out.reshape(o.shape[0], s, d)

    return Island(
        "attn_out", rules=rules, run=run,
        inputs={"o": P(bspec, None, rules.dim(h_full, tp)),
                "wo": rules.w2d(h_full, d, tp_dim=0)},
        out_specs=P(bspec, None, None),
        body=body, reference=reference,
        gathers={"wo": Gather(dim=1, size=d)},
        enable=run.pk_attn_out_island,
        divisible=((h_full, tp), (b * s, tp)),
        comm=Comm("matmul_all_reduce", m=b_loc * s, n=d,
                  k=h_full // tp_size if h_full % tp_size == 0 else h_full,
                  dtype_bytes=_wire_bytes(cfg, run)))


def attention_block(p, x, cfg: ArchConfig, run: RunConfig,
                    rules: ShardingRules | None, *, causal=True,
                    positions=None, cross_kv=None, seq_sharded=False):
    """Full attention sub-layer (projections + mixing + out-proj).

    p: {"wq","wk","wv","wo"}; x: (B, S, d) [if seq_sharded: ring/ulysses
    attention runs over the tp axis via the SP island].
    cross_kv: precomputed (k, v) for cross-attention (enc-dec decoder).
    """
    b, s, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    with jax.named_scope("qkv"):
        q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(b, s, hq, hd)
        q = q.transpose(0, 2, 1, 3)
        if cross_kv is None:
            k = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
            v = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
        else:
            k, v = cross_kv
        if positions is None:
            positions = jnp.arange(s)
        if cross_kv is None:                     # RoPE on self-attention only
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)

    win = cfg.sliding_window if cross_kv is None else None

    def dense_mix(q, k, v):
        if rules is not None:
            q = constrain(q, rules, rules.act_bhsd(hq))
        if k.shape[2] >= XLA_ATTN_CHUNK_THRESHOLD:
            return _chunked_attention(q, k, v, causal=causal, window=win)
        return _full_attention(q, k, v, causal=causal, window=win)

    if seq_sharded and rules is not None:
        island = sp_attention_island(cfg, run, rules, b, s, causal=causal,
                                     reference=dense_mix)
        o = island(q=q, k=k, v=v)
    else:
        o = dense_mix(q, k, v)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
    out = attn_out_island(cfg, run, rules, b, s)(o=o, wo=p["wo"])
    if rules is not None:
        out = constrain(out, rules, rules.act_btd())
    return out


def _write_rows(cache, row, pos):
    """Write one decode step's row per slot into a stacked cache leaf.

    cache: (n_periods, B, H, S, hd), or (n_periods, B, H, S) for the int8
    mode's per-token scale planes; row: the same with S = 1. Scalar ``pos``
    is the classic lockstep decode (dynamic_update_slice); a ``(B,)``
    vector scatters each slot's row at its own position — the
    continuous-batching pool's slots sit at different depths — and a
    position past the cache writes nothing (``mode="drop"``: the engine
    parks inactive slots there). Either is an in-place update of a donated
    cache."""
    row = row.astype(cache.dtype)
    if jnp.ndim(pos) == 0:
        return lax.dynamic_update_slice(
            cache, row, (0, 0, 0, pos) + (0,) * (cache.ndim - 4))
    # index every (period, slot, head): the write's window is then the
    # minor hd dim alone, which the TPU scatters into the slab's own layout
    # (a window over the heads too makes XLA relayout the whole slab)
    n_p, b, h = cache.shape[:3]
    return cache.at[jnp.arange(n_p)[:, None, None],
                    jnp.arange(b)[None, :, None],
                    jnp.arange(h)[None, None, :],
                    pos[None, :, None]].set(row[:, :, :, 0], mode="drop",
                                            unique_indices=True)


# int8 KV cache (ServeConfig.kv_dtype="int8"): K/V stored as int8 with ONE
# f32 scale per (token, head) — quantize on write, dequantize on read. The
# scale planes are cache-shaped minus the hd dim and ride in the cache tree
# as "k_scale"/"v_scale" leaves; quantization is detected from the cache
# dtype, so kv_dtype="bf16" trees never touch this path.

KV_SCALE_EPS = 1e-12


def _kv_quantize(new):
    """Symmetric per-(token, head) int8: ``new (..., hd)`` ->
    ``(q int8, scale f32 of shape new.shape[:-1])``."""
    f = new.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(f), axis=-1), KV_SCALE_EPS) / 127.0
    q = jnp.clip(jnp.round(f / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def _kv_dequantize(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def decode_island(cfg: ArchConfig, run: RunConfig,
                  rules: ShardingRules | None, b: int, s_max: int, *,
                  long_ctx: bool, pos, window,
                  quant: bool = False) -> Island:
    """One-token decode attention over the read-only KV cache plus the
    step's new row. The scores over the old positions ``ki < pos`` (window
    applied) and the new row's ``q·k_new`` meet in one f32 softmax,
    ``o = p_old @ V_old + p_new · v_new`` — the same as writing the row
    first and attending over ``ki < pos + 1``. The row counts where it will
    be written (``pos < s_max``; over the sequence-sharded cache, on the
    shard that holds ``pos``), so a parked slot reads the cache as it
    stays; ``decode_write_island`` writes it after the layer scan.
    Sharded: flash-decode logsumexp merge over the tp axis (DESIGN §4).
    ``pos`` may be a scalar (lockstep decode) or a per-slot ``(B,)`` vector
    (the serving engine's mixed pool). ``quant``: the cache is int8 with
    per-(token, head) f32 scale planes (``cache_ks``/``cache_vs`` inputs),
    dequantized for the mix; the caller passes the new row as the cache
    will hold it."""
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    vec = jnp.ndim(pos) > 0

    def _mix(q, k_, v_, k_new, v_new, offset, pos_in, axis=None):
        """Local partial attention, merged over ``axis`` when sharded.
        ``pos_in`` scalar or (B_loc,) — the shard-local slice of pos."""
        g, s_loc = hq // hkv, k_.shape[2]
        qg = q.reshape(q.shape[0], hkv, g, 1, hd)
        s_old = jnp.einsum("bkgqd,bksd->bkgqs", qg, k_,
                           preferred_element_type=jnp.float32) * hd ** -0.5
        s_new = jnp.einsum("bkgqd,bkqd->bkgq", qg, k_new,
                           preferred_element_type=jnp.float32) * hd ** -0.5
        p4 = jnp.reshape(pos_in, (-1, 1, 1, 1) if vec else ())  # (b,1,1,1)
        ki = offset + jnp.arange(s_loc)
        keep = ki < p4[..., None]
        if window is not None:
            keep &= ki > p4[..., None] - window
        s_old = jnp.where(keep, s_old, NEG_INF)
        s_new = jnp.where((p4 >= offset) & (p4 < offset + s_loc), s_new,
                          NEG_INF)
        m = jnp.maximum(s_old.max(axis=-1), s_new)               # (b,k,g,1)
        if axis is not None:
            m = lax.pmax(m, axis)
        p_old = jnp.exp(s_old - m[..., None])
        p_new = jnp.exp(s_new - m)
        l_ = p_old.sum(axis=-1) + p_new
        o = jnp.einsum("bkgqs,bksd->bkgqd", p_old, v_.astype(jnp.float32)) \
            + p_new[..., None] * v_new.astype(jnp.float32)[:, :, None]
        if axis is not None:
            l_, o = lax.psum(l_, axis), lax.psum(o, axis)
        o = o / jnp.maximum(l_, 1e-30)[..., None]
        return o.reshape(q.shape[0], hq, 1, hd).astype(q.dtype)

    def _cached(q, cache_k, cache_v, kw):
        """The cache's K/V as the mix reads them."""
        if not quant:
            return cache_k, cache_v
        return (_kv_dequantize(cache_k, kw["cache_ks"], q.dtype),
                _kv_dequantize(cache_v, kw["cache_vs"], q.dtype))

    # Per-slot (vector) pos is an ISLAND INPUT sharded like the batch — a
    # closure capture would hand every shard the global-batch vector while
    # its arrays are dp-local. The scalar (lockstep) form keeps the closure.
    def reference(q, cache_k, cache_v, k_new, v_new, **kw):
        k_, v_ = _cached(q, cache_k, cache_v, kw)
        return _mix(q, k_, v_, k_new, v_new, 0, kw.get("pos", pos))

    if rules is None:
        return Island("decode_attn", run=run, reference=reference)
    tp = rules.tp
    axis = (tuple(run.dp_axes) + (tp,)) if long_ctx else tp
    cache_spec = rules.kv_cache(hkv, b, long_ctx=long_ctx)
    bspec = None if long_ctx else rules.dim(b, rules.dp)
    qspec = P(bspec, None, None, None)

    def body(ctx, q, cache_k, cache_v, k_new, v_new, **kw):
        k_, v_ = _cached(q, cache_k, cache_v, kw)
        offset = lax.axis_index(axis) * cache_k.shape[2]
        return _mix(q, k_, v_, k_new, v_new, offset, kw.get("pos", pos),
                    axis)

    inputs = {"q": qspec, "cache_k": cache_spec, "cache_v": cache_spec,
              "k_new": qspec, "v_new": qspec}
    if quant:
        inputs["cache_ks"] = inputs["cache_vs"] = P(*cache_spec[:3])
    if vec:
        inputs["pos"] = P(bspec)
    return Island(
        "decode_attn", rules=rules, run=run, axis=tp, fallback_axes=axis,
        inputs=inputs, out_specs=qspec,
        body=body, reference=reference,
        enable=run.decode_seq_shard,
        divisible=((s_max, axis),),
        comm=Comm("psum", backend="bulk", n_chunks=1,
                  payload_bytes=2 * b * hq * hd * 4))


def decode_write_island(cfg: ArchConfig, run: RunConfig,
                        rules: ShardingRules | None, b: int, s_max: int, *,
                        long_ctx: bool, pos, quant: bool = False) -> Island:
    """Write one decode step's K/V rows into one attention position's
    stacked (n_periods, B, Hkv, S_max, hd) cache after the layer scan: one
    in-place write per leaf (``_write_rows``). Over the sequence-sharded
    cache each shard writes only the rows whose position it holds — a
    jit-level scatter into the seq-sharded slab would make XLA gather it
    (the PK §3.1.4 one-sided, pre-allocated slot applied to the KV cache).
    Inputs ``cache`` and ``rows`` are {leaf: array} trees: ``k``/``v``, and
    ``k_scale``/``v_scale`` with ``quant``. Same fallback predicate as
    ``decode_island``."""
    vec = jnp.ndim(pos) > 0

    def reference(cache, rows, **kw):
        p_ = kw.get("pos", pos)
        return jax.tree.map(lambda c, r: _write_rows(c, r, p_), cache, rows)

    if rules is None:
        return Island("cache_write", run=run, reference=reference)
    tp = rules.tp
    axis = (tuple(run.dp_axes) + (tp,)) if long_ctx else tp
    cache_spec = P(None, *rules.kv_cache(cfg.n_kv_heads, b,
                                         long_ctx=long_ctx))
    bspec = None if long_ctx else rules.dim(b, rules.dp)
    row_spec = P(None, bspec, None, None, None)
    leaf = {"k": cache_spec, "v": cache_spec}
    row = {"k": row_spec, "v": row_spec}
    if quant:
        leaf["k_scale"] = leaf["v_scale"] = P(*cache_spec[:4])
        row["k_scale"] = row["v_scale"] = P(None, bspec, None, None)

    def body(ctx, cache, rows, **kw):
        s_loc = cache["k"].shape[3]
        lp = kw.get("pos", pos) - lax.axis_index(axis) * s_loc
        # a row another shard holds goes past this shard's end: dropped
        lp = jnp.where((lp >= 0) & (lp < s_loc), lp, s_loc)
        return reference(cache, rows,
                         pos=jnp.broadcast_to(lp, cache["k"].shape[1:2]))

    inputs = {"cache": leaf, "rows": row}
    if vec:
        inputs["pos"] = P(bspec)
    return Island(
        "cache_write", rules=rules, run=run, axis=tp, fallback_axes=axis,
        inputs=inputs, out_specs=leaf, body=body, reference=reference,
        enable=run.decode_seq_shard, divisible=((s_max, axis),))


def decode_attention(p, x, cache_k, cache_v, pos, cfg: ArchConfig,
                     run: RunConfig, rules: ShardingRules | None, *,
                     cross_kv=None, long_ctx=False, k_scale=None,
                     v_scale=None):
    """One-token decode with KV cache.

    x: (B, 1, d); cache_k/v: (B, Hkv, S_max, hd), read only; pos: scalar
    current index or per-slot ``(B,)``. Returns (out (B,1,d), k_row, v_row):
    the new K/V rows (B, Hkv, 1, hd) the caller writes at ``pos`` after the
    layer scan (``decode_write_island``). Self-attention runs through the
    decode Island: with run.decode_seq_shard over the sharded cache — the SP
    serving path (DESIGN §4) — and otherwise, or on a single-device or
    indivisible mesh, through the island's dense reference.

    int8 mode: ``cache_k`` is int8 and ``k_scale``/``v_scale`` carry the
    per-(token, head) f32 scale planes — the new rows are quantized here and
    enter the attention dequantized, as the cache will hold them; the
    return grows to (out, k_row, v_row, k_scale_row, v_scale_row).
    """
    b, _, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    quant = k_scale is not None
    with jax.named_scope("qkv"):
        q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(b, 1, hq, hd).transpose(0, 2, 1, 3)
        if cross_kv is None:
            k_new = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(b, 1, hkv, hd).transpose(0, 2, 1, 3)
            v_new = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(b, 1, hkv, hd).transpose(0, 2, 1, 3)
            # scalar pos = lockstep decode; (B,) pos = per-slot positions
            # (the serving engine's mixed pool) — RoPE takes the (B, 1)
            # form directly
            positions = pos[:, None] if jnp.ndim(pos) else jnp.full((1,), pos)
            q = apply_rope(q, positions, cfg.rope_theta)
            k_new = apply_rope(k_new, positions, cfg.rope_theta)

    if cross_kv is None:
        if quant:
            (k_row, ks_row), (v_row, vs_row) = (_kv_quantize(k_new),
                                                _kv_quantize(v_new))
            rows = (k_row, v_row, ks_row, vs_row)
            k_new = _kv_dequantize(k_row, ks_row, q.dtype)
            v_new = _kv_dequantize(v_row, vs_row, q.dtype)
        else:
            k_new = k_new.astype(cache_k.dtype)
            v_new = v_new.astype(cache_v.dtype)
            rows = (k_new, v_new)
        # the island's dense reference is the single-device cache path
        island = decode_island(cfg, run, rules, b, cache_k.shape[2],
                               long_ctx=long_ctx, pos=pos,
                               window=cfg.sliding_window, quant=quant)
        kw = {"pos": pos} if jnp.ndim(pos) else {}
        if quant:
            kw["cache_ks"], kw["cache_vs"] = k_scale, v_scale
        o = island(q=q, cache_k=cache_k, cache_v=cache_v, k_new=k_new,
                   v_new=v_new, **kw)
    else:
        k_att, v_att = cross_kv
        o = _full_attention(q, k_att, v_att, causal=False, window=None)
        rows = (None, None)
    with jax.named_scope("attn_out"):
        o = o.transpose(0, 2, 1, 3).reshape(b, 1, hq * hd)
        out = jnp.einsum("bsh,hd->bsd", o, p["wo"])
    return (out, *rows)


def prefill_write_island(cfg: ArchConfig, run: RunConfig,
                         rules: ShardingRules | None, b: int,
                         L: int, *, quant: bool = False) -> Island:
    """Shard-local write of a prompt's K/V block into the sequence-sharded
    cache: each tp shard takes its own [off, off+s_loc) window of the
    (replicated, activation-sized) new K/V. A ``dynamic_update_slice`` on
    the sharded cache at the jit level would make XLA re-shard /
    all-gather the whole cache per layer — the same trap decode_island's
    in-island write avoids for the one-token case. ``quant``: ``new`` is
    the pre-quantized int8 block and ``new_s`` its per-(token, head) scale
    plane; both land in the sharded (cache, scale) pair."""
    hkv = cfg.n_kv_heads

    if quant:
        def reference(cache, scale, new, new_s):
            cache = lax.dynamic_update_slice(cache, new.astype(cache.dtype),
                                             (0, 0, 0, 0))
            scale = lax.dynamic_update_slice(scale, new_s, (0, 0, 0))
            return cache, scale
    else:
        def reference(cache, new):
            return lax.dynamic_update_slice(cache, new.astype(cache.dtype),
                                            (0, 0, 0, 0))

    if rules is None:
        return Island("prefill_write", run=run, reference=reference)
    tp = rules.tp
    cache_spec = rules.kv_cache(hkv, b)
    scale_spec = P(*cache_spec[:3])
    bspec = rules.dim(b, rules.dp)

    if quant:
        def body(ctx, cache, scale, new, new_s):
            s_loc = cache.shape[2]
            off = lax.axis_index(tp) * s_loc
            idx = off + jnp.arange(s_loc)              # global positions
            window = jnp.take(new, jnp.clip(idx, 0, L - 1), axis=2)
            swin = jnp.take(new_s, jnp.clip(idx, 0, L - 1), axis=2)
            hit = idx < L
            cache = jnp.where(hit[None, None, :, None],
                              window.astype(cache.dtype), cache)
            scale = jnp.where(hit[None, None, :], swin, scale)
            return cache, scale

        return Island(
            "prefill_write", rules=rules, run=run,
            inputs={"cache": cache_spec, "scale": scale_spec,
                    "new": P(bspec, None, None, None),
                    "new_s": P(bspec, None, None)},
            out_specs=(cache_spec, scale_spec),
            body=body, reference=reference,
            enable=run.decode_seq_shard)

    def body(ctx, cache, new):
        s_loc = cache.shape[2]
        off = lax.axis_index(tp) * s_loc
        idx = off + jnp.arange(s_loc)                  # global positions
        window = jnp.take(new, jnp.clip(idx, 0, L - 1), axis=2)
        hit = (idx < L)[None, None, :, None]
        return jnp.where(hit, window.astype(cache.dtype), cache)

    return Island(
        "prefill_write", rules=rules, run=run,
        inputs={"cache": cache_spec, "new": P(bspec, None, None, None)},
        out_specs=cache_spec,
        body=body, reference=reference,
        enable=run.decode_seq_shard)


def prefill_attention_block(p, x, cache_k, cache_v, cfg: ArchConfig,
                            run: RunConfig, rules: ShardingRules | None,
                            *, k_scale=None, v_scale=None):
    """Batched prefill: causal attention over the whole (padded) prompt with
    the K/V written into the decode cache at positions [0, L).

    The serving engine's prefill bucket runs this at the bucket's (B, L) —
    so the attention out-projection island here sees m = B_loc·L and can
    resolve to a *different* backend/chunk schedule than the decode bucket's
    m = B_loc·1 call (the whole point of per-bucket plans). Right-padding is
    safe: rows past a slot's real length are causal-masked garbage the
    caller discards, and the padded cache tail is never attended because
    decode masks ``ki < pos`` with ``pos`` the slot's real length.
    Returns (out (B, L, d), new_cache_k, new_cache_v) — plus the updated
    ``k_scale``/``v_scale`` planes in int8 mode, where the prompt's K/V is
    quantized once and the prefill attends over the dequantized values so
    prefill logits see exactly what later decode steps will read.
    """
    b, s, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    quant = k_scale is not None
    with jax.named_scope("qkv"):
        q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(b, s, hq, hd).transpose(0, 2, 1, 3)
        k = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
        v = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
        positions = jnp.arange(s)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if rules is not None:
        q = constrain(q, rules, rules.act_bhsd(hq))
    if quant:
        qk, sk = _kv_quantize(k)
        qv, sv = _kv_quantize(v)
        k_att = _kv_dequantize(qk, sk, q.dtype)
        v_att = _kv_dequantize(qv, sv, q.dtype)
    else:
        k_att, v_att = k, v
    win = cfg.sliding_window
    with jax.named_scope("prefill_attn"):
        if s >= XLA_ATTN_CHUNK_THRESHOLD:
            o = _chunked_attention(q, k_att, v_att, causal=True, window=win)
        else:
            o = _full_attention(q, k_att, v_att, causal=True, window=win)
    write = prefill_write_island(cfg, run, rules, b, s, quant=quant)
    if quant:
        new_k, k_scale = write(cache=cache_k, scale=k_scale, new=qk,
                               new_s=sk)
        new_v, v_scale = write(cache=cache_v, scale=v_scale, new=qv,
                               new_s=sv)
    else:
        new_k = write(cache=cache_k, new=k)
        new_v = write(cache=cache_v, new=v)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
    out = attn_out_island(cfg, run, rules, b, s)(o=o, wo=p["wo"])
    if rules is not None:
        out = constrain(out, rules, rules.act_btd())
    if quant:
        return out, new_k, new_v, k_scale, v_scale
    return out, new_k, new_v


# ---------------------------------------------------------------------------
# Paged KV cache (runtime/paging.py pool + block tables)
# ---------------------------------------------------------------------------
#
# The paged islands are the block-table twins of decode_island /
# prefill_write_island: the page *interior* is striped over the tp axis
# exactly like the slab's sequence dim, so each shard writes its own stripe
# of every page and attention keeps the flash-decode logsumexp merge. The
# only new machinery is indexing: reads gather pages through the block
# table, writes scatter with mode="drop" so rows whose block table is the
# engine's -1 sentinel (free slots, slots mid-prefill) write nothing — that
# is what makes interleaving decode ticks between prefill chunks safe.


def _paged_gather(pool, bt):
    """pool (N, Hkv, s[, hd]); bt (B, P) ids (clipped) -> (B, Hkv, P*s[, hd]).
    Rank-3 pools are the int8 mode's per-(token, head) scale planes."""
    g = pool[jnp.clip(bt, 0, pool.shape[0] - 1)]       # (B, P, Hkv, s[, hd])
    g = jnp.moveaxis(g, 1, 2)                          # (B, Hkv, P, s[, hd])
    b, hk, pm, s = g.shape[:4]
    return g.reshape(b, hk, pm * s, *g.shape[4:])


def _page_positions(pmax: int, ps: int, off, s_loc: int):
    """Global cache position of every gathered cell: (P*s_loc,)."""
    return (jnp.arange(pmax)[:, None] * ps
            + off + jnp.arange(s_loc)[None, :]).reshape(-1)


def _paged_mix(q, gk, gv, ki, *, kv_len=None, q_pos=None, window, axis):
    """Attention over gathered pages. ``ki`` maps each gathered cell to its
    global cache position; masking is ``ki < kv_len`` (decode, per-slot) or
    ``ki <= q_pos`` (prefill chunk, causal vs. global query positions), so
    allocated-but-unwritten page tails are never attended. ``axis`` None =
    dense (full pages); else shard-local partials + logsumexp merge."""
    b, hq, sq, hd = q.shape
    hkv = gk.shape[1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, sq, hd)
    s_ = jnp.einsum("bkgqd,bksd->bkgqs", qg, gk,
                    preferred_element_type=jnp.float32) * hd ** -0.5
    kib = ki[None, None, None, None, :]
    if kv_len is not None:
        keep = kib < kv_len[:, None, None, None, None]
        lim = kv_len[:, None, None, None, None] - 1
    else:
        qp = q_pos[None, None, None, :, None]
        keep = kib <= qp
        lim = qp
    if window is not None:
        keep = keep & (kib > lim - window)
    s_ = jnp.where(keep, s_, NEG_INF)
    m_loc = s_.max(axis=-1)
    if axis is None:
        p_ = jnp.exp(s_ - m_loc[..., None])
        l_ = p_.sum(axis=-1)
        o = jnp.einsum("bkgqs,bksd->bkgqd", p_, gv.astype(jnp.float32))
    else:
        m_glob = lax.pmax(m_loc, axis)
        p_ = jnp.exp(s_ - m_glob[..., None])
        l_ = lax.psum(p_.sum(axis=-1), axis)
        o = lax.psum(
            jnp.einsum("bkgqs,bksd->bkgqd", p_, gv.astype(jnp.float32)),
            axis)
    o = o / jnp.maximum(l_, 1e-30)[..., None]
    return o.reshape(b, hq, sq, hd).astype(q.dtype)


def _paged_decode_write(pool, new, bt, pos, ps: int, off, s_loc: int):
    """Scatter one token per slot into its block-table page at ``pos``.
    Misses (position outside this shard's stripe, unmapped page) drop."""
    n = pool.shape[0]
    pmax = bt.shape[1]
    lp = jnp.clip(pos // ps, 0, pmax - 1)
    pid = jnp.take_along_axis(bt, lp[:, None], axis=1)[:, 0]
    r = pos % ps
    hit = (r >= off) & (r < off + s_loc) & (pid >= 0)
    rl = jnp.clip(r - off, 0, s_loc - 1)
    pid_safe = jnp.where(hit, pid, n)
    return pool.at[pid_safe, :, rl].set(
        new[:, :, 0].astype(pool.dtype), mode="drop")


def _paged_chunk_write(pool, new, bt, c0, wf, ps: int, off, s_loc: int):
    """Write one prefill chunk's K/V (``new``, positions [c0, c0+sq)) into
    block-table pages: gather the touched pages, select per cell between the
    chunk value and the current content, scatter whole pages back. The
    per-cell select is what makes copy-on-write prefix resume sound —
    positions below ``wf`` (per-slot ``write_from``) keep the donor pages'
    values byte-for-byte even though the boundary chunk recomputes them.
    Rank-3 (pool, new) pairs — the int8 scale planes — take the same
    write with the hd dim absent."""
    n = pool.shape[0]
    b, hk, sq = new.shape[:3]
    pmax = bt.shape[1]
    npg = -(-sq // ps)
    pgs = c0 // ps + jnp.arange(npg)
    pid = jnp.take(bt, jnp.clip(pgs, 0, pmax - 1), axis=1)     # (B, npg)
    pid = jnp.where((pgs < pmax)[None, :], pid, -1)
    tt = jnp.arange(npg)[:, None] * ps + off + jnp.arange(s_loc)[None, :]
    src = jnp.take(new, jnp.clip(tt.reshape(-1), 0, sq - 1), axis=2)
    src = jnp.moveaxis(src.reshape(b, hk, npg, s_loc, *new.shape[3:]), 1, 2)
    cur = pool[jnp.clip(pid, 0, n - 1)]            # (B, npg, hk, s_loc[, hd])
    t_glob = c0 + tt                                 # (npg, s_loc) global pos
    cell = ((tt < sq)[None, :, None, :]
            & (t_glob[None, :, None, :] >= wf[:, None, None, None]))
    if pool.ndim == 4:           # value pool (…, hd); scale planes are rank 3
        cell = cell[..., None]
    vals = jnp.where(cell, src.astype(pool.dtype), cur)
    pid_safe = jnp.where(pid >= 0, pid, n)
    return pool.at[pid_safe].set(vals, mode="drop")


def _dp_pool_base(rules: ShardingRules, partitioned: bool):
    """Global id of this shard's first pool page (0 when un-partitioned),
    as a traced scalar factory for use inside shard_map bodies."""
    if not partitioned:
        return lambda n_loc: 0
    axes = rules.run.dp_axes

    def base(n_loc):
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * rules.mesh.shape[a] + lax.axis_index(a)
        return idx * n_loc
    return base


def paged_decode_island(cfg: ArchConfig, run: RunConfig,
                        rules: ShardingRules | None, b: int, page_size: int,
                        *, window, quant: bool = False) -> Island:
    """One-token decode over the paged pool: block-table page write + gather
    + flash-decode logsumexp merge over the tp axis. Declares the same name
    and ``Comm`` coordinates as the slab ``decode_island`` — the merge
    collective is identical — so frozen per-bucket plans and overrides apply
    unchanged to the paged layout. ``quant``: int8 pools with per-(token,
    head) f32 scale pools (``pool_ks``/``pool_vs``) — the token is
    quantized before its page write, gathers dequantize."""
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    def _write_gather(pool_k, pool_v, pool_ks, pool_vs, k_new, v_new, bt,
                     pos, ps, off, s_loc, qdt):
        """Shared write+gather: returns (gk, gv, new pools tuple)."""
        if quant:
            qk, sk = _kv_quantize(k_new)
            qv, sv = _kv_quantize(v_new)
            pk = _paged_decode_write(pool_k, qk, bt, pos, ps, off, s_loc)
            pv = _paged_decode_write(pool_v, qv, bt, pos, ps, off, s_loc)
            pks = _paged_decode_write(pool_ks, sk, bt, pos, ps, off, s_loc)
            pvs = _paged_decode_write(pool_vs, sv, bt, pos, ps, off, s_loc)
            gk = _kv_dequantize(_paged_gather(pk, bt),
                                _paged_gather(pks, bt), qdt)
            gv = _kv_dequantize(_paged_gather(pv, bt),
                                _paged_gather(pvs, bt), qdt)
            return gk, gv, (pk, pv, pks, pvs)
        pk = _paged_decode_write(pool_k, k_new, bt, pos, ps, off, s_loc)
        pv = _paged_decode_write(pool_v, v_new, bt, pos, ps, off, s_loc)
        return _paged_gather(pk, bt), _paged_gather(pv, bt), (pk, pv)

    def reference(q, pool_k, pool_v, k_new, v_new, bt, pos, **kw):
        ps = pool_k.shape[2]
        gk, gv, pools = _write_gather(
            pool_k, pool_v, kw.get("pool_ks"), kw.get("pool_vs"),
            k_new, v_new, bt, pos, ps, 0, ps, q.dtype)
        ki = _page_positions(bt.shape[1], ps, 0, ps)
        o = _paged_mix(q, gk, gv, ki, kv_len=pos + 1, window=window,
                       axis=None)
        return (o, *pools)

    if rules is None:
        return Island("decode_attn", run=run, reference=reference)
    tp = rules.tp
    bspec = rules.dim(b, rules.dp)
    partitioned = bspec is not None
    pool_spec = P(rules.dp if partitioned else None, None, tp, None)
    scale_spec = P(*pool_spec[:3])
    qspec = P(bspec, None, None, None)
    base_fn = _dp_pool_base(rules, partitioned)

    def body(ctx, q, pool_k, pool_v, k_new, v_new, bt, pos, **kw):
        n_loc, _, s_loc = pool_k.shape[:3]
        off = lax.axis_index(tp) * s_loc
        bt_l = jnp.where(bt >= 0, bt - base_fn(n_loc), -1)
        gk, gv, pools = _write_gather(
            pool_k, pool_v, kw.get("pool_ks"), kw.get("pool_vs"),
            k_new, v_new, bt_l, pos, page_size, off, s_loc, q.dtype)
        ki = _page_positions(bt.shape[1], page_size, off, s_loc)
        o = _paged_mix(q, gk, gv, ki, kv_len=pos + 1, window=window,
                       axis=tp)
        return (o, *pools)

    inputs = {"q": qspec, "pool_k": pool_spec, "pool_v": pool_spec,
              "k_new": qspec, "v_new": qspec, "bt": P(bspec, None),
              "pos": P(bspec)}
    if quant:
        inputs["pool_ks"] = scale_spec
        inputs["pool_vs"] = scale_spec
    return Island(
        "decode_attn", rules=rules, run=run, axis=tp, fallback_axes=tp,
        inputs=inputs,
        out_specs=((qspec, pool_spec, pool_spec, scale_spec, scale_spec)
                   if quant else (qspec, pool_spec, pool_spec)),
        body=body, reference=reference,
        enable=run.decode_seq_shard,
        divisible=((page_size, tp),),
        comm=Comm("psum", backend="bulk", n_chunks=1,
                  payload_bytes=2 * b * hq * hd * 4))


def paged_prefill_island(cfg: ArchConfig, run: RunConfig,
                         rules: ShardingRules | None, b: int, s: int,
                         page_size: int, *, window,
                         quant: bool = False) -> Island:
    """One prefill chunk over the paged pool: chunk K/V written into the
    group's block-table pages (shard-local stripes), then causal attention
    of the chunk's queries against every mapped page — donor prefix, earlier
    chunks, and the chunk itself — with the tp logsumexp merge. ``c0`` is
    the chunk's global start position, ``wf`` the per-slot write_from floor
    below which writes are suppressed (copy-on-write prefix resume).
    ``quant``: int8 pools + scale pools; the chunk's K/V is quantized once
    before the write and the queries attend over dequantized pages — so
    even the chunk's own K/V is seen at cache precision, keeping chunked
    and single-shot schedules token-identical."""
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    def _write_gather(pool_k, pool_v, pool_ks, pool_vs, k_new, v_new, bt,
                     c0, wf, ps, off, s_loc, qdt):
        if quant:
            qk, sk = _kv_quantize(k_new)
            qv, sv = _kv_quantize(v_new)
            pk = _paged_chunk_write(pool_k, qk, bt, c0, wf, ps, off, s_loc)
            pv = _paged_chunk_write(pool_v, qv, bt, c0, wf, ps, off, s_loc)
            pks = _paged_chunk_write(pool_ks, sk, bt, c0, wf, ps, off, s_loc)
            pvs = _paged_chunk_write(pool_vs, sv, bt, c0, wf, ps, off, s_loc)
            gk = _kv_dequantize(_paged_gather(pk, bt),
                                _paged_gather(pks, bt), qdt)
            gv = _kv_dequantize(_paged_gather(pv, bt),
                                _paged_gather(pvs, bt), qdt)
            return gk, gv, (pk, pv, pks, pvs)
        pk = _paged_chunk_write(pool_k, k_new, bt, c0, wf, ps, off, s_loc)
        pv = _paged_chunk_write(pool_v, v_new, bt, c0, wf, ps, off, s_loc)
        return _paged_gather(pk, bt), _paged_gather(pv, bt), (pk, pv)

    def reference(q, pool_k, pool_v, k_new, v_new, bt, c0, wf, **kw):
        ps = pool_k.shape[2]
        gk, gv, pools = _write_gather(
            pool_k, pool_v, kw.get("pool_ks"), kw.get("pool_vs"),
            k_new, v_new, bt, c0, wf, ps, 0, ps, q.dtype)
        ki = _page_positions(bt.shape[1], ps, 0, ps)
        o = _paged_mix(q, gk, gv, ki, q_pos=c0 + jnp.arange(s),
                       window=window, axis=None)
        return (o, *pools)

    if rules is None:
        return Island("paged_prefill_attn", run=run, reference=reference)
    tp = rules.tp
    bspec = rules.dim(b, rules.dp)
    partitioned = bspec is not None
    pool_spec = P(rules.dp if partitioned else None, None, tp, None)
    scale_spec = P(*pool_spec[:3])
    qspec = P(bspec, None, None, None)
    base_fn = _dp_pool_base(rules, partitioned)

    def body(ctx, q, pool_k, pool_v, k_new, v_new, bt, c0, wf, **kw):
        n_loc, _, s_loc = pool_k.shape[:3]
        off = lax.axis_index(tp) * s_loc
        bt_l = jnp.where(bt >= 0, bt - base_fn(n_loc), -1)
        gk, gv, pools = _write_gather(
            pool_k, pool_v, kw.get("pool_ks"), kw.get("pool_vs"),
            k_new, v_new, bt_l, c0, wf, page_size, off, s_loc, q.dtype)
        ki = _page_positions(bt.shape[1], page_size, off, s_loc)
        o = _paged_mix(q, gk, gv, ki, q_pos=c0 + jnp.arange(s),
                       window=window, axis=tp)
        return (o, *pools)

    inputs = {"q": qspec, "pool_k": pool_spec, "pool_v": pool_spec,
              "k_new": qspec, "v_new": qspec, "bt": P(bspec, None),
              "c0": P(), "wf": P(bspec)}
    if quant:
        inputs["pool_ks"] = scale_spec
        inputs["pool_vs"] = scale_spec
    return Island(
        "paged_prefill_attn", rules=rules, run=run, axis=tp,
        fallback_axes=tp,
        inputs=inputs,
        out_specs=((qspec, pool_spec, pool_spec, scale_spec, scale_spec)
                   if quant else (qspec, pool_spec, pool_spec)),
        body=body, reference=reference,
        enable=run.decode_seq_shard,
        divisible=((page_size, tp),),
        comm=Comm("psum", backend="bulk", n_chunks=1,
                  payload_bytes=2 * b * hq * s * hd * 4))


def paged_decode_attention(p, x, pool_k, pool_v, bt, pos, cfg: ArchConfig,
                           run: RunConfig, rules: ShardingRules | None, *,
                           k_scale=None, v_scale=None):
    """One-token decode against the paged pool (the block-table twin of
    ``decode_attention``). x: (B, 1, d); pool_k/v: (N_pages, Hkv, page, hd);
    bt: (B, P) block table (−1 = unmapped — the write drops, so free and
    mid-prefill slots are inert); pos: per-slot (B,) positions.
    Returns (out (B,1,d), new_pool_k, new_pool_v) — plus the updated scale
    pools in int8 mode (``k_scale``/``v_scale`` given)."""
    b, _, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    quant = k_scale is not None
    with jax.named_scope("qkv"):
        q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(b, 1, hq, hd).transpose(0, 2, 1, 3)
        k_new = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(b, 1, hkv, hd).transpose(0, 2, 1, 3)
        v_new = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(b, 1, hkv, hd).transpose(0, 2, 1, 3)
        positions = pos[:, None]
        q = apply_rope(q, positions, cfg.rope_theta)
        k_new = apply_rope(k_new, positions, cfg.rope_theta)
    island = paged_decode_island(cfg, run, rules, b, pool_k.shape[2],
                                 window=cfg.sliding_window, quant=quant)
    kw = {"pool_ks": k_scale, "pool_vs": v_scale} if quant else {}
    res = island(q=q, pool_k=pool_k, pool_v=pool_v, k_new=k_new,
                 v_new=v_new, bt=bt, pos=pos, **kw)
    with jax.named_scope("attn_out"):
        o = res[0].transpose(0, 2, 1, 3).reshape(b, 1, hq * hd)
        out = jnp.einsum("bsh,hd->bsd", o, p["wo"])
    return (out, *res[1:])


def paged_prefill_attention_block(p, x, pool_k, pool_v, bt, chunk_start,
                                  write_from, cfg: ArchConfig,
                                  run: RunConfig,
                                  rules: ShardingRules | None, *,
                                  k_scale=None, v_scale=None):
    """One chunk of paged prefill attention: x (B, cl, d) are the chunk's
    hidden states (global positions [chunk_start, chunk_start+cl)); K/V land
    in the block table's pages and the queries attend over every mapped
    page. Returns (out (B, cl, d), new_pool_k, new_pool_v) — plus the
    updated scale pools in int8 mode."""
    b, s, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    quant = k_scale is not None
    with jax.named_scope("qkv"):
        q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(b, s, hq, hd).transpose(0, 2, 1, 3)
        k = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
        v = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
        positions = chunk_start + jnp.arange(s)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if rules is not None:
        q = constrain(q, rules, rules.act_bhsd(hq))
    island = paged_prefill_island(cfg, run, rules, b, s, pool_k.shape[2],
                                  window=cfg.sliding_window, quant=quant)
    kw = {"pool_ks": k_scale, "pool_vs": v_scale} if quant else {}
    res = island(q=q, pool_k=pool_k, pool_v=pool_v, k_new=k, v_new=v,
                 bt=bt, c0=jnp.asarray(chunk_start, jnp.int32),
                 wf=write_from, **kw)
    o = res[0].transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
    out = attn_out_island(cfg, run, rules, b, s)(o=o, wo=p["wo"])
    if rules is not None:
        out = constrain(out, rules, rules.act_btd())
    return (out, *res[1:])


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------

def mlp_island(cfg: ArchConfig, run: RunConfig,
               rules: ShardingRules | None, b: int, s: int) -> Island:
    """Megatron MLP as the PK GEMM+AR island (paper §4.1): x (replicated over
    tp) × w1 (col-shard) -> act -> × w2 (row-shard) -> overlapped GEMM+AR via
    CommContext (the policy picks bulk for tiny token counts — decode — and
    the ring schedule otherwise). FSDP gathers of the weight shards run
    inside the island so XLA overlaps them with the previous chunk's
    compute."""
    act = get_act(cfg.act)
    d, ff = cfg.d_model, cfg.d_ff
    gated = cfg.gated_mlp

    def reference(x, w1, w3, w2):
        h = jnp.einsum("bsd,df->bsf", x, w1)
        if gated:
            h = act(h) * jnp.einsum("bsd,df->bsf", x, w3)
        else:
            h = act(h)
        out = jnp.einsum("bsf,fd->bsd", h, w2)
        if rules is not None:
            out = constrain(out, rules, rules.act_btd())
        return out

    if rules is None:
        return Island("mlp", run=run, reference=reference)
    tp = rules.tp
    tp_size = rules.mesh.shape[tp]
    bspec = rules.dim(b, rules.dp)
    b_loc = rules.local_batch(b)
    w1s = rules.w2d(d, ff, tp_dim=1)
    w2s = rules.w2d(ff, d, tp_dim=0)

    def body(ctx, x, w1, w3, w2):
        t = x.reshape(-1, d)
        h = jnp.einsum("td,df->tf", t, w1)
        if gated:
            h = act(h) * jnp.einsum("td,df->tf", t, w3)
        else:
            h = act(h)
        out = ctx.matmul_all_reduce(h.astype(x.dtype), w2)
        return out.reshape(x.shape[0], s, d)

    gathers = {"w1": Gather(dim=0, size=d), "w2": Gather(dim=1, size=d)}
    if gated:
        gathers["w3"] = Gather(dim=0, size=d)
    return Island(
        "mlp", rules=rules, run=run,
        inputs={"x": P(bspec, None, None), "w1": w1s,
                "w3": w1s if gated else P(), "w2": w2s},
        out_specs=P(bspec, None, None),
        body=body, reference=reference, gathers=gathers,
        enable=run.pk_overlap,
        divisible=((ff, tp),),
        comm=Comm("matmul_all_reduce", m=b_loc * s, n=d,
                  k=ff // tp_size if ff % tp_size == 0 else ff,
                  dtype_bytes=_wire_bytes(cfg, run)))


def mlp_block(p, x, cfg: ArchConfig, run: RunConfig,
              rules: ShardingRules | None):
    """Dense (optionally gated) MLP with TP. PK mode: the two GEMMs run as
    one Island with overlapped AG+GEMM / GEMM+AR rings (paper §4.1)."""
    b, s, _ = x.shape
    island = mlp_island(cfg, run, rules, b, s)
    w3 = p["w3"] if cfg.gated_mlp else jnp.zeros((), x.dtype)
    return island(x=x, w1=p["w1"], w3=w3, w2=p["w2"])


def moe_island(cfg: ArchConfig, run: RunConfig,
               rules: ShardingRules | None, b: int, s: int) -> Island:
    """MoE island over the tp axis with device-major expert weights
    (core/moe.py). Both variants — resident 2D-TP serving and the default
    EP×TP — share one gating/capacity plan (``pk_moe.dispatch_plan``), so
    trace- and serve-path chunking can never diverge."""
    d = cfg.d_model
    gated = cfg.gated_mlp

    def _undo_device_major(w, *, ff_axis):
        # (M, E_loc, ...) device-major PGL -> (E, ...) with the full ff:
        # rank r = g*tp_ff + j holds expert group g's ff slice j, so regroup
        # to (ep, tp_ff, E_loc, ...), move the tp_ff axis next to its ff_loc
        # slice (`ff_axis` is ff_loc's absolute axis in w) and merge both
        # pairs. tp_ff == 1 (the common case) reduces to a plain reshape.
        m_dev, e_loc = w.shape[0], w.shape[1]
        ep = cfg.n_experts // e_loc
        tp_ff = m_dev // ep
        assert ep * e_loc == cfg.n_experts and tp_ff * ep == m_dev, w.shape
        w = w.reshape(ep, tp_ff, e_loc, *w.shape[2:])
        w = jnp.moveaxis(w, 1, ff_axis)    # -> (ep, e_loc, ..., tp_ff, ff_loc, ...)
        shape = [ep * e_loc] + list(w.shape[2:])
        shape[ff_axis - 1:ff_axis + 1] = [shape[ff_axis - 1] * shape[ff_axis]]
        return w.reshape(shape)

    def reference(x, router, w1, w3, w2):
        # dense oracle: every expert on every token, no capacity drop; the
        # device-major (M, E_loc, d, ff/tp_ff) layout is reconstructed to
        # (E, d, ff) exactly (elementwise act + ff-sliced GEMMs commute with
        # the concat), so reference_mode works for every EP×TP split
        y, aux = pk_moe.moe_reference_dense(
            x.reshape(-1, d), router, _undo_device_major(w1, ff_axis=3),
            _undo_device_major(w3, ff_axis=3) if gated else None,
            _undo_device_major(w2, ff_axis=2),
            n_experts=cfg.n_experts, top_k=cfg.top_k)
        return y.reshape(x.shape), jnp.asarray(aux)[None]

    if rules is None:
        return Island("moe", run=run, reference=reference)
    tp = rules.tp
    f = rules.fsdp_axes
    bspec = rules.dim(b, rules.dp)
    b_loc = rules.local_batch(b)
    # ONE gating/capacity plan for every variant: the serve path sees the
    # dp-gathered token count, the train path the local count.
    n_tok = b * s if run.serve_moe_tp_data else b_loc * s
    moe_chunks = run.moe_chunks
    if moe_chunks == 0:
        # AUTO: measured-first off the `calibrate --per-island` MoE-dispatch
        # a2a rows (island "moe_dispatch"), analytic a2a chunk policy
        # otherwise — the same resolution order as the Ulysses island.
        n_dev = rules.mesh.shape[tp]
        base = pk_moe.dispatch_plan(n_tok, n_experts=cfg.n_experts,
                                    top_k=cfg.top_k,
                                    capacity_factor=cfg.capacity_factor)
        shape = (n_dev, max(cfg.n_experts // max(n_dev, 1), 1), base.cap,
                 cfg.d_model)
        ctx = comm_context(run, tp, mesh=rules.mesh,
                           island=island_key("moe_dispatch", "all_to_all",
                                             _dtype_bytes(cfg)))
        moe_chunks = ctx.a2a_chunk_schedule(
            shape, 0, 0, dtype_bytes=_dtype_bytes(cfg)).n_chunks
    plan = pk_moe.dispatch_plan(n_tok, n_experts=cfg.n_experts,
                                top_k=cfg.top_k,
                                capacity_factor=cfg.capacity_factor,
                                n_chunks=moe_chunks)
    gathers: dict[str, Gather] = {}

    if run.serve_moe_tp_data:
        # resident 2D-TP: weights stay put (ff sliced over dp); tokens are
        # all-gathered over dp (activation-sized), expert partials are
        # psum_scatter'd back — O(T*d) traffic instead of O(W) per step.
        def body(ctx, x, router, w1, w3, w2):
            w1, w2 = w1[0], w2[0]
            w3 = w3[0] if gated else None
            t = x.reshape(-1, d)
            if bspec is not None:
                names = (rules.dp,) if isinstance(rules.dp, str) \
                    else tuple(rules.dp)
                for a in names:
                    t = lax.all_gather(t, a, axis=0, tiled=True)
            y, aux = pk_moe.pk_moe_replicated(
                t, router, w1, w3, w2, axis_name=tp,
                n_experts=cfg.n_experts, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor, plan=plan, ctx=ctx)
            if bspec is not None:
                y = lax.psum_scatter(y.astype(jnp.float32), rules.dp,
                                     scatter_dimension=0, tiled=True)
            else:
                y = lax.psum(y.astype(jnp.float32), rules.dp)
            return y.astype(x.dtype).reshape(x.shape), \
                lax.pmean(aux, tp)[None]

        dpff = rules.dim(cfg.d_ff // (rules.mesh.shape[tp] //
                                      pk_moe.ep_tp_split(cfg.n_experts,
                                                         rules.mesh.shape[tp])[0]),
                         rules.dp)
        wspec = P(tp, None, None, dpff)
        w2spec = P(tp, None, dpff, None)
    else:
        def body(ctx, x, router, w1, w3, w2):
            w1, w2 = w1[0], w2[0]
            w3 = w3[0] if gated else None
            t = x.reshape(-1, d)
            y, aux = pk_moe.pk_moe_replicated(
                t, router, w1, w3, w2, axis_name=tp, n_experts=cfg.n_experts,
                top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                plan=plan, ring_combine=run.pk_ring_psum, ctx=ctx)
            return y.reshape(x.shape), lax.pmean(aux, tp)[None]

        # device-major PGL weights: (M, E_loc, d[, /fsdp], ff_loc)
        wspec = P(tp, None, rules.dim(d, f), None)
        w2spec = P(tp, None, None, rules.dim(d, f))
        gathers = {"w1": Gather(dim=2, size=d), "w2": Gather(dim=3, size=d)}
        if gated:
            gathers["w3"] = Gather(dim=2, size=d)

    return Island(
        "moe", rules=rules, run=run,
        inputs={"x": P(bspec, None, None), "router": P(), "w1": wspec,
                "w3": wspec if gated else P(), "w2": w2spec},
        out_specs=(P(bspec, None, None), P(bspec)),
        body=body, reference=reference, gathers=gathers,
        comm=Comm("psum", backend="ring" if run.pk_ring_psum else "bulk",
                  n_chunks=plan.n_chunks,
                  payload_bytes=n_tok * d * _dtype_bytes(cfg)))


def moe_block(p, x, cfg: ArchConfig, run: RunConfig,
              rules: ShardingRules | None):
    """MoE sub-layer; returns (out, aux_loss)."""
    b, s, _ = x.shape
    island = moe_island(cfg, run, rules, b, s)
    w3 = p["w3"] if cfg.gated_mlp else jnp.zeros((), x.dtype)
    out, aux = island(x=x, router=p["router"], w1=p["w1"], w3=w3, w2=p["w2"])
    return out, jnp.mean(aux.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Vocab-parallel embedding + loss
# ---------------------------------------------------------------------------

def embed_island(run: RunConfig, rules: ShardingRules | None, v: int,
                 d_model: int, b: int) -> Island:
    """Megatron vocab-parallel embedding island: gather from the LOCAL
    (V_loc, d_loc) shard, combine with activation-sized collectives — never
    all-gather the table itself (a (B,S)-token lookup must move O(B·S·d),
    not O(V·d))."""

    def reference(emb, tok):
        return jnp.take(emb, tok, axis=0)

    if rules is None:
        return Island("embed", run=run, reference=reference)
    tp = rules.tp
    f = rules.fsdp_axes

    def body(ctx, emb, tok):
        v_loc = emb.shape[0]
        v0 = lax.axis_index(tp) * v_loc
        local = tok - v0
        ok = (local >= 0) & (local < v_loc)
        x = jnp.take(emb, jnp.clip(local, 0, v_loc - 1), axis=0)
        x = jnp.where(ok[..., None], x, 0)
        x = lax.psum(x, tp)                      # combine vocab shards
        if f is not None and x.shape[-1] < d_model:
            names = (f,) if isinstance(f, str) else tuple(f)
            for a in names:                      # gather the d shards
                x = lax.all_gather(x, a, axis=-1, tiled=True)
        return x

    bspec = rules.dim(b, rules.dp)
    return Island(
        "embed", rules=rules, run=run,
        inputs={"emb": P(tp, rules.dim(d_model, f)), "tok": P(bspec, None)},
        out_specs=P(bspec, None, None),
        body=body, reference=reference,
        divisible=((v, tp),),
        comm=Comm("psum", backend="bulk", n_chunks=1))


def embed_tokens(p, tokens, rules: ShardingRules | None,
                 run: RunConfig | None = None):
    """tokens (B, S) -> (B, S, d). Vocab-parallel island when sharded;
    plain take otherwise (the island's fallback)."""
    emb = p["embed"]
    v, d_model = emb.shape
    b = tokens.shape[0]
    island = embed_island(run if run is not None else RunConfig(),
                          rules, v, d_model, b)
    return island(emb=emb, tok=tokens)


def lm_loss_island(run: RunConfig, rules: ShardingRules | None, b: int,
                   d: int, v: int) -> Island:
    """Chunked vocab-parallel cross-entropy island: softmax statistics are
    psum-merged over the vocab (tp) shard; never materializes (B,S,V)."""

    def scan_body_dense(head):
        def body(carry, args):
            xi, ti, wi = args
            logits = jnp.einsum("bsd,dv->bsv", xi, head).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, ti[..., None], axis=-1)[..., 0]
            return (carry[0] + jnp.sum((lse - tgt) * wi),
                    carry[1] + jnp.sum(wi)), None
        return body

    def reference(xc, tc, wc, head):
        (tot, cnt), _ = lax.scan(scan_body_dense(head),
                                 (jnp.zeros(()), jnp.zeros(())),
                                 (xc, tc, wc))
        return tot, cnt

    if rules is None:
        return Island("lm_loss", run=run, reference=reference)
    tp = rules.tp
    hspec = rules.w2d(d, v, tp_dim=1)

    def body(ctx, xc, tc, wc, head):
        v_loc = head.shape[1]
        v0 = lax.axis_index(tp) * v_loc

        def step(carry, args):
            xi, ti, wi = args
            logits = jnp.einsum("bsd,dv->bsv", xi, head).astype(jnp.float32)
            # global max is for numerical stability only — no gradient needed
            m_loc = lax.stop_gradient(logits).max(axis=-1)
            m = lax.pmax(m_loc, tp)
            se = lax.psum(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), tp)
            lse = m + jnp.log(se)
            loc = ti - v0
            ok = (loc >= 0) & (loc < v_loc)
            tgt = jnp.take_along_axis(logits, jnp.clip(loc, 0, v_loc - 1)[..., None],
                                      axis=-1)[..., 0]
            tgt = lax.psum(jnp.where(ok, tgt, 0.0), tp)
            # rank-1 carries: legacy shard_map cannot transpose rank-0
            # residuals crossing the island boundary
            return (carry[0] + jnp.sum((lse - tgt) * wi)[None],
                    carry[1] + jnp.sum(wi)[None]), None

        (tot, cnt), _ = lax.scan(step, (jnp.zeros((1,)), jnp.zeros((1,))),
                                 (xc, tc, wc))
        return tot, cnt

    bspec = rules.dim(b, rules.dp)
    return Island(
        "lm_loss", rules=rules, run=run,
        inputs={"xc": P(None, bspec, None, None), "tc": P(None, bspec),
                "wc": P(None, bspec), "head": hspec},
        out_specs=(P(bspec), P(bspec)),
        body=body, reference=reference,
        gathers={"head": Gather(dim=0, size=d)},
        divisible=((v, tp),),
        comm=Comm("psum", backend="bulk", n_chunks=1))


def lm_loss(p, x, targets, weights, cfg: ArchConfig, run: RunConfig,
            rules: ShardingRules | None, *, chunk: int = 512):
    """Chunked vocab-parallel cross-entropy. x: (B,S,d); targets (B,S)."""
    head = p["lm_head"]
    b, s, d = x.shape
    v = head.shape[1]
    n_chunks = max(1, s // chunk) if s % chunk == 0 else 1
    xc = x.reshape(b, n_chunks, s // n_chunks, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, n_chunks, s // n_chunks).transpose(1, 0, 2)
    wc = weights.reshape(b, n_chunks, s // n_chunks).transpose(1, 0, 2)
    island = lm_loss_island(run, rules, b, d, v)
    tot, cnt = island(xc=xc, tc=tc, wc=wc, head=head)
    return jnp.sum(tot) / jnp.maximum(jnp.sum(cnt), 1.0)


@jax.named_scope("head")
def lm_logits(p, x, rules: ShardingRules | None):
    """Full logits for serving (B, S, V)."""
    logits = jnp.einsum("bsd,dv->bsv", x, p["lm_head"]).astype(jnp.float32)
    if rules is not None:
        logits = constrain(logits, rules,
                           P(rules.dp, None,
                             rules.dim(logits.shape[-1], rules.tp)))
    return logits


# ---------------------------------------------------------------------------
# Plan report: the whole forward pass's overlap schedule from one object
# ---------------------------------------------------------------------------

def _forward_islands(cfg: ArchConfig, run: RunConfig,
                     rules: ShardingRules | None, *, batch: int = 8,
                     seq: int = 128, phase: str = "all",
                     page_size: int = 0) -> list:
    """Every PK island a forward pass (and a decode step) of this
    (cfg, run, mesh) will build — the single island inventory behind both
    ``island_plans`` and ``island_comm_sweeps``.

    ``phase`` narrows the inventory to one serving bucket's step program:
    ``"prefill"`` is the full-sequence cache-building forward (GEMM islands
    at m = B_loc·seq, no decode or loss islands), ``"decode"`` the one-token
    step (GEMM islands at m = B_loc·1 plus the decode-attention island);
    ``"all"`` (default) is the historical union every launcher prints.

    ``page_size`` > 0 switches the serving phases to the paged-cache island
    set: decode keeps the ``decode_attn`` name and Comm coordinates (frozen
    plans apply unchanged) and prefill gains the ``paged_prefill_attn``
    merge island the chunk program runs.
    """
    if phase not in ("all", "prefill", "decode"):
        raise ValueError(f"unknown island phase {phase!r}")
    b = batch
    s = 1 if phase == "decode" else seq
    pattern = cfg.layer_pattern()
    v = cfg.padded_vocab(rules.mesh.shape[rules.tp] if rules else 16)
    islands = [embed_island(run, rules, v, cfg.d_model, b)]
    if any(sp.mixer == "attn" for sp in pattern):
        if run.sp_attention != "none" and phase == "all":
            islands.append(
                sp_attention_island(cfg, run, rules, b, s, causal=True))
        islands.append(attn_out_island(cfg, run, rules, b, s))
        if page_size and phase == "prefill":
            islands.append(paged_prefill_island(
                cfg, run, rules, b, s, page_size,
                window=cfg.sliding_window))
        if phase in ("all", "decode"):
            if page_size and phase == "decode":
                islands.append(paged_decode_island(
                    cfg, run, rules, b, page_size,
                    window=cfg.sliding_window))
            else:
                islands.append(decode_island(
                    cfg, run, rules, b, seq, long_ctx=False, pos=0,
                    window=cfg.sliding_window))
    if any(sp.mlp == "dense" for sp in pattern):
        islands.append(mlp_island(cfg, run, rules, b, s))
    if any(sp.mlp == "moe" for sp in pattern):
        islands.append(moe_island(cfg, run, rules, b, s))
    if phase == "all":
        islands.append(lm_loss_island(run, rules, b, cfg.d_model, v))
    return islands


def island_plans(cfg: ArchConfig, run: RunConfig,
                 rules: ShardingRules | None, *, batch: int = 8,
                 seq: int = 128, phase: str = "all",
                 page_size: int = 0) -> list[IslandPlan]:
    """Trace-free overlap schedule for every PK island a forward pass (and a
    decode step) of this (cfg, run, mesh) will build: chosen backend, chunk
    count, hidden fraction (measured on a calibrated mesh, else predicted)
    — or the fallback reason. Launchers print this via
    ``repro.core.template.render_plans``; the dry-run records it in its JSON
    artifact. ``phase`` narrows to one serving bucket's step program (see
    ``_forward_islands``) — the serving engine resolves a plan table per
    shape bucket this way; ``page_size`` > 0 swaps in the paged-cache
    serving islands."""
    return [i.plan() for i in _forward_islands(cfg, run, rules,
                                               batch=batch, seq=seq,
                                               phase=phase,
                                               page_size=page_size)]


def island_comm_sweeps(cfg: ArchConfig, run: RunConfig,
                       rules: ShardingRules | None, *, batch: int = 8,
                       seq: int = 128, phase: str = "all"):
    """Per-island calibration sweep specs (``autotune.IslandSweep``) for
    every active GEMM-collective *and all-to-all* island of this forward
    pass — the driver behind ``python -m repro.autotune calibrate
    --per-island``. GEMM islands carry the exact (op, m, n, k, dtype)
    coordinates their ``CommContext`` dispatch queries with; a2a islands
    (Ulysses re-sharding, MoE dispatch) carry the local payload shape and
    split/concat axes, stored under ``CommContext.a2a_coords``. ``phase``
    narrows to one serving bucket's inventory, so the serving buckets can
    be calibrated at their exact shapes."""
    from repro.core.autotune import IslandSweep
    from repro.core.comms import GEMM_OP_KIND, CommContext
    sweeps = []
    for isl in _forward_islands(cfg, run, rules, batch=batch, seq=seq,
                                phase=phase):
        c = isl.comm
        if c is None or isl.fallback_reason() is not None:
            continue
        if c.op in GEMM_OP_KIND:
            sweeps.append(IslandSweep(island=isl.island_key, op=c.op,
                                      m=c.m, n=c.n, k=c.k,
                                      dtype_bytes=c.dtype_bytes))
        elif c.op == "all_to_all" and c.shape is not None:
            m, n, k = CommContext.a2a_coords(c.shape, c.split_axis,
                                             c.concat_axis)
            sweeps.append(IslandSweep(
                island=isl.island_key, op="all_to_all", m=m, n=n, k=k,
                dtype_bytes=c.dtype_bytes, shape=tuple(c.shape),
                split_axis=c.split_axis, concat_axis=c.concat_axis))
    if any(sp.mlp == "moe" for sp in cfg.layer_pattern()) \
            and rules is not None:
        # MoE a2a dispatch (pk_moe_a2a): the destination-major payload
        # (n_dev, E_loc, capacity, d) transposed with split==concat==0.
        # Not a declared island Comm (the moe island's dominant collective
        # is the combine psum), but the chunk policy dispatches off these
        # rows when RunConfig.moe_chunks = 0 (auto).
        n_dev = rules.mesh.shape[rules.tp]
        if cfg.n_experts % n_dev == 0:
            b_loc = rules.local_batch(batch)
            s = 1 if phase == "decode" else seq     # the bucket's token count
            n_tok = batch * s if run.serve_moe_tp_data else b_loc * s
            plan = pk_moe.dispatch_plan(n_tok, n_experts=cfg.n_experts,
                                        top_k=cfg.top_k,
                                        capacity_factor=cfg.capacity_factor)
            shape = (n_dev, cfg.n_experts // n_dev, plan.cap, cfg.d_model)
            m, n, k = CommContext.a2a_coords(shape, 0, 0)
            sweeps.append(IslandSweep(
                island=island_key("moe_dispatch", "all_to_all",
                                  _dtype_bytes(cfg)),
                op="all_to_all", m=m, n=n, k=k,
                dtype_bytes=_dtype_bytes(cfg), shape=shape,
                split_axis=0, concat_axis=0))
    return sweeps
