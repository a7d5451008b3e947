"""Model assembly: parameter templates (shape+sharding+init in one source of
truth), scan-over-periods forward passes for train / prefill / decode, for all
assigned families (dense, MoE, SSM, hybrid, enc-dec, VLM/audio-stub).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ArchConfig, LayerSpec, RunConfig
from repro.core.moe import ep_tp_split
from repro.models import layers as L
from repro.models import ssm as S
from repro.models.sharding import ShardingRules

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


@dataclasses.dataclass(frozen=True)
class PD:
    """Parameter definition: shape + sharding spec + init rule."""
    shape: tuple[int, ...]
    spec: P
    init: str = "normal"          # normal | zeros | ones | a_log | dt_bias
    dtype: Any = jnp.bfloat16

    def stacked(self, n: int) -> "PD":
        return dataclasses.replace(self, shape=(n, *self.shape),
                                   spec=P(None, *self.spec))


def _is_pd(x):
    return isinstance(x, PD)


# ---------------------------------------------------------------------------
# Templates
# ---------------------------------------------------------------------------

def _attn_pds(cfg: ArchConfig, r: ShardingRules | None, dt) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    sp = (lambda i, o, tp_dim: r.w2d(i, o, tp_dim=tp_dim)) if r else \
        (lambda i, o, tp_dim: P(None, None))
    return {
        "norm": PD((d,), P(None), "ones", dt),
        "wq": PD((d, hq * hd), sp(d, hq * hd, 1), "normal", dt),
        "wk": PD((d, hkv * hd), sp(d, hkv * hd, 1), "normal", dt),
        "wv": PD((d, hkv * hd), sp(d, hkv * hd, 1), "normal", dt),
        "wo": PD((hq * hd, d), sp(hq * hd, d, 0), "normal", dt),
    }


def _mlp_pds(cfg: ArchConfig, r: ShardingRules | None, dt) -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    sp = (lambda i, o, tp_dim: r.w2d(i, o, tp_dim=tp_dim)) if r else \
        (lambda i, o, tp_dim: P(None, None))
    out = {
        "norm": PD((d,), P(None), "ones", dt),
        "w1": PD((d, ff), sp(d, ff, 1), "normal", dt),
        "w2": PD((ff, d), sp(ff, d, 0), "normal", dt),
    }
    if cfg.gated_mlp:
        out["w3"] = PD((d, ff), sp(d, ff, 1), "normal", dt)
    return out


def _moe_pds(cfg: ArchConfig, r: ShardingRules | None, dt) -> dict:
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    m = r.mesh.shape[r.tp] if r is not None else 1
    ep, tp_ff = ep_tp_split(e, m)
    e_loc, ff_loc = e // ep, ff // tp_ff
    fs = r.dim(d, r.fsdp_axes) if r is not None else None
    tp = r.tp if r is not None else None
    if r is not None and r.run.serve_moe_tp_data:
        # resident 2D TP: ff_loc sharded over the dp axes as tensor
        # parallelism — serving never all-gathers expert weights
        dpff = r.dim(ff_loc, r.dp)
        w1s = P(tp, None, None, dpff)
        w2s = P(tp, None, dpff, None)
    else:
        w1s = P(tp, None, fs, None)
        w2s = P(tp, None, None, fs)
    out = {
        "norm": PD((d,), P(None), "ones", dt),
        "router": PD((d, e), P(None, None), "normal", jnp.float32),
        # device-major PGL layout over the tp axis (DESIGN §4 EP×TP)
        "w1": PD((m, e_loc, d, ff_loc), w1s, "normal", dt),
        "w2": PD((m, e_loc, ff_loc, d), w2s, "normal", dt),
    }
    if cfg.gated_mlp:
        out["w3"] = PD((m, e_loc, d, ff_loc), w1s, "normal", dt)
    return out


def _mamba_pds(cfg: ArchConfig, r: ShardingRules | None, dt) -> dict:
    d, di, n, ck, dtr = (cfg.d_model, cfg.d_inner, cfg.ssm_state,
                         cfg.conv_kernel, cfg.dtr)
    tp = r.tp if r is not None else None
    fs = r.dim(d, r.fsdp_axes) if r is not None else None
    tpd = (lambda s: r.dim(s, tp)) if r is not None else (lambda s: None)
    return {
        "norm": PD((d,), P(None), "ones", dt),
        "in_proj": PD((d, 2 * di), P(fs, tpd(2 * di)), "normal", dt),
        "conv_w": PD((di, ck), P(tpd(di), None), "normal", dt),
        "conv_b": PD((di,), P(tpd(di)), "zeros", dt),
        "x_proj": PD((di, dtr + 2 * n), P(tpd(di), None), "normal", dt),
        "dt_proj": PD((dtr, di), P(None, tpd(di)), "normal", dt),
        "dt_bias": PD((di,), P(tpd(di)), "dt_bias", jnp.float32),
        "A_log": PD((di, n), P(tpd(di), None), "a_log", jnp.float32),
        "D": PD((di,), P(tpd(di)), "ones", jnp.float32),
        "out_proj": PD((di, d), P(tpd(di), fs), "normal", dt),
    }


def _block_pds(spec: LayerSpec, cfg: ArchConfig, r, dt, *, cross: bool) -> dict:
    out = {}
    if spec.mixer == "attn":
        out["attn"] = _attn_pds(cfg, r, dt)
    else:
        out["mamba"] = _mamba_pds(cfg, r, dt)
    if cross:
        out["cross"] = _attn_pds(cfg, r, dt)
    if spec.mlp == "dense":
        out["mlp"] = _mlp_pds(cfg, r, dt)
    elif spec.mlp == "moe":
        out["moe"] = _moe_pds(cfg, r, dt)
    return out


def param_template(cfg: ArchConfig, run: RunConfig,
                   rules: ShardingRules | None) -> dict:
    """The full parameter tree as PDs (single source of truth for shapes,
    shardings and init)."""
    dt = DTYPES[cfg.dtype]
    d = cfg.d_model
    v = cfg.padded_vocab(rules.mesh.shape[rules.tp] if rules else 16)
    fs = rules.dim(d, rules.fsdp_axes) if rules is not None else None
    tpv = rules.dim(v, rules.tp) if rules is not None else None
    tree: dict[str, Any] = {
        "embed": PD((v, d), P(tpv, fs), "normal", dt),
        "final_norm": PD((d,), P(None), "ones", dt),
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = PD((d, v), P(fs, tpv), "normal", dt)
    pattern = cfg.layer_pattern()
    blocks = {}
    for i, spec in enumerate(pattern):
        pds = _block_pds(spec, cfg, rules, dt, cross=cfg.encoder_decoder)
        blocks[f"pos{i}"] = jax.tree.map(
            lambda pd: pd.stacked(cfg.n_periods), pds, is_leaf=_is_pd)
    tree["blocks"] = blocks
    if cfg.encoder_decoder:
        enc_pds = _block_pds(LayerSpec("attn", "dense"), cfg, rules, dt,
                             cross=False)
        tree["enc_blocks"] = jax.tree.map(
            lambda pd: pd.stacked(cfg.n_encoder_layers), enc_pds,
            is_leaf=_is_pd)
        tree["enc_final_norm"] = PD((d,), P(None), "ones", dt)
    return tree


def param_specs(template) -> Any:
    return jax.tree.map(lambda pd: pd.spec, template, is_leaf=_is_pd)


def abstract_params(template) -> Any:
    return jax.tree.map(lambda pd: jax.ShapeDtypeStruct(pd.shape, pd.dtype),
                        template, is_leaf=_is_pd)


def init_params(template, key, d_model: int) -> Any:
    leaves, treedef = jax.tree.flatten(template, is_leaf=_is_pd)
    keys = jax.random.split(key, len(leaves))
    scale = d_model ** -0.5

    def mk(pd: PD, k):
        if pd.init == "zeros":
            return jnp.zeros(pd.shape, pd.dtype)
        if pd.init == "ones":
            return jnp.ones(pd.shape, pd.dtype)
        if pd.init == "a_log":
            n = pd.shape[-1]
            return jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                                    pd.shape).astype(pd.dtype)
        if pd.init == "dt_bias":
            u = jax.random.uniform(k, pd.shape, jnp.float32,
                                   minval=1e-3, maxval=1e-1)
            return (u + jnp.log(-jnp.expm1(-u))).astype(pd.dtype)  # inv softplus
        return (jax.random.normal(k, pd.shape, jnp.float32)
                * scale).astype(pd.dtype)

    return jax.tree.unflatten(treedef, [mk(pd, k) for pd, k in
                                        zip(leaves, keys)])


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _bf16_grad_barrier(x):
    return x


def _bgb_fwd(x):
    return x, None


def _bgb_bwd(_, g):
    # Cast the residual-stream cotangent to bf16: the backward Megatron
    # all-reduces inherit this dtype — 2x less AR traffic, and bf16 is the
    # standard production choice for activation grads (§Perf G2).
    return (g.astype(jnp.bfloat16).astype(g.dtype) if g.dtype == jnp.float32
            else g,)


_bf16_grad_barrier.defvjp(_bgb_fwd, _bgb_bwd)


def _apply_block(bp, spec: LayerSpec, x, cfg, run, rules, *, causal=True,
                 enc_out=None, seq_sharded=False):
    """One layer, pre-norm residual. Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if run.bf16_backward_ars:
        x = _bf16_grad_barrier(x)
    if spec.mixer == "attn":
        a = bp["attn"]
        h = L.attention_block(a, L.rms_norm(a["norm"], x, cfg.norm_eps),
                              cfg, run, rules, causal=causal,
                              seq_sharded=seq_sharded)
        x = x + checkpoint_name(h, "subblock_out")
    else:
        mp = bp["mamba"]
        h, _ = S.mamba_block(mp, L.rms_norm(mp["norm"], x, cfg.norm_eps),
                             cfg, run, rules)
        x = x + checkpoint_name(h, "subblock_out")
    if enc_out is not None and "cross" in bp:
        cp = bp["cross"]
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        b, se, _ = enc_out.shape
        k = jnp.einsum("bsd,dh->bsh", enc_out, cp["wk"]).reshape(
            b, se, hkv, hd).transpose(0, 2, 1, 3)
        v = jnp.einsum("bsd,dh->bsh", enc_out, cp["wv"]).reshape(
            b, se, hkv, hd).transpose(0, 2, 1, 3)
        h = L.attention_block(cp, L.rms_norm(cp["norm"], x, cfg.norm_eps),
                              cfg, run, rules, causal=False, cross_kv=(k, v))
        x = x + h
    if spec.mlp == "dense":
        mp = bp["mlp"]
        h = L.mlp_block(mp, L.rms_norm(mp["norm"], x, cfg.norm_eps),
                        cfg, run, rules)
        x = x + checkpoint_name(h, "subblock_out")
    elif spec.mlp == "moe":
        mp = bp["moe"]
        h, a_l = L.moe_block(mp, L.rms_norm(mp["norm"], x, cfg.norm_eps),
                             cfg, run, rules)
        x = x + checkpoint_name(h, "subblock_out")
        aux = aux + a_l
    return x, aux


def _scan_blocks(blocks, x, cfg: ArchConfig, run: RunConfig, rules, *,
                 causal=True, enc_out=None, seq_sharded=False):
    """lax.scan over n_periods; each step applies the full layer pattern."""
    pattern = cfg.layer_pattern()

    def body(carry, period_params):
        x, aux = carry
        for i, spec in enumerate(pattern):
            x, a = _apply_block(period_params[f"pos{i}"], spec, x, cfg, run,
                                rules, causal=causal, enc_out=enc_out,
                                seq_sharded=seq_sharded)
            aux = aux + a
        return (x, aux), None

    if run.remat:
        policy = (jax.checkpoint_policies.save_only_these_names(
            "subblock_out") if run.save_collectives else None)
        body = jax.checkpoint(body, prevent_cse=False, policy=policy)
    if not run.scan_layers:                  # cost-calibration: no while loop
        carry = (x, jnp.zeros((), jnp.float32))
        for i in range(cfg.n_periods):
            carry, _ = body(carry, jax.tree.map(lambda a: a[i], blocks))
        return carry
    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)), blocks)
    return x, aux


def _scan_encoder(enc_blocks, x, cfg, run, rules):
    def body(carry, lp):
        x = carry
        x, _ = _apply_block(lp, LayerSpec("attn", "dense"), x, cfg, run,
                            rules, causal=False)
        return x, None

    if run.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    if not run.scan_layers:
        n_enc = jax.tree.leaves(enc_blocks)[0].shape[0]
        for i in range(n_enc):
            x, _ = body(x, jax.tree.map(lambda a: a[i], enc_blocks))
        return x
    x, _ = lax.scan(body, x, enc_blocks)
    return x


def _merge_frontend(x_tok, frontend_embeds, cfg: ArchConfig):
    """VLM: replace the first n_frontend_tokens embeddings with the stub
    patch embeddings (precomputed by input_specs)."""
    if frontend_embeds is None or cfg.frontend != "vision":
        return x_tok
    n = cfg.n_frontend_tokens
    return jnp.concatenate([frontend_embeds.astype(x_tok.dtype),
                            x_tok[:, n:]], axis=1)


def forward_train(params, batch, cfg: ArchConfig, run: RunConfig,
                  rules: ShardingRules | None, *, seq_sharded=False):
    """Returns (loss, metrics). batch keys: tokens (B,S), targets (B,S),
    weights (B,S) [+ frontend_embeds (B,n,d) | enc_embeds (B,Se,d)]."""
    tokens = batch["tokens"]
    x = L.embed_tokens(params, tokens, rules, run)
    x = _merge_frontend(x, batch.get("frontend_embeds"), cfg)
    if rules is not None:
        x = L.constrain(x, rules, rules.act_btd())

    enc_out = None
    if cfg.encoder_decoder:
        enc_x = batch["enc_embeds"].astype(x.dtype)
        enc_out = _scan_encoder(params["enc_blocks"], enc_x, cfg, run, rules)
        enc_out = L.rms_norm(params["enc_final_norm"], enc_out, cfg.norm_eps)

    x, aux = _scan_blocks(params["blocks"], x, cfg, run, rules,
                          causal=True, enc_out=enc_out,
                          seq_sharded=seq_sharded)
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    loss = L.lm_loss({"lm_head": head}, x, batch["targets"], batch["weights"],
                     cfg, run, rules, chunk=run.loss_chunk)
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux_loss": aux}


# ---------------------------------------------------------------------------
# Serving: prefill + decode with caches
# ---------------------------------------------------------------------------

def cache_template(cfg: ArchConfig, run: RunConfig, rules: ShardingRules | None,
                   *, batch: int, s_max: int, enc_len: int = 0,
                   long_ctx: bool = False, slot_pos: bool = False,
                   kv_dtype: str = "bf16") -> dict:
    """ShapeDtypeStruct+spec tree for the decode cache (PD-style).

    ``slot_pos=True`` gives the cache a per-slot ``(batch,)`` position
    vector instead of the lockstep scalar — the continuous-batching engine's
    decode pool holds sequences admitted at different times.

    ``kv_dtype="int8"`` stores the K/V slabs as int8 and adds per-(token,
    head) f32 scale planes (``k_scale``/``v_scale``, the slab shape minus
    hd) — the layers quantize on write and dequantize on read, roughly
    halving cache HBM. ``"bf16"`` (default) is byte-for-byte today's tree."""
    dt = DTYPES[cfg.dtype]
    hkv, hd, di, n, ck = (cfg.n_kv_heads, cfg.hd, cfg.d_inner, cfg.ssm_state,
                          cfg.conv_kernel)
    np_ = cfg.n_periods
    kv_spec = rules.kv_cache(hkv, batch, long_ctx=long_ctx) if rules else \
        P(None, None, None, None)
    ssm_spec = rules.ssm_cache(batch) if rules else P(None, None)
    bspec = rules.dim(batch, rules.dp) if rules else None
    pos_pd = PD((batch,), P(bspec), "zeros", jnp.int32) if slot_pos \
        else PD((), P(), "zeros", jnp.int32)
    tree: dict[str, Any] = {"pos": pos_pd, "blocks": {}}
    kv_dt = {"bf16": dt, "int8": jnp.int8}[kv_dtype]
    for i, spec in enumerate(cfg.layer_pattern()):
        if spec.mixer == "attn":
            kv = {
                "k": PD((np_, batch, hkv, s_max, hd), P(None, *kv_spec), "zeros", kv_dt),
                "v": PD((np_, batch, hkv, s_max, hd), P(None, *kv_spec), "zeros", kv_dt),
            }
            if kv_dtype == "int8":
                sspec = P(None, *kv_spec[:3])
                kv["k_scale"] = PD((np_, batch, hkv, s_max), sspec, "zeros",
                                   jnp.float32)
                kv["v_scale"] = PD((np_, batch, hkv, s_max), sspec, "zeros",
                                   jnp.float32)
            tree["blocks"][f"pos{i}"] = kv
        else:
            tree["blocks"][f"pos{i}"] = {
                "h": PD((np_, batch, di, n), P(None, *ssm_spec), "zeros", jnp.float32),
                "conv": PD((np_, batch, ck - 1, di),
                           P(None, bspec, None,
                             rules.dim(di, rules.tp) if rules else None),
                           "zeros", dt),
            }
    if cfg.encoder_decoder and enc_len:
        # cross K/V must be sequence-sharded too — replicated over the tp
        # axis it would cost O(B*Henc*Senc*hd) per device (27 GB observed)
        enc_sp = rules.dim(enc_len, rules.tp) if rules else None
        cross_spec = P(None, bspec, None, enc_sp, None)
        tree["cross"] = {
            "k": PD((np_ * len(cfg.layer_pattern()), batch, hkv, enc_len, hd),
                    cross_spec, "zeros", dt),
            "v": PD((np_ * len(cfg.layer_pattern()), batch, hkv, enc_len, hd),
                    cross_spec, "zeros", dt),
        }
    return tree


def _scan_cache(body, x, blocks, cache_blocks, cfg: ArchConfig,
                run: RunConfig):
    """Apply ``body(x, (period_params, period_cache)) -> (x, out)`` over the
    periods, each period's cache slice a scan input and ``out`` stacked as
    the scan output (unrolled without ``run.scan_layers``). Named
    ``cache_scan``: the cache slicing and the stacking carry it in their
    ``op_name`` metadata."""
    with jax.named_scope("cache_scan"):
        if run.scan_layers:
            return lax.scan(body, x, (blocks, cache_blocks))
        new_list = []
        for i in range(cfg.n_periods):
            x, nc = body(x, jax.tree.map(lambda a: a[i],
                                         (blocks, cache_blocks)))
            new_list.append(nc)
        return x, jax.tree.map(lambda *xs: jnp.stack(xs), *new_list)


def write_decode_rows(cache_blocks, rows, pos, cfg: ArchConfig,
                      run: RunConfig, rules: ShardingRules | None, *,
                      long_ctx: bool = False):
    """Write the K/V rows one decode step's layer scan returned into the
    stacked slabs, once, after the scan. ``rows``: {attention position:
    {leaf: (n_periods, B, Hkv, 1[, hd])}}; each leaf takes one in-place
    write at (period, slot, ``pos[slot]``) — a dynamic_update_slice for
    scalar ``pos`` — through ``L.decode_write_island`` (scope
    ``cache_write``). Returns the written entries of ``cache_blocks``."""
    kw = {"pos": pos} if jnp.ndim(pos) else {}
    out = {}
    for name, r in rows.items():
        c = cache_blocks[name]
        island = L.decode_write_island(
            cfg, run, rules, c["k"].shape[1], c["k"].shape[3],
            long_ctx=long_ctx, pos=pos, quant="k_scale" in c)
        out[name] = island(cache=c, rows=r, **kw)
    return out


def decode_step(params, cache, tokens, cfg: ArchConfig, run: RunConfig,
                rules: ShardingRules | None, *, long_ctx: bool = False):
    """One decode step. tokens: (B, 1) int32. Returns (logits, new_cache).

    Scans over periods with the per-period cache slices as scan inputs.
    Slab caches: the scan returns only what changed — each attention
    layer's new K/V rows, each Mamba layer's state — and the rows are
    written into the slabs after the scan (``write_decode_rows``), so the
    donated cache is updated in place. RoPE position = cache["pos"]. A
    cache carrying ``block_tables`` routes attention through the paged-pool
    islands (``runtime/paging.py`` layout), whose pools still thread
    through the scan — same step signature, so ``make_serve_step`` and the
    engine's jit/donation story are unchanged.
    """
    pos = cache["pos"]
    bt = cache.get("block_tables")
    x = L.embed_tokens(params, tokens, rules, run)
    pattern = cfg.layer_pattern()

    def body(x, args):
        period_params, period_cache = args
        new_cache = {}
        for i, spec in enumerate(pattern):
            bp = period_params[f"pos{i}"]
            cp = period_cache[f"pos{i}"]
            if spec.mixer == "attn":
                a = bp["attn"]
                scales = ({"k_scale": cp["k_scale"], "v_scale": cp["v_scale"]}
                          if "k_scale" in cp else {})
                if bt is not None:          # the whole updated pools
                    h, *kv = L.paged_decode_attention(
                        a, L.rms_norm(a["norm"], x, cfg.norm_eps), cp["k"],
                        cp["v"], bt, pos, cfg, run, rules, **scales)
                else:                       # the new rows
                    h, *kv = L.decode_attention(
                        a, L.rms_norm(a["norm"], x, cfg.norm_eps), cp["k"],
                        cp["v"], pos, cfg, run, rules, long_ctx=long_ctx,
                        **scales)
                x = x + h
                new_cache[f"pos{i}"] = dict(zip(
                    ("k", "v", "k_scale", "v_scale"), kv))
            else:
                mp = bp["mamba"]
                h, (nh, nconv) = S.mamba_block(
                    mp, L.rms_norm(mp["norm"], x, cfg.norm_eps), cfg, run,
                    rules, cache=(cp["h"], cp["conv"]))
                x = x + h
                new_cache[f"pos{i}"] = {"h": nh, "conv": nconv}
            if spec.mlp == "dense":
                mp = bp["mlp"]
                x = x + L.mlp_block(mp, L.rms_norm(mp["norm"], x, cfg.norm_eps),
                                    cfg, run, rules)
            elif spec.mlp == "moe":
                mp = bp["moe"]
                h, _ = L.moe_block(mp, L.rms_norm(mp["norm"], x, cfg.norm_eps),
                                   cfg, run, rules)
                x = x + h
        return x, new_cache

    x, new_blocks = _scan_cache(body, x, params["blocks"], cache["blocks"],
                                cfg, run)
    if bt is None:
        rows = {f"pos{i}": new_blocks[f"pos{i}"]
                for i, spec in enumerate(pattern) if spec.mixer == "attn"}
        new_blocks = {**new_blocks, **write_decode_rows(
            cache["blocks"], rows, pos, cfg, run, rules, long_ctx=long_ctx)}
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    logits = L.lm_logits({"lm_head": head}, x, rules)
    new_cache = {"pos": pos + 1, "blocks": new_blocks}
    if bt is not None:
        new_cache["block_tables"] = bt
    if "cross" in cache:
        new_cache["cross"] = cache["cross"]
    return logits, new_cache


def decode_step_encdec(params, cache, tokens, cfg: ArchConfig, run: RunConfig,
                       rules: ShardingRules | None):
    """Whisper decode: self-attention cache + precomputed cross K/V. The
    scan returns each layer's new self-attention K/V rows, written into the
    cache after it (``write_decode_rows``)."""
    pos = cache["pos"]
    x = L.embed_tokens(params, tokens, rules, run)
    pattern = cfg.layer_pattern()
    ck, cv = cache["cross"]["k"], cache["cross"]["v"]

    def body(carry, args):
        x, li = carry
        period_params, period_cache = args
        new_cache = {}
        for i, spec in enumerate(pattern):
            bp = period_params[f"pos{i}"]
            cp = period_cache[f"pos{i}"]
            a = bp["attn"]
            h, nk, nv = L.decode_attention(
                a, L.rms_norm(a["norm"], x, cfg.norm_eps), cp["k"], cp["v"],
                pos, cfg, run, rules)
            x = x + h
            new_cache[f"pos{i}"] = {"k": nk, "v": nv}
            crp = bp["cross"]
            h, _, _ = L.decode_attention(
                crp, L.rms_norm(crp["norm"], x, cfg.norm_eps), None, None,
                pos, cfg, run, rules, cross_kv=(ck[li], cv[li]))
            x = x + h
            mp = bp["mlp"]
            x = x + L.mlp_block(mp, L.rms_norm(mp["norm"], x, cfg.norm_eps),
                                cfg, run, rules)
        return (x, li + 1), new_cache

    if not run.scan_layers:
        carry = (x, 0)
        new_list = []
        for i in range(cfg.n_periods):
            carry, nc = body(carry, jax.tree.map(
                lambda a: a[i], (params["blocks"], cache["blocks"])))
            new_list.append(nc)
        x, _ = carry
        new_blocks = jax.tree.map(lambda *xs: jnp.stack(xs), *new_list)
    else:
        (x, _), new_blocks = lax.scan(body, (x, 0),
                                      (params["blocks"], cache["blocks"]))
    new_blocks = write_decode_rows(cache["blocks"], new_blocks, pos, cfg, run,
                                   rules)
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    logits = L.lm_logits({"lm_head": head}, x, rules)
    return logits, {"pos": pos + 1, "blocks": new_blocks,
                    "cross": cache["cross"]}


def prefill_step(params, cache, tokens, prompt_lens, cfg: ArchConfig,
                 run: RunConfig, rules: ShardingRules | None):
    """Batched cache-building prefill: ONE full-sequence forward over the
    (right-padded) prompts writes every layer's K/V — and SSM state — into
    the decode cache and returns each slot's next-token logits.

    tokens: (B, L) int32 right-padded prompts; prompt_lens: (B,) real
    lengths (or a scalar for uniform lockstep prefill). Returns
    (logits (B, 1, V) at each slot's last real position, cache) with
    ``cache["pos"]`` set to the prompt lengths. Right-padding is masked for
    attention (decode attends ``ki < pos``); SSM state cannot mask pads, so
    SSM/hybrid callers must prefill at exact prompt length (the serving
    engine's ``exact_buckets``). The GEMM islands inside run at the bucket's
    (B, L) coordinates — the prefill half of the per-bucket plan story.
    """
    if cfg.encoder_decoder:
        raise NotImplementedError(
            "batched cache prefill covers decoder-only models; the enc-dec "
            "path precomputes cross K/V separately (decode_step_encdec)")
    b, s = tokens.shape
    x = L.embed_tokens(params, tokens, rules, run)
    if rules is not None:
        x = L.constrain(x, rules, rules.act_btd())
    pattern = cfg.layer_pattern()

    def body(x, args):
        period_params, period_cache = args
        new_cache = {}
        for i, spec in enumerate(pattern):
            bp = period_params[f"pos{i}"]
            cp = period_cache[f"pos{i}"]
            if spec.mixer == "attn":
                a = bp["attn"]
                scales = ({"k_scale": cp["k_scale"], "v_scale": cp["v_scale"]}
                          if "k_scale" in cp else {})
                h, nk, nv, *ns = L.prefill_attention_block(
                    a, L.rms_norm(a["norm"], x, cfg.norm_eps), cp["k"],
                    cp["v"], cfg, run, rules, **scales)
                x = x + h
                nc = {"k": nk, "v": nv}
                if scales:
                    nc["k_scale"], nc["v_scale"] = ns
                new_cache[f"pos{i}"] = nc
            else:
                mp = bp["mamba"]
                h, (nh, nconv) = S.mamba_block(
                    mp, L.rms_norm(mp["norm"], x, cfg.norm_eps), cfg, run,
                    rules, cache=(cp["h"], cp["conv"]))
                x = x + h
                new_cache[f"pos{i}"] = {"h": nh, "conv": nconv}
            if spec.mlp == "dense":
                mp = bp["mlp"]
                x = x + L.mlp_block(mp, L.rms_norm(mp["norm"], x,
                                                   cfg.norm_eps),
                                    cfg, run, rules)
            elif spec.mlp == "moe":
                mp = bp["moe"]
                h, _ = L.moe_block(mp, L.rms_norm(mp["norm"], x,
                                                  cfg.norm_eps),
                                   cfg, run, rules)
                x = x + h
        return x, new_cache

    x, new_blocks = _scan_cache(body, x, params["blocks"], cache["blocks"],
                                cfg, run)
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    # per-slot last REAL position only — never the (B, L, V) logits
    idx = jnp.reshape(jnp.asarray(prompt_lens) - 1, (-1, 1, 1))
    x_last = jnp.take_along_axis(
        x, jnp.broadcast_to(idx, (b, 1, x.shape[-1])), axis=1)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    logits = L.lm_logits({"lm_head": head}, x_last, rules)
    new_pos = (jnp.broadcast_to(jnp.asarray(prompt_lens), (b,))
               if jnp.ndim(cache["pos"]) else
               jnp.asarray(prompt_lens).reshape(()).astype(jnp.int32))
    new_cache = {"pos": new_pos.astype(jnp.int32), "blocks": new_blocks}
    if "cross" in cache:
        new_cache["cross"] = cache["cross"]
    return logits, new_cache


def prefill_paged_step(params, cache, tokens, block_tables, prompt_lens,
                       chunk_start, write_from, cfg: ArchConfig,
                       run: RunConfig, rules: ShardingRules | None):
    """One chunk of paged cache-building prefill.

    tokens: (G, cl) — the chunk's token window, global positions
    [chunk_start, chunk_start+cl); block_tables: (G, P) the *group's* page
    mapping (NOT the live cache rows — those stay at the −1 sentinel until
    the final chunk commits, so interleaved decode ticks cannot touch
    half-built pages); prompt_lens: (G,) real lengths; write_from: (G,)
    per-slot floor below which K/V writes are suppressed (positions already
    covered by shared prefix pages). Returns (logits (G, 1, V) at each
    slot's last real position *clamped into this chunk* — the engine keeps
    the logits from the chunk containing L−1 — and the cache with updated
    pools). Attention-only architectures (paged_cache_template validates).
    """
    b, s = tokens.shape
    x = L.embed_tokens(params, tokens, rules, run)
    if rules is not None:
        x = L.constrain(x, rules, rules.act_btd())
    pattern = cfg.layer_pattern()
    c0 = jnp.asarray(chunk_start, jnp.int32)
    wf = jnp.asarray(write_from, jnp.int32)

    def body(x, args):
        period_params, period_cache = args
        new_cache = {}
        for i, spec in enumerate(pattern):
            bp = period_params[f"pos{i}"]
            cp = period_cache[f"pos{i}"]
            assert spec.mixer == "attn", "paged prefill is attention-only"
            a = bp["attn"]
            scales = ({"k_scale": cp["k_scale"], "v_scale": cp["v_scale"]}
                      if "k_scale" in cp else {})
            h, nk, nv, *ns = L.paged_prefill_attention_block(
                a, L.rms_norm(a["norm"], x, cfg.norm_eps), cp["k"],
                cp["v"], block_tables, c0, wf, cfg, run, rules, **scales)
            x = x + h
            nc = {"k": nk, "v": nv}
            if scales:
                nc["k_scale"], nc["v_scale"] = ns
            new_cache[f"pos{i}"] = nc
            if spec.mlp == "dense":
                mp = bp["mlp"]
                x = x + L.mlp_block(mp, L.rms_norm(mp["norm"], x,
                                                   cfg.norm_eps),
                                    cfg, run, rules)
            elif spec.mlp == "moe":
                mp = bp["moe"]
                h, _ = L.moe_block(mp, L.rms_norm(mp["norm"], x,
                                                  cfg.norm_eps),
                                   cfg, run, rules)
                x = x + h
        return x, new_cache

    x, new_blocks = _scan_cache(body, x, params["blocks"], cache["blocks"],
                                cfg, run)
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    # each slot's last real position clamped into this chunk's window — the
    # engine keeps the logits row from the chunk that contains L−1
    idx = jnp.clip(jnp.asarray(prompt_lens) - 1 - c0, 0, s - 1)
    idx = jnp.reshape(idx, (-1, 1, 1))
    x_last = jnp.take_along_axis(
        x, jnp.broadcast_to(idx, (b, 1, x.shape[-1])), axis=1)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    logits = L.lm_logits({"lm_head": head}, x_last, rules)
    # pos and the live block tables pass through untouched: the engine
    # commits both host-side only after the final chunk
    return logits, {"pos": cache["pos"], "blocks": new_blocks,
                    "block_tables": cache["block_tables"]}


def forward_prefill(params, batch, cfg: ArchConfig, run: RunConfig,
                    rules: ShardingRules | None):
    """Prefill forward: full-sequence logits for the last position.

    For the dry-run's prefill cells this is the train forward without the
    loss (cache building is exercised by the serving example; the dominant
    cost — the full forward — is identical)."""
    tokens = batch["tokens"]
    x = L.embed_tokens(params, tokens, rules, run)
    x = _merge_frontend(x, batch.get("frontend_embeds"), cfg)
    if rules is not None:
        x = L.constrain(x, rules, rules.act_btd())
    enc_out = None
    if cfg.encoder_decoder:
        enc_x = batch["enc_embeds"].astype(x.dtype)
        enc_out = _scan_encoder(params["enc_blocks"], enc_x, cfg, run, rules)
        enc_out = L.rms_norm(params["enc_final_norm"], enc_out, cfg.norm_eps)
    x, _ = _scan_blocks(params["blocks"], x, cfg, run, rules, causal=True,
                        enc_out=enc_out)
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    return L.lm_logits({"lm_head": head}, x[:, -1:], rules)
