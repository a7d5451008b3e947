"""input_specs(): ShapeDtypeStruct stand-ins for every model input of every
(arch × shape-cell), plus the abstract train/serve state — weak-type-correct,
shardable, zero allocation (assignment MULTI-POD DRY-RUN §2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, RunConfig, ShapeCell
from repro.models import transformer as T
from repro.models.sharding import ShardingRules
from repro.optim.adamw import AdamWState
from repro.train.step import TrainState


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def batch_specs(cfg: ArchConfig, cell: ShapeCell, rules: ShardingRules):
    """(abstract_batch, batch_shardings) for a train/prefill cell."""
    b, s = cell.global_batch, cell.seq_len
    dp = rules.dp
    batch = {"tokens": _sds((b, s), jnp.int32)}
    specs = {"tokens": P(dp, None)}
    if cell.kind == "train":
        batch["targets"] = _sds((b, s), jnp.int32)
        batch["weights"] = _sds((b, s), jnp.float32)
        specs["targets"] = P(dp, None)
        specs["weights"] = P(dp, None)
    if cfg.frontend == "vision":
        batch["frontend_embeds"] = _sds((b, cfg.n_frontend_tokens, cfg.d_model),
                                        jnp.bfloat16)
        specs["frontend_embeds"] = P(dp, None, None)
    if cfg.encoder_decoder:
        batch["enc_embeds"] = _sds((b, s, cfg.d_model), jnp.bfloat16)
        specs["enc_embeds"] = P(dp, None, None)
    return batch, specs


def train_state_specs(cfg: ArchConfig, run: RunConfig, rules: ShardingRules,
                      moment_dtype=jnp.float32):
    """(abstract TrainState, sharding tree). Moments inherit param specs."""
    tmpl = T.param_template(cfg, run, rules)
    params = T.abstract_params(tmpl)
    pspecs = T.param_specs(tmpl)
    moments = jax.tree.map(lambda p: _sds(p.shape, moment_dtype), params)
    state = TrainState(
        params=params,
        opt=AdamWState(step=_sds((), jnp.int32), m=moments, v=moments))
    specs = TrainState(
        params=pspecs,
        opt=AdamWState(step=P(), m=pspecs, v=pspecs))
    return state, specs


def decode_specs(cfg: ArchConfig, run: RunConfig, rules: ShardingRules,
                 cell: ShapeCell):
    """(abstract (params, cache, tokens), shardings) for a decode cell."""
    b, s = cell.global_batch, cell.seq_len
    long_ctx = cell.name == "long_500k"
    tmpl = T.param_template(cfg, run, rules)
    params = T.abstract_params(tmpl)
    pspecs = T.param_specs(tmpl)
    ct = T.cache_template(cfg, run, rules, batch=b, s_max=s,
                          enc_len=s if cfg.encoder_decoder else 0,
                          long_ctx=long_ctx)
    cache = T.abstract_params(ct)
    cspecs = T.param_specs(ct)
    dp = rules.dp
    tokens = _sds((b, 1), jnp.int32)
    tspec = P(rules.dim(b, dp), None)
    return (params, cache, tokens), (pspecs, cspecs, tspec)


def named(mesh: Mesh, tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree, is_leaf=lambda x: isinstance(x, P))


def init_params(tmpl, seed: int, d_model: int, mesh: Mesh | None = None):
    """``T.init_params`` as one jitted program whose outputs land directly
    in the template's shardings on ``mesh`` (the default device when None).
    Eager init would first build the whole unsharded model, f32 draws
    included, on one device."""
    kw = {} if mesh is None else {
        "out_shardings": named(mesh, T.param_specs(tmpl))}
    init = functools.partial(T.init_params, tmpl, d_model=d_model)
    return jax.jit(init, **kw)(jax.random.PRNGKey(seed))
