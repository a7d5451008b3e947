"""All assigned archs on a 2x2 (data x model) mesh with PK islands: train
forward+grads finite, sharded decode runs, prefill/decode consistency."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import RunConfig
from repro.models import (cache_template, decode_step, decode_step_encdec,
                          forward_train, init_params, param_template)
from repro.models.sharding import ShardingRules
from repro.models.transformer import param_specs, prefill_step


def _setup(arch, mesh, run):
    cfg = get_config(arch).reduced()
    rules = ShardingRules(mesh, run)
    tmpl = param_template(cfg, run, rules)
    params = init_params(tmpl, jax.random.PRNGKey(0), cfg.d_model)
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, param_specs(tmpl))
    return cfg, rules, params


def test_jitted_init_lands_in_the_template_shardings(mesh22):
    """launch.specs.init_params draws the weights in one program whose
    outputs already carry the template's shardings (no whole unsharded copy
    on one device first) and, on the CPU, equal the eager init bit for
    bit."""
    from repro.launch.specs import init_params as sharded_init
    run = RunConfig(dp_axes=("data",), fsdp=True, pk_overlap=True)
    cfg = get_config("tinyllama-1.1b").reduced()
    tmpl = param_template(cfg, run, ShardingRules(mesh22, run))
    got = sharded_init(tmpl, 0, cfg.d_model, mesh22)
    eager = init_params(tmpl, jax.random.PRNGKey(0), cfg.d_model)

    def check(g, e, s):
        assert g.sharding.is_equivalent_to(NamedSharding(mesh22, s), g.ndim)
        assert (g.shape, g.dtype) == (e.shape, e.dtype)
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(e, np.float32))
    jax.tree.map(check, got, eager, param_specs(tmpl))


def _batch(cfg, b, s):
    batch = {"tokens": jnp.zeros((b, s), jnp.int32),
             "targets": jnp.ones((b, s), jnp.int32),
             "weights": jnp.ones((b, s), jnp.float32)}
    if cfg.frontend == "vision":
        batch["frontend_embeds"] = jnp.ones(
            (b, cfg.n_frontend_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.encoder_decoder:
        batch["enc_embeds"] = jnp.ones((b, s, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_sharded_train_and_decode(arch, mesh22):
    run = RunConfig(dp_axes=("data",), fsdp=True, pk_overlap=True)
    cfg, rules, params = _setup(arch, mesh22, run)
    b, s = 4, 32
    batch = _batch(cfg, b, s)

    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, bt: forward_train(p, bt, cfg, run, rules)[0]))(params, batch)
    assert np.isfinite(float(loss)), arch
    gnorm = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0, arch

    ct = cache_template(cfg, run, rules, batch=b, s_max=s,
                        enc_len=s if cfg.encoder_decoder else 0)
    cache = init_params(ct, jax.random.PRNGKey(1), cfg.d_model)
    cache = jax.tree.map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh22, sp)),
        cache, param_specs(ct))
    step = decode_step_encdec if cfg.encoder_decoder else decode_step
    logits, _ = jax.jit(lambda p, c, t: step(p, c, t, cfg, run, rules))(
        params, cache, jnp.zeros((b, 1), jnp.int32))
    assert np.all(np.isfinite(np.asarray(logits, np.float32))), arch


def test_pk_vs_baseline_same_loss(mesh22):
    """PK overlapped islands must not change the math."""
    arch = "tinyllama-1.1b"
    batchd = None
    losses = {}
    for pk in (True, False):
        run = RunConfig(dp_axes=("data",), fsdp=True, pk_overlap=pk)
        cfg, rules, params = _setup(arch, mesh22, run)
        batch = _batch(cfg, 4, 32)
        loss, _ = jax.jit(lambda p, bt, run=run, rules=rules:
                          forward_train(p, bt, cfg, run, rules))(params, batch)
        losses[pk] = float(loss)
    assert abs(losses[True] - losses[False]) < 2e-2, losses


def test_decode_matches_teacher_forcing(mesh22):
    """Token-by-token decode with the sharded KV cache must reproduce the
    prefill (full-forward) logits — the serving-path correctness oracle."""
    from repro.models import forward_prefill
    arch = "tinyllama-1.1b"
    run = RunConfig(dp_axes=("data",), fsdp=False, pk_overlap=False)
    cfg, rules, params = _setup(arch, mesh22, run)
    b, s = 2, 8
    key = jax.random.PRNGKey(3)
    toks = jax.random.randint(key, (b, s), 0, cfg.vocab_size)

    logits_full = forward_prefill(params, {"tokens": toks}, cfg, run, rules)

    ct = cache_template(cfg, run, rules, batch=b, s_max=s)
    cache = init_params(ct, jax.random.PRNGKey(1), cfg.d_model)
    cache = jax.tree.map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh22, sp)),
        cache, param_specs(ct))
    step = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg, run, rules))
    for i in range(s):
        logits_step, cache = step(params, cache, toks[:, i:i + 1])
    np.testing.assert_allclose(
        np.asarray(logits_step[:, 0]), np.asarray(logits_full[:, 0]),
        rtol=2e-2, atol=2e-2)


#: cases of one decode step against the full forward of the same tokens
DECODE_CASES = {
    "bf16_mixed": {"lens": (3, 7, 12, 5)},
    "int8": {"lens": (3, 7, 12, 5), "kv_dtype": "int8"},
    "window": {"lens": (6, 9, 12, 5), "window": 4},
    "parked": {"lens": (3, 7, 12, 5), "parked": 1},
    "scalar": {"lens": 8},
    "unscanned": {"lens": (3, 7, 12, 5), "scan_layers": False},
    # in f32: bf16 rounding parts the Mamba block's chunked prefill scan
    # from its one-token recurrence by more than the attention path
    "hybrid": {"lens": 6, "arch": "jamba-1.5-large-398b", "dtype": "float32"},
    "sharded": {"lens": (3, 7, 12, 5), "mesh": True},
    "sharded_int8_parked": {"lens": (3, 7, 12, 5), "mesh": True,
                            "kv_dtype": "int8", "parked": 2},
}


def _dequant(entry):
    """{leaf: f32 array} of one attention position's K/V as attention
    reads it (int8 leaves times their scale planes)."""
    out = {}
    for n in ("k", "v"):
        x = np.asarray(entry[n], np.float32)
        if f"{n}_scale" in entry:
            x = x * np.asarray(entry[f"{n}_scale"])[..., None]
        out[n] = x
    return out


@pytest.mark.parametrize("case", DECODE_CASES)
def test_decode_rows_match_teacher_forcing(case, request):
    """Decode attends to the read-only cache plus the step's new row and
    writes the row after the layer scan. Teacher-forced from a prefill at
    per-slot lengths, each step's logits equal the prefill of one more
    token at every live slot, the rows it wrote equal that prefill's
    cache, every other cache entry is left as it was, and a parked slot
    (pos >= s_max) writes nothing."""
    kw = DECODE_CASES[case]
    cfg = get_config(kw.get("arch", "tinyllama-1.1b")).reduced()
    if "window" in kw:
        cfg = dataclasses.replace(cfg, sliding_window=kw["window"])
    if "dtype" in kw:
        cfg = dataclasses.replace(cfg, dtype=kw["dtype"])
    run = RunConfig(dp_axes=("data",), fsdp=False,
                    scan_layers=kw.get("scan_layers", True))
    mesh = request.getfixturevalue("mesh22") if kw.get("mesh") else None
    rules = ShardingRules(mesh, run) if mesh is not None else None

    def place(tree, tmpl):
        if mesh is None:
            return tree
        return jax.tree.map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
            tree, param_specs(tmpl))

    tmpl = param_template(cfg, run, rules)
    params = place(init_params(tmpl, jax.random.PRNGKey(0), cfg.d_model),
                   tmpl)
    b, s_max, steps = 4, 16, 3
    lens = np.asarray(kw["lens"])
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, lens.max() + steps),
                              0, cfg.vocab_size)
    fill = jax.jit(lambda p, c, t, n: prefill_step(p, c, t, n, cfg, run,
                                                   rules))

    def prefill(n):
        """(logits, cache) after each slot's first lens + n tokens."""
        ct = cache_template(cfg, run, rules, batch=b, s_max=s_max,
                            slot_pos=lens.ndim > 0,
                            kv_dtype=kw.get("kv_dtype", "bf16"))
        cache = place(init_params(ct, jax.random.PRNGKey(1), cfg.d_model), ct)
        return fill(params, cache, toks[:, :lens.max() + n],
                    jnp.asarray(lens + n, jnp.int32))

    _, cache = prefill(0)
    start = jax.tree.map(np.asarray, cache["blocks"])
    live = np.ones(b, bool)
    if "parked" in kw:
        live[kw["parked"]] = False
        cache["pos"] = cache["pos"].at[kw["parked"]].set(s_max + 3)
    step = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg, run, rules))
    for j in range(steps):
        at = np.broadcast_to(lens + j, (b,))
        logits, cache = step(params, cache, toks[np.arange(b), at][:, None])
        ref, ref_cache = prefill(j + 1)
        np.testing.assert_allclose(np.asarray(logits[live, 0], np.float32),
                                   np.asarray(ref[live, 0], np.float32),
                                   rtol=2e-2, atol=2e-2)

    for i, spec in enumerate(cfg.layer_pattern()):
        name = f"pos{i}"
        got, was = cache["blocks"][name], start[name]
        if spec.mixer != "attn":              # the Mamba state moves whole
            jax.tree.map(lambda g, r: np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(r, np.float32),
                rtol=2e-2, atol=2e-2), got, ref_cache["blocks"][name])
            continue
        new, ref_kv = _dequant(got), _dequant(ref_cache["blocks"][name])
        for slot in range(b):
            lo = int(np.broadcast_to(lens, (b,))[slot])
            hi = lo + steps if live[slot] else lo
            for leaf, arr in got.items():    # rows outside [lo, hi) stay
                arr = np.asarray(arr)
                np.testing.assert_array_equal(arr[:, slot, :, :lo],
                                              was[leaf][:, slot, :, :lo])
                np.testing.assert_array_equal(arr[:, slot, :, hi:],
                                              was[leaf][:, slot, :, hi:])
            for n in ("k", "v"):
                np.testing.assert_allclose(new[n][:, slot, :, lo:hi],
                                           ref_kv[n][:, slot, :, lo:hi],
                                           rtol=2e-2, atol=2e-2)
