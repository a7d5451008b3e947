"""Dense decoder: pre-norm RMSNorm, rotate-half RoPE, grouped-query causal
attention, a GELU (tanh) or SwiGLU MLP, no biases, a separate head.

What the benchmark needs of this architecture, for every configuration
file that names ``"bench_model": "dense"``: the check of the file against
the program's ``ArchConfig``, the shapes the counts take, the operations
and bytes of a step, the float32 reference forward pass, and the widths of
its tiny copy for the CPU tests.

The reference reads the weights by name from the tree the benchmark drew
from the seed (``embed``, ``final_norm``, ``lm_head``, and per layer
``blocks/pos0/{attn,mlp}/...`` stacked over layers), upcasts them one layer
at a time, and follows the published layer equations with the departures
each configuration file lists. It imports nothing of the program.

Counts are of useful work only: real prompt tokens, live decode slots and
the KV each slot holds now, never bucket padding, inert group rows or the
slab's unused tail. So a share of a peak computed from them cannot pass
100% unless the device time leaves out part of the work.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import BF16
from bench.reference import F32, HIGHEST, QBLOCK, _mm, _rms, _rope

#: published config.json key -> the program's ArchConfig field
HF_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "head_dim": "hd",
           "intermediate_size": "d_ff", "vocab_size": "vocab_size",
           "rope_theta": "rope_theta"}
ACTS = {"gelu_pytorch_tanh": "gelu", "silu": "silu"}


def arch_config(conf: dict):
    """The program's ArchConfig for this configuration file, checked
    against the file's published keys so the two cannot drift apart."""
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(conf["repro_arch"]),
                              **conf["arch_overrides"])
    want = {f: conf[k] for k, f in HF_KEYS.items() if k in conf}
    want["act"] = ACTS[conf["hidden_act"]]
    got = {f: getattr(cfg, f) for f in want}
    if got != want:
        raise ValueError(f"{conf['repro_arch']}: program config {got} "
                         f"differs from the configuration file {want}")
    return cfg


# -- shapes and counts -----------------------------------------------------

def shapes(conf: dict) -> dict:
    """Model shapes from a configuration file's published keys."""
    d, hq = conf["hidden_size"], conf["num_attention_heads"]
    return {"layers": conf["num_hidden_layers"], "d": d, "hq": hq,
            "hkv": conf["num_key_value_heads"],
            "hd": conf.get("head_dim", d // hq),
            "ff": conf["intermediate_size"], "vocab": conf["vocab_size"],
            "gated": conf["reference"]["mlp"] == "swiglu"}


def layer_params(s: dict) -> int:
    """Matrix parameters of one layer (norm vectors excluded)."""
    attn = s["d"] * s["hd"] * (2 * s["hq"] + 2 * s["hkv"])
    mlp = (3 if s["gated"] else 2) * s["d"] * s["ff"]
    return attn + mlp


def weight_bytes(s: dict) -> int:
    """bf16 bytes of every parameter: layers, norms, embedding, head."""
    per_layer = layer_params(s) + 2 * s["d"]
    return BF16 * (s["layers"] * per_layer + 2 * s["vocab"] * s["d"]
                   + s["d"])


def kv_bytes_per_token(s: dict) -> int:
    return BF16 * 2 * s["layers"] * s["hkv"] * s["hd"]


def prefill_flops(s: dict, step) -> int:
    """The prompts one prefill step admitted (``step.prompt_lens``): every
    matrix of every layer on each of their tokens, causal attention (QK^T
    and PV over the positions each token sees), and the head on each
    prompt's last position only."""
    out = 0
    for n in step.prompt_lens:
        dense = 2 * n * s["layers"] * layer_params(s)
        attn = 4 * s["layers"] * s["hq"] * s["hd"] * n * (n + 1) // 2
        out += dense + attn + 2 * s["d"] * s["vocab"]
    return out


def decode_flops(s: dict, step) -> int:
    """One decode step over the live slots; ``step.kv_lens`` holds, per
    live slot, the positions its new token attends to (cache plus
    itself)."""
    rows = len(step.kv_lens)
    dense = 2 * rows * (s["layers"] * layer_params(s) + s["d"] * s["vocab"])
    attn = 4 * s["layers"] * s["hq"] * s["hd"] * sum(step.kv_lens)
    return dense + attn


def decode_attn_bytes(s: dict, step) -> int:
    """The KV that the live slots of one decode step hold now, which its
    attention has to read: never the slab's full length."""
    return kv_bytes_per_token(s) * sum(step.kv_lens)


def decode_bytes(s: dict, step) -> int:
    """Bytes one decode step must read: every weight but the embedding
    table (of which it gathers one row a slot), and the KV that each live
    slot holds now."""
    rows = len(step.kv_lens)
    weights = weight_bytes(s) - BF16 * s["vocab"] * s["d"]
    return weights + BF16 * rows * s["d"] + decode_attn_bytes(s, step)


# -- the float32 reference -------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Arch:
    layers: int
    hq: int
    hkv: int
    hd: int
    vocab: int
    theta: float
    eps: float
    mlp: str             # "gelu_tanh" | "swiglu"

    @classmethod
    def of(cls, conf: dict) -> "Arch":
        d, hq = conf["hidden_size"], conf["num_attention_heads"]
        eps = conf.get("rms_norm_eps", conf.get("norm_epsilon"))
        return cls(conf["num_hidden_layers"], hq,
                   conf["num_key_value_heads"], conf.get("head_dim", d // hq),
                   conf["vocab_size"], float(conf["rope_theta"]), float(eps),
                   conf["reference"]["mlp"])


@functools.partial(jax.jit, static_argnames=("a", "fp8"))
def _layer(x, blocks, i, a: Arch, fp8: bool):
    """One decoder layer on x (T, d) in float32."""
    at = {k: v[i].astype(F32) for k, v in blocks["attn"].items()}
    ml = {k: v[i].astype(F32) for k, v in blocks["mlp"].items()}
    t = x.shape[0]
    h = _rms(x, at["norm"], a.eps)
    q = _rope(_mm(h, at["wq"], fp8).reshape(t, a.hq, a.hd), a.theta)
    k = _rope(_mm(h, at["wk"], fp8).reshape(t, a.hkv, a.hd), a.theta)
    v = _mm(h, at["wv"], fp8).reshape(t, a.hkv, a.hd)
    g = a.hq // a.hkv
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(t)

    def block(args):
        qb, q0 = args                                  # (QBLOCK, hq, hd)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST)
        s = s * a.hd ** -0.5
        qpos = q0 + jnp.arange(qb.shape[0])
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    nb = t // QBLOCK
    o = jax.lax.map(block, (q.reshape(nb, QBLOCK, a.hq, a.hd),
                            jnp.arange(nb) * QBLOCK))
    x = x + _mm(o.reshape(t, a.hq * a.hd), at["wo"], fp8)
    h = _rms(x, ml["norm"], a.eps)
    if a.mlp == "swiglu":
        u = jax.nn.silu(_mm(h, ml["w1"], fp8)) * _mm(h, ml["w3"], fp8)
    else:
        u = jax.nn.gelu(_mm(h, ml["w1"], fp8), approximate=True)
    return x + _mm(u, ml["w2"], fp8)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("a", "fp8"))
def _head(x, norm, head, rows, a: Arch, fp8: bool):
    h = _rms(x[rows], norm.astype(F32), a.eps)
    return _mm(h, head.astype(F32), fp8)[:, :a.vocab]


def logits(weights, conf: dict, tokens, rows, fp8: bool = False):
    """Logits (len(rows), vocab) at ``rows`` of the sequence ``tokens``
    (length a multiple of QBLOCK; padding after the real tokens does not
    reach them under the causal mask)."""
    a = Arch.of(conf)
    blocks = weights["blocks"]["pos0"]
    x = _embed(weights["embed"], tokens)
    for i in range(a.layers):
        x = _layer(x, blocks, np.int32(i), a, fp8)
    return _head(x, weights["final_norm"], weights["lm_head"], rows, a, fp8)


# -- the tiny copy for the CPU tests ---------------------------------------

TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256}
ARCH = {"hidden_size": "d_model", "intermediate_size": "d_ff",
        "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
        "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
        "vocab_size": "vocab_size"}


def tiny(conf: dict) -> dict:
    """The keys that make ``conf`` a copy a CPU test can hold: its
    published widths and the program's overrides to match them."""
    return {**TINY, "arch_overrides": {ARCH[k]: v for k, v in TINY.items()}}
