"""The plain reference: a float32 forward pass of the served decoder in
straightforward ``jax.numpy`` at HIGHEST matmul precision, and the
comparison that decides a run's ``correct``.

It imports nothing of the program. It reads the weights by name from the
tree the benchmark drew from the seed (``embed``, ``final_norm``,
``lm_head``, and per layer ``blocks/pos0/{attn,mlp}/...`` stacked over
layers), upcasts them one layer at a time, and follows the published layer
equations with the departures each configuration file lists: pre-norm
RMSNorm, rotate-half RoPE, grouped-query causal attention, a GELU (tanh) or
SwiGLU MLP, no biases.

The comparison: run the reference once over each sampled request's prompt
and served tokens, and take the widest gap by which a served token's
logit lies below the reference's best at that position. The control
(``fp8=True``) is the same pass with every projection's operands cast to
float8 e4m3 (per-row activation and per-column weight scales), the step
below the bfloat16 the configuration serves in; it reads the gap of the
token that it puts first.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
QBLOCK = 128             # query rows per attention block


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


def _q8(x, axis):
    """float8 e4m3 round trip with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (T, H, hd); rotate-half RoPE at positions 0..T-1."""
    t, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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


@jax.jit
def _gap(ref, pick):
    """Per row: the reference's best logit less its logit of ``pick``."""
    got = jnp.take_along_axis(ref, pick[:, None], axis=1)[:, 0]
    return ref.max(axis=1) - got


def logits(weights, a: Arch, tokens, rows, fp8: bool = False):
    """Logits (len(rows), vocab) at ``rows`` of the sequence ``tokens``
    (length a multiple of QBLOCK; padding after the real tokens does not
    reach them under the causal mask)."""
    blocks = weights["blocks"]["pos0"]
    x = _embed(weights["embed"], tokens)
    for i in range(a.layers):
        x = _layer(x, blocks, np.int32(i), a, fp8)
    return _head(x, weights["final_norm"], weights["lm_head"], rows, a, fp8)


def gaps(weights, a: Arch, prompt, served, t_pad: int, n_pad: int,
         control: bool = False) -> dict:
    """Per served token, the reference's best logit less the logit of the
    token served (``program``) and, with ``control``, of the token the
    float8 pass puts first (``control``)."""
    prompt, served = np.asarray(prompt), np.asarray(served)
    n, p = len(served), len(prompt)
    tokens = np.zeros(t_pad, np.int32)
    tokens[:p] = prompt
    tokens[p:p + n - 1] = served[:-1]
    rows = np.full(n_pad, p - 1, np.int32)
    rows[:n] = p - 1 + np.arange(n)
    pick = np.zeros(n_pad, np.int32)
    pick[:n] = served
    ref = logits(weights, a, tokens, rows)
    out = {"program": np.asarray(_gap(ref, pick))[:n]}
    if control:
        low = logits(weights, a, tokens, rows, fp8=True)
        out["control"] = np.asarray(_gap(ref, jnp.argmax(low, axis=1)))[:n]
    return out


def sample(done: list, k: int, r: np.random.Generator) -> list:
    """k finished requests drawn with ``r``, the longest among them."""
    if not done:
        return []
    order = sorted(range(len(done)),
                   key=lambda i: (len(done[i].tokens), len(done[i].prompt)))
    longest = order[-1]
    rest = order[:-1]
    picks = r.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [done[longest]] + [done[rest[i]] for i in sorted(picks)]


def pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


def compare(weights, conf: dict, reqs: list, r: np.random.Generator,
            control: bool = False) -> dict:
    """The numbers ``correct`` is decided on, over a sample of the
    finished requests: the widest logit gap and the tokens compared."""
    serve = conf["serve"]
    a = Arch.of(conf)
    t_pad = pad_to(serve["bucket_edges"][-1] + serve["max_new_tokens"],
                   QBLOCK)
    done = [q for q in reqs if q.tokens is not None]
    picked = sample(done, conf["check"]["requests"], r)
    prog, ctrl = [], []
    for q in picked:
        g = gaps(weights, a, q.prompt, q.tokens, t_pad,
                 serve["max_new_tokens"], control)
        prog.append(g["program"])
        if control:
            ctrl.append(g["control"])
    out = {"requests": len(picked),
           "tokens": int(sum(len(g) for g in prog)),
           "logit_gap": float(max((g.max() for g in prog), default=np.nan))}
    if control:
        out["control_gap"] = float(max(g.max() for g in ctrl))
    return out
