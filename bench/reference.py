"""The plain reference and the comparison that decides a run's ``correct``,
for every architecture: each model module under ``bench/models/`` gives
its own float32 forward pass (``logits``) in straightforward ``jax.numpy``
at HIGHEST matmul precision, built from the pieces here, and imports
nothing of the program.

The comparison: run the reference once over each sampled request's prompt
and served tokens, and take the widest gap by which a served token's
logit lies below the reference's best at that position. The control
(``fp8=True``) is the same pass with every projection's operands cast to
float8 e4m3 (per-row activation and per-column weight scales, ``_mm``),
the step below the bfloat16 the configuration serves in; it reads the gap
of the token that it puts first.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
QBLOCK = 128             # query rows per attention block


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


@jax.jit
def _gap(ref, pick):
    """Per row: the reference's best logit less its logit of ``pick``."""
    got = jnp.take_along_axis(ref, pick[:, None], axis=1)[:, 0]
    return ref.max(axis=1) - got


def gaps(model, weights, conf: dict, prompt, served, t_pad: int,
         n_pad: int, control: bool = False) -> dict:
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
    ref = model.logits(weights, conf, tokens, rows)
    out = {"program": np.asarray(_gap(ref, pick))[:n]}
    if control:
        low = model.logits(weights, conf, tokens, rows, fp8=True)
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


def compare(model, weights, conf: dict, reqs: list,
            r: np.random.Generator, control: bool = False) -> dict:
    """The numbers ``correct`` is decided on, over a sample of the
    finished requests, with the reference of ``model`` (the configuration's
    module): the widest logit gap and the tokens compared."""
    serve = conf["serve"]
    t_pad = pad_to(serve["bucket_edges"][-1] + serve["max_new_tokens"],
                   QBLOCK)
    done = [q for q in reqs if q.tokens is not None]
    picked = sample(done, conf["check"]["requests"], r)
    prog, ctrl = [], []
    for q in picked:
        g = gaps(model, weights, conf, q.prompt, q.tokens, t_pad,
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
