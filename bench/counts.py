"""Operations and bytes the served algorithm needs, from shapes alone.

``shapes`` is the dict ``shapes_of`` builds from a configuration file's
published keys. Counts are of useful work only: real prompt tokens, live
decode slots and the KV each slot holds now, never bucket padding, inert
group rows or the slab's unused tail. So a share of a peak computed from
them cannot pass 100% unless the device time leaves out part of the work.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
BF16 = 2


def peak(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name} (known: {sorted(table)})")
    return table[device_kind]


def shapes_of(conf: dict) -> dict:
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


def prefill_flops(s: dict, prompt_len: int) -> int:
    """One prompt's prefill: every matrix of every layer on each of its
    tokens, causal attention (QK^T and PV over the positions each token
    sees), and the head on the last position only."""
    n = prompt_len
    dense = 2 * n * s["layers"] * layer_params(s)
    attn = 4 * s["layers"] * s["hq"] * s["hd"] * n * (n + 1) // 2
    return dense + attn + 2 * s["d"] * s["vocab"]


def decode_flops(s: dict, kv_lens) -> int:
    """One decode step over the live slots; ``kv_lens`` holds, per live
    slot, the positions its new token attends to (cache plus itself)."""
    rows = len(kv_lens)
    dense = 2 * rows * (s["layers"] * layer_params(s) + s["d"] * s["vocab"])
    attn = 4 * s["layers"] * s["hq"] * s["hd"] * sum(kv_lens)
    return dense + attn


def decode_bytes(s: dict, kv_lens) -> int:
    """Bytes one decode step must read: every weight but the embedding
    table (of which it gathers one row a slot), and the KV that each live
    slot holds now."""
    rows = len(kv_lens)
    weights = weight_bytes(s) - BF16 * s["vocab"] * s["d"]
    return (weights + BF16 * rows * s["d"]
            + kv_bytes_per_token(s) * sum(kv_lens))
