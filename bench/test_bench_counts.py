"""bench/counts.py and bench/peaks.json against hand arithmetic."""

import pytest

from bench import counts
from bench.cell import load_json
from bench.tiny import ROOT

TINY = {"layers": 2, "d": 8, "hq": 2, "hkv": 1, "hd": 4, "ff": 16,
        "vocab": 32, "gated": True}


def test_layer_params_by_hand():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8 = 192; gated MLP 3 x 8 x 16 = 384
    assert counts.layer_params(TINY) == 192 + 384
    plain = dict(TINY, gated=False)
    assert counts.layer_params(plain) == 192 + 256


def test_weight_bytes_by_hand():
    # 2 layers x (576 + 2 norms of 8) + embed and head 2 x 32 x 8 + norm 8
    assert counts.weight_bytes(TINY) == 2 * (2 * 592 + 512 + 8)


def test_prefill_flops_by_hand():
    n = 5
    dense = 2 * n * 2 * 576
    attn = 4 * 2 * 2 * 4 * (5 * 6 // 2)      # QK^T and PV, causal
    head = 2 * 8 * 32                         # last position only
    assert counts.prefill_flops(TINY, n) == dense + attn + head


def test_decode_counts_by_hand():
    kv = [3, 7]
    dense = 2 * 2 * (2 * 576 + 8 * 32)
    attn = 4 * 2 * 2 * 4 * 10
    assert counts.decode_flops(TINY, kv) == dense + attn
    per_token = 2 * 2 * 2 * 1 * 4            # bf16 K and V, 2 layers
    assert counts.kv_bytes_per_token(TINY) == per_token
    weights = counts.weight_bytes(TINY) - 2 * 32 * 8
    assert counts.decode_bytes(TINY, kv) == (weights + 2 * 2 * 8
                                             + per_token * 10)


@pytest.mark.parametrize("name,gb", [("starcoder2-15b-l10", 8.88),
                                     ("internlm2-20b-tp4", 39.72)])
def test_config_weight_bytes(name, gb):
    conf = load_json(ROOT / "bench" / "configs" / f"{name}.json")
    s = counts.shapes_of(conf)
    assert counts.weight_bytes(s) / 1e9 == pytest.approx(gb, abs=0.01)


def test_peaks_known_and_unknown():
    p = counts.peak("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError, match="no peaks"):
        counts.peak("TPU v9 imaginary")
