"""The dense model module's counts (``bench/models/dense.py``) and
``bench/peaks.json`` against hand arithmetic, and against the integers the
counts gave before they moved into the module."""

import pytest

from bench import cell as C
from bench import counts
from bench.loop import Step
from bench.tiny import ROOT

D = C.load_model("dense")
TINY = {"layers": 2, "d": 8, "hq": 2, "hkv": 1, "hd": 4, "ff": 16,
        "vocab": 32, "gated": True}
CONFIGS = ["starcoder2-15b-l10", "internlm2-20b-tp4"]


def prefill(*lens):
    return Step("prefill", 0.0, 0.0, [], list(lens))


def decode(kv_lens):
    return Step("decode", 0.0, 0.0, list(kv_lens), [])


def shapes(name):
    return D.shapes(C.load_json(ROOT / "bench" / "configs" / f"{name}.json"))


def test_layer_params_by_hand():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8 = 192; gated MLP 3 x 8 x 16 = 384
    assert D.layer_params(TINY) == 192 + 384
    plain = dict(TINY, gated=False)
    assert D.layer_params(plain) == 192 + 256


def test_weight_bytes_by_hand():
    # 2 layers x (576 + 2 norms of 8) + embed and head 2 x 32 x 8 + norm 8
    assert D.weight_bytes(TINY) == 2 * (2 * 592 + 512 + 8)


def test_prefill_flops_by_hand():
    n = 5
    dense = 2 * n * 2 * 576
    attn = 4 * 2 * 2 * 4 * (5 * 6 // 2)      # QK^T and PV, causal
    head = 2 * 8 * 32                         # last position only
    assert D.prefill_flops(TINY, prefill(n)) == dense + attn + head
    # a group of prompts counts each prompt alone; no prompt, no work
    assert D.prefill_flops(TINY, prefill(n, 3)) == \
        D.prefill_flops(TINY, prefill(n)) + D.prefill_flops(TINY, prefill(3))
    assert D.prefill_flops(TINY, prefill()) == 0


def test_decode_counts_by_hand():
    kv = decode([3, 7])
    dense = 2 * 2 * (2 * 576 + 8 * 32)
    attn = 4 * 2 * 2 * 4 * 10
    assert D.decode_flops(TINY, kv) == dense + attn
    per_token = 2 * 2 * 2 * 1 * 4            # bf16 K and V, 2 layers
    assert D.kv_bytes_per_token(TINY) == per_token
    assert D.decode_attn_bytes(TINY, kv) == per_token * 10
    weights = D.weight_bytes(TINY) - 2 * 32 * 8
    assert D.decode_bytes(TINY, kv) == (weights + 2 * 2 * 8
                                        + per_token * 10)


@pytest.mark.parametrize("name,gb", [("starcoder2-15b-l10", 8.88),
                                     ("internlm2-20b-tp4", 39.72)])
def test_config_weight_bytes(name, gb):
    assert D.weight_bytes(shapes(name)) / 1e9 == pytest.approx(gb, abs=0.01)


PROMPTS = (1, 32, 1020, 3072)
KV = ([1], [33, 1100, 4096], list(range(7, 4097, 128)))
#: what ``bench/counts.py`` gave for these lengths before the counts moved
#: into the dense module (``weight_bytes``, ``prefill_flops`` per prompt,
#: ``decode_flops`` and ``decode_bytes`` per list of KV lengths)
BEFORE = {
    "starcoder2-15b-l10": (
        8883793920,
        [8279801856, 246352183296, 7957661515776, 24739993092096],
        [8279801856, 26123747328, 280603656192],
        [8279846912, 8386940928, 9585029120]),
    "internlm2-20b-tp4": (
        39722299392,
        [38585106432, 1200055910400, 38811072724992, 120605630791680],
        [38585106432, 121920159744, 1309843390464],
        [38585327616, 39613218816, 51111800832])}


@pytest.mark.parametrize("name", CONFIGS)
def test_counts_equal_those_before_the_move(name):
    s = shapes(name)
    weight, pre, dflops, dbytes = BEFORE[name]
    assert D.weight_bytes(s) == weight
    assert [D.prefill_flops(s, prefill(n)) for n in PROMPTS] == pre
    assert [D.decode_flops(s, decode(k)) for k in KV] == dflops
    assert [D.decode_bytes(s, decode(k)) for k in KV] == dbytes
    # a step that admitted all four prompts counts their sum
    assert D.prefill_flops(s, prefill(*PROMPTS)) == sum(pre)


def test_total_gives_none_for_a_count_the_module_lacks():
    steps = [decode([3, 7]), decode([4])]
    assert counts.total(D, "decode_attn_bytes", TINY, steps) == \
        D.kv_bytes_per_token(TINY) * 14

    class NoCounts:
        @staticmethod
        def decode_flops(s, step):
            return None

    assert counts.total(NoCounts, "decode_bytes", TINY, steps) is None
    assert counts.total(NoCounts, "decode_flops", TINY, steps) is None
    assert counts.total(NoCounts, "decode_flops", TINY, []) == 0


def test_peaks_known_and_unknown():
    p = counts.peak("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError, match="no peaks"):
        counts.peak("TPU v9 imaginary")
