"""Tiny copies of the cells for the CPU tests: the same configuration files
and code paths, at widths a CPU test can hold."""

import copy
from pathlib import Path

from bench.cell import load_json

ROOT = Path(__file__).resolve().parents[1]

TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256}
ARCH = {"hidden_size": "d_model", "intermediate_size": "d_ff",
        "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
        "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
        "vocab_size": "vocab_size"}


def tiny_conf(name: str, mesh=None, prefill_batch: int = 1) -> dict:
    """The named configuration at tiny widths, checked the same way."""
    conf = copy.deepcopy(load_json(ROOT / "bench" / "configs"
                                   / f"{name}.json"))
    conf.update(TINY)
    conf["arch_overrides"] = {ARCH[k]: v for k, v in TINY.items()}
    conf["mesh"] = mesh
    conf["serve"].update(max_batch=4, prefill_batch=prefill_batch,
                         bucket_edges=[16, 32], max_new_tokens=8)
    conf["check"]["requests"] = 3
    return conf


TINY_MIX = {"loop": "open", "rate_per_s": 40.0, "preroll_s": 0.3,
            "pool": 32, "block": 8, "schedule_seed": 1,
            "prompt": {"median": 12, "sigma": 0.6, "min": 2, "max": 32},
            "output": {"median": 4, "sigma": 0.6, "min": 2, "max": 8}}


def tiny_cell(conf: dict, mix: dict | None = None, trace: bool = False):
    """A resolved cell, as ``cell.resolve`` returns one, around ``conf``."""
    spec = load_json(ROOT / "BENCHMARK.json")
    return {"name": "tiny", "chips": 1, "config": conf,
            "traffic": mix or dict(TINY_MIX),
            "end_to_end": spec["end_to_end"],
            "per_layer": spec["per_layer"],
            "metrics_dir": ROOT / "bench" / "metrics"}
