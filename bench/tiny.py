"""Tiny copies of the cells for the CPU tests: the same configuration files
and code paths, at the widths the configuration's model module gives for
a CPU test (its ``tiny``)."""

import copy
from pathlib import Path

from bench.cell import load_json, load_model

ROOT = Path(__file__).resolve().parents[1]


def tiny_conf(name: str, mesh=None, prefill_batch: int = 1,
              root: Path = ROOT) -> dict:
    """The named configuration at tiny widths, checked the same way."""
    conf = copy.deepcopy(load_json(root / "bench" / "configs"
                                   / f"{name}.json"))
    conf.update(load_model(conf["bench_model"], root).tiny(conf))
    conf["mesh"] = mesh
    conf["serve"].update(max_batch=4, prefill_batch=prefill_batch,
                         bucket_edges=[16, 32], max_new_tokens=8)
    conf["check"]["requests"] = 3
    return conf


TINY_MIX = {"loop": "open", "rate_per_s": 40.0, "preroll_s": 0.3,
            "pool": 32, "block": 8, "schedule_seed": 1,
            "prompt": {"median": 12, "sigma": 0.6, "min": 2, "max": 32},
            "output": {"median": 4, "sigma": 0.6, "min": 2, "max": 8}}


def tiny_cell(conf: dict, mix: dict | None = None):
    """A resolved cell, as ``cell.resolve`` returns one, around ``conf``."""
    spec = load_json(ROOT / "BENCHMARK.json")
    return {"name": "tiny", "chips": 1, "config": conf,
            "model": load_model(conf["bench_model"]),
            "traffic": mix or dict(TINY_MIX),
            "end_to_end": spec["end_to_end"],
            "per_layer": spec["per_layer"],
            "metrics_dir": ROOT / "bench" / "metrics"}
