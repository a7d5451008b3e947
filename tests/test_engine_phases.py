"""The serving engine's own timing and names, on the CPU at tiny widths:

* every prefill and decode step splits into the engine's phase vocabulary,
  on the slab and the paged layout, and the phases tile the step;
* the prefill padding counters count real prompt tokens and bucket x rows;
* the compiled step programs carry stable module names and the island and
  layer scopes in their ``op_name`` metadata, on the dense-reference path
  (no mesh) and on the ``shard_map`` path (emulated (2, 2) mesh).
"""

import dataclasses
import re

import pytest

from repro.configs.base import ServeConfig
from repro.runtime.straggler import StepTimer

SLAB = ServeConfig(max_batch=4, prefill_batch=2, bucket_edges=(8, 16),
                   max_new_tokens=3)
PAGED = dataclasses.replace(SLAB, cache_layout="paged", page_size=4)
CHUNKED = dataclasses.replace(PAGED, prefill_chunk=8)

PREFILL = {"engine.schedule", "engine.prefill.inputs", "engine.dispatch",
           "engine.sample", "engine.prefill.scatter", "engine.bookkeeping"}
DECODE = {"engine.schedule", "engine.decode.inputs", "engine.dispatch",
          "engine.sample", "engine.bookkeeping"}


def _engine(serve, mesh_shape=None):
    from repro.launch.serve import build_engine
    return build_engine("tinyllama-1.1b", reduced=True,
                        mesh_shape=mesh_shape, serve=serve)


def _tiles(eng):
    assert sum(eng.last_phases.values()) >= 0.95 * eng.step_times[-1]


@pytest.mark.parametrize("serve", [SLAB, PAGED], ids=["slab", "paged"])
def test_phases_tile_prefill_and_decode_steps(serve):
    eng = _engine(serve)
    eng.submit(tuple(range(1, 6)))
    eng.submit(tuple(range(1, 8)))
    assert eng.step() == "prefill"
    assert set(eng.last_phases) == PREFILL
    _tiles(eng)
    assert eng.step() == "decode"
    assert set(eng.last_phases) == DECODE
    _tiles(eng)
    while eng.step() is not None:
        pass
    total = eng.stats()["phase_s"]
    assert set(total) == PREFILL | DECODE
    assert sum(total.values()) >= 0.95 * sum(eng.step_times)


def test_chunked_prefill_steps_use_the_same_phases():
    eng = _engine(CHUNKED)
    eng.submit(tuple(range(1, 15)))           # bucket 16: two chunks of 8
    kinds = []
    while (kind := eng.step()) is not None:
        kinds.append(kind)
        assert set(eng.last_phases) <= PREFILL | DECODE
        _tiles(eng)
    assert kinds[:2] == ["prefill", "prefill"]


@pytest.mark.parametrize("serve", [SLAB, CHUNKED], ids=["slab", "chunked"])
def test_prefill_padding_counters(serve):
    eng = _engine(serve)
    eng.submit(tuple(range(1, 6)))            # 5 tokens, bucket 8
    eng.submit(tuple(range(1, 12)))           # 11 tokens, bucket 16
    eng.run()
    # one group per bucket (fcfs stops at the bucket change), each of
    # prefill_batch rows; a chunk holds the tokens of its 8 positions
    assert eng.prefill_tokens == 5 + 11
    if serve.prefill_chunk:
        assert eng.prefill_slot_tokens == (8 + 2 * 8) * 2
    else:
        assert eng.prefill_slot_tokens == (8 + 16) * 2


def test_step_timer_phases_accumulate():
    t = StepTimer()
    with t:
        for name in ("a", "b", "a"):
            with t.phase(name):
                sum(range(1000))
    assert set(t.phases) == {"a", "b"}
    assert sum(t.phases.values()) <= t.dt


def _scopes(text: str) -> set:
    return {p for m in re.findall(r'op_name="([^"]*)"', text)
            for p in m.split(";")[0].split("/")}


@pytest.mark.parametrize("mesh_shape", [None, (2, 2)],
                         ids=["reference", "shard_map"])
def test_step_programs_are_named_and_scoped(mesh_shape):
    eng = _engine(SLAB, mesh_shape)
    eng.submit(tuple(range(1, 6)))
    eng.run()
    progs = eng.step_programs()
    assert set(progs) == {"jit_serve_decode", "jit_serve_prefill_8"}
    decode = progs["jit_serve_decode"].as_text()
    prefill = progs["jit_serve_prefill_8"].as_text()
    assert decode.startswith("HloModule jit_serve_decode")
    assert prefill.startswith("HloModule jit_serve_prefill_8")
    common = {"embed", "mlp", "qkv", "norm", "head", "cache_scan"}
    assert common | {"decode_attn", "attn_out"} <= _scopes(decode)
    assert common | {"prefill_attn", "prefill_write", "attn_out"} \
        <= _scopes(prefill)
