"""The phase, program and scope reduction (``bench/phases.py``): on the
recorded v5e trace and ``test_bench_trace``'s hand-made events it leaves
every key of ``bench/trace.reduce`` as that gives it, but for the device
ops, which it names by program; on hand-made events with engine spans and
a device clock a millisecond behind, it finds the lag, labels idle gaps by
engine phase and reads the per-layer values."""

import json

import pytest

from bench import phases as PH
from bench import trace as TR
from bench.test_bench_trace import PROBE, hand_made

MS = 1_000_000     # events in nanoseconds, written in milliseconds
LAG = 1.0          # the device clock reads this many ms behind the host's


def _same_as_trace(ev):
    """Every key as ``bench/trace.reduce`` gives it; the device ops with the
    same self times, each name prefixed by its program."""
    base = TR.reduce(ev)
    got = PH.reduce(ev)
    same = [k for k in base if k != "device_ops"]
    assert json.dumps({k: got[k] for k in same}) == \
        json.dumps({k: base[k] for k in same})
    assert [t for _, t in got["device_ops"]] == \
        pytest.approx([t for _, t in base["device_ops"]])
    assert [n.split("/", 1)[1] for n, _ in got["device_ops"]] == \
        [n for n, _ in base["device_ops"]]
    return got


def test_recorded_trace_keys_unchanged():
    ev = PH.events(str(PROBE))
    assert ev["engine"] == []
    assert {PH.program(m) for m, _, _ in ev["modules"]["/device:TPU:0"]} \
        == {"jit__lambda"}
    got = _same_as_trace(ev)
    assert [n for n, _ in got["device_ops"]][:2] == \
        ["jit__lambda/convolution_tanh_fusion", "jit__lambda/fusion"]
    assert got["clock_lag_ms"] == 0.0
    assert got["device_programs"][0][0] == "jit__lambda"
    assert got["decode_device_ms"] is None
    assert got["decode_host_ms"] is None
    assert got["decode_attn_island_ms"] is None


def test_hand_made_trace_keys_unchanged():
    got = _same_as_trace(hand_made())
    assert {n for n, _ in got["device_ops"]} == \
        {"none/fusion.1", "none/all-reduce.2", "none/fusion.3"}
    assert got["clock_lag_ms"] == 0.0
    assert got["device_programs"] == []
    assert got["device_scopes"][0][:2] == ["none", "unscoped"]


def engine_run():
    """One decode step and one prefill step in a 100 ms window. Host spans
    on the host clock; the device's modules and ops in true time, stamped
    ``LAG`` ms early. Each program starts 10 us after its dispatch."""
    def ms(evs, shift=0.0):
        return [(n, round((s - shift) * MS), round((e - shift) * MS))
                for n, s, e in evs]

    host = ms([("bench.traced", 0, 100), ("bench.step", 10, 46),
               ("bench.step", 46, 80), ("bench.wait", 80, 100)])
    engine = ms([
        ("engine.step", 10.05, 45.95), ("engine.schedule", 10.05, 10.2),
        ("engine.decode.inputs", 10.2, 10.5),
        ("engine.dispatch", 10.5, 10.7), ("engine.sample", 10.7, 44.9),
        ("engine.bookkeeping", 44.9, 45.95),
        ("engine.step", 46.05, 79.95), ("engine.schedule", 46.05, 46.1),
        ("engine.prefill.inputs", 46.1, 57.0),
        ("engine.dispatch", 57.0, 57.2), ("engine.sample", 57.2, 74.9),
        ("engine.prefill.scatter", 74.9, 78.0),
        ("engine.bookkeeping", 78.0, 79.95)])
    modules = ms([("jit_other(1)", 0, 10.1),
                  ("jit_serve_decode(2)", 10.51, 44.8),
                  ("jit_broadcast_in_dim(3)", 46.2, 56.0),
                  ("jit_serve_prefill_3072(4)", 57.01, 74.8),
                  ("jit_scatter(5)", 75.2, 77.0)], LAG)
    ops = ms([("fusion.9", 0, 10.1), ("fusion.1", 10.51, 21.0),
              ("fusion.2", 21.0, 44.8), ("broadcast.2", 46.2, 56.0),
              ("fusion.1", 57.01, 74.8),
              ("dynamic-update-slice.3", 75.2, 77.0)], LAG)
    return {"devices": {"/device:TPU:0": ops}, "async": {}, "host": host,
            "engine": engine, "modules": {"/device:TPU:0": modules}}


SCOPES = {"jit_serve_decode": {"fusion.1": "decode_attn", "fusion.2": "mlp"},
          "jit_serve_prefill_3072": {"fusion.1": "mlp"}}


def test_clock_lag_and_step_kinds():
    ev = engine_run()
    lag = PH.clock_lag(ev)
    assert lag / MS == pytest.approx(LAG - 0.01)
    assert [k for k, _, _ in PH.step_kinds(ev, lag)] == ["decode", "prefill"]


def test_idle_gaps_labelled_by_phase_after_the_shift():
    ev = engine_run()
    got = PH.reduce(ev, SCOPES)
    assert got["clock_lag_ms"] == pytest.approx(LAG - 0.01)
    gaps = {n: t * 1e3 for n, t in got["idle_gaps"]}
    assert gaps == pytest.approx({"engine.decode.inputs": 0.41,
                                  "engine.bookkeeping": 1.4,
                                  "engine.prefill.inputs": 1.01,
                                  "engine.prefill.scatter": 0.4,
                                  "bench.wait": 23.01})
    # unshifted, the gap before the decode program falls outside any span
    assert "bench.other" in dict(TR.reduce(ev)["idle_gaps"])
    # busy time is read unshifted, as bench/trace.py reads it
    assert got["busy_s"] == TR.reduce(ev)["busy_s"]


def test_programs_scopes_and_decode_metrics():
    got = PH.reduce(engine_run(), SCOPES)
    progs = {p: t * 1e3 for p, t in got["device_programs"]}
    assert progs == pytest.approx({
        "jit_serve_decode": 34.29, "jit_serve_prefill_3072": 17.79,
        "jit_broadcast_in_dim": 9.8, "jit_other": 9.1, "jit_scatter": 1.8})
    scopes = {(p, s): t * 1e3 for p, s, t in got["device_scopes"]}
    # one op name in two programs: two rows, each with its own scope
    assert scopes[("jit_serve_decode", "decode_attn")] == pytest.approx(10.49)
    assert scopes[("jit_serve_decode", "mlp")] == pytest.approx(23.8)
    assert scopes[("jit_serve_prefill_3072", "mlp")] == pytest.approx(17.79)
    assert scopes[("jit_scatter", "unscoped")] == pytest.approx(1.8)
    # one op name in two programs: two device-op rows
    ops = {n: t * 1e3 for n, t in got["device_ops"]}
    assert ops["jit_serve_decode/fusion.1"] == pytest.approx(10.49)
    assert ops["jit_serve_prefill_3072/fusion.1"] == pytest.approx(17.79)
    assert "fusion.1" not in ops
    assert got["decode_device_ms"] == pytest.approx(34.29)
    # the decode step span 10.05-45.95 on the host clock holds device work
    # 10.05-10.09 and 10.50-44.79 after the 0.99 ms shift
    assert got["decode_host_ms"] == pytest.approx(35.9 - 0.04 - 34.29)
    assert got["decode_attn_island_ms"] == pytest.approx(10.49)


def test_metrics_are_none_without_spans_or_scopes():
    ev = engine_run()
    assert PH.reduce(ev)["decode_attn_island_ms"] is None
    ev["engine"] = []
    got = PH.reduce(ev, SCOPES)
    assert got["clock_lag_ms"] == 0.0
    assert got["decode_host_ms"] is None
    assert got["decode_device_ms"] == pytest.approx(34.29)
    ev["modules"] = {}
    got = PH.reduce(ev, SCOPES)
    assert got["decode_device_ms"] is None
    assert got["decode_attn_island_ms"] is None


def test_pad_frac():
    assert PH.pad_frac(300, 800) == pytest.approx(1 - 300 / 800)
    assert PH.pad_frac(0, 0) is None


HLO = """HloModule jit_serve_decode, is_scheduled=true

%fused_computation.3 (param_0: bf16[8]) -> bf16[8] {
  %param_0 = bf16[8]{0} parameter(0)
  ROOT %add.1 = bf16[8]{0} add(%param_0, %param_0), metadata={op_name="jit(serve_decode)/cache_scan/while/body/closed_call/decode_attn/add"}
}

ENTRY %main.4 (Arg_0.1: bf16[8]) -> bf16[8] {
  %Arg_0.1 = bf16[8]{0} parameter(0)
  %fusion.3 = bf16[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.3
  %copy.5 = bf16[8]{0} copy(%fusion.3), metadata={op_name="jit(serve_decode)/cache_scan/while"}
  %dot.2 = bf16[8]{0} dot(%copy.5, %copy.5), metadata={op_name="jit(serve_decode)/head/bsd,dv->bsv/dot_general"}
  %copy.6 = bf16[8]{0} copy(%Arg_0.1)
  ROOT %copy.7 = bf16[8]{0} copy(%dot.2)
}
"""


def test_scope_map_reads_op_name_metadata():
    assert PH.scope_of("jit(serve_decode)/cache_scan/while/body/closed_call"
                       "/mlp/jit(_where)/select_n") == "mlp"
    assert PH.scope_of("jit(serve_decode)/qkv/transpose;qkv/reshape") \
        == "qkv"
    assert PH.scope_of("reduce_sum") == "unscoped"
    m = PH.scope_map(HLO)
    assert m["fusion.3"] == "decode_attn"      # from its computation's root
    assert m["copy.5"] == "cache_scan"
    assert m["dot.2"] == "head"
    assert m["copy.6"] == "unscoped"         # a copy of a parameter
    assert m["copy.7"] == "head"             # a copy takes its operand's


def test_scope_map_of_an_engine_program():
    """The engine's own compiled decode program (tiny widths, CPU): most of
    its instructions map to a named scope, the decode island among them."""
    from repro.configs.base import ServeConfig
    from repro.launch.serve import build_engine

    eng = build_engine("tinyllama-1.1b", reduced=True, serve=ServeConfig(
        max_batch=2, prefill_batch=1, bucket_edges=(8,), max_new_tokens=2))
    eng.submit(tuple(range(1, 6)))
    eng.run()
    m = PH.scope_map(eng.step_programs()["jit_serve_decode"].as_text())
    assert {"decode_attn", "mlp", "qkv", "norm", "cache_scan"} \
        <= set(m.values())
