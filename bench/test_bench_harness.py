"""The harness on the CPU: traffic per seed, cells found by name, a new
architecture joining by new files alone, engine counters, the readers,
the last line's keys, refusal without a TPU, and BENCHMARK.json's own
limits."""

import gc
import io
import json
import os
import re
import shutil
import subprocess
import sys
from collections import Counter
from contextlib import redirect_stdout
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import cell as C
from bench import counts, loop
from bench import run as R
from bench.loop import Step
from bench.tiny import ROOT, TINY_MIX, tiny_cell, tiny_conf
from bench.traffic import Traffic

SPEC = C.load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def draws(spec, seed, n=40):
    t = Traffic(spec, seed, 1000)
    return [t.next() for _ in range(n)]


@pytest.mark.parametrize("mix", ["chat", "rag-batch"])
def test_traffic_same_seed_same_requests(mix):
    spec = C.load_json(ROOT / "bench" / "traffic" / f"{mix}.json")
    a, b = draws(spec, 2**40 + 3), draws(spec, 2**40 + 3)
    for (da, pa, oa), (db, pb, ob) in zip(a, b):
        assert da == db and oa == ob and np.array_equal(pa, pb)


@pytest.mark.parametrize("mix", ["chat", "rag-batch"])
def test_traffic_seeds_share_one_schedule(mix):
    spec = C.load_json(ROOT / "bench" / "traffic" / f"{mix}.json")
    n = spec["pool"]
    a, b = draws(spec, 1, n), draws(spec, 2**40 + 9, n)
    assert [(d, len(p), o) for d, p, o in a] == \
        [(d, len(p), o) for d, p, o in b]
    assert not any(np.array_equal(pa, pb) for (_, pa, _), (_, pb, _)
                   in zip(a, b))
    lo, hi = spec["prompt"]["min"], spec["prompt"]["max"]
    assert all(lo <= len(p) <= hi for _, p, _ in a)


def test_traffic_schedule_is_stratified_in_blocks():
    spec = C.load_json(ROOT / "bench" / "traffic" / "chat.json")
    n, blk = spec["pool"], spec["block"]
    other = {**spec, "schedule_seed": spec["schedule_seed"] + 1}
    a, b = draws(spec, 5, n), draws(other, 5, n)
    assert sorted(len(p) for _, p, _ in a) == sorted(len(p) for _, p, _ in b)
    assert sorted(o for _, _, o in a) == sorted(o for _, _, o in b)
    assert [len(p) for _, p, _ in a] != [len(p) for _, p, _ in b]
    assert a[-1][0] == pytest.approx(b[-1][0])          # same total span
    q = sorted(len(p) for _, p, _ in a)      # strata of n // blk values
    nb = n // blk
    for k in range(0, n, blk):       # each block: one of each stratum
        got = sorted(len(p) for _, p, _ in a[k:k + blk])
        assert all(q[g * nb] <= v <= q[(g + 1) * nb - 1]
                   for g, v in enumerate(got))


def copy_tree(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_new_config_mix_and_metric_found_by_name(tmp_path):
    root = copy_tree(tmp_path)
    b = root / "bench"
    shutil.copy(b / "configs" / "starcoder2-15b-l10.json",
                b / "configs" / "other-model.json")
    (b / "traffic" / "burst.json").write_text(json.dumps(
        {**TINY_MIX, "rate_per_s": 9.0}))
    (b / "metrics" / "steps_in_window.py").write_text(
        "def read(rec):\n    return len(rec['run'].steps)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "other-model", "source": "x",
                            "file": "bench/configs/other-model.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "other-model.burst",
                              "config": "other-model", "traffic": "burst",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "x", "moves": "output_tok_s",
                              "workloads": ["other-model.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = C.resolve("other-model.burst", root)
    assert cell["traffic"]["rate_per_s"] == 9.0
    assert cell["config"]["repro_arch"] == "starcoder2-15b"
    names = [m["name"] for m in cell["per_layer"]]
    assert "steps_in_window" in names and "exposed_comm_frac" not in names

    class Run:
        steps = [1, 2, 3]

    assert R.read_metric(cell["metrics_dir"], "steps_in_window",
                         {"run": Run()}) == 3
    with pytest.raises(KeyError):
        C.resolve("no-such.cell", root)


def run_main(conf, trace, devices, cell=None):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = R.main(["--workload", "tiny", "--seed", str(2**35 + 1),
                     "--seconds", "1.5", "--trace", str(trace)],
                    cell=cell or tiny_cell(conf), devices=devices)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_loop_yields_last_line_keys(trace):
    conf = tiny_conf("starcoder2-15b-l10")
    res = run_main(conf, trace, jax.devices("cpu")[:1])
    assert list(res)[:3] == ["correct", "attempted", "failed"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(res["device"])
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        # host-side readers and the engine's counters read; device shares
        # and the trace's readers stay silent on a CPU
        assert {"compile_s", "queue_wait_p90_ms", "decode_slots_mean",
                "prefill_pad_frac"} <= set(res["metrics"])
        assert 0 <= res["metrics"]["prefill_pad_frac"]["value"] < 1
        assert not {"prefill_mfu", "decode_attn_roofline",
                    "decode_device_ms"} & set(res["metrics"])
    else:
        assert set(res["metrics"]) == {m["name"]
                                       for m in SPEC["end_to_end"]}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}


def test_profiler_stops_after_the_window_with_gc_off(monkeypatch, tmp_path):
    conf = tiny_conf("starcoder2-15b-l10")
    built = C.build(C.load_model("dense"), conf, 11, jax.devices("cpu")[:1])
    vocab = built.cfg.vocab_size
    loop.warm_up(built.engine, vocab, C.rng(11, 4))
    seen = {}
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace

    def on(name, real):
        def call(*a):
            seen[name] = (loop.clock(), gc.isenabled())
            real(*a)
        return call

    monkeypatch.setattr(jax.profiler, "start_trace", on("start", start))
    monkeypatch.setattr(jax.profiler, "stop_trace", on("stop", stop))
    run = loop.serve(built.engine, Traffic(TINY_MIX, 11, vocab),
                     preroll_s=0.3, seconds=2.0, trace_dir=str(tmp_path),
                     trace_s=0.5)
    t_start, gc_on = seen["start"]
    assert run.w0 <= t_start - run.start < run.w1 - 0.5 and not gc_on
    assert seen["stop"][0] - run.start >= run.w1
    assert gc.isenabled() and run.gc_pauses == [] and run.gc_objects > 0
    assert all(len(s.host) == len(loop.HOST) for s in run.steps)
    assert loop.trace_file(str(tmp_path)) is not None
    lo, hi = run.traced
    assert t_start - run.start <= lo < hi <= run.w1
    # no counters declared: none kept
    assert run.counters == {} and all(s.counters == {} for s in run.steps)


def test_counters_are_the_change_over_steps_and_window():
    conf = tiny_conf("starcoder2-15b-l10")
    eng = C.build(C.load_model("dense"), conf, 13,
                  jax.devices("cpu")[:1]).engine
    loop.warm_up(eng, 256, C.rng(13, 4))
    seen = []                      # prefill_tokens before each step
    step = eng.step

    def spy():
        seen.append(eng.prefill_tokens)
        return step()

    eng.step = spy
    names = ("prefill_tokens", "prefill_slot_tokens", "no_such_counter")
    run = loop.serve(eng, Traffic(TINY_MIX, 13, 256), preroll_s=0.3,
                     seconds=1.5, counters=names)
    seen.append(eng.prefill_tokens)
    assert len(seen) == len(run.steps) + 1
    assert [s.counters["prefill_tokens"] for s in run.steps] == \
        [b - a for a, b in zip(seen, seen[1:])]
    first = next(i for i, s in enumerate(run.steps) if s.t0 >= run.w0)
    assert run.counters["prefill_tokens"] == seen[-1] - seen[first] > 0
    assert run.counters["prefill_slot_tokens"] >= \
        run.counters["prefill_tokens"]
    # a missing attribute reads None; an undeclared one is not kept
    assert run.counters["no_such_counter"] is None
    assert all(s.counters["no_such_counter"] is None for s in run.steps)
    assert "step_no" not in run.counters
    assert set(run.counters) == set(names)


def test_counters_declared_by_readers_and_model_once():
    cell = tiny_cell(tiny_conf("starcoder2-15b-l10"))
    assert R.counters_of(cell) == ("prefill_tokens", "prefill_slot_tokens")

    class Model:
        COUNTERS = ("prefill_tokens", "routed_tokens")

    cell["model"] = Model
    assert R.counters_of(cell) == ("prefill_tokens", "prefill_slot_tokens",
                                   "routed_tokens")


def test_record_keeps_the_steps_of_the_traced_span():
    class Run:
        traced = (1.0, 2.0)
        steps = [Step("decode", t, t + 0.1, [5], []) for t in
                 (0.5, 1.0, 1.5, 2.0)]

    model = C.load_model("dense")
    conf = C.load_json(ROOT / "bench" / "configs" / "starcoder2-15b-l10.json")
    rec = R.record(Run(), conf, model, "TPU v5 lite", 1, 0.0, {}, 0)
    assert [s.t0 for s in rec["traced_steps"]] == [1.0, 1.5]
    assert rec["model"] is model and rec["shapes"] == model.shapes(conf)
    Run.traced = None
    assert R.record(Run(), conf, model, "cpu", 1, 0.0, {}, 0)[
        "traced_steps"] == []


def test_decode_attn_roofline_reads_one_scope_of_one_program():
    model = C.load_model("dense")
    conf = C.load_json(ROOT / "bench" / "configs" / "starcoder2-15b-l10.json")
    s = model.shapes(conf)
    trace = {"device_scopes": [["jit_serve_decode", "mlp", 1.0],
                               ["jit_serve_decode", "decode_attn", 0.004],
                               ["jit_serve_prefill_512", "decode_attn", 1.0]]}
    rec = {"trace": trace, "model": model, "shapes": s, "chips": 1,
           "peak": counts.peak("TPU v5 lite"),
           "traced_steps": [Step("decode", 1.0, 1.02, [1000, 2000], []),
                            Step("prefill", 1.02, 1.1, [], [500]),
                            Step("decode", 1.1, 1.12, [1001, 2001], [])]}
    kv = model.kv_bytes_per_token(s) * 6002
    got = R.read_metric(ROOT / "bench" / "metrics", "decode_attn_roofline",
                        rec)
    assert got == pytest.approx(100 * kv / (819e9 * 0.004))
    assert 0 < got < 100

    class NoCount:
        pass

    for silent in ({"trace": {}}, {"trace": None}, {"peak": None},
                   {"traced_steps": []}, {"model": NoCount}):
        assert R.read_metric(ROOT / "bench" / "metrics",
                             "decode_attn_roofline", {**rec, **silent}) \
            is None


@pytest.mark.parametrize("name", ["decode_device_ms", "decode_host_ms",
                                  "decode_attn_island_ms"])
def test_trace_readers_pass_the_reduction_on(name):
    metrics = ROOT / "bench" / "metrics"
    assert R.read_metric(metrics, name, {"trace": {name: 3.65}}) == 3.65
    assert R.read_metric(metrics, name, {"trace": {name: None}}) is None
    assert R.read_metric(metrics, name, {"trace": {}}) is None


def test_prefill_pad_frac_reads_the_window_counters():
    class Run:
        counters = {"prefill_tokens": 300, "prefill_slot_tokens": 800}

    metrics = ROOT / "bench" / "metrics"
    assert R.read_metric(metrics, "prefill_pad_frac", {"run": Run()}) == \
        pytest.approx(1 - 300 / 800)
    Run.counters = {"prefill_tokens": None, "prefill_slot_tokens": 800}
    assert R.read_metric(metrics, "prefill_pad_frac", {"run": Run()}) \
        is None


def counted(f, name, calls):
    def call(*a, **k):
        calls[name] += 1
        return f(*a, **k)
    return call


def test_new_architecture_joins_by_new_files(tmp_path, monkeypatch):
    """A configuration naming a model module that only its own tree has
    (the dense module with the GELU's tanh approximation off): the cell,
    the engine's check, the reference and the readers all use it, and no
    file of the copied ``bench/`` changes."""
    root = copy_tree(tmp_path)
    b = root / "bench"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    dense = (b / "models" / "dense.py").read_text()
    assert dense.count("approximate=True") == 1
    (b / "models" / "dense_erf.py").write_text(
        dense.replace("approximate=True", "approximate=False"))
    conf = C.load_json(b / "configs" / "starcoder2-15b-l10.json")
    (b / "configs" / "erf-model.json").write_text(
        json.dumps({**conf, "bench_model": "dense_erf"}))
    (b / "metrics" / "decode_kv_bytes.py").write_text(
        "from bench.counts import total\n\n\n"
        "def read(rec):\n"
        "    steps = [x for x in rec['run'].steps if x.kind == 'decode']\n"
        "    return total(rec['model'], 'decode_attn_bytes', rec['shapes'],"
        " steps)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "erf-model", "source": "x",
                            "file": "bench/configs/erf-model.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "erf-model.chat",
                              "config": "erf-model", "traffic": "chat",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "decode_kv_bytes", "unit": "B",
                              "better": "lower", "source": "program_counter",
                              "layer": "x", "moves": "itl_p99_ms",
                              "workloads": ["erf-model.chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = C.resolve("erf-model.chat", root)
    model = cell["model"]
    assert Path(model.__file__) == b / "models" / "dense_erf.py"
    assert model is not C.load_model("dense")
    names = [m["name"] for m in cell["per_layer"]]
    assert "decode_kv_bytes" in names and "decode_attn_roofline" not in names
    calls = Counter()
    for name in ("arch_config", "shapes", "logits", "decode_attn_bytes"):
        monkeypatch.setattr(model, name,
                            counted(getattr(model, name), name, calls))
    cell.update(config=tiny_conf("erf-model", root=root),
                traffic=dict(TINY_MIX))
    res = run_main(None, 1, jax.devices("cpu")[:1], cell=cell)
    assert res["correct"] is True
    assert calls["arch_config"] == 1 and calls["shapes"] == 1
    assert calls["logits"] >= 3                    # a sampled request each
    assert calls["decode_attn_bytes"] >= 1
    assert res["metrics"]["decode_kv_bytes"]["value"] > 0
    # the reference the module gives is its own: exact GELU, not tanh
    w = C.make_weights(C.build(C.load_model("dense"), cell["config"], 3,
                               jax.devices("cpu")[:1]).tmpl, 3, 64)
    tokens, rows = np.arange(128, dtype=np.int32) % 256, np.arange(4)
    erf = np.asarray(model.logits(w, cell["config"], tokens, rows))
    tanh = np.asarray(C.load_model("dense").logits(w, cell["config"],
                                                   tokens, rows))
    assert 0 < np.abs(erf - tanh).max() < 1e-2
    after = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()
             and "__pycache__" not in p.parts}
    assert {p: v for p, v in after.items() if p in before} == \
        {p: v for p, v in before.items() if "__pycache__" not in p.parts}
    assert {p.relative_to(b).as_posix() for p in set(after) - set(before)} \
        == {"models/dense_erf.py", "configs/erf-model.json",
            "metrics/decode_kv_bytes.py"}


def test_four_chip_path_on_emulated_mesh():
    conf = tiny_conf("internlm2-20b-tp4", mesh=[1, 4], prefill_batch=2)
    res = run_main(conf, 0, jax.devices("cpu")[:4])
    assert res["correct"] is True and res["device"]["count"] == 4


def bench_cmd(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "starcoder2-15b-l10.chat", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_nonzero_with_no_result():
    p = bench_cmd(ROOT)
    assert p.returncode == 2
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_only_benchmark_files_exits_nonzero(tmp_path):
    p = bench_cmd(copy_tree(tmp_path))
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_benchmark_json_keeps_to_its_limits():
    s = SPEC
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= s["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (s["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for p in s["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
    names = ([c["name"] for c in s["configs"]]
             + [w["name"] for w in s["workloads"]]
             + [m["name"] for m in s["end_to_end"] + s["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in s["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
        conf = C.load_json(ROOT / c["file"])
        assert set(c["reduced"]) == set(conf["reduced"])
        model = C.load_model(conf["bench_model"])
        assert all(callable(getattr(model, f, None)) for f in
                   ("arch_config", "shapes", "logits", "tiny"))
    for w in s["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json"
                ).is_file()
    e2e = {m["name"] for m in s["end_to_end"]}
    assert "setup_s" in e2e
    for m in s["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in s["workloads"]}
    for m in s["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
        assert m["moves"] in e2e
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", m["unit"])
    assert sum(w["chips"] == 4 for w in s["workloads"]) <= \
        max(1, len(s["workloads"]) // 2)
    assert os.path.getsize(ROOT / "BENCHMARK.json") <= 64 * 1024


#: keys and names of one architecture's layers: only ``bench/models/`` and
#: ``bench/configs/`` may hold them
ARCH_WORDS = re.compile(
    r"\b(hidden_size|intermediate_size|num_hidden_layers|num_attention_heads"
    r"|num_key_value_heads|head_dim|swiglu|gelu|silu)\b|[\"'](wq|w1)[\"']")


def test_harness_names_no_architecture():
    files = [p for p in (ROOT / "bench").glob("*.py")
             if not p.name.startswith(("test_", "conftest"))]
    files += list((ROOT / "bench" / "metrics").glob("*.py"))
    for p in files:
        assert ARCH_WORDS.search(p.read_text().lower()) is None, p.name
    assert ARCH_WORDS.search(
        (ROOT / "bench" / "models" / "dense.py").read_text())
