"""The harness on the CPU: traffic per seed, cells found by name, the last
line's keys, refusal without a TPU, and BENCHMARK.json's own limits."""

import gc
import io
import json
import os
import re
import shutil
import subprocess
import sys
from contextlib import redirect_stdout

import jax
import numpy as np
import pytest

from bench import cell as C
from bench import loop
from bench import run as R
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


def run_main(conf, trace, devices):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = R.main(["--workload", "tiny", "--seed", str(2**35 + 1),
                     "--seconds", "1.5", "--trace", str(trace)],
                    cell=tiny_cell(conf), devices=devices)
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
        # host-side readers read; device shares stay silent on a CPU
        assert {"compile_s", "queue_wait_p90_ms", "decode_slots_mean"} <= \
            set(res["metrics"])
        assert "prefill_mfu" not in res["metrics"]
    else:
        assert set(res["metrics"]) == {m["name"]
                                       for m in SPEC["end_to_end"]}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}


def test_profiler_stops_after_the_window_with_gc_off(monkeypatch, tmp_path):
    conf = tiny_conf("starcoder2-15b-l10")
    built = C.build(conf, 11, jax.devices("cpu")[:1])
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
    for m in s["per_layer"]:
        assert m["moves"] in e2e
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", m["unit"])
    assert sum(w["chips"] == 4 for w in s["workloads"]) <= \
        max(1, len(s["workloads"]) // 2)
    assert os.path.getsize(ROOT / "BENCHMARK.json") <= 64 * 1024
