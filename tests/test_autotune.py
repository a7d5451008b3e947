"""Empirical autotuner: calibration round-trip, measured-policy dispatch,
graceful analytic fallback, CLI, and the bench-artifact schema."""

import dataclasses
import importlib.util
import json
import sys
import warnings
from pathlib import Path

import pytest

# the harness (benchmarks/) lives next to src/, not inside it
sys.path.insert(0, str(Path(__file__).parent.parent))

from repro.core import autotune
from repro.core.comms import CommContext

N = 4


@pytest.fixture(scope="module")
def table(mesh4):
    """One real (tiny-grid) calibration of the 4-device CPU mesh."""
    return autotune.calibrate(mesh=mesh4, grid="tiny", reps=1)


@pytest.fixture(autouse=True)
def _fresh_caches():
    autotune.clear_caches()
    yield
    autotune.clear_caches()


def _synthetic(fingerprint, rows, **corr):
    corrections = {"ici_bandwidth": 1e8, "remote_sync_s": 1e-4,
                   "gemm_efficiency": 1e-4, "kernel_launch_s": 1e-5}
    corrections.update(corr)
    return autotune.CalibrationTable(fingerprint=fingerprint,
                                     corrections=corrections,
                                     measurements=rows)


def _rows(op, us_by_backend, m, n, k, axis_size=N):
    return [{"op": op, "backend": be, "axis_size": axis_size,
             "m": m, "n": n, "k": k, "us": us}
            for be, us in us_by_backend.items()]


# ---------------------------------------------------------------------------
# Calibration + persistence
# ---------------------------------------------------------------------------

def test_calibrate_covers_registered_backends(table, mesh4):
    cov = table.ops_covered()
    for op in ("all_gather_matmul", "matmul_reduce_scatter",
               "matmul_all_reduce", "psum"):
        assert cov.get(op), f"no measurements for {op}"
    backends = {(r["op"], r["backend"]) for r in table.measurements}
    assert ("all_gather_matmul", "bulk") in backends
    assert ("all_gather_matmul", "ring") in backends
    assert ("all_gather_matmul", "ring_bidir") in backends
    assert ("psum", "ring") in backends
    for key in ("ici_bandwidth", "remote_sync_s", "gemm_efficiency",
                "kernel_launch_s"):
        assert table.corrections[key] > 0, key
    live = autotune.live_fingerprint("tpu_v5e", mesh4)
    assert table.fingerprint.compatible(live, strict=True)


def test_round_trip_through_json(table, tmp_path, mesh4):
    path = table.save(tmp_path / "cal.json")
    loaded = autotune.CalibrationTable.load(path)
    assert loaded.to_json() == table.to_json()

    # the loaded table drives a measured context exactly like the original
    for cal in (loaded, str(path)):
        ctx = CommContext(axis_name="x", mesh=mesh4, policy="measured",
                          calibration=cal)
        active = ctx.active_calibration()
        assert active is not None
        assert active.corrections == table.corrections
        hw = ctx.effective_hw()
        assert hw.ici_bandwidth == pytest.approx(
            table.corrections["ici_bandwidth"])
        assert hw.gemm_efficiency < 1.0
        assert hw is not ctx.hw


def test_rejects_foreign_schema(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"schema": "something-else/v9"}))
    with pytest.raises(ValueError, match="repro-autotune"):
        autotune.CalibrationTable.load(p)


def test_measured_us_refuses_far_extrapolation(table):
    row = next(r for r in table.measurements if r["op"] == "psum")
    near = table.measured_us("psum", row["backend"], row["m"], row["n"],
                             row["k"])
    assert near == pytest.approx(row["us"])
    far = table.measured_us("psum", row["backend"], row["m"] * 100,
                            row["n"] * 100, max(row["k"] * 100, 1))
    assert far is None


def test_calibration_rows_match_dispatch_coordinates(mesh4, table):
    """The (m, n, k) calibrate() stores must be the exact coordinates
    auto_gemm_backend queries with — a systematic offset (e.g. recording
    AG rows at m * n_dev) would put every row past the 4x lookup cutoff
    and measured dispatch would silently never activate."""
    for op in ("all_gather_matmul", "matmul_reduce_scatter",
               "matmul_all_reduce"):
        r = next(r for r in table.measurements if r["op"] == op)
        assert table.best_backend(op, r["m"], r["n"], r["k"],
                                  allowed=("bulk", "ring"),
                                  axis_size=N) is not None, op

    # AG end-to-end: a (nsz, nsz//4) global operand sharded over the axis
    # dispatches at m = m_loc * n_dev = nsz — the grid point itself, so the
    # measured argmin (not the analytic policy) must decide
    nsz = 128
    measured = {r["backend"]: r["us"] for r in table.measurements
                if r["op"] == "all_gather_matmul" and r["m"] == nsz}
    assert measured, "tiny grid did not store AG rows at dispatch m"
    ctx = CommContext(axis_name="x", mesh=mesh4, policy="measured",
                      calibration=table)
    assert ctx.auto_gemm_backend("all_gather_matmul", nsz, nsz // 4,
                                 nsz // 4) == min(measured, key=measured.get)


# ---------------------------------------------------------------------------
# Measured-policy dispatch
# ---------------------------------------------------------------------------

def test_measured_policy_overrides_analytic_choice(mesh4):
    """Shapes where the analytic model says bulk (tiny GEMM) dispatch to
    ring when the measurements say ring is faster — and vice versa."""
    live = autotune.live_fingerprint("tpu_v5e", mesh4)
    analytic = CommContext(axis_name="x", mesh=mesh4)

    # analytic: tiny GEMM -> bulk. measured: ring 10x faster -> ring.
    t = _synthetic(live, _rows("matmul_reduce_scatter",
                               {"bulk": 1000.0, "ring": 100.0}, 64, 16, 8))
    ctx = CommContext(axis_name="x", mesh=mesh4, policy="measured",
                      calibration=t)
    assert analytic.auto_gemm_backend("matmul_reduce_scatter", 64, 16, 8) \
        == "bulk"
    assert ctx.auto_gemm_backend("matmul_reduce_scatter", 64, 16, 8) == "ring"

    # analytic: big GEMM -> ring. measured: bulk faster -> bulk.
    big = 8192
    t2 = _synthetic(live, _rows("matmul_reduce_scatter",
                                {"bulk": 50.0, "ring": 500.0}, big, big, big))
    ctx2 = CommContext(axis_name="x", mesh=mesh4, policy="measured",
                       calibration=t2)
    assert analytic.auto_gemm_backend("matmul_reduce_scatter", big, big, big) \
        == "ring"
    assert ctx2.auto_gemm_backend("matmul_reduce_scatter", big, big, big) \
        == "bulk"


def test_measured_dispatch_respects_feasibility(mesh4):
    """A measured win for ring_bidir must not leak to calls whose operands
    cannot split across the two rings (bidir_ok=False)."""
    live = autotune.live_fingerprint("tpu_v5e", mesh4)
    rows = _rows("all_gather_matmul",
                 {"bulk": 900.0, "ring": 500.0, "ring_bidir": 100.0},
                 512, 128, 128)
    ctx = CommContext(axis_name="x", mesh=mesh4, policy="measured",
                      calibration=_synthetic(live, rows))
    assert ctx.auto_gemm_backend("all_gather_matmul", 512, 128, 128) \
        == "ring_bidir"
    assert ctx.auto_gemm_backend("all_gather_matmul", 512, 128, 128,
                                 bidir_ok=False) == "ring"


def test_measured_needs_two_backends(mesh4):
    """One-sided coverage is not a comparison: dispatch falls back to the
    analytic policy rather than echoing the only measured backend."""
    live = autotune.live_fingerprint("tpu_v5e", mesh4)
    t = _synthetic(live, _rows("matmul_reduce_scatter", {"ring": 1.0},
                               64, 16, 8))
    ctx = CommContext(axis_name="x", mesh=mesh4, policy="measured",
                      calibration=t)
    assert ctx.auto_gemm_backend("matmul_reduce_scatter", 64, 16, 8) == "bulk"


def test_measured_dispatch_is_dtype_aware(mesh4):
    """bf16-measured rows must not decide for f32 payloads: ring's measured
    win comes from halving the bytes, which an f32 payload doesn't get."""
    live = autotune.live_fingerprint("tpu_v5e", mesh4)
    rows = _rows("psum", {"bulk": 999.0, "ring": 1.0}, N, 64, 1)
    for r in rows:
        r["dtype_bytes"] = 2
    t = _synthetic(live, rows)
    assert t.best_backend("psum", N, 64, 1, allowed=("bulk", "ring"),
                          axis_size=N, dtype_bytes=2) == "ring"
    assert t.best_backend("psum", N, 64, 1, allowed=("bulk", "ring"),
                          axis_size=N, dtype_bytes=4) is None
    # rows without a recorded dtype (older tables) stay dtype-agnostic
    for r in rows:
        del r["dtype_bytes"]
    assert t.best_backend("psum", N, 64, 1, allowed=("bulk", "ring"),
                          axis_size=N, dtype_bytes=4) == "ring"


def test_measured_psum_dispatch(mesh4, table, monkeypatch):
    """psum's auto backend consults the table: with ring measured 999x
    faster (dtype-agnostic synthetic rows), a f32 payload — which the
    analytic heuristic sends to bulk — dispatches to the ring impl."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import repro.core.comms as comms
    from repro import compat

    nsz = 64
    live = autotune.live_fingerprint("tpu_v5e", mesh4)
    t = _synthetic(live, _rows("psum", {"bulk": 999.0, "ring": 1.0},
                               N, nsz, 1))
    ctx = CommContext(axis_name="x", mesh=mesh4, policy="measured",
                      calibration=t)

    calls = []
    orig = comms.pk_psum_ring
    monkeypatch.setattr(comms, "pk_psum_ring",
                        lambda *a, **k: calls.append(1) or orig(*a, **k))
    x = jnp.ones((N, N, nsz), jnp.float32)   # per-device payload: (N, nsz)
    compat.shard_map(lambda v: ctx.psum(v[0])[None], mesh=mesh4,
                     in_specs=P("x"), out_specs=P("x"), check_vma=False)(x)
    assert calls, "measured policy did not route psum to the ring impl"


# ---------------------------------------------------------------------------
# Island-keyed dispatch (calibrate --per-island)
# ---------------------------------------------------------------------------

MLP_KEY = autotune.island_key("mlp", "matmul_all_reduce", 2)
ATTN_KEY = autotune.island_key("attn_out", "matmul_all_reduce", 2)


def _island_table(live):
    """Global rows say bulk wins; mlp-island rows say ring wins; attn_out
    rows agree with the global table — all at the same (m, n, k)."""
    rows = _rows("matmul_all_reduce", {"bulk": 10.0, "ring": 100.0},
                 256, 64, 16)
    mlp = _rows("matmul_all_reduce", {"bulk": 100.0, "ring": 10.0},
                256, 64, 16)
    for r in mlp:
        r["island"] = MLP_KEY
    attn = _rows("matmul_all_reduce", {"bulk": 5.0, "ring": 500.0},
                 256, 64, 16)
    for r in attn:
        r["island"] = ATTN_KEY
    return _synthetic(live, rows + mlp + attn)


def test_island_keyed_rows_beat_global(mesh4):
    """Two islands with different layouts can resolve to different backends
    at the SAME (m, n, k): each context prefers its own island's rows and
    only falls back to the global grid when it has none."""
    live = autotune.live_fingerprint("tpu_v5e", mesh4)
    t = _island_table(live)
    mk = dict(mesh=mesh4, policy="measured", calibration=t)
    no_key = CommContext(axis_name="x", **mk)
    mlp = CommContext(axis_name="x", island=MLP_KEY, **mk)
    attn = CommContext(axis_name="x", island=ATTN_KEY, **mk)
    other = CommContext(axis_name="x",
                        island=autotune.island_key("decode", "psum", 4), **mk)
    assert no_key.auto_gemm_backend("matmul_all_reduce", 256, 64, 16) == "bulk"
    assert mlp.auto_gemm_backend("matmul_all_reduce", 256, 64, 16) == "ring"
    assert attn.auto_gemm_backend("matmul_all_reduce", 256, 64, 16) == "bulk"
    # an island with no rows of its own falls back to the global grid
    assert other.auto_gemm_backend("matmul_all_reduce", 256, 64, 16) == "bulk"


def test_island_rows_never_leak_across_islands(mesh4):
    """An island whose key has rows must not see another island's rows as
    evidence — only its own tier, then the global tier."""
    live = autotune.live_fingerprint("tpu_v5e", mesh4)
    mlp = _rows("matmul_all_reduce", {"bulk": 100.0, "ring": 10.0},
                256, 64, 16)
    for r in mlp:
        r["island"] = MLP_KEY
    t = _synthetic(live, mlp)           # island rows ONLY, no global tier
    attn = CommContext(axis_name="x", mesh=mesh4, policy="measured",
                       calibration=t, island=ATTN_KEY)
    # attn_out has no rows and there is no global tier -> analytic fallback
    # (tiny GEMM -> bulk)
    assert attn.auto_gemm_backend("matmul_all_reduce", 256, 64, 16) == "bulk"
    assert t.best_backend("matmul_all_reduce", 256, 64, 16,
                          allowed=("bulk", "ring"), axis_size=N,
                          island=ATTN_KEY) is None


def test_best_chunks_measured(mesh4):
    """best_chunks returns the argmin-us chunk count at the nearest point
    with >= 2 distinct measured counts; one count is not a comparison."""
    live = autotune.live_fingerprint("tpu_v5e", mesh4)
    rows = []
    for c, us in ((1, 100.0), (2, 40.0), (4, 60.0)):
        rows.append({"op": "matmul_reduce_scatter", "backend": "ring",
                     "axis_size": N, "m": 256, "n": 64, "k": 16,
                     "n_chunks": c, "us": us})
    t = _synthetic(live, rows)
    assert t.best_chunks("matmul_reduce_scatter", "ring", 256, 64, 16,
                         axis_size=N) == 2
    one = _synthetic(live, rows[:1])
    assert one.best_chunks("matmul_reduce_scatter", "ring", 256, 64, 16,
                           axis_size=N) is None
    # the measured count feeds the context's chunk resolution
    ctx = CommContext(axis_name="x", mesh=mesh4, policy="measured",
                      calibration=t)
    sched = ctx.gemm_chunk_schedule("matmul_reduce_scatter", 256, 64, 16,
                                    backend="ring")
    assert sched.n_chunks == 2 and sched.source == "measured"


def test_per_island_calibrate_sweeps_and_dispatches(mesh4):
    """calibrate(islands=...) measures backend x chunk rows tagged with the
    island key at the island's exact coordinates, and a context carrying
    that key dispatches from them."""
    sweeps = (autotune.IslandSweep(island=MLP_KEY, op="matmul_all_reduce",
                                   m=8 * N, n=16, k=8),)
    table = autotune.calibrate(mesh=mesh4, grid="tiny", reps=1,
                               islands=sweeps)
    tagged = [r for r in table.measurements if r.get("island") == MLP_KEY]
    assert {r["backend"] for r in tagged} >= {"bulk", "ring"}
    assert {r["n_chunks"] for r in tagged if r["backend"] == "ring"} \
        == set(autotune.ISLAND_CHUNK_SWEEP)
    assert all((r["m"], r["n"], r["k"]) == (8 * N, 16, 8) for r in tagged)
    ctx = CommContext(axis_name="x", mesh=mesh4, policy="measured",
                      calibration=table, island=MLP_KEY)
    # island rows cover >= 2 backends at the exact coordinates: the measured
    # argmin (whichever side won on this machine) decides, not the analytic
    # small-GEMM heuristic
    best = min(((r["backend"], r["us"]) for r in tagged),
               key=lambda be_us: be_us[1])[0]
    assert ctx.auto_gemm_backend("matmul_all_reduce", 8 * N, 16, 8) == best


def test_per_island_calibrate_dtype_axis(mesh4):
    """The --per-island dtype axis (CLI helper + core sweep): a GEMM island
    swept at b2 AND its int8-wire twin produce paired ``…|b2`` / ``…|b1``
    row families at the same coordinates, each tagged with its own
    dtype_bytes — so the b1 dispatch query never reads b2 evidence."""
    from repro.autotune import int8_island_sweeps
    sweeps = [autotune.IslandSweep(island=MLP_KEY, op="matmul_all_reduce",
                                   m=8 * N, n=16, k=8)]
    sweeps += int8_island_sweeps(sweeps)
    b1_key = autotune.island_key("mlp", "matmul_all_reduce", 1)
    assert [sw.island for sw in sweeps] == [MLP_KEY, b1_key]
    table = autotune.calibrate(mesh=mesh4, grid="tiny", reps=1,
                               islands=tuple(sweeps))
    by_key = {key: [r for r in table.measurements
                    if r.get("island") == key]
              for key in (MLP_KEY, b1_key)}
    for key, want_b in ((MLP_KEY, 2), (b1_key, 1)):
        rows = by_key[key]
        assert rows, key
        assert all(r["dtype_bytes"] == want_b for r in rows)
        assert all((r["m"], r["n"], r["k"]) == (8 * N, 16, 8) for r in rows)
        assert {r["backend"] for r in rows} >= {"bulk", "ring"}


def test_measured_us_island_dtype_precedence(mesh4):
    """Same island name calibrated at both widths: each context reads its
    own ``b{dtype}`` family; measured_us at one width never falls through
    to the other width's rows."""
    live = autotune.live_fingerprint("tpu_v5e", mesh4)
    b1_key = autotune.island_key("mlp", "matmul_all_reduce", 1)
    rows = []
    for key, db, us in ((MLP_KEY, 2, 10.0), (b1_key, 1, 99.0)):
        rows.append({"op": "matmul_all_reduce", "backend": "ring",
                     "axis_size": N, "m": 256, "n": 64, "k": 16,
                     "dtype_bytes": db, "n_chunks": 1, "island": key,
                     "us": us})
    t = _synthetic(live, rows)
    assert t.measured_us("matmul_all_reduce", "ring", 256, 64, 16,
                         axis_size=N, dtype_bytes=2,
                         island=MLP_KEY) == 10.0
    assert t.measured_us("matmul_all_reduce", "ring", 256, 64, 16,
                         axis_size=N, dtype_bytes=1,
                         island=b1_key) == 99.0
    # cross-width queries find nothing in the island tier (and there is no
    # matching global row): width is part of the evidence, not a fallback
    assert t.measured_us("matmul_all_reduce", "ring", 256, 64, 16,
                         axis_size=N, dtype_bytes=1, island=MLP_KEY,
                         island_only=True) is None
    assert t.measured_us("matmul_all_reduce", "ring", 256, 64, 16,
                         axis_size=N, dtype_bytes=2, island=b1_key,
                         island_only=True) is None


# ---------------------------------------------------------------------------
# Graceful fallback
# ---------------------------------------------------------------------------

def test_measured_without_table_warns_and_falls_back(mesh4, tmp_path,
                                                     monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "empty"))
    monkeypatch.setattr(autotune, "_SEED_DIR", tmp_path / "no-seeds")
    autotune.clear_caches()
    ctx = CommContext(axis_name="x", mesh=mesh4, policy="measured")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert ctx.active_calibration() is None
        be = ctx.auto_gemm_backend("matmul_reduce_scatter", 16, 12, 32)
    assert be == "bulk"        # identical to the analytic policy
    assert any("falling back to analytic" in str(w.message) for w in rec)


def test_unreadable_explicit_path_warns_and_falls_back(mesh4, tmp_path):
    ctx = CommContext(axis_name="x", mesh=mesh4, policy="measured",
                      calibration=str(tmp_path / "missing.json"))
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert ctx.active_calibration() is None
    assert any("could not be loaded" in str(w.message) for w in rec)


def test_fingerprint_mismatch_falls_back(mesh4, table):
    foreign = dataclasses.replace(table.fingerprint, backend="tpu",
                                  device_kind="TPU v5 lite")
    t = autotune.CalibrationTable(fingerprint=foreign,
                                  corrections=dict(table.corrections),
                                  measurements=list(table.measurements))
    ctx = CommContext(axis_name="x", mesh=mesh4, policy="measured",
                      calibration=t)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert ctx.active_calibration() is None
        assert ctx.effective_hw() is ctx.hw
    assert any("does not match" in str(w.message) for w in rec)
    # an EXPLICITLY supplied table that is rejected warns under "auto" too
    auto_ctx = CommContext(axis_name="x", mesh=mesh4, policy="auto",
                           calibration=t)
    autotune.clear_caches()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert auto_ctx.active_calibration() is None
    assert any("does not match" in str(w.message) for w in rec)


def test_auto_policy_silent_on_implicit_miss(mesh4, tmp_path, monkeypatch):
    """auto's silence is reserved for the implicit cache/seed search
    finding nothing — no tables anywhere, no warning."""
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "empty"))
    monkeypatch.setattr(autotune, "_SEED_DIR", tmp_path / "no-seeds")
    autotune.clear_caches()
    ctx = CommContext(axis_name="x", mesh=mesh4, policy="auto")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert ctx.active_calibration() is None
    assert not rec


def test_unknown_policy_rejected(mesh4):
    ctx = CommContext(axis_name="x", mesh=mesh4, policy="vibes")
    with pytest.raises(ValueError, match="unknown comm policy"):
        ctx.active_calibration()


def test_auto_policy_finds_cached_table(mesh4, table, tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_caches()
    marked = autotune.CalibrationTable(
        fingerprint=table.fingerprint,
        corrections={**table.corrections, "gemm_efficiency": 0.123},
        measurements=[], notes="from-the-cache")
    marked.save(autotune.cache_path(table.fingerprint))
    ctx = CommContext(axis_name="x", mesh=mesh4, policy="auto")
    active = ctx.active_calibration()
    assert active is not None
    # the user cache wins over any in-repo seed
    assert active.notes == "from-the-cache"
    assert ctx.effective_hw().gemm_efficiency == pytest.approx(0.123)


def test_shipped_seed_matches_emulated_mesh():
    """The checked-in cpu_emulated seed must keep fingerprint-matching a
    CPU process on the pinned jax version (else the measured policy can
    never activate from a clean checkout)."""
    seed = autotune.CalibrationTable.load(
        Path(autotune._SEED_DIR) / "cpu_emulated.json")
    live = autotune.live_fingerprint("tpu_v5e")
    if live.backend != "cpu":
        pytest.skip("seed table targets the CPU-emulated mesh")
    assert seed.fingerprint.compatible(live)
    assert autotune.find_table("tpu_v5e") is not None


def test_analytic_policy_ignores_tables(mesh4, table):
    ctx = CommContext(axis_name="x", mesh=mesh4, policy="analytic",
                      calibration=table)
    assert ctx.active_calibration() is None
    assert ctx.effective_hw() is ctx.hw


# ---------------------------------------------------------------------------
# Staleness (table age + spot-probe drift)
# ---------------------------------------------------------------------------

def test_unknown_hw_raises_instead_of_assuming_v5e(table):
    """A table stamped for hardware with no HardwareSpec is an error, not a
    silent fall-back to the v5e peaks."""
    from repro.core import costmodel as cm
    assert cm.spec_by_name("TPU_V5E") is cm.TPU_V5E
    foreign = dataclasses.replace(
        table, fingerprint=dataclasses.replace(table.fingerprint,
                                               hw="tpu_v7x"))
    with pytest.raises(KeyError, match="tpu_v7x"):
        autotune.staleness(foreign, probe=True)


def test_table_age_days_parses_created(table):
    t = dataclasses.replace(table, created="2020-01-01T00:00:00")
    assert autotune.table_age_days(t) > 365
    assert autotune.table_age_days(
        dataclasses.replace(table, created="not-a-date")) is None
    assert autotune.table_age_days(
        dataclasses.replace(table, created="")) is None


def test_staleness_flags_old_table(table):
    old = dataclasses.replace(table, created="2020-01-01T00:00:00")
    msgs = autotune.staleness(old, probe=False)
    assert len(msgs) == 1 and "days old" in msgs[0]


def test_staleness_fresh_table_quiet(table):
    import time as _time

    fresh = dataclasses.replace(
        table, created=_time.strftime("%Y-%m-%dT%H:%M:%S"))
    assert autotune.staleness(fresh, probe=False) == []


def test_staleness_spot_probe_catches_drift(table):
    import time as _time

    # a freshly-stamped table whose machine-local corrections are absurd:
    # only the spot probe can catch it
    drifted = autotune.CalibrationTable(
        fingerprint=table.fingerprint,
        corrections={**table.corrections, "kernel_launch_s": 1e4,
                     "gemm_efficiency": 1e9},
        created=_time.strftime("%Y-%m-%dT%H:%M:%S"))
    msgs = autotune.staleness(drifted, reps=1)
    assert any("kernel_launch_s drifted" in m for m in msgs)
    assert any("gemm_efficiency drifted" in m for m in msgs)


def test_measured_policy_warns_once_on_stale_table(mesh4, table):
    stale = dataclasses.replace(table, created="2020-01-01T00:00:00")
    ctx = CommContext(axis_name="x", mesh=mesh4, policy="measured",
                      calibration=stale)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        # the stale table is still USED (measured keeps dispatching on it)
        assert ctx.active_calibration() is stale
        assert ctx.active_calibration() is stale
    stale_warnings = [w for w in rec if "days old" in str(w.message)]
    assert len(stale_warnings) == 1    # warn-once, not per lookup


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_check_stale_and_fresh(table, tmp_path, capsys):
    import time as _time

    from repro.autotune import main

    old = dataclasses.replace(table, created="2020-01-01T00:00:00")
    p_old = old.save(tmp_path / "old.json")
    assert main(["check", str(p_old), "--no-probe"]) == 1
    assert "STALE" in capsys.readouterr().err

    fresh = dataclasses.replace(
        table, created=_time.strftime("%Y-%m-%dT%H:%M:%S"))
    p_fresh = fresh.save(tmp_path / "fresh.json")
    assert main(["check", str(p_fresh), "--no-probe"]) == 0
    assert "within threshold" in capsys.readouterr().out


def test_cli_show_and_diff(table, tmp_path, capsys):
    from repro.autotune import main

    a = table.save(tmp_path / "a.json")
    b_table = autotune.CalibrationTable(
        fingerprint=table.fingerprint,
        corrections={**table.corrections,
                     "ici_bandwidth": table.corrections["ici_bandwidth"] * 2},
        measurements=list(table.measurements))
    b = b_table.save(tmp_path / "b.json")

    assert main(["show", str(a)]) == 0
    out = capsys.readouterr().out
    assert "fingerprint:" in out and "ici_bandwidth" in out

    assert main(["diff", str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "+100.0%" in out

    assert main(["diff", str(a)]) == 0          # one-sided: vs analytic
    assert "analytic" in capsys.readouterr().out


def test_cli_diff_refuses_incompatible(table, tmp_path, capsys):
    from repro.autotune import main

    a = table.save(tmp_path / "a.json")
    foreign = autotune.CalibrationTable(
        fingerprint=dataclasses.replace(table.fingerprint, backend="tpu"),
        corrections=dict(table.corrections))
    b = foreign.save(tmp_path / "b.json")
    assert main(["diff", str(a), str(b)]) == 1
    assert "incompatible" in capsys.readouterr().err


def test_cli_calibrate_writes_cache(tmp_path, monkeypatch, capsys):
    from repro.autotune import main

    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    out = tmp_path / "fresh.json"
    assert main(["calibrate", "--grid", "tiny", "--reps", "1",
                 "--out", str(out)]) == 0
    t = autotune.CalibrationTable.load(out)
    assert t.ops_covered()
    assert t.corrections["ici_bandwidth"] > 0


# ---------------------------------------------------------------------------
# Bench artifact schema (scripts/check_bench.py)
# ---------------------------------------------------------------------------

def _load_check_bench():
    path = Path(__file__).parent.parent / "scripts" / "check_bench.py"
    spec = importlib.util.spec_from_file_location("check_bench", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_doc(**overrides):
    doc = {
        "schema": "repro-bench/v1", "created": "2026-01-01T00:00:00",
        "jax_version": "0", "backend": "cpu", "device_kind": "cpu",
        "n_devices": 8, "pred_hw": "tpu_v5e",
        "figures": [
            {"figure": "fig7", "status": "ok", "error": None, "n_rows": 2,
             "pred_err_median": 0.5,
             "rows": [
                 {"name": "fig7/pk/N=512", "us_per_call": 100.0,
                  "derived": "", "predicted_us": 50.0, "pred_err": -0.5},
                 {"name": "fig7/baseline/N=512", "us_per_call": 200.0,
                  "derived": "", "predicted_us": None, "pred_err": None},
             ]},
        ],
    }
    doc.update(overrides)
    return doc


def test_bench_schema_validation():
    cb = _load_check_bench()
    assert cb.validate_schema(_bench_doc()) == []
    assert cb.validate_schema({"schema": "nope"})
    assert cb.validate_schema(_bench_doc(figures=[{"figure": "x"}]))
    bad_row = _bench_doc()
    bad_row["figures"][0]["rows"][0]["us_per_call"] = "fast"
    assert any("us_per_call" in e for e in cb.validate_schema(bad_row))
    failed = _bench_doc()
    failed["figures"][0]["status"] = "failed"
    assert any("error" in e for e in cb.validate_schema(failed))
    # fig_health rows tag the serving condition: string or null only
    moded = _bench_doc()
    moded["figures"][0]["rows"][0]["mode"] = "degraded"
    assert cb.validate_schema(moded) == []
    moded["figures"][0]["rows"][0]["mode"] = 3
    assert any(".mode" in e for e in cb.validate_schema(moded))
    # fig_fused_chunks rows tag the sub-chunk count + schedule source
    chunked = _bench_doc()
    chunked["figures"][0]["rows"][0].update(sub_chunks=4,
                                            chunks_src="measured")
    assert cb.validate_schema(chunked) == []
    chunked["figures"][0]["rows"][0]["sub_chunks"] = 0
    assert any(".sub_chunks" in e for e in cb.validate_schema(chunked))
    chunked["figures"][0]["rows"][0].update(sub_chunks=4,
                                            chunks_src="vibes")
    assert any(".chunks_src" in e for e in cb.validate_schema(chunked))


def test_bench_regression_gate():
    cb = _load_check_bench()
    base = _bench_doc()
    ok = _bench_doc()
    assert cb.compare(ok, base, 0.25) == []
    slow = _bench_doc()
    for r in slow["figures"][0]["rows"]:
        r["us_per_call"] *= 1.5
    assert any("slowdown" in p for p in cb.compare(slow, base, 0.25))
    gone = _bench_doc(figures=[])
    assert any("not in run" in p for p in cb.compare(gone, base, 0.25))
    broke = _bench_doc()
    broke["figures"][0]["status"] = "failed"
    broke["figures"][0]["error"] = "boom"
    assert any("failed" in p for p in cb.compare(broke, base, 0.25))


def test_recorder_emits_valid_schema():
    cb = _load_check_bench()
    from benchmarks.common import Recorder

    rec = Recorder()
    rec.start_figure("figX")
    rec.add("figX/a", 10.0, "", 12.0)
    rec.add("figX/b", 20.0, "", None)
    rec.start_figure("figY")
    rec.fail(RuntimeError("exploded"))
    doc = rec.report()
    assert cb.validate_schema(doc) == []
    figy = doc["figures"][1]
    assert figy["status"] == "failed" and "exploded" in figy["error"]
    figx = doc["figures"][0]
    assert figx["pred_err_median"] == pytest.approx(0.2)


def test_checked_in_baseline_is_valid():
    cb = _load_check_bench()
    path = Path(__file__).parent.parent / "benchmarks" / "BENCH_baseline.json"
    doc = json.loads(path.read_text())
    assert cb.validate_schema(doc) == []
    assert all(f["status"] == "ok" for f in doc["figures"])


def test_best_backend_compares_only_shared_grid_points():
    """A backend measured only at a much smaller shape must not win on
    shape size: backends are compared at one shared (m, n, k) point."""
    fp = autotune.live_fingerprint("tpu_v5e")
    rows = [
        # bulk measured at both sizes; ring only at the small one (the
        # sweep's try/except skipped it) — 60us@128 vs 3000us@512 is not
        # an apples-to-apples race
        {"op": "matmul_all_reduce", "backend": "bulk", "axis_size": N,
         "m": 128, "n": 128, "k": 64, "us": 50.0},
        {"op": "matmul_all_reduce", "backend": "ring", "axis_size": N,
         "m": 128, "n": 128, "k": 64, "us": 60.0},
        {"op": "matmul_all_reduce", "backend": "bulk", "axis_size": N,
         "m": 512, "n": 128, "k": 64, "us": 3000.0},
    ]
    table = _synthetic(fp, rows)
    # querying at the large shape: the only shared point is (128, 128, 64),
    # where bulk wins — ring's small-shape time must not beat bulk's
    # large-shape time
    assert table.best_backend("matmul_all_reduce", 512, 128, 64,
                              allowed=("bulk", "ring"), axis_size=N) == "bulk"
    assert table.best_backend("matmul_all_reduce", 128, 128, 64,
                              allowed=("bulk", "ring"), axis_size=N) == "bulk"
    # a one-sided table (ring rows removed) yields no dispatch at all
    table1 = _synthetic(fp, [r for r in rows if r["backend"] == "bulk"])
    assert table1.best_backend("matmul_all_reduce", 128, 128, 64,
                               allowed=("bulk", "ring"), axis_size=N) is None


# ---------------------------------------------------------------------------
# all-to-all island sweep (Ulysses / MoE dispatch) + measured a2a dispatch
# ---------------------------------------------------------------------------

def _a2a_sweep():
    # local payload (8, 4, 16, 16), split heads (dim 1), concat seq (dim 2)
    shape = (8, 4, 16, 16)
    m, n, k = CommContext.a2a_coords(shape, 1, 2)
    return autotune.IslandSweep(island="attn_ulysses|all_to_all|b2",
                                op="all_to_all", m=m, n=n, k=k,
                                dtype_bytes=2, shape=shape,
                                split_axis=1, concat_axis=2)


def test_calibrate_sweeps_a2a_islands(mesh4):
    table = autotune.calibrate(mesh=mesh4, grid="tiny", reps=1,
                               islands=[_a2a_sweep()])
    rows = [r for r in table.measurements
            if r["op"] == "all_to_all" and r.get("island")]
    assert rows, "a2a island sweep produced no rows"
    backends = {(r["backend"], r["n_chunks"]) for r in rows}
    assert ("bulk", 1) in backends
    assert any(be == "chunked" and c > 1 for be, c in backends)
    sw = _a2a_sweep()
    for r in rows:
        assert (r["m"], r["n"], r["k"]) == (sw.m, sw.n, sw.k)
        assert r["island"] == sw.island


def test_a2a_chunk_schedule_measured_dispatch(mesh4, tmp_path):
    """a2a_chunk_schedule prefers island-keyed measured rows and reports
    the argmin chunk count; without usable rows it answers analytically."""
    sw = _a2a_sweep()
    fp = autotune.live_fingerprint("tpu_v5e", mesh4)

    def r(be, c, us):
        return {"op": "all_to_all", "backend": be, "axis_size": N,
                "m": sw.m, "n": sw.n, "k": sw.k, "dtype_bytes": 2,
                "n_chunks": c, "island": sw.island, "us": us}

    table = _synthetic(fp, [r("bulk", 1, 500.0), r("chunked", 2, 100.0),
                            r("chunked", 4, 300.0)])
    path = Path(table.save(tmp_path / "a2a.json"))
    autotune.clear_caches()
    ctx = CommContext(axis_name="x", mesh=mesh4, policy="measured",
                      calibration=str(path), island=sw.island)
    sched = ctx.a2a_chunk_schedule(sw.shape, 1, 2)
    assert sched.source == "measured"
    assert sched.n_chunks == 2
    # bulk measured fastest -> 1 chunk, still a measurement
    table2 = _synthetic(fp, [r("bulk", 1, 50.0), r("chunked", 2, 100.0),
                             r("chunked", 4, 300.0)])
    path2 = Path(table2.save(tmp_path / "a2a2.json"))
    autotune.clear_caches()
    ctx2 = dataclasses.replace(ctx, calibration=str(path2))
    sched2 = ctx2.a2a_chunk_schedule(sw.shape, 1, 2)
    assert sched2.source == "measured" and sched2.n_chunks == 1
    # no table -> analytic
    ctx3 = CommContext(axis_name="x", mesh=mesh4, policy="analytic")
    assert ctx3.a2a_chunk_schedule(sw.shape, 1, 2).source == "analytic"


def test_ulysses_auto_chunks_consume_a2a_rows(mesh22, tmp_path):
    """RunConfig.ulysses_chunks=0 (auto): the sp attention island resolves
    its a2a chunk count from the island-keyed measured rows, and the chunked
    island still matches the dense reference numerically."""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.configs.base import RunConfig
    from repro.models import layers as L
    from repro.models.sharding import ShardingRules

    cfg = get_config("tinyllama-1.1b").reduced()
    b, s = 4, 16
    # island payload on this mesh: (b_loc=2, hq=4, s_loc=8, hd=16)
    shape = (2, 4, 8, 16)
    m, n, k = CommContext.a2a_coords(shape, 1, 2)
    key = autotune.island_key("attn_ulysses", "all_to_all", 2)
    fp = autotune.live_fingerprint("tpu_v5e", mesh22)

    def r(be, c, us):
        return {"op": "all_to_all", "backend": be, "axis_size": 2,
                "m": m, "n": n, "k": k, "dtype_bytes": 2, "n_chunks": c,
                "island": key, "us": us}

    table = _synthetic(fp, [r("bulk", 1, 500.0), r("chunked", 2, 100.0),
                            r("chunked", 4, 400.0)])
    path = table.save(tmp_path / "ul.json")
    autotune.clear_caches()
    run = RunConfig(dp_axes=("data",), fsdp=False, sp_attention="ulysses",
                    ulysses_chunks=0, comm_policy="measured",
                    calibration_path=str(path))
    rules = ShardingRules(mesh22, run)
    island = L.sp_attention_island(cfg, run, rules, b, s, causal=True)
    plan = island.plan()
    assert plan.backend == "chunked" and plan.n_chunks == 2
    assert plan.source == "measured"

    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jax.random.normal(jax.random.PRNGKey(0), (b, hq, s, hd))
    kk = jax.random.normal(jax.random.PRNGKey(1), (b, hkv, s, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, s, hd))

    def reference(q, k, v):
        return L._full_attention(q, k, v, causal=True, window=None)

    isl = L.sp_attention_island(cfg, run, rules, b, s, causal=True,
                                reference=reference)
    got = isl(q=q, k=kk, v=v)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(reference(q, kk, v)),
                               rtol=2e-4, atol=2e-4)


def test_moe_auto_chunks_resolve(mesh8):
    """RunConfig.moe_chunks=0 (auto) resolves a concrete dispatch-plan
    chunk count (analytic without a table) and the plan stays coherent."""
    from repro.configs import get_config
    from repro.configs.base import RunConfig
    from repro.models import layers as L
    from repro.models.sharding import ShardingRules

    cfg = get_config("moonshot-v1-16b-a3b").reduced()
    run = RunConfig(dp_axes=("data",), fsdp=False, moe_chunks=0)
    rules = ShardingRules(mesh8, run)
    isl = L.moe_island(cfg, run, rules, 4, 8)
    assert isl.comm.n_chunks >= 1
    assert isl.plan().n_chunks >= 1
