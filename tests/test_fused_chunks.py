"""Chunk-pipelined fused kernels (the ChunkSchedule -> pk_comm /
collective_matmul seam):

* dispatch precedence for the fused backend — explicit ``n_chunks=`` >
  ``RunConfig.comm_chunks`` > measured fused×chunks rows > the analytic
  ``fused_pipeline_cost`` argmin;
* the fused cost term itself (one launch, local-sync chunk handoffs, a
  finer argmin than the ring's);
* ``calibrate --per-island`` case generation (fused×{1,2,4,8} on TPU only,
  never at the int8 wire width) and the CLI's b1-replica helper;
* ``Island.plan()`` / ``plan_overrides`` / ``serving_plan_record`` carrying
  frozen fused chunk schedules exactly like ring ones;
* on interpret-capable JAX builds: chunked fused kernels bit-identical to
  their 1-chunk selves and allclose to the jnp oracles, for divisible and
  non-divisible (``fit_chunks`` fallback) requested counts.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import autotune, costmodel as cm
from repro.core.comms import CommContext
from repro.core.schedule import choose_gemm_chunks, fit_chunks

N = 4


def _synthetic(fingerprint, rows, **corr):
    corrections = {"ici_bandwidth": 1e8, "remote_sync_s": 1e-4,
                   "gemm_efficiency": 1e-4, "kernel_launch_s": 1e-5}
    corrections.update(corr)
    return autotune.CalibrationTable(fingerprint=fingerprint,
                                     corrections=corrections,
                                     measurements=rows)


def _fused_rows(op, us_by_chunks, m, n, k, island=None, axis_size=N):
    rows = [{"op": op, "backend": "fused", "axis_size": axis_size,
             "m": m, "n": n, "k": k, "dtype_bytes": 2, "n_chunks": c,
             "us": us} for c, us in us_by_chunks.items()]
    if island is not None:
        for r in rows:
            r["island"] = island
    return rows


@pytest.fixture(autouse=True)
def _fresh_caches():
    autotune.clear_caches()
    yield
    autotune.clear_caches()


# ---------------------------------------------------------------------------
# The fused cost term
# ---------------------------------------------------------------------------

def test_fused_cost_single_launch_and_local_sync():
    """The fused pipeline pays ONE kernel launch regardless of chunk count
    (ring pipelines pay per step) and its sync grows with the cheap
    local-sync constant, so finer chunking stays affordable."""
    hw = cm.TPU_V5E
    one = cm.fused_pipeline_cost(4096, 1024, 512, axis_size=N, sub_chunks=1)
    fine = cm.fused_pipeline_cost(4096, 1024, 512, axis_size=N, sub_chunks=8)
    assert one.t_launch == fine.t_launch == hw.kernel_launch_s
    ring = cm.chunk_pipeline_cost(4096, 1024, 512, axis_size=N, sub_chunks=8)
    # VMEM-resident operands: chunking never re-reads HBM (the jax-level
    # ring's t_mem grows with the count) and chunk handoffs cost local
    # syncs, not remote ones — the two terms that move the fused argmin
    assert fine.t_mem == one.t_mem < ring.t_mem
    assert fine.t_sync < ring.t_sync
    # chunk handoffs are local semaphore waits, not ring rendezvous: the
    # sync delta from 1 -> 8 sub-chunks is hops * 7 local syncs exactly
    hops = N - 1
    assert fine.t_sync - one.t_sync == pytest.approx(
        hops * 7 * hw.local_sync_s)


def test_fused_analytic_argmin_at_least_as_fine_as_ring():
    """With launch overhead amortized and syncs local, the fused argmin sits
    at the same or a finer chunk count than the ring pipeline's."""
    for kind in ("all_gather", "reduce_scatter", "all_reduce"):
        ring = choose_gemm_chunks(4096, 1024, 512, axis_size=N, kind=kind)
        fused = choose_gemm_chunks(4096, 1024, 512, axis_size=N, kind=kind,
                                   fused=True)
        assert fused.n_chunks >= ring.n_chunks, kind
        assert "fused_pipeline_cost" in fused.reason


def test_fit_chunks_degrades_never_rejects():
    assert fit_chunks(16, 4) == 4
    assert fit_chunks(16, 3) == 2       # largest divisor <= request
    assert fit_chunks(7, 4) == 1
    assert fit_chunks(0, 4) == 1


@pytest.mark.parametrize("extent,req,align,want", [
    (64, 4, 16, 4),     # 16-row chunks: on the bf16 tiling
    (64, 8, 16, 4),     # 8-row chunks would be off it
    (8, 4, 2, 4),
    (8, 4, 16, 1),      # no aligned split: one whole-extent chunk
    (48, 8, 16, 3),     # largest count that divides AND stays aligned
])
def test_fit_chunks_keeps_rows_on_the_tiling(extent, req, align, want):
    from repro.core.schedule import row_tile
    assert row_tile(2) == 16 and row_tile(4) == 8 and row_tile(1) == 32
    assert fit_chunks(extent, req, align=align) == want


# ---------------------------------------------------------------------------
# Dispatch precedence (explicit > context > measured > analytic)
# ---------------------------------------------------------------------------

def test_fused_chunk_precedence(mesh4):
    live = autotune.live_fingerprint("tpu_v5e", mesh4)
    t = _synthetic(live, _fused_rows("matmul_all_reduce",
                                     {1: 100.0, 2: 60.0, 4: 20.0, 8: 50.0},
                                     256, 64, 16))
    mk = dict(axis_name="x", mesh=mesh4, policy="measured", calibration=t)
    ctx = CommContext(**mk)
    # measured tier: argmin over the fused×chunks rows
    sched = ctx.gemm_chunk_schedule("matmul_all_reduce", 256, 64, 16,
                                    backend="fused")
    assert (sched.n_chunks, sched.source) == (4, "measured")
    assert sched.chunk_dim == "m"       # fused payload chunks are row cuts
    # explicit per-call count beats the table
    sched = ctx.gemm_chunk_schedule("matmul_all_reduce", 256, 64, 16,
                                    backend="fused", n_chunks=3)
    assert (sched.n_chunks, sched.source) == (3, "explicit")
    # context-wide default (RunConfig.comm_chunks) beats the table too
    sched = CommContext(chunks=2, **mk).gemm_chunk_schedule(
        "matmul_all_reduce", 256, 64, 16, backend="fused")
    assert (sched.n_chunks, sched.source) == (2, "explicit")
    # no table -> the analytic fused argmin
    sched = CommContext(axis_name="x", mesh=mesh4).gemm_chunk_schedule(
        "matmul_all_reduce", 4096, 1024, 512, backend="fused")
    assert sched.source == "analytic" and sched.n_chunks >= 1
    # bulk takes no sub-chunks whatever the table says
    sched = ctx.gemm_chunk_schedule("matmul_all_reduce", 256, 64, 16,
                                    backend="bulk")
    assert sched.n_chunks == 1


def test_fused_measured_rows_island_first(mesh4):
    """An island's fused×chunks rows beat the global grid's at the same
    coordinates — same tiering as backend dispatch."""
    live = autotune.live_fingerprint("tpu_v5e", mesh4)
    key = autotune.island_key("mlp", "matmul_all_reduce", 2)
    rows = (_fused_rows("matmul_all_reduce", {1: 100.0, 2: 10.0},
                        256, 64, 16)
            + _fused_rows("matmul_all_reduce", {1: 100.0, 8: 10.0},
                          256, 64, 16, island=key))
    t = _synthetic(live, rows)
    mk = dict(axis_name="x", mesh=mesh4, policy="measured", calibration=t)
    glob = CommContext(**mk).gemm_chunk_schedule(
        "matmul_all_reduce", 256, 64, 16, backend="fused")
    isl = CommContext(island=key, **mk).gemm_chunk_schedule(
        "matmul_all_reduce", 256, 64, 16, backend="fused")
    assert (glob.n_chunks, isl.n_chunks) == (2, 8)
    assert glob.source == isl.source == "measured"


# ---------------------------------------------------------------------------
# Calibration sweep cases (fused×chunks, TPU-gated, full-precision only)
# ---------------------------------------------------------------------------

def _sweep(dtype_bytes=2, op="matmul_all_reduce"):
    return autotune.IslandSweep(
        island=autotune.island_key("mlp", op, dtype_bytes), op=op,
        m=8 * N, n=16, k=8, dtype_bytes=dtype_bytes)


def test_island_sweep_cases_off_tpu_excludes_fused():
    cases = autotune.island_sweep_cases(_sweep(), N,
                                        ("bulk", "ring", "fused"))
    assert ("bulk", 1) in cases
    assert {c for be, c in cases if be == "ring"} \
        == set(autotune.ISLAND_CHUNK_SWEEP)
    assert not any(be == "fused" for be, _ in cases)


def test_island_sweep_cases_on_tpu_sweeps_fused_chunks(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cases = autotune.island_sweep_cases(_sweep(), N,
                                        ("bulk", "ring", "fused"))
    assert {c for be, c in cases if be == "fused"} \
        == set(autotune.ISLAND_FUSED_CHUNK_SWEEP)
    # ... but never at the int8 wire width (fused ships full precision)
    b1 = autotune.island_sweep_cases(_sweep(dtype_bytes=1), N,
                                     ("bulk", "ring", "fused"))
    assert not any(be == "fused" for be, _ in b1)


def test_cli_int8_island_sweeps_replicates_gemm_islands():
    """The CLI's --per-island dtype axis: every full-precision GEMM island
    gets a ``|b1`` twin swept at the int8 wire width; non-GEMM islands and
    already-b1 islands don't."""
    from repro.autotune import int8_island_sweeps
    gemm = _sweep()
    psum = autotune.IslandSweep(island=autotune.island_key("q", "psum", 2),
                                op="psum", m=N, n=16, k=1)
    b1 = _sweep(dtype_bytes=1)
    extra = int8_island_sweeps([gemm, psum, b1])
    assert len(extra) == 1
    tw = extra[0]
    assert tw.dtype_bytes == 1
    assert tw.island == autotune.island_key("mlp", "matmul_all_reduce", 1)
    assert (tw.op, tw.m, tw.n, tw.k) == (gemm.op, gemm.m, gemm.n, gemm.k)


# ---------------------------------------------------------------------------
# plan() / plan_overrides / serving_plan_record carry fused schedules
# ---------------------------------------------------------------------------

def test_plan_reports_measured_fused_chunks(mesh4, tmp_path):
    """A pinned-fused island on a calibrated mesh reports src=measured with
    the chunk count straight from the fused×chunks table rows, and
    plan_overrides freezes sub-chunks-per-step for the bucket contexts."""
    from repro.core.template import Comm, Island, plan_overrides
    live = autotune.live_fingerprint("tpu_v5e", mesh4)
    key = autotune.island_key("mlp", "matmul_all_reduce", 2)
    rows = ([{"op": "matmul_all_reduce", "backend": "bulk", "axis_size": N,
              "m": 64, "n": 64, "k": 32, "dtype_bytes": 2, "n_chunks": 1,
              "island": key, "us": 100.0}]
            + _fused_rows("matmul_all_reduce", {2: 10.0, 4: 4.0},
                          64, 64, 32, island=key))
    path = _synthetic(live, rows).save(tmp_path / "cal.json")
    isl = Island("mlp", mesh=mesh4, axis="x",
                 comm=Comm(op="matmul_all_reduce", m=64, n=64, k=32,
                           backend="fused"),
                 ctx_kwargs={"policy": "measured", "calibration": str(path)})
    p = isl.plan()
    assert p.backend == "fused"
    assert p.source == "measured"
    assert p.n_chunks == N * 4          # ring steps × measured sub-chunks
    assert p.wire is None               # fused ships full precision
    assert 0.0 <= p.hidden_fraction <= 1.0
    assert ("mlp", "fused", 4) in plan_overrides([p])


def test_plan_overrides_normalizes_fused_like_ring():
    from repro.core.template import IslandPlan, plan_overrides
    mk = dict(axis="x", axis_size=N, fallback=False, reason="",
              op="matmul_reduce_scatter")
    ov = plan_overrides([
        IslandPlan(island="a", backend="fused", n_chunks=8, **mk),
        IslandPlan(island="b", backend="ring", n_chunks=8, **mk),
        IslandPlan(island="c", backend="bulk", n_chunks=1, **mk)])
    assert ("a", "fused", 2) in ov and ("b", "ring", 2) in ov
    assert ("c", "bulk", None) in ov


def test_serving_plan_record_carries_fused_schedules(mesh22):
    """A comm_backend=fused A/B run: every GEMM island in the per-bucket
    record reports the fused backend with its resolved chunk schedule, and
    the frozen overrides carry the sub-chunk counts."""
    from repro.configs import get_config
    from repro.configs.base import RunConfig, ServeConfig
    from repro.models.sharding import ShardingRules
    from repro.runtime.serving import serving_plan_record
    cfg = get_config("tinyllama-1.1b").reduced()
    run = RunConfig(dp_axes=("data",), fsdp=False, comm_backend="fused")
    rules = ShardingRules(mesh22, run)
    serve = ServeConfig(max_batch=4, prefill_batch=2, bucket_edges=(16,),
                        max_new_tokens=4)
    rec = serving_plan_record(cfg, run, rules, serve)
    pre = {p["island"]: p for p in rec["buckets"]["prefill@16"]["islands"]}
    mlp = pre["mlp"]
    assert mlp["backend"] == "fused"
    assert mlp["wire"] is None
    n_dev = mlp["axis_size"]
    assert mlp["n_chunks"] % n_dev == 0 and mlp["n_chunks"] >= n_dev
    ov = {tuple(o[:2]): o[2]
          for o in rec["buckets"]["prefill@16"]["overrides"]}
    assert ov[("mlp", "fused")] == mlp["n_chunks"] // n_dev


# ---------------------------------------------------------------------------
# Interpret-mode equivalence: chunked == 1-chunk (bit-identical) == oracle
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sm(mesh4):
    return partial(compat.shard_map, mesh=mesh4, check_vma=False)


@pytest.mark.parametrize("n_chunks", [1, 2, 4, 3])
def test_ag_matmul_chunked(sm, n_chunks):
    from repro.kernels import ref
    from repro.kernels.collective_matmul import ag_matmul_fused
    # chunk rows stay multiples of the f32 row tile (8) up to 4 chunks
    m_loc, k, n_out = 32, 32, 24
    x = jax.random.normal(jax.random.PRNGKey(0), (N * m_loc, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n_out), jnp.float32)

    def run(c):
        f = jax.jit(sm(
            lambda x, w: ag_matmul_fused(x, w, "x", n_chunks=c)
            .reshape(N * m_loc, n_out)[None],
            in_specs=(P("x"), P()), out_specs=P("x")))
        return np.asarray(f(x, w))

    got = run(n_chunks)
    want = np.asarray(ref.ag_matmul_ref(x, w))
    for d in range(N):
        np.testing.assert_allclose(got[d], want, rtol=1e-4, atol=1e-4)
    # same dots over the same sub-slices in the same order: bit-identical
    assert np.array_equal(got, run(1))


@pytest.mark.parametrize("n_chunks", [1, 2, 4, 3])
def test_matmul_rs_chunked(sm, n_chunks):
    from repro.kernels import ref
    from repro.kernels.collective_matmul import matmul_rs_fused
    m, k_loc, n_out = N * 32, 8, 24     # row blocks of 32: 4 chunks of 8
    x = jax.random.normal(jax.random.PRNGKey(0), (m, N * k_loc), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (N * k_loc, n_out),
                          jnp.float32)

    def run(c):
        f = jax.jit(sm(lambda x, w: matmul_rs_fused(x, w, "x", n_chunks=c),
                       in_specs=(P(None, "x"), P("x", None)),
                       out_specs=P("x", None)))
        return np.asarray(f(x, w))

    got = run(n_chunks)
    np.testing.assert_allclose(got, np.asarray(ref.matmul_rs_ref(x, w)),
                               rtol=1e-4, atol=1e-4)
    assert np.array_equal(got, run(1))


@pytest.mark.parametrize("n_chunks", [1, 2, 4, 3])
def test_matmul_ar_chunked(sm, n_chunks):
    from repro.kernels import ref
    from repro.kernels.collective_matmul import matmul_ar_fused
    m, k_loc, n_out = N * 32, 8, 24     # row blocks of 32: 4 chunks of 8
    x = jax.random.normal(jax.random.PRNGKey(0), (m, N * k_loc), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (N * k_loc, n_out),
                          jnp.float32)

    def run(c):
        f = jax.jit(sm(
            lambda x, w: matmul_ar_fused(x, w, "x", n_chunks=c)
            .reshape(m, n_out)[None],
            in_specs=(P(None, "x"), P("x", None)), out_specs=P("x")))
        return np.asarray(f(x, w))

    got = run(n_chunks)
    want = np.asarray(ref.matmul_ar_ref(x, w))
    for d in range(N):
        np.testing.assert_allclose(got[d], want, rtol=1e-4, atol=1e-4)
    assert np.array_equal(got, run(1))


@pytest.mark.parametrize("n_chunks", [2, 4, 3])
def test_ring_collectives_chunked(sm, n_chunks):
    from repro.kernels import ref
    from repro.kernels.pk_comm import ring_all_gather, ring_reduce_scatter
    x = jax.random.normal(jax.random.PRNGKey(0), (N, 32, 16), jnp.float32)
    f = jax.jit(sm(
        lambda x: ring_all_gather(x[0], "x", n_chunks=n_chunks)[None],
        in_specs=P("x"), out_specs=P("x")))
    got = np.asarray(f(x))
    for d in range(N):
        np.testing.assert_allclose(got[d], np.asarray(x))
    xg = jax.random.normal(jax.random.PRNGKey(1), (N, N, 32, 16), jnp.float32)
    g = jax.jit(sm(
        lambda x: ring_reduce_scatter(x[0], "x", n_chunks=n_chunks)[None],
        in_specs=P("x"), out_specs=P("x")))
    np.testing.assert_allclose(np.asarray(g(xg)),
                               np.asarray(ref.reduce_scatter_ref(xg)),
                               rtol=1e-5, atol=1e-5)
