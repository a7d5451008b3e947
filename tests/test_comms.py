"""CommContext: policy dispatch, explicit overrides, backend equivalence,
and the central collective-id allocator (the unified comms API)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat
import repro.core.comms as comms
from repro.core.comms import (CommContext, OP_BACKENDS, collective_id,
                              register_collective, registered_collectives)

N = 4
BIG = 8192          # comfortably past the v5e hiding threshold / sync cutoff


@pytest.fixture(scope="module")
def sm(mesh4):
    return partial(compat.shard_map, mesh=mesh4, check_vma=False)


@pytest.fixture(scope="module")
def ctx(mesh4):
    return CommContext(axis_name="x", mesh=mesh4)


# ---------------------------------------------------------------------------
# Policy dispatch (trace-free)
# ---------------------------------------------------------------------------

def test_tiny_gemm_dispatches_bulk(ctx):
    """Small problem sizes: decomposed schedules lose to sync overhead
    (paper Fig. 7 small-M regime) — the policy must stay bulk."""
    for op in ("all_gather_matmul", "matmul_reduce_scatter",
               "matmul_all_reduce"):
        assert ctx.auto_gemm_backend(op, 16, 12, 32) == "bulk", op


def test_big_ag_gemm_dispatches_bidir_on_even_axis(ctx):
    assert ctx.auto_gemm_backend("all_gather_matmul", BIG, BIG, BIG) \
        == "ring_bidir"


def test_bidir_respects_constraints(ctx, mesh4):
    # context-level opt-out
    no_bidir = CommContext(axis_name="x", mesh=mesh4, allow_bidir=False)
    assert no_bidir.auto_gemm_backend("all_gather_matmul", BIG, BIG, BIG) \
        == "ring"
    # odd local row count cannot split halves across the two rings
    assert ctx.auto_gemm_backend("all_gather_matmul", BIG, BIG, BIG,
                                 bidir_ok=False) == "ring"


def test_big_gemm_rs_dispatches_ring(ctx):
    # RS/AR have no bidirectional variant: enabled policy maps to "ring"
    assert ctx.auto_gemm_backend("matmul_reduce_scatter", BIG, BIG, BIG) \
        == "ring"
    assert ctx.auto_gemm_backend("matmul_all_reduce", BIG, BIG, BIG) == "ring"
    # ...and the cost model must not credit them with the second link-pair
    # (only AG+GEMM implements the bidirectional ring)
    assert ctx.gemm_policy(BIG, BIG, BIG,
                           kind="reduce_scatter").strategy == "ring"
    assert ctx.gemm_policy(BIG, BIG, BIG,
                           kind="all_gather").strategy == "ring_bidir"


def test_context_pin_degrades_for_unsupported_op(sm, mesh4):
    """RunConfig.comm_backend='ring_bidir' must not crash ops without a
    bidirectional variant: the pin falls back to the policy for that op."""
    pinned = CommContext(axis_name="x", mesh=mesh4, backend="ring_bidir")
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8 * N))
    w = jax.random.normal(jax.random.PRNGKey(1), (8 * N, 8))
    got = _run(sm, pinned.matmul_all_reduce,
               (P(None, "x"), P("x", None)), P(), x, w)
    np.testing.assert_allclose(got, np.asarray(x @ w), rtol=1e-4, atol=1e-4)
    # a typo'd pin is still an error, not a silent policy run
    typo = CommContext(axis_name="x", mesh=mesh4, backend="rinng")
    with pytest.raises(ValueError, match="unknown backend"):
        typo.matmul_all_reduce(x, w)


def test_psum_ring_override_shape_contract(sm, mesh4, ctx):
    """Per-call backend='ring' with an indivisible leading dim raises (no
    silent bulk measurement); a context pin degrades to bulk."""
    bad = jnp.ones((2 * N + 1, 4))
    with pytest.raises(ValueError, match="divisible by the axis size"):
        ctx.psum(bad, backend="ring")
    pinned = CommContext(axis_name="x", mesh=mesh4, backend="ring")
    got = _run(sm, pinned.psum, P(), P(None), bad)
    np.testing.assert_allclose(got, N * np.asarray(bad))


def test_a2a_policy_chunks_large_payloads(ctx):
    from repro.core.schedule import choose_a2a_chunks
    small = choose_a2a_chunks(2 ** 10, axis_size=N, downstream_compute_s=0.0)
    big = choose_a2a_chunks(2 ** 28, axis_size=N, downstream_compute_s=1e-3)
    assert small == 1
    assert big > 1


def test_registry_and_availability(ctx):
    for op, backends in OP_BACKENDS.items():
        assert "bulk" in backends, op
        avail = ctx.available_backends(op)
        assert set(avail) <= set(backends)


def test_unknown_backend_raises(ctx):
    with pytest.raises(ValueError, match="no backend"):
        ctx.psum(jnp.ones((4,)), backend="nope")


# ---------------------------------------------------------------------------
# Explicit override beats both the policy and the context backend
# ---------------------------------------------------------------------------

def test_per_call_override_wins(sm, mesh4, monkeypatch):
    calls = []
    orig = comms.pk_all_gather_matmul

    def spy(*args, **kwargs):
        calls.append(kwargs.get("bidirectional"))
        return orig(*args, **kwargs)

    monkeypatch.setattr(comms, "pk_all_gather_matmul", spy)
    bulk_ctx = CommContext(axis_name="x", mesh=mesh4, backend="bulk")
    x = jax.random.normal(jax.random.PRNGKey(0), (4 * N, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 8))

    # context says bulk -> the ring impl must NOT be called
    f = jax.jit(sm(lambda x, w: bulk_ctx.all_gather_matmul(x, w),
                   in_specs=(P("x"), P()), out_specs=P()))
    f(x, w)
    assert calls == []

    # per-call override says ring -> the ring impl MUST be called
    g = jax.jit(sm(lambda x, w: bulk_ctx.all_gather_matmul(x, w,
                                                           backend="ring"),
                   in_specs=(P("x"), P()), out_specs=P()))
    g(x, w)
    assert calls == [False]


def test_shape_guard_pinned_vs_explicit(sm, mesh4):
    """Decode-shaped GEMMs (m not divisible by the axis): a context-pinned
    ring backend degrades to bulk like the policy does; a per-call override
    raises with the constraint named."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8 * N))   # m=3, axis=4
    w = jax.random.normal(jax.random.PRNGKey(1), (8 * N, 8))
    pinned = CommContext(axis_name="x", mesh=mesh4, backend="ring")
    got = _run(sm, pinned.matmul_all_reduce,
               (P(None, "x"), P("x", None)), P(), x, w)
    np.testing.assert_allclose(got, np.asarray(x @ w), rtol=1e-4, atol=1e-4)

    ctx = CommContext(axis_name="x", mesh=mesh4)
    with pytest.raises(ValueError, match="divisible by the axis size"):
        ctx.matmul_all_reduce(x, w, backend="ring")
    # a single local row cannot split across the two ring directions
    with pytest.raises(ValueError, match="at least 2 local rows"):
        ctx.all_gather_matmul(jax.random.normal(jax.random.PRNGKey(2),
                                                (1, 8)),
                              jnp.ones((8, 8)), backend="ring_bidir")


def test_bidir_odd_m_loc_is_legal(sm, ctx):
    """An odd local row count used to be rejected by the full-shard parity
    guard ("even local row count"); the chunk-pipelined ring validates the
    chunked sub-shape instead and splits the shard unevenly (ceil right,
    floor left), so the config is legal — and exact."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3 * N, 8))   # m_loc = 3
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    got = _run(sm, partial(ctx.all_gather_matmul, backend="ring_bidir"),
               (P("x"), P()), P(), x, w)
    np.testing.assert_allclose(got, np.asarray(x @ w), rtol=1e-4, atol=1e-4)
    # ...and the policy may now consider bidir for odd local rows
    assert ctx.auto_gemm_backend("all_gather_matmul", BIG + 4, BIG, BIG) \
        in ("ring", "ring_bidir")


# ---------------------------------------------------------------------------
# Numerical equivalence: every available backend of every op == bulk,
# on the 4-device CPU mesh.
# ---------------------------------------------------------------------------

def _run(sm, fn, in_specs, out_specs, *args):
    return np.asarray(jax.jit(sm(fn, in_specs=in_specs,
                                 out_specs=out_specs))(*args))


def test_gemm_ops_backend_equivalence(sm, ctx):
    x_ag = jax.random.normal(jax.random.PRNGKey(0), (8 * N, 16))
    w_ag = jax.random.normal(jax.random.PRNGKey(1), (16, 12))
    x_rs = jax.random.normal(jax.random.PRNGKey(2), (16, 8 * N))
    w_rs = jax.random.normal(jax.random.PRNGKey(3), (8 * N, 12))

    cases = {
        "all_gather_matmul": (ctx.all_gather_matmul, (x_ag, w_ag),
                              (P("x"), P()), P(), x_ag @ w_ag),
        "matmul_reduce_scatter": (ctx.matmul_reduce_scatter, (x_rs, w_rs),
                                  (P(None, "x"), P("x", None)),
                                  P("x", None), x_rs @ w_rs),
        "matmul_all_reduce": (ctx.matmul_all_reduce, (x_rs, w_rs),
                              (P(None, "x"), P("x", None)), P(),
                              x_rs @ w_rs),
    }
    for op, (meth, args, in_specs, out_specs, want) in cases.items():
        for be in ctx.available_backends(op) + (None,):
            got = _run(sm, partial(meth, backend=be), in_specs, out_specs,
                       *args)
            np.testing.assert_allclose(got, np.asarray(want), rtol=1e-4,
                                       atol=1e-4, err_msg=f"{op}/{be}")


@pytest.mark.parametrize("nc", [1, 2, 4, 3])    # 3: non-divisible fallback
def test_gemm_ops_chunked_equivalence(sm, ctx, nc):
    """The chunk-pipelined ring schedules are bit-compatible with the dense
    reference for every chunk count and both chunk dims — including counts
    that do not divide the chunked sub-shape (fitted to a divisor) and the
    bidirectional multi-chunk-per-step variant."""
    x_ag = jax.random.normal(jax.random.PRNGKey(0), (8 * N, 16))
    w_ag = jax.random.normal(jax.random.PRNGKey(1), (16, 12))
    x_rs = jax.random.normal(jax.random.PRNGKey(2), (16, 8 * N))
    w_rs = jax.random.normal(jax.random.PRNGKey(3), (8 * N, 12))

    cases = {
        ("all_gather_matmul", "ring"): (
            ctx.all_gather_matmul, (x_ag, w_ag), (P("x"), P()), P(),
            x_ag @ w_ag),
        ("all_gather_matmul", "ring_bidir"): (
            ctx.all_gather_matmul, (x_ag, w_ag), (P("x"), P()), P(),
            x_ag @ w_ag),
        ("matmul_reduce_scatter", "ring"): (
            ctx.matmul_reduce_scatter, (x_rs, w_rs),
            (P(None, "x"), P("x", None)), P("x", None), x_rs @ w_rs),
        ("matmul_all_reduce", "ring"): (
            ctx.matmul_all_reduce, (x_rs, w_rs),
            (P(None, "x"), P("x", None)), P(), x_rs @ w_rs),
    }
    for (op, be), (meth, args, in_specs, out_specs, want) in cases.items():
        for dim in ("m", "n"):
            got = _run(sm, partial(meth, backend=be, n_chunks=nc,
                                   chunk_dim=dim), in_specs, out_specs,
                       *args)
            np.testing.assert_allclose(got, np.asarray(want), rtol=1e-4,
                                       atol=1e-4,
                                       err_msg=f"{op}/{be}/c={nc}/{dim}")


def test_chunk_schedule_resolution(ctx, mesh4):
    """gemm_chunk_schedule precedence: explicit kwarg > context chunks= >
    analytic scheduler; bulk takes no sub-chunks."""
    s = ctx.gemm_chunk_schedule("matmul_all_reduce", BIG, BIG, BIG,
                                backend="ring", n_chunks=4)
    assert s.n_chunks == 4 and s.source == "explicit"
    pinned = CommContext(axis_name="x", mesh=mesh4, chunks=2)
    s = pinned.gemm_chunk_schedule("matmul_all_reduce", BIG, BIG, BIG,
                                   backend="ring")
    assert s.n_chunks == 2 and s.source == "explicit"
    s = ctx.gemm_chunk_schedule("matmul_all_reduce", BIG, BIG, BIG,
                                backend="bulk", n_chunks=8)
    assert s.n_chunks == 1
    s = ctx.gemm_chunk_schedule("matmul_all_reduce", BIG, BIG, BIG,
                                backend="ring")
    assert s.source == "analytic" and s.n_chunks >= 1
    assert s.chunk_dim == "m"


def test_fit_chunks_fallback():
    from repro.core.schedule import fit_chunks
    assert fit_chunks(8, 3) == 2        # largest divisor <= request
    assert fit_chunks(7, 4) == 1
    assert fit_chunks(8, 16) == 8       # clamped to the extent
    assert fit_chunks(0, 4) == 1


def test_a2a_chunk_policy_validates_sub_shape():
    """choose_a2a_chunks with shape= fits the count to what the bystander
    dims can actually split — a payload whose dims cannot divide the naive
    count no longer silently bulks the whole transfer."""
    from repro.core.schedule import a2a_chunk_axis, choose_a2a_chunks
    big = 2 ** 28
    # dim 3 (size 6) cannot split by 8 but can by 3
    assert a2a_chunk_axis((1, 4, 8, 6), 1, 2, 8) == (3, 6)
    c = choose_a2a_chunks(big, axis_size=N, downstream_compute_s=1e-3,
                          shape=(1, 4, 8, 6), split_axis=1, concat_axis=2)
    assert c > 1 and 6 % c == 0
    # no bystander dim at all -> bulk
    assert choose_a2a_chunks(big, axis_size=N, downstream_compute_s=1e-3,
                             shape=(4, 8), split_axis=0, concat_axis=1) == 1


def test_all_to_all_backend_equivalence(sm, ctx):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, N * 4, 16))
    want = None
    for be, nc in (("bulk", None), ("chunked", 2), ("chunked", None),
                   (None, 4), (None, None)):
        got = _run(sm, lambda t, be=be, nc=nc: ctx.all_to_all(
            t, split_axis=2, concat_axis=1, backend=be, n_chunks=nc),
            P(None, "x"), P(None, None, "x"), x)
        want = got if want is None else want
        np.testing.assert_allclose(got, want, err_msg=f"a2a/{be}/{nc}")


def test_psum_and_shift_backend_equivalence(sm, ctx):
    y = jax.random.normal(jax.random.PRNGKey(0), (4 * N, 8))
    want = None
    for be in ctx.available_backends("psum") + (None,):
        got = _run(sm, lambda t, be=be: ctx.psum(t, backend=be)[None],
                   P("x"), P("x"), y)
        want = got if want is None else want
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                   err_msg=f"psum/{be}")

    got = _run(sm, lambda t: ctx.ring_shift(t), P("x"), P("x"), y)
    np.testing.assert_allclose(got, np.asarray(jnp.roll(y, 4 * N // N,
                                                        axis=0)))

    got = _run(sm, lambda t: ctx.all_gather(t), P("x"), P(), y)
    np.testing.assert_allclose(got, np.asarray(y))

    got = _run(sm, lambda t: ctx.reduce_scatter(t), P(), P("x"), y)
    np.testing.assert_allclose(got, np.asarray(N * y), rtol=1e-5)


# ---------------------------------------------------------------------------
# Collective-id allocator
# ---------------------------------------------------------------------------

def test_collective_ids_unique_and_stable():
    ids = registered_collectives()
    assert len(set(ids.values())) == len(ids)          # no collisions
    assert collective_id("ring_all_gather") == ids["ring_all_gather"]
    fresh = register_collective("test_comms_fresh_kernel")
    assert fresh not in ids.values()                   # new name, new id
    assert collective_id("test_comms_fresh_kernel") == fresh  # stable
    assert register_collective("test_comms_fresh_kernel") == fresh


def test_collective_id_rejects_unregistered_names():
    # trace-time allocation would be trace-order-dependent across SPMD
    # processes — lookups of unknown kernels must fail loudly instead
    with pytest.raises(KeyError, match="not registered"):
        collective_id("never_registered_kernel")


def test_canonical_kernels_preregistered():
    ids = registered_collectives()
    for name in ("ring_all_gather", "ring_reduce_scatter", "p2p_ring_shift",
                 "ag_matmul_fused", "matmul_rs_fused",
                 "lcsc_ring_all_gather"):
        assert name in ids


# ---------------------------------------------------------------------------
# Removed module (was a deprecation shim for one release)
# ---------------------------------------------------------------------------

def test_collectives_module_removed_with_migration_message():
    with pytest.raises(ImportError, match="repro.core.comms"):
        from repro.core.collectives import pk_all_to_all  # noqa: F401
