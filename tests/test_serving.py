"""Continuous-batching serving engine (repro.runtime.serving):

* batched-vs-sequential token equivalence (bit-identical) on the 4- and
  8-device emulated meshes, mixed buckets and mixed admission times;
* deterministic bucket admission/eviction under a scripted request trace;
* per-bucket Island plans actually consumed: the decode bucket's jitted
  step runs a different (plan-driven) backend/chunk schedule than the
  prefill bucket's on a calibrated mesh;
* the plan-override plumbing itself (core.template.plan_overrides /
  RunConfig.island_overrides -> CommContext pins).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.configs import get_config
from repro.configs.base import RunConfig, ServeConfig
from repro.core.template import (Comm, Island, island_override,
                                 plan_overrides)
from repro.models.sharding import ShardingRules
from repro.runtime.serving import resolve_serving_plans, serving_plan_record


def _engine(mesh_shape, serve, arch="tinyllama-1.1b", **kw):
    from repro.launch.serve import build_engine
    return build_engine(arch, reduced=True, mesh_shape=mesh_shape,
                        serve=serve, **kw)


def _trace(serve, vocab, n, seed=0):
    from repro.launch.serve import synthetic_trace
    return synthetic_trace(n, serve, vocab, seed=seed)


SERVE = ServeConfig(max_batch=4, prefill_batch=2, bucket_edges=(8, 16),
                    max_new_tokens=4)


# ---------------------------------------------------------------------------
# Token equivalence: continuous batching == one-request-at-a-time
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_shape", [(2, 2), (2, 4)])
def test_continuous_matches_sequential(mesh_shape):
    eng = _engine(mesh_shape, SERVE)
    trace = _trace(SERVE, eng.cfg.vocab_size, 5)
    done = eng.run(trace)
    assert len(done) == len(trace)
    for c in done:
        assert len(c.tokens) == SERVE.max_new_tokens
        # sequential: a fresh engine, one request, no batching effects
        solo = _engine(mesh_shape, SERVE)
        ref = solo.run([trace[c.rid]])[0]
        assert c.tokens == ref.tokens, (c.rid, c.tokens, ref.tokens)


def test_continuous_matches_static_batch():
    eng = _engine((2, 4), SERVE)
    trace = _trace(SERVE, eng.cfg.vocab_size, 4)
    done = {c.rid: c.tokens for c in eng.run(trace)}
    static = eng.generate_static(trace, SERVE.max_new_tokens)
    for rid, toks in enumerate(static):
        assert done[rid] == toks


def test_engine_no_mesh_dense_fallback():
    """rules=None routes every island to its dense reference; the engine
    must still batch correctly."""
    eng = _engine(None, SERVE)
    trace = _trace(SERVE, eng.cfg.vocab_size, 3)
    done = eng.run(trace)
    solo = _engine(None, SERVE)
    ref = solo.run([trace[1]])
    assert done[1].tokens == ref[0].tokens


def test_prefill_logits_are_the_served_first_tokens():
    """prefill_logits runs the engine's own prefill program, changes no
    engine state, and gives the same logits for another copy of the
    weights; its argmax is the first token the engine then serves."""
    eng = _engine(None, SERVE)
    rng = np.random.RandomState(3)
    trace = [tuple(int(t) for t in rng.randint(0, eng.cfg.vocab_size, n))
             for n in (5, 7)]                    # one bucket (8), one group
    logits = eng.prefill_logits(trace)
    assert logits.shape == (2, eng.cfg.vocab_size)
    assert logits.dtype == np.float32 and np.isfinite(logits).all()
    assert not eng.events and not eng.completions
    copy = jax.tree.map(jnp.copy, eng.params)
    np.testing.assert_array_equal(eng.prefill_logits(trace, params=copy),
                                  logits)
    done = {c.rid: c.tokens for c in eng.run(trace)}
    assert [done[0][0], done[1][0]] == logits.argmax(-1).tolist()
    with pytest.raises(ValueError, match="prompts per prefill group"):
        eng.prefill_logits(trace * 2)


# ---------------------------------------------------------------------------
# Admission / eviction determinism under a scripted trace
# ---------------------------------------------------------------------------

def _scripted_trace(vocab):
    # lengths chosen to exercise both buckets and slot reuse
    lens = [5, 12, 3, 8, 16, 2, 7]
    rng = np.random.RandomState(7)
    return [tuple(int(t) for t in rng.randint(0, vocab, size=n))
            for n in lens]


def test_admission_eviction_deterministic():
    runs = []
    for _ in range(2):
        eng = _engine((2, 2), SERVE)
        eng.run(_scripted_trace(eng.cfg.vocab_size))
        runs.append((eng.events, eng.step_kinds,
                     {r: c.tokens for r, c in eng.completions.items()}))
    assert runs[0] == runs[1]
    events = runs[0][0]
    admits = [e for e in events if e[0] == "admit"]
    retires = [e for e in events if e[0] == "retire"]
    assert len(admits) == len(retires) == 7
    # every admit names the right bucket for its prompt length
    lens = [5, 12, 3, 8, 16, 2, 7]
    for (_, _, rid, _, bucket, mem) in admits:
        assert bucket == SERVE.bucket_for(lens[rid])
        assert mem["resident_slots"] >= 1      # memory metrics ride along
    # fcfs: admission order == arrival order
    assert [a[2] for a in admits] == sorted(a[2] for a in admits)
    # slots are reused only after retirement
    live = set()
    for e in events:
        if e[0] == "admit":
            assert e[3] not in live
            live.add(e[3])
        else:
            live.discard(e[3])


def test_bucket_greedy_fills_groups():
    serve = dataclasses.replace(SERVE, queue_policy="bucket-greedy")
    eng = _engine((2, 2), serve)
    # arrival order alternates buckets; greedy groups same-bucket requests
    rng = np.random.RandomState(3)
    prompts = [tuple(int(t) for t in rng.randint(0, eng.cfg.vocab_size,
                                                 size=n))
               for n in (4, 12, 6, 14)]
    eng.run(prompts)
    admits = [e for e in eng.events if e[0] == "admit"]
    first_group = [a for a in admits if a[1] == 0]       # step 0 prefill
    assert {a[2] for a in first_group} == {0, 2}         # both bucket-8 reqs
    # and the engine still completes everything with correct tokens
    solo = _engine((2, 2), serve)
    ref = solo.run([prompts[1]])
    assert eng.completions[1].tokens == ref[0].tokens


def test_exact_buckets_required_for_ssm():
    with pytest.raises(ValueError, match="exact_buckets"):
        _engine(None, SERVE, arch="falcon-mamba-7b")
    serve = dataclasses.replace(SERVE, exact_buckets=True)
    eng = _engine(None, serve, arch="falcon-mamba-7b")
    rng = np.random.RandomState(0)
    prompts = [tuple(int(t) for t in rng.randint(0, eng.cfg.vocab_size,
                                                 size=n)) for n in (5, 5, 3)]
    done = eng.run(prompts)
    solo = _engine(None, serve, arch="falcon-mamba-7b")
    assert done[2].tokens == solo.run([prompts[2]])[0].tokens


# ---------------------------------------------------------------------------
# Per-bucket plans: resolved, recorded, and consumed
# ---------------------------------------------------------------------------

def test_serving_plan_record_shape(mesh22):
    cfg = get_config("tinyllama-1.1b").reduced()
    run = RunConfig(dp_axes=("data",), fsdp=False)
    rules = ShardingRules(mesh22, run)
    rec = serving_plan_record(cfg, run, rules, SERVE)
    assert set(rec["buckets"]) == {"prefill@8", "prefill@16", "decode"}
    assert rec["cache"]["layout"] == "slab"
    assert rec["cache"]["resident_capacity"] == {"8": 4, "16": 4}
    pre = rec["buckets"]["prefill@16"]
    dec = rec["buckets"]["decode"]
    assert pre["phase"] == "prefill" and pre["seq"] == 16
    assert dec["phase"] == "decode" and dec["seq"] == 1
    names_pre = {p["island"] for p in pre["islands"]}
    names_dec = {p["island"] for p in dec["islands"]}
    assert "decode_attn" in names_dec and "decode_attn" not in names_pre
    assert "mlp" in names_pre and "mlp" in names_dec
    # overrides are json-ready [name, backend, chunks] triples
    for name, be, chunks in pre["overrides"]:
        assert isinstance(name, str)


def test_per_bucket_plans_consumed_and_distinct(tmp_path):
    """The acceptance loop: a calibrated mesh where the prefill bucket's
    MLP measures ring-with-2-sub-chunks fastest while the decode bucket's
    tiny GEMM measures bulk fastest. The engine must (a) record those
    distinct plans per bucket and (b) thread them into each bucket's
    CommContext via island_overrides."""
    from repro.core import autotune

    mesh = compat.make_mesh((1, 4), ("data", "model"))
    cfg = get_config("tinyllama-1.1b").reduced()
    serve = ServeConfig(max_batch=8, prefill_batch=4, bucket_edges=(16,),
                        max_new_tokens=4)
    # mlp Comm coordinates on this mesh: prefill (m=4*16=64, n=64, k=32),
    # decode (m=8, n=64, k=32)
    live = autotune.live_fingerprint("tpu_v5e", mesh)
    key = autotune.island_key("mlp", "matmul_all_reduce", 2)

    def rows(m, us_by, n_chunks=1):
        return [{"op": "matmul_all_reduce", "backend": be, "axis_size": 4,
                 "m": m, "n": 64, "k": 32, "dtype_bytes": 2,
                 "n_chunks": n_chunks, "island": key, "us": us}
                for be, us in us_by.items()]

    table = autotune.CalibrationTable(
        fingerprint=live,
        corrections={"ici_bandwidth": 1e8, "remote_sync_s": 1e-6,
                     "gemm_efficiency": 1e-4, "kernel_launch_s": 1e-6},
        measurements=(rows(64, {"bulk": 100.0, "ring": 10.0})
                      + rows(64, {"ring": 4.0}, n_chunks=2)
                      + rows(8, {"bulk": 5.0, "ring": 400.0})))
    path = table.save(tmp_path / "serving-cal.json")
    autotune.clear_caches()
    try:
        eng = _engine((1, 4), serve,
                      comm_policy="measured",
                      run_overrides={"calibration_path": str(path)})
        pre = {p.island: p for p in eng.bucket_plans["prefill@16"].plans}
        dec = {p.island: p for p in eng.bucket_plans["decode"].plans}
        assert pre["mlp"].backend == "ring"
        assert pre["mlp"].source == "measured"
        assert pre["mlp"].n_chunks == 4 * 2          # ring steps x sub-chunks
        assert dec["mlp"].backend == "bulk"
        assert dec["mlp"].source == "measured"
        # distinct backend AND chunk settings across the two buckets
        assert (pre["mlp"].backend, pre["mlp"].n_chunks) != \
            (dec["mlp"].backend, dec["mlp"].n_chunks)
        # ...and the overrides reach the jitted steps' contexts
        assert ("mlp", "ring", 2) in eng.bucket_plans["prefill@16"].overrides
        assert ("mlp", "bulk", None) in eng.bucket_plans["decode"].overrides
        run_pre = eng._runs["prefill@16"]
        run_dec = eng._runs["decode"]
        assert island_override(run_pre, "mlp") == ("ring", 2, "plan")
        assert island_override(run_dec, "mlp") == ("bulk", None, "plan")
        from repro.models.layers import mlp_island
        rules = eng.rules
        ctx_pre = mlp_island(cfg, run_pre, rules, 4, 16).make_context()
        ctx_dec = mlp_island(cfg, run_dec, rules, 8, 1).make_context()
        assert ctx_pre.backend == "ring" and ctx_pre.chunks == 2
        assert ctx_dec.backend == "bulk"
        # and the engine still generates correctly under the overrides
        trace = _trace(serve, eng.cfg.vocab_size, 3)
        done = eng.run(trace)
        solo = _engine((1, 4), serve, comm_policy="measured",
                       run_overrides={"calibration_path": str(path)})
        assert done[0].tokens == solo.run([trace[0]])[0].tokens
    finally:
        autotune.clear_caches()


def test_seeded_calibration_reaches_serving_plans():
    """With the in-repo cpu_emulated seed (8-dev mesh, auto policy) at the
    seed's calibrated coordinates, the prefill bucket's MLP plan is
    MEASURED — the serving table consumes the same rows the launchers do."""
    mesh = compat.make_mesh((1, 8), ("data", "model"))
    cfg = get_config("tinyllama-1.1b").reduced()
    run = RunConfig(dp_axes=("data",), fsdp=False, comm_policy="auto")
    rules = ShardingRules(mesh, run)
    serve = ServeConfig(max_batch=8, prefill_batch=8, bucket_edges=(128,),
                        max_new_tokens=4)
    table = resolve_serving_plans(cfg, run, rules, serve)
    pre = {p.island: p for p in table["prefill@128"].plans}
    # (m, n, k) = (1024, 64, 16) — exactly the seed's mlp island rows
    assert pre["mlp"].source == "measured"
    assert not pre["mlp"].fallback


# ---------------------------------------------------------------------------
# plan_overrides / island_override unit behavior
# ---------------------------------------------------------------------------

def test_plan_overrides_normalization(mesh4):
    isl = Island("ring_isl", mesh=mesh4, axis="x", inputs={"x": P()},
                 out_specs=P(), body=lambda ctx, x: x,
                 comm=Comm("matmul_all_reduce", m=4096, n=4096, k=4096,
                           n_chunks=2))
    plan = isl.plan()
    ovs = plan_overrides([plan])
    assert len(ovs) == 1
    name, be, chunks = ovs[0]
    assert name == "ring_isl" and be == plan.backend
    if plan.backend in ("ring", "ring_bidir"):
        # plan.n_chunks is ring steps x sub-chunks; the override carries
        # sub-chunks (what CommContext.chunks means)
        assert chunks == plan.n_chunks // plan.axis_size == 2
    # fallback plans produce no override
    dense = Island("no_mesh", reference=lambda x: x)
    assert plan_overrides([dense.plan()]) == ()
    # later entries win
    run = RunConfig(island_overrides=(("a", "bulk", None),
                                      ("a", "ring", 4)))
    assert island_override(run, "a") == ("ring", 4, "plan")
    assert island_override(run, "b") is None
    # 4-tuple entries carry an explicit source tag (health demotions)
    run = RunConfig(island_overrides=(("a", "bulk", None, "health"),))
    assert island_override(run, "a") == ("bulk", None, "health")


def test_override_pins_context_and_plan_roundtrip(mesh4):
    run = RunConfig(island_overrides=(("pinned", "ring", 2),))
    isl = Island("pinned", mesh=mesh4, axis="x", run=run,
                 inputs={"x": P()}, out_specs=P(), body=lambda ctx, x: x,
                 comm=Comm("matmul_all_reduce", m=4096, n=4096, k=4096))
    ctx = isl.make_context()
    assert ctx.backend == "ring" and ctx.chunks == 2
    plan = isl.plan()
    assert plan.backend == "ring"
    assert plan.n_chunks == 4 * 2
    # explicit ctx_kwargs at the declaration site still beat the override
    expl = Island("pinned", mesh=mesh4, axis="x", run=run,
                  inputs={"x": P()}, out_specs=P(), body=lambda ctx, x: x,
                  comm=Comm("matmul_all_reduce", m=4096, n=4096, k=4096),
                  ctx_kwargs={"backend": "bulk"})
    assert expl.make_context().backend == "bulk"


# ---------------------------------------------------------------------------
# Vector-pos decode == scalar-pos decode (the pool's core invariant)
# ---------------------------------------------------------------------------

def test_vector_pos_decode_matches_scalar(mesh22):
    from repro.models import transformer as T
    cfg = get_config("tinyllama-1.1b").reduced()
    run = RunConfig(dp_axes=("data",), fsdp=False)
    rules = ShardingRules(mesh22, run)
    params = T.init_params(T.param_template(cfg, run, rules),
                           jax.random.PRNGKey(0), cfg.d_model)
    toks = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0,
                              cfg.vocab_size)
    lens = jnp.full((4,), 8, jnp.int32)

    def gen(slot_pos):
        ct = T.cache_template(cfg, run, rules, batch=4, s_max=16,
                              slot_pos=slot_pos)
        cache = T.init_params(ct, jax.random.PRNGKey(1), cfg.d_model)
        logits, cache = jax.jit(
            lambda p, c, t, ln: T.prefill_step(p, c, t, ln, cfg, run,
                                               rules))(
            params, cache, toks, lens if slot_pos else 8)
        outs = [logits]
        tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)[:, None] \
            .astype(jnp.int32)
        for _ in range(3):
            logits, cache = jax.jit(
                lambda p, c, t: T.decode_step(p, c, t, cfg, run, rules))(
                params, cache, tok)
            outs.append(logits)
            tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)[:, None] \
                .astype(jnp.int32)
        return outs

    for a, b in zip(gen(True), gen(False)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
