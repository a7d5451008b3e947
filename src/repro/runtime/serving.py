"""Template-driven continuous-batching serving engine.

The ROADMAP's serving batcher: a request queue with shape-bucketed
admission, prefill/decode interleaving over one shared slot-cache, and a
per-bucket jitted step cache — where *every* bucket's step program is built
from the unified ``core.template.Island`` declarations with that bucket's
resolved overlap plan threaded back into its ``CommContext``.

The plan loop (the point of the whole engine)::

    per bucket:  island_plans(cfg, run, rules, batch, seq, phase=...)
                      │  trace-free: backend / chunks / hidden fraction,
                      │  measured on a calibrated mesh (island rows first)
                      ▼
                 plan_overrides(plans)  ──►  RunConfig.island_overrides
                      │
                      ▼
                 jit(prefill_step | decode_step)   ← Island.make_context()
                      pins each island to exactly the schedule its
                      recorded plan reported

Prefill buckets run the full-sequence cache-building forward at
(prefill_batch, bucket_len) — their GEMM islands see m = B_loc·L — while the
decode bucket's one-token step sees m = B_loc·1, so on a calibrated mesh the
two can (and do) resolve to different backends or chunk counts for the SAME
island. That is Syncopate's chunk-centric observation applied to serving:
per-phase chunk choices are where overlap wins or dies.

Scheduling: prefill-priority. Each engine step is either one prefill of a
bucket group (up to ``ServeConfig.prefill_batch`` queued requests sharing a
bucket, padded with inert slots so each bucket compiles exactly one program)
or one decode tick over the whole slot pool. Slots hold sequences at
different depths — the decode step runs with a per-slot position vector
(``cache_template(slot_pos=True)``), stale cache masked by ``ki < pos``.

Memory: ``ServeConfig.cache_layout`` picks between the dense per-slot slab
and the paged pool (``runtime/paging.py``): a fixed page pool + per-slot
block tables + a host-side refcounting allocator, with copy-on-write prefix
sharing and page-aligned chunked prefill (``prefill_chunk``) so a long
prompt's prefill is split across engine steps and decode ticks interleave
mid-prefill. Admission in paged mode allocates a request's full page span up
front; pool exhaustion surfaces as admission backpressure (the step decodes
instead, draining pages), never as an error.

Determinism: admission, eviction, and token choice (greedy argmax) are pure
functions of the submitted trace; ``events`` records every admit/retire
(with cache-memory metrics) plus every prefill chunk so scheduling
regressions are diffable. Continuous-batched outputs are bit-identical to
sequential (one-request-at-a-time) processing — pinned by
tests/test_serving.py on the emulated meshes, for both cache layouts.

Fleet hooks (``runtime/fleet.py`` drives N engines as replicas):

* ``run(step_budget=k)`` — cooperative stepping: run at most ``k`` engine
  steps and return, so an external driver can interleave replicas
  deterministically (``step()`` itself stays public for one-at-a-time
  drivers);
* ``drain()`` — stop admitting; in-flight slots finish, queued requests are
  handed back via ``take_queued()`` (the fleet router requeues them);
* ``take_undone()`` — kill support: pop EVERY not-yet-completed request
  (queued + in-flight slots + mid-prefill job rows) exactly once, in rid
  order, so the router can requeue a dead replica's work;
* ``load()`` — router feedback: queued + live slots + mid-prefill rows;
* ``inject_step_delay(dt)`` — tests/fault plans inflate the next recorded
  step time (feeds the watchdog and the fleet's straggler signal without
  wall-clock sleeps);
* ``prefix_match_len(prompt)`` — cache-affinity routing feedback: longest
  prefix the paged ``PrefixCache`` already holds for this prompt.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig, RunConfig, ServeConfig
from repro.core.template import IslandPlan, plan_overrides, render_plans
from repro.models import transformer as T
from repro.models.layers import island_plans
from repro.models.sharding import ShardingRules
from repro.runtime import paging
from repro.runtime.health import (COMM_FAULT_KINDS, PAYLOAD_FAULT_KINDS,
                                  CommFaultPlan, HealthMonitor,
                                  demotion_ladder, take_guard_trips)
from repro.runtime.straggler import StepTimer, StragglerWatchdog
from repro.train.step import (make_paged_prefill_step,
                              make_prefill_cache_step, make_serve_step)

__all__ = ["Request", "Completion", "BucketPlan", "ServingEngine",
           "resolve_serving_plans", "render_serving_plans",
           "serving_plan_record"]


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request. ``prompt`` is the token ids; generation is
    greedy argmax for ``max_new_tokens`` tokens (no EOS in the synthetic
    vocab — length is the stop condition)."""

    rid: int
    prompt: tuple[int, ...]
    max_new_tokens: int


@dataclasses.dataclass
class Completion:
    rid: int
    prompt_len: int
    bucket: int
    tokens: list[int]
    admitted_step: int
    finished_step: int
    slot: int


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """The resolved overlap schedule for one bucket's step program."""

    phase: str                       # "prefill" | "decode"
    bucket: int                      # padded prompt length / pool size
    batch: int
    seq: int
    plans: tuple[IslandPlan, ...]
    overrides: tuple                 # frozen RunConfig.island_overrides

    def asdict(self) -> dict:
        return {"phase": self.phase, "bucket": self.bucket,
                "batch": self.batch, "seq": self.seq,
                "islands": [p.asdict() for p in self.plans],
                "overrides": [list(o) for o in self.overrides]}


def padded_s_max(serve: ServeConfig, rules: ShardingRules | None) -> int:
    """The slot-cache length: worst prompt + generation, rounded up so the
    sequence-sharded KV cache divides the tp axis (extra tail slots are
    never attended — decode masks ``ki < pos``)."""
    tp = rules.mesh.shape[rules.tp] if rules is not None else 1
    return -(-serve.s_max // tp) * tp


def resolve_page_geometry(serve: ServeConfig,
                          rules: ShardingRules | None) -> paging.PageGeometry:
    """The engine's page-pool geometry for this (serve, mesh) pair — page
    size padded to the tp stripe, pool partitioned with the slot batch."""
    tp = rules.mesh.shape[rules.tp] if rules is not None else 1
    return paging.resolve_page_geometry(
        serve, s_max=padded_s_max(serve, rules), tp_size=tp,
        n_partitions=paging.page_partitions(rules, serve.max_batch))


def resolve_serving_plans(cfg: ArchConfig, run: RunConfig,
                          rules: ShardingRules | None,
                          serve: ServeConfig) -> dict[str, BucketPlan]:
    """Evaluate ``island_plans()`` per shape bucket: one prefill entry per
    bucket edge (at the bucket's exact (prefill_batch, L) coordinates) plus
    the decode pool's one-token entry. The returned overrides are what the
    engine threads into each bucket's jitted step.

    Paged layout: the decode entry resolves the paged decode island (same
    ``decode_attn`` name and Comm coordinates, so frozen plans carry over),
    and with ``prefill_chunk`` set every bucket shares ONE chunk-shaped
    prefill program — the inventory collapses to a single
    ``prefill@chunk{cl}`` entry at (prefill_batch, chunk) coordinates."""
    paged = serve.cache_layout == "paged"
    ps = resolve_page_geometry(serve, rules).page_size if paged else 0
    out: dict[str, BucketPlan] = {}
    if paged and serve.prefill_chunk:
        cl = serve.prefill_chunk
        plans = tuple(island_plans(cfg, run, rules,
                                   batch=serve.prefill_batch, seq=cl,
                                   phase="prefill", page_size=ps))
        out[f"prefill@chunk{cl}"] = BucketPlan(
            "prefill", cl, serve.prefill_batch, cl, plans,
            plan_overrides(plans))
    else:
        for edge in serve.bucket_edges:
            plans = tuple(island_plans(cfg, run, rules,
                                       batch=serve.prefill_batch, seq=edge,
                                       phase="prefill", page_size=ps))
            out[f"prefill@{edge}"] = BucketPlan(
                "prefill", edge, serve.prefill_batch, edge, plans,
                plan_overrides(plans))
    plans = tuple(island_plans(cfg, run, rules, batch=serve.max_batch,
                               seq=padded_s_max(serve, rules),
                               phase="decode", page_size=ps))
    out["decode"] = BucketPlan("decode", serve.max_batch, serve.max_batch,
                               1, plans, plan_overrides(plans))
    return out


def render_serving_plans(table: dict[str, BucketPlan]) -> str:
    """Printable per-bucket island table (the serve CLI shows this)."""
    lines = []
    for name, bp in table.items():
        lines.append(f"[{name}] batch={bp.batch} seq={bp.seq}")
        lines.append(render_plans(bp.plans))
    return "\n".join(lines)


def serving_plan_record(cfg: ArchConfig, run: RunConfig,
                        rules: ShardingRules | None,
                        serve: ServeConfig) -> dict:
    """JSON-able per-bucket plan table (dry-run artifact / plan diffing):
    resolves the full serving schedule without building the engine, so plan
    regressions are reviewable from the artifact alone."""
    table = resolve_serving_plans(cfg, run, rules, serve)
    s_max = padded_s_max(serve, rules)
    kv_dt = serve.kv_dtype
    cache: dict[str, Any] = {"layout": serve.cache_layout,
                             "s_max": s_max,
                             "kv_dtype": kv_dt,
                             # f32 scale-plane bytes per cached position
                             # (0 in bf16 — no scale leaves exist)
                             "scale_bytes_per_pos": (
                                 cfg.n_layers * cfg.n_kv_heads * 2 * 4
                                 if kv_dt == "int8" else 0),
                             "slab_bytes": paging.slab_hbm_bytes(
                                 cfg, serve.max_batch, s_max,
                                 kv_dtype=kv_dt)}
    if serve.cache_layout == "paged":
        geom = resolve_page_geometry(serve, rules)
        cache.update({
            "page_size": geom.page_size, "n_pages": geom.n_pages,
            "pages_per_slot": geom.pages_per_slot,
            "n_partitions": geom.n_partitions,
            "prefill_chunk": serve.prefill_chunk,
            "pool_bytes": paging.pool_hbm_bytes(cfg, geom, kv_dtype=kv_dt),
            # per-bucket resident-slot capacity at full span (L + max_new)
            "resident_capacity": {
                str(e): geom.resident_capacity(e + serve.max_new_tokens,
                                               serve.max_batch)
                for e in serve.bucket_edges}})
    else:
        cache["resident_capacity"] = {str(e): serve.max_batch
                                      for e in serve.bucket_edges}
    return {"config": {"max_batch": serve.max_batch,
                       "prefill_batch": serve.prefill_batch,
                       "bucket_edges": list(serve.bucket_edges),
                       "max_new_tokens": serve.max_new_tokens,
                       "queue_policy": serve.queue_policy},
            "comm_policy": run.comm_policy,
            "comm_wire": run.comm_wire or "bf16",
            "cache": cache,
            "buckets": {name: bp.asdict() for name, bp in table.items()}}


def _jit_step(fn, plan: str):
    """``jax.jit`` of one step program, the cache (argument 1) donated,
    named after its bucket plan (``decode``, ``prefill@<bucket>``,
    ``prefill@chunk<cl>``): the device trace's module line then reads
    ``jit_serve_decode``, ``jit_serve_prefill_<bucket>`` or
    ``jit_serve_prefill_chunk<cl>``."""
    fn.__name__ = fn.__qualname__ = "serve_" + plan.replace("@", "_")
    return jax.jit(fn, donate_argnums=(1,))


@dataclasses.dataclass
class _Slot:
    rid: int
    last_token: int
    remaining: int
    tokens: list[int]
    admitted_step: int
    bucket: int
    prompt_len: int


@dataclasses.dataclass
class _PrefillJob:
    """One in-flight chunked paged prefill: a bucket group whose chunks run
    across engine steps (decode ticks interleave between them). Group rows
    are partition-aligned — row ``p*rows_per_part + i`` computes on dp shard
    ``p`` and writes that shard's pool partition."""

    bucket: int
    chunk_len: int
    n_chunks: int                    # ceil(bucket / chunk_len)
    next_chunk: int                  # resumes past fully-shared chunks
    end_chunk: int                   # last chunk any row needs
    reqs: list                       # Request | None per group row
    slot_ids: list                   # int | None per group row
    tokens: np.ndarray               # (G, n_chunks*cl) right-padded prompts
    lens: np.ndarray                 # (G,) real lengths (1 for pad rows)
    write_from: np.ndarray           # (G,) shared-prefix write floor
    group_bt: np.ndarray             # (G, pages_per_slot) global page ids
    pages: list                      # per row: owned page list (refs held)
    shared: list                     # per row: leading shared-page count
    logit_chunk: list                # per row: chunk containing L-1
    first_token: list                # per row: captured greedy first token
    poisoned: list                   # per row: non-finite logits seen
    started_step: int


class ServingEngine:
    """Continuous-batching engine over one (cfg, run, rules, params).

    The caller owns parameter construction/sharding (see
    ``launch.serve.build_engine``); the engine owns the slot cache, the
    request queue, the per-bucket jitted step cache, and the schedule.
    """

    def __init__(self, cfg: ArchConfig, run: RunConfig,
                 rules: ShardingRules | None, params,
                 serve: ServeConfig | None = None,
                 comm_faults: CommFaultPlan | str | None = None):
        self.cfg = cfg
        self.serve = serve if serve is not None else ServeConfig()
        if cfg.encoder_decoder:
            raise NotImplementedError(
                "the continuous-batching engine covers decoder-only models")
        if any(sp.mixer == "mamba" for sp in cfg.layer_pattern()) \
                and not self.serve.exact_buckets:
            raise ValueError(
                "SSM state cannot mask right-padded prompts; use "
                "ServeConfig(exact_buckets=True) for SSM/hybrid archs")
        self.base_run = run
        self.rules = rules
        self.params = params
        # --- per-bucket plan resolution (the startup plan loop) ----------
        self.bucket_plans = resolve_serving_plans(cfg, run, rules, self.serve)
        self._runs = {name: dataclasses.replace(run,
                                                island_overrides=bp.overrides)
                      for name, bp in self.bucket_plans.items()}
        # --- decode pool state -------------------------------------------
        b = self.serve.max_batch
        self.s_max = padded_s_max(self.serve, rules)
        self.paged = self.serve.cache_layout == "paged"
        if self.paged:
            self.geom = resolve_page_geometry(self.serve, rules)
            if self.serve.prefill_batch % self.geom.n_partitions:
                raise ValueError(
                    f"paged prefill groups are partition-aligned: "
                    f"prefill_batch ({self.serve.prefill_batch}) must be a "
                    f"multiple of the pool partition count "
                    f"({self.geom.n_partitions})")
            self._cache_tmpl = paging.paged_cache_template(
                cfg, self._runs["decode"], rules, batch=b, geom=self.geom,
                kv_dtype=self.serve.kv_dtype)
            self.cache = self._sharded_zeros(self._cache_tmpl)
            # block tables start fully unmapped (-1), never all-zeros: a
            # zero row would alias every free slot onto physical page 0
            self._commit_leaf("block_tables",
                              jnp.full((b, self.geom.pages_per_slot), -1,
                                       jnp.int32))
            self.allocator = paging.PageAllocator(self.geom)
            self.prefix = paging.PrefixCache(self.allocator)
            # MoE capacity dropping makes K/V depend on batch composition,
            # so a donor's pages are not reusable bit-for-bit — disable
            # sharing there (chunked prefill itself is still fine)
            self._share_ok = all(sp.mlp == "dense"
                                 for sp in cfg.layer_pattern())
            self._bt_host = np.full((b, self.geom.pages_per_slot), -1,
                                    np.int32)
            self._slot_pages: list[list[int] | None] = [None] * b
        else:
            self.geom = None
            self._cache_tmpl = T.cache_template(
                cfg, self._runs["decode"], rules, batch=b, s_max=self.s_max,
                slot_pos=True, kv_dtype=self.serve.kv_dtype)
            self.cache = self._sharded_zeros(self._cache_tmpl)
        self._job: _PrefillJob | None = None
        self._decode_fn = _jit_step(
            make_serve_step(cfg, self._runs["decode"], rules), "decode")
        self._prefill_fns: dict[int, Any] = {}     # bucket L -> jitted step
        self._prefill_tmpls: dict[int, Any] = {}
        self._static_fns: dict[tuple[int, int], tuple] = {}
        # --- host-side scheduler state -----------------------------------
        self.queue: collections.deque[Request] = collections.deque()
        self.slots: list[_Slot | None] = [None] * b
        self.completions: dict[int, Completion] = {}
        self.events: list[tuple] = []
        self.step_no = 0
        self.step_kinds: list[str] = []
        self.watchdog = StragglerWatchdog()
        self.step_times: list[float] = []
        self.last_phases: dict[str, float] = {}   # split of the last step
        self.phase_s: dict[str, float] = {}       # seconds per phase, summed
        self.tokens_generated = 0
        # prefill padding: real prompt tokens dispatched, and bucket x rows
        # dispatched (inert group rows included)
        self.prefill_tokens = 0
        self.prefill_slot_tokens = 0
        self._next_rid = 0
        # fleet hooks: original Request per live rid (so a killed replica's
        # in-flight work can be requeued), admission gate, injected delay
        self._requests: dict[int, Request] = {}
        self.draining = False
        self._injected_delay = 0.0
        # cache-memory accounting (both layouts track peak residency)
        self.prefix_hits = 0
        self.shared_pages_reused = 0
        self.cow_copies = 0
        self.admission_blocked = 0
        self._peak_pages = 0
        self._peak_slots = 0
        # --- runtime health (runtime/health.py) ---------------------------
        if isinstance(comm_faults, str):
            comm_faults = CommFaultPlan.parse(comm_faults)
        self.comm_faults = comm_faults if comm_faults is not None \
            else CommFaultPlan()
        self._active_faults: list[dict] = []
        self._current_fault: tuple | None = None   # (kind, island, hop)
        self._fault_fns: dict[tuple, Any] = {}     # faulted-trace jit cache
        self._base_plans = dict(self.bucket_plans)  # pristine, pre-health
        self._hov: tuple = ()                      # live health overrides
        self._retries: dict[int, int] = {}
        self._not_before: dict[int, int] = {}      # retry backoff gate
        self._submit_step: dict[int, int] = {}
        self.quarantined: dict[int, dict] = {}
        self.expired: dict[int, dict] = {}
        self.health: HealthMonitor | None = None
        self._health_ev_seen = 0
        if self.serve.health_monitor:
            self.health = HealthMonitor(
                self._health_ladders(),
                factor=self.serve.health_factor,
                demote_after=self.serve.health_demote_after,
                probation=self.serve.health_probation)

    # -- plumbing ----------------------------------------------------------

    def _sharded_zeros(self, tmpl):
        tree = jax.tree.map(
            lambda pd: jnp.zeros(pd.shape, pd.dtype), tmpl,
            is_leaf=lambda x: isinstance(x, T.PD))
        if self.rules is None:
            return tree
        specs = T.param_specs(tmpl)
        return jax.tree.map(
            lambda x, s: jax.device_put(x, self.rules.named(s)), tree, specs)

    def _recommit_cache(self, cache):
        """Re-pin the slot cache to its declared shardings after host-side
        scatter updates (``.at[slots].set`` results default-commit)."""
        if self.rules is None:
            return cache
        specs = T.param_specs(self._cache_tmpl)
        return jax.tree.map(
            lambda x, s: jax.device_put(x, self.rules.named(s)), cache, specs)

    def _commit_leaf(self, name: str, val) -> None:
        """Replace ONE top-level cache leaf, re-pinned to its sharding —
        cheaper than recommitting the whole pool for block-table edits."""
        if self.rules is not None:
            spec = T.param_specs(self._cache_tmpl)[name]
            val = jax.device_put(val, self.rules.named(spec))
        self.cache = {**self.cache, name: val}

    def _mem_metrics(self) -> dict:
        """Cache-memory snapshot attached to every admit/retire event."""
        live = sum(s is not None for s in self.slots)
        self._peak_slots = max(self._peak_slots, live)
        m: dict[str, Any] = {"resident_slots": live}
        if self.paged:
            rp = self.allocator.resident_pages
            self._peak_pages = max(self._peak_pages, rp)
            m["resident_pages"] = rp
            m["free_pages"] = self.geom.n_pages - rp
        return m

    def _greedy(self, logits) -> np.ndarray:
        """Next token per slot — the ONE sampling rule both the engine and
        the static baseline use, so batched-vs-sequential equivalence is a
        scheduling property, not a sampling accident."""
        return np.asarray(
            jnp.argmax(logits[:, -1, :self.cfg.vocab_size], axis=-1),
            dtype=np.int32)

    def _prefill_fn(self, bucket: int):
        if bucket not in self._prefill_fns:
            name = f"prefill@{bucket}"
            if name not in self.bucket_plans:
                # exact_buckets: lengths inside the largest edge that are
                # not pre-declared resolve their plan on first use
                run = self.base_run
                plans = tuple(island_plans(
                    self.cfg, run, self.rules, batch=self.serve.prefill_batch,
                    seq=bucket, phase="prefill"))
                self.bucket_plans[name] = BucketPlan(
                    "prefill", bucket, self.serve.prefill_batch, bucket,
                    plans, plan_overrides(plans))
                self._base_plans[name] = self.bucket_plans[name]
                # live health demotions layer above the fresh plan too
                self._runs[name] = dataclasses.replace(
                    run, island_overrides=(
                        self.bucket_plans[name].overrides + self._hov))
            run = self._runs[name]
            self._prefill_fns[bucket] = _jit_step(
                make_prefill_cache_step(self.cfg, run, self.rules), name)
            self._prefill_tmpls[bucket] = T.cache_template(
                self.cfg, run, self.rules, batch=self.serve.prefill_batch,
                s_max=self.s_max, slot_pos=True,
                kv_dtype=self.serve.kv_dtype)
        return self._prefill_fns[bucket]

    def _paged_prefill_fn(self, bucket: int):
        """Jitted chunk program, keyed by chunk length: with
        ``prefill_chunk`` set every bucket shares ONE (G, cl) program; in
        single-shot paged mode (chunk = bucket) it is per-bucket like the
        slab path."""
        cl = self.serve.prefill_chunk or bucket
        if cl not in self._prefill_fns:
            name = (f"prefill@chunk{cl}" if self.serve.prefill_chunk
                    else f"prefill@{bucket}")
            if name not in self.bucket_plans:
                run = self.base_run
                plans = tuple(island_plans(
                    self.cfg, run, self.rules,
                    batch=self.serve.prefill_batch, seq=cl, phase="prefill",
                    page_size=self.geom.page_size))
                self.bucket_plans[name] = BucketPlan(
                    "prefill", bucket, self.serve.prefill_batch, cl,
                    plans, plan_overrides(plans))
                self._base_plans[name] = self.bucket_plans[name]
                self._runs[name] = dataclasses.replace(
                    run, island_overrides=(
                        self.bucket_plans[name].overrides + self._hov))
            self._prefill_fns[cl] = _jit_step(
                make_paged_prefill_step(self.cfg, self._runs[name],
                                        self.rules),
                name)
        return self._prefill_fns[cl]

    @property
    def compiled_buckets(self) -> list[int]:
        """Prefill buckets a step has been jitted for (the jit cache)."""
        return sorted(self._prefill_fns)

    def step_programs(self) -> dict[str, Any]:
        """{module name: ``jax.stages.Compiled``} of the decode step and of
        each prefill program jitted so far, lowered again at the shapes the
        engine calls them with, so the compile cache serves them. Their HLO
        carries the ``op_name`` scopes (islands, ``qkv``, ``norm``,
        ``head``, ``cache_scan``) that a device trace's op events lack."""
        g = self.serve.prefill_batch

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        def abstract(tmpl):
            return jax.tree.map(
                lambda pd: jax.ShapeDtypeStruct(
                    pd.shape, pd.dtype, sharding=(
                        self.rules.named(pd.spec) if self.rules else None)),
                tmpl, is_leaf=lambda x: isinstance(x, T.PD))

        calls = [(self._decode_fn,
                  (self.cache, i32(self.serve.max_batch, 1)))]
        for n, fn in self._prefill_fns.items():
            calls.append((fn, (self.cache, i32(g, n),
                               i32(g, self.geom.pages_per_slot), i32(g),
                               i32(), i32(g)) if self.paged else
                          (abstract(self._prefill_tmpls[n]), i32(g, n),
                           i32(g))))
        return {f"jit_{fn.__name__}": fn.lower(self.params, *args).compile()
                for fn, args in calls}

    # -- runtime health ----------------------------------------------------

    def _health_ladders(self) -> dict:
        """island -> demotion ladder, from the planned backends. The first
        bucket declaring an island wins (the ladders only need the backend
        family, which is stable across buckets)."""
        ladders: dict[str, tuple] = {}
        for bp in self.bucket_plans.values():
            for p in bp.plans:
                if p.fallback or p.backend is None or p.island in ladders:
                    continue
                lad = demotion_ladder(p.backend)
                if lad:
                    ladders[p.island] = lad
        return ladders

    def inject_comm_fault(self, kind: str, island: str, ticks: int = 1,
                          hop: int = 0, stall_dt: float = 1.0) -> None:
        """Activate a comms-level fault NOW, for ``ticks`` engine steps —
        the fleet's (and the scripted ``CommFaultPlan``'s) entry point."""
        if kind not in COMM_FAULT_KINDS:
            raise ValueError(f"unknown comm fault kind {kind!r}; one of "
                             f"{COMM_FAULT_KINDS}")
        self._active_faults.append({"kind": kind, "island": island,
                                    "hop": int(hop),
                                    "remaining": max(1, int(ticks)),
                                    "stall_dt": float(stall_dt)})
        self.events.append(("comm_fault", self.step_no, kind, island,
                            max(1, int(ticks))))
        if kind == "linkdown" and self.health is not None:
            if self.health.link_down(island, self.step_no):
                self._refresh_health_overrides()

    def _fire_comm_faults(self) -> None:
        """Activate scripted events for the step ABOUT to run, then pick the
        payload fault (if any) this step's traces must carry."""
        for ev in self.comm_faults.at(self.step_no + 1):
            self.inject_comm_fault(ev.kind, ev.island, ticks=ev.ticks,
                                   hop=ev.hop, stall_dt=ev.stall_dt)
        self._current_fault = None
        for f in self._active_faults:
            if f["kind"] in PAYLOAD_FAULT_KINDS:
                self._current_fault = (f["kind"], f["island"], f["hop"])
                break

    def _tick_comm_faults(self) -> None:
        still = []
        for f in self._active_faults:
            f["remaining"] -= 1
            if f["remaining"] > 0:
                still.append(f)
            else:
                self.events.append(("comm_fault_end", self.step_no,
                                    f["kind"], f["island"]))
                if f["kind"] == "linkdown" and self.health is not None:
                    # link restored; re-promotion earns its way back through
                    # the probation window, not instantly
                    self.health.link_up(f["island"], self.step_no)
        self._active_faults = still

    def _faulted_fn(self, key: tuple, fault: tuple):
        """Jitted step variant whose ``RunConfig.comm_fault`` poisons the
        targeted ring hop (trace-time static) — cached per (program, fault)
        so repeated fault ticks reuse the compiled program."""
        k = (key, fault)
        if k not in self._fault_fns:
            phase, bucket = key
            if phase == "decode":
                run = dataclasses.replace(self._runs["decode"],
                                          comm_fault=fault)
                self._fault_fns[k] = _jit_step(
                    make_serve_step(self.cfg, run, self.rules),
                    "decode")
            elif phase == "paged":
                self._paged_prefill_fn(bucket)     # materialize the plan
                cl = self.serve.prefill_chunk or bucket
                name = (f"prefill@chunk{cl}" if self.serve.prefill_chunk
                        else f"prefill@{bucket}")
                run = dataclasses.replace(self._runs[name], comm_fault=fault)
                self._fault_fns[k] = _jit_step(
                    make_paged_prefill_step(self.cfg, run, self.rules),
                    name)
            else:
                self._prefill_fn(bucket)           # materialize the plan
                run = dataclasses.replace(self._runs[f"prefill@{bucket}"],
                                          comm_fault=fault)
                self._fault_fns[k] = _jit_step(
                    make_prefill_cache_step(self.cfg, run, self.rules),
                    f"prefill@{bucket}")
        return self._fault_fns[k]

    def _stall_applies(self, island: str, kind: str) -> bool:
        """A scripted link stall penalizes a step only while the island's
        CURRENT effective backend still rides the slow link (a ring-family
        schedule). A health demotion to bulk routes around it — which is
        exactly the throughput recovery the monitor's demotion buys."""
        names = ([n for n, bp in self.bucket_plans.items()
                  if bp.phase == "prefill"] if kind == "prefill"
                 else ["decode"])
        for n in names:
            for p in self.bucket_plans[n].plans:
                if p.fallback or p.island != island:
                    continue
                # health overrides patch bucket_plans live, so p.backend
                # already reflects any demotion
                if p.backend in ("ring", "ring_bidir", "chunked", "fused"):
                    return True
        return False

    def _refresh_health_overrides(self) -> None:
        """Re-layer the monitor's demotions (source ``"health"``) above every
        bucket's frozen plan overrides, patch the live plan records, and
        re-jit the step programs. The calibration table and the measured
        dispatch below this layer are never touched — promotion is just the
        override disappearing."""
        hov = self.health.overrides() if self.health is not None else ()
        self._hov = hov
        by_island = {o[0]: o for o in hov}
        for name, base in self._base_plans.items():
            plans = tuple(
                dataclasses.replace(
                    p, backend=by_island[p.island][1],
                    n_chunks=(by_island[p.island][2]
                              if by_island[p.island][2] is not None
                              else p.n_chunks),
                    source="health",
                    reason=f"health demotion -> {by_island[p.island][1]}")
                if (not p.fallback and p.island in by_island) else p
                for p in base.plans)
            ov = base.overrides + hov
            self.bucket_plans[name] = dataclasses.replace(
                base, plans=plans, overrides=ov)
            self._runs[name] = dataclasses.replace(
                self.base_run, island_overrides=ov)
        self._decode_fn = _jit_step(
            make_serve_step(self.cfg, self._runs["decode"], self.rules),
            "decode")
        self._prefill_fns.clear()
        self._fault_fns.clear()

    def _drain_health_events(self) -> None:
        if self.health is None:
            return
        for ev in self.health.events[self._health_ev_seen:]:
            self.events.append(("health_" + ev[0],) + tuple(ev[1:]))
        self._health_ev_seen = len(self.health.events)

    def plan_record(self) -> dict:
        """LIVE per-bucket plan table — unlike ``serving_plan_record()``,
        which re-resolves from config, this reflects runtime health
        demotions (``src=health`` islands and the layered overrides)."""
        return {"buckets": {n: bp.asdict()
                            for n, bp in self.bucket_plans.items()},
                "health_overrides": [list(o) for o in self._hov]}

    def _finite_rows(self, logits) -> np.ndarray:
        """(B,) bool per batch row: the final-position logits are all
        finite — the poison detector (NaN and ±inf both trip it)."""
        v = self.cfg.vocab_size
        return np.asarray(jnp.isfinite(
            jnp.max(jnp.abs(logits[:, -1, :v]), axis=-1)))

    def _poisoned(self, req: Request, reason: str) -> None:
        """Retry-with-backoff, or quarantine once retries are exhausted."""
        attempt = self._retries.get(req.rid, 0)
        if attempt < self.serve.max_retries:
            self._retries[req.rid] = attempt + 1
            self._not_before[req.rid] = (
                self.step_no + self.serve.retry_backoff * (2 ** attempt))
            self.queue.append(req)
            self.events.append(("retry", self.step_no, req.rid, attempt + 1))
        else:
            self.quarantined[req.rid] = {"prompt_len": len(req.prompt),
                                         "step": self.step_no,
                                         "reason": reason}
            self._requests.pop(req.rid, None)
            self.events.append(("quarantine", self.step_no, req.rid))

    def _evict_slot(self, slot: int) -> None:
        """Drop a live slot WITHOUT a completion (quarantine/deadline).
        Slab cache rows left behind are inert — the next admission into the
        slot overwrites every position it will ever attend to."""
        s = self.slots[slot]
        self._requests.pop(s.rid, None)
        self.slots[slot] = None
        if self.paged:
            self._bt_host[slot] = -1
            self._commit_leaf("block_tables",
                              self.cache["block_tables"].at[slot].set(-1))
            self.allocator.release(self._slot_pages[slot] or [])
            self._slot_pages[slot] = None

    def _expire_deadlines(self) -> None:
        dl = self.serve.deadline_steps
        if not dl:
            return
        for r in [r for r in self.queue
                  if self.step_no - self._submit_step.get(r.rid,
                                                          self.step_no) >= dl]:
            self.queue.remove(r)
            self._requests.pop(r.rid, None)
            self.expired[r.rid] = {"tokens": [], "step": self.step_no,
                                   "where": "queued"}
            self.events.append(("deadline", self.step_no, r.rid))
        for i, s in enumerate(self.slots):
            if s is not None and (self.step_no - self._submit_step.get(
                    s.rid, self.step_no)) >= dl:
                self.expired[s.rid] = {"tokens": list(s.tokens),
                                       "step": self.step_no, "where": "slot"}
                self.events.append(("deadline", self.step_no, s.rid))
                self._evict_slot(i)

    # -- request intake ----------------------------------------------------

    def submit(self, prompt: Sequence[int], max_new_tokens: int | None = None,
               rid: int | None = None) -> int:
        prompt = tuple(int(t) for t in prompt)
        if not prompt:
            raise ValueError("empty prompt")
        self.serve.bucket_for(len(prompt))       # validate length up front
        mx = max_new_tokens if max_new_tokens is not None \
            else self.serve.max_new_tokens
        if not 1 <= mx <= self.serve.max_new_tokens:
            # the slot cache is sized for bucket + max_new_tokens; a longer
            # generation would walk pos past s_max and silently drop K/V
            raise ValueError(
                f"max_new_tokens must be in [1, "
                f"{self.serve.max_new_tokens}] (ServeConfig sized the "
                f"cache); got {mx}")
        if rid is None:
            rid = self._next_rid
        self._next_rid = max(self._next_rid, rid) + 1
        req = Request(rid, prompt, mx)
        self._requests[rid] = req
        self._submit_step[rid] = self.step_no    # deadline clock starts now
        self.queue.append(req)
        return rid

    # -- fleet hooks -------------------------------------------------------

    @property
    def pending(self) -> bool:
        """True while any submitted request has not completed yet."""
        return (bool(self.queue) or self._job is not None
                or any(s is not None for s in self.slots))

    def drain(self) -> None:
        """Stop admitting: queued requests stay queued (the fleet router
        takes them via ``take_queued``), in-flight slots finish normally."""
        if not self.draining:
            self.draining = True
            self.events.append(("drain", self.step_no))

    def take_queued(self) -> list[Request]:
        """Pop every queued (not yet admitted) request, in queue order —
        the drain-snapshot hook: nothing on-device references these."""
        out = list(self.queue)
        self.queue.clear()
        return out

    def take_undone(self) -> list[Request]:
        """Pop EVERY not-yet-completed request — queued, mid-prefill job
        rows, and in-flight decode slots — exactly once, in rid order. The
        kill hook: the engine is dead afterwards (its device state is
        abandoned), the returned originals are what the router requeues."""
        undone: dict[int, Request] = {r.rid: r for r in self.queue}
        self.queue.clear()
        if self._job is not None:
            for r in self._job.reqs:
                if r is not None:
                    undone[r.rid] = r
            self._job = None
        for i, s in enumerate(self.slots):
            if s is not None:
                undone[s.rid] = self._requests[s.rid]
                self.slots[i] = None
        return [undone[k] for k in sorted(undone)]

    def load(self) -> int:
        """Router feedback: queued + live decode slots + mid-prefill job
        rows — everything this replica still owes compute to."""
        job_rows = 0 if self._job is None \
            else sum(r is not None for r in self._job.reqs)
        return (len(self.queue) + sum(s is not None for s in self.slots)
                + job_rows)

    def inject_step_delay(self, dt: float) -> None:
        """Inflate the NEXT recorded step time by ``dt`` seconds (fault
        injection: feeds the watchdog and the fleet straggler signal
        deterministically, without a wall-clock sleep)."""
        self._injected_delay += dt

    def prefix_match_len(self, prompt: Sequence[int]) -> int:
        """Longest prefix of ``prompt`` the paged ``PrefixCache`` already
        holds (0 for the slab layout or when sharing is disabled) — the
        feedback the fleet's cache-affinity router steers on."""
        if not self.paged or not self._share_ok:
            return 0
        prompt = tuple(int(t) for t in prompt)
        sched = ("chunk", self.serve.prefill_chunk
                 or self.serve.bucket_for(len(prompt)))
        return max(self.prefix.lookup(p, prompt, sched)[0]
                   for p in range(self.geom.n_partitions))

    # -- scheduling --------------------------------------------------------

    def _next_group(self):
        """(bucket, requests, slot_ids) to prefill next, or None.

        Prefill-priority: whenever slots are free and the queue is
        non-empty, admit. ``fcfs`` takes only the contiguous same-bucket
        prefix behind the queue head; ``bucket-greedy`` scans the whole
        queue for head-bucket requests (may reorder across buckets).
        """
        free = [i for i, s in enumerate(self.slots) if s is None]
        # retry backoff: a poisoned request sits out until its gate opens
        eligible = [r for r in self.queue
                    if self._not_before.get(r.rid, 0) <= self.step_no]
        if self.draining or not free or not eligible:
            return None
        cap = min(len(free), self.serve.prefill_batch)
        head_bucket = self.serve.bucket_for(len(eligible[0].prompt))
        group = []
        if self.serve.queue_policy == "fcfs":
            for r in eligible:
                if len(group) == cap or \
                        self.serve.bucket_for(len(r.prompt)) != head_bucket:
                    break
                group.append(r)
        else:                                    # bucket-greedy
            for r in eligible:
                if len(group) == cap:
                    break
                if self.serve.bucket_for(len(r.prompt)) == head_bucket:
                    group.append(r)
        for r in group:
            self.queue.remove(r)
        return head_bucket, group, free[:len(group)]

    # -- paged scheduling --------------------------------------------------

    def _next_group_paged(self):
        """Paged admission: place queued head-bucket requests into
        partition-aligned group rows, allocating each request's FULL page
        span (prompt + max_new — no mid-decode allocation) up front, with
        prefix-share lookup against the registry. Stops at the first
        request that fits nowhere (strict order → deterministic
        backpressure); returns (bucket, placements) or None. Each placement
        is (request, slot, row, pages, n_shared, cow_src, write_from)."""
        if self.draining or self._job is not None or not self.queue:
            return None
        eligible = [r for r in self.queue
                    if self._not_before.get(r.rid, 0) <= self.step_no]
        if not eligible:
            return None
        geom, serve = self.geom, self.serve
        b_loc = serve.max_batch // geom.n_partitions
        rows_per_part = serve.prefill_batch // geom.n_partitions
        free = {p: [i for i in range(p * b_loc, (p + 1) * b_loc)
                    if self.slots[i] is None]
                for p in range(geom.n_partitions)}
        if not any(free.values()):
            return None
        head_bucket = serve.bucket_for(len(eligible[0].prompt))
        if serve.queue_policy == "fcfs":
            cands = []
            for r in eligible:
                if serve.bucket_for(len(r.prompt)) != head_bucket:
                    break
                cands.append(r)
        else:                                    # bucket-greedy
            cands = [r for r in eligible
                     if serve.bucket_for(len(r.prompt)) == head_bucket]
        sched = ("chunk", serve.prefill_chunk or head_bucket)
        placements, used = [], {p: 0 for p in range(geom.n_partitions)}
        blocked = False
        for r in cands:
            if len(placements) == serve.prefill_batch:
                break
            need = geom.pages_for(len(r.prompt) + r.max_new_tokens)
            placed = False
            for p in range(geom.n_partitions):
                if not free[p] or used[p] >= rows_per_part:
                    continue
                shared, cow_src, wf = [], None, 0
                if self._share_ok:
                    m, ent = self.prefix.lookup(p, r.prompt, sched)
                    if ent is not None and m:
                        nfull = m // geom.page_size
                        shared = list(ent.pages[:nfull])
                        if m % geom.page_size and nfull < len(ent.pages):
                            cow_src = ent.pages[nfull]
                        wf = m
                # retain BEFORE the eviction loop so evicting the donor
                # entry cannot free the pages we are about to share
                self.allocator.retain(shared)
                if cow_src is not None:
                    self.allocator.retain([cow_src])
                while True:
                    fresh = self.allocator.alloc(p, need - len(shared))
                    if fresh is not None or not self.prefix.evict_one(p):
                        break
                if fresh is None:
                    self.allocator.release(shared)
                    if cow_src is not None:
                        self.allocator.release([cow_src])
                    continue
                slot = free[p].pop(0)
                row = p * rows_per_part + used[p]
                used[p] += 1
                placements.append((r, slot, row, shared + fresh,
                                   len(shared), cow_src, wf))
                placed = True
                break
            if not placed:
                blocked = True
                break
        if not placements:
            if blocked:
                self.admission_blocked += 1
            return None
        for pl in placements:
            self.queue.remove(pl[0])
        return head_bucket, placements

    def _start_prefill_job(self, bucket: int, placements: list) -> None:
        geom, serve = self.geom, self.serve
        g = serve.prefill_batch
        cl = serve.prefill_chunk or bucket
        n_chunks = -(-bucket // cl)
        job = _PrefillJob(
            bucket=bucket, chunk_len=cl, n_chunks=n_chunks,
            next_chunk=n_chunks - 1, end_chunk=0,
            reqs=[None] * g, slot_ids=[None] * g,
            tokens=np.zeros((g, n_chunks * cl), np.int32),
            lens=np.ones((g,), np.int32),
            write_from=np.zeros((g,), np.int32),
            group_bt=np.full((g, geom.pages_per_slot), -1, np.int32),
            pages=[[] for _ in range(g)], shared=[0] * g,
            logit_chunk=[0] * g, first_token=[None] * g,
            poisoned=[False] * g,
            started_step=self.step_no)
        copies = []
        for (r, slot, row, pages, nsh, cow_src, wf) in placements:
            length = len(r.prompt)
            job.reqs[row], job.slot_ids[row] = r, slot
            job.tokens[row, :length] = r.prompt
            job.lens[row] = length
            job.write_from[row] = wf
            job.group_bt[row, :len(pages)] = pages
            job.pages[row], job.shared[row] = pages, nsh
            if cow_src is not None:
                # boundary page: device-copy donor -> first fresh page
                copies.append((cow_src, pages[nsh]))
                self.cow_copies += 1
            if wf:
                self.prefix_hits += 1
                self.shared_pages_reused += nsh
            lc = (length - 1) // cl
            job.logit_chunk[row] = lc
            job.end_chunk = max(job.end_chunk, lc)
            # a fully-shared prefix still owes the logits chunk (min)
            job.next_chunk = min(job.next_chunk, min(wf // cl, lc))
        if copies:
            self._cow_device_copy(copies)
        for (_, _, _, _, _, cow_src, _) in placements:
            if cow_src is not None:
                self.allocator.release([cow_src])   # admission's temp retain
        self._job = job

    def _cow_device_copy(self, copies: list[tuple[int, int]]) -> None:
        """Copy donor boundary pages into fresh ones across every layer's
        K/V pool (page dim is axis 1: (periods, pages, Hkv, page, hd)).
        src and dst always share a partition, so the copy is shard-local."""
        src = jnp.asarray([s for s, _ in copies])
        dst = jnp.asarray([d for _, d in copies])
        blocks = jax.tree.map(lambda x: x.at[:, dst].set(x[:, src]),
                              self.cache["blocks"])
        self.cache = self._recommit_cache({**self.cache, "blocks": blocks})

    def _prefill_chunk_step(self, t: StepTimer) -> None:
        """Run the job's next chunk; the live cache's block-table rows stay
        at the -1 sentinel until ``_finish_prefill_job`` commits, so decode
        ticks interleaved between chunks cannot touch half-built pages."""
        job = self._job
        c = job.next_chunk
        c0 = c * job.chunk_len
        with t.phase("engine.prefill.inputs"):
            fn = self._paged_prefill_fn(job.bucket)
            if self._current_fault is not None:
                fn = self._faulted_fn(("paged", job.bucket),
                                      self._current_fault)
            args = (jnp.asarray(job.tokens[:, c0:c0 + job.chunk_len]),
                    jnp.asarray(job.group_bt), jnp.asarray(job.lens),
                    jnp.asarray(c0, jnp.int32), jnp.asarray(job.write_from))
            real = [r is not None for r in job.reqs]
            self.prefill_tokens += int(np.clip(job.lens[real] - c0, 0,
                                               job.chunk_len).sum())
            self.prefill_slot_tokens += job.chunk_len * len(job.reqs)
        with t.phase("engine.dispatch"):
            logits, self.cache = fn(self.params, self.cache, *args)
        with t.phase("engine.sample"):
            finite = self._finite_rows(logits)
            first = self._greedy(logits)
        with t.phase("engine.bookkeeping"):
            for row, r in enumerate(job.reqs):
                if r is not None and job.logit_chunk[row] == c:
                    if not finite[row]:
                        job.poisoned[row] = True
                    job.first_token[row] = int(first[row])
            self.events.append(
                ("prefill_chunk", self.step_no,
                 tuple(r.rid for r in job.reqs if r is not None),
                 c, job.n_chunks))
            job.next_chunk += 1
        if job.next_chunk > job.end_chunk:
            self._finish_prefill_job(t)

    def _finish_prefill_job(self, t: StepTimer) -> None:
        """Last chunk done: commit block-table rows + positions into the
        live cache, open the slots, register prompts for prefix sharing."""
        job, geom = self._job, self.geom
        self._job = None
        # poisoned rows never commit: block-table rows stay -1, their pages
        # go back to the pool, and the request retries or quarantines
        with t.phase("engine.bookkeeping"):
            for i in [i for i, r in enumerate(job.reqs)
                      if r is not None and job.poisoned[i]]:
                self.allocator.release(job.pages[i])
                self._poisoned(job.reqs[i], "prefill_nonfinite")
        rows = [i for i, r in enumerate(job.reqs)
                if r is not None and not job.poisoned[i]]
        if not rows:
            return
        with t.phase("engine.prefill.scatter"):
            idx = np.asarray([job.slot_ids[i] for i in rows])
            for i in rows:
                self._bt_host[job.slot_ids[i]] = job.group_bt[i]
            self._commit_leaf("block_tables",
                              self.cache["block_tables"]
                              .at[idx].set(jnp.asarray(job.group_bt[rows])))
            self._commit_leaf("pos", self.cache["pos"]
                              .at[idx].set(jnp.asarray(job.lens[rows])))
        with t.phase("engine.bookkeeping"):
            for i in rows:
                r, slot = job.reqs[i], job.slot_ids[i]
                self._slot_pages[slot] = job.pages[i]
                if self._share_ok:
                    part = geom.slot_partition(slot, self.serve.max_batch)
                    self.prefix.register(
                        part, r.prompt,
                        job.pages[i][:geom.pages_for(len(r.prompt))],
                        ("chunk", job.chunk_len))
                tok = job.first_token[i]
                self.slots[slot] = _Slot(
                    rid=r.rid, last_token=tok, remaining=r.max_new_tokens - 1,
                    tokens=[tok], admitted_step=job.started_step,
                    bucket=job.bucket, prompt_len=len(r.prompt))
                self.tokens_generated += 1
                self.events.append(("admit", self.step_no, r.rid, slot,
                                    job.bucket, self._mem_metrics()))
                if self.slots[slot].remaining == 0:
                    self._retire(slot)

    def _prefill_inputs(self, bucket: int, prompts) -> tuple:
        """(tokens, lens) of one prefill group, right-padded to ``bucket``;
        rows past ``prompts`` are inert 1-token pads."""
        g = self.serve.prefill_batch
        tokens = np.zeros((g, bucket), np.int32)
        lens = np.ones((g,), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p
            lens[i] = len(p)
        return jnp.asarray(tokens), jnp.asarray(lens)

    def prefill_logits(self, prompts: Sequence[Sequence[int]],
                       params=None) -> np.ndarray:
        """Last-position logits ``(len(prompts), vocab)`` in f32 from this
        engine's own jitted prefill program for the prompts' bucket. The
        cache it builds is dropped: no slot, queue or counter changes.
        ``params`` (default: the engine's) runs the same program on another
        copy of the weights, e.g. one placed on another backend."""
        if not 1 <= len(prompts) <= self.serve.prefill_batch:
            raise ValueError(f"1..{self.serve.prefill_batch} prompts per "
                             f"prefill group; got {len(prompts)}")
        bucket = self.serve.bucket_for(max(len(p) for p in prompts))
        fn = self._prefill_fn(bucket)
        tokens, lens = self._prefill_inputs(bucket, prompts)
        logits, _ = fn(self.params if params is None else params,
                       self._sharded_zeros(self._prefill_tmpls[bucket]),
                       tokens, lens)
        return np.asarray(logits[:len(prompts), -1, :self.cfg.vocab_size],
                          np.float32)

    def _prefill(self, t: StepTimer, bucket: int, reqs: list[Request],
                 slot_ids: list[int]) -> None:
        with t.phase("engine.prefill.inputs"):
            fn = self._prefill_fn(bucket)
            if self._current_fault is not None:
                fn = self._faulted_fn(("prefill", bucket),
                                      self._current_fault)
            tokens, lens = self._prefill_inputs(bucket,
                                                [r.prompt for r in reqs])
            gcache = self._sharded_zeros(self._prefill_tmpls[bucket])
            self.prefill_tokens += sum(len(r.prompt) for r in reqs)
            self.prefill_slot_tokens += bucket * self.serve.prefill_batch
        with t.phase("engine.dispatch"):
            logits, gcache = fn(self.params, gcache, tokens, lens)
        with t.phase("engine.sample"):
            finite = self._finite_rows(logits)
            first = self._greedy(logits)
        # only finite rows scatter into the live cache and open slots —
        # poisoned rows retry or quarantine, and because every slot's cache
        # row is independent the survivors' tokens are unaffected
        ok = [i for i in range(len(reqs)) if finite[i]]
        bad = [i for i in range(len(reqs)) if not finite[i]]
        if ok:
            idx = np.asarray([slot_ids[i] for i in ok])
            rows = np.asarray(ok)

            def scatter(dst, src):
                if dst.ndim == 1:                # pos: batch is dim 0
                    return dst.at[idx].set(src[rows])
                return dst.at[:, idx].set(src[:, rows])

            with t.phase("engine.prefill.scatter"):
                self.cache = self._recommit_cache(
                    jax.tree.map(scatter, self.cache, gcache))
        with t.phase("engine.bookkeeping"):
            for i in ok:
                r, slot = reqs[i], slot_ids[i]
                self.slots[slot] = _Slot(
                    rid=r.rid, last_token=int(first[i]),
                    remaining=r.max_new_tokens - 1,
                    tokens=[int(first[i])], admitted_step=self.step_no,
                    bucket=bucket, prompt_len=len(r.prompt))
                self.events.append(("admit", self.step_no, r.rid, slot,
                                    bucket, self._mem_metrics()))
                self.tokens_generated += 1
                if self.slots[slot].remaining == 0:
                    self._retire(slot)
            for i in bad:
                self._poisoned(reqs[i], "prefill_nonfinite")

    def _retire(self, slot: int) -> None:
        s = self.slots[slot]
        self._requests.pop(s.rid, None)
        self.completions[s.rid] = Completion(
            rid=s.rid, prompt_len=s.prompt_len, bucket=s.bucket,
            tokens=list(s.tokens), admitted_step=s.admitted_step,
            finished_step=self.step_no, slot=slot)
        self.slots[slot] = None
        if self.paged:
            # unmap BEFORE releasing: a freed page may be re-allocated next
            # step, and this slot keeps decoding inertly (writes must hit
            # the -1 sentinel and drop, never a recycled page)
            self._bt_host[slot] = -1
            self._commit_leaf("block_tables",
                              self.cache["block_tables"].at[slot].set(-1))
            self.allocator.release(self._slot_pages[slot] or [])
            self._slot_pages[slot] = None
        self.events.append(("retire", self.step_no, s.rid, slot,
                            self._mem_metrics()))

    def _decode_tick(self, t: StepTimer) -> None:
        with t.phase("engine.decode.inputs"):
            tokens = np.zeros((self.serve.max_batch, 1), np.int32)
            for i, s in enumerate(self.slots):
                if s is not None:
                    tokens[i, 0] = s.last_token
            fn = self._decode_fn
            if self._current_fault is not None:
                fn = self._faulted_fn(("decode", 0), self._current_fault)
            tokens = jnp.asarray(tokens)
        with t.phase("engine.dispatch"):
            logits, self.cache = fn(self.params, self.cache, tokens)
        with t.phase("engine.sample"):
            finite = self._finite_rows(logits)
            nxt = self._greedy(logits)
        with t.phase("engine.bookkeeping"):
            for i, s in enumerate(self.slots):
                if s is None:
                    continue
                if not finite[i]:
                    # mid-decode poison: the slot's cache row may hold
                    # corrupted K/V, so quarantine directly — no retry,
                    # since replaying the partial generation cannot be
                    # trusted from poisoned state
                    self.quarantined[s.rid] = {"prompt_len": s.prompt_len,
                                               "step": self.step_no,
                                               "reason": "decode_nonfinite"}
                    self.events.append(("quarantine", self.step_no, s.rid))
                    self._evict_slot(i)
                    continue
                s.last_token = int(nxt[i])
                s.tokens.append(s.last_token)
                s.remaining -= 1
                self.tokens_generated += 1
                if s.remaining == 0:
                    self._retire(i)

    def _schedule(self) -> tuple[str | None, Any]:
        """(kind, group) of the step about to run: ``("prefill", group)``
        (a paged group is None while a chunked job is in flight),
        ``("decode", None)``, ``("idle", None)`` while every queued request
        backs off after a retry, or ``(None, None)`` with nothing pending.

        Paged mode runs ONE prefill chunk per prefill step and alternates
        with decode ticks while a job is in flight (chunk, decode, chunk,
        ...), so decode latency is bounded by a chunk — the whole point of
        chunked prefill. Pool exhaustion shows up here as "no group" with a
        non-empty queue: the step decodes instead, draining pages."""
        active = any(s is not None for s in self.slots)
        if not self.paged:
            group = self._next_group()
            if group is not None:
                return "prefill", group
            if active:
                return "decode", None
            return ("idle" if self.queue and not self.draining
                    else None), None
        group = self._next_group_paged()
        if group is not None:
            return "prefill", group
        if self._job is not None and not (
                active and self.step_kinds
                and self.step_kinds[-1] == "prefill"):
            return "prefill", None
        if self._job is not None or active:
            return "decode", None
        if self.queue and not self.draining:
            if any(self._not_before.get(r.rid, 0) <= self.step_no
                   for r in self.queue):
                raise RuntimeError(
                    "paged admission deadlock: queue non-empty but "
                    "no slots/pages can ever free (pool undersized?)")
            return "idle", None
        return None, None

    def step(self) -> str | None:
        """One engine step: a bucket prefill when admission is possible,
        else a decode tick over the pool. Returns the step kind, or None
        when fully idle.

        The step is timed in phases (``StepTimer.phase``) that tile it, on
        both cache layouts: ``engine.schedule``, ``engine.prefill.inputs``,
        ``engine.decode.inputs``, ``engine.dispatch`` (the jitted call up to
        its return), ``engine.sample`` (where the host waits on the
        device), ``engine.prefill.scatter`` and ``engine.bookkeeping``.
        ``last_phases`` holds the split of the step just taken,
        ``stats()["phase_s"]`` the sums; a profiler trace shows the same
        names inside an ``engine.step`` span."""
        t = StepTimer()
        with TraceAnnotation("engine.step"), t:
            with t.phase("engine.schedule"):
                self._fire_comm_faults()
                self._expire_deadlines()
                kind, group = self._schedule()
            if kind is None:
                return None
            if kind == "prefill" and self.paged:
                if group is not None:
                    with t.phase("engine.prefill.inputs"):
                        self._start_prefill_job(*group)
                self._prefill_chunk_step(t)
            elif kind == "prefill":
                self._prefill(t, *group)
            elif kind == "decode":
                self._decode_tick(t)
            with t.phase("engine.bookkeeping"):
                # an idle tick records no time: nothing ran
                self._record_step(kind,
                                  t.elapsed() if kind != "idle" else 0.0)
        self.last_phases = t.phases
        for name, sec in t.phases.items():
            self.phase_s[name] = self.phase_s.get(name, 0.0) + sec
        return kind

    def _record_step(self, kind: str, dt: float) -> str:
        """Shared step accounting: injected fault delay folds into the
        recorded time (watchdog + fleet feed see it; no wall-clock sleep),
        scripted link stalls attribute their synthetic hop time to the
        targeted island's health feed, boundary-guard trips drain into the
        event log, and the health monitor's verdicts re-layer the plans."""
        dt += self._injected_delay
        self._injected_delay = 0.0
        # scripted stall: synthetic per-hop time, only while the island's
        # CURRENT backend still rides the slow link (post-demotion steps
        # route around it — the throughput recovery the monitor buys)
        stall: dict[str, float] = {}
        if kind in ("prefill", "decode"):
            for f in self._active_faults:
                if f["kind"] == "stall" and \
                        self._stall_applies(f["island"], kind):
                    stall[f["island"]] = (stall.get(f["island"], 0.0)
                                          + f["stall_dt"])
        dt += sum(stall.values())
        self.step_no += 1
        self.step_kinds.append(kind)
        self.step_times.append(dt)
        if kind != "idle" and self.watchdog.record(self.step_no, dt):
            print(f"[serve] STRAGGLER step {self.step_no} ({kind}): "
                  f"{dt:.3f}s (deadline {self.watchdog.deadline:.3f}s)")
        # island boundary guards that tripped during this step's device work
        changed = False
        for island, n in sorted(take_guard_trips().items()):
            self.events.append(("guard_trip", self.step_no, island, n))
            if self.health is not None:
                changed |= self.health.guard_trip(island, self.step_no)
        # per-island health feed: shared base time + this island's stall cut
        if self.health is not None and kind in ("prefill", "decode"):
            base = dt - sum(stall.values())
            for island in self.health.islands:
                changed |= self.health.record(
                    island, self.step_no, base + stall.get(island, 0.0))
        if changed:
            self._refresh_health_overrides()
        self._drain_health_events()
        self._tick_comm_faults()
        return kind

    def run(self, requests=None, max_steps: int = 100_000,
            step_budget: int | None = None) -> list[Completion]:
        """Drain the queue (plus ``requests``, submitted first) to
        completion; returns the completions finished during THIS call, in
        submission (rid) order. ``self.completions`` keeps the full
        history across calls.

        ``step_budget`` makes the call cooperative: run at most that many
        engine steps and return whatever finished, leaving the rest pending
        — an external driver (the fleet) interleaves replicas by calling
        each with a small budget in a deterministic rotation. A budgeted
        call never raises on an undrained queue."""
        done_before = set(self.completions)
        for r in requests or ():
            if isinstance(r, Request):
                self.submit(r.prompt, r.max_new_tokens, rid=r.rid)
            else:
                self.submit(r)
        limit = max_steps if step_budget is None else min(max_steps,
                                                          step_budget)
        drained = False
        for _ in range(limit):
            if self.step() is None:
                drained = True
                break
        if not drained and step_budget is None:
            raise RuntimeError(f"engine did not drain in {max_steps} steps")
        return [self.completions[k] for k in sorted(self.completions)
                if k not in done_before]

    # -- static baseline + stats ------------------------------------------

    def _static_step_fns(self, n: int, bucket: int) -> tuple:
        """Jitted (prefill, decode, cache template) for a static batch of
        ``n`` at ``bucket`` — cached so repeated static runs (warm-up then
        timing, the bench harness) hit jax's trace cache instead of
        recompiling fresh wrappers every call."""
        key = (n, bucket)
        if key not in self._static_fns:
            run = self.base_run
            pre_plans = tuple(island_plans(self.cfg, run, self.rules,
                                           batch=n, seq=bucket,
                                           phase="prefill"))
            dec_plans = tuple(island_plans(self.cfg, run, self.rules,
                                           batch=n, seq=self.s_max,
                                           phase="decode"))
            run_pre = dataclasses.replace(
                run, island_overrides=plan_overrides(pre_plans))
            run_dec = dataclasses.replace(
                run, island_overrides=plan_overrides(dec_plans))
            tmpl = T.cache_template(self.cfg, run_dec, self.rules, batch=n,
                                    s_max=self.s_max, slot_pos=True,
                                    kv_dtype=self.serve.kv_dtype)
            self._static_fns[key] = (
                jax.jit(make_prefill_cache_step(self.cfg, run_pre,
                                                self.rules),
                        donate_argnums=(1,)),
                jax.jit(make_serve_step(self.cfg, run_dec, self.rules),
                        donate_argnums=(1,)),
                tmpl)
        return self._static_fns[key]

    def generate_static(self, prompts: Sequence[Sequence[int]],
                        max_new_tokens: int | None = None) -> list[list[int]]:
        """Static-batch baseline: every prompt padded to ONE bucket,
        prefilled as a single batch, decoded in lockstep until the longest
        request finishes (shorter ones over-decode and are trimmed) — the
        throughput bar continuous batching is measured against. Uses the
        same prefill/decode math and greedy rule as the engine."""
        mx = max_new_tokens if max_new_tokens is not None \
            else self.serve.max_new_tokens
        if not 1 <= mx <= self.serve.max_new_tokens:
            raise ValueError(
                f"max_new_tokens must be in [1, "
                f"{self.serve.max_new_tokens}] (ServeConfig sized the "
                f"cache); got {mx}")
        n = len(prompts)
        bucket = self.serve.bucket_for(max(len(p) for p in prompts))
        if any(sp.mixer == "mamba" for sp in self.cfg.layer_pattern()) \
                and any(len(p) != bucket for p in prompts):
            # same invariant the engine's __init__ guard protects: the SSM
            # recurrent state scans right-padding it cannot mask
            raise ValueError(
                "static SSM batches require uniform prompt lengths equal "
                f"to the bucket ({bucket}); got "
                f"{sorted({len(p) for p in prompts})}")
        prefill, decode, tmpl = self._static_step_fns(n, bucket)
        cache = self._sharded_zeros(tmpl)
        tokens = np.zeros((n, bucket), np.int32)
        lens = np.zeros((n,), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = list(p)
            lens[i] = len(p)
        logits, cache = prefill(self.params, cache, jnp.asarray(tokens),
                                jnp.asarray(lens))
        last = self._greedy(logits)
        out = [[int(t)] for t in last]
        for _ in range(mx - 1):
            logits, cache = decode(self.params, cache,
                                   jnp.asarray(last[:, None]))
            last = self._greedy(logits)
            for i in range(n):
                out[i].append(int(last[i]))
        return [seq[:mx] for seq in out]

    def cache_stats(self) -> dict:
        """Cache-memory story: layout, pool bytes vs the slab equivalent,
        residency peaks, prefix-sharing and backpressure counters."""
        slab = paging.slab_hbm_bytes(self.cfg, self.serve.max_batch,
                                     self.s_max,
                                     kv_dtype=self.serve.kv_dtype)
        out: dict[str, Any] = {
            "layout": self.serve.cache_layout,
            "kv_dtype": self.serve.kv_dtype,
            "peak_resident_slots": self._peak_slots,
            "slab_bytes": slab,
        }
        if not self.paged:
            out["hbm_bytes"] = slab
            return out
        g = self.geom
        out.update({
            "hbm_bytes": paging.pool_hbm_bytes(self.cfg, g,
                                               kv_dtype=self.serve.kv_dtype),
            "page_size": g.page_size, "n_pages": g.n_pages,
            "pages_per_slot": g.pages_per_slot,
            "n_partitions": g.n_partitions,
            "resident_pages": self.allocator.resident_pages,
            "peak_resident_pages": self._peak_pages,
            "peak_pool_occupancy": self._peak_pages / g.n_pages,
            "prefix_hits": self.prefix_hits,
            "shared_pages_reused": self.shared_pages_reused,
            "cow_copies": self.cow_copies,
            "admission_blocked": self.admission_blocked,
        })
        return out

    def stats(self) -> dict:
        total = sum(self.step_times)
        return {
            "steps": self.step_no,
            "prefill_steps": self.step_kinds.count("prefill"),
            "decode_steps": self.step_kinds.count("decode"),
            "idle_steps": self.step_kinds.count("idle"),
            "tokens_generated": self.tokens_generated,
            "wall_s": total,
            "tokens_per_s": self.tokens_generated / total if total else 0.0,
            "phase_s": dict(self.phase_s),
            "straggler_events": len(self.watchdog.events),
            "compiled_buckets": self.compiled_buckets,
            "cache": self.cache_stats(),
            "quarantined": len(self.quarantined),
            "expired": len(self.expired),
            "retries": sum(self._retries.values()),
            "guard_trips": sum(e[0] == "guard_trip" for e in self.events),
            "health_demotions": (
                sum(e[0] == "demote" for e in self.health.events)
                if self.health is not None else 0),
        }
