"""Empirical autotuner: measured correction factors for the §3.1.1 cost model.

The paper's thesis is that the analytic decomposition

    T_kernel = T_launch + max(T_comp, T_mem, T_comm) + T_non_overlap + T_sync

plus a runtime resource-split search picks the optimal overlap schedule.
``core/costmodel.py`` supplies the analytic half from datasheet constants;
this module closes the loop the way chunk-centric autotuners do (Syncopate,
arXiv 2601.20595): it **micro-benchmarks every registered comm backend on the
live mesh**, fits the measurements back into per-``HardwareSpec`` correction
factors, and persists them as a versioned JSON *calibration table* that
``CommContext(policy="measured")`` dispatches from.

Three layers:

``calibrate(mesh=...)``
    Runs the micro-benchmarks (link latency/bandwidth sweep, local GEMM
    efficiency probe, per-op × per-backend × shape-grid timings) and returns
    a ``CalibrationTable``.
``CalibrationTable``
    The persisted artifact: a ``Fingerprint`` of the machine it was measured
    on, fitted ``corrections`` (achieved ICI bandwidth, real
    ``remote_sync_s``, sustained GEMM efficiency, launch overhead) and the
    raw per-shape ``measurements``. ``table.spec(hw)`` yields a corrected
    ``HardwareSpec`` for the analytic model; ``table.best_backend(...)``
    answers dispatch queries directly from the measurements.
``find_table(hw_name)``
    Resolution used by ``CommContext``: the user cache
    (``~/.cache/repro/autotune-<hw>-<jax>.json``) first, then the in-repo
    seed tables under ``core/calibrations/`` (``tpu_v5e`` analytic seed,
    ``cpu_emulated`` measured on the 8-device emulated mesh). Tables whose
    fingerprint does not match the live process are ignored — the measured
    policy then degrades to analytic instead of dispatching from someone
    else's machine.

CLI (``python -m repro.autotune``): ``calibrate`` / ``show`` / ``diff``.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import time
import warnings
from pathlib import Path
from typing import Any, Sequence

SCHEMA = "repro-autotune/v1"
SCHEMA_VERSION = 1


def island_key(name: str, op: str, dtype_bytes: int = 2) -> str:
    """Stable calibration-row key for one island's declared collective.

    Derived from the island's name plus the ``Comm`` coordinates that change
    which backend wins (op kind and element width — the layout-bearing
    parts); NOT from (m, n, k), which stays a lookup coordinate so nearby
    shapes can share rows. Two islands with different layouts/dtypes at the
    same (m, n, k) get different keys and can dispatch differently.
    """
    return f"{name}|{op}|b{int(dtype_bytes)}"


@dataclasses.dataclass(frozen=True)
class IslandSweep:
    """One island's coordinates for the ``calibrate(islands=...)`` sweep:
    the exact (op, m, n, k, dtype) its ``CommContext`` dispatch queries
    with, plus the key the measured rows are tagged with.

    GEMM×collective islands carry the global GEMM (m, n, k). ``all_to_all``
    islands (Ulysses re-sharding, MoE dispatch) additionally carry the local
    payload ``shape`` and split/concat axes; their (m, n, k) follow the
    ``CommContext.a2a_coords`` convention (payload elements, split extent,
    concat extent) so the sweep's rows land exactly where the a2a chunk
    policy queries."""

    island: str            # island_key(...) the rows carry
    op: str                # a GEMM_OPS member or "all_to_all"
    m: int
    n: int
    k: int
    dtype_bytes: int = 2
    shape: tuple[int, ...] | None = None    # a2a local payload shape
    split_axis: int | None = None
    concat_axis: int | None = None

#: ops the calibrator sweeps; mirrors comms.OP_BACKENDS keys it can measure.
GEMM_OPS = ("all_gather_matmul", "matmul_reduce_scatter", "matmul_all_reduce")
DEFAULT_OPS = GEMM_OPS + ("psum",)

#: per-device square sizes for the GEMM-op grid. "tiny" keeps test runtime
#: in check on the emulated mesh; "small" is the CLI default there; "full"
#: is sized for a real TPU slice.
GRIDS: dict[str, tuple[int, ...]] = {
    "tiny": (128,),
    "small": (128, 256, 512),
    "full": (256, 512, 1024, 2048, 4096),
}

_SEED_DIR = Path(__file__).parent / "calibrations"


def cache_dir() -> Path:
    env = os.environ.get("REPRO_CACHE_DIR")
    if env:
        return Path(env).expanduser()
    return Path("~/.cache/repro").expanduser()


# ---------------------------------------------------------------------------
# Fingerprint
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Fingerprint:
    """Identity of (spec being corrected, software, devices) for one table."""

    hw: str             # HardwareSpec.name the corrections apply to
    jax_version: str
    backend: str        # jax.default_backend() at measurement time
    device_kind: str
    n_devices: int

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Fingerprint":
        return cls(hw=d["hw"], jax_version=d["jax_version"],
                   backend=d["backend"], device_kind=d["device_kind"],
                   n_devices=int(d["n_devices"]))

    @staticmethod
    def _jax_mm(v: str) -> str:
        return ".".join(v.split(".")[:2])

    def compatible(self, live: "Fingerprint", *, strict: bool = False) -> bool:
        """Can a table stamped `self` serve a process that looks like `live`?

        Non-strict (the dispatch default) requires the same corrected spec,
        jax backend, device kind and jax major.minor — the quantities the
        corrections actually depend on. Strict additionally pins the exact
        jax version and device count (used by ``diff`` to refuse
        apples-to-oranges comparisons).
        """
        base = (self.hw == live.hw and self.backend == live.backend
                and self.device_kind == live.device_kind
                and self._jax_mm(self.jax_version)
                == self._jax_mm(live.jax_version))
        if not strict:
            return base
        return (base and self.jax_version == live.jax_version
                and self.n_devices == live.n_devices)


def live_fingerprint(hw_name: str, mesh=None) -> Fingerprint:
    """Fingerprint of the current process (and optionally one mesh)."""
    import jax

    from repro.launch.mesh import device_fingerprint

    d = device_fingerprint(mesh)
    return Fingerprint(hw=hw_name, jax_version=jax.__version__,
                       backend=d["backend"], device_kind=d["device_kind"],
                       n_devices=d["n_devices"])


def cache_path(fp: Fingerprint) -> Path:
    """``~/.cache/repro/autotune-<hw>-<jax>.json`` for this fingerprint."""
    return cache_dir() / f"autotune-{fp.hw}-{fp.jax_version}.json"


# ---------------------------------------------------------------------------
# CalibrationTable
# ---------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)
class CalibrationTable:
    """Measured corrections + raw micro-benchmark rows for one machine.

    ``corrections`` maps ``HardwareSpec`` field names to fitted values
    (subset of: ``ici_bandwidth``, ``remote_sync_s``, ``gemm_efficiency``,
    ``kernel_launch_s``). ``measurements`` rows are
    ``{op, backend, axis_size, m, n, k, us}`` with (m, n, k) the *global*
    GEMM shape — the same coordinates ``CommContext.auto_gemm_backend``
    receives, so dispatch lookups need no shape translation.
    """

    fingerprint: Fingerprint
    corrections: dict[str, float]
    measurements: list[dict] = dataclasses.field(default_factory=list)
    version: int = SCHEMA_VERSION
    created: str = ""
    notes: str = ""

    # -- persistence -------------------------------------------------------

    def to_json(self) -> dict:
        return {
            "schema": SCHEMA,
            "version": self.version,
            "created": self.created,
            "notes": self.notes,
            "fingerprint": self.fingerprint.to_dict(),
            "corrections": dict(self.corrections),
            "measurements": list(self.measurements),
        }

    @classmethod
    def from_json(cls, doc: dict) -> "CalibrationTable":
        if doc.get("schema") != SCHEMA:
            raise ValueError(
                f"not a {SCHEMA} document (schema={doc.get('schema')!r})")
        return cls(fingerprint=Fingerprint.from_dict(doc["fingerprint"]),
                   corrections={k: float(v)
                                for k, v in doc["corrections"].items()},
                   measurements=list(doc.get("measurements", [])),
                   version=int(doc.get("version", SCHEMA_VERSION)),
                   created=doc.get("created", ""),
                   notes=doc.get("notes", ""))

    def save(self, path: str | Path) -> Path:
        path = Path(path).expanduser()
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.to_json(), indent=1, sort_keys=True))
        return path

    @classmethod
    def load(cls, path: str | Path) -> "CalibrationTable":
        return cls.from_json(json.loads(Path(path).expanduser().read_text()))

    # -- consumption -------------------------------------------------------

    def spec(self, base):
        """`base` HardwareSpec with this table's corrections applied."""
        return base.calibrated(**self.corrections)

    @staticmethod
    def _log_dist(m: int, n: int, k: int, row_m, row_n, row_k) -> float:
        return max(abs(math.log(max(m, 1) / max(row_m, 1))),
                   abs(math.log(max(n, 1) / max(row_n, 1))),
                   abs(math.log(max(k, 1) / max(row_k, 1))))

    def _rows_for(self, op: str, *, island: str | None,
                  axis_size: int | None, dtype_bytes: int | None,
                  island_only: bool = False):
        """Measurement rows of `op` usable for a dispatch query, in island
        precedence order: rows tagged with the caller's island key first,
        then the global (untagged) rows as a fallback tier. Rows tagged with
        a *different* island never match — another island's layout is not
        evidence about this one. Yields at most two non-empty tiers;
        ``island_only`` drops the global fallback tier (callers that must
        not mix measurements across tiers, e.g. a hidden-fraction delta)."""
        tiers: list[list[dict]] = [[], []]
        for row in self.measurements:
            if row["op"] != op:
                continue
            if axis_size is not None and row["axis_size"] != axis_size:
                continue
            if dtype_bytes is not None:
                row_b = row.get("dtype_bytes")
                if row_b is None:
                    # Legacy rows predate the dtype axis. They were measured
                    # on full-precision payloads, so they may serve any
                    # full-precision query — but never a quantized-wire
                    # (sub-2-byte) one: a b1 lookup resolving onto a
                    # bf16-era measurement would report the unquantized
                    # ring's time as the int8 ring's.
                    if dtype_bytes < 2:
                        continue
                elif row_b != dtype_bytes:
                    continue
            tag = row.get("island")
            if tag is None:
                tiers[1].append(row)
            elif island is not None and tag == island:
                tiers[0].append(row)
        if island_only and island is not None:
            tiers = tiers[:1]
        return [t for t in tiers if t]

    def _argmin_at_nearest(self, tier, m: int, n: int, k: int,
                           key_of_row, max_ratio: float):
        """argmin of ``us`` over ``key_of_row(row)`` groups at the single
        nearest (m, n, k) grid point where >= 2 distinct keys were measured
        (one key is not a comparison), or None past ``max_ratio`` log
        distance. Shared by ``best_backend`` (key = backend) and
        ``best_chunks`` (key = n_chunks) so their distance/tie-break/
        extrapolation rules can never diverge."""
        pts: dict[tuple, dict] = {}
        for row in tier:
            key = key_of_row(row)
            if key is None:
                continue
            point = (row["m"], row["n"], row["k"])
            timed = pts.setdefault(point, {})
            timed[key] = min(timed.get(key, math.inf), float(row["us"]))
        best_point, best_d = None, math.inf
        for point, timed in pts.items():
            if len(timed) < 2:
                continue
            d = self._log_dist(m, n, k, *point)
            if d < best_d:
                best_point, best_d = point, d
        if best_point is None or best_d > math.log(max_ratio):
            return None
        timed = pts[best_point]
        return min(timed, key=timed.get)

    def measured_us(self, op: str, backend: str, m: int, n: int, k: int,
                    *, axis_size: int | None = None,
                    dtype_bytes: int | None = None,
                    island: str | None = None, island_only: bool = False,
                    max_ratio: float = 4.0) -> float | None:
        """Interpolated measurement for (op, backend) at the nearest grid
        point, or None when the closest point is further than ``max_ratio``
        away in every-dimension log distance (extrapolating a microbench
        across >4x in shape is how analytic models go wrong in the first
        place — refuse, and let the caller fall back to analytic).

        ``dtype_bytes`` filters to rows measured at that element width: a
        bf16 ring's measured win (half the bytes of an f32-promoted bulk
        collective) does not transfer to an f32 payload. Rows without a
        recorded dtype (older tables) match any full-precision width but
        never a quantized-wire query (``dtype_bytes < 2``). ``island`` prefers
        rows calibrated for that island key (``calibrate --per-island``)
        and falls back to the global rows (``island_only`` disables the
        fallback).
        """
        for tier in self._rows_for(op, island=island, axis_size=axis_size,
                                   dtype_bytes=dtype_bytes,
                                   island_only=island_only):
            best, best_d = None, math.inf
            for row in tier:
                if row["backend"] != backend:
                    continue
                d = self._log_dist(m, n, k, row["m"], row["n"], row["k"])
                # equal-distance tie (e.g. chunk-count variants at one grid
                # point): the backend's best configuration represents it
                if d < best_d or (best is not None and d == best_d
                                  and float(row["us"]) < float(best["us"])):
                    best, best_d = row, d
            if best is not None and best_d <= math.log(max_ratio):
                return float(best["us"])
        return None

    def best_backend(self, op: str, m: int, n: int, k: int, *,
                     allowed: Sequence[str],
                     axis_size: int | None = None,
                     dtype_bytes: int | None = None,
                     island: str | None = None,
                     max_ratio: float = 4.0) -> str | None:
        """argmin over measured backends of `op` near (m, n, k), restricted
        to `allowed` (the caller's shape/VMEM-feasible set).

        Backends are only compared **at a single shared grid point** — the
        nearest (m, n, k) where at least two allowed backends were measured.
        Comparing each backend's own nearest point would let a backend the
        sweep only captured at a much smaller shape "win" on shape size
        rather than speed (a skipped grid point would then pin the slower
        backend). ``island`` restricts the comparison to that island's
        calibrated rows first (two islands with different layouts can then
        resolve differently at the same shape), falling back to the global
        rows. None when no shared point is within ``max_ratio`` log
        distance — the caller falls back to the analytic policy."""
        for tier in self._rows_for(op, island=island, axis_size=axis_size,
                                   dtype_bytes=dtype_bytes):
            best = self._argmin_at_nearest(
                tier, m, n, k,
                lambda r: r["backend"] if r["backend"] in allowed else None,
                max_ratio)
            if best is not None:
                return best
        return None

    def best_chunks(self, op: str, backend: str, m: int, n: int, k: int, *,
                    axis_size: int | None = None,
                    dtype_bytes: int | None = None,
                    island: str | None = None,
                    max_ratio: float = 4.0) -> int | None:
        """Measured sub-chunk count for a chunk-pipelined ring: the argmin-us
        ``n_chunks`` among this (op, backend)'s rows at the nearest grid
        point where at least two distinct chunk counts were measured (one
        count is not a comparison — return None and let the analytic chunk
        scheduler decide). Same island-first/global-fallback precedence as
        ``best_backend``."""
        for tier in self._rows_for(op, island=island, axis_size=axis_size,
                                   dtype_bytes=dtype_bytes):
            best = self._argmin_at_nearest(
                tier, m, n, k,
                lambda r: (int(r.get("n_chunks", 1) or 1)
                           if r["backend"] == backend else None),
                max_ratio)
            if best is not None:
                return best
        return None

    def ops_covered(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for row in self.measurements:
            out[row["op"]] = out.get(row["op"], 0) + 1
        return out


# ---------------------------------------------------------------------------
# Table resolution (cache -> in-repo seeds), used by CommContext.
# ---------------------------------------------------------------------------

_warned: set[str] = set()


def _warn_once(key: str, msg: str) -> None:
    if key not in _warned:
        _warned.add(key)
        warnings.warn(msg, stacklevel=3)


def _candidate_paths(fp: Fingerprint) -> list[Path]:
    paths = [cache_path(fp)]
    if _SEED_DIR.is_dir():
        paths.extend(sorted(_SEED_DIR.glob("*.json")))
    return paths


def find_table(hw_name: str) -> CalibrationTable | None:
    """The calibration table ``policy="measured"|"auto"`` dispatches from.

    Search order: the user cache for this (hw, jax) pair, then the checked-in
    seed tables. The first table whose fingerprint is compatible with the
    live process wins; incompatible and unreadable tables are skipped (the
    latter with a one-shot warning naming the file).
    """
    live = live_fingerprint(hw_name)
    for path in _candidate_paths(live):
        if not path.is_file():
            continue
        try:
            table = CalibrationTable.load(path)
        except (ValueError, KeyError, json.JSONDecodeError) as e:
            _warn_once(f"unreadable:{path}",
                       f"ignoring unreadable calibration table {path}: {e}")
            continue
        if table.fingerprint.compatible(live):
            return table
    return None


def clear_caches() -> None:
    """Reset memoized lookups (tests; after writing a new cache table)."""
    _load_cached.cache_clear()
    _find_cached.cache_clear()
    _live_cached.cache_clear()
    _warned.clear()


_load_cached = functools.lru_cache(maxsize=32)(CalibrationTable.load)
_find_cached = functools.lru_cache(maxsize=8)(find_table)
# the process-wide fingerprint never changes within a process; memoized so
# every policy-routed collective doesn't re-read jax.devices() at trace time
_live_cached = functools.lru_cache(maxsize=8)(live_fingerprint)


def resolve_table(calibration: Any, hw_name: str,
                  policy: str) -> CalibrationTable | None:
    """Map a ``CommContext`` (policy, calibration) pair to a usable table.

    ``calibration`` may be a ``CalibrationTable``, a path, or None (search
    cache + seeds). Under ``policy="measured"`` a missing or
    fingerprint-mismatched table warns once and returns None — the context
    then runs the analytic policy, which is always available; under
    ``"auto"`` the same fallback is silent by design.
    """
    if policy == "analytic":
        return None
    if policy not in ("measured", "auto"):
        raise ValueError(
            f"unknown comm policy {policy!r}; expected 'analytic', "
            "'measured' or 'auto'")
    table: CalibrationTable | None
    if isinstance(calibration, CalibrationTable):
        table = calibration
    elif calibration is not None:
        try:
            table = _load_cached(str(calibration))
        except (OSError, ValueError, KeyError, json.JSONDecodeError) as e:
            # an EXPLICITLY configured path that doesn't load warns under
            # "auto" too — auto's silence covers implicit search misses,
            # not a broken user-supplied argument
            _warn_once(f"load:{calibration}",
                       f"calibration table {calibration!r} could not be "
                       f"loaded ({e}); falling back to analytic costs")
            return None
    else:
        table = _find_cached(hw_name)
        if table is None:
            if policy == "measured":
                _warn_once(f"missing:{hw_name}",
                           "policy='measured' but no calibration table found "
                           f"for hw={hw_name!r} (searched "
                           f"{cache_path(live_fingerprint(hw_name))} and "
                           f"{_SEED_DIR}); run `python -m repro.autotune "
                           "calibrate`; falling back to analytic costs")
            return None
    live = _live_cached(hw_name)
    if not table.fingerprint.compatible(live):
        # like the unreadable-path case above: an explicitly supplied table
        # that gets rejected warns under "auto" too — only tables found by
        # the implicit cache/seed search are silently skipped there
        if policy == "measured" or calibration is not None:
            _warn_once(f"fingerprint:{hw_name}:{table.fingerprint}",
                       f"calibration table fingerprint {table.fingerprint} "
                       f"does not match this process {live}; falling back "
                       "to analytic costs")
        return None
    if policy == "measured":
        # fingerprints match, but measurements can rot in place (driver or
        # thermal changes the fingerprint cannot see): surface age once —
        # the table is still USED, staleness is a warning, not a rejection
        age = table_age_days(table)
        if age is not None and age > STALE_AFTER_DAYS:
            _warn_once(f"stale:{hw_name}",
                       f"calibration table for hw={hw_name!r} is "
                       f"{age:.0f} days old (> {STALE_AFTER_DAYS:.0f}); "
                       "its measurements may no longer reflect this machine "
                       "— re-run `python -m repro.autotune calibrate`, or "
                       "audit with `python -m repro.autotune check`")
    return table


# ---------------------------------------------------------------------------
# Staleness audit (the `python -m repro.autotune check` backend)
# ---------------------------------------------------------------------------

#: age past which a fingerprint-compatible table warns under
#: ``policy="measured"`` (and fails ``repro.autotune check``)
STALE_AFTER_DAYS = 30.0


def table_age_days(table: CalibrationTable) -> float | None:
    """Days since the table's ``created`` stamp; None when unparseable."""
    if not table.created:
        return None
    try:
        t = time.mktime(time.strptime(table.created, "%Y-%m-%dT%H:%M:%S"))
    except ValueError:
        return None
    return max(0.0, (time.time() - t) / 86400.0)


def staleness(table: CalibrationTable, *,
              max_age_days: float = STALE_AFTER_DAYS,
              drift_threshold: float = 0.5, probe: bool = True,
              reps: int = 3) -> list[str]:
    """Reasons this table should be re-calibrated; empty = looks fresh.

    Two independent checks: the ``created`` stamp's age against
    ``max_age_days``, and (with ``probe``) a quick spot re-measurement of
    the machine-local corrections — kernel launch overhead and sustained
    GEMM efficiency — against the table's fitted values at
    ``drift_threshold`` relative drift. The spot probe runs two tiny jitted
    micro-benchmarks, not a re-calibration.
    """
    msgs: list[str] = []
    age = table_age_days(table)
    if age is None:
        msgs.append("table has no parseable 'created' timestamp — age "
                    "cannot be checked")
    elif age > max_age_days:
        msgs.append(f"table is {age:.0f} days old "
                    f"(threshold {max_age_days:.0f})")
    if not probe:
        return msgs
    from repro.core import costmodel as cm

    hw = cm.spec_by_name(table.fingerprint.hw)
    probes = {"kernel_launch_s": _measure_launch(reps),
              "gemm_efficiency": _measure_gemm_efficiency(hw, reps)}
    for key, now in probes.items():
        old = table.corrections.get(key)
        if not old:
            continue
        drift = abs(now - old) / old
        if drift > drift_threshold:
            msgs.append(f"{key} drifted {drift * 100:.0f}% vs spot probe "
                        f"(table {old:.3g}, now {now:.3g}; threshold "
                        f"{drift_threshold * 100:.0f}%)")
    return msgs


# ---------------------------------------------------------------------------
# Micro-benchmarks
# ---------------------------------------------------------------------------


def _timeit(fn, *args, reps: int = 3, warmup: int = 1) -> float:
    """Median seconds per call (same protocol as benchmarks/common.timeit,
    duplicated here so `src/` never imports the benchmarks package)."""
    import jax

    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def _fit_line(xs: Sequence[float], ys: Sequence[float]) -> tuple[float, float]:
    """Least-squares y = a + b*x; returns (a, b)."""
    n = len(xs)
    mx = sum(xs) / n
    my = sum(ys) / n
    sxx = sum((x - mx) ** 2 for x in xs)
    if sxx == 0.0:
        return my, 0.0
    b = sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / sxx
    return my - b * mx, b


def _measure_link(mesh, axis_name: str, reps: int) -> tuple[float, float]:
    """(achieved bytes/s per link-direction, per-hop overhead seconds).

    Times a one-hop ``ppermute`` ring rotation over a payload sweep and fits
    t = overhead + bytes/B. The intercept is everything the analytic model
    books under T_launch + T_sync for one remote hop; the slope is the
    *achieved* link bandwidth, contention included.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro import compat
    from repro.core.comms import ring_shift

    sizes = (2 ** 14, 2 ** 18, 2 ** 22)        # 16 KiB .. 4 MiB per device
    xs, ys = [], []
    for nbytes in sizes:
        n_el = nbytes // 4
        x = jnp.ones((mesh.shape[axis_name], n_el), jnp.float32)
        f = jax.jit(compat.shard_map(
            lambda t: ring_shift(t, axis_name),
            mesh=mesh, in_specs=P(axis_name), out_specs=P(axis_name),
            check_vma=False))
        t = _timeit(f, x, reps=reps)
        xs.append(float(nbytes))
        ys.append(t)
    overhead, inv_bw = _fit_line(xs, ys)
    bw = (1.0 / inv_bw) if inv_bw > 0 else xs[-1] / max(ys[-1], 1e-12)
    return max(bw, 1.0), max(overhead, 1e-9)


def _measure_gemm_efficiency(hw, reps: int) -> float:
    """Sustained local-GEMM fraction of ``hw.peak_flops_bf16``.

    Probed in bf16 — the dtype the peak is quoted for and the calibrated
    ops run in; an f32 probe would understate the MXU severalfold on real
    hardware. On the CPU-emulated mesh the result is far below 1.0 — that
    is the point: the measured policy then prices compute at what the
    machine actually delivers instead of the datasheet number.
    """
    import jax
    import jax.numpy as jnp

    n = 512
    a = jnp.ones((n, n), jnp.bfloat16)
    f = jax.jit(lambda a: a @ a)
    t = _timeit(f, a, reps=reps)
    achieved = 2.0 * n ** 3 / max(t, 1e-12)
    return min(max(achieved / hw.peak_flops_bf16, 1e-9), 1.0)


def _measure_launch(reps: int) -> float:
    """Dispatch overhead of a trivial jitted op (T_launch analogue)."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((8,), jnp.float32)
    f = jax.jit(lambda t: t + 1.0)
    return max(_timeit(f, x, reps=reps), 1e-9)


def _gemm_case(op: str, nsz: int, n_dev: int):
    """(global operand arrays, in_specs, out_specs, (m, n, k)) for one grid
    point of `op`, mirroring the paper-figure shapes in benchmarks/.

    (m, n, k) MUST be the exact coordinates ``CommContext``'s dispatch
    queries with (``auto_gemm_backend``'s arguments): for AG+GEMM that is
    the gathered GEMM's global m (= the sharded array's global rows,
    m_loc * n_dev); for RS/AR it is (global m, n, local k). Rows stored in
    any other coordinate system would never be found by ``measured_us``'s
    4x-log-distance lookup and the measured policy would silently never
    activate (tests pin this coupling).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if op == "all_gather_matmul":
        x = jax.random.normal(jax.random.PRNGKey(0), (nsz, nsz // 4),
                              jnp.bfloat16)
        w = jax.random.normal(jax.random.PRNGKey(1), (nsz // 4, nsz // 4),
                              jnp.bfloat16)
        # sharded rows: m_loc = nsz / n_dev, so dispatch sees m = nsz
        return ((x, w), (P("x"), P()), P(), (nsz, nsz // 4, nsz // 4))
    x = jax.random.normal(jax.random.PRNGKey(0), (nsz, n_dev * (nsz // 8)),
                          jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1),
                          (n_dev * (nsz // 8), nsz // 4), jnp.bfloat16)
    out = (P("x", None) if op == "matmul_reduce_scatter" else P())
    return ((x, w), (P(None, "x"), P("x", None)), out,
            (nsz, nsz // 4, nsz // 8))


def _feasible(op: str, backend: str, n_dev: int, nsz: int,
              available: Sequence[str]) -> bool:
    if backend not in available:
        return False
    if backend == "ring_bidir":
        # the bidirectional ring splits the local shard across the two
        # directions: needs at least 2 local rows (uneven splits are fine)
        # and an even device count — on an odd axis the impl silently runs
        # the unidirectional ring, which would mislabel the measured rows
        return (op == "all_gather_matmul" and n_dev % 2 == 0
                and nsz // n_dev >= 2)
    if backend == "fused":
        # interpret-mode fused kernels are orders of magnitude slower than
        # the thing they emulate; timing them off-TPU would poison the table
        import jax
        return jax.default_backend() == "tpu"
    return True


def _sweep_gemm_ops(ctx, mesh, axis_name: str, sizes: Sequence[int],
                    reps: int, log, *, dtype_bytes: int = 2) -> list[dict]:
    """One pass over the GEMM-op grid at one wire width.

    ``dtype_bytes=2`` is the classic bf16 sweep. ``dtype_bytes=1`` measures
    the *int8 wire*: operands stay bf16 but ring backends run with
    ``wire="int8"`` (quantize → int8+scales ring → dequantize), and the bulk
    baseline is timed unquantized but recorded under the same ``b1`` width —
    so a ``dtype_bytes=1`` dispatch query compares the int8 ring against the
    full-precision bulk it would actually be replacing. The fused backend is
    excluded at b1 (fused kernels ship full precision; timing one under a
    quantized-wire label would poison the table)."""
    import jax
    from functools import partial

    from repro import compat

    n_dev = mesh.shape[axis_name]
    wire = "int8" if dtype_bytes == 1 else None
    rows: list[dict] = []
    for op in GEMM_OPS:
        avail = ctx.available_backends(op)
        for nsz in sizes:
            args, in_specs, out_specs, (m, n, k) = _gemm_case(op, nsz, n_dev)
            for be in ("bulk", "ring", "ring_bidir", "fused"):
                if be == "fused" and wire is not None:
                    continue
                if not _feasible(op, be, n_dev, nsz, avail):
                    continue
                # the global grid pins the classic 1-chunk ring; chunk-count
                # variants are swept per island (calibrate --per-island)
                fn = jax.jit(compat.shard_map(
                    partial(getattr(ctx, op), backend=be, n_chunks=1,
                            wire=wire),
                    mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                    check_vma=False))
                try:
                    t = _timeit(fn, *args, reps=reps)
                except Exception as e:  # noqa: BLE001 — skip, don't abort
                    log(f"  {op}/{be}/N={nsz}/b{dtype_bytes}: SKIPPED "
                        f"({type(e).__name__})")
                    continue
                rows.append({"op": op, "backend": be, "axis_size": n_dev,
                             "m": m, "n": n, "k": k,
                             "dtype_bytes": dtype_bytes,
                             "n_chunks": 1, "us": t * 1e6})
                log(f"  {op}/{be}/N={nsz}/b{dtype_bytes}: {t * 1e6:.1f} us")
    return rows


#: sub-chunk counts the per-island sweep measures for each ring backend.
ISLAND_CHUNK_SWEEP = (1, 2, 4)

#: sub-chunk counts the per-island sweep measures for the fused backend —
#: one more octave than the rings: in-kernel chunk handoffs are a scalar-core
#: descriptor issue + local semaphore wait, so the fused pipeline tolerates
#: (and usually prefers) finer chunking (``costmodel.fused_pipeline_cost``).
ISLAND_FUSED_CHUNK_SWEEP = (1, 2, 4, 8)


def island_sweep_cases(sw: IslandSweep, n_dev: int,
                       available: Sequence[str]) -> list[tuple[str, int]]:
    """The (backend, n_chunks) grid the per-island GEMM sweep times for one
    island — exposed as a pure function so the fused-inclusion rules are
    unit-testable without running a calibration.

    * ``bulk`` is always timed, at 1 chunk (no pipeline to sub-chunk);
    * ``ring`` sweeps :data:`ISLAND_CHUNK_SWEEP`; ``ring_bidir`` joins it for
      AG×GEMM on an even axis with >= 2 local rows;
    * ``fused`` sweeps :data:`ISLAND_FUSED_CHUNK_SWEEP` when feasible (real
      TPU only — interpret-mode timings would poison the table) and the
      sweep is full-precision: fused kernels do not ship a quantized wire,
      so b1 sweeps exclude them the same way the global grid does.
    """
    backends = ["bulk", "ring"]
    if (sw.op == "all_gather_matmul" and sw.m // n_dev >= 2
            and n_dev % 2 == 0):
        backends.append("ring_bidir")
    if sw.dtype_bytes != 1 and _feasible(sw.op, "fused", n_dev, sw.m,
                                         available):
        backends.append("fused")
    cases: list[tuple[str, int]] = []
    for be in backends:
        if be == "bulk":
            counts: Sequence[int] = (1,)
        elif be == "fused":
            counts = ISLAND_FUSED_CHUNK_SWEEP
        else:
            counts = ISLAND_CHUNK_SWEEP
        cases += [(be, c) for c in counts]
    return cases


def _sweep_a2a_island(ctx, mesh, axis_name: str, sw: IslandSweep,
                      reps: int, log) -> list[dict]:
    """Measure bulk vs chunked all-to-all at one island's exact payload
    shape, tagging rows with its key — the a2a analogue of the GEMM island
    sweep. Rows are stored under the ``CommContext.a2a_coords`` (m, n, k)
    so ``a2a_chunk_schedule`` finds them."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from repro import compat
    from repro.core.schedule import a2a_chunk_axis

    n_dev = mesh.shape[axis_name]
    shape = tuple(sw.shape)
    sa, ca = sw.split_axis, sw.concat_axis
    if shape[sa] % n_dev != 0:
        log(f"  island {sw.island}: a2a split dim {shape[sa]} not divisible "
            f"by {n_dev}-device axis, skipped")
        return []
    dtype = jnp.bfloat16 if sw.dtype_bytes == 2 else jnp.float32
    gshape = list(shape)
    gshape[ca] *= n_dev                     # concat dim sharded on input
    x = jax.random.normal(jax.random.PRNGKey(0), tuple(gshape), dtype)
    in_specs = P(*[axis_name if d == ca else None for d in range(len(shape))])
    out_specs = P(*[axis_name if d == sa else None for d in range(len(shape))])
    cases = [("bulk", 1)]
    seen = {1}
    for c in ISLAND_CHUNK_SWEEP[1:]:
        fit = a2a_chunk_axis(shape, sa, ca, c)
        if fit is None or fit[1] in seen:
            continue
        seen.add(fit[1])
        cases.append(("chunked", fit[1]))
    rows: list[dict] = []
    for be, c in cases:
        fn = jax.jit(compat.shard_map(
            partial(ctx.all_to_all, split_axis=sa, concat_axis=ca,
                    backend=be, n_chunks=c),
            mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False))
        try:
            t = _timeit(fn, x, reps=reps)
        except Exception as e:  # noqa: BLE001 — skip, don't abort
            log(f"  {sw.island}/{be}/c={c}: SKIPPED ({type(e).__name__})")
            continue
        rows.append({"op": "all_to_all", "backend": be, "axis_size": n_dev,
                     "m": sw.m, "n": sw.n, "k": sw.k,
                     "dtype_bytes": sw.dtype_bytes, "n_chunks": c,
                     "island": sw.island, "us": t * 1e6})
        log(f"  {sw.island}/{be}/c={c}: {t * 1e6:.1f} us")
    return rows


def _sweep_islands(ctx, mesh, axis_name: str, sweeps: Sequence[IslandSweep],
                   reps: int, log) -> list[dict]:
    """Measure every feasible backend × chunk count at each island's exact
    declared coordinates, tagging the rows with the island key so
    ``CommContext(island=...)`` dispatch (and ``Island.plan()``) prefers
    them over the generic shape grid."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from repro import compat

    n_dev = mesh.shape[axis_name]
    rows: list[dict] = []
    for sw in sweeps:
        if sw.op == "all_to_all" and sw.shape is not None:
            rows += _sweep_a2a_island(ctx, mesh, axis_name, sw, reps, log)
            continue
        if sw.op not in GEMM_OPS:
            log(f"  island {sw.island}: op {sw.op} not sweepable, skipped")
            continue
        if sw.m % n_dev != 0:
            log(f"  island {sw.island}: m={sw.m} not divisible by "
                f"{n_dev}-device axis, skipped")
            continue
        # dtype_bytes=1 is the int8-wire sweep: bf16 operands, ring backends
        # run quantized (wire="int8"), bulk timed unquantized under the same
        # b1 key — the comparison measured dispatch actually makes.
        wire = "int8" if sw.dtype_bytes == 1 else None
        dtype = jnp.float32 if sw.dtype_bytes == 4 else jnp.bfloat16
        if sw.op == "all_gather_matmul":
            x = jax.random.normal(jax.random.PRNGKey(0), (sw.m, sw.k), dtype)
            w = jax.random.normal(jax.random.PRNGKey(1), (sw.k, sw.n), dtype)
            in_specs, out_specs = (P(axis_name), P()), P()
        else:
            x = jax.random.normal(jax.random.PRNGKey(0),
                                  (sw.m, n_dev * sw.k), dtype)
            w = jax.random.normal(jax.random.PRNGKey(1),
                                  (n_dev * sw.k, sw.n), dtype)
            in_specs = (P(None, axis_name), P(axis_name, None))
            out_specs = (P(axis_name, None)
                         if sw.op == "matmul_reduce_scatter" else P())
        for be, c in island_sweep_cases(sw, n_dev,
                                        ctx.available_backends(sw.op)):
            fused = be == "fused"
            fn = jax.jit(compat.shard_map(
                partial(getattr(ctx, sw.op), backend=be, n_chunks=c,
                        wire=None if fused else wire),
                mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False))
            try:
                t = _timeit(fn, x, w, reps=reps)
            except Exception as e:  # noqa: BLE001 — skip, don't abort
                log(f"  {sw.island}/{be}/c={c}: SKIPPED "
                    f"({type(e).__name__})")
                continue
            rows.append({"op": sw.op, "backend": be, "axis_size": n_dev,
                         "m": sw.m, "n": sw.n, "k": sw.k,
                         "dtype_bytes": sw.dtype_bytes, "n_chunks": c,
                         "island": sw.island, "us": t * 1e6})
            log(f"  {sw.island}/{be}/c={c}: {t * 1e6:.1f} us")
    return rows


def _sweep_psum(ctx, mesh, axis_name: str, sizes: Sequence[int],
                reps: int, log) -> list[dict]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro import compat

    n_dev = mesh.shape[axis_name]
    rows: list[dict] = []
    for nsz in sizes:
        x = jnp.ones((n_dev * n_dev, nsz), jnp.bfloat16)
        for be in ("bulk", "ring"):
            fn = jax.jit(compat.shard_map(
                lambda t, be=be: ctx.psum(t[0], backend=be)[None],
                mesh=mesh, in_specs=P(axis_name), out_specs=P(axis_name),
                check_vma=False))
            xs = x.reshape(n_dev, n_dev, nsz)
            try:
                t = _timeit(fn, xs, reps=reps)
            except Exception as e:  # noqa: BLE001
                log(f"  psum/{be}/N={nsz}: SKIPPED ({type(e).__name__})")
                continue
            rows.append({"op": "psum", "backend": be, "axis_size": n_dev,
                         "m": n_dev, "n": nsz, "k": 1, "dtype_bytes": 2,
                         "us": t * 1e6})
            log(f"  psum/{be}/N={nsz}: {t * 1e6:.1f} us")
    return rows


def calibrate(mesh=None, *, axis_name: str = "x", hw=None,
              grid: str | Sequence[int] = "small", reps: int = 3,
              notes: str = "", verbose: bool = False,
              islands: Sequence[IslandSweep] = (),
              dtypes: Sequence[int] = (2,)) -> CalibrationTable:
    """Run the full micro-benchmark suite and fit a ``CalibrationTable``.

    With ``mesh=None`` a 1-D mesh over every visible device is built. The
    returned table is NOT saved; callers pick the destination
    (``table.save(autotune.cache_path(table.fingerprint))`` for the user
    cache the measured policy searches). ``islands`` adds per-island sweeps
    (backend × chunk count at each island's exact declared coordinates,
    rows tagged with the island key) — the CLI derives them from a model
    config via ``calibrate --per-island``. ``dtypes`` is the wire-width
    axis: each entry runs the GEMM-op grid once at that element width
    (``2`` = bf16; ``1`` = int8 wire — ring backends quantized, bulk
    baseline unquantized but recorded under ``b1`` — so measured dispatch
    can conclude int8-ring beats bf16-bulk from the table alone).
    """
    from repro.core import costmodel as cm
    from repro.core.comms import CommContext
    from repro.launch.mesh import make_mesh

    hw = hw if hw is not None else cm.TPU_V5E
    log = print if verbose else (lambda *_: None)
    if mesh is None:
        import jax
        mesh = make_mesh((len(jax.devices()),), (axis_name,))
    sizes = GRIDS[grid] if isinstance(grid, str) else tuple(grid)
    # Pin each microbench's backend explicitly (policy stays analytic here —
    # a measured policy would consult the very table being built).
    ctx = CommContext(axis_name=axis_name, mesh=mesh, hw=hw)

    log(f"calibrating on {mesh.shape} mesh, grid={sizes} ...")
    bw, hop_overhead = _measure_link(mesh, axis_name, reps)
    log(f"  link: {bw / 1e9:.3f} GB/s achieved, "
        f"{hop_overhead * 1e6:.1f} us/hop overhead")
    eff = _measure_gemm_efficiency(hw, reps)
    log(f"  gemm: {eff:.2e} of {hw.name} peak sustained")
    launch = _measure_launch(reps)
    log(f"  launch: {launch * 1e6:.1f} us")

    rows: list[dict] = []
    for db in dtypes:
        rows += _sweep_gemm_ops(ctx, mesh, axis_name, sizes, reps, log,
                                dtype_bytes=int(db))
    rows += _sweep_psum(ctx, mesh, axis_name, sizes, reps, log)
    if islands:
        log(f"per-island sweep ({len(tuple(islands))} islands) ...")
        rows += _sweep_islands(ctx, mesh, axis_name, tuple(islands), reps,
                               log)

    return CalibrationTable(
        fingerprint=live_fingerprint(hw.name, mesh),
        corrections={
            "ici_bandwidth": bw,
            "remote_sync_s": hop_overhead,
            "gemm_efficiency": eff,
            "kernel_launch_s": launch,
        },
        measurements=rows,
        created=time.strftime("%Y-%m-%dT%H:%M:%S"),
        notes=notes,
    )
