"""A cell's set-up: its entry in ``BENCHMARK.json``, its configuration and
traffic files, the model module its configuration names, the chips, the
weights drawn from the seed, and the serving engine under test, built from
the program's public pieces the way ``launch/serve.build_engine`` builds
one (which takes no depth-cut configuration)."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path) -> dict:
    return json.loads(Path(path).read_text())


def load_file(path: Path):
    """The Python file at ``path`` as a module, executed once per path."""
    key = f"bench:{Path(path).resolve()}"
    mod = sys.modules.get(key)
    if mod is None:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return mod


def load_model(name: str, root: Path = ROOT):
    """The model module ``bench/models/<name>.py`` that a configuration
    file names under ``bench_model``."""
    return load_file(root / "bench" / "models" / f"{name}.py")


def resolve(workload: str, root: Path = ROOT) -> dict:
    """The cell named ``workload``: its chips, configuration, model module
    and traffic, and the metrics it reports (end-to-end with ``--trace 0``,
    per-layer with ``--trace 1``), all found by the names
    ``BENCHMARK.json`` and the configuration file give."""
    spec = load_json(root / "BENCHMARK.json")
    cell = next((w for w in spec["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moves = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in moves)]
    config = load_json(root / conf["file"])
    return {"name": workload, "chips": cell["chips"], "config": config,
            "model": load_model(config["bench_model"], root),
            "traffic": load_json(root / "bench" / "traffic"
                                 / f"{cell['traffic']}.json"),
            "end_to_end": e2e, "per_layer": per_layer,
            "metrics_dir": root / "bench" / "metrics"}


def seed_words(seed: int, salt: int = 0) -> np.ndarray:
    """Two 32-bit words from any whole-number seed (the driver's exceed
    32 bits) and a salt that separates the streams drawn from one seed."""
    ss = np.random.SeedSequence([seed % 2**64, salt])
    return ss.generate_state(2).astype(np.uint32)


def rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed, salt))


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, for every program however short its compile: the second run
    of a cell there reads every program the first one compiled. Unlike the
    program's ``compat.enable_compile_cache`` it does not follow
    ``JAX_COMPILATION_CACHE_DIR``: two checkouts measured side by side
    must share no cache, and a variable set for both would join them."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def check_devices(chips: int):
    """The chips this cell measures, or exit 2 with no result: no
    fallback to another backend, kind or count."""
    import jax

    from bench.counts import peak

    devs = jax.devices()
    kind = devs[0].device_kind
    why = None
    if devs[0].platform != "tpu":
        why = f"no TPU: JAX's default backend is {devs[0].platform!r}"
    elif len(devs) < chips:
        why = f"the cell needs {chips} TPUs; JAX sees {len(devs)}"
    else:
        try:
            peak(kind)
        except KeyError as e:
            why = str(e)
    if why:
        print(f"[bench] {why}", file=sys.stderr)
        raise SystemExit(2)
    return devs[:chips]


class CompileClock:
    """Seconds JAX spent compiling (persistent-cache reads included) and
    the number of compilations, from ``jax.monitoring`` events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def programs(self) -> int:
        """Programs compiled or read from the persistent cache so far."""
        return self.count + self.cache_hits


def make_weights(tmpl, seed: int, d_model: int, mesh=None):
    """Every parameter of the program's template, drawn from the seed in
    one jitted call, in the dtype it is served in and straight into its
    sharding: matrices normal with std ``d_model**-0.5``, norm gains 1."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from repro.models import transformer as T

    def is_pd(x):
        return isinstance(x, T.PD)

    leaves, treedef = jax.tree.flatten(tmpl, is_leaf=is_pd)

    def draw(words):
        key = jax.random.wrap_key_data(words)
        out = []
        for i, pd in enumerate(leaves):
            if pd.init == "ones":
                out.append(jnp.ones(pd.shape, pd.dtype))
            elif pd.init == "zeros":
                out.append(jnp.zeros(pd.shape, pd.dtype))
            elif pd.init == "normal":
                z = jax.random.normal(jax.random.fold_in(key, i), pd.shape,
                                      jnp.float32)
                out.append((z * d_model ** -0.5).astype(pd.dtype))
            else:
                raise ValueError(f"no draw for init {pd.init!r}")
        return jax.tree.unflatten(treedef, out)

    kw = {}
    if mesh is not None:
        kw["out_shardings"] = jax.tree.map(
            lambda pd: NamedSharding(mesh, pd.spec), tmpl, is_leaf=is_pd)
    return jax.jit(draw, **kw)(jnp.asarray(seed_words(seed, 1)))


@dataclasses.dataclass
class Built:
    cfg: object          # the program's ArchConfig
    mesh: object
    tmpl: dict           # the program's parameter template
    engine: object       # ServingEngine


def build(model, conf: dict, seed: int, devices=None) -> Built:
    """Weights from the seed and the engine that serves them, the program's
    configuration checked by ``model.arch_config``."""
    from repro import compat
    from repro.configs.base import RunConfig, ServeConfig
    from repro.models import transformer as T
    from repro.models.sharding import ShardingRules
    from repro.runtime.serving import ServingEngine

    cfg = model.arch_config(conf)
    sv = dict(conf["serve"])
    sv["bucket_edges"] = tuple(sv["bucket_edges"])
    serve = ServeConfig(**sv)
    mesh = None
    if conf["mesh"]:
        n = int(np.prod(conf["mesh"]))
        mesh = compat.make_mesh(conf["mesh"], ("data", "model"),
                                devices=list(devices)[:n] if devices
                                else None)
    run = RunConfig(dp_axes=("data",), fsdp=False,
                    decode_seq_shard=mesh is not None,
                    comm_policy=conf["run"]["comm_policy"])
    rules = ShardingRules(mesh, run) if mesh is not None else None
    tmpl = T.param_template(cfg, run, rules)
    params = make_weights(tmpl, seed, cfg.d_model, mesh)
    engine = ServingEngine(cfg, run, rules, params, serve)
    return Built(cfg, mesh, tmpl, engine)
