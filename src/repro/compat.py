"""The one place the tree touches JAX's mesh, shard_map and Pallas-TPU
spellings, and the process-wide compile cache.

Exports
-------
shard_map(f, *, mesh, in_specs, out_specs, check_vma)
    ``jax.shard_map``. Only ``core/template.py`` (and the calibration
    harness) may call it — tests/test_template.py guards that.
make_mesh(shape, axes)
    ``jax.make_mesh`` with explicit ``AxisType.Auto`` axis types.
axis_size / CompilerParams / ANY / interpret_params()
    ``lax.axis_size`` and the Pallas-TPU names the kernels use.
default_interpret() / kernel_interpret(interpret)
    The interpret-mode default every kernel wrapper shares: compiled on a
    TPU, TPU interpret mode (semaphore + remote-DMA emulation) elsewhere.
enable_compile_cache()
    JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``,
    or at ``<repo>/.jax_cache`` when that variable is unset.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax
from jax import lax
from jax.experimental.pallas import tpu as pltpu

axis_size = lax.axis_size
CompilerParams = pltpu.CompilerParams
ANY = pltpu.MemorySpace.ANY

#: cache location used when ``JAX_COMPILATION_CACHE_DIR`` is unset; fixed
#: (no temporary name), so a second run in the same checkout finds the
#: first run's executables
REPO_COMPILE_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(tuple(axes)), **kwargs)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def interpret_params(**kwargs):
    """TPU interpret mode: per-device semaphores and remote DMAs on CPU."""
    return pltpu.InterpretParams(**kwargs)


def default_interpret() -> bool:
    """Kernels run compiled on TPU, interpreted everywhere else."""
    return jax.default_backend() != "tpu"


def kernel_interpret(interpret: bool | None):
    """The ``pallas_call(interpret=...)`` value of a communication kernel.
    ``None`` resolves through ``default_interpret()``, so a TPU always
    compiles; interpreting means the TPU interpret mode, the one that
    emulates remote DMAs."""
    if interpret is None:
        interpret = default_interpret()
    return interpret_params() if interpret else False


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads the
    variable itself) and nothing else is set. Otherwise the cache lives at
    the fixed ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_COMPILE_CACHE))
    return str(REPO_COMPILE_CACHE)
