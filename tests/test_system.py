"""End-to-end behaviour tests: the public train/serve paths on an emulated
mesh with all substrates active (PK overlap, FSDP, checkpointing)."""

from pathlib import Path

import numpy as np
import pytest


def test_end_to_end_train_on_mesh(tmp_path):
    from repro.launch.train import build_and_train
    state, log = build_and_train(
        "tinyllama-1.1b", steps=12, reduced=True, mesh_shape=(2, 4),
        mesh_axes=("data", "model"), batch=4, seq=32,
        ckpt_dir=str(tmp_path), lr=3e-3, microbatches=2, log_every=1,
        ckpt_every=6)
    assert log[-1]["step"] == 12
    assert np.isfinite(log[-1]["loss"])
    # checkpoint committed
    from repro.ckpt.manager import CheckpointManager
    assert CheckpointManager(tmp_path).latest_step() == 12


def test_end_to_end_train_compressed_grads(tmp_path):
    from repro.launch.train import build_and_train
    _, log = build_and_train(
        "tinyllama-1.1b", steps=20, reduced=True, mesh_shape=None,
        mesh_axes=None, batch=4, seq=32, ckpt_dir=str(tmp_path), lr=5e-3,
        compress_grads=True, log_every=1, ckpt_every=100)
    first = np.mean([m["loss"] for m in log[:3]])
    last = np.mean([m["loss"] for m in log[-3:]])
    assert last < first, "int8+EF compressed training must still learn"


def test_end_to_end_serve(capsys):
    from repro.launch.serve import generate
    out = generate("tinyllama-1.1b", reduced=True, batch=2, prompt_len=4,
                   gen_tokens=8, mesh_shape=(2, 4))
    assert out.shape == (2, 8)
    assert np.all((np.asarray(out) >= 0))


def test_end_to_end_serve_ssm():
    from repro.launch.serve import generate
    out = generate("falcon-mamba-7b", reduced=True, batch=2, prompt_len=2,
                   gen_tokens=6, mesh_shape=None)
    assert out.shape == (2, 6)


def test_moe_arch_trains_on_mesh(tmp_path):
    from repro.launch.train import build_and_train
    _, log = build_and_train(
        "moonshot-v1-16b-a3b", steps=6, reduced=True, mesh_shape=(2, 4),
        mesh_axes=("data", "model"), batch=4, seq=32,
        ckpt_dir=str(tmp_path), log_every=1, ckpt_every=100)
    assert np.isfinite(log[-1]["loss"])


def test_hybrid_arch_trains_on_mesh(tmp_path):
    from repro.launch.train import build_and_train
    _, log = build_and_train(
        "jamba-1.5-large-398b", steps=4, reduced=True, mesh_shape=(2, 4),
        mesh_axes=("data", "model"), batch=4, seq=32,
        ckpt_dir=str(tmp_path), log_every=1, ckpt_every=100)
    assert np.isfinite(log[-1]["loss"])


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without it
    the cache sits at the fixed <repo>/.jax_cache."""
    import jax

    from repro import compat
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert compat.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo_cache = str(Path(__file__).resolve().parents[1] / ".jax_cache")
        assert compat.enable_compile_cache() == repo_cache
        assert jax.config.jax_compilation_cache_dir == repo_cache
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_chip_smoke_refuses_without_tpu(capsys):
    """The chip smoke stops before any work when JAX finds no TPU: no CPU
    run stands in for the chip."""
    import importlib.util
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    with pytest.raises(SystemExit) as e:
        smoke.check_device(1)
    assert e.value.code == 2
    assert "no TPU found" in capsys.readouterr().err
