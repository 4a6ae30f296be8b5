"""End-to-end system behaviour tests (the paper's pipeline as a system).

These tie the layers together the way the deliverables use them:
train -> quantize -> specialize -> emit, the exactness of the
specialized artifacts, and where the entry points keep JAX's compile
cache.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import dataset, mlp, netgen, quantize


@pytest.fixture(scope="module")
def paper_system():
    """A small trained instance of the paper's full pipeline."""
    xtr, ytr, xte, yte = dataset.train_test_split(500, 300, seed=11)
    cfg = mlp.MLPConfig(n_hidden=96, epochs=30, lr=2.0, seed=13)
    params = mlp.train(cfg, xtr, ytr)
    return params, xte, yte


def test_paper_pipeline_end_to_end(paper_system):
    """train -> ladder -> netgen -> specialized artifact, all consistent."""
    params, xte, yte = paper_system
    qnet = quantize.quantize(params)
    fn = netgen.specialize(qnet, backend="jnp")
    acc = float(np.mean(np.asarray(fn(jnp.asarray(xte))) == yte))
    base_acc = mlp.accuracy(mlp.predict_l0(params), xte, yte)
    assert acc > base_acc - 0.12          # paper: few-point cost
    v = netgen.emit_verilog(netgen.prune(qnet)[0], addend=True)
    assert v.count("assign") > qnet.w1.shape[1]  # one assign per node + I/O


def test_verilog_addend_form_has_no_multiplies(paper_system):
    params, _, _ = paper_system
    qnet = quantize.quantize(params)
    v = netgen.emit_verilog(netgen.prune(qnet)[0], addend=True)
    body = v.split("// hidden-input sums")[1]
    assert "*" not in body.split("// prediction")[0]


def test_compile_cache_dir_env_or_fixed_checkout_path(monkeypatch, tmp_path):
    """Entry points keep JAX's persistent cache where
    JAX_COMPILATION_CACHE_DIR says, setting no other location; without
    it, in one fixed, gitignored directory of the checkout."""
    from pathlib import Path

    from jax.experimental.compilation_cache import compilation_cache
    from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", "unchanged")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == "unchanged"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        compilation_cache.reset_cache()
    root = Path(__file__).resolve().parents[1]
    assert CHECKOUT_CACHE_DIR == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
