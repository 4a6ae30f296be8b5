"""End-to-end system behaviour tests (the paper's pipeline as a system).

These tie the layers together the way the deliverables use them:
train -> quantize -> specialize -> serve, exactness of the specialized
artifacts, and the LM-side train->serve round trip through checkpoints.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import configs
from repro.core import dataset, mlp, netgen, quantize
from repro.models import api, base
from repro.optim import adamw
from repro.serve.engine import Engine, ServeConfig
from repro.train import step as step_lib


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


def test_lm_train_then_serve_roundtrip(tmp_path):
    """Train a smoke LM a few steps, checkpoint, restore, serve: the
    engine must produce identical generations from restored params."""
    from repro.checkpoint import ckpt as ckpt_lib

    cfg = configs.smoke("gemma-2b")
    shape = base.ShapeConfig("t", 16, 4, "train")
    oc = adamw.OptConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    state = base.tree_init(step_lib.abstract_state(cfg), jax.random.PRNGKey(0))
    train_step = jax.jit(step_lib.make_train_step(cfg, shape, oc))
    from repro.data.pipeline import make_batch
    for s in range(5):
        b = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape, s).items()}
        state, _ = train_step(state, b)

    path = ckpt_lib.save(str(tmp_path), 5, state)
    restored = ckpt_lib.restore(path, step_lib.abstract_state(cfg))

    prompts = (np.arange(8, dtype=np.int32).reshape(2, 4) * 3) % cfg.vocab
    sc = ServeConfig(max_len=32, max_new_tokens=6)
    out1 = Engine(cfg, state["params"], sc).generate(prompts)
    out2 = Engine(cfg, restored["params"], sc).generate(prompts)
    np.testing.assert_array_equal(out1, out2)


def test_w8_served_lm_matches_quality(tmp_path):
    """Paper technique on a (briefly) trained LM: W8 generations mostly
    agree with fp generations (greedy argmax is robust to 1% weight
    perturbation on a confident model)."""
    from repro.quantized import apply as qapply

    cfg = configs.smoke("llama3.2-3b")
    params = base.tree_init(api.abstract_params(cfg), jax.random.PRNGKey(5))
    qp = qapply.quantize_params_for_serving(cfg, params, min_size=0)
    prompts = (np.arange(12, dtype=np.int32).reshape(3, 4) * 11) % cfg.vocab
    sc = ServeConfig(max_len=32, max_new_tokens=4)
    out_fp = Engine(cfg, params, sc).generate(prompts)
    out_q = Engine(cfg, qp, sc).generate(prompts)
    agree = (out_fp == out_q).mean()
    assert agree >= 0.5, agree            # random-init logits are near-ties


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
