"""Launch layer: sharding-spec plumbing, step builders, host-mesh lowering.
(The 512-device production dry-run is exercised by repro.launch.dryrun;
here we prove the same code path lowers on the local host mesh.)"""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_shape
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import (lower_step, make_optimizer, opt_state_specs,
                                shardings_from_specs)
from repro.models.api import abstract_params, build_model


def test_shardings_from_specs_structure():
    mesh = make_host_mesh()
    shapes = {"a": jax.ShapeDtypeStruct((8, 4), jnp.float32),
              "b": {"c": jax.ShapeDtypeStruct((4,), jnp.float32)}}
    specs = {"a": ("batch", "ff"), "b": {"c": ("embed",)}}
    with jax.set_mesh(mesh):
        sh = shardings_from_specs(mesh, shapes, specs)
    assert sh["a"].mesh.shape == mesh.shape
    assert isinstance(sh["b"]["c"].spec, P)


def test_opt_state_specs_match_structure():
    cfg = get_config("llama3.2-3b", smoke=True)
    model = build_model(cfg)
    aparams = abstract_params(model)
    for name in ("sgd", "adamw"):
        opt = make_optimizer(name)
        aopt = jax.eval_shape(opt.init, aparams)
        specs = opt_state_specs(name, model.param_specs())
        # every opt-state leaf has a reachable spec path (no KeyErrors)
        mesh = make_host_mesh()
        with jax.set_mesh(mesh):
            sh = shardings_from_specs(mesh, aopt, specs)
        assert jax.tree_util.tree_structure(sh) == \
            jax.tree_util.tree_structure(aopt)


@pytest.mark.parametrize("shape_id", ["train_4k", "decode_32k"])
def test_lower_step_on_host_mesh(shape_id):
    """The dry-run code path lowers with a 1-device mesh too (smoke cfg,
    reduced shape by monkeypatching the ShapeConfig)."""
    from repro.configs.base import ShapeConfig
    cfg = get_config("granite-moe-1b-a400m", smoke=True)
    model = build_model(cfg)
    kind = "train" if shape_id == "train_4k" else "decode"
    shape = ShapeConfig("t", seq_len=64, global_batch=2, kind=kind)
    mesh = make_host_mesh()
    with jax.set_mesh(mesh):
        lowered, k = lower_step(model, shape, mesh)
        compiled = lowered.compile()
    assert k == kind
    assert compiled.cost_analysis() is not None


def test_host_mesh_train_step_decreases_loss():
    from repro.launch.steps import make_train_step
    cfg = get_config("llama3.2-3b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = make_optimizer("adamw", lr=5e-3)
    state = opt.init(params)
    step = jax.jit(make_train_step(model, opt))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    losses = []
    for _ in range(8):
        params, state, loss = step(params, state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_placement(monkeypatch, tmp_path, env_dir):
    """The entry points' cache: $JAX_COMPILATION_CACHE_DIR untouched where
    set, otherwise the fixed ``<checkout>/.jax_cache``."""
    from repro.launch import compile_cache
    if env_dir:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / env_dir))
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.use_compile_cache()
        if env_dir:
            assert path == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == was
        else:
            assert path == str(compile_cache.CHECKOUT_CACHE)
            assert compile_cache.CHECKOUT_CACHE.parent == \
                Path(__file__).resolve().parents[1]
            assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
