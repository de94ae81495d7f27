"""Compile the decision path's device programs for a TPU v5e chip.

Nothing runs: each program is lowered and compiled by the TPU compiler for
a chip that is described, not attached, at the shapes the scheduler uses.
That catches what interpret mode cannot (tiling, VMEM limits, Mosaic
lowering) without a chip.  The topology is described inside a fixture so
that only the worker given this file loads the TPU library; the persistent
compilation cache is off around every compile, since an entry written for
a described chip cannot be read back here.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.agent import (PPOConfig, adam_init, greedy_step,
                              init_params, policy_step, ppo_update_step)
from repro.core.features import CV_SIZE, MAX_QUEUE_SIZE, OV_SIZE
from repro.kernels.policy_mlp import policy_mlp
from repro.kernels.predict_mlp import predict_mlp
from repro.predict.predictor import PREDICT_FEATURES, QuantileMLP


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(tree, sharding):
    """Shapes of ``tree`` (arrays or ShapeDtypeStructs) placed on the chip."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding), tree)


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _param_shapes(cfg: PPOConfig):
    return jax.eval_shape(lambda: init_params(cfg))


def _actor_specs(one_chip):
    return _spec(_param_shapes(PPOConfig()), one_chip)


@pytest.mark.parametrize("rows", [256, 16384])
def test_policy_mlp_compiles(one_chip, no_cache, rows):
    actor = _actor_specs(one_chip)["actor"]
    args = [_f32((rows, OV_SIZE), one_chip)]
    for lyr in actor:
        args += [lyr["w"], lyr["b"]]
    lowered = policy_mlp.lower(*args, _f32((rows,), one_chip),
                               interpret=False)
    lowered.compile()
    assert "tpu_custom_call" in lowered.as_text()


@pytest.mark.parametrize("rows", [512, 4096])
def test_predict_mlp_compiles(one_chip, no_cache, rows):
    p = _spec(QuantileMLP().params, one_chip)
    lowered = predict_mlp.lower(
        _f32((rows, PREDICT_FEATURES), one_chip), p["w1"], p["b1"], p["w2"],
        p["b2"], p["w3"], p["b3"], interpret=False)
    lowered.compile()
    assert "tpu_custom_call" in lowered.as_text()


def test_greedy_step_compiles(one_chip, no_cache):
    greedy_step.lower(_actor_specs(one_chip),
                      _f32((MAX_QUEUE_SIZE, OV_SIZE), one_chip),
                      _f32((MAX_QUEUE_SIZE,), one_chip)).compile()


def test_policy_step_compiles(one_chip, no_cache):
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    policy_step.lower(_actor_specs(one_chip),
                      _f32((MAX_QUEUE_SIZE, OV_SIZE), one_chip),
                      _f32((MAX_QUEUE_SIZE, CV_SIZE), one_chip),
                      _f32((MAX_QUEUE_SIZE,), one_chip), key).compile()


def test_ppo_update_step_compiles(one_chip, no_cache):
    cfg = PPOConfig()
    params = _param_shapes(cfg)
    opt = _spec(jax.eval_shape(adam_init, params), one_chip)
    P, Q = cfg.max_steps, MAX_QUEUE_SIZE
    batch = {
        "ov": _f32((P, Q, OV_SIZE), one_chip),
        "cv": _f32((P, Q, CV_SIZE), one_chip),
        "mask": _f32((P, Q), one_chip),
        "action": jax.ShapeDtypeStruct((P,), jnp.int32, sharding=one_chip),
        **{k: _f32((P,), one_chip) for k in ("logp", "ret", "adv", "valid")},
    }
    compiled = ppo_update_step.lower(
        _spec(params, one_chip), opt, batch, clip_eps=cfg.clip_eps,
        value_coef=cfg.value_coef, entropy_coef=cfg.entropy_coef, lr=cfg.lr,
        max_norm=cfg.max_grad_norm).compile()
    assert compiled.memory_analysis() is not None
