"""Training-loop and serving integration tests (CPU, reduced configs)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import SyntheticLMDataset
from repro.models import build_model
from repro.train import OptConfig, make_train_step, opt_init
from repro.train.compression import dequantize_int8, quantize_int8


def test_loss_decreases():
    cfg = get_config("yi-6b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = opt_init(params)
    step = jax.jit(make_train_step(model, OptConfig(lr=3e-3, warmup_steps=2,
                                                    total_steps=30)))
    ds = SyntheticLMDataset(cfg.vocab_size, 64, 8, seed=0)
    losses = []
    for i in range(25):
        b = {k: jnp.asarray(v) for k, v in ds.batch_at(i).items()}
        params, opt, m = step(params, opt, b)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.2, losses


def test_microbatch_equivalence():
    """mb=2 gradient accumulation ~ mb=1 on the same global batch."""
    cfg = get_config("stablelm-1.6b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    ds = SyntheticLMDataset(cfg.vocab_size, 32, 8, seed=1)
    b = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}
    oc = OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    p1, _, m1 = make_train_step(model, oc, microbatches=1)(
        params, opt_init(params), b)
    p2, _, m2 = make_train_step(model, oc, microbatches=2)(
        params, opt_init(params), b)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 5e-2
    d = jax.tree.map(lambda a, b_: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b_.astype(jnp.float32)))), p1, p2)
    assert max(jax.tree.leaves(d)) < 0.05


def test_train_loop_with_checkpoint_restart(tmp_path):
    from repro.launch.train import train_loop
    out1 = train_loop("granite-moe-1b-a400m", smoke=True, steps=6, batch=4,
                      seq=32, ckpt_dir=str(tmp_path), ckpt_interval=3,
                      log_every=0)
    # restart: resumes from step 6 checkpoint and continues to 8
    out2 = train_loop("granite-moe-1b-a400m", smoke=True, steps=8, batch=4,
                      seq=32, ckpt_dir=str(tmp_path), ckpt_interval=3,
                      log_every=0)
    assert len(out2["losses"]) == 2  # only steps 6..8 ran


def test_serve_engine_continuous_batching():
    from repro.serve.engine import Request, ServeEngine
    cfg = get_config("h2o-danube-1.8b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, batch_size=2)
    rng = np.random.default_rng(0)
    reqs = [Request(req_id=i, prompt=list(rng.integers(1, 500, size=5)),
                    max_new_tokens=4) for i in range(5)]
    done = eng.run(reqs)
    assert len(done) == 5
    assert all(len(r.output) == 4 for r in done)
    assert all(0 <= t < cfg.vocab_size + 200 for r in done for t in r.output)


def test_quantization_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, 64)) * 3)
    q, s = quantize_int8(x)
    back = dequantize_int8(q, s)
    assert float(jnp.max(jnp.abs(back - x))) <= float(s) * 0.5 + 1e-6


def test_compressed_pod_allreduce_subprocess():
    from conftest import run_py
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as PS
from repro.train.compression import pod_allreduce_compressed
mesh = jax.make_mesh((4,), ("pod",),
                     axis_types=(jax.sharding.AxisType.Auto,))
x = jnp.arange(4 * 8, dtype=jnp.float32).reshape(4, 8)
def f(xs):
    out = pod_allreduce_compressed({"g": xs[0]}, "pod")
    return out["g"][None]
y = jax.shard_map(f, mesh=mesh, in_specs=(PS("pod"),), out_specs=PS("pod"))(x)
want = jnp.mean(x, axis=0)
err = float(jnp.max(jnp.abs(y[0] - want)))
assert err < 0.2, err
print("compress-ok", err)
"""
    out = run_py(code, devices=4)
    assert "compress-ok" in out


def test_pipeline_parallel_subprocess():
    """GPipe over 4 stages == sequential application of all stages."""
    from conftest import run_py
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.train.pipeline import make_pipelined_apply
S, M, mb, L, d = 4, 8, 2, 4, 16
mesh = jax.make_mesh((S,), ("pod",),
                     axis_types=(jax.sharding.AxisType.Auto,))
k = jax.random.PRNGKey(0)
Ws = jax.random.normal(k, (S, d, d)) * 0.3
def stage_fn(W, x):
    return jnp.tanh(x @ W)
h = jax.random.normal(jax.random.PRNGKey(1), (M, mb, L, d))
apply = make_pipelined_apply(stage_fn, mesh, axis_name="pod",
                             num_microbatches=M)
got = apply(Ws, h)
want = h
for s in range(S):
    want = jnp.tanh(want @ Ws[s])
err = float(jnp.max(jnp.abs(got - want)))
assert err < 1e-4, err
print("pipeline-ok", err)
"""
    out = run_py(code, devices=4)
    assert "pipeline-ok" in out
