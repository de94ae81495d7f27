"""Per-kernel shape/dtype sweeps vs the ref.py oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


@pytest.mark.parametrize("B,H,KV,L,D", [
    (1, 2, 2, 128, 64),
    (2, 4, 2, 256, 64),
    (1, 8, 2, 128, 128),
    (2, 2, 1, 256, 80),     # non-128 head dim exercises lane padding
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 64])
def test_flash_attention_sweep(B, H, KV, L, D, dtype, window):
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (B, H, L, D), dtype)
    k = rand(ks[1], (B, KV, L, D), dtype)
    v = rand(ks[2], (B, KV, L, D), dtype)
    got = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_k=64)
    kr = jnp.repeat(k, H // KV, axis=1).reshape(B * H, L, D)
    vr = jnp.repeat(v, H // KV, axis=1).reshape(B * H, L, D)
    want = ref.flash_attention_ref(q.reshape(B * H, L, D), kr, vr,
                                   causal=True, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32).reshape(B * H, L, D),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("B,L,H,P,N,chunk", [
    (1, 64, 2, 16, 32, 16),
    (2, 128, 4, 32, 64, 32),
    (1, 256, 2, 64, 128, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(B, L, H, P, N, chunk, dtype):
    ks = jax.random.split(KEY, 5)
    xh = rand(ks[0], (B, L, H, P), dtype, 0.5)
    dt = jax.nn.softplus(rand(ks[1], (B, L, H)))
    A = -jnp.exp(rand(ks[2], (H,), scale=0.3))
    Bs = rand(ks[3], (B, L, N), scale=0.3)
    Cs = rand(ks[4], (B, L, N), scale=0.3)
    y, S = ops.ssd_scan(xh, dt, A, Bs, Cs, chunk=chunk)
    want = ref.ssd_scan_ref(
        xh.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1)[..., None], A, Bs, Cs
    ).transpose(0, 2, 1, 3)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)
    assert S.shape == (B, H, P, N) and np.isfinite(np.asarray(S)).all()


def test_ssd_init_state_consistency():
    """Running [first half; second half with carried state] == full run."""
    ks = jax.random.split(KEY, 5)
    B, L, H, P, N = 1, 128, 2, 16, 32
    xh = rand(ks[0], (B, L, H, P), scale=0.5)
    dt = jax.nn.softplus(rand(ks[1], (B, L, H)))
    A = -jnp.exp(rand(ks[2], (H,), scale=0.3))
    Bs = rand(ks[3], (B, L, N), scale=0.3)
    Cs = rand(ks[4], (B, L, N), scale=0.3)
    y_full, S_full = ops.ssd_scan(xh, dt, A, Bs, Cs, chunk=32)
    h = L // 2
    y1, S1 = ops.ssd_scan(xh[:, :h], dt[:, :h], A, Bs[:, :h], Cs[:, :h],
                          chunk=32)
    y2, S2 = ops.ssd_scan(xh[:, h:], dt[:, h:], A, Bs[:, h:], Cs[:, h:],
                          chunk=32, init_state=S1)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_full[:, h:]),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S_full), atol=2e-3,
                               rtol=2e-3)


@pytest.mark.parametrize("Q,F,H1,H2", [(256, 8, 64, 32), (128, 8, 32, 16),
                                     (4096, 8, 64, 32)])
def test_policy_mlp_sweep(Q, F, H1, H2):
    ks = jax.random.split(KEY, 7)
    x = rand(ks[0], (Q, F))
    params = [{"w": rand(ks[1], (F, H1)), "b": rand(ks[2], (H1,))},
              {"w": rand(ks[3], (H1, H2)), "b": rand(ks[4], (H2,))},
              {"w": rand(ks[5], (H2, 1)), "b": rand(ks[6], (1,))}]
    mask = (jnp.arange(Q) < Q // 2).astype(jnp.float32)
    got = ops.policy_mlp(x, params, mask)
    want = ref.policy_mlp_ref(x, params[0]["w"], params[0]["b"],
                              params[1]["w"], params[1]["b"],
                              params[2]["w"], params[2]["b"], mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("T,d,E,k", [(256, 64, 16, 2), (512, 32, 8, 4),
                                     (512, 128, 64, 8)])
def test_moe_router_sweep(T, d, E, k):
    ks = jax.random.split(KEY, 2)
    x = rand(ks[0], (T, d))
    w = rand(ks[1], (d, E), scale=0.1)
    gw, gi = ops.moe_router(x, w, k)
    ww, wi = ref.moe_router_ref(x, w, k)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(ww), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def test_model_flash_vs_xla_path():
    """LM forward with impl.attn='flash' (interpret) equals the XLA path."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.models.lm import ModelImpl
    cfg = get_config("yi-6b", smoke=True)
    m_x = build_model(cfg, impl=ModelImpl(attn="xla"))
    m_f = build_model(cfg, impl=ModelImpl(attn="flash"))
    params = m_x.init(KEY)
    toks = jax.random.randint(KEY, (2, 128), 0, cfg.vocab_size)
    lx = m_x.forward(params, toks)
    lf = m_f.forward(params, toks)
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lf), atol=0.1,
                               rtol=0.1)


def test_chunked_attention_equals_full():
    """XLA chunked q-block attention == full-matrix attention."""
    from repro.models.attention import _sdpa_chunked, _sdpa_full, causal_mask
    ks = jax.random.split(KEY, 3)
    B, H, KV, L, D = 1, 4, 2, 1024, 32
    q = rand(ks[0], (B, H, L, D))
    k = rand(ks[1], (B, KV, L, D))
    v = rand(ks[2], (B, KV, L, D))
    for win in (0, 128):
        got = _sdpa_chunked(q, k, v, causal=True, window=win, block_q=256)
        mask = causal_mask(L, L, win)[:, :, 0]
        want = _sdpa_full(q, k, v, mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_interpret_default_follows_backend(monkeypatch):
    """Interpret mode is the default only on the CPU backend: a TPU process
    compiles every kernel unless the caller asks for the interpreter."""
    assert ops._interpret(None) is (jax.default_backend() == "cpu")
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops._interpret(None) is False
    assert ops._interpret(True) is True
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "cpu")
    assert ops._interpret(None) is True
    assert ops._interpret(False) is False
