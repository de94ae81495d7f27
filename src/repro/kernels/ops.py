"""jit'd public wrappers around the Pallas kernels.

Handle layout plumbing (GQA broadcast, head-dim padding, chunk padding).
Interpret mode is the default only on the CPU backend, where the Pallas
interpreter is the sole way to run a kernel; on a TPU the kernel is always
compiled unless the caller passes ``interpret=True``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_bh
from repro.kernels.moe_router import moe_router as _moe_router
from repro.kernels.policy_mlp import policy_mlp as _policy_mlp
from repro.kernels.predict_mlp import predict_mlp as _predict_mlp
from repro.kernels.ssd_scan import ssd_scan_bh


def _interpret(flag: bool | None) -> bool:
    if flag is not None:
        return flag
    return jax.default_backend() == "cpu"


def _pad_last(x: jax.Array, multiple: int) -> tuple[jax.Array, int]:
    d = x.shape[-1]
    pad = (-d) % multiple
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x, d


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B, H, L, D); k, v: (B, KV, L, D) -> (B, H, L, D)."""
    B, H, L, D = q.shape
    KV = k.shape[1]
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
    qf, D0 = _pad_last(q.reshape(B * H, L, D), 128)
    kf, _ = _pad_last(k.reshape(B * H, L, D), 128)
    vf, _ = _pad_last(v.reshape(B * H, L, D), 128)
    out = flash_attention_bh(qf, kf, vf, causal=causal, window=window,
                             block_q=block_q, block_k=block_k,
                             interpret=_interpret(interpret),
                             sm_scale=1.0 / (D0 ** 0.5))
    return out[..., :D0].reshape(B, H, L, D0)


def ssd_scan(xh, dt, A, Bs, Cs, *, chunk: int = 256, init_state=None,
             interpret: bool | None = None):
    """Layout-matching wrapper for models.mamba.ssd_chunked.

    xh: (B, L, H, P); dt: (B, L, H); A: (H,); Bs/Cs: (B, L, N).
    Returns (y (B, L, H, P), final_state (B, H, P, N)) — the final state is
    recomputed with one jnp pass (cheap relative to the scan itself)."""
    B, L, H, P = xh.shape
    x_bh = xh.transpose(0, 2, 1, 3)                     # (B, H, L, P)
    dt_bh = dt.transpose(0, 2, 1)[..., None]            # (B, H, L, 1)
    chunk = min(chunk, L)
    y = ssd_scan_bh(x_bh, dt_bh, A, Bs, Cs, chunk=chunk,
                    interpret=_interpret(interpret))
    y = y.transpose(0, 2, 1, 3)
    # final state via closed form (needed only at prefill->decode handoff)
    a = dt_bh[..., 0] * A[None, :, None]                # (B, H, L)
    cs = jnp.cumsum(a, axis=-1)
    total = cs[..., -1:]
    carry = jnp.exp(total - cs)                          # (B, H, L)
    xdt = x_bh.astype(jnp.float32) * dt_bh
    S = jnp.einsum("bhlp,bln,bhl->bhpn", xdt, Bs.astype(jnp.float32), carry)
    if init_state is not None:
        S0 = init_state.astype(jnp.float32)              # (B, H, P, N)
        S = S + S0 * jnp.exp(total)[..., None]
        # y also owes the initial state's contribution: exp(cs_t) C_t . S0
        y_init = jnp.einsum("bln,bhpn,bhl->blhp", Cs.astype(jnp.float32), S0,
                            jnp.exp(cs))
        y = (y.astype(jnp.float32) + y_init).astype(y.dtype)
    return y, S


def policy_mlp(x, params: list[dict], mask, *, interpret: bool | None = None):
    """Actor forward via the fused kernel. params = agent.params['actor']."""
    w1, b1 = params[0]["w"], params[0]["b"]
    w2, b2 = params[1]["w"], params[1]["b"]
    w3, b3 = params[2]["w"], params[2]["b"]
    return _policy_mlp(x, w1, b1, w2, b2, w3, b3, mask,
                       interpret=_interpret(interpret))


def predict_mlp(x, params: dict, *, interpret: bool | None = None):
    """Runtime-predictor forward via the fused kernel.

    params = ``repro.predict.QuantileMLP.params`` (keys w1/b1/w2/b2/w3/b3).
    Returns per-quantile log-runtime residuals (B, Q) in f32."""
    return _predict_mlp(x, params["w1"], params["b1"], params["w2"],
                        params["b2"], params["w3"], params["b3"],
                        interpret=_interpret(interpret))


def moe_router(x, router_w, k: int, *, interpret: bool | None = None):
    T = x.shape[0]
    block_t = 256 if T % 256 == 0 else T
    return _moe_router(x, router_w, k, block_t=block_t,
                       interpret=_interpret(interpret))
