"""Fused quantile-head runtime-predictor MLP Pallas kernel.

One kernel evaluates the 2-hidden-layer predictor over a whole pending
window and emits every quantile head at once:
x(B,F) -> tanh(xW1+b1) -> tanh(.W2+b2) -> .W3+b3 -> (B,Q) residuals.
The heads predict *log-runtime residuals* over the declared-estimate
anchor (see ``repro.predict``), so the kernel output feeds directly into
``anchor * exp(residual)``.  Like ``policy_mlp``, everything fits in VMEM,
so fusing the three matmuls removes the HBM round-trips between layers —
batched window scoring stays off the decision-loop critical path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.policy_mlp import dense


def _predict_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, w3_ref, b3_ref,
                    o_ref):
    x = x_ref[...].astype(jnp.float32)
    h = jnp.tanh(dense(x, w1_ref, b1_ref))
    h = jnp.tanh(dense(h, w2_ref, b2_ref))
    out = dense(h, w3_ref, b3_ref)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def predict_mlp(x, w1, b1, w2, b2, w3, b3, *, interpret: bool = False):
    """x: (B, F); w1: (F, H1); w2: (H1, H2); w3: (H2, Q).
    Returns per-quantile log-runtime residuals (B, Q) in f32."""
    B = x.shape[0]
    Q = w3.shape[1]
    return pl.pallas_call(
        _predict_kernel,
        grid=(),
        in_specs=[pl.BlockSpec(x.shape, None), pl.BlockSpec(w1.shape, None),
                  pl.BlockSpec(b1.shape, None), pl.BlockSpec(w2.shape, None),
                  pl.BlockSpec(b2.shape, None), pl.BlockSpec(w3.shape, None),
                  pl.BlockSpec(b3.shape, None)],
        out_specs=pl.BlockSpec((B, Q), None),
        out_shape=jax.ShapeDtypeStruct((B, Q), jnp.float32),
        interpret=interpret,
    )(x, w1, b1, w2, b2, w3, b3)
