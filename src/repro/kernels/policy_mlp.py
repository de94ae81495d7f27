"""Fused RLTune policy-MLP Pallas kernel — the paper's inference hot path.

One kernel evaluates the 3-layer actor MLP over the whole 256-job queue
(sliding-window shared weights), applies the queue mask, and emits logits:
x(256,8) -> tanh(xW1+b1) -> tanh(.W2+b2) -> .W3+b3 -> mask.  Everything fits
in VMEM (a few KB), so fusion removes all HBM round-trips between layers —
this is what keeps the paper's ~0.7 ms decision latency.  Deep batches from
``kernels.batch_score`` stream through in ``ROW_BLOCK``-row grid steps with
the weights resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def dense(x, w_ref, b_ref):
    """``x @ w + b`` inside a kernel, in full f32 on the MXU: Mosaic's
    default (one bf16 pass) errs by ~1e-2 of the output scale, enough to
    reorder near-tied queue logits."""
    return jax.lax.dot_general(
        x, w_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32) + b_ref[...]


def _policy_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, w3_ref, b3_ref,
                   mask_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    h = jnp.tanh(dense(x, w1_ref, b1_ref))
    h = jnp.tanh(dense(h, w2_ref, b2_ref))
    logits = dense(h, w3_ref, b3_ref)
    logits = logits[:, 0]
    o_ref[...] = jnp.where(mask_ref[...] > 0, logits, -1e9).astype(o_ref.dtype)


#: rows per grid step: a whole 16384-row bucket at full f32 precision needs
#: ~32 MB of scoped VMEM (16 MB on a v5e), and compile time grows with the
#: block, so long batches stream through in blocks of this many rows
ROW_BLOCK = 2048


@functools.partial(jax.jit, static_argnames=("interpret",))
def policy_mlp(x, w1, b1, w2, b2, w3, b3, mask, *, interpret: bool = False):
    """x: (Q, F); w1: (F, H1); w2: (H1, H2); w3: (H2, 1); mask: (Q,).
    Returns masked logits (Q,) in f32.  Q a multiple of ``ROW_BLOCK`` runs
    in row blocks; any other Q in one block."""
    Q, F = x.shape
    tb = ROW_BLOCK if Q % ROW_BLOCK == 0 else Q

    def whole(a):
        return pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)

    return pl.pallas_call(
        _policy_kernel,
        grid=(Q // tb,),
        in_specs=[pl.BlockSpec((tb, F), lambda i: (i, 0)), whole(w1),
                  whole(b1), whole(w2), whole(b2), whole(w3), whole(b3),
                  pl.BlockSpec((tb,), lambda i: (i,))],
        out_specs=pl.BlockSpec((tb,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Q,), jnp.float32),
        interpret=interpret,
    )(x, w1, b1, w2, b2, w3, b3, mask)
