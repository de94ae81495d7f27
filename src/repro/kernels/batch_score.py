"""Shape-bucketed JIT batch scoring over the fused policy-MLP kernel.

Deep queue windows (qw >> MAX_QUEUE_SIZE=256) leave the actor blind to the
tail: ``RLPrioritizer`` ranks the first 256 jobs and keeps everything beyond
in FIFO order.  ``BucketedScorer`` scores arbitrary-length feature batches
through the same fused Pallas MLP (``kernels/policy_mlp.py``) so the tail
can be ordered by the policy too — the batch is padded up to a power-of-two
bucket, so ``jax.jit`` compiles once per bucket (log2 many shapes across a
whole run) instead of once per distinct queue depth.  Batches beyond the
largest bucket are scored in bucket-size chunks.

On the CPU backend the kernel runs in the Pallas interpreter (the
``kernels.ops`` convention); on a TPU it is always compiled.  The
scorer is opt-in end to end — nothing routes through it unless a caller
passes one to ``RLPrioritizer(deep_scorer=...)``.
"""
from __future__ import annotations

import numpy as np

from repro.obs.spans import span

#: bucket ladder bounds: smallest bucket matches the actor window, largest
#: caps compile count (and VMEM footprint) at 16k-deep benches
MIN_BUCKET = 256
MAX_BUCKET = 16384


def bucket_for(n: int, *, lo: int = MIN_BUCKET, hi: int = MAX_BUCKET) -> int:
    """Smallest power-of-two bucket >= n, clamped to [lo, hi]."""
    b = lo
    while b < n and b < hi:
        b <<= 1
    return b


def run_bucketed(fn, rows: np.ndarray, *, lo: int = MIN_BUCKET,
                 hi: int = MAX_BUCKET) -> np.ndarray:
    """Apply a row-wise ``fn(x_pad, m)`` to (n, F) rows in chunks of at most
    ``hi`` rows, each zero-padded to its power-of-two bucket so that a
    jitted ``fn`` compiles once per bucket.  ``m`` is the chunk's count of
    real rows; the padded rows of each result are sliced away."""
    rows = np.asarray(rows, dtype=np.float32)
    outs = []
    for lo_row in range(0, rows.shape[0], hi):
        chunk = rows[lo_row:lo_row + hi]
        m = chunk.shape[0]
        x_pad = np.zeros((bucket_for(m, lo=lo, hi=hi), rows.shape[1]),
                         dtype=np.float32)
        x_pad[:m] = chunk
        outs.append(np.asarray(fn(x_pad, m), dtype=np.float32)[:m])
    if not outs:
        return np.zeros((0,), dtype=np.float32)
    return np.concatenate(outs)


class BucketedScorer:
    """Batch-score (n, F) feature rows with the fused policy MLP.

    ``params`` is the actor parameter list (``agent.params["actor"]``:
    three ``{"w", "b"}`` layers).  ``score`` pads the batch to its bucket,
    runs the Pallas kernel once per chunk, and returns the real rows'
    logits as float32 numpy.  ``compiled_buckets`` exposes which bucket
    shapes have been traced — tests pin that repeated nearby sizes reuse
    one compilation.
    """

    def __init__(self, params: list[dict], *, interpret: bool | None = None,
                 max_bucket: int = MAX_BUCKET):
        self.params = params
        self.interpret = interpret
        self.max_bucket = int(max_bucket)
        self._buckets: set[int] = set()

    @property
    def compiled_buckets(self) -> tuple[int, ...]:
        return tuple(sorted(self._buckets))

    def _score_bucket(self, x_pad: np.ndarray, mask: np.ndarray) -> np.ndarray:
        from repro.kernels import ops
        self._buckets.add(x_pad.shape[0])
        out = ops.policy_mlp(x_pad, self.params, mask,
                             interpret=self.interpret)
        return np.asarray(out, dtype=np.float32)

    def score(self, feats: np.ndarray) -> np.ndarray:
        """(n, F) float32 rows -> (n,) float32 logits (masked rows never
        leak: padding is scored at -1e9 and sliced away)."""
        def run(x_pad: np.ndarray, m: int) -> np.ndarray:
            mask = np.zeros((x_pad.shape[0],), dtype=np.float32)
            mask[:m] = 1.0
            return self._score_bucket(x_pad, mask)

        n = len(feats)
        with span("rank.scorer", rows=n,
                  bucket=bucket_for(min(n, self.max_bucket),
                                    hi=self.max_bucket)):
            return run_bucketed(run, feats, hi=self.max_bucket)
