"""Plain references and the numbers that decide ``correct``.

Device answers (the actor's queue order, the deep scorer's logits, the
runtime predictor's residuals) are compared with a float64 numpy forward
of the same tanh MLP, written here from the published layer equations:
``h = tanh(x W1 + b1); h = tanh(h W2 + b2); y = h W3 + b3``.  It imports
nothing from the program.

The schedule (start instants, placements and the decision counters) is
compared with the program's retained reference loop (``optimized=False``)
replayed from the engine's state saved at the window's start, with the
program's prioritizer chain as the timed run had it (the harness's
traced-run wrapper taken out).

``control_*`` compute the same forward one precision step lower
(bfloat16 operands, float32 accumulation) on the device; put in the
program's place they must fail the limits.
"""
from __future__ import annotations

import pickle

import numpy as np

from recorders import TracedPrioritizer, counters


def mlp64(layers, x) -> np.ndarray:
    """float64 forward of a tanh MLP given as [(W, b), ...]."""
    h = np.asarray(x, np.float64)
    for i, (w, b) in enumerate(layers):
        h = h @ np.asarray(w, np.float64) + np.asarray(b, np.float64)
        if i < len(layers) - 1:
            h = np.tanh(h)
    return h


def order_gap(ref: np.ndarray, order: np.ndarray) -> float:
    """Widest gap by which the item an order puts at position p lies below
    the reference's p-th best (0 for an order the reference agrees with,
    ties included)."""
    best = np.sort(ref)[::-1]
    return float(np.max(best - ref[order])) if ref.size else 0.0


def rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """Max abs error over the reference's largest magnitude."""
    got = np.asarray(got, np.float64)
    err = float(np.max(np.abs(got - ref))) if ref.size else 0.0
    scale = float(np.max(np.abs(ref))) if ref.size else 0.0
    if err == 0.0:
        return 0.0
    return err / scale if scale > 0.0 else float("inf")


def actor_layers(actor) -> list:
    return [(np.asarray(l["w"]), np.asarray(l["b"])) for l in actor]


def predictor_layers(p: dict) -> list:
    return [(p["w1"], p["b1"]), (p["w2"], p["b2"]), (p["w3"], p["b3"])]


def actor_order(logits: np.ndarray, n: int) -> np.ndarray:
    """The queue order the program's actor answered, over its n real rows
    (``act`` returns rank-encoded logits: higher ranks first)."""
    return np.argsort(-np.asarray(logits[:n], np.float64), kind="stable")


def device_numbers(samples, actor, forward=mlp64) -> dict:
    """The compared numbers over the sampled device answers.

    ``forward(layers, x)`` computes what the answers are judged against:
    the float64 reference, or a control put in the program's place (then
    the control's own answers are judged instead of the sampled ones)."""
    lay = actor_layers(actor)
    out = {"actor_order_gap": 0.0, "scorer_rel_err": 0.0,
           "predictor_rel_err": 0.0}
    for ov, mask, _action, logits in samples["actor"]:
        n = int(mask.sum())
        ref = mlp64(lay, ov[:n])[:, 0]
        if forward is mlp64:
            order = actor_order(logits, n)
        else:
            order = np.argsort(-forward(lay, ov[:n])[:, 0], kind="stable")
        out["actor_order_gap"] = max(out["actor_order_gap"],
                                     order_gap(ref, order))
    for feats, got in samples["scorer"]:
        ref = mlp64(lay, feats)[:, 0]
        if forward is not mlp64:
            got = forward(lay, feats)[:, 0]
        out["scorer_rel_err"] = max(out["scorer_rel_err"], rel_err(got, ref))
    for x, got, params in samples["predictor"]:
        play = predictor_layers(params)
        ref = mlp64(play, x)
        if forward is not mlp64:
            got = forward(play, x)
        out["predictor_rel_err"] = max(out["predictor_rel_err"],
                                       rel_err(got, ref))
    return out


def control_forward(precision: str):
    """Device forward of the same MLP one precision step lower:
    ``"bfloat16"`` rounds operands to bfloat16 and accumulates in float32
    (the TPU's one-pass default); ``"high"`` is float32 in three bfloat16
    passes."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fwd(layers, x):
        h = x
        for i, (w, b) in enumerate(layers):
            if precision == "bfloat16":
                h = jnp.dot(h.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32) + b
            else:
                h = jnp.dot(h, w, precision=jax.lax.Precision.HIGH,
                            preferred_element_type=jnp.float32) + b
            if i < len(layers) - 1:
                h = jnp.tanh(h)
        return h

    def forward(layers, x):
        lay = [(jnp.asarray(w, jnp.float32), jnp.asarray(b, jnp.float32))
               for w, b in layers]
        return np.asarray(fwd(lay, jnp.asarray(x, jnp.float32)), np.float64)

    return forward


# ------------------------------------------------------------ schedule ----


def naive_blob(blob: bytes) -> bytes:
    """The engine state of ``blob`` switched to the program's reference
    loop: ``optimized=False`` and an uncached cluster, with the harness's
    traced-run wrapper taken out of the prioritizer chain wherever it sits;
    every program prioritizer in the chain (a VC-quota gate with its usage
    included) stays."""
    state = pickle.loads(blob)
    state["optimized"] = False
    state["cluster"].cache_enabled = False
    state["prioritizer"] = without_traced(state["prioritizer"])
    return pickle.dumps(state, protocol=pickle.HIGHEST_PROTOCOL)


def without_traced(pri):
    """The prioritizer chain (each wrapper's ``base`` the next) with the
    harness's ``TracedPrioritizer`` spliced out.  A wrapper that bound its
    base's ``rank_window`` (the program's ``QuotaPrioritizer``) is rebound
    to the new base."""
    if isinstance(pri, TracedPrioritizer):
        return pri.base
    outer = pri
    while (inner := getattr(outer, "base", None)) is not None:
        if isinstance(inner, TracedPrioritizer):
            outer.base = inner.base
            if hasattr(outer, "_base_rank_window"):
                outer._base_rank_window = getattr(inner.base, "rank_window",
                                                  None)
            break
        outer = inner
    return pri


def replay_schedule(engine_cls, hooks_cls, blob: bytes, stream: list,
                    edges: list, ticks: list, starts: list,
                    min_decisions: int, start_log_cls) -> dict:
    """Replay the reference loop from the state ``blob`` and compare it
    with the timed run.

    ``stream`` is the part of the job stream not yet submitted when the
    state was saved (fresh records, sorted by submit instant); ``edges``
    the window edges the timed run stepped to after that, in order.
    ``ticks`` holds, for each event batch the timed run processed after
    the save, its counters and how many of its ``starts`` (the start log
    since the save) had happened.  The replay submits the arrivals due by
    each edge and steps towards it one event batch at a time, as the
    service loop does, and stops after the batch in which the reference
    made its ``min_decisions``-th decision.  ``hooks_cls`` fans the
    engine's hook calls out to the reference's start log.  Returns the
    mismatch count and what was compared."""
    log = start_log_cls()
    ref = engine_cls.load_state(naive_blob(blob), hooks=(hooks_cls(log),))
    base = counters(ref)
    feed, k, made = 0, 0, 0
    for edge in edges:
        hi = feed
        while hi < len(stream) and stream[hi].submit_time <= edge:
            hi += 1
        if hi > feed:
            ref.submit(stream[feed:hi])
            feed = hi
        while made < min_decisions and ref.step(edge, max_events=1):
            k += 1
            made = ref.decisions - base[0]
        if made >= min_decisions:
            break
    got = tuple(c - b for c, b in zip(counters(ref), base))
    if k == 0 or k > len(ticks):
        return {"mismatches": 1, "events": k, "decisions": made,
                "starts": len(log.starts), "counters": got}
    want, n_starts = ticks[k - 1]
    want = tuple(c - b for c, b in zip(want, base))
    mism = sum(a != b for a, b in zip(got, want))
    mism += _diff(log.starts, starts[:n_starts])
    return {"mismatches": mism, "events": k, "decisions": made,
            "starts": len(log.starts), "counters": got,
            "want_counters": want}


def _diff(got: list, want: list) -> int:
    """Start records in one log and not in the other."""
    a, b = sorted(got), sorted(want)
    common = len(set(a) & set(b))
    return (len(a) - common) + (len(b) - common)
