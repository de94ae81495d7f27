#!/usr/bin/env python3
"""Smoke run of the scheduler's device path on one TPU chip.

Drives the main path once through its normal entry points, in this one
process, with no fallback:

1. device gate: the first JAX device must be a TPU;
2. kernels: the fused policy MLP (through ``BucketedScorer``, buckets 256
   and 4096) and the fused runtime predictor against a float64 numpy
   forward of the same weights, and a check that each lowers to a Mosaic
   kernel (``tpu_custom_call``), not the interpreter;
3. main stream: ``run_scenario("flash-crowd", 10_000 jobs)`` ranked by the
   greedy PPO actor with the deep-window scorer, MILP allocation, EASY
   backfill and the kernel runtime predictor;
4. reference: the first 1,000 jobs of that stream through the engine
   and the naive reference loop (``repro.sched.reference``) give
   identical schedules, and the device actor's
   top-1 equals a float64 numpy actor on 200 sampled decisions of (3);
5. training: PPO updates through ``repro.rl.StreamingTrainer``.

Every phase runs even when an earlier one failed; the script exits 1 if
any check failed.  The last line of standard output is a JSON object
``{"ok": true, "device": {...}}``, printed only when every check passed.

Run from the repository root:  python3 chip_smoke.py
"""
from __future__ import annotations

import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

NUM_JOBS = 10_000
QUEUE_WINDOW = 4096
REF_JOBS = 1_000
TOP1_SAMPLES = 200
SEED = 0
#: a kernel agrees with the float64 forward when its max abs error is
#: within this fraction of the reference's largest magnitude
KERNEL_RTOL = 1e-4

FAILURES: list[str] = []


def check(ok: bool, what: str) -> None:
    if not ok:
        FAILURES.append(what)
        print(f"  CHECK FAILED: {what}", flush=True)


def phase(fn):
    """Run one phase; an exception fails the phase, not the whole run."""
    name = fn.__name__.lstrip("_")
    print(f"== {name}", flush=True)
    t0 = time.perf_counter()
    try:
        out = fn()
    except Exception:  # noqa: BLE001 - reported, and fails the run below
        traceback.print_exc()
        FAILURES.append(f"phase {name} raised")
        out = None
    print(f"   {name} wall_s={time.perf_counter() - t0:.2f}", flush=True)
    return out


class CompileClock:
    """Sums XLA compile time (persistent-cache reads included) and counts
    persistent-cache hits, from JAX's own monitoring events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def mlp_reference(layers, x):
    """float64 numpy forward of a tanh MLP given as [{"w", "b"}, ...]."""
    import numpy as np
    h = np.asarray(x, np.float64)
    for i, lyr in enumerate(layers):
        h = h @ np.asarray(lyr["w"], np.float64) + np.asarray(lyr["b"],
                                                               np.float64)
        if i < len(layers) - 1:
            h = np.tanh(h)
    return h


def max_err(got, ref) -> tuple[float, float]:
    import numpy as np
    err = float(np.max(np.abs(np.asarray(got, np.float64) - ref)))
    return err, float(np.max(np.abs(ref)))


def main() -> int:
    try:
        import jax
    except ImportError as e:
        print(f"chip_smoke: JAX is not importable: {e}", file=sys.stderr)
        return 2
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform={dev.platform!r} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run it from the "
              "repository root", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import numpy as np

    from repro.core.agent import PPOAgent, PPOConfig
    from repro.core.env import RLPrioritizer
    from repro.core.features import OV_SIZE
    from repro.kernels import ops
    from repro.kernels.batch_score import BucketedScorer
    from repro.launch.compile_cache import use_compile_cache
    from repro.predict import RuntimePredictor
    from repro.predict.predictor import PREDICT_FEATURES, QuantileMLP
    from repro.rl import StreamingConfig, StreamingTrainer
    from repro.sched import (MultiHooks, get_scenario, reference_run,
                             run_scenario, run_stream, wrap_tenancy)

    cache_dir = use_compile_cache()
    clock = CompileClock(jax)
    print(f"compile cache: {cache_dir}", flush=True)
    # one device-side agent for the main stream and the reference runs
    agent = PPOAgent(PPOConfig(seed=SEED))
    actor = agent.params["actor"]
    samples: list[tuple] = []

    def _kernels():
        rng = np.random.default_rng(SEED)
        scorer = BucketedScorer(actor)
        for n, bucket in ((200, 256), (3000, 4096)):
            x = rng.random((n, OV_SIZE), dtype=np.float32)
            ref = mlp_reference(actor, x)[:, 0]
            err, scale = max_err(scorer.score(x), ref)
            print(f"  policy_mlp bucket={bucket} rows={n} max_abs_err={err!r}"
                  f" ref_max_abs={scale!r}", flush=True)
            check(err <= KERNEL_RTOL * scale,
                  f"policy_mlp bucket {bucket} error {err} vs scale {scale}")
        check(scorer.compiled_buckets == (256, 4096),
              f"scorer buckets {scorer.compiled_buckets}")
        x_pad = jax.ShapeDtypeStruct((256, OV_SIZE), np.float32)
        mask = jax.ShapeDtypeStruct((256,), np.float32)
        text = jax.jit(lambda x, m: ops.policy_mlp(x, actor, m)).lower(
            x_pad, mask).as_text()
        check("tpu_custom_call" in text, "policy_mlp lowered without Mosaic")

        mlp = QuantileMLP(seed=SEED)
        # the head starts at zero; seeded weights make the comparison real
        mlp.params["w3"] = rng.standard_normal(mlp.params["w3"].shape
                                               ).astype(np.float32)
        mlp.params["b3"] = rng.standard_normal(mlp.params["b3"].shape
                                               ).astype(np.float32)
        p = mlp.params
        layers = [{"w": p["w1"], "b": p["b1"]}, {"w": p["w2"], "b": p["b2"]},
                  {"w": p["w3"], "b": p["b3"]}]
        x = rng.random((512, PREDICT_FEATURES), dtype=np.float32)
        err, scale = max_err(ops.predict_mlp(x, p), mlp_reference(layers, x))
        print(f"  predict_mlp rows=512 max_abs_err={err!r} "
              f"ref_max_abs={scale!r}", flush=True)
        check(err <= KERNEL_RTOL * scale,
              f"predict_mlp error {err} vs scale {scale}")
        text = jax.jit(lambda x: ops.predict_mlp(x, p)).lower(
            jax.ShapeDtypeStruct((512, PREDICT_FEATURES), np.float32)
        ).as_text()
        check("tpu_custom_call" in text, "predict_mlp lowered without Mosaic")

    def _main_stream():
        # reservoir of greedy decisions with at least two queued jobs
        rng = np.random.default_rng(SEED + 1)
        seen = [0]
        act = agent.act

        def recording_act(ov, cv, mask, explore=True, record=True):
            action, logits = act(ov, cv, mask, explore=explore, record=record)
            if mask.sum() >= 2:
                k = seen[0]
                seen[0] += 1
                item = (ov.copy(), mask.copy(), action)
                if k < TOP1_SAMPLES:
                    samples.append(item)
                else:
                    j = int(rng.integers(k + 1))
                    if j < TOP1_SAMPLES:
                        samples[j] = item
            return action, logits

        scorer = BucketedScorer(actor)
        predictor = RuntimePredictor(use_kernel=True)
        pri = RLPrioritizer(agent, explore=False, deep_scorer=scorer)
        agent.act = recording_act
        c0, t0 = clock.seconds, time.perf_counter()
        try:
            sr = run_scenario("flash-crowd", num_jobs=NUM_JOBS, seed=SEED,
                              prioritizer=pri, queue_window=QUEUE_WINDOW,
                              allocator="milp", backfill=True,
                              predictor=predictor)
        finally:
            del agent.act
        wall = time.perf_counter() - t0
        eng = sr.engine
        peak = max(s.queue_len for s in sr.telemetry.samples)
        errors = sum(len(h.errors) for h in eng.hooks
                     if isinstance(h, MultiHooks))
        print(f"  jobs_completed={len(sr.batch.jobs)} of {NUM_JOBS}", flush=True)
        print(f"  decisions={eng.decisions} milp_solver={eng.milp_calls} "
              f"milp_fallback={eng.milp_fallbacks} backfills={eng.backfills}")
        print(f"  peak_pending_depth={peak} buckets={scorer.compiled_buckets}"
              f" predictor_kernel={predictor.use_kernel} hook_errors={errors}")
        print(f"  compile_s={clock.seconds - c0:.2f} wall_s={wall:.2f}",
              flush=True)
        check(len(sr.batch.jobs) == NUM_JOBS and eng.done,
              f"{len(sr.batch.jobs)} of {NUM_JOBS} jobs completed")
        check(peak > 1024, f"peak pending depth {peak} <= 1024")
        check(len(scorer.compiled_buckets) >= 3,
              f"buckets {scorer.compiled_buckets}")
        check(predictor.use_kernel, "predictor left the kernel path")
        check(errors == 0, f"{errors} hook errors")

    def _reference():
        run = get_scenario("flash-crowd").build(NUM_JOBS, SEED)
        jobs = sorted(run.jobs, key=lambda j: (j.submit_time, j.job_id))
        jobs = jobs[:REF_JOBS]

        def prioritizer():
            return wrap_tenancy(
                RLPrioritizer(agent, explore=False,
                              deep_scorer=BucketedScorer(actor)),
                run.sla_users, run.vc_quotas)
        kw = dict(allocator="milp", backfill=True, queue_window=QUEUE_WINDOW,
                  fault_model=run.fault_model)
        # the whole stream up front on both sides: the predictor caches
        # each job's features when it is submitted, so chunked submission
        # is a different (assisted) schedule
        fast = run_stream(run.spec, [j.clone_pending() for j in jobs],
                          prioritizer(), chunked_submit=False,
                          predictor=RuntimePredictor(use_kernel=True),
                          **kw).engine
        naive = reference_run(run.spec, [j.clone_pending() for j in jobs],
                              prioritizer(),
                              predictor=RuntimePredictor(use_kernel=True),
                              **kw)
        out = {}
        for name, eng in (("engine", fast), ("reference", naive)):
            out[name] = (
                sorted((j.job_id, j.start_time, j.finish_time, j.restarts,
                        tuple(sorted((j.placement or {}).items())))
                       for j in eng.completed),
                (eng.decisions, eng.milp_calls, eng.milp_fallbacks,
                 eng.backfills))
            print(f"  {name} jobs={len(eng.completed)} "
                  f"counters(decisions, milp, fallback, backfills)="
                  f"{out[name][1]}", flush=True)
        check(len(out["engine"][0]) == REF_JOBS, "reference stream incomplete")
        check(out["engine"][0] == out["reference"][0],
              "engine and reference job tuples differ")
        check(out["engine"][1] == out["reference"][1],
              "engine and reference counters differ")

        agree, gaps = 0, []
        for ov, mask, action in samples:
            ref = np.where(mask > 0, mlp_reference(actor, ov)[:, 0], -np.inf)
            top2 = np.sort(ref)[-2:]
            gaps.append(float(top2[1] - top2[0]))
            agree += int(int(np.argmax(ref)) == action)
        print(f"  top1_agree={agree}/{len(samples)} "
              f"min_top2_gap={min(gaps, default=math.nan)!r}", flush=True)
        check(len(samples) == TOP1_SAMPLES,
              f"only {len(samples)} decisions sampled")
        check(agree == len(samples),
              f"top-1 differs on {len(samples) - agree} decisions")

    def _training():
        trainer = StreamingTrainer(StreamingConfig(num_jobs=160, streams=2,
                                                   seed=SEED))
        before = jax.tree.map(np.asarray, trainer.agent.params)
        eps = trainer.train()
        losses = [e.loss for e in eps if e.updated]
        changed = any(not np.array_equal(a, np.asarray(b)) for a, b in zip(
            jax.tree.leaves(before), jax.tree.leaves(trainer.agent.params)))
        print(f"  episodes={len(eps)} updates={len(losses)} "
              f"losses={losses!r} params_changed={changed}", flush=True)
        check(bool(losses) and all(math.isfinite(v) for v in losses),
              f"PPO losses {losses}")
        check(changed, "PPO params unchanged")

    t0 = time.perf_counter()
    phase(_kernels)
    phase(_main_stream)
    phase(_reference)
    phase(_training)
    print(f"total compile_s={clock.seconds:.2f} "
          f"cache_hits={clock.cache_hits} "
          f"wall_s={time.perf_counter() - t0:.2f}", flush=True)
    if FAILURES:
        print(f"chip_smoke: {len(FAILURES)} check(s) failed: "
              + "; ".join(FAILURES), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
