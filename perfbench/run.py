#!/usr/bin/env python3
"""Benchmark harness: one run of one cell of ``BENCHMARK.json``.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with a TPU.  A cell names a
configuration (``configs/<name>.json``: cluster, job profile, scheduler
settings) and a traffic mix (``traffic/<name>.json``: how the stream is
shaped); every metric is a reader ``metrics/<name>.py``; the limits of
the reference comparison are ``limits/<cell>.json``.  Nothing in
this file is particular to a cell.

One run: generate the job stream from the seed and the deployment's
actor weights, build the program's RLTune service loop
(``repro.sched.run_stream`` with the PPO actor ranking, the deep-window
scorer, the MILP allocator, EASY backfill and the runtime predictor, and
the program's VC-quota gate where the configuration states
``scheduler.vc_quotas``),
replay the stream until the traffic's warm-up instant, warm every shape the
window will use, then measure for ``--seconds`` of wall time, ending at the
next rescan-window edge.  The engine's state is saved when the window opens
and once more at an edge inside it drawn from the seed (that save's time is
left out of the window's).  After the window: check the schedule against
the configuration's guarantees (``guarantees.py``), compare what the window
produced with the references (``reference.py``), reduce the trace
(``--trace 1``), and print one JSON line last on standard output.

``--rehearse`` allows a CPU backend, for rehearsals at small sizes
(``--override`` patches the configuration and traffic); its output is
marked ``"rehearsal": true`` and is no measurement.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SRC = os.path.join(ROOT, "src")
#: JAX's persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = os.path.join(HERE, ".jax_cache")
TRACE_DIR = os.path.join(HERE, ".trace")


class StopWindow(Exception):
    """Raised by the window callback to end the service loop."""


class Fail(Exception):
    """The run cannot give a result (exit code 2, nothing printed)."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def merge(base: dict, patch: dict) -> dict:
    out = dict(base)
    for k, v in patch.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def load_cell(name: str, override: dict) -> dict:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Fail(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    mine = [m for m in bench["end_to_end"] + bench["per_layer"]
            if name in m.get("workloads", [name])]
    return {
        "cell": cell,
        "config": merge(load_json(os.path.join(ROOT, entry["file"])),
                        override.get("config", {})),
        "traffic": merge(load_json(os.path.join(
            HERE, "traffic", cell["traffic"] + ".json")),
            override.get("traffic", {})),
        "limits": load_json(os.path.join(HERE, "limits", name + ".json")),
        "end_to_end": [m for m in mine if m in bench["end_to_end"]],
        "per_layer": [m for m in mine if m in bench["per_layer"]],
    }


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def sub_seeds(seed: int, n: int) -> list[int]:
    """Independent 31-bit seeds derived from any whole number."""
    import numpy as np
    return [int(s) % (2 ** 31) for s in
            np.random.SeedSequence(seed).generate_state(n)]


def host_times() -> dict | None:
    """The host's CPU seconds stolen by the hypervisor and waiting on I/O,
    summed over its CPUs (``/proc/stat``); None where there is no such
    file."""
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
    except OSError:
        return None
    hz = os.sysconf("SC_CLK_TCK")
    return {"iowait_s": int(fields[5]) / hz, "steal_s": int(fields[8]) / hz}


class CompileCount:
    """Compilations and persistent-cache hits, from JAX's own events;
    those inside the measured window are counted apart."""

    def __init__(self, jax):
        self.in_window = False
        self.compiles = [0, 0]
        self.cache_hits = [0, 0]
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles[self.in_window] += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits[self.in_window] += 1


def make_actor(jax, widths: list[int], final_scale: float, seed: int):
    """The actor's weights, drawn on the device in one jitted call: the
    program's initialisation (He-normal, last layer scaled) with small
    non-zero biases, so the reference comparison covers them too."""
    import jax.numpy as jnp

    def init(key):
        layers = []
        for i, (a, b) in enumerate(zip(widths[:-1], widths[1:])):
            key, kw, kb = jax.random.split(key, 3)
            s = final_scale if i == len(widths) - 2 else 1.0
            layers.append({
                "w": jax.random.normal(kw, (a, b), jnp.float32)
                * (s * (2.0 / a) ** 0.5),
                "b": jax.random.normal(kb, (b,), jnp.float32) * (0.1 * s)})
        return layers

    return jax.block_until_ready(jax.jit(init)(jax.random.PRNGKey(seed)))


def predictor_init(params: dict, seed: int) -> dict:
    """Initial runtime-predictor weights drawn from the seed with the
    program's initialisation (scaled normal hidden layers, zero heads)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    out = {}
    for k, v in params.items():
        if k in ("w1", "w2"):
            out[k] = (rng.standard_normal(v.shape) / v.shape[0] ** 0.5
                      ).astype(np.float32)
        else:
            out[k] = np.zeros(v.shape, np.float32)
    return out


class Loop:
    """The service loop's window callback: warm-up, then the window.

    Every stamp is the host clock less the harness's own time inside the
    window (the mid-window state save)."""

    def __init__(self, ctx: dict, mid_at: float):
        self.ctx = ctx
        self.phase = "warm"
        self.stamps: list[float] = []
        #: the main thread's and the process's CPU seconds at each stamp
        self.cpu: list[tuple] = []
        self.edges: list[tuple] = []
        self.depth = [None, None]
        self.span = None
        self.paused = 0.0
        #: seconds into the window after which the state is saved again
        self.mid_at = mid_at

    def __call__(self, engine, t, windows):
        ctx = self.ctx
        if self.phase == "warm":
            if t >= ctx["traffic"]["warm_until_s"]:
                self.begin(engine, t)
            return
        now = time.perf_counter() - self.paused
        self.stamps.append(now)
        self.cpu.append((time.thread_time(), time.process_time()))
        self.edges.append((t, ctx["counters"](engine),
                           len(ctx["start_log"].starts)))
        d = len(engine.pending)
        self.depth = [d if self.depth[0] is None else min(self.depth[0], d),
                      d if self.depth[1] is None else max(self.depth[1], d)]
        if now - self.stamps[0] >= ctx["seconds"]:
            self.end(engine, t)
            raise StopWindow
        if self.mid_at is not None and now - self.stamps[0] >= self.mid_at:
            self.mid_at = None
            self.paused += self.save(engine, t)
        if self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = ctx["annotation"]("window")
            self.span.__enter__()

    def save(self, engine, t) -> float:
        """Save the engine's state for a reference replay from edge ``t``
        (the sampler's wrappers come off for the pickle); returns the
        seconds it took."""
        ctx = self.ctx
        t0 = time.perf_counter()
        with ctx["annotation"]("harness_save"):
            ctx["sampler"].detach()
            blob = engine.save_state()
            ctx["sampler"].attach()
        ctx["segments"].append({
            "blob": blob, "t_from": t,
            "starts_from": len(ctx["start_log"].starts),
            "ticks_from": len(ctx["tick_log"].ticks)})
        return time.perf_counter() - t0

    def begin(self, engine, t):
        """Warm every shape the window uses, save the engine's state for
        the reference, and open the window."""
        ctx = self.ctx
        ctx["marks"]["warmup_replay"] = time.perf_counter()
        ctx["warm_shapes"](engine)
        ctx["marks"]["shape_warmup"] = time.perf_counter()
        ctx["save_s"] = self.save(engine, t)
        ctx["t_from"] = t
        ctx["starts_from"] = ctx["segments"][0]["starts_from"]
        ctx["at_start"] = ctx["counters"](engine)
        ctx["errors_at_start"] = ctx["hook_errors"](engine)
        gc.collect()
        for part in ctx["active"]:
            part.active = True
        ctx["compiles"].in_window = True
        ctx["host_at_start"] = host_times()
        if ctx["trace"]:
            ctx["start_trace"]()
            self.span = ctx["annotation"]("window")
            self.span.__enter__()
        self.phase = "window"
        self.stamps.append(time.perf_counter())
        self.cpu.append((time.thread_time(), time.process_time()))
        self.edges.append((t, ctx["at_start"], ctx["starts_from"]))

    def end(self, engine, t):
        ctx = self.ctx
        for part in ctx["active"]:
            part.active = False
        ctx["compiles"].in_window = False
        ctx["host_at_end"] = host_times()
        ctx["at_end"] = ctx["counters"](engine)
        ctx["errors_at_end"] = ctx["hook_errors"](engine)
        ctx["t_to"] = t
        if self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None
            ctx["stop_trace"]()


def run(args) -> dict:
    override = json.loads(args.override) if args.override else {}
    spec = load_cell(args.workload, override)
    cell, cfg, traffic = spec["cell"], spec["config"], spec["traffic"]
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise Fail(f"no program under {SRC}")

    import jax
    devices = jax.devices()
    dev = devices[0]
    marks = {"device_start": time.perf_counter()}
    if not args.rehearse and (dev.platform != "tpu"
                              or len(devices) < cell["chips"]):
        raise Fail(f"needs {cell['chips']} TPU chip(s), found "
                   f"{len(devices)} {dev.platform} device(s)")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileCount(jax)

    sys.path.insert(0, SRC)
    import numpy as np

    from repro.core.agent import PPOAgent, PPOConfig
    from repro.core.env import RLPrioritizer
    from repro.core.types import ClusterSpec, Job, NodeSpec
    from repro.kernels.batch_score import BucketedScorer
    from repro.predict import RuntimePredictor
    from repro.sched import MultiHooks, run_stream, wrap_tenancy
    from repro.sched.engine import SchedulerEngine

    import guarantees
    import recorders
    import reference
    import streams

    stream_seed, sample_seed, mid_seed = sub_seeds(args.seed, 3)
    sch = cfg["scheduler"]
    actor_cfg = sch["actor"]
    # the deployment's policy: the same weights in every run
    weight_seed = sub_seeds(actor_cfg["weights_seed"], 1)[0]
    cols = streams.stream_columns(cfg["jobs"], traffic, stream_seed)
    jobs = streams.make_jobs(cols, Job)
    cluster = streams.make_cluster(cfg["cluster"], ClusterSpec, NodeSpec)

    agent = PPOAgent(PPOConfig(seed=weight_seed))
    actor = make_actor(jax, actor_cfg["widths"], actor_cfg["final_scale"],
                       weight_seed)
    agent.params = dict(agent.params, actor=actor)
    scorer = BucketedScorer(actor)
    pri = RLPrioritizer(agent, explore=False, deep_scorer=scorer)
    pcfg = sch["predictor"]
    predictor = RuntimePredictor(assist=pcfg["assist"],
                                 use_kernel=pcfg["use_kernel"])
    predictor.mlp.params = predictor_init(predictor.mlp.params, weight_seed)
    marks["inputs"] = time.perf_counter()

    sampler = recorders.Sampler(agent, scorer, predictor, sample_seed,
                                traffic["samples"])
    sampler.attach()
    start_log = recorders.StartLog()
    tick_log = recorders.TickLog(start_log)
    gc_clock = recorders.GcClock()
    hooks = [start_log, tick_log]
    active = [sampler, tick_log, gc_clock]
    slots = actor_cfg["queue_slots"]
    clock = tail = None
    if args.trace:
        clock = recorders.LayerClock()
        tail = recorders.TracedPrioritizer(pri, slots)
        pri = tail
        hooks.append(clock)
        active += [clock, tail]
    if sch.get("vc_quotas"):
        # the program's VC-quota gate, outermost in every kind of run, so
        # that the service loop feeds it the engine's starts and finishes
        pri = wrap_tenancy(pri, vc_quotas={
            int(vc): float(q) for vc, q in sch["vc_quotas"].items()})

    rows_lo, rows_hi = traffic["window_rows"]
    k_look = sch["lookahead_k"]

    def warm_shapes(engine):
        """Every batch size the window's decisions hand the device: the
        actor's one shape, the deep scorer's tail rows and the
        predictor's window and lookahead rows."""
        ov = np.zeros((slots, actor_cfg["widths"][0]), np.float32)
        mask = np.zeros((slots,), np.float32)
        mask[0] = 1.0
        agent.act(ov, None, mask, explore=False, record=False)
        for n in range(max(rows_lo - slots, 1), rows_hi - slots + 1):
            scorer.score(np.zeros((n, actor_cfg["widths"][0]), np.float32))
        if pcfg["assist"]:
            sizes = sorted(set(range(1, k_look + 1))
                           | set(range(max(rows_lo, 1), rows_hi + 1)))
            for n in sizes:
                predictor.predict_quantiles(jobs[:n], engine)

    log_dir = os.path.join(TRACE_DIR, str(os.getpid()))

    def start_trace():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)

    ctx = {
        "traffic": traffic, "seconds": args.seconds, "trace": args.trace,
        "marks": marks,
        "sampler": sampler, "start_log": start_log, "tick_log": tick_log,
        "active": active, "segments": [],
        "compiles": compiles, "counters": recorders.counters,
        "hook_errors": lambda eng: sum(
            sum(h.error_counts.values()) for h in eng.hooks
            if isinstance(h, MultiHooks)),
        "warm_shapes": warm_shapes, "start_trace": start_trace,
        "stop_trace": jax.profiler.stop_trace,
        "annotation": jax.profiler.TraceAnnotation,
    }
    # the second save, a quarter to three quarters into the window
    mid_u = np.random.default_rng(mid_seed).random()
    loop = Loop(ctx, args.seconds * (0.25 + 0.5 * mid_u))
    dry = True
    try:
        run_stream(cluster, jobs, pri,
                   rescan_interval=sch["rescan_interval_s"],
                   allocator=sch["allocator"], backfill=sch["backfill"],
                   lookahead_k=k_look, queue_window=sch["queue_window"],
                   chunked_submit=sch["chunked_submit"], hooks=tuple(hooks),
                   on_window=loop, predictor=predictor)
    except StopWindow:
        dry = False
    sampler.detach()
    gc.callbacks.remove(gc_clock)
    if not ctx["segments"]:
        raise Fail("the stream ended before the warm-up did; "
                   "traffic num_jobs is too small")
    stats = dev.memory_stats() or {}
    ctx.update(stamps=loop.stamps, edges=loop.edges, submit=cols["submit"],
               setup_s=loop.stamps[0] - T_PROCESS - ctx["save_s"],
               memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)))
    del jobs[:]
    gc.collect()

    # ---- guarantees and reference comparison, after the window ------------
    t_ref = time.perf_counter()
    broken, checked = guarantees.violations(
        cfg["cluster"], cols, start_log.starts, start_log.decisions, sch)
    guarantees_s = time.perf_counter() - t_ref
    numbers = {"guarantee_violations": sum(broken.values())}
    samples = {k: [it for it in getattr(sampler, k).items if it is not None]
               for k in ("actor", "scorer", "predictor")}
    numbers.update(reference.device_numbers(samples, actor))
    fresh = streams.make_jobs(cols, Job)
    replays = []
    segments = ctx.pop("segments")
    for seg in segments:
        later = [j for j in fresh if j.submit_time > seg["t_from"]]
        replays.append(reference.replay_schedule(
            SchedulerEngine, MultiHooks, seg["blob"], later,
            [t for t, _, _ in loop.edges if t > seg["t_from"]],
            [(c, n - seg["starts_from"])
             for c, n in tick_log.ticks[seg["ticks_from"]:]],
            start_log.starts[seg["starts_from"]:],
            traffic["reference_decisions"], recorders.StartLog))
        del seg["blob"]
    numbers["schedule_mismatches"] = sum(r["mismatches"] for r in replays)
    # a device number is compared where the window sampled its entry
    # point; each kind of device call the cell makes must be sampled
    need = {"actor": True, "scorer": rows_lo > slots,
            "predictor": pcfg["assist"]}
    number_of = {"actor": "actor_order_gap", "scorer": "scorer_rel_err",
                 "predictor": "predictor_rel_err"}
    limits = {"guarantee_violations": 0}
    limits.update({k: v for k, v in spec["limits"].items()
                   if k not in number_of.values()})
    for kind, name in number_of.items():
        if samples[kind] and name in spec["limits"]:
            limits[name] = spec["limits"][name]
        if need[kind]:
            numbers[f"{kind}_samples_missing"] = int(not samples[kind])
            limits[f"{kind}_samples_missing"] = 0
    # the window must end while arrivals are still due
    numbers["stream_ran_dry"] = int(dry or ctx["t_to"] >= cols["submit"][-1])
    limits["stream_ran_dry"] = 0
    reference_s = time.perf_counter() - t_ref

    # ---- metrics -----------------------------------------------------------
    ctx.update(config=cfg, device_kind=dev.device_kind, layer_clock=clock,
               tail=tail, trace_result=None)
    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devices),
                     "memory_peak_bytes": ctx["memory_peak_bytes"]}
    breakdown = None
    if args.trace:
        import xplane
        path = xplane.find_xplane(log_dir)
        red = xplane.reduce_xplane(path, {
            "policy_mlp": r"^policy_mlp(\.\d+)?$"}) if path else None
        shutil.rmtree(log_dir, ignore_errors=True)
        ctx["trace_result"] = red
        if red is not None:
            result_device.update(busy_s=red["busy_s"],
                                 window_s=red["window_s"])
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
    wanted = spec["per_layer"] if args.trace else spec["end_to_end"]
    metrics = {}
    for m in wanted:
        v = load_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    attempted = ctx["at_end"][0] - ctx["at_start"][0]
    failed = (ctx["at_end"][2] - ctx["at_start"][2]
              + ctx["errors_at_end"] - ctx["errors_at_start"])
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    gaps = np.diff(loop.stamps) * 1e3
    wall = loop.stamps[-1] - loop.stamps[0]
    sub = ctx["submit"]
    arrived = int(np.searchsorted(sub, ctx["t_to"], side="right")
                  - np.searchsorted(sub, ctx["t_from"], side="right"))
    k = int(np.argmax(gaps))
    longest = {"ms": round(float(gaps[k]), 3),
               "thread_cpu_ms": round(
                   1e3 * (loop.cpu[k + 1][0] - loop.cpu[k][0]), 3),
               "process_cpu_ms": round(
                   1e3 * (loop.cpu[k + 1][1] - loop.cpu[k][1]), 3),
               "at_s": round(loop.stamps[k] - loop.stamps[0], 3),
               "counters": [a - b for a, b in zip(loop.edges[k + 1][1],
                                                  loop.edges[k][1])]}
    info = {
        "windows": len(loop.stamps) - 1,
        "decisions": attempted,
        "jobs_per_s": arrived / wall,
        "window_ms_quantiles": [round(float(np.percentile(gaps, q)), 3)
                                for q in (5, 25, 50, 75, 90, 95, 99, 100)],
        "window_depth_min": loop.depth[0], "window_depth_max": loop.depth[1],
        "guarantees_broken": broken, "guarantees_checked": checked,
        "longest_window": longest,
        "host_in_window": None if ctx["host_at_start"] is None else {
            k: round(ctx["host_at_end"][k] - v, 3)
            for k, v in ctx["host_at_start"].items()},
        "gc_in_window": {"passes": gc_clock.count,
                         "seconds": [round(x, 4) for x in gc_clock.seconds],
                         "longest_s": round(gc_clock.longest, 4)},
        "window_rows_declared": [rows_lo, rows_hi],
        "compiles_in_window": compiles.compiles[1],
        "cache_hits_in_window": compiles.cache_hits[1],
        "compiles_in_setup": compiles.compiles[0],
        "cache_hits_in_setup": compiles.cache_hits[0],
        "setup_parts_s": dict(zip(marks, np.diff(
            [T_PROCESS] + list(marks.values())).round(3).tolist())),
        "state_save_s": ctx["save_s"], "mid_save_s": loop.paused,
        "reference_s": reference_s, "guarantees_s": guarantees_s,
        "window_edges": [ctx["t_from"], ctx["t_to"]],
        "reference_from": [s["t_from"] for s in segments],
        "reference_events": [r["events"] for r in replays],
        "reference_decisions": [r["decisions"] for r in replays],
        "reference_starts": [r["starts"] for r in replays],
        "samples": {k: len(v) for k, v in samples.items()},
        "samples_seen": {k: getattr(sampler, k).seen for k in samples},
    }
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": result_device}
    if args.rehearse:
        out["rehearsal"] = True
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return {"result": out, "info": info, "samples": samples, "actor": actor,
            "numbers": numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--override", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    try:
        if args.override and not args.rehearse:
            raise Fail("--override is for rehearsals only")
        res = run(args)
    except Fail as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    out, info = res["result"], res["info"]
    for k, v in info.items():
        print(f"info {k}: {v}", file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
