"""Benchmark driver — one module per paper table/figure.

Prints human-readable tables plus ``name,us_per_call,derived`` CSV rows at
the end.  Modules may additionally expose a ``JSON_PATH`` machine-readable
artifact (e.g. ``BENCH_streaming.json``) that is listed in the run summary
so cross-PR perf tracking knows where to look.  Module selection:
``python -m benchmarks.run [module ...]`` with modules in {latency, kernels,
roofline, variability, naive, qssf, util, transfer, policies, streaming,
federation, rl_streaming, autoscaling, preemption, chaos, scale_curve,
prediction}.
``--smoke`` runs every selected module that supports it in its fast CI mode
(modules whose ``run`` accepts a ``smoke`` kwarg; others run normally).
``--rss`` stamps peak-RSS (resource.getrusage) into every bench point of
modules that support it.  REPRO_BENCH_SCALE=full for paper-scale runs.

A module that raises marks the whole run failed: remaining modules still
execute (maximum signal per CI run), but the driver exits nonzero so the
pipeline cannot green-light on a half-complete benchmark sweep.
"""
from __future__ import annotations

import inspect
import os
import sys
import time

MODULES = ("latency", "kernels", "roofline", "variability", "naive", "qssf",
           "util", "transfer", "policies", "streaming", "federation",
           "rl_streaming", "autoscaling", "preemption", "chaos",
           "scale_curve", "prediction")


def main() -> None:
    args = sys.argv[1:]
    smoke = "--smoke" in args
    if "--rss" in args:
        # env (not a module global) so benches see it regardless of import
        # order, and standalone `python -m benchmarks.bench_*` matches
        from benchmarks.common import RSS_ENV
        os.environ[RSS_ENV] = "1"
    want = [a for a in args if a not in ("--smoke", "--rss")] or list(MODULES)
    rows: list[str] = []
    artifacts: list[str] = []
    failed: list[str] = []
    t0 = time.time()
    special = {"roofline": "benchmarks.roofline",
               "naive": "benchmarks.bench_naive_vs_pro"}
    for name in want:
        modname = special.get(name, f"benchmarks.bench_{name}")
        mod = __import__(modname, fromlist=["run"])
        print(f"\n{'=' * 72}\n== {name}\n{'=' * 72}")
        t1 = time.time()
        ok = True
        try:
            if smoke and "smoke" in inspect.signature(mod.run).parameters:
                mod.run(rows, smoke=True)
            else:
                mod.run(rows)
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"[bench {name} FAILED] {e!r}")
            rows.append(f"{name}/FAILED,0,{e!r}")
            failed.append(name)
            ok = False
        path = getattr(mod, "JSON_PATH", None)
        # only report the artifact on success — a stale file from a prior
        # run must not be ingested as this run's numbers
        if ok and path and os.path.exists(path):
            artifacts.append(os.path.normpath(path))
        print(f"-- {name} done in {time.time() - t1:.0f}s")

    print(f"\n{'=' * 72}\n== CSV (name,us_per_call,derived)\n{'=' * 72}")
    for r in rows:
        print(r)
    for a in artifacts:
        print(f"# json artifact: {a}")
    print(f"# total bench time {time.time() - t0:.0f}s")
    if failed:
        print(f"# FAILED modules: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
