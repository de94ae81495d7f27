#!/usr/bin/env python3
"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 perfbench/calibrate.py --workload <cell> --seconds <s> --seeds <n> [--first <seed>]

For each seed, one run of the cell in this process (the compile cache and
the device are shared), then the compared numbers of the program and of
the controls: the same sampled device inputs pushed through the reference
MLP one precision step lower on the device (``bfloat16`` operands with
float32 accumulation, and float32 in three bfloat16 passes, ``high``).
Unlike the benchmark's runs, which share one base stream and one set of
actor weights, each seed here also draws the traffic's base stream and the
actor's weights, so that the readings cover many streams and policies.
Prints one JSON line per seed and a summary: the lower reading of each
number (the largest the program gives) and its upper reading (the
smallest a control gives).  Not run by the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first", type=int, default=4_100_000_000)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--override", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    spec = importlib.util.spec_from_file_location(
        "perfbench_run", os.path.join(HERE, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    import reference

    controls = {p: reference.control_forward(p) for p in ("bfloat16", "high")}
    base = json.loads(args.override) if args.override else {}
    rows = []
    for i in range(args.seeds):
        seed = args.first + 7919 * i
        stream, weights = run.sub_seeds(seed, 5)[3:]
        drawn = {"traffic": {"base_seed": stream}, "config": {
            "scheduler": {"actor": {"weights_seed": weights}}}}
        res = run.run(argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds, trace=0,
            rehearse=args.rehearse,
            override=json.dumps(run.merge(drawn, base))))
        row = {"seed": seed, "base_seed": stream, "weights_seed": weights,
               "correct": res["result"]["correct"],
               "program": {k: v["value"] for k, v in
                           res["result"]["checks"].items()}}
        for name, fwd in controls.items():
            row[name] = reference.device_numbers(res["samples"], res["actor"],
                                                 forward=fwd)
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {}
    for k in rows[0]["bfloat16"]:
        if k not in rows[0]["program"]:
            continue        # the cell makes no such device call
        summary[k] = {
            "lower": max(r["program"][k] for r in rows),
            **{f"upper_{c}": min(r[c][k] for r in rows) for c in controls}}
    for k in ("schedule_mismatches", "guarantee_violations"):
        summary[k] = {"lower": max(r["program"][k] for r in rows)}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
