"""Shared benchmark helpers: agent training/caching, evaluation, CSV rows,
and the provenance stamp every ``BENCH_*.json`` artifact carries."""
from __future__ import annotations

import datetime
import os
import platform
import subprocess
import sys
import time


from repro.core import improvement
from repro.core.trainer import RLTuneTrainer, TrainerConfig
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()

ART = os.path.join(os.path.dirname(os.path.abspath(__file__)), "artifacts")
AGENTS = os.path.join(ART, "agents")
os.makedirs(AGENTS, exist_ok=True)

# benchmark scale knobs (CPU container budget); REPRO_BENCH_SCALE=full for
# paper-scale runs (100 batches/epoch, batch 256)
SCALE = os.environ.get("REPRO_BENCH_SCALE", "quick")
TRAIN_BATCHES = int(os.environ.get("REPRO_BENCH_TRAIN_BATCHES",
                                   {"quick": 60, "full": 100}[SCALE]))
BATCH_SIZE = {"quick": 128, "full": 256}[SCALE]
EVAL_BATCHES = int(os.environ.get("REPRO_BENCH_EVAL_BATCHES",
                                  {"quick": 4, "full": 10}[SCALE]))


#: set by ``benchmarks.run --rss`` (or exported directly): benches that
#: consult ``rss_enabled()`` stamp ``peak_rss_mb`` into every bench point
RSS_ENV = "REPRO_BENCH_RSS"


def rss_enabled() -> bool:
    return os.environ.get(RSS_ENV, "") not in ("", "0")


def peak_rss_mb() -> float | None:
    """Peak resident set size of this process in MB (None where the
    ``resource`` module is unavailable, e.g. non-POSIX hosts).  Linux
    reports ``ru_maxrss`` in KB, macOS in bytes — normalized here so the
    stamped JSON is comparable across hosts."""
    try:
        import resource
    except ImportError:
        return None
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    if sys.platform == "darwin":
        peak /= 1024.0
    return round(peak / 1024.0, 1)


def _git_sha() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
        if out.returncode == 0:
            return out.stdout.strip()
    except OSError:
        pass
    return "unknown"


def provenance(seed: int | None = None) -> dict:
    """Provenance stamp for ``BENCH_*.json`` artifacts: enough to answer
    "what code, what toolchain, what knobs, when" for any number a later
    PR compares against.  ``jax`` is imported guarded — CPU-only containers
    without it still produce a valid stamp."""
    try:
        import jax
        jax_version = jax.__version__
    except Exception:  # noqa: BLE001 — any import-time failure reads as absent
        jax_version = None
    try:
        import numpy
        numpy_version = numpy.__version__
    except Exception:  # noqa: BLE001
        numpy_version = None
    stamp = {
        "git_sha": _git_sha(),
        "python": sys.version.split()[0],
        "jax": jax_version,
        "numpy": numpy_version,
        "host": platform.node() or "unknown",
        "machine": platform.machine(),
        "wall_clock_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "scale": SCALE,
    }
    if seed is not None:
        stamp["seed"] = seed
    return stamp


def agent_path(trace: str, policy: str, metric: str, variant: str) -> str:
    return os.path.join(AGENTS, f"{trace}__{policy}__{metric}__{variant}")


def get_trainer(trace: str, policy: str, metric: str = "wait",
                variant: str = "pro", train: bool = True,
                seed: int = 0) -> RLTuneTrainer:
    """Train (or load cached) RLTune agent for (trace, base policy, metric)."""
    from repro.ckpt.checkpoint import latest_step, load_checkpoint, \
        save_checkpoint
    cfg = TrainerConfig(trace=trace, base_policy=policy, metric=metric,
                        variant=variant, batch_size=BATCH_SIZE,
                        batches_per_epoch=TRAIN_BATCHES, epochs=1, seed=seed)
    tr = RLTuneTrainer(cfg)
    path = agent_path(trace, policy, metric, variant)
    if train:
        if latest_step(path) is not None:
            state, _ = load_checkpoint(path, tr.agent.state_dict())
            tr.agent.load_state_dict(state)
        else:
            t0 = time.time()
            tr.train()
            save_checkpoint(path, 1, tr.agent.state_dict())
            print(f"#   trained {trace}/{policy}/{metric}/{variant} "
                  f"in {time.time() - t0:.0f}s")
    return tr


def eval_pair(tr: RLTuneTrainer, num_batches: int = 0) -> dict:
    ev = tr.evaluate(num_batches=num_batches or EVAL_BATCHES,
                     batch_size=BATCH_SIZE)
    out = {}
    for m in ("wait", "jct", "bsld", "util"):
        out[m] = (ev["base"][m], ev["rl"][m],
                  improvement(ev["base"][m], ev["rl"][m],
                              lower_is_better=(m != "util")))
    return out


def row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"
