"""Cost model linking the scheduler to the DL platform substrate.

Job runtimes for platform-generated traces are derived from the per-arch
analytic roofline terms (``repro.launch.roofline``): a training job of
`steps` steps on `chips` chips of a given GPU/TPU SKU takes
steps x max(compute, memory) x (ref_chips / chips) / sku_speed  seconds.
This closes the loop: RLTune schedules the same architectures whose
distributed execution the substrate lowers.
"""
from __future__ import annotations

import numpy as np

from repro.core.types import Job

# relative throughput of cluster SKUs vs the roofline reference chip (v5e)
SKU_SPEED = {"v5e": 1.0, "V100": 0.63, "P100": 0.24, "T4": 0.33,
             "K80": 0.11, "M40": 0.15, "any": 0.5}


def step_time(arch: str, shape: str = "train_4k", chips: int = 256,
              sku: str = "v5e") -> float:
    """Roofline-bound step time (s) for (arch, shape) on `chips` chips."""
    from repro.configs import get_config
    from repro.launch.roofline import analytic_cost, roofline_terms
    from repro.models.lm import LM
    cfg = get_config(arch)
    ana = analytic_cost(cfg, shape, chips=256, model=LM(cfg))
    terms = roofline_terms(ana["flops_per_chip"], ana["hbm_bytes_per_chip"],
                           0.0)
    bound = max(terms["compute_s"], terms["memory_s"])
    return bound * 256 / max(chips, 1) / SKU_SPEED.get(sku, 0.5)


def platform_job_runtime(arch: str, num_gpus: int, sku: str,
                         steps: int, shape: str = "train_4k") -> float:
    """Wall seconds for a training job of `steps` steps on num_gpus of sku."""
    return steps * step_time(arch, shape, chips=num_gpus, sku=sku)


def generate_platform_trace(num_jobs: int, seed: int = 0,
                            arrival_rate: float = 0.03) -> list[Job]:
    """A trace whose jobs are the assigned architectures with roofline-derived
    runtimes (alternative to the statistical Philly/Helios/Alibaba profiles)."""
    from repro.configs import ALL_ARCHS
    rng = np.random.default_rng(seed)
    jobs: list[Job] = []
    t = 0.0
    for i in range(num_jobs):
        t += float(rng.exponential(1.0 / arrival_rate))
        arch = str(rng.choice(ALL_ARCHS))
        num_gpus = int(rng.choice([1, 2, 4, 8, 16], p=[.35, .25, .2, .15, .05]))
        steps = int(rng.lognormal(4.0, 1.0))
        rt = float(np.clip(platform_job_runtime(arch, num_gpus, "V100", steps),
                           60.0, 7 * 86400.0))
        est = rt * float(rng.lognormal(0.0, 0.5))
        jobs.append(Job(job_id=i, user=int(rng.integers(0, 64)),
                        submit_time=t, runtime=rt, est_runtime=est,
                        num_gpus=num_gpus, gpu_type="any", arch=arch))
    return jobs
