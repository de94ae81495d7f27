"""Job-stream generator: one general generator driven by data files.

A configuration file (``configs/<name>.json``) gives the cluster and the
job profile of a deployment; a traffic file (``traffic/<name>.json``)
gives how the stream is shaped over that profile.  The same seed always
gives the same stream.

The profile model is that of the published trace analyses, as the
program's own synthetic trace generator also reads them: a two-state
Markov-modulated Poisson process of arrivals (calm / burst, switching per
arrival), lognormal runtimes clipped to [30 s, max_runtime], user runtime
estimates with lognormal noise, Zipf-like user popularity, and
categorical GPU-demand and GPU-type mixes, and each job's virtual cluster
(VC): uniform over ``NUM_VCS`` ids, or drawn from the profile's optional
``vc_share``, ``[[vc, share], ...]`` (a multi-tenant deployment's demand
per VC; shares are normalised).  It is kept here, vectorized,
so that the benchmark's traffic does not move when the program's
generator does.

Traffic shapers (each optional, all in the traffic file):

- ``rate_share``: the profile's arrival rate is multiplied by this share;
- ``crowd``: ``{"jobs": n, "span_s": s}`` adds n profile jobs whose submit
  instants are uniform over [0, s] (a flash crowd);
- ``backlog``: ``{"jobs": n, "at_s": t}`` adds n profile jobs all submitted
  at instant t (the queue of a saturated virtual cluster);

Submit instants are floored to whole seconds (``RESOLUTION_S``), as a
cluster log records them (Slurm and the published traces log whole
seconds); arrivals in one second reach the scheduler as one event.

Every run seed gets the same set of jobs and the same set of submit
instants, drawn from the traffic file's ``base_seed``; the run seed only
permutes which job takes which instant after the warm-up instant
``warm_until_s``, within consecutive blocks of ``SHUFFLE_BLOCK`` arrivals.
The warm-up, and so the cluster's state when the measured window opens, is
the same in every run, and the load over time is the same too: the seed
changes the order of the work, not its amount.
"""
from __future__ import annotations

import math

import numpy as np

#: number of virtual-cluster ids a job is drawn from where the profile
#: states no ``vc_share`` (uniform)
NUM_VCS = 5
#: grid of the submit instants, in seconds
RESOLUTION_S = 1.0
#: the run seed reorders arrivals within consecutive blocks of this many
SHUFFLE_BLOCK = 32


def _arrivals(rng: np.random.Generator, prof: dict, n: int,
              share: float) -> np.ndarray:
    """Submit instants of ``n`` MMPP arrivals starting at t = 0."""
    base = float(prof["arrival_rate"]) * share
    u = rng.random(n)
    bursty = np.empty(n, dtype=bool)
    state = False
    p_burst, p_calm = float(prof["burst_prob"]), float(prof["calm_prob"])
    for i in range(n):
        # the gap before arrival i is drawn in the state before it switches
        bursty[i] = state
        state = (u[i] >= p_calm) if state else (u[i] < p_burst)
    rate = np.where(bursty, base * float(prof["burst_factor"]), base)
    return np.cumsum(rng.exponential(1.0, n) / rate)


def _profile_jobs(rng: np.random.Generator, prof: dict, n: int) -> dict:
    """Per-job columns (sizes, runtimes, owners) of ``n`` profile jobs."""
    sigma = float(prof["runtime_sigma"])
    mu = math.log(float(prof["runtime_mean"])) - 0.5 * sigma * sigma
    max_rt = float(prof["max_runtime"])
    runtime = np.clip(rng.lognormal(mu, sigma, n), 30.0, max_rt)
    est = np.clip(runtime * rng.lognormal(0.0, float(prof["est_noise_sigma"]),
                                          n), 30.0, 2.0 * max_rt)
    users = int(prof["num_users"])
    user_w = 1.0 / np.arange(1, users + 1) ** 1.1
    demand, dprob = zip(*prof["gpu_demand"])
    types, tprob = zip(*prof["gpu_types"])
    cols = {
        "runtime": runtime,
        "est": est,
        "user": rng.choice(users, size=n, p=user_w / user_w.sum()),
        "gpus": rng.choice(np.asarray(demand), size=n,
                           p=np.asarray(dprob) / sum(dprob)),
        "gpu_type": rng.choice(np.asarray(types), size=n,
                               p=np.asarray(tprob) / sum(tprob)),
    }
    # the VC is drawn last: a vc_share changes no other column of these jobs
    share = prof.get("vc_share")
    if share:
        vcs, vprob = zip(*share)
        cols["vc"] = rng.choice(np.asarray(vcs, dtype=np.int64), size=n,
                                p=np.asarray(vprob) / sum(vprob))
    else:
        cols["vc"] = rng.integers(0, NUM_VCS, size=n)
    return cols


def stream_columns(prof: dict, traffic: dict, seed: int) -> dict:
    """The stream as columns sorted by submit instant; ``job_id`` is the
    position in that order."""
    rng = np.random.default_rng(int(traffic["base_seed"]))
    n = int(traffic["num_jobs"])
    submit = [_arrivals(rng, prof, n, float(traffic.get("rate_share", 1.0)))]
    parts = [_profile_jobs(rng, prof, n)]
    crowd = traffic.get("crowd")
    if crowd:
        k = int(crowd["jobs"])
        submit.append(np.sort(rng.uniform(0.0, float(crowd["span_s"]), k)))
        parts.append(_profile_jobs(rng, prof, k))
    backlog = traffic.get("backlog")
    if backlog:
        k = int(backlog["jobs"])
        submit.append(np.full(k, float(backlog["at_s"])))
        parts.append(_profile_jobs(rng, prof, k))
    t = np.floor(np.concatenate(submit) / RESOLUTION_S) * RESOLUTION_S
    order = np.argsort(t, kind="stable")
    cols = {key: np.concatenate([p[key] for p in parts])[order]
            for key in parts[0]}
    t = t[order]
    first = int(np.searchsorted(t, float(traffic["warm_until_s"]),
                                side="right"))
    perm = np.arange(t.size)
    perm[first:] = first + _block_permutation(
        np.random.default_rng(seed), t.size - first, SHUFFLE_BLOCK)
    cols = {key: col[perm] for key, col in cols.items()}
    cols["submit"] = t
    cols["job_id"] = np.arange(t.size)
    return cols


def _block_permutation(rng: np.random.Generator, n: int,
                       block: int) -> np.ndarray:
    """A permutation of range(n) that moves items only within consecutive
    blocks of ``block``."""
    perm = np.arange(n)
    for lo in range(0, n, block):
        rng.shuffle(perm[lo:lo + block])
    return perm


def make_jobs(cols: dict, job_cls) -> list:
    """Instantiate the program's job records from stream columns."""
    return [job_cls(job_id=int(i), user=int(u), submit_time=float(s),
                    runtime=float(r), est_runtime=float(e), num_gpus=int(g),
                    gpu_type=str(ty), vc=int(v))
            for i, u, s, r, e, g, ty, v in zip(
                cols["job_id"], cols["user"], cols["submit"],
                cols["runtime"], cols["est"], cols["gpus"],
                cols["gpu_type"], cols["vc"])]


def make_cluster(cluster: dict, spec_cls, node_cls):
    """The program's cluster spec from a configuration's node groups."""
    nodes = []
    for grp in cluster["node_groups"]:
        for _ in range(int(grp["count"])):
            nodes.append(node_cls(len(nodes), grp["gpu_type"],
                                  int(grp["gpus"]), int(grp["cpus"]),
                                  float(grp["mem_gb"]), float(grp["speed"])))
    return spec_cls(nodes=nodes, name=cluster["name"])
