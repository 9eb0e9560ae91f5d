"""The one traffic generator: turns a mix file (`bench/traffic/<mix>.json`)
and a seed into a list of requests.

A mix file holds parameters only:

  arrivals       "poisson" (open loop at `rate_per_s`) or "backlog" (every
                 request due at once; the driver keeps `depth` queued)
  rate_per_s     offered rate of a poisson mix
  depth          requests kept waiting in the admission queue (backlog)
  steps          {"<num_steps>": share, ...}: the step-budget mix
  cfg_scale      guidance scale; every request is guided
  traffic_seed   seed of the arrival times and step budgets

A poisson mix's inter-arrival gaps are i.i.d. exponential at `rate_per_s`
and every request's step budget is drawn i.i.d. from `steps`, both from
the mix's own `traffic_seed`: every run offers the same arrivals and the
same sizes.  The run's seed draws what the requests carry: class labels
(uniform over the configuration's classes) and noise seeds.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

#: largest noise seed handed to the program (a 31-bit PRNG seed)
_NOISE_SEED_MAX = 2**31 - 1

_KEYS = {"policy", "arrivals", "rate_per_s", "depth", "steps", "cfg_scale",
         "traffic_seed", "warmup_s", "drain_s"}


@dataclass(frozen=True)
class Request:
    rid: int
    due_s: float          # offset from the start of the traffic
    num_steps: int
    label: int
    cfg_scale: float
    noise_seed: int


def _streams(seed: int, k: int) -> List[np.random.Generator]:
    """`k` independent generators from one seed: each quantity has its own
    stream, so the first requests are the same whatever `n` is asked."""
    return [np.random.default_rng(s)
            for s in np.random.SeedSequence(seed).spawn(k)]


def generate(mix: dict, seed: int, n: int, num_classes: int) -> List[Request]:
    """The first `n` requests of `mix` under `seed`, in arrival order."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    unknown = set(mix) - _KEYS
    if unknown:
        raise ValueError(f"unknown mix keys {sorted(unknown)}")
    arrivals = mix["arrivals"]
    gap_rng, step_rng = _streams(int(mix["traffic_seed"]), 2)
    if arrivals == "poisson":
        rate = float(mix["rate_per_s"])
        if rate <= 0:
            raise ValueError(f"rate_per_s must be positive, got {rate}")
        due = np.cumsum(gap_rng.exponential(1.0 / rate, size=n))
    elif arrivals == "backlog":
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown arrivals {arrivals!r}")
    budgets = [int(k) for k in mix["steps"]]
    shares = np.asarray([float(v) for v in mix["steps"].values()])
    if (shares < 0).any() or shares.sum() <= 0:
        raise ValueError(f"step shares must be non-negative with a positive "
                         f"sum: {mix['steps']}")
    steps = step_rng.choice(budgets, size=n, p=shares / shares.sum())
    label_rng, noise_rng = _streams(seed, 2)
    labels = label_rng.integers(0, num_classes, size=n)
    seeds = noise_rng.integers(0, _NOISE_SEED_MAX, size=n)
    scale = float(mix["cfg_scale"])
    return [Request(rid=i, due_s=float(due[i]), num_steps=int(steps[i]),
                    label=int(labels[i]), cfg_scale=scale,
                    noise_seed=int(seeds[i]))
            for i in range(n)]
