"""The plain reference a served request is compared with, and the
comparison that decides `correct`.

For each sampled request the reference samples again from the same
initial noise (drawn from the request's seed as the serving path draws it),
with the DDIM update, classifier-free guidance and the cache policy's
reuse arithmetic written out here in float64 on the host, and the backbone
(`backbones/<family>.py`) run in float32 at HIGHEST matmul precision.  A
cached request must differ from an uncached one by design, so the
reference replays the compute/reuse decision the server took at each step,
as a language-model check replays the served tokens, and checks the
decisions on their own:

  metric_gap         the worst gap, over delta, between the accumulated
                     TeaCache distance the server reported for a step
                     (`TickEvent.metric`) and the reference's own
  decision_mismatch  served decisions that break their rule: step 0
                     computes; a TeaCache step computes iff the server's
                     own reported distance reached delta; FasterCacheCFG
                     refreshes the uncond branch iff step % interval == 0;
                     an uncached request computes both branches every step

Reuse arithmetic, per step s of an n-step request:

  TeaCache        d = distance(signal_s, signal_{s-1}); compute iff s == 0
                  or acc + d >= delta; computing caches eps_c and zeroes
                  acc, reusing returns the cached eps_c and sets acc += d
  FasterCacheCFG  compute the uncond branch iff s % interval == 0 (caching
                  prev2 <- prev <- eps_u); otherwise
                  eps_u = prev + w (prev - prev2), w = s / (n - 1)
  guidance        eps = eps_u + scale (eps_c - eps_u)
  DDIM            x0 = (x - sqrt(1 - a_t) eps) / sqrt(a_t),
                  x  <- sqrt(a_next) x0 + sqrt(1 - a_next) eps,
                  a_next = 1 after the last step

The control is the same replay with every matrix product rounded to
float8 (e4m3, one scale per operand), the precision step below the
configuration's bfloat16.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: largest finite float8 e4m3 value
_F8_MAX = 448.0


def dot_highest(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _to_f8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _F8_MAX
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def dot_f8(a, b):
    return jnp.matmul(_to_f8(a), _to_f8(b),
                      precision=jax.lax.Precision.HIGHEST)


DOTS = {"reference": dot_highest, "control": dot_f8}


# -- noise schedule (the engine's linear DDPM schedule) -------------------

def alpha_bars(schedule: dict) -> np.ndarray:
    """The float32 alpha-bar table of a linear beta schedule (betas built
    in float64, cast to float32; the product accumulated in float64)."""
    if schedule["kind"] != "linear":
        raise ValueError(f"unknown noise schedule {schedule['kind']!r}")
    betas = np.linspace(schedule["beta_min"], schedule["beta_max"],
                        schedule["T"], dtype=np.float64).astype(np.float32)
    alphas = (1.0 - betas).astype(np.float32)
    return np.cumprod(alphas, dtype=np.float64).astype(np.float32)


def spaced(T: int, n: int) -> np.ndarray:
    """n sampling timesteps from T-1 down to 0, evenly spaced."""
    return np.linspace(T - 1, 0, n).round().astype(np.int64)


# -- signal distances of the signal-thresholded policies ------------------

def _rel_l1(sig, prev, frames):
    del frames
    num = jnp.sum(jnp.abs(sig - prev))
    return num / (jnp.sum(jnp.abs(sig)) + jnp.sum(jnp.abs(prev)) + 1e-8)


def _rel_l1_frame_max(sig, prev, frames):
    s = sig.reshape(frames, -1)
    p = prev.reshape(frames, -1)
    num = jnp.sum(jnp.abs(s - p), axis=1)
    den = jnp.sum(jnp.abs(s), axis=1) + jnp.sum(jnp.abs(p), axis=1) + 1e-8
    return jnp.max(num / den)


#: policies whose compute decision thresholds a signal distance
SIGNAL_DISTANCES = {"teacache": _rel_l1, "teacache_video": _rel_l1_frame_max}
#: policies that compute on every step
ALWAYS = {"none"}


@dataclass
class Served:
    """What the server did for one request: its inputs, the decision it
    took at each step, and the sample it returned."""
    rid: int
    noise_seed: int
    num_steps: int
    label: int
    cfg_scale: float
    slot: int                   # the engine slot it was served in
    want_cond: np.ndarray       # (num_steps,) bool
    want_uncond: np.ndarray     # (num_steps,) bool
    metric: np.ndarray          # (num_steps,) float32, the server's
    #                             accumulated distance; nan where none
    x0: np.ndarray              # (T, in_dim)


@dataclass
class Replay:
    x0: np.ndarray
    metric: np.ndarray          # (num_steps,) accumulated distance, nan
    #                             at step 0 and for unthresholded policies
    decision_mismatch: int      # served decisions that break their rule


class Reference:
    """Jitted reference pieces for one model and cache policy."""

    def __init__(self, backbone, model: dict, params, policy: dict,
                 schedule: dict, dot: Callable):
        self.model = model
        self.params = params
        self.policy = policy
        self.abar = alpha_bars(schedule)
        self.T = schedule["T"]
        self.null = model["dit_num_classes"]
        self.frames = max(model.get("dit_num_frames", 0), 1)
        self._fwd = jax.jit(partial(backbone.forward, dot, cfg=model))
        self._sig = jax.jit(partial(backbone.signal, dot, cfg=model))
        name = policy["policy"]
        if name not in SIGNAL_DISTANCES and name not in ALWAYS:
            raise ValueError(f"the reference has no replay of policy {name!r}")
        cfg_name = policy.get("cfg_policy")
        if cfg_name not in (None, "fastercache_cfg"):
            raise ValueError(f"the reference has no replay of CFG policy "
                             f"{cfg_name!r}")
        if cfg_name and policy.get("cfg_args", {}).get("mode", "extrapolate") \
                != "extrapolate":
            raise ValueError("the reference replays FasterCacheCFG's "
                             "extrapolate mode only")
        dist = SIGNAL_DISTANCES.get(name)
        self._dist = (None if dist is None
                      else jax.jit(partial(dist, frames=self.frames)))

    def noise(self, seed: int, rid: int, shape) -> np.ndarray:
        key = jax.random.fold_in(jax.random.PRNGKey(seed), rid)
        return np.asarray(jax.random.normal(key, shape), np.float64)

    def replay(self, req: Served) -> Replay:
        n = req.num_steps
        shape = req.x0.shape
        ts = spaced(self.T, n)
        x = self.noise(req.noise_seed, req.rid, shape)
        guided = req.cfg_scale > 0.0
        pol = self.policy
        delta = float(pol.get("args", {}).get("delta", 0.1))
        interval = int(pol.get("cfg_args", {}).get("interval", 1))
        has_cfg_policy = pol.get("cfg_policy") is not None
        delta32 = np.float32(delta)
        metric = np.full(n, np.nan)
        mismatch = 0
        cache_c = prev_u = prev2_u = None
        prev_sig, acc = None, 0.0
        for s in range(n):
            t = np.asarray([ts[s]], np.float32)
            want_c = bool(req.want_cond[s])
            want_u = bool(req.want_uncond[s])
            # -- check the server's decisions ---------------------------
            if self._dist is not None:
                sig = self._sig(self.params, jnp.asarray(x[None], jnp.float32),
                                jnp.asarray(t), jnp.asarray([req.label]))
                if s == 0:
                    mismatch += int(not want_c)
                else:
                    m = acc + float(self._dist(sig, prev_sig))
                    metric[s] = m
                    served = np.float32(req.metric[s])
                    mismatch += int(want_c != bool(served >= delta32))
                    acc = 0.0 if want_c else m
                prev_sig = sig
            else:
                mismatch += int(not want_c)
            if guided:
                ref_u = (s % interval == 0) if has_cfg_policy else True
                mismatch += int(want_u != ref_u)
            else:
                mismatch += int(want_u)
            # -- the step, following the server's decisions -------------
            rows, labels = [], []
            if want_c:
                labels.append(req.label)
            if guided and want_u:
                labels.append(self.null)
            if labels:
                B = len(labels)
                out = np.asarray(self._fwd(
                    self.params,
                    jnp.asarray(np.broadcast_to(x, (B,) + shape), jnp.float32),
                    jnp.asarray(np.repeat(t, B)),
                    jnp.asarray(labels, jnp.int32)), np.float64)
                rows = list(out)
            if want_c:
                cache_c = rows.pop(0)
            if cache_c is None:
                raise ValueError(f"request {req.rid}: no cond output to "
                                 f"reuse at step {s}")
            eps = cache_c
            if guided:
                if want_u:
                    prev2_u, prev_u = prev_u, rows.pop(0)
                    eps_u = prev_u
                else:
                    if prev_u is None:
                        raise ValueError(f"request {req.rid}: no uncond "
                                         f"output to reuse at step {s}")
                    w = s / max(n - 1, 1)
                    p2 = prev2_u if prev2_u is not None else 0.0
                    eps_u = prev_u + w * (prev_u - p2)
                eps = eps_u + req.cfg_scale * (cache_c - eps_u)
            a_t = float(self.abar[ts[s]])
            a_n = float(self.abar[ts[s + 1]]) if s + 1 < n else 1.0
            x0 = (x - np.sqrt(1.0 - a_t) * eps) / np.sqrt(a_t)
            x = np.sqrt(a_n) * x0 + np.sqrt(1.0 - a_n) * eps
        return Replay(x0=x, metric=metric, decision_mismatch=mismatch)

    def metric_gap(self, a, b) -> float:
        """Worst gap, over delta, between two per-step distance series."""
        delta = float(self.policy.get("args", {}).get("delta", 0.1))
        d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
        d = d[~np.isnan(d)]
        return float(d.max()) / delta if d.size else 0.0


def rel_l2(x, ref) -> float:
    x = np.asarray(x, np.float64).ravel()
    ref = np.asarray(ref, np.float64).ravel()
    return float(np.linalg.norm(x - ref) / max(np.linalg.norm(ref), 1e-30))


def compare(ref: Reference, sample: Sequence[Served],
            control: Optional[Reference] = None) -> Dict[str, float]:
    """The numbers compared for `sample`: the worst relative L2 gap of a
    served sample from the reference's, the worst metric gap and the count
    of decision mismatches.  With `control`, also the control's readings
    of the first two, put in the server's place: the worst gap of its
    sample, and of its accumulated distance, from the reference's
    (`control_x0_rel_l2`, `control_metric_gap`)."""
    out = {"x0_rel_l2": 0.0, "metric_gap": 0.0, "decision_mismatch": 0}
    if control is not None:
        out["control_x0_rel_l2"] = 0.0
        out["control_metric_gap"] = 0.0
    for req in sample:
        r = ref.replay(req)
        out["x0_rel_l2"] = max(out["x0_rel_l2"], rel_l2(req.x0, r.x0))
        out["metric_gap"] = max(out["metric_gap"],
                                ref.metric_gap(req.metric, r.metric))
        out["decision_mismatch"] += r.decision_mismatch
        if control is not None:
            c = control.replay(req)
            out["control_x0_rel_l2"] = max(out["control_x0_rel_l2"],
                                           rel_l2(c.x0, r.x0))
            out["control_metric_gap"] = max(out["control_metric_gap"],
                                            ref.metric_gap(c.metric,
                                                           r.metric))
    return out


def draw_sample(candidates: List[Served], k: int, seed: int) -> List[Served]:
    """`k` finished requests drawn from the seed: the longest, then one
    from each slot not yet in the sample, then any, each in seeded order."""
    if not candidates:
        return []
    rng = np.random.default_rng([seed, 7])
    order = [int(i) for i in rng.permutation(len(candidates))]
    longest = max(order, key=lambda i: candidates[i].num_steps)
    picked = [longest]
    slots = {candidates[longest].slot}
    for i in order:
        if i not in picked and candidates[i].slot not in slots:
            picked.append(i)
            slots.add(candidates[i].slot)
    picked += [i for i in order if i not in picked]
    return [candidates[i] for i in sorted(picked[:k])]
