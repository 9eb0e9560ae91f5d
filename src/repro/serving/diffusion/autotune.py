"""SLA-driven cache-policy autotuning for the diffusion serving engine.

The policy zoo (repro.core.POLICY_REGISTRY) trades quality for compute along
method-specific hyperparameters; which point is right depends on the traffic
class being served ("interactive" preview traffic tolerates lower PSNR for
latency; "quality" traffic does not).  The autotuner sweeps candidate
(policy, hyperparams) pairs on a small calibration batch against the exact
(uncached) trajectory and picks, per traffic class, the cheapest candidate
that still meets the SLA:

    minimize   compute_fraction                 (~ 1/speedup, survey §III-B)
    subject to PSNR(x0_policy, x0_exact) >= sla.min_psnr
               est_latency <= sla.max_latency_ms     (when step times given)

Falling back to the highest-PSNR candidate when nothing is feasible keeps
the server serving rather than erroring on an over-tight SLA.

CFG-aware tuning: with `cfg_scale > 0` the calibration reference is the
exact two-branch guided trajectory and each candidate is additionally swept
over `cfg_intervals` — unconditional-branch reuse intervals (None = naive
two-branch; N = FasterCacheCFG(interval=N)).  The minimized cost becomes the
*row-weighted* compute fraction (cond computes + uncond computes) / (2 T),
i.e. the fraction of backbone rows a guided request actually dispatches.

Latency is priced in actual backbone rows: the row-compacted engine
dispatches exactly the rows the pool's schedules want, so the per-request
estimate is  T * (occupancy * rows_per_step * ms_per_row + tick_overhead_ms)
with rows_per_step = cond fraction + uncond fraction and `occupancy` the
busy-slot count sharing each tick (phase alignment puts a homogeneous pool's
refreshes on the same ticks).  Feed it
`row_time_ms=ServingTelemetry.row_time_ms()` from a prior serving run; the
older `step_time_ms` tick-kind pricing (which charged a whole-pool tick even
for a 1-row refresh) is kept as a fallback for dense-engine measurements.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core import CachePolicy, FasterCacheCFG, make_policy
from repro.core.metrics import psnr
from repro.diffusion import ddim_step, linear_schedule, sample
from repro.diffusion.pipeline import CachedDenoiser, cfg_denoise_fn


@dataclass(frozen=True)
class SLA:
    """Per-traffic-class serving objective."""
    name: str = "default"
    min_psnr: float = 20.0           # quality floor vs the exact trajectory
    max_latency_ms: Optional[float] = None  # per-request budget (optional)


@dataclass
class TunedPolicy:
    """Autotuner output: a constructible policy choice + its measurements."""
    policy_name: str
    kwargs: Dict = field(default_factory=dict)
    psnr: float = 0.0
    #: minimized cost: cond compute fraction for unguided tuning, the
    #: row-weighted (cond + uncond) / 2 fraction for guided tuning
    compute_fraction: float = 1.0
    est_latency_ms: Optional[float] = None
    feasible: bool = True
    #: guided tuning only: FasterCacheCFG reuse interval (None = naive
    #: two-branch) and the resulting uncond-branch compute fraction
    cfg_interval: Optional[int] = None
    uncond_compute_fraction: float = 0.0
    #: cond-branch compute fraction alone (== compute_fraction for unguided
    #: tuning); kept separately so `price_and_pick` can re-price the rows a
    #: candidate gathers per step without re-running the quality sweep
    cond_compute_fraction: float = 1.0
    #: True when the serving engine can plan every tick on the host (both
    #: branches' want_compute are step-only): no fused want pass, no device
    #: sync per tick.  State-dependent policies (TeaCache & co) pay that
    #: sync, which `price_and_pick` charges via its `plan_ms` surcharge.
    static_plan: bool = True

    def make(self) -> CachePolicy:
        return make_policy(self.policy_name, **self.kwargs)

    def make_cfg_policy(self, num_steps: int) -> Optional[CachePolicy]:
        """The tuned uncond-branch gate for DiffusionServingEngine /
        CachedDenoiser, or None for naive two-branch guidance."""
        if self.cfg_interval is None:
            return None
        return FasterCacheCFG(self.cfg_interval, num_steps)

    @property
    def align(self) -> int:
        """Phase-alignment interval for the serving scheduler: the lcm of
        the two branch intervals so their refreshes land on shared ticks."""
        a = max(int(self.kwargs.get("interval", 1)), 1)
        b = max(int(self.cfg_interval or 1), 1)
        return a * b // math.gcd(a, b)


#: default sweep: one representative per taxonomy branch, two operating
#: points for the interval-scheduled families
DEFAULT_CANDIDATES: List[Tuple[str, Dict]] = [
    ("none", {}),
    ("fora", {"interval": 2}),
    ("fora", {"interval": 4}),
    ("taylorseer", {"interval": 2, "order": 1}),
    ("taylorseer", {"interval": 4, "order": 2}),
    ("teacache", {"delta": 0.1}),
    ("teacache", {"delta": 0.3}),
    ("magcache", {"delta": 0.1}),
    ("freqca", {"interval": 4}),
]


def _plans_on_host(policy: CachePolicy, num_steps: int) -> bool:
    """Mirror of the serving engine's static-plan probe: True when
    want_compute is a pure function of the step index, i.e. the engine
    will plan ticks host-side with no per-tick device sync."""
    try:
        for s in range(num_steps):
            bool(policy.want_compute(None, s, None))
        return True
    except Exception:
        return False


def _measured_compute_fraction(policy: CachePolicy, state, num_steps: int) -> float:
    """Computes issued / steps, from whichever counter the policy keeps."""
    pol = state.get("policy", {}) if isinstance(state, dict) else {}
    if isinstance(pol, dict):
        for key in ("n_compute", "n_valid"):
            if key in pol:
                return float(np.asarray(pol[key])) / max(num_steps, 1)
    sched = policy.static_schedule(num_steps)
    if sched is not None:
        return sum(map(bool, sched)) / max(num_steps, 1)
    return 1.0


def calibration_reference(params, cfg, num_steps: int, batch: int = 1,
                          seed: int = 0, noise_schedule=None,
                          cfg_scale: float = 0.0, class_label: int = 0):
    """Exact (uncached) calibration trajectory shared by all candidates.

    With cfg_scale > 0 the reference is the exact two-branch guided
    trajectory, so candidate PSNR measures guided-output fidelity."""
    sched = noise_schedule or linear_schedule(1000)
    ts = sched.spaced(num_steps)
    xT = jax.random.normal(jax.random.PRNGKey(seed),
                           (batch, cfg.dit_tokens, cfg.dit_in_dim))
    exact, _ = sample(cfg_denoise_fn(params, cfg, cfg_scale, class_label),
                      xT, ts, sched, step_fn=ddim_step)
    return sched, ts, xT, np.asarray(exact)


def evaluate_candidate(name: str, kwargs: Dict, params, cfg, sched, ts, xT,
                       exact: np.ndarray, cfg_scale: float = 0.0,
                       cfg_interval: Optional[int] = None,
                       class_label: int = 0) -> Tuple[float, float, float]:
    """Run one candidate on the calibration trajectory.

    Returns (psnr_db, cond_compute_fraction, uncond_compute_fraction)."""
    policy = make_policy(name, **kwargs)
    cfg_pol = (FasterCacheCFG(cfg_interval, len(ts))
               if (cfg_scale > 0.0 and cfg_interval is not None) else None)
    den = CachedDenoiser(params, cfg, policy, cfg_scale=cfg_scale,
                         cfg_policy=cfg_pol, class_label=class_label)
    x0, state = sample(den, xT, ts, sched, step_fn=ddim_step,
                       denoiser_state=den.init_state(xT.shape[0]))
    q = float(psnr(np.asarray(x0), exact))
    cf = _measured_compute_fraction(policy, state, len(ts))
    if cfg_scale <= 0.0:
        cf_u = 0.0
    elif cfg_pol is None:
        cf_u = 1.0                      # naive: uncond recomputes every step
    else:
        sched_u = cfg_pol.static_schedule(len(ts))
        cf_u = sum(map(bool, sched_u)) / max(len(ts), 1)
    return q, cf, cf_u


def sweep_candidates(params, cfg,
                     candidates: Optional[Sequence[Tuple[str, Dict]]] = None,
                     num_steps: int = 16, batch: int = 1, seed: int = 0,
                     noise_schedule=None, cfg_scale: float = 0.0,
                     cfg_intervals: Sequence[Optional[int]] = (None,),
                     verbose: bool = False) -> List[TunedPolicy]:
    """Quality sweep: run every candidate on the calibration trajectory.

    Measures ONLY traffic-independent quantities — PSNR vs the exact
    trajectory and per-branch compute fractions.  No SLA judgement, no
    latency: those depend on live row pricing and pool occupancy, which is
    `price_and_pick`'s job.  This split is what makes online retuning cheap:
    the control plane sweeps once at startup and re-prices the cached list
    (host-side arithmetic over ~10 entries) on every retune window instead
    of re-running trajectories."""
    candidates = list(candidates if candidates is not None
                      else DEFAULT_CANDIDATES)
    cfg_ivs = list(cfg_intervals) if cfg_scale > 0.0 else [None]
    sched, ts, xT, exact = calibration_reference(
        params, cfg, num_steps, batch, seed, noise_schedule,
        cfg_scale=cfg_scale)

    evaluated: List[TunedPolicy] = []
    for name, kwargs in candidates:
        # resolve the full hyperparameters here so TunedPolicy.make()
        # reconstructs exactly what was calibrated (magcache sizes its
        # gamma curve from num_steps)
        kwargs = dict(kwargs)
        kwargs.setdefault("num_steps", num_steps)
        host_plan = _plans_on_host(make_policy(name, **kwargs), num_steps)
        for ci in cfg_ivs:
            q, cf, cf_u = evaluate_candidate(
                name, kwargs, params, cfg, sched, ts, xT, exact,
                cfg_scale=cfg_scale, cfg_interval=ci)
            # guided cost = fraction of backbone rows dispatched per step
            cost = (cf + cf_u) / 2.0 if cfg_scale > 0.0 else cf
            # the engine plans on the host only when BOTH branches admit a
            # step-only schedule (ci=None means an all-True host plan)
            static = host_plan and (
                ci is None
                or _plans_on_host(FasterCacheCFG(ci, num_steps), num_steps))
            evaluated.append(TunedPolicy(name, dict(kwargs), psnr=q,
                                         compute_fraction=cost,
                                         cfg_interval=ci,
                                         uncond_compute_fraction=cf_u,
                                         cond_compute_fraction=cf,
                                         static_plan=static))
            if verbose:
                tag = f" cfg_iv={ci}" if cfg_scale > 0.0 else ""
                print(f"  {name:12s} {kwargs}{tag} "
                      f"psnr={q:6.2f}dB cf={cost:.3f}")
    return evaluated


def price_and_pick(evaluated: Sequence[TunedPolicy], sla: SLA,
                   num_steps: int = 16,
                   step_time_ms: Optional[Tuple[float, float]] = None,
                   row_time_ms: Optional[Tuple[float, float]] = None,
                   occupancy: int = 1,
                   plan_ms: float = 0.0,
                   verbose: bool = False,
                   registry=None) -> TunedPolicy:
    """Price swept candidates against live timings and pick for the SLA.

    registry: optional repro.obs MetricsRegistry — each pricing run lands
    in its event ring (winner, feasible count, timing inputs) so retune
    decisions are auditable alongside the serving metrics.

    Pure host-side arithmetic over the `sweep_candidates` output — cheap
    enough to run on every control-plane retune window with fresh
    `row_time_ms` / `occupancy` from the sliding telemetry window.  With
    row pricing the pick minimizes estimated latency (quality breaks
    ties); without timings it falls back to compute fraction.  Falls back
    to the highest-PSNR candidate (marked infeasible) when nothing meets
    the SLA, so the server keeps serving on an over-tight objective.

    plan_ms: measured host cost per tick of planning on the device (the
    carried plan's read, or the fused want pass + its device sync on a
    session's first tick and on admission ticks;
    `TelemetryWindow.plan_time_ms()`), charged per step to candidates
    without a host-side static plan.  Row counts alone misprice
    state-dependent policies — a TeaCache tick that skips every row still
    pays for its device plan — and this surcharge is what
    lets the online tuner prefer a calibrated static schedule over a
    dynamic policy with fewer rows but slower wall-clock ticks."""
    priced: List[TunedPolicy] = []
    for t in evaluated:
        # rows this candidate gathers per step in the compacted engine
        rows_per_step = t.cond_compute_fraction + t.uncond_compute_fraction
        lat = None
        if row_time_ms is not None:
            t_row, t_tick = row_time_ms
            lat = num_steps * (max(occupancy, 1) * rows_per_step * t_row
                               + t_tick)
            if not t.static_plan:
                lat += num_steps * max(plan_ms, 0.0)
        elif step_time_ms is not None:
            t_full, t_skip = step_time_ms
            cost = t.compute_fraction
            lat = num_steps * (cost * t_full + (1.0 - cost) * t_skip)
        ok = t.psnr >= sla.min_psnr and (
            lat is None or sla.max_latency_ms is None
            or lat <= sla.max_latency_ms)
        priced.append(replace(t, est_latency_ms=lat, feasible=ok))
        if verbose:
            tag = (f" cfg_iv={t.cfg_interval}"
                   if t.cfg_interval is not None else "")
            lat_s = f" lat={lat:.1f}ms" if lat is not None else ""
            print(f"  [{sla.name}] {t.policy_name:12s} {t.kwargs}{tag} "
                  f"psnr={t.psnr:6.2f}dB cf={t.compute_fraction:.3f}"
                  f"{lat_s} {'ok' if ok else 'infeasible'}")

    feasible = [t for t in priced if t.feasible]
    if feasible:
        if row_time_ms is not None:
            # cheapest feasible by estimated wall-clock (rows + plan
            # surcharge); quality breaks ties.  Without the surcharge this
            # ordering coincides with compute_fraction, so the objective
            # only *diverges* when a candidate needs device-planned ticks.
            pick = min(feasible,
                       key=lambda t: (t.est_latency_ms, -t.psnr))
        else:
            # no timings: cheapest feasible by rows; quality breaks ties
            pick = min(feasible, key=lambda t: (t.compute_fraction, -t.psnr))
    else:
        # nothing meets the SLA: serve the closest-to-exact candidate
        best = max(priced, key=lambda t: t.psnr)
        pick = replace(best, feasible=False)
    if registry is not None:
        registry.event(
            "autotune.price_and_pick", sla=sla.name,
            picked=pick.policy_name, feasible=pick.feasible,
            n_candidates=len(priced), n_feasible=len(feasible),
            est_latency_ms=pick.est_latency_ms,
            row_time_ms=row_time_ms, occupancy=occupancy, plan_ms=plan_ms)
    return pick


def autotune(params, cfg, sla: SLA,
             candidates: Optional[Sequence[Tuple[str, Dict]]] = None,
             num_steps: int = 16, batch: int = 1, seed: int = 0,
             noise_schedule=None,
             step_time_ms: Optional[Tuple[float, float]] = None,
             row_time_ms: Optional[Tuple[float, float]] = None,
             occupancy: int = 1,
             cfg_scale: float = 0.0,
             cfg_intervals: Sequence[Optional[int]] = (None,),
             verbose: bool = False) -> TunedPolicy:
    """Sweep candidates against `sla` on a calibration batch.

    row_time_ms: measured (ms_per_backbone_row, skip_tick_ms) from a prior
    serving run — `ServingTelemetry.row_time_ms()` — prices a candidate's
    latency by backbone rows: a request waits for its whole tick, and with
    phase-aligned admission a homogeneous pool's co-resident slots gather
    rows on the same ticks, so the per-step estimate is
    `occupancy * rows_per_step * ms_per_row + skip_tick_ms` with
    rows_per_step = cond fraction + uncond fraction.  Pass
    `occupancy=slots` (or the typical busy-slot count) for a loaded pool;
    the default 1 prices an otherwise-idle engine and UNDER-estimates
    per-request latency under load by roughly the occupancy factor.

    step_time_ms: legacy tick-kind pricing, (backbone_tick_ms, skip_tick_ms)
    from `ServingTelemetry.step_time_ms()` — used only when row_time_ms is
    not given (it charges a whole-pool backbone tick even for a 1-row
    refresh, over-estimating compacted serving).  Without either, only the
    PSNR floor is enforced.

    cfg_scale > 0 tunes for *guided* traffic: every (policy, hyperparams)
    candidate is crossed with `cfg_intervals` (uncond-branch reuse intervals;
    None = naive two-branch) and the minimized compute fraction weights both
    branches' backbone rows.

    Composition of `sweep_candidates` (trajectory quality measurement) and
    `price_and_pick` (SLA pricing) — call those directly to amortize the
    sweep across repeated re-pricings (the online control plane does).
    """
    evaluated = sweep_candidates(
        params, cfg, candidates=candidates, num_steps=num_steps, batch=batch,
        seed=seed, noise_schedule=noise_schedule, cfg_scale=cfg_scale,
        cfg_intervals=cfg_intervals)
    return price_and_pick(evaluated, sla, num_steps=num_steps,
                          step_time_ms=step_time_ms, row_time_ms=row_time_ms,
                          occupancy=occupancy, verbose=verbose)


def autotune_traffic_classes(params, cfg, slas: Mapping[str, SLA],
                             **kw) -> Dict[str, TunedPolicy]:
    """One tuned policy per traffic class (e.g. interactive vs quality)."""
    return {name: autotune(params, cfg, sla, **kw)
            for name, sla in slas.items()}
