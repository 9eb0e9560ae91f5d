"""DiffusionServingEngine — step-interleaved continuous batching for
latent generation with per-slot cache states, including classifier-free
guidance with per-slot CFG-branch reuse (FasterCacheCFG, survey §III-C).

Device side, every tick gathers EXACTLY the backbone rows the per-slot
policies want computed this tick (row compaction, the default):

  * Each active slot contributes a cond row iff its main policy wants a
    compute and an uncond row iff it is guided and its CFG policy wants an
    uncond refresh.  The wanted rows are gathered into one compacted batch,
    padded to the next power-of-two bucket, run through the backbone (slot
    axis == batch axis, backbone outside vmap), and scattered back to the
    S-row y_c / y_u layout before the vmapped per-slot policy step — each
    slot still takes its own COMPUTE / REUSE / FORECAST branch (lax.cond
    vmaps to a select), rows that were not gathered arrive as zeros and may
    only reach discarded branches.  One jit program per bucket size (all
    gather/scatter indices are traced), so the program count is bounded by
    log2(2S) + 2 regardless of request mix.
  * A tick with zero wanted rows dispatches the skip program — no backbone
    at all, only forecast/reuse arithmetic.

This is the batch dimension's version of block-level partial computing
(DeepCache / Cache-Me-if-You-Can): a TeaCache pool where one slot fires
dispatches a 1-row bucket, not a whole-pool batch, and a mixed
guided/unguided pool pays per uncond row instead of doubling the batch
whenever any slot refreshes its CFG branch.

`row_compaction=False` restores the dense engine — one of exactly three
whole-pool programs per tick (tick_full over 2S rows, tick_cond_only over S
rows, tick_skip) — kept as the equivalence/benchmark baseline; the
compacted engine must reproduce its per-request outputs exactly
(tests/test_serving_compaction.py).  The tick *kinds* full/cond/skip are
still reported either way; under compaction they classify which branches
the gathered rows came from while the row counters carry the real cost.

Modalities: the engine serves whatever backbone the config selects —
image/audio DiT or the factorized video DiT (`cfg.dit_num_frames > 0`);
latent rows are (cfg.dit_tokens, cfg.dit_in_dim) either way.  One engine
instance hosts ONE modality (token shapes must agree across slots);
repro.modalities.MixedModalityEngine runs several engines as per-modality
sub-pools under one scheduler/telemetry umbrella by driving the
tick-granular `ServeSession` API below instead of the blocking `serve()`.

CFG doubles backbone cost; FasterCacheCFG(interval=N) drops each slot's
uncond row from (N-1)/N of its backbone ticks — serving throughput lands
between 1x and 2x of naive two-branch serving
(benchmarks/bench_serving.py --cfg).  A request's `null_label` may be an
arbitrary conditioning VECTOR (negative prompt) instead of a class id; the
engine threads it through the uncond rows as a per-slot embedding override.

Host side, the SlotScheduler refills finished slots from the admission
queue mid-flight.  Refill resets the slot's combined cache state — main
policy AND CFG branch — to a fresh `init_state` (reset-on-refill): slot
reuse must never leak either cache between requests.  Guided and unguided
requests share one pool; an unguided slot's uncond output is discarded by a
select (never blended), and its `want_uncond` is masked off so pure-unguided
pools never pay for the 2S-row program.

The DDIM update is re-derived here in traced per-slot form (gathered
alpha-bar tables instead of Python-float arithmetic) because slots sit at
different timesteps of *different* step-budget grids within one program.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from types import MappingProxyType
from typing import (Callable, Dict, List, Mapping, Optional, Sequence,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (CachePolicy, SlotBatchedPolicy, cache_state_bytes,
                        make_policy)
from repro.diffusion import NoiseSchedule, linear_schedule
from repro.diffusion.pipeline import slot_compact_denoise_fns, slot_want_fns
from repro.models import dit
from repro.obs.clock import monotonic
from repro.obs.profiling import ProgramIR, ProgramProfile, compile_program
from repro.obs.trace import span

from .scheduler import DiffusionRequest, SlotScheduler
from .telemetry import RequestRecord, ServingTelemetry


def request_noise_key(req: DiffusionRequest):
    """Per-request PRNG key for the initial latent noise.

    Folds the request id into the user seed: requests left at the default
    `seed=0` must still draw *distinct* initial noise (identical seeds once
    made every default request produce the identical sample)."""
    return jax.random.fold_in(jax.random.PRNGKey(req.seed), req.request_id)


def compact_rows(want_c: np.ndarray, want_u: np.ndarray, slots: int):
    """Plan one row-compacted tick from the per-slot want masks.

    Returns (bucket, row_slot, row_uncond, row_dest): the wanted cond rows
    first, then the wanted uncond rows, padded to the next power-of-two
    bucket (capped at the tick's dense batch — `slots` for cond-only ticks,
    `2*slots` otherwise) so the engine compiles at most one tick program per
    bucket size.
    `row_slot[b]` is the source slot of compacted row b, `row_uncond[b]`
    selects the null label, and `row_dest[b]` is the scatter target in the
    (2*slots + 1)-row buffer: cond row of slot i -> i, uncond row -> slots+i,
    padding -> the 2*slots dump row (discarded).  bucket == 0 means a pure
    skip tick (no backbone program at all)."""
    c_rows = np.nonzero(want_c)[0].astype(np.int32)
    u_rows = np.nonzero(want_u)[0].astype(np.int32)
    n = len(c_rows) + len(u_rows)
    if n == 0:
        z = np.zeros((0,), np.int32)
        return 0, z, np.zeros((0,), bool), z
    # capped at this tick's dense batch (S for cond-only ticks, 2S when any
    # uncond row is gathered): for non-power-of-two slot counts the next
    # power of two can overshoot the whole-pool batch, which would make a
    # busy compacted tick dispatch MORE rows than the dense engine
    cap = 2 * slots if len(u_rows) else slots
    bucket = min(1 << (int(n) - 1).bit_length(), cap)
    row_slot = np.zeros((bucket,), np.int32)
    row_uncond = np.zeros((bucket,), bool)
    row_dest = np.full((bucket,), 2 * slots, np.int32)
    row_slot[:len(c_rows)] = c_rows
    row_dest[:len(c_rows)] = c_rows
    row_slot[len(c_rows):n] = u_rows
    row_uncond[len(c_rows):n] = True
    row_dest[len(c_rows):n] = u_rows + slots
    return bucket, row_slot, row_uncond, row_dest


@dataclass
class DiffusionResult:
    """One served request: final latent sample + its telemetry record."""
    request_id: int
    x0: np.ndarray
    record: RequestRecord


@dataclass
class TickEvent:
    """Everything one engine tick decided and produced, for observer hooks.

    ServeSession calls each hook with one TickEvent per tick (after
    harvest), which is how the control plane (repro.serving.control)
    watches a live engine: TelemetryWindow derives sliding-window row
    pricing and occupancy from it, SignalTraceLog records per-slot
    want/metric traces.  All arrays are host-side copies indexed by slot;
    slots not active this tick carry request_id -1.

    `metric` is the per-slot `CachePolicy.want_metric` scalar (the value
    the refresh decision thresholded on; 0 on free slots); None when the
    engine planned the tick from a host-side static schedule (no device
    metric exists).
    `plan_seconds` is the host time spent DECIDING the tick, the
    `engine.plan` phase: for state-dependent policies the read of the plan
    the previous tick program computed, or, on a session's first tick and
    on ticks whose admission refilled a slot, the fused want pass and its
    device_get sync; ~0 for static schedules planned on the host — the
    overhead the online tuner's cost model charges non-static candidates
    per step.  `seconds` is host time from dispatch to
    `block_until_ready` (`engine.dispatch` + `engine.wait`), not a device
    time.  `t_start` is the `monotonic()` reading at which the tick
    began, and `phases` a read-only mapping of the host seconds of each
    phase span that closed before the event was built, in the order they
    ran: every phase ServeSession.tick lists but `engine.hooks`, which
    holds the hook calls themselves (its seconds reach the profiler trace
    and the registry only).
    `latents` is the pre-tick (slots, tokens, in_dim) latent batch — only
    populated when the session was started with `capture_latents=True`
    (it costs a device transfer per tick)."""
    tick: int
    modality: str
    kind: str                       # "full" | "cond" | "skip"
    seconds: float                  # host s, dispatch to block_until_ready
    rows_computed: int
    rows_padding: int
    active: np.ndarray              # (S,) bool
    request_ids: np.ndarray         # (S,) int64, -1 = free slot
    steps: np.ndarray               # (S,) int32 per-slot step index
    tvals: np.ndarray               # (S,) float32 model-facing timesteps
    labels: np.ndarray              # (S,) int32 class conditioning
    guided: np.ndarray              # (S,) bool
    want_cond: np.ndarray           # (S,) bool, after active masking
    want_uncond: np.ndarray         # (S,) bool, after active masking
    plan_seconds: float = 0.0       # host time of the want/plan decision
    metric: Optional[np.ndarray] = None     # (S,) float32 or None
    latents: Optional[np.ndarray] = None    # (S, T, D) pre-tick, opt-in
    admitted: List[DiffusionRequest] = field(default_factory=list)
    finished: List[RequestRecord] = field(default_factory=list)
    t_start: float = 0.0            # monotonic() when the tick began
    phases: Mapping[str, float] = field(default_factory=dict)


#: observer hook signature: called once per tick, must not mutate the engine
TickHook = Callable[[TickEvent], None]


class ServeSession:
    """One in-flight batch of requests, advanced one tick at a time.

    `DiffusionServingEngine.serve()` drives a session to completion; the
    mixed-modality engine (repro.modalities) interleaves the sessions of
    several per-modality sub-pools under one umbrella by calling `tick()`
    round-robin and `finish()` once every session reports `done`."""

    def __init__(self, engine: "DiffusionServingEngine",
                 requests: Sequence[DiffusionRequest],
                 telemetry: Optional[ServingTelemetry] = None,
                 hooks: Optional[Sequence[TickHook]] = None,
                 capture_latents: bool = False,
                 modality: Optional[str] = None,
                 metrics=None):
        for r in requests:
            self._validate(engine, r)
        # per-slot timestep/conditioning tables live on the engine, so two
        # interleaved sessions of one engine would corrupt each other
        if engine._session_active:
            raise RuntimeError(
                "engine already has a session in flight; finish() it first "
                "(use one engine per modality sub-pool, never shared)")
        engine._session_active = True
        self.engine = engine
        self.requests = list(requests)
        #: observer hooks, called once per tick with a TickEvent
        self.hooks: List[TickHook] = list(hooks or ())
        #: copy the pre-tick latent batch into each TickEvent (opt-in:
        #: costs one device transfer per tick; the control plane's probe
        #: logging needs it to replay the backbone offline)
        self.capture_latents = bool(capture_latents)
        #: modality label stamped on TickEvents (an engine hosts ONE
        #: modality); inferred from the first request when not given
        self.modality = (modality if modality is not None
                         else (requests[0].modality if requests else "image"))
        self.tele = telemetry if telemetry is not None else ServingTelemetry()
        self.tele.cache_state_bytes_per_slot = cache_state_bytes(engine._fresh)
        self.tele.start()
        #: opt-in repro.obs.MetricsRegistry — tick paths, scheduler
        #: admission, and request lifecycle publish into it; None costs
        #: nothing (naming: repro_<subsystem>_<metric>_<unit>)
        self.metrics = metrics

        self.sched = SlotScheduler(engine.slots, engine.align)
        if metrics is not None:
            self.sched.bind_metrics(metrics, modality=self.modality)
        now = monotonic
        self.recs: Dict[int, RequestRecord] = {
            r.request_id: RequestRecord(r.request_id, r.num_steps,
                                        r.traffic_class,
                                        cfg_scale=r.cfg_scale,
                                        modality=r.modality,
                                        enqueue_time=now())
            for r in requests}
        self.sched.submit_all(requests)

        T, D = engine.tokens, engine.in_dim
        self.xs = jnp.zeros((engine.slots, T, D), jnp.float32)
        self.states = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None],
                                       (engine.slots,) + a.shape).copy(),
            engine._fresh)
        # device-resident negative-prompt tables: (slots, d_model) is the
        # one per-slot operand that grows with the model, so it is uploaded
        # only when admission changes it, not on every tick
        self._null_vecs = jnp.asarray(engine._null_vecs)
        self._null_mask = jnp.asarray(engine._null_mask)
        # device-resident per-slot cross-attn text tables (K/V + masks for
        # prompt and negative prompt), rebuilt only when admission changes
        # a slot's prompt — text is step-invariant, so every tick reuses
        # them verbatim ({} on text-free engines: zero operand leaves)
        self._txt = engine._build_text_tables()
        # the (want_cond, want_uncond, metric) device triple the last tick
        # program computed for the next tick; None until a tick ran, and
        # always on engines with a host static plan
        self._plan = None
        self.results: Dict[int, DiffusionResult] = {}
        self.ticks = 0
        self._finished = False

    @staticmethod
    def _validate(engine: "DiffusionServingEngine",
                  r: DiffusionRequest) -> None:
        """Reject malformed requests before any work runs, not at admission
        deep inside a tick — same contract as admission itself
        (engine._check_request is the single source of truth)."""
        engine._check_request(r)

    @property
    def done(self) -> bool:
        return self.sched.idle()

    # ------------------------------------------------------------------
    def submit(self, request: DiffusionRequest) -> None:
        """Mid-session admission: enqueue one more request on a live
        session.  It is admitted at the next phase-aligned tick with a free
        slot (reset-on-refill applies exactly as for initial requests).
        This is what lets the control plane keep one session serving an
        open-ended stream instead of batching requests up front."""
        if self._finished:
            raise RuntimeError("session already finished; submit to a new "
                               "session instead")
        if request.request_id in self.recs:
            raise ValueError(f"request id {request.request_id} already "
                             f"submitted to this session")
        self._validate(self.engine, request)
        self.requests.append(request)
        self.recs[request.request_id] = RequestRecord(
            request.request_id, request.num_steps, request.traffic_class,
            cfg_scale=request.cfg_scale, modality=request.modality,
            enqueue_time=monotonic())
        self.sched.submit(request)

    def transfer_queued(self) -> List[DiffusionRequest]:
        """Pop every request still waiting in the admission queue (never
        admitted to a slot) and drop its bookkeeping here, so the caller
        can resubmit it to another session.  The control plane's blue/green
        rollover uses this: in-flight slots drain on this session under the
        policy that admitted them, while the un-admitted backlog follows
        the session that will actually admit it — otherwise a rollover
        would strand the backlog on the outgoing policy."""
        moved = self.sched.queue.pop_many(len(self.sched.queue))
        for r in moved:
            del self.recs[r.request_id]
            self.requests.remove(r)
        return moved

    # ------------------------------------------------------------------
    def tick(self) -> None:
        """One engine tick: refill free slots, plan the wanted rows,
        dispatch the matching program, advance and harvest.

        The tick is one `engine.tick` span holding nine phase spans, one
        after another: admit, prepare, plan, upload, dispatch, wait,
        account, harvest, hooks (each `engine.<phase>`; repro.obs.span).
        The hooks get the first eight in the TickEvent's `phases`; with a
        registry, all nine are published once `engine.hooks` has closed."""
        if self._finished:
            raise RuntimeError("session already finished; the engine's "
                               "per-slot tables may belong to a new session")
        eng, sched, tele = self.engine, self.sched, self.tele
        now = monotonic
        T, D = eng.tokens, eng.in_dim
        phases: Dict[str, float] = {}
        with span("engine.tick", tick=self.ticks,
                  modality=self.modality) as whole:
            # -- refill free slots from the queue (phase-aligned) -------
            with span("engine.admit", phases) as sp:
                admitted = sched.admit(self.ticks)
                for slot, req in admitted:
                    noise = jax.random.normal(request_noise_key(req), (T, D))
                    self.xs, self.states = eng._refill(
                        self.xs, self.states, slot.index, noise, eng._fresh)
                    eng._install_request(slot.index, req)
                    rec = self.recs[req.request_id]
                    rec.admit_time = now()
                    rec.admit_tick = self.ticks
                    rec.slot = slot.index
                if admitted:
                    self._null_vecs = jnp.asarray(eng._null_vecs)
                    self._null_mask = jnp.asarray(eng._null_mask)
                    # one text_kv pass per admission wave (not per tick):
                    # project the newly installed prompt embeddings to
                    # per-slot K/V tables
                    self._txt = eng._build_text_tables()
                sp.count(requests=len(admitted))

            with span("engine.prepare", phases):
                active = np.asarray(sched.active_mask())
                steps = np.asarray(sched.steps(), np.int32)
                idx = np.minimum(steps, eng.max_steps - 1)
                rows = np.arange(eng.slots)
                tvals = eng._tv[rows, idx]
                ab_t = eng._ab[rows, idx]
                ab_n = eng._ab[rows, idx + 1]
                # per-slot trajectory-progress weight for FasterCacheCFG
                cfg_ws = (idx.astype(np.float32)
                          / np.maximum(eng._nsteps - 1, 1))
                # per-slot request ids + optional pre-tick latents,
                # captured BEFORE the device tick / harvest mutate them
                rids = np.asarray([s.request.request_id if s.busy else -1
                                   for s in sched.slots], np.int64)
                latents = (np.asarray(self.xs) if self.capture_latents
                           else None)
            whole.count(active=int(active.sum()))

            with span("engine.plan", phases) as sp:
                # the previous tick program planned this tick, unless
                # admission refilled a slot since
                plan = None if admitted else self._plan
                self._plan = None
                want_c, want_u, metric = eng._plan_all(
                    self.states, idx, self.xs, tvals, plan)
                source = ("static" if not eng._device_plan else
                          "carried" if plan is not None else "want_pass")
                sp.count(on_device=int(metric is not None),
                         carried=int(plan is not None))

            with span("engine.upload", phases) as sp:
                want_c = want_c & active
                want_u = want_u & active
                if metric is not None:
                    # a free slot has no request to measure (and a carried
                    # plan evaluated it at another step than a want pass)
                    metric = np.where(active, metric, np.float32(0.0))
                n_c, n_u = int(want_c.sum()), int(want_u.sum())
                if n_u:
                    kind = "full"      # some slot refreshes its uncond cache
                elif n_c:
                    kind = "cond"      # cond-branch rows only
                else:
                    kind = "skip"
                # rows a dense whole-pool tick of this kind dispatches (the
                # dense engine's actual batch; also what row compaction
                # saves against)
                dense_rows = {"full": 2 * eng.slots, "cond": eng.slots,
                              "skip": 0}[kind]
                host = [idx, tvals, eng._labels, eng._nulls, eng._scales,
                        cfg_ws, ab_t, ab_n]
                if eng._device_plan:
                    # the next tick's timesteps, for the tick program's
                    # own plan of that tick
                    host.append(eng._tv[rows, np.minimum(
                        idx + 1, eng.max_steps - 1)])
                if eng.row_compaction:
                    bucket, *row_plan = compact_rows(want_c, want_u,
                                                     eng.slots)
                    host += row_plan
                else:
                    bucket = dense_rows
                (idx_d, tvals_d, labels_d, nulls_d, scales_d, cfg_ws_d,
                 ab_t_d, ab_n_d, *rest_d) = [jnp.asarray(a) for a in host]
                args = (eng.params, self.states, idx_d, self.xs, tvals_d,
                        labels_d, nulls_d, self._null_vecs, self._null_mask,
                        self._txt, scales_d, cfg_ws_d, ab_t_d, ab_n_d,
                        *rest_d)
                sp.count(arrays=len(host),
                         nbytes=sum(a.nbytes for a in host))

            with span("engine.dispatch", phases, bucket=bucket):
                program = (eng._compact_tick(bucket) if eng.row_compaction
                           else eng._ticks[kind])
                self.xs, self.states, *nxt = program(*args)
                if nxt:
                    # the next tick's plan reaches the host while this
                    # tick's wait runs, not after the next tick's dispatch
                    self._plan = nxt[0]
                    for a in self._plan:
                        a.copy_to_host_async()
            with span("engine.wait", phases):
                self.xs.block_until_ready()
            tick_s = phases["engine.dispatch"] + phases["engine.wait"]

            with span("engine.account", phases):
                if eng.row_compaction:
                    rows_done = n_c + n_u
                    rows_pad = bucket - rows_done
                    tele.record_tick(kind, tick_s,
                                     rows_computed=rows_done,
                                     rows_padding=rows_pad,
                                     rows_saved=dense_rows - rows_done)
                else:
                    rows_done, rows_pad = dense_rows, 0
                    tele.record_tick(kind, tick_s, rows_computed=dense_rows)
                # uncond accounting in rows actually refreshing a CFG
                # cache: a dense full tick used to add `slots`, over-
                # counting inactive and unguided slots into the
                # autotuner's row cost
                tele.uncond_rows_computed += n_u
                tele.uncond_rows_saved += int(
                    (active & eng._guided & ~want_u).sum())
                for slot in sched.slots:
                    if slot.busy and want_c[slot.index]:
                        self.recs[slot.request.request_id].computed_steps += 1
                    if slot.busy and want_u[slot.index]:
                        self.recs[
                            slot.request.request_id].uncond_computed_steps += 1

            # -- advance + harvest finished slots -----------------------
            with span("engine.harvest", phases) as sp:
                sched.advance()
                finished: List[RequestRecord] = []
                for slot, req in sched.harvest():
                    rec = self.recs[req.request_id]
                    rec.finish_time = now()
                    rec.finish_tick = self.ticks + 1
                    tele.finish_request(rec)
                    finished.append(rec)
                    self.results[req.request_id] = DiffusionResult(
                        req.request_id, np.asarray(self.xs[slot.index]), rec)
                sp.count(requests=len(finished))

            with span("engine.hooks", phases):
                if self.hooks:
                    event = TickEvent(
                        tick=self.ticks, modality=self.modality, kind=kind,
                        seconds=tick_s, plan_seconds=phases["engine.plan"],
                        rows_computed=rows_done,
                        rows_padding=rows_pad, active=active,
                        request_ids=rids, steps=steps,
                        tvals=np.asarray(tvals, np.float32),
                        labels=eng._labels.copy(),
                        guided=eng._guided.copy(),
                        want_cond=want_c, want_uncond=want_u,
                        metric=metric, latents=latents,
                        admitted=[req for _, req in admitted],
                        finished=finished, t_start=whole.t0,
                        phases=MappingProxyType(dict(phases)))
                    for hook in self.hooks:
                        hook(event)
            if self.metrics is not None:
                self._publish_tick(kind, tick_s, rows_done, rows_pad,
                                   dense_rows - rows_done
                                   if eng.row_compaction else 0,
                                   n_u, int(active.sum()), len(finished),
                                   phases, source)
            self.ticks += 1

    def _publish_tick(self, kind: str, tick_s: float, rows_done: int,
                      rows_pad: int, rows_saved: int, n_u: int,
                      occupancy: int, finished: int,
                      phases: Mapping[str, float], source: str) -> None:
        """One tick's worth of registry updates (metric names follow
        repro_<subsystem>_<metric>_<unit>, labels carry dimensions),
        made after the tick's `engine.hooks` span closed, so `phases`
        holds all nine.  `source` says where the tick's plan came from:
        "carried" (the previous tick program), "want_pass" or
        "static"."""
        m, mod = self.metrics, self.modality
        phase_s = m.counter("repro_engine_phase_seconds_total",
                            "host seconds of each phase span of "
                            "ServeSession.tick")
        for name, sec in phases.items():
            phase_s.inc(sec, phase=name, modality=mod)
        m.counter("repro_engine_ticks_total",
                  "engine ticks by program kind").inc(
            kind=kind, modality=mod)
        m.counter("repro_engine_plans_total",
                  "tick plans by source: carried from the previous tick "
                  "program, the standalone want pass, or a host static "
                  "schedule").inc(source=source, modality=mod)
        m.counter("repro_engine_tick_seconds_total",
                  "host seconds from dispatch to block_until_ready of "
                  "tick programs").inc(
            tick_s, kind=kind, modality=mod)
        m.counter("repro_engine_rows_computed_total",
                  "backbone rows carrying real per-slot work").inc(
            rows_done, modality=mod)
        m.counter("repro_engine_rows_padding_total",
                  "pow-2 bucket padding rows dispatched").inc(
            rows_pad, modality=mod)
        m.counter("repro_engine_rows_saved_total",
                  "rows a dense whole-pool tick would have added").inc(
            rows_saved, modality=mod)
        m.counter("repro_engine_uncond_rows_computed_total",
                  "uncond rows refreshing a CFG cache").inc(
            n_u, modality=mod)
        m.counter("repro_engine_requests_finished_total",
                  "requests completed").inc(finished, modality=mod)
        m.gauge("repro_engine_occupancy_slots",
                "busy slots at the latest tick").set(occupancy, modality=mod)
        m.histogram("repro_engine_tick_seconds",
                    "host seconds from dispatch to block_until_ready, "
                    "per tick").observe(
            tick_s, modality=mod)

    # ------------------------------------------------------------------
    def finish(self) -> List[DiffusionResult]:
        """Close the session: preempted accounting, telemetry stop, results
        in request order.  Idempotent."""
        if not self._finished:
            # requests cut off before completion (mid-flight or still
            # queued) are reported as preempted, never silently dropped with
            # half-filled records poisoning the latency aggregates
            for r in self.requests:
                if r.request_id not in self.results:
                    self.tele.preempt_request(self.recs[r.request_id])
                    if self.metrics is not None:
                        self.metrics.counter(
                            "repro_engine_requests_preempted_total",
                            "requests cut off before completion").inc(
                            modality=self.modality)
            self.tele.stop()
            self.engine.telemetry = self.tele
            self.engine._session_active = False
            self._finished = True
        return [self.results[r.request_id] for r in self.requests
                if r.request_id in self.results]


class DiffusionServingEngine:
    """Fixed-slot continuous-batching server over one DiT backbone."""

    def __init__(self, params, cfg, policy: Union[CachePolicy, str, None] = None,
                 *, slots: int = 8, max_steps: int = 64,
                 noise_schedule: Optional[NoiseSchedule] = None,
                 align: Optional[int] = None,
                 cfg_policy: Union[CachePolicy, str, None] = None,
                 row_compaction: bool = True,
                 conditioner=None):
        self.params, self.cfg = params, cfg
        self.slots = slots
        self.max_steps = max_steps
        self.row_compaction = bool(row_compaction)
        # text conditioning (T2I/T2V): a repro.conditioning.PromptCache that
        # resolves DiffusionRequest.prompt_tokens at admission; requires a
        # text-enabled config (per-block cross-attention branches)
        self.text_enabled = cfg.dit_text_len > 0
        if conditioner is not None and not self.text_enabled:
            raise ValueError(f"conditioner given but config '{cfg.name}' is "
                             f"not text-enabled (dit_text_len == 0)")
        self.conditioner = conditioner
        self.sched = noise_schedule or linear_schedule(1000)
        # string-built policies get the engine's actual geometry: num_steps
        # for step-indexed curves (magcache), frames for the temporal
        # policies (teacache_video's per-frame reduction must group by the
        # CONFIG's frame count, not the registry default)
        policy_kw = {"num_steps": max_steps}
        if cfg.dit_num_frames > 0:
            policy_kw["frames"] = cfg.dit_num_frames
        if isinstance(policy, str):
            policy = make_policy(policy, **policy_kw)
        self.policy = policy if policy is not None else make_policy("none")
        # uncond-branch gate for guided requests; None = naive two-branch
        # serving (every guided slot recomputes its uncond row each step)
        if isinstance(cfg_policy, str):
            cfg_policy = make_policy(cfg_policy, **policy_kw)
        self.cfg_policy = cfg_policy
        # phase-aligned admission: default to the lcm of the two compute
        # intervals so both branches' refreshes land on shared ticks
        if align is not None:
            self.align = align
        else:
            a = max(int(getattr(self.policy, "interval", 1)), 1)
            b = max(int(getattr(cfg_policy, "interval", 1)), 1) \
                if cfg_policy is not None else 1
            self.align = a * b // math.gcd(a, b)

        # latent row shape for this engine's modality (video folds the frame
        # axis into the token axis: dit_tokens = frames * per-frame patches)
        self.tokens, self.in_dim = cfg.dit_tokens, cfg.dit_in_dim
        T, D = self.tokens, self.in_dim
        self._feat = (1, T, D)                      # per-slot policy feature
        self._sig_shape = (1, T, cfg.d_model)       # TeaCache signal shape
        self.batched = SlotBatchedPolicy(self.policy, slots)
        # every program below takes the model params as its first operand:
        # closed over, jit would bake them into each executable as literals
        (compact_backbone_fn, backbone2_fn, backbone_fn,
         apply_fn) = slot_compact_denoise_fns(cfg, self.policy, cfg_policy)
        # combined per-slot state: main policy branch + uncond CFG branch
        # (an empty dict when cfg_policy is None — NoCachePolicy is stateless)
        uncond_pol = self.cfg_policy
        self._fresh = {
            "policy": self.batched.init_slot_state(
                self._feat, signal_shape=self._sig_shape),
            "cfg": (uncond_pol.init_state(self._feat)
                    if uncond_pol is not None else {}),
        }

        # cond want + uncond want + trace metric in ONE pass — the TeaCache
        # signal is computed over the whole slot batch outside vmap
        # (repro.diffusion.pipeline.slot_want_fns)
        want_all_fn = slot_want_fns(cfg, self.policy, cfg_policy)

        def slot_step(params, states, steps, xs, tvals, labels, scales,
                      cfg_ws, ab_t, ab_n, y_c, y_u, *tv_next):
            """Shared tail of every tick program: vmapped per-slot policy
            step + traced per-slot DDIM update.  Given the next tick's
            timesteps `tv_next` (engines that plan on the device), it also
            returns the next tick's (want_cond, want_uncond, metric): the
            want pass over the updated states and latents at step
            min(steps + 1, max_steps - 1), guided where scale > 0 — valid
            for every slot still busy next tick, as long as no admission
            refills a slot in between."""
            eps, states = jax.vmap(partial(apply_fn, params))(
                states, steps, xs, tvals, labels, scales, cfg_ws, y_c, y_u)
            a_t = ab_t[:, None, None]
            a_n = ab_n[:, None, None]
            x0_hat = (xs - jnp.sqrt(1.0 - a_t) * eps) / jnp.sqrt(a_t)
            x_next = jnp.sqrt(a_n) * x0_hat + jnp.sqrt(1.0 - a_n) * eps
            if not tv_next:
                return x_next, states
            nxt = jnp.minimum(steps + 1, max_steps - 1)
            return x_next, states, want_all_fn(
                params, states, nxt, x_next, tv_next[0], labels,
                scales > 0.0)

        def make_tick(mode: str):
            """Dense whole-pool programs (row_compaction=False baseline):
            the backbone runs OUTSIDE vmap over S or 2S rows.  `txt` is the
            per-slot text-table dict — an EMPTY dict on text-free engines,
            which contributes zero jit operand leaves, so their program
            signature is exactly the pre-text one."""
            def tick(params, states, steps, xs, tvals, labels, nulls,
                     null_vecs, null_mask, txt, scales, cfg_ws, ab_t, ab_n,
                     *tv_next):
                if mode == "full":
                    y_c, y_u = backbone2_fn(params, xs, tvals, labels, nulls,
                                            null_vecs, null_mask, txt=txt)
                elif mode == "cond":
                    y_c = backbone_fn(params, xs, tvals, labels, txt=txt)
                    y_u = jnp.zeros_like(xs)
                else:
                    y_c = y_u = jnp.zeros_like(xs)
                return slot_step(params, states, steps, xs, tvals, labels,
                                 scales, cfg_ws, ab_t, ab_n, y_c, y_u,
                                 *tv_next)
            return jax.jit(tick)

        def make_compact_tick(bucket: int):
            """One parameterized row-compacted program per bucket size: the
            backbone runs over the gathered `bucket`-row batch only; the
            scatter restores the S-row y_c / y_u layout (missing rows zero —
            they only reach branches the per-slot select discards).  All
            index operands are traced, so this compiles once per bucket.
            `tv_next`, on engines that plan on the device, sits between
            `ab_n` and the row plan."""
            def tick(params, states, steps, xs, tvals, labels, nulls,
                     null_vecs, null_mask, txt, scales, cfg_ws, ab_t, ab_n,
                     *rest):
                *tv_next, row_slot, row_uncond, row_dest = rest
                if bucket == 0:
                    y_c = y_u = jnp.zeros_like(xs)
                else:
                    y_c, y_u = compact_backbone_fn(
                        params, xs, tvals, labels, nulls, null_vecs,
                        null_mask, txt, row_slot, row_uncond, row_dest)
                return slot_step(params, states, steps, xs, tvals, labels,
                                 scales, cfg_ws, ab_t, ab_n, y_c, y_u,
                                 *tv_next)
            return jax.jit(tick)

        # program builders are kept either way: repro.analysis.ir re-traces
        # programs through them to capture jaxprs AFTER warmup swapped the
        # tick caches to bare Compiled executables (which carry no jaxpr)
        self._make_compact_tick = make_compact_tick
        self._make_tick = make_tick
        if self.row_compaction:
            self._compact_ticks = {}   # bucket size -> jit'd program (lazy)
            self._ticks = None
        else:
            self._ticks = {kind: make_tick(kind)
                           for kind in ("full", "cond", "skip")}
        # the standalone want pass, for ticks with no carried plan (a
        # session's first tick, and every tick whose admission refilled a
        # slot: a fresh slot's metric needs its new noise's signal)
        self._want_all = jax.jit(want_all_fn)
        # the pre-compile jit wrapper, kept for IR re-capture (warmup swaps
        # self._want_all for its Compiled executable)
        self._want_src = self._want_all

        def build_text_tables(params, te, tm, ne, nm):
            """Per-slot cross-attn K/V over ALL layers at once, from the
            admission-time prompt / negative-prompt embedding tables.  Runs
            once per admission wave — text K/V is step-invariant, so no
            tick program carries a single text-projection FLOP.  Embeddings
            are re-zeroed under their masks (defense in depth: the zero-
            K/V + all-masked no-op branch must hold bit-exactly)."""
            te = jnp.where(tm[..., None], te, 0.0)
            ne = jnp.where(nm[..., None], ne, 0.0)
            tk, tv = dit.text_kv(params, te, cfg)
            nk, nv = dit.text_kv(params, ne, cfg)
            return {"k": tk, "v": tv, "mask": tm,
                    "nk": nk, "nv": nv, "nmask": nm}

        self._text_tables_src = build_text_tables
        self._text_tables = (jax.jit(build_text_tables)
                             if self.text_enabled else None)

        def refill(xs, states, slot, noise, fresh):
            return (xs.at[slot].set(noise),
                    SlotBatchedPolicy.reset_slot(states, slot, fresh))

        self._refill = jax.jit(refill)

        # Policies whose want_compute depends only on the step (interval
        # schedules, or the conservative always-True default) admit a
        # host-side compute plan with no device round trip.  Deriving it
        # from want_compute itself — NOT static_schedule — keeps the plan
        # sound for policies like ToCa whose off-schedule branch still
        # calls compute_fn: their base want_compute is True everywhere, so
        # they simply never get skip ticks.  State-dependent predicates
        # (TeaCache & co) raise on the None state and take the device path.
        self._static_plan = self._probe_static_plan(self.policy)
        # the uncond mirror: all-True when cfg_policy is None (naive mode)
        self._static_cfg_plan = (
            self._probe_static_plan(uncond_pol) if uncond_pol is not None
            else np.ones((max_steps,), bool))

        # otherwise each tick program ends by planning the next tick (the
        # fused want pass on its own outputs: slot_step's `tv_next`), and
        # the session carries that plan
        self._device_plan = (self._static_plan is None
                             or self._static_cfg_plan is None)

        # host-side per-slot timestep tables, padded to max_steps (+1 for the
        # terminal alpha-bar = 1.0 that closes the DDIM update)
        self._ab = np.ones((slots, max_steps + 1), np.float32)
        self._tv = np.zeros((slots, max_steps), np.float32)
        self._labels = np.zeros((slots,), np.int32)
        self._nulls = np.full((slots,), cfg.dit_num_classes, np.int32)
        # negative-prompt conditioning vectors (per slot) + their mask
        self._null_vecs = np.zeros((slots, cfg.d_model), np.float32)
        self._null_mask = np.zeros((slots,), bool)
        # per-slot prompt / negative-prompt embedding tables (host side;
        # zero-size when the config is not text-enabled) — the admission-
        # time inputs of build_text_tables, padded to cfg.dit_text_len
        Lt = cfg.dit_text_len
        self._txt_embed = np.zeros((slots, Lt, cfg.d_model), np.float32)
        self._txt_mask = np.zeros((slots, Lt), bool)
        self._neg_embed = np.zeros((slots, Lt, cfg.d_model), np.float32)
        self._neg_mask = np.zeros((slots, Lt), bool)
        self._scales = np.zeros((slots,), np.float32)
        self._nsteps = np.ones((slots,), np.int32)
        self._guided = np.zeros((slots,), bool)
        #: ServingTelemetry of the most recent serve() call
        self.telemetry: Optional[ServingTelemetry] = None
        # guards the one-live-session invariant (see ServeSession)
        self._session_active = False
        #: per-program cost cards filled by warmup() — keyed by bucket size
        #: (row-compacted), tick kind (dense), plus "want" for the plan pass
        self.program_profile: Dict[object, ProgramProfile] = {}
        #: captured jaxpr/StableHLO per program (same keys), filled by
        #: warmup(verify=True) or lazily by _capture_program_ir()
        self.program_ir: Dict[object, ProgramIR] = {}
        #: repro.analysis.ir findings from the last warmup(verify=True);
        #: None = never verified, [] = verified clean
        self.ir_findings: Optional[List] = None
        self._warmed = False

    def _compact_tick(self, bucket: int):
        """The jit'd row-compacted program for one bucket size (lazy; at most
        log2(2*slots) + 2 programs ever exist)."""
        fn = self._compact_ticks.get(bucket)
        if fn is None:
            fn = self._compact_ticks[bucket] = self._make_compact_tick(bucket)
        return fn

    # -- text conditioning ---------------------------------------------
    def _text_table_operands(self):
        """Dummy (params, te, tm, ne, nm) operands shaped like one
        admission wave's inputs to build_text_tables (text-enabled engines
        only)."""
        S, Lt = self.slots, self.cfg.dit_text_len
        te = jnp.zeros((S, Lt, self.cfg.d_model), jnp.float32)
        tm = jnp.zeros((S, Lt), bool)
        return self.params, te, tm, te, tm

    def _empty_txt(self):
        """An all-masked per-slot text-table dict (zero K/V, zero masks) —
        the exact no-op under the cross-attn masking invariant.  {} on
        text-free engines: an empty dict contributes zero jit operand
        leaves, keeping their tick signature byte-identical to pre-text."""
        if not self.text_enabled:
            return {}
        S, Lt = self.slots, self.cfg.dit_text_len
        kd = self.params["blocks"]["cross"]["wk"].shape[-1]
        z = jnp.zeros((S, self.cfg.num_layers, Lt, kd), jnp.float32)
        m = jnp.zeros((S, Lt), bool)
        return {"k": z, "v": z, "mask": m, "nk": z, "nv": z, "nmask": m}

    def _build_text_tables(self):
        """The live per-slot text-table dict from the host embedding
        tables: one jitted text_kv pass over every slot, re-run only when
        admission changed a slot's prompt (never per tick)."""
        if not self.text_enabled:
            return {}
        return self._text_tables(
            self.params,
            jnp.asarray(self._txt_embed), jnp.asarray(self._txt_mask),
            jnp.asarray(self._neg_embed), jnp.asarray(self._neg_mask))

    # ------------------------------------------------------------------
    def _warmup_operands(self):
        """Operands shaped exactly like a live tick's: the 14-tuple every
        tick program takes (the model params first; the text-table dict is
        empty on text-free engines — zero operand leaves), 15 with the next
        tick's timesteps on engines that plan on the device, and the fused
        want pass's 7-tuple (shared prefixes, so warmup and IR capture
        trace the same shapes a session dispatches)."""
        S = self.slots
        T, D = self.tokens, self.in_dim
        xs = jnp.zeros((S, T, D), jnp.float32)
        states = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (S,) + a.shape).copy(),
            self._fresh)
        zi = jnp.zeros((S,), jnp.int32)
        zf = jnp.zeros((S,), jnp.float32)
        nv = jnp.zeros((S, self.cfg.d_model), jnp.float32)
        nm = jnp.zeros((S,), bool)
        ab = jnp.full((S,), 0.5, jnp.float32)
        tick_args = (self.params, states, zi, xs, zf, zi, zi, nv, nm,
                     self._empty_txt(), zf, zf, ab, ab)
        if self._device_plan:
            tick_args += (zf,)                  # the next tick's timesteps
        want_args = (self.params, states, zi, xs, zf, zi, nm)
        return tick_args, want_args

    def _warmup_buckets(self) -> List[int]:
        """Every bucket a tick can request, mirroring compact_rows exactly:
        cond-only ticks pad n in 1..S capped at S, ticks with uncond rows
        pad n in 1..2S capped at 2S."""
        S = self.slots
        return sorted(
            {0}
            | {min(1 << (n - 1).bit_length(), S) for n in range(1, S + 1)}
            | {min(1 << (n - 1).bit_length(), 2 * S)
               for n in range(1, 2 * S + 1)})

    def warmup(self, verify: bool = False) -> Dict[object, ProgramProfile]:
        """Compile every tick program on dummy inputs before serving, and
        profile each one while at it.

        Row compaction spreads the engine across one program per bucket size;
        without warmup each first-seen bucket pays its XLA compile inside a
        live tick (state-dependent policies like TeaCache surface new bucket
        sizes mid-run, long after admission warmed the common ones).  The
        mixed-modality engine calls this on every sub-pool so the first
        mixed tick doesn't pay several modality-shaped compiles at once.

        Each program is AOT-compiled (repro.obs.profiling.compile_program)
        so the per-program compile time and the XLA cost model's FLOPs /
        bytes are captured into `self.program_profile` — keyed by bucket
        size (compacted), tick kind (dense), plus "want" for the fused
        plan pass — and the compiled executable is swapped into the tick
        cache so serving never re-pays the compile.  Returns the profile
        dict; `repro.obs.profiling.redundancy_ratio` combines it with
        telemetry row counters into measured-FLOPs-saved.

        `verify=True` additionally captures each program's jaxpr/StableHLO
        during the same trace pipeline and runs the repro.analysis.ir
        contract checks (host callbacks, f64/weak-type leaks, donation
        aliasing, const bloat) over the whole program set: findings land
        in `self.ir_findings` and on each returned profile's
        `ir_findings`.  Warmup also pre-runs the small host-utility
        programs a live session dispatches outside the tick programs
        (admission noise, the jit'd refill, the harvest row gather), so
        steady-state serving after warmup compiles NOTHING — the
        ir-retrace sentinel enforces exactly that."""
        if self._warmed:
            if verify and self.ir_findings is None:
                self._run_ir_verification()
            return self.program_profile
        args, want_args = self._warmup_operands()
        # the fused want pass also compiles on first use; without this a
        # state-dependent policy pays that compile inside its first live tick
        if self._device_plan:
            if verify:
                self._want_all, prof, ir = compile_program(
                    self._want_src, *want_args, key="want", want_ir=True)
                self.program_ir["want"] = ir
            else:
                self._want_all, prof = compile_program(
                    self._want_all, *want_args, key="want")
            self.program_profile["want"] = prof
        # text-serving programs: the admission-time K/V table build, and
        # the conditioner's text encoder — both outside the tick loop, but
        # a live session dispatches them, so the zero-recompile-after-
        # warmup claim must cover them too
        if self.text_enabled:
            targs = self._text_table_operands()
            if verify:
                self._text_tables, prof, ir = compile_program(
                    self._text_tables, *targs, key="text_kv", want_ir=True)
                self.program_ir["text_kv"] = ir
            else:
                self._text_tables, prof = compile_program(
                    self._text_tables, *targs, key="text_kv")
            self.program_profile["text_kv"] = prof
            if self.conditioner is not None:
                if verify:
                    prof, ir = self.conditioner.warmup(verify=True)
                    self.program_ir["text_encoder"] = ir
                else:
                    prof = self.conditioner.warmup()
                self.program_profile["text_encoder"] = prof
        if self.row_compaction:
            S = self.slots
            for bucket in self._warmup_buckets():
                row_slot = jnp.zeros((bucket,), jnp.int32)
                row_uncond = jnp.zeros((bucket,), bool)
                row_dest = jnp.full((bucket,), 2 * S, jnp.int32)
                fn = self._make_compact_tick(bucket)
                if verify:
                    compiled, prof, ir = compile_program(
                        fn, *args, row_slot, row_uncond, row_dest,
                        key=bucket, want_ir=True)
                    self.program_ir[bucket] = ir
                else:
                    compiled, prof = compile_program(
                        fn, *args, row_slot, row_uncond, row_dest,
                        key=bucket)
                self._compact_ticks[bucket] = compiled
                self.program_profile[bucket] = prof
                # run once: validates the compiled avals against real-shaped
                # operands now instead of inside the first live tick
                compiled(*args, row_slot, row_uncond, row_dest)[0] \
                    .block_until_ready()
        else:
            for kind in ("full", "cond", "skip"):
                if verify:
                    compiled, prof, ir = compile_program(
                        self._ticks[kind], *args, key=kind, want_ir=True)
                    self.program_ir[kind] = ir
                else:
                    compiled, prof = compile_program(
                        self._ticks[kind], *args, key=kind)
                self._ticks[kind] = compiled
                self.program_profile[kind] = prof
                compiled(*args)[0].block_until_ready()
        # pre-warm the host-utility programs a live session dispatches
        # outside the tick programs: admission noise (PRNGKey / fold_in /
        # normal), the jit'd refill, and the harvest row gather+transfer.
        # Without this the first admission/harvest pays their compiles
        # mid-session — which the retrace sentinel rightly counts
        states, xs = args[1], args[3]
        key = jax.random.fold_in(jax.random.PRNGKey(0), 0)
        noise = jax.random.normal(key, (self.tokens, self.in_dim))
        warm_xs, _ = self._refill(xs, states, 0, noise, self._fresh)
        np.asarray(warm_xs[0])
        if self.text_enabled:
            # validates the compiled text_kv avals against the real host
            # tables (and warms their host->device transfers)
            jax.tree_util.tree_map(lambda a: a.block_until_ready(),
                                   self._build_text_tables())
        self._warmed = True
        if verify:
            self._run_ir_verification()
        return self.program_profile

    def _capture_program_ir(self) -> Dict[object, ProgramIR]:
        """ProgramIR per warmup program key, capturing lazily when warmup
        ran without verify: programs are re-traced through their stored
        builders (fresh jit wrappers — the warmed caches hold bare
        Compiled executables, which carry no jaxpr)."""
        if not self._warmed:
            self.warmup()
        if self.program_ir:
            return self.program_ir
        from repro.obs.profiling import capture_ir
        args, want_args = self._warmup_operands()
        if self._device_plan:
            self.program_ir["want"] = capture_ir(
                self._want_src, *want_args, key="want")
        if self.text_enabled:
            self.program_ir["text_kv"] = capture_ir(
                jax.jit(self._text_tables_src), *self._text_table_operands(),
                key="text_kv")
            if self.conditioner is not None:
                self.program_ir["text_encoder"] = \
                    self.conditioner.capture_ir()
        if self.row_compaction:
            S = self.slots
            for bucket in self._warmup_buckets():
                row_slot = jnp.zeros((bucket,), jnp.int32)
                row_uncond = jnp.zeros((bucket,), bool)
                row_dest = jnp.full((bucket,), 2 * S, jnp.int32)
                self.program_ir[bucket] = capture_ir(
                    self._make_compact_tick(bucket), *args, row_slot,
                    row_uncond, row_dest, key=bucket)
        else:
            for kind in ("full", "cond", "skip"):
                self.program_ir[kind] = capture_ir(
                    self._make_tick(kind), *args, key=kind)
        return self.program_ir

    def _run_ir_verification(self) -> None:
        """verify_programs over the captured IR set; findings land on
        self.ir_findings and on the matching program profiles.  The
        analysis layer is imported lazily — engines serving in production
        never pay for it unless verify was requested."""
        import dataclasses
        from repro.analysis.ir import verify_programs_by_key
        by_key = verify_programs_by_key(self)
        self.ir_findings = [
            f for _, fs in sorted(by_key.items(), key=lambda kv: str(kv[0]))
            for f in fs]
        for k, prof in list(self.program_profile.items()):
            attached = tuple(by_key.get(k, ()))
            if attached:
                self.program_profile[k] = dataclasses.replace(
                    prof, ir_findings=attached)

    def _probe_static_plan(self, policy: CachePolicy) -> Optional[np.ndarray]:
        try:
            return np.asarray(
                [bool(policy.want_compute(None, s, None))
                 for s in range(self.max_steps)], bool)
        except Exception:
            return None

    # ------------------------------------------------------------------
    def _check_request(self, req: DiffusionRequest) -> None:
        """The one request-shape contract, shared by session submission
        (ServeSession._validate) and slot admission (_install_request) —
        previously duplicated at both sites and free to drift."""
        if req.num_steps > self.max_steps:
            raise ValueError(f"request {req.request_id}: num_steps="
                             f"{req.num_steps} > max_steps={self.max_steps}")
        if req.null_label is not None and np.ndim(req.null_label) > 0:
            shape = np.shape(req.null_label)
            if shape != (self.cfg.d_model,):
                raise ValueError(
                    f"request {req.request_id}: null_label vector shape "
                    f"{shape} != (d_model={self.cfg.d_model},)")
        if req.prompt_tokens is not None or req.neg_prompt_tokens is not None:
            if not self.text_enabled:
                raise ValueError(
                    f"request {req.request_id}: prompt on non-text config "
                    f"'{self.cfg.name}' (dit_text_len == 0)")
            if self.conditioner is None:
                raise ValueError(
                    f"request {req.request_id}: prompt given but the engine "
                    f"has no conditioner (pass conditioner=PromptCache(...))")
        if (req.neg_prompt_tokens is not None and req.null_label is not None
                and np.ndim(req.null_label) > 0):
            raise ValueError(
                f"request {req.request_id}: neg_prompt_tokens conflicts "
                f"with a vector-valued null_label — both claim the uncond "
                f"conditioning vector")

    def _install_request(self, slot: int, req: DiffusionRequest) -> None:
        self._check_request(req)
        ts = self.sched.spaced(req.num_steps)
        abar = self.sched.alpha_bars[ts].astype(np.float32)
        self._ab[slot, :] = 1.0
        self._ab[slot, :req.num_steps] = abar
        self._tv[slot, :] = 0.0
        self._tv[slot, :req.num_steps] = ts.astype(np.float32)
        self._labels[slot] = req.class_label
        null = req.null_label
        self._null_vecs[slot, :] = 0.0
        self._null_mask[slot] = False
        if null is None:
            self._nulls[slot] = self.cfg.dit_num_classes
        elif np.ndim(null) == 0:
            self._nulls[slot] = int(null)
        else:
            # negative prompt: an arbitrary conditioning vector overrides the
            # class-embedding lookup on this slot's uncond rows (shape was
            # checked by _check_request)
            self._nulls[slot] = self.cfg.dit_num_classes
            self._null_vecs[slot, :] = np.asarray(null, np.float32)
            self._null_mask[slot] = True
        if self.text_enabled:
            # reset-on-refill extends to the text tables: slot reuse must
            # never leak a previous request's prompt into this one
            self._txt_embed[slot] = 0.0
            self._txt_mask[slot] = False
            self._neg_embed[slot] = 0.0
            self._neg_mask[slot] = False
            if req.prompt_tokens is not None:
                pe = self.conditioner.get(req.prompt_tokens)
                self._txt_embed[slot] = pe.embed
                self._txt_mask[slot] = pe.mask
            if req.neg_prompt_tokens is not None:
                ne = self.conditioner.get(req.neg_prompt_tokens)
                self._neg_embed[slot] = ne.embed
                self._neg_mask[slot] = ne.mask
                # the pooled negative-prompt embedding rides the null-vec
                # path: uncond rows condition on it instead of the
                # null-class embedding, AND cross-attend its K/V above
                self._nulls[slot] = self.cfg.dit_num_classes
                self._null_vecs[slot, :] = ne.pooled
                self._null_mask[slot] = True
        self._scales[slot] = req.cfg_scale
        self._nsteps[slot] = req.num_steps
        # guided exactly where the tick program blends (its float32 scale
        # > 0), which is also how a tick program's own plan reads it
        self._guided[slot] = self._scales[slot] > 0.0

    def _plan_all(self, states, steps, xs, tvals, plan=None):
        """Per-slot (want_cond, want_uncond, metric) plan — before active
        masking; want_uncond is already masked by the per-slot guided flag.

        When BOTH branches admit a host-side static schedule the plan costs
        no device round trip at all (and metric is None — nothing dynamic
        was measured).  Otherwise `plan` is the device triple the previous
        tick program computed for this tick (its copy to the host started
        at that tick's dispatch), or, when None, the fused want pass runs
        here; a branch that is static anyway is then overridden from its
        host plan (the device predicate for it is mirrored, so this is
        equivalence-preserving, not a behavior switch)."""
        if not self._device_plan:
            return (self._static_plan[steps],
                    self._static_cfg_plan[steps] & self._guided, None)
        if plan is None:
            plan = self._want_all(
                self.params, states, jnp.asarray(steps), xs,
                jnp.asarray(tvals), jnp.asarray(self._labels),
                jnp.asarray(self._guided))
        # repro-lint: disable-next-line=host-sync-in-hot-path -- the one per-tick plan read: a carried plan is already on the host; the want pass (first tick, admission ticks) waits its round trip here, surcharged in plan cost
        wc, wu, metric = jax.device_get(plan)
        wc, wu = np.asarray(wc, bool), np.asarray(wu, bool)
        if self._static_plan is not None:
            wc = self._static_plan[steps]
        if self._static_cfg_plan is not None:
            wu = self._static_cfg_plan[steps] & self._guided
        return wc, wu, np.asarray(metric, np.float32)

    # ------------------------------------------------------------------
    def start_session(self, requests: Sequence[DiffusionRequest],
                      telemetry: Optional[ServingTelemetry] = None,
                      hooks: Optional[Sequence[TickHook]] = None,
                      capture_latents: bool = False,
                      modality: Optional[str] = None,
                      metrics=None) -> ServeSession:
        """Begin a tick-granular serving session (see ServeSession).

        At most ONE session per engine may be in flight (enforced): the
        per-slot timestep/conditioning tables live on the engine.
        Interleaving across engines (the mixed-modality pool) is fine.
        `hooks` observe each tick (TickEvent); `capture_latents` copies the
        pre-tick latent batch into each event (device transfer per tick);
        `metrics` (a repro.obs MetricsRegistry) opts the session into
        publishing the repro_engine_* / repro_scheduler_* instrument set."""
        return ServeSession(self, requests, telemetry, hooks=hooks,
                            capture_latents=capture_latents,
                            modality=modality, metrics=metrics)

    def serve(self, requests: Sequence[DiffusionRequest],
              telemetry: Optional[ServingTelemetry] = None,
              max_ticks: Optional[int] = None,
              hooks: Optional[Sequence[TickHook]] = None,
              capture_latents: bool = False,
              metrics=None) -> List[DiffusionResult]:
        """Run every request through the slot pool; returns results in
        request order.  With max_ticks, unfinished requests are recorded as
        preempted in telemetry (never silently dropped)."""
        session = self.start_session(requests, telemetry, hooks=hooks,
                                     capture_latents=capture_latents,
                                     metrics=metrics)
        try:
            while not session.done:
                session.tick()
                if max_ticks is not None and session.ticks >= max_ticks:
                    break
        finally:
            # also on a failed tick: release the engine's session latch and
            # record unfinished requests as preempted, so the engine stays
            # retryable after an error (finish() is idempotent)
            session.finish()
        return session.finish()
