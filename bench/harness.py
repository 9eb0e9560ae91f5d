"""The benchmark harness: finds a cell's files by name, builds the server,
drives the cell's traffic through it, and reads the metrics.

Everything that belongs to one configuration, traffic mix, cache policy,
metric, backbone family or cell sits in a file of its own under `bench/`:

  configs/<config>.json     model shapes, engine settings, weight seed
  traffic/<mix>.json        parameters of the one generator (traffic.py),
                            and the cache policy the mix is served under
  policies/<policy>.json    cache policy and CFG policy with thresholds
  limits/<cell>.json        what `correct` compares, with each limit
  metrics/<metric>.py       one reader per metric; `name.serve` and
                            `name.batch` share `metrics/name.py`
  flops/<family>.py         analytic FLOPs of one backbone row
  backbones/<family>.py     the weights and the plain reference backbone
  peaks.json                chip peaks keyed by device_kind

A cell adds files and a `workloads` entry; no file here changes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

import traffic as traffic_gen

#: JAX's persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = ".jax_cache"


def use_checkout_cache(root: Path) -> str:
    """Give the program the checkout's compile cache: set before JAX is
    imported, so the program's own cache placement takes it, whatever
    directory the environment named."""
    path = str(Path(root).resolve() / CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    return path


class Bench:
    """Discovery of the benchmark's files under `<root>/bench`."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.dir = self.root / "bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def data(self, kind: str, name: str) -> dict:
        return json.loads((self.dir / kind / f"{name}.json").read_text())

    def module(self, kind: str, name: str):
        path = self.dir / kind / f"{name}.py"
        if not path.is_file():
            raise FileNotFoundError(f"no {kind} module {path}")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def backbone(self, family: str):
        if str(self.dir) not in sys.path:
            sys.path.insert(0, str(self.dir))
        return importlib.import_module(f"backbones.{family}")

    def reader(self, metric: str) -> Callable:
        return self.module("metrics", metric.split(".")[0]).read

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.dir / "peaks.json").read_text())["devices"]
        if device_kind not in table:
            raise KeyError(f"device kind {device_kind!r} has no peaks in "
                           f"bench/peaks.json")
        return table[device_kind]

    def metrics_for(self, cell: str, section: str) -> List[dict]:
        """The metrics of `section` ("end_to_end" or "per_layer") that
        this cell reports."""
        return [m for m in self.spec[section]
                if "workloads" not in m or cell in m["workloads"]]

    def cell(self, name: str) -> "Cell":
        w = self.workload(name)
        config = self.data("configs", w["config"])
        mix = self.data("traffic", w["traffic"])
        return Cell(name=name, workload=w, config=config, mix=mix,
                    policy=self.data("policies", mix["policy"]),
                    limits=self.data("limits", name))


@dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    mix: dict
    policy: dict
    limits: dict

    @property
    def model(self) -> dict:
        return self.config["model"]


# ---------------------------------------------------------------------------
# set-up: weights, engine
# ---------------------------------------------------------------------------

def make_params(backbone, model: dict, seed: int):
    """The served weights, made on the device in one jitted call from the
    configuration's weight seed, in the configuration's dtype."""
    import jax
    import jax.numpy as jnp
    specs = backbone.param_specs(model)
    dtype = jnp.dtype(model["dtype"])

    def build():
        keys = jax.random.split(jax.random.PRNGKey(seed), len(specs))
        tree: dict = {}
        for (path, shape, std), k in zip(specs, keys):
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = (jax.random.normal(k, shape, jnp.float32)
                              * std).astype(dtype)
        return tree

    return jax.jit(build)()


def arch_config(config: dict):
    """The program's ArchConfig for this configuration file: the named repo
    config with every model key of the file applied."""
    from repro.configs import get_config
    model = config["model"]
    if model["head_dim"] * model["num_heads"] != model["d_model"]:
        raise ValueError("head_dim * num_heads must equal d_model")
    return dataclasses.replace(get_config(config["repo_config"]), **model)


def make_engine(cell: Cell, params):
    from repro.core import make_policy
    from repro.serving.diffusion import DiffusionServingEngine
    eng = cell.config["engine"]
    kw = {"num_steps": eng["max_steps"]}
    frames = cell.model.get("dit_num_frames", 0)
    if frames:
        kw["frames"] = frames
    pol = cell.policy
    policy = make_policy(pol["policy"], **kw, **pol.get("args", {}))
    cfg_policy = (make_policy(pol["cfg_policy"], **kw,
                              **pol.get("cfg_args", {}))
                  if pol.get("cfg_policy") else None)
    return DiffusionServingEngine(params, arch_config(cell.config), policy,
                                  slots=eng["slots"],
                                  max_steps=eng["max_steps"],
                                  cfg_policy=cfg_policy)


def build_server(bench: Bench, cell: Cell):
    """A cell's backbone module, weights and warmed-up engine."""
    backbone = bench.backbone(cell.config["family"])
    params = make_params(backbone, cell.model, int(cell.config["weight_seed"]))
    engine = make_engine(cell, params)
    engine.warmup()
    return backbone, params, engine


def configure_jax() -> str:
    """Place the compile cache as the program does, and cache every
    program: the want pass and the small programs compile in under JAX's
    default one-second threshold, so without this they would never be
    cached.  Returns the cache directory."""
    from repro.launch.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def tpu_devices(chips: int = 1):
    """JAX's devices when they are at least `chips` TPU chips, else None."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        return None
    return devices


# ---------------------------------------------------------------------------
# the traffic loop
# ---------------------------------------------------------------------------

@dataclass
class TickRec:
    """What one engine tick did, as its TickEvent reported it."""
    t0: float                   # host clock when the tick was called
    plan_s: float
    rows: int                   # backbone rows carrying requests
    padding: int                # bucket padding rows
    active: int                 # busy slots
    guided: int                 # busy slots of guided requests


class Recorder:
    """TickEvent hook: keeps per-tick counts, each request's decision at
    each of its steps (with the distance the policy reported for it), and
    when each finished request was on the host."""

    def __init__(self, clock, annotate):
        self.clock = clock
        self.annotate = annotate
        self.ticks: List[TickRec] = []
        self.want: Dict[int, Dict[int, tuple]] = {}
        self.finished_at: Dict[int, float] = {}
        self.slot: Dict[int, int] = {}
        self._t0 = 0.0

    def __call__(self, ev) -> None:
        with self.annotate("bench.hook"):
            now = self.clock()
            for rec in ev.finished:
                self.finished_at[rec.request_id] = now
            act = ev.active
            self.ticks.append(TickRec(
                self._t0, ev.plan_seconds, ev.rows_computed,
                ev.rows_padding, int(act.sum()), int((act & ev.guided).sum())))
            metric = (ev.metric if ev.metric is not None
                      else np.full(len(act), np.nan, np.float32))
            for slot in np.nonzero(act)[0]:
                rid = int(ev.request_ids[slot])
                self.slot[rid] = int(slot)
                self.want.setdefault(rid, {})[int(ev.steps[slot])] = (
                    bool(ev.want_cond[slot]), bool(ev.want_uncond[slot]),
                    float(metric[slot]))


class Loop:
    """Offers a cell's requests to one ServeSession on the host clock and
    ticks the session while it has work."""

    def __init__(self, session, reqs, mix: dict, rec: Recorder, clock,
                 annotate):
        from repro.serving.diffusion import DiffusionRequest
        self._mk = DiffusionRequest
        self.session = session
        self.reqs = reqs
        self.backlog = mix["arrivals"] == "backlog"
        self.depth = int(mix.get("depth", 0))
        self.rec = rec
        self.clock = clock
        self.annotate = annotate
        self.t_start = clock()
        self.next = 0
        self.due: Dict[int, float] = {}
        self.lag: Dict[int, float] = {}

    def _submit(self, r) -> None:
        self.due[r.rid] = self.t_start + r.due_s
        self.lag[r.rid] = self.clock() - self.due[r.rid]
        self.session.submit(self._mk(r.rid, r.num_steps, seed=r.noise_seed,
                                     class_label=r.label,
                                     cfg_scale=r.cfg_scale))

    def offer(self, now: float) -> None:
        with self.annotate("bench.submit"):
            if self.backlog:
                while len(self.session.sched.queue) < self.depth:
                    if self.next == len(self.reqs):
                        raise RuntimeError("backlog mix ran out of requests")
                    self._submit(self.reqs[self.next])
                    self.next += 1
                return
            while (self.next < len(self.reqs)
                   and self.t_start + self.reqs[self.next].due_s <= now):
                self._submit(self.reqs[self.next])
                self.next += 1

    def tick(self) -> None:
        with self.annotate("bench.tick"):
            self.rec._t0 = self.clock()
            self.session.tick()

    def run_until(self, deadline: float) -> None:
        """Offer due requests and tick until `deadline` (a tick that starts
        before it runs to its end)."""
        while True:
            now = self.clock()
            if now >= deadline:
                return
            self.offer(now)
            if self.session.done:
                if self.next == len(self.reqs):
                    return
                wake = min(deadline, self.t_start + self.reqs[self.next].due_s)
                with self.annotate("bench.wait_arrival"):
                    time.sleep(max(0.0, wake - self.clock()))
                continue
            self.tick()

    def drain(self, rids, deadline: float) -> None:
        """Tick, offering nothing, until every request in `rids` finished
        or `deadline` passed."""
        pending = set(rids)
        while pending - self.rec.finished_at.keys():
            if self.clock() >= deadline or self.session.done:
                return
            self.tick()


# ---------------------------------------------------------------------------
# one run of one cell
# ---------------------------------------------------------------------------

@dataclass
class RunRecord:
    """What the metric readers read (metrics/<name>.py: read(run))."""
    setup_s: float
    window_s: float
    latencies_s: List[float]
    ticks: List[TickRec]
    steps_advanced: int
    flops_per_row: float
    peak_flops: float
    trace: Optional[object] = None      # trace_reduce.Summary


def _served(rec: Recorder, session, reqs_by_id, rids) -> list:
    """Served records of the finished requests among `rids`."""
    from reference import Served
    out = []
    for rid in rids:
        res = session.results.get(rid)
        if res is None:
            continue
        r = reqs_by_id[rid]
        want = rec.want.get(rid, {})
        steps = [want.get(s, (False, False, np.nan))
                 for s in range(r.num_steps)]
        out.append(Served(rid=rid, noise_seed=r.noise_seed,
                          num_steps=r.num_steps, label=r.label,
                          cfg_scale=r.cfg_scale, slot=rec.slot[rid],
                          want_cond=np.asarray([w[0] for w in steps], bool),
                          want_uncond=np.asarray([w[1] for w in steps], bool),
                          metric=np.asarray([w[2] for w in steps],
                                            np.float32),
                          x0=np.asarray(res.x0)))
    return out


def _requests_needed(mix: dict, warm_s: float, seconds: float) -> int:
    if mix["arrivals"] == "backlog":
        return 100_000
    expected = float(mix["rate_per_s"]) * (warm_s + seconds)
    return int(expected * 1.5) + 64


def serve_cell(cell: Cell, engine, seed: int, seconds: float, *,
               trace_dir: Optional[str] = None, annotate=None,
               clock=time.perf_counter, log=None):
    """Drive one cell's traffic through `engine` for a window of `seconds`
    after the mix's warm-up.  Returns (Recorder, session, window dict)."""
    from repro.analysis.ir import RetraceSentinel
    annotate = annotate or (lambda name: contextlib.nullcontext())
    mix = cell.mix
    warm_s = float(mix["warmup_s"])
    reqs = traffic_gen.generate(mix, seed, _requests_needed(mix, warm_s,
                                                            seconds),
                                cell.model["dit_num_classes"])
    rec = Recorder(clock, annotate)
    session = engine.start_session([], hooks=[rec])
    loop = Loop(session, reqs, mix, rec, clock, annotate)
    loop.run_until(loop.t_start + warm_s)
    if trace_dir is not None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # keep the host cost of tracing low
        opts.host_tracer_level = 1         # the bench.* annotations
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    n_warm_ticks = len(rec.ticks)
    t_open = clock()
    t_close = t_open + seconds
    with RetraceSentinel() as sentinel:
        with annotate("bench.window"):
            loop.run_until(t_close)
            if not loop.backlog:
                # requests due before the close that the last tick held up
                loop.offer(t_close - 1e-9)
        t_end = max(clock(), t_close) if not loop.backlog else clock()
        if trace_dir is not None:
            import jax
            jax.profiler.stop_trace()
        measured = [rid for rid, due in loop.due.items()
                    if t_open <= due < t_close]
        if not loop.backlog:
            loop.drain(measured, clock() + float(mix.get("drain_s", 60.0)))
    window_ticks = [t for t in rec.ticks[n_warm_ticks:] if t.t0 < t_close]
    if loop.backlog:
        # a backlog cell counts the work finished inside the window
        measured = [rid for rid, t in rec.finished_at.items()
                    if t_open <= t <= t_end]
    win = {"t_open": t_open, "t_close": t_close, "t_end": t_end,
           "ticks": window_ticks, "measured": measured,
           "due": loop.due, "lag": loop.lag, "compiles": sentinel.count,
           "compiled_names": sorted(set(sentinel.compiled_names)),
           "reqs": {r.rid: r for r in reqs[:loop.next]}}
    return rec, session, win


def judge(numbers: dict, limits: dict) -> tuple:
    """Each number of the cell's limits file beside its limit, and whether
    the run is correct: a non-empty sample and every number within its
    limit."""
    checks = {k: {"value": numbers[k], "limit": limits["checks"][k]}
              for k in limits["checks"]}
    correct = (numbers["sampled"] > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    return checks, correct


def run_cell(bench: Bench, name: str, seed: int, seconds: float,
             trace: bool, *, t_process: float, require_tpu: bool = True,
             log=None) -> tuple:
    """One run of one cell.  Returns (exit code, result dict or None)."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    cache_dir = configure_jax()
    import jax

    cell = bench.cell(name)
    chips = int(cell.workload["chips"])
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and tpu_devices(chips) is None:
        log(f"bench: cell {name} needs {chips} TPU chip(s); JAX sees "
            f"{len(devices)} {dev.platform!r} device(s); nothing run")
        return 2, None
    log(f"bench: {name} seed {seed} on {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache_dir}")
    peak = bench.peaks(dev.device_kind) if require_tpu else \
        {"bf16_flops_per_s": float("nan")}

    backbone, params, engine = build_server(bench, cell)

    annotate = jax.profiler.TraceAnnotation
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        rec, session, win = serve_cell(cell, engine, seed, seconds,
                                       trace_dir=trace_dir,
                                       annotate=annotate)
        t_open = win["t_open"]
        stats = [d.memory_stats() or {} for d in devices[:chips]]
        mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
        session.finish()
        summary = None
        if trace_dir is not None:
            import trace_reduce
            summary = trace_reduce.reduce_dir(trace_dir)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    measured = win["measured"]
    reqs = win["reqs"]
    lat = [rec.finished_at[r] - win["due"][r] for r in measured
           if r in rec.finished_at]
    served = _served(rec, session, reqs, measured)
    nonfinite = sum(1 for s in served if not np.isfinite(s.x0).all())
    attempted = len(measured)
    failed = attempted - len(lat) + nonfinite

    flops = bench.module("flops", cell.config["family"]).flops_per_row(
        cell.model)
    run = RunRecord(
        setup_s=t_open - t_process,
        window_s=(win["t_end"] - t_open),
        latencies_s=lat, ticks=win["ticks"],
        steps_advanced=sum(t.active for t in win["ticks"]),
        flops_per_row=flops, peak_flops=peak["bf16_flops_per_s"],
        trace=summary)

    # -- correctness: program state freed, then the reference ------------
    finite = [s for s in served if np.isfinite(s.x0).all()]
    del engine, session
    gc.collect()
    import reference as ref_mod
    lim = cell.limits
    sample = ref_mod.draw_sample(finite, int(lim["sample"]), seed)
    ref = ref_mod.Reference(backbone, cell.model, params, cell.policy,
                            cell.config["noise_schedule"],
                            ref_mod.DOTS["reference"])
    t_ref = time.perf_counter()
    numbers = ref_mod.compare(ref, sample)
    lags = [win["lag"][r] for r in measured if r in win["lag"]]
    if lags and not cell.mix["arrivals"] == "backlog":
        log(f"bench: generator lateness over {len(lags)} requests: mean "
            f"{1e3 * float(np.mean(lags)):.3f} ms, max "
            f"{1e3 * float(np.max(lags)):.3f} ms")
    log(f"bench: reference over {len(sample)} request(s) took "
        f"{time.perf_counter() - t_ref:.3f} s")
    numbers["window_compiles"] = win["compiles"]
    numbers["failed"] = failed
    numbers["sampled"] = len(sample)
    checks, correct = judge(numbers, lim)
    if win["compiles"]:
        log(f"bench: compiled inside the window: {win['compiled_names']}")

    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench.metrics_for(name, section):
        v = bench.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    return 0, result
