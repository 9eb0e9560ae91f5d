"""The engine's host spans: ServeSession.tick runs nine phase spans under
one `engine.tick` span (repro.obs.span), whose seconds are the TickEvent's
`phases`, `plan_seconds` and `seconds`, the registry's phase counter and
the TraceRecorder's plan and tick spans, and which land on the profiler
trace's host plane with their counts."""
import glob

import jax
import pytest

from repro.configs import get_config
from repro.core import FasterCacheCFG
from repro.models import init_params, perturb_zero_init
from repro.obs import MetricsRegistry, TraceRecorder, monotonic, span
from repro.serving.diffusion import DiffusionRequest, DiffusionServingEngine

NUM_STEPS = 8
SLOTS = 2
PHASES = ("engine.admit", "engine.prepare", "engine.plan", "engine.upload",
          "engine.dispatch", "engine.wait", "engine.account",
          "engine.harvest", "engine.hooks")


def _requests(n, modalities=("image",)):
    """Mixed guided/unguided requests with mixed step budgets."""
    return [DiffusionRequest(i, num_steps=(NUM_STEPS, NUM_STEPS - 2)[i % 2],
                             seed=i, class_label=i % 5,
                             modality=modalities[i % len(modalities)],
                             cfg_scale=2.5 if i % 2 == 0 else 0.0)
            for i in range(n)]


def _host_spans(trace_dir):
    """(name, start_ns, end_ns, stats) of every `engine.*` event on the
    host plane of the one trace under `trace_dir`."""
    from jax.profiler import ProfileData
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert len(files) == 1
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One teacache + FasterCacheCFG session of a tiny DiT, observed by a
    list hook, a TraceRecorder and a registry, inside a profiler trace."""
    cfg = get_config("dit-xl").reduced(num_layers=2, d_model=64,
                                       num_heads=4, num_kv_heads=4,
                                       d_ff=128, dit_patch_tokens=8,
                                       dit_in_dim=4, dit_num_classes=10)
    params = perturb_zero_init(init_params(jax.random.PRNGKey(0), cfg))
    eng = DiffusionServingEngine(params, cfg, "teacache", slots=SLOTS,
                                 max_steps=NUM_STEPS,
                                 cfg_policy=FasterCacheCFG(3, NUM_STEPS))
    eng.warmup()
    events, recorder, registry = [], TraceRecorder(eng.policy), \
        MetricsRegistry()
    trace_dir = str(tmp_path_factory.mktemp("engine_trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        eng.serve(_requests(5), hooks=[events.append, recorder],
                  metrics=registry)
        t_end = monotonic()
    finally:
        jax.profiler.stop_trace()
    return {"events": events, "t_end": t_end, "recorder": recorder,
            "registry": registry, "spans": _host_spans(trace_dir)}


@pytest.fixture(scope="module")
def served_mixed():
    """The same request shapes through MixedModalityEngine: an image pool
    (teacache + FasterCacheCFG) and an audio pool (teacache)."""
    from repro.modalities import MixedModalityEngine, make_workload
    pools = {m: make_workload(m, smoke=True).engine(
        "teacache", slots=SLOTS, max_steps=NUM_STEPS,
        cfg_policy=(FasterCacheCFG(3, NUM_STEPS) if m == "image" else None))
        for m in ("image", "audio")}
    engine = MixedModalityEngine(pools)
    events = {m: [] for m in pools}
    # the even requests, the guided ones, go to the image pool
    engine.serve(_requests(6, ("image", "audio")),
                 hooks={m: [ev.append] for m, ev in events.items()})
    return {"events": events, "t_end": monotonic()}


@pytest.fixture(params=["engine", "mixed"])
def sessions(request):
    """Each session's TickEvents, with the clock reading after it."""
    if request.param == "engine":
        s = request.getfixturevalue("served")
        return [(s["events"], s["t_end"])]
    s = request.getfixturevalue("served_mixed")
    return [(ev, s["t_end"]) for ev in s["events"].values()]


def test_every_tick_hands_its_hooks_the_eight_closed_phases(sessions):
    """Every phase but `engine.hooks`, which is still open while the hooks
    run; the mapping is read-only and unchanged after the tick."""
    for events, _ in sessions:
        assert events
        for ev in events:
            assert tuple(ev.phases) == PHASES[:-1]
            assert all(s >= 0.0 for s in ev.phases.values())
            with pytest.raises(TypeError):
                ev.phases["engine.hooks"] = 0.0


def test_plan_and_tick_seconds_are_their_spans(sessions):
    for events, _ in sessions:
        for ev in events:
            assert ev.plan_seconds == ev.phases["engine.plan"]
            assert ev.seconds == (ev.phases["engine.dispatch"]
                                  + ev.phases["engine.wait"])


def test_phases_fit_inside_the_tick(sessions):
    """A tick's phases run one after another inside it: their sum is no
    more than the time to the next tick's start (or the end of serving)."""
    for events, t_end in sessions:
        ends = [ev.t_start for ev in events[1:]] + [t_end]
        for ev, end in zip(events, ends):
            assert ev.t_start > 0.0
            assert sum(ev.phases.values()) <= end - ev.t_start


def test_registry_phase_counter_sums_the_phases(served):
    """The registry gets all nine phases, `engine.hooks` included."""
    counter = served["registry"].counter("repro_engine_phase_seconds_total")
    for name in PHASES[:-1]:
        assert counter.value(phase=name, modality="image") == pytest.approx(
            sum(ev.phases[name] for ev in served["events"]))
    assert counter.value(phase="engine.hooks", modality="image") > 0.0


def test_spans_nest_under_engine_tick_on_the_profiler_host_plane(served):
    spans = served["spans"]
    ticks = [s for s in spans if s[0] == "engine.tick"]
    assert len(ticks) == len(served["events"])
    for n, (_, lo, hi, stats) in enumerate(ticks):
        assert stats["tick"] == n
        assert stats["modality"] == "image"
        assert stats["active"] == int(served["events"][n].active.sum())
        inside = [s for s in spans
                  if s[0] != "engine.tick" and lo <= s[1] and s[2] <= hi]
        assert tuple(s[0] for s in inside) == PHASES
    # every phase span lies inside some engine.tick span
    children = [s for s in spans if s[0] != "engine.tick"]
    assert len(children) == len(PHASES) * len(ticks)
    for _, s, e, _ in children:
        assert any(lo <= s and e <= hi for _, lo, hi, _ in ticks)
    for _, _, _, stats in (s for s in spans if s[0] == "engine.upload"):
        assert stats["arrays"] > 0 and stats["nbytes"] >= stats["arrays"]


def test_span_counts_match_the_tick_events(served):
    by_name = {}
    for name, _, _, stats in served["spans"]:
        by_name.setdefault(name, []).append(stats)
    events = served["events"]
    assert [s["requests"] for s in by_name["engine.admit"]] == \
        [len(ev.admitted) for ev in events]
    assert [s["requests"] for s in by_name["engine.harvest"]] == \
        [len(ev.finished) for ev in events]
    assert [s["on_device"] for s in by_name["engine.plan"]] == \
        [int(ev.metric is not None) for ev in events]
    assert [s["bucket"] for s in by_name["engine.dispatch"]] == \
        [ev.rows_computed + ev.rows_padding for ev in events]


def test_recorder_draws_plan_and_tick_spans_from_the_phases(served):
    """The plan span ends no later than the tick program's span starts,
    and the recorder emits no untimed instant markers."""
    events = served["recorder"].chrome_trace()["traceEvents"]
    assert not [e for e in events if e["ph"] == "i"]
    plans = {e["args"]["tick"]: e for e in events if e["name"] == "plan"}
    runs = {e["args"]["tick"]: e for e in events
            if e["name"].startswith("tick:")}
    assert plans and set(plans) <= set(runs)
    for tick, plan in plans.items():
        assert plan["ts"] + plan["dur"] <= runs[tick]["ts"]
        assert plan["dur"] == pytest.approx(
            served["events"][tick].plan_seconds * 1e6)


def test_span_times_into_its_sink():
    sink = {}
    with span("engine.test", sink, a=1) as s:
        s.count(b=2)
    first = s.seconds
    assert first >= 0.0 and sink == {"engine.test": first}
    with span("engine.test", sink) as s2:
        pass
    assert sink["engine.test"] == first + s2.seconds
    with span("engine.test") as s3:           # no sink: timed only
        pass
    assert s3.seconds >= 0.0 and s3.t0 > 0.0
