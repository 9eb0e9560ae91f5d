"""The carried plan: each tick program of an engine that plans on the
device ends with the want pass over its own outputs, and the session reads
that plan on the next tick instead of dispatching the standalone want pass,
unless admission refilled a slot in between.  The decisions, the reported
metric and the served samples must be those of a want pass run every tick;
engines whose plan is a host static schedule build no tail at all."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import FasterCacheCFG
from repro.models import init_params, perturb_zero_init
from repro.obs import MetricsRegistry
from repro.serving.diffusion import (DiffusionRequest, DiffusionServingEngine,
                                     ServeSession)

NUM_STEPS = 8
SLOTS = 2
#: the tick at which the second wave of requests is submitted
SUBMIT_AT = 4

_TINY = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
             d_ff=128, dit_in_dim=4, dit_num_classes=10)

#: engine cases: (config, policy, cfg_policy, row_compaction)
CASES = {
    "teacache+fastercache": ("image", "teacache", "fastercache", True),
    "teacache+fastercache-dense": ("image", "teacache", "fastercache", False),
    # a step-dependent predicate; the naive uncond branch's host static
    # plan overrides the device's
    "magcache+naive-cfg": ("image", "magcache", None, True),
    "teacache_video+fastercache": ("video", "teacache_video", "fastercache",
                                   True),
}


def _config(kind):
    if kind == "video":
        return get_config("dit-video").reduced(
            dit_patch_tokens=4, dit_num_frames=2, **_TINY)
    return get_config("dit-xl").reduced(dit_patch_tokens=8, **_TINY)


def _engine(kind, policy, cfg_policy, compact):
    cfg = _config(kind)
    params = perturb_zero_init(init_params(jax.random.PRNGKey(0), cfg))
    eng = DiffusionServingEngine(
        params, cfg, policy, slots=SLOTS, max_steps=NUM_STEPS,
        cfg_policy=(FasterCacheCFG(3, NUM_STEPS)
                    if cfg_policy == "fastercache" else None),
        row_compaction=compact)
    eng.warmup()
    return eng


def _requests():
    """Five requests of mixed budgets, guided and unguided: three at the
    start (one waits for a slot), two submitted mid-session, so slots
    finish, stand free for a few ticks, and are refilled."""
    return [DiffusionRequest(i, num_steps=(NUM_STEPS, NUM_STEPS - 2,
                                           NUM_STEPS - 3)[i % 3],
                             seed=i, class_label=i % 5,
                             cfg_scale=2.5 if i % 2 == 0 else 0.0)
            for i in range(5)]


def _drive(eng, drop_carried=False):
    """Serve the requests tick by tick; returns the TickEvents, the samples
    by request id, each tick's plan source (from the registry counter) and
    how many times the standalone want pass was dispatched."""
    reqs = _requests()
    want_calls = []
    want = eng._want_all

    def counted(*args):
        want_calls.append(1)
        return want(*args)

    events, sources = [], []
    registry = MetricsRegistry()
    counter = registry.counter("repro_engine_plans_total")
    eng._want_all = counted
    orig_tick = ServeSession.tick
    try:
        with pytest.MonkeyPatch.context() as mp:
            if drop_carried:
                def tick(self):
                    self._plan = None
                    orig_tick(self)
                mp.setattr(ServeSession, "tick", tick)
            session = eng.start_session(reqs[:3], hooks=[events.append],
                                        metrics=registry)
            try:
                while not session.done or session.ticks <= SUBMIT_AT:
                    if session.ticks == SUBMIT_AT:
                        for r in reqs[3:]:
                            session.submit(r)
                    before = {s: counter.value(source=s, modality="image")
                              for s in ("carried", "want_pass", "static")}
                    session.tick()
                    sources.append([
                        s for s, v in before.items()
                        if counter.value(source=s, modality="image") == v + 1])
            finally:
                results = session.finish()
    finally:
        eng._want_all = want
    return (events, {r.request_id: r.x0 for r in results}, sources,
            len(want_calls))


@pytest.fixture(scope="module", params=list(CASES))
def served(request):
    """One engine per case, served with the carried plan and again with
    the want pass forced on every tick."""
    eng = _engine(*CASES[request.param])
    return {"engine": eng, "carried": _drive(eng),
            "want_pass": _drive(eng, drop_carried=True)}


def test_carried_plan_decides_as_the_want_pass(served):
    events, x0s, _, _ = served["carried"]
    ref_events, ref_x0s, _, _ = served["want_pass"]
    assert len(events) == len(ref_events)
    for ev, ref in zip(events, ref_events):
        np.testing.assert_array_equal(ev.active, ref.active)
        np.testing.assert_array_equal(ev.want_cond, ref.want_cond)
        np.testing.assert_array_equal(ev.want_uncond, ref.want_uncond)
        np.testing.assert_allclose(ev.metric, ref.metric, rtol=1e-6)
    assert set(x0s) == set(ref_x0s) == set(range(5))
    for rid, x0 in x0s.items():
        np.testing.assert_allclose(x0, ref_x0s[rid], rtol=1e-5, atol=1e-6)
    # the session covered what the carried plan must survive: admission
    # mid-session, a slot standing free, cache reuse next to refreshes,
    # guided and unguided slots side by side
    admit_ticks = [ev.tick for ev in events if ev.admitted]
    assert admit_ticks[0] == 0 and max(admit_ticks) > SUBMIT_AT
    assert any(not ev.active.all() and not ev.admitted for ev in events)
    wants = np.concatenate([ev.want_cond[ev.active] for ev in events])
    assert wants.any() and not wants.all()
    assert any((ev.active & ev.guided).any() and (ev.active & ~ev.guided).any()
               for ev in events)


def test_want_pass_runs_only_on_the_first_and_admission_ticks(served):
    events, _, sources, want_calls = served["carried"]
    expect = ["want_pass" if n == 0 or ev.admitted else "carried"
              for n, ev in enumerate(events)]
    assert sources == [[s] for s in expect]
    assert want_calls == expect.count("want_pass")
    assert "carried" in expect
    # forced every tick, the want pass is the plan of every tick
    ref_events, _, ref_sources, ref_calls = served["want_pass"]
    assert ref_sources == [["want_pass"]] * len(ref_events)
    assert ref_calls == len(ref_events)


def test_static_plan_engine_builds_no_tail_and_never_calls_want():
    """fora with naive (uncached) CFG plans both branches on the host: its
    tick programs take no next timesteps, return no plan, and the session
    carries none."""
    eng = _engine("image", "fora", None, True)
    assert not eng._device_plan
    assert "want" not in eng.program_profile
    tick_args, _ = eng._warmup_operands()
    assert len(tick_args) == 14
    events, x0s, sources, want_calls = _drive(eng)
    assert set(x0s) == set(range(5))
    assert want_calls == 0
    assert sources == [["static"]] * len(events)
    assert all(ev.metric is None for ev in events)
