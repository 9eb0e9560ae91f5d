"""Modality layer: per-modality cached==uncached equivalence across the
whole policy registry, temporal-aware policies (per-frame TeaCache signal,
PAB branch broadcast), mixed-modality serving (refill isolation, per-
modality row accounting, warmup), negative-prompt null conditioning and
the FasterCacheCFG low-frequency residual variant."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (POLICY_REGISTRY, FasterCacheCFG, TemporalPABStack,
                        TemporalTeaCachePolicy, make_policy)
from repro.core.learned import init_gate
from repro.diffusion import ddim_step, linear_schedule, sample
from repro.diffusion.pipeline import backbone_fns, cfg_denoise_fn
from repro.modalities import (MODALITIES, MixedModalityEngine, get_modality,
                              make_workload)
from repro.serving.diffusion import DiffusionRequest, request_noise_key

NUM_STEPS = 8

#: always-compute hyperparameters: with these, every registry policy must
#: reproduce the exact uncached trajectory (the survey's C_t := F(x_t) base
#: case extended to whole trajectories) on every modality's shapes
ALWAYS_COMPUTE = {
    "none": {},
    "fora": {"interval": 1},
    "delta_dit": {"interval": 1},
    "teacache": {"delta": 0.0},
    "teacache_video": {"delta": 0.0},
    "magcache": {"delta": 0.0},
    "easycache": {"tau": 0.0},
    "foresight": {"gamma": 0.0},
    "taylorseer": {"interval": 1},
    "newtonseer": {"interval": 1},
    "hicache": {"interval": 1},
    "abcache": {"interval": 1},
    "foca": {"interval": 1},
    "freqca": {"interval": 1},
    "toca": {"interval": 1},
    "clusca": {"interval": 1},
    "speca": {"interval": 1},
    "fastercache_cfg": {"interval": 1},
    # PAB at model granularity: ranges all 1 -> every module type (incl.
    # the text cross-attn branch) recomputes each step
    "pab": {"ranges": dict.fromkeys(
        ("spatial_attn", "temporal_attn", "cross_attn", "mlp"), 1)},
    # constructor-argument policies: callable entries get the workload so
    # the gate/profile can match its latent shapes.  threshold=1.0 makes
    # the learned gate refresh every step (sigmoid <= 1); delta=0.0 under
    # a strictly positive profile recomputes at every calibrated step.
    "lazydit": lambda wl: {"gate": init_gate(jax.random.PRNGKey(0),
                                             wl.latent_dim),
                           "threshold": 1.0},
    "blockcache": lambda wl: {"profile": [1.0] * NUM_STEPS, "delta": 0.0},
}


def _tiny_workload(name):
    spec = get_modality(name)
    overrides = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                     d_ff=128, dit_patch_tokens=8, dit_in_dim=4,
                     dit_num_classes=10)
    if spec.temporal:
        overrides.update(dit_patch_tokens=4, dit_num_frames=2)
    if spec.text:
        overrides.update(dit_text_len=4)
    cfg = get_config(spec.arch_id).reduced(**overrides)
    wl = make_workload(name, cfg=cfg)
    if spec.text:
        # one shared PromptCache per text workload; the sweep conditions
        # every trajectory on the same (prompt, negative-prompt) pair so
        # cached==uncached equivalence covers the cross-attn branch too
        cache = wl.conditioner(seed=0)
        wl.extras["conditioner"] = cache
        wl.extras["text"] = cache.get("tiny smoke prompt")
        wl.extras["neg_text"] = cache.get("bad")
    return wl


@pytest.fixture(scope="module")
def workloads():
    return {name: _tiny_workload(name) for name in MODALITIES}


@pytest.fixture(scope="module")
def exact_cache():
    """Memoized exact (uncached) trajectories keyed by (modality,
    cfg_scale) — the registry sweep would otherwise recompute them per
    policy."""
    return {}


def _exact(exact_cache, workloads, modality, cfg_scale=0.0):
    key = (modality, cfg_scale)
    if key not in exact_cache:
        exact_cache[key], _ = _trajectory(workloads[modality], None,
                                          cfg_scale=cfg_scale)
    return exact_cache[key]


def _trajectory(wl, policy=None, seed=1, batch=1, **den_kw):
    if wl.spec.text:                 # text modalities denoise under prompts
        den_kw.setdefault("text", wl.extras["text"])
        den_kw.setdefault("neg_text", wl.extras["neg_text"])
    sched = linear_schedule(200)
    ts = sched.spaced(NUM_STEPS)
    xT = wl.noise(jax.random.PRNGKey(seed), batch)
    den = wl.denoiser(policy, **den_kw)
    x0, state = sample(den, xT, ts, sched, step_fn=ddim_step,
                       denoiser_state=den.init_state(batch))
    return np.asarray(x0), state


# ----------------------------------------------------------------------
# registry coverage notice + cached==uncached equivalence sweep
# ----------------------------------------------------------------------

def test_always_compute_map_covers_registry():
    """A new registry policy must declare its always-compute point here so
    the modality sweep below keeps covering the whole registry."""
    assert set(ALWAYS_COMPUTE) == set(POLICY_REGISTRY)


@pytest.mark.parametrize("modality", sorted(MODALITIES))
@pytest.mark.parametrize("name", sorted(POLICY_REGISTRY))
def test_always_compute_policies_match_uncached(workloads, exact_cache,
                                                modality, name):
    """Every registry policy, forced to its always-compute operating point,
    must reproduce the exact uncached trajectory on every modality's shapes
    — image latents, video clips (frame axis), audio mel-spectrograms."""
    wl = workloads[modality]
    extras = ALWAYS_COMPUTE[name]
    if callable(extras):
        extras = extras(wl)
    pol = wl.make_policy(name, num_steps=NUM_STEPS, **extras)
    if name == "fastercache_cfg":
        # CFG-branch policy: exercise it in its slot (uncond gate) instead
        exact = _exact(exact_cache, workloads, modality, cfg_scale=2.0)
        cached, _ = _trajectory(wl, None, cfg_scale=2.0, cfg_policy=pol)
    else:
        exact = _exact(exact_cache, workloads, modality)
        cached, _ = _trajectory(wl, pol)
    np.testing.assert_allclose(cached, exact, atol=1e-4, rtol=1e-4,
                               err_msg=f"{name} on {modality}")


@pytest.mark.parametrize("modality", sorted(MODALITIES))
def test_caching_actually_skips_per_modality(workloads, modality):
    """The same interval policy must SAVE compute on every modality (the
    cross-modality claim): n_compute < num_steps, output finite."""
    wl = workloads[modality]
    x0, state = _trajectory(wl, wl.make_policy("taylorseer", interval=4,
                                               num_steps=NUM_STEPS))
    assert np.isfinite(x0).all()
    # predictive policies track validity, interval schedule does the saving
    sched = make_policy("taylorseer", interval=4).static_schedule(NUM_STEPS)
    assert sum(sched) < NUM_STEPS


# ----------------------------------------------------------------------
# temporal-aware policies (core/temporal.py)
# ----------------------------------------------------------------------

def test_temporal_teacache_per_frame_reduction_fires_on_one_frame():
    """Motion concentrated in ONE frame must refresh the max-reduced policy
    while the clip-mean signal distance stays below threshold."""
    F, P, d = 4, 6, 8
    shape = (1, F * P, d)
    base = jnp.ones(shape)
    moved = base.at[:, :P, :].add(2.0)          # only frame 0 changes
    pol_max = TemporalTeaCachePolicy(delta=0.2, frames=F, reduce="max")
    pol_mean = TemporalTeaCachePolicy(delta=0.2, frames=F, reduce="mean")
    d_max = float(pol_max._signal_distance(moved, base))
    d_mean = float(pol_mean._signal_distance(moved, base))
    assert d_max > 0.2 > d_mean     # per-frame max sees it, clip mean doesn't
    # plain TeaCache's clip-level distance agrees with the mean view's scale
    from repro.core import TeaCachePolicy
    d_plain = float(TeaCachePolicy(0.2)._signal_distance(moved, base))
    assert abs(d_plain - d_mean) < d_max / 2


def test_temporal_teacache_want_compute_mirrors_apply(workloads):
    """The serving engine trusts want_compute to mirror apply's branch."""
    wl = workloads["video"]
    pol = wl.make_policy("teacache_video", num_steps=NUM_STEPS, delta=0.15)
    shape = (1, wl.tokens, wl.latent_dim)
    state = pol.init_state(shape, signal_shape=(1, wl.tokens, 8))
    key = jax.random.PRNGKey(0)
    for step in range(6):
        key, k1, k2 = jax.random.split(key, 3)
        x = jax.random.normal(k1, shape)
        sig = jax.random.normal(k2, (1, wl.tokens, 8)) * 0.05 * step
        want = bool(pol.want_compute(state, jnp.asarray(step), x, signal=sig))
        before = int(state["n_compute"])
        _, state = pol.apply(state, jnp.asarray(step), x, lambda v: v + 1.0,
                             signal=sig)
        assert (int(state["n_compute"]) - before == 1) == want


def test_temporal_pab_broadcasts_temporal_attention_longer(workloads):
    """PAB-faithful broadcast: over a trajectory the temporal-attention
    branch recomputes at a LONGER interval than the spatial branch, and the
    all-compute step (step 0) is exact."""
    wl = workloads["video"]
    calls = {"spatial_attn": 0, "temporal_attn": 0, "mlp": 0}
    from repro.models import video_dit
    counted = {
        name: (lambda p, x, c, fn=fn, n=name:
               (calls.__setitem__(n, calls[n] + 1),
                fn(p, x, c, wl.cfg))[1])
        for name, fn in video_dit.BRANCH_FNS.items()}
    stack = TemporalPABStack(counted, wl.cfg.num_layers)
    assert stack.intervals["temporal_attn"] > stack.intervals["spatial_attn"]

    feat = (1, wl.tokens, wl.cfg.d_model)
    state = stack.init(feat)
    x = jax.random.normal(jax.random.PRNGKey(0), feat)
    c = jax.random.normal(jax.random.PRNGKey(1), (1, wl.cfg.d_model))
    for step in range(8):
        calls_before = dict(calls)
        _, state = stack(state, step, x, wl.params["blocks"], c)
        for name in calls:
            computed = calls[name] > calls_before[name]
            assert computed == (step % stack.intervals[name] == 0), (name, step)
    # tracing calls each branch once per concrete-step compute step (the
    # scan traces the layer body once); spatial fired on more steps
    assert calls["spatial_attn"] > calls["temporal_attn"]


def test_pab_video_granularity_step0_exact(workloads):
    """At step 0 every PAB branch computes, so the pab_video denoiser's
    first backbone output must equal the plain forward."""
    wl = workloads["video"]
    den = wl.denoiser(granularity="pab_video")
    x = wl.noise(jax.random.PRNGKey(3), 1)
    t_vec = jnp.full((1,), 10.0, jnp.float32)
    eps, _ = den(den.init_state(1), 0, x, t_vec)
    fwd, _ = backbone_fns(wl.cfg)
    ref = fwd(wl.params, x, t_vec, jnp.zeros((1,), jnp.int32))
    np.testing.assert_allclose(np.asarray(eps), np.asarray(ref), atol=1e-5)


def test_pab_video_reduces_compute_and_stays_finite(workloads):
    wl = workloads["video"]
    x0, _ = _trajectory(wl, granularity="pab_video")
    assert np.isfinite(x0).all()
    stack = wl.pab_stack()
    assert 0.0 < stack.compute_fraction(NUM_STEPS) < 1.0


# ----------------------------------------------------------------------
# serving: engine == single-trajectory reference per modality
# ----------------------------------------------------------------------

def _engine_vs_reference(wl, policy_name, policy_kw, cfg_policy=None,
                         cfg_scale=0.0):
    pol = wl.make_policy(policy_name, num_steps=NUM_STEPS, **policy_kw)
    eng = wl.engine(pol, slots=2, max_steps=NUM_STEPS, cfg_policy=cfg_policy)
    req = DiffusionRequest(0, NUM_STEPS, seed=7, cfg_scale=cfg_scale)
    res = eng.serve([req])
    sched = linear_schedule(1000)
    ts = sched.spaced(NUM_STEPS)
    xT = jax.random.normal(request_noise_key(req),
                           (1, wl.tokens, wl.latent_dim))
    ref_pol = wl.make_policy(policy_name, num_steps=NUM_STEPS, **policy_kw)
    den = wl.denoiser(ref_pol, cfg_scale=cfg_scale, cfg_policy=cfg_policy)
    ref, _ = sample(den, xT, ts, sched, step_fn=ddim_step,
                    denoiser_state=den.init_state(1))
    np.testing.assert_allclose(res[0].x0, np.asarray(ref[0]), atol=5e-3,
                               rtol=1e-3)
    return eng, res


@pytest.mark.parametrize("modality,policy,kw", [
    ("image", "teacache", {"delta": 0.1}),
    ("video", "teacache_video", {"delta": 0.1}),
    ("video", "fora", {"interval": 3}),
    ("audio", "taylorseer", {"interval": 2}),
])
def test_serving_matches_reference_per_modality(workloads, modality, policy,
                                                kw):
    _engine_vs_reference(workloads[modality], policy, kw)


def test_video_serving_temporal_cache_saves_rows(workloads):
    """Acceptance: temporal caching reduces backbone rows on the video
    workload at equal output vs the request's own reference trajectory."""
    wl = workloads["video"]
    eng, res = _engine_vs_reference(wl, "teacache_video", {"delta": 0.3})
    s = eng.telemetry.summary()
    assert s["backbone_rows_saved"] > 0
    assert res[0].record.computed_steps < NUM_STEPS


# ----------------------------------------------------------------------
# mixed-modality pools
# ----------------------------------------------------------------------

def _mixed_engine(workloads, slots=2, cfg_policy_image=None):
    return MixedModalityEngine({
        "image": workloads["image"].engine(
            make_policy("teacache", delta=0.1), slots=slots,
            max_steps=NUM_STEPS, cfg_policy=cfg_policy_image),
        "video": workloads["video"].engine(
            workloads["video"].make_policy("teacache_video", delta=0.1,
                                           num_steps=NUM_STEPS),
            slots=slots, max_steps=NUM_STEPS),
        "audio": workloads["audio"].engine(
            make_policy("fora", interval=2), slots=slots,
            max_steps=NUM_STEPS),
    })


def _mixed_requests(n):
    mods = ("image", "video", "audio")
    return [DiffusionRequest(i, num_steps=NUM_STEPS - 2 * (i % 2), seed=i,
                             class_label=i % 5, modality=mods[i % 3])
            for i in range(n)]


def test_mixed_pool_end_to_end_with_per_modality_telemetry(workloads):
    eng = _mixed_engine(workloads)
    reqs = _mixed_requests(9)
    res = eng.serve(reqs)
    assert [r.request_id for r in res] == list(range(9))
    assert all(np.isfinite(r.x0).all() for r in res)
    # per-modality shapes survived the pool
    shapes = {r.record.modality: r.x0.shape for r in res}
    assert shapes["video"][0] == workloads["video"].tokens
    assert shapes["image"][0] == workloads["image"].tokens

    per = eng.telemetry.by_modality()
    assert set(per) == {"image", "video", "audio"}
    for m, s in per.items():
        assert s["requests"] == 3
        assert s["backbone_rows_computed"] > 0
    top = eng.telemetry.summary()
    assert top["requests"] == 9
    assert top["backbone_rows_computed"] == sum(
        s["backbone_rows_computed"] for s in per.values())
    # token-weighted accounting: video rows are wider than their count
    assert top["backbone_tokens_computed"] > top["backbone_rows_computed"]
    assert set(top["rows_by_modality"]) == {"image", "video", "audio"}


def test_mixed_pool_refill_isolation(workloads):
    """More requests than slots: every request's output must equal serving
    it alone on a fresh engine (reset-on-refill across modality sub-pools —
    slot reuse never leaks cache state between requests)."""
    eng = _mixed_engine(workloads)
    reqs = _mixed_requests(8)              # 8 requests over 3 pools x 2 slots
    res = eng.serve(reqs)
    assert len(res) == 8
    for req, r in zip(reqs, res):
        solo = _mixed_engine(workloads).serve([req])[0]
        np.testing.assert_allclose(r.x0, solo.x0, atol=5e-4, rtol=1e-3,
                                   err_msg=f"request {req.request_id} "
                                           f"({req.modality})")


def test_mixed_pool_rejects_unknown_modality(workloads):
    eng = _mixed_engine(workloads)
    with pytest.raises(KeyError):
        eng.serve([DiffusionRequest(0, NUM_STEPS, modality="3d")])


def test_string_policy_gets_config_frame_count(workloads):
    """The engine's string-policy path must size teacache_video's per-frame
    grouping from the CONFIG, not the registry default."""
    wl = workloads["video"]
    eng = wl.engine("teacache_video", slots=1, max_steps=NUM_STEPS)
    assert eng.policy.frames == wl.frames


def test_one_session_per_engine_enforced(workloads):
    """Interleaved sessions of ONE engine would corrupt its per-slot tables
    — the second start_session must refuse; finish() releases the engine."""
    eng = workloads["image"].engine("none", slots=1, max_steps=NUM_STEPS)
    s1 = eng.start_session([DiffusionRequest(0, NUM_STEPS)])
    with pytest.raises(RuntimeError):
        eng.start_session([DiffusionRequest(1, NUM_STEPS)])
    while not s1.done:
        s1.tick()
    s1.finish()
    assert len(eng.serve([DiffusionRequest(2, NUM_STEPS)])) == 1


def test_mixed_pool_rejects_shared_engine_instance(workloads):
    eng = workloads["image"].engine("none", slots=1, max_steps=NUM_STEPS)
    with pytest.raises(ValueError):
        MixedModalityEngine({"a": eng, "b": eng})


def test_mixed_warmup_precompiles_every_bucket(workloads):
    """engine.warmup() across sub-pools: every bucket program a compacted
    tick can request must already be compiled before the first tick."""
    eng = _mixed_engine(workloads)
    eng.warmup()
    for name, pool in eng.pools.items():
        S = pool.slots
        expected = ({0}
                    | {min(1 << (n - 1).bit_length(), S)
                       for n in range(1, S + 1)}
                    | {min(1 << (n - 1).bit_length(), 2 * S)
                       for n in range(1, 2 * S + 1)})
        assert set(pool._compact_ticks) == expected, name
    # serving dispatches only pre-compiled buckets — nothing new appears
    eng.serve(_mixed_requests(3))
    for name, pool in eng.pools.items():
        S = pool.slots
        expected = ({0}
                    | {min(1 << (n - 1).bit_length(), S)
                       for n in range(1, S + 1)}
                    | {min(1 << (n - 1).bit_length(), 2 * S)
                       for n in range(1, 2 * S + 1)})
        assert set(pool._compact_ticks) == expected, name


def test_compacted_matches_dense_video_pool(workloads):
    """Row compaction must stay output-equal on the video modality."""
    wl = workloads["video"]
    reqs = [DiffusionRequest(i, num_steps=NUM_STEPS, seed=i,
                             cfg_scale=2.0 if i % 2 == 0 else 0.0)
            for i in range(3)]
    out = {}
    for compact in (True, False):
        eng = wl.engine(wl.make_policy("teacache_video", delta=0.1,
                                       num_steps=NUM_STEPS),
                        slots=2, max_steps=NUM_STEPS,
                        cfg_policy=FasterCacheCFG(3, NUM_STEPS),
                        row_compaction=compact)
        out[compact] = eng.serve(reqs)
    for a, b in zip(out[True], out[False]):
        np.testing.assert_allclose(a.x0, b.x0, atol=5e-4, rtol=1e-3)
        assert a.record.computed_steps == b.record.computed_steps


# ----------------------------------------------------------------------
# negative-prompt null conditioning (CFG follow-up #1)
# ----------------------------------------------------------------------

def test_null_vector_conditioning_matches_reference(workloads):
    """A null_label VECTOR must flow through the serving engine and match
    the single-trajectory CachedDenoiser(null_embed=...) path."""
    wl = workloads["image"]
    vec = np.asarray(jax.random.normal(jax.random.PRNGKey(9),
                                       (wl.cfg.d_model,))) * 0.1
    req = DiffusionRequest(0, NUM_STEPS, seed=3, cfg_scale=2.5,
                           null_label=vec)
    eng = wl.engine(make_policy("fora", interval=2), slots=2,
                    max_steps=NUM_STEPS,
                    cfg_policy=FasterCacheCFG(2, NUM_STEPS))
    res = eng.serve([req])
    sched = linear_schedule(1000)
    ts = sched.spaced(NUM_STEPS)
    xT = jax.random.normal(request_noise_key(req),
                           (1, wl.tokens, wl.latent_dim))
    den = wl.denoiser(make_policy("fora", interval=2), cfg_scale=2.5,
                      cfg_policy=FasterCacheCFG(2, NUM_STEPS), null_embed=vec)
    ref, _ = sample(den, xT, ts, sched, step_fn=ddim_step,
                    denoiser_state=den.init_state(1))
    np.testing.assert_allclose(res[0].x0, np.asarray(ref[0]), atol=5e-3,
                               rtol=1e-3)


def test_null_vector_changes_output_vs_null_class(workloads):
    """The vector must actually condition the uncond branch: output differs
    from the default null-class run, and the uncached cfg_denoise_fn
    reference agrees with the engine on both."""
    wl = workloads["image"]
    vec = np.asarray(jax.random.normal(jax.random.PRNGKey(11),
                                       (wl.cfg.d_model,))) * 0.5
    eng = wl.engine("none", slots=1, max_steps=NUM_STEPS)
    base = eng.serve([DiffusionRequest(0, NUM_STEPS, seed=4, cfg_scale=2.0)])
    with_vec = eng.serve([DiffusionRequest(0, NUM_STEPS, seed=4,
                                           cfg_scale=2.0, null_label=vec)])
    assert np.abs(base[0].x0 - with_vec[0].x0).max() > 1e-4

    req = DiffusionRequest(0, NUM_STEPS, seed=4, cfg_scale=2.0,
                           null_label=vec)
    sched = linear_schedule(1000)
    ts = sched.spaced(NUM_STEPS)
    xT = jax.random.normal(request_noise_key(req),
                           (1, wl.tokens, wl.latent_dim))
    exact, _ = sample(cfg_denoise_fn(wl.params, wl.cfg, 2.0, null_embed=vec),
                      xT, ts, sched, step_fn=ddim_step)
    np.testing.assert_allclose(with_vec[0].x0, np.asarray(exact[0]),
                               atol=5e-3, rtol=1e-3)


def test_null_vector_bad_shape_rejected(workloads):
    wl = workloads["image"]
    eng = wl.engine("none", slots=1, max_steps=NUM_STEPS)
    with pytest.raises(ValueError):
        eng.serve([DiffusionRequest(0, NUM_STEPS, cfg_scale=2.0,
                                    null_label=np.zeros(3, np.float32))])


# ----------------------------------------------------------------------
# FasterCacheCFG low-frequency residual variant (CFG follow-up #2)
# ----------------------------------------------------------------------

def test_fastercache_lowfreq_interval1_exact(workloads, exact_cache):
    """At interval=1 the lowfreq variant never reuses: exact guided output
    on every modality."""
    for name, wl in workloads.items():
        exact = _exact(exact_cache, workloads, name, cfg_scale=2.0)
        got, _ = _trajectory(wl, None, cfg_scale=2.0,
                             cfg_policy=FasterCacheCFG(
                                 1, NUM_STEPS, mode="lowfreq"))
        np.testing.assert_allclose(got, exact, atol=1e-4, rtol=1e-4)


def test_fastercache_lowfreq_serving_matches_reference(workloads):
    """Engine == CachedDenoiser on the lowfreq cond-residual mode (the
    cond_out signal must thread identically through both paths)."""
    wl = workloads["image"]
    _engine_vs_reference(wl, "fora", {"interval": 2},
                         cfg_policy=FasterCacheCFG(2, NUM_STEPS,
                                                   mode="lowfreq"),
                         cfg_scale=2.5)


def test_fastercache_lowfreq_differs_from_extrapolate(workloads):
    """The two reconstructions are genuinely different approximations, both
    finite and both cheaper than naive two-branch (same schedule)."""
    wl = workloads["image"]
    outs = {}
    for mode in ("extrapolate", "lowfreq"):
        pol = FasterCacheCFG(3, NUM_STEPS, mode=mode)
        outs[mode], _ = _trajectory(wl, None, cfg_scale=3.0, cfg_policy=pol)
        assert np.isfinite(outs[mode]).all()
        assert pol.static_schedule(NUM_STEPS).count(True) < NUM_STEPS
    assert np.abs(outs["extrapolate"] - outs["lowfreq"]).max() > 1e-5


def test_fastercache_lowfreq_halves_cache_memory():
    shape = (1, 16, 8)
    from repro.core import cache_state_bytes
    extra = FasterCacheCFG(4, 8).init_state(shape)
    low = FasterCacheCFG(4, 8, mode="lowfreq").init_state(shape)
    assert cache_state_bytes(low) == cache_state_bytes(extra) // 2
