#!/usr/bin/env python3
"""Chip smoke run: serve DiT-XL/2 at its published widths on one TPU.

Drives the diffusion serving path through the entry points a user calls
(`DiffusionServingEngine.warmup` and `.serve`) with random weights at full
width: 28 layers, d_model 1152, 256 latent tokens, 1000 classes, bf16
params, the AdaLN-zero leaves perturbed (`models.perturb_zero_init`).

  1. A TeaCache + FasterCacheCFG engine (8 slots, 50-step budget) serves 16
     requests: 50- and 25-step DDIM, half of them guided at cfg_scale 4.0.
  2. An uncached engine serves one guided and one unguided request. Each
     x0 is compared with the plain sampler (`diffusion.samplers.sample` over
     `pipeline.cfg_denoise_fn`) on the same device; the relative L2 error
     must be at most 1e-2.

Both serves run under `RetraceSentinel` and must compile nothing after
warmup.  The lines before the last report the compile seconds of each
warmed program, the requests served, the relative errors, peak device
memory and the recompile counts; none of them is a benchmark number.  The
last line is one JSON object naming the device.  With no TPU, the script
exits non-zero before it serves anything.

    python chip_smoke.py
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SLOTS = 8
MAX_STEPS = 50
SHORT_STEPS = 25
N_REQUESTS = 16
CFG_SCALE = 4.0
CFG_INTERVAL = 3      # FasterCacheCFG: uncond branch refreshed every 3rd step
REL_TOL = 1e-2
SEED = 0


class SmokeFailure(RuntimeError):
    """A phase of the smoke run produced a wrong or missing result."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _rel_l2(x, ref) -> float:
    import numpy as np
    x = np.asarray(x, np.float64).ravel()
    ref = np.asarray(ref, np.float64).ravel()
    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))


def _warm(engine, label: str, log) -> dict:
    profiles = engine.warmup()
    secs = {str(k): p.compile_seconds for k, p in profiles.items()}
    for k, s in secs.items():
        log(f"compile {label} program {k}: {s:.3f} s")
    return secs


def serve_smoke(cfg, log=print) -> dict:
    """Serve the smoke traffic on `cfg` through both engines and check the
    results; raises SmokeFailure on any wrong result.  Returns what it
    measured (compile seconds, served count, relative errors, recompile
    counts, peak device memory)."""
    import jax
    import numpy as np

    from repro.analysis.ir import RetraceSentinel
    from repro.core import FasterCacheCFG
    from repro.diffusion import ddim_step, sample
    from repro.diffusion.pipeline import cfg_denoise_fn
    from repro.models import init_params, perturb_zero_init
    from repro.serving.diffusion import (DiffusionRequest,
                                         DiffusionServingEngine,
                                         request_noise_key)

    params = perturb_zero_init(init_params(jax.random.PRNGKey(SEED), cfg),
                               seed=SEED)
    T, D = cfg.dit_tokens, cfg.dit_in_dim
    out = {"compile_seconds": {}, "recompiles": {}, "rel_err": {}}

    # -- phase 1: cached engine, mixed budgets, half guided ---------------
    engine = DiffusionServingEngine(
        params, cfg, "teacache", slots=SLOTS, max_steps=MAX_STEPS,
        cfg_policy=FasterCacheCFG(CFG_INTERVAL, MAX_STEPS))
    out["compile_seconds"]["cached"] = _warm(engine, "cached", log)
    requests = [
        DiffusionRequest(i, SHORT_STEPS if i % 4 == 3 else MAX_STEPS,
                         seed=SEED,
                         class_label=(37 * i) % cfg.dit_num_classes,
                         cfg_scale=CFG_SCALE if i % 2 == 0 else 0.0)
        for i in range(N_REQUESTS)]
    with RetraceSentinel() as sentinel:
        results = engine.serve(requests)
    out["recompiles"]["cached"] = sentinel.count
    out["served"] = len(results)
    log(f"served {len(results)} of {len(requests)} requests "
        f"(recompiles during serve: {sentinel.count})")
    _check(len(results) == len(requests),
           f"served {len(results)} of {len(requests)} requests")
    for r in results:
        _check(r.x0.shape == (T, D), f"request {r.request_id}: x0 shape "
               f"{r.x0.shape} != {(T, D)}")
        _check(bool(np.isfinite(r.x0).all()),
               f"request {r.request_id}: non-finite x0")
    _check(sentinel.count == 0, f"cached serve compiled {sentinel.count} "
           f"program(s) after warmup: {sorted(set(sentinel.compiled_names))}")

    # -- phase 2: uncached engine against the plain sampler ---------------
    exact = DiffusionServingEngine(params, cfg, "none", slots=2,
                                   max_steps=MAX_STEPS)
    out["compile_seconds"]["none"] = _warm(exact, "none", log)
    pair = [DiffusionRequest(0, MAX_STEPS, seed=SEED + 1, class_label=7,
                             cfg_scale=CFG_SCALE),
            DiffusionRequest(1, MAX_STEPS, seed=SEED + 1, class_label=11)]
    with RetraceSentinel() as sentinel:
        served = exact.serve(pair)
    out["recompiles"]["none"] = sentinel.count
    log(f"served {len(served)} of {len(pair)} reference requests "
        f"(recompiles during serve: {sentinel.count})")
    _check(len(served) == len(pair),
           f"served {len(served)} of {len(pair)} reference requests")
    _check(sentinel.count == 0, f"uncached serve compiled {sentinel.count} "
           f"program(s) after warmup: {sorted(set(sentinel.compiled_names))}")
    for req, res in zip(pair, served):
        ts = exact.sched.spaced(req.num_steps)
        x_T = jax.random.normal(request_noise_key(req), (1, T, D))
        ref, _ = sample(cfg_denoise_fn(params, cfg, req.cfg_scale,
                                       req.class_label),
                        x_T, ts, exact.sched, step_fn=ddim_step)
        ref = np.asarray(ref[0])
        _check(bool(np.isfinite(ref).all()),
               f"reference for request {req.request_id} is not finite")
        err = _rel_l2(res.x0, ref)
        kind = "guided" if req.guided else "unguided"
        out["rel_err"][kind] = err
        log(f"relative L2 error, {kind} request vs plain sampler: {err!r}")
        _check(err <= REL_TOL, f"{kind} request: relative L2 error {err} "
               f"> {REL_TOL}")

    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    log(f"peak_bytes_in_use: {out['peak_bytes_in_use']}")
    return out


def main() -> int:
    from repro.launch.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {dev.platform!r} devices); "
              f"nothing served", file=sys.stderr)
        return 2
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache: "
          f"{cache_dir}")

    from repro.configs import get_config
    serve_smoke(get_config("dit-xl"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
