"""`correct` at a size a CPU test can hold: a sound run passes, the float8
control reads far above the program, and a run with the timed path
broken underneath comes out not correct, once per fault a serving cell
can have."""
import time

import numpy as np
import pytest

import harness
import reference
import tiny
from repro.serving.diffusion import engine as engine_mod

CELL = "tiny-dit.teacache.poisson"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell=CELL, seed=5):
    rc, res = harness.run_cell(harness.Bench(root), cell, seed, 1.0, False,
                               t_process=time.perf_counter(),
                               require_tpu=False)
    assert rc == 0
    return res


def test_sound_run_is_correct(root):
    res = _run(root)
    assert res["correct"], res["checks"]
    assert res["checks"]["x0_rel_l2"]["value"] < 0.01
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", [CELL, "tiny-video.teacache.backlog",
                                  "tiny-dit.uncached.poisson"])
def test_control_reads_far_above_the_program(root, cell):
    bench = harness.Bench(root)
    c = bench.cell(cell)
    backbone, params, engine = harness.build_server(bench, c)
    rec, session, win = harness.serve_cell(c, engine, 9, 1.0)
    session.finish()
    served = harness._served(rec, session, win["reqs"], win["measured"])
    sample = reference.draw_sample(served, 3, 9)
    refs = [reference.Reference(backbone, c.model, params, c.policy,
                                c.config["noise_schedule"],
                                reference.DOTS[k])
            for k in ("reference", "control")]
    out = reference.compare(refs[0], sample, control=refs[1])
    for k in ("x0_rel_l2", "metric_gap"):
        if k in c.limits["checks"]:
            assert out[f"control_{k}"] > 3 * out[k]
            assert out[f"control_{k}"] > c.limits["checks"][k]
    # the control in the served requests' place, judged as a run is
    numbers = dict(out, window_compiles=win["compiles"], failed=0,
                   sampled=len(sample))
    assert harness.judge(numbers, c.limits)[1]
    assert not harness.judge(
        dict(numbers, x0_rel_l2=out["control_x0_rel_l2"],
             metric_gap=out["control_metric_gap"]), c.limits)[1]


def _wrap_tick(monkeypatch, change):
    orig = engine_mod.DiffusionServingEngine._compact_tick

    def broken(self, bucket):
        fn = orig(self, bucket)

        def run(*args):
            return change(self, fn, args)
        return run
    monkeypatch.setattr(engine_mod.DiffusionServingEngine, "_compact_tick",
                        broken)


def _state_unchanged(monkeypatch):
    _wrap_tick(monkeypatch, lambda eng, fn, args: (args[3], args[1]))


def _half_the_rows_left_out(monkeypatch):
    import jax.numpy as jnp

    def change(eng, fn, args):
        dest = np.asarray(args[-1]).copy()
        dest[len(dest) // 2:] = 2 * eng.slots      # the discarded row
        return fn(*args[:-1], jnp.asarray(dest))
    _wrap_tick(monkeypatch, change)


def _answer_altered(monkeypatch):
    orig = engine_mod.DiffusionResult

    def altered(rid, x0, rec):
        x0 = np.array(x0)
        x0[0] = -x0[0]
        return orig(rid, x0, rec)
    monkeypatch.setattr(engine_mod, "DiffusionResult", altered)


def _cache_threshold_altered(monkeypatch):
    orig = harness.make_engine

    def make(cell, params):
        eng = orig(cell, params)
        eng.policy.delta = 100.0 * eng.policy.delta   # never refreshes
        return eng
    monkeypatch.setattr(harness, "make_engine", make)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_rows_left_out,
                                   _answer_altered,
                                   _cache_threshold_altered])
def test_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(root)
    assert not res["correct"], res["checks"]
    failed = [k for k, c in res["checks"].items()
              if c["value"] > c["limit"]]
    assert failed


def _served(rid, slot, steps):
    z = np.zeros(steps, bool)
    return reference.Served(rid=rid, noise_seed=rid, num_steps=steps,
                            label=0, cfg_scale=4.0, slot=slot, want_cond=z,
                            want_uncond=z, metric=np.zeros(steps, np.float32),
                            x0=np.zeros((4, 4)))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_sample_covers_every_slot_and_the_longest(seed):
    # twelve finished requests over four slots; one long one in slot 2
    cands = [_served(i, i % 4, 50 if i == 6 else 25) for i in range(12)]
    sample = reference.draw_sample(cands, 4, seed)
    assert {s.slot for s in sample} == {0, 1, 2, 3}
    assert any(s.rid == 6 for s in sample)
    assert reference.draw_sample(cands, 4, seed) == sample
