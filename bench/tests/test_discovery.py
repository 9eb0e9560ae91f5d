"""A configuration, traffic mix, cache policy, limits and metric added as
new files are found by name, and a run of the new cell reports the new
metric, with no existing file of the benchmark edited."""
import hashlib
import json
import time

import harness
import tiny


def _hashes(root):
    return {p.relative_to(root): hashlib.sha1(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_files_are_found_and_run(tmp_path):
    root = tiny.make_root(tmp_path)
    before = _hashes(root)
    b = root / "bench"
    cfg = json.loads((b / "configs" / "tiny-dit.json").read_text())
    cfg["name"] = "tiny-dit-b"
    cfg["engine"]["slots"] = 2
    (b / "configs" / "tiny-dit-b.json").write_text(json.dumps(cfg))
    (b / "policies" / "fora2.json").write_text(json.dumps(
        {"policy": "none", "cfg_policy": "fastercache_cfg",
         "cfg_args": {"interval": 2}}))
    mix = json.loads((b / "traffic" / "tiny.uncached.poisson.json")
                     .read_text())
    mix.update(policy="fora2", rate_per_s=20.0)
    (b / "traffic" / "tiny.cfg2.poisson.json").write_text(json.dumps(mix))
    (b / "limits" / "tiny-dit-b.cfg2.poisson.json").write_text(json.dumps(
        {"sample": 2, "checks": tiny.LIMITS}))
    (b / "metrics" / "ticks_per_s.py").write_text(
        "def read(run):\n    return len(run.ticks) / run.window_s\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-dit-b", "source": "test",
                            "file": "bench/configs/tiny-dit-b.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-dit-b.cfg2.poisson",
                              "config": "tiny-dit-b",
                              "traffic": "tiny.cfg2.poisson", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "ticks_per_s", "unit": "1/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["tiny-dit-b.cfg2.poisson"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    bench = harness.Bench(root)
    cell = bench.cell("tiny-dit-b.cfg2.poisson")
    assert cell.config["engine"]["slots"] == 2
    assert cell.policy["cfg_args"] == {"interval": 2}
    rc, res = harness.run_cell(bench, "tiny-dit-b.cfg2.poisson", 5, 1.0,
                               False, t_process=time.perf_counter(),
                               require_tpu=False)
    assert rc == 0 and res["correct"], res
    assert res["metrics"]["ticks_per_s"]["value"] > 0
    after = _hashes(root)
    assert {k: v for k, v in after.items() if k in before} == before
