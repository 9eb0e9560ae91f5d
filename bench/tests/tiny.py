"""A copy of the benchmark at a size a CPU test can hold: the benchmark's
files under a temporary root, plus tiny configurations of both families
and one cell per cache policy, named in that root's BENCHMARK.json."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent

TINY_MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 2,
              "num_kv_heads": 2, "head_dim": 32, "d_ff": 128,
              "dit_patch_tokens": 16, "dit_in_dim": 4,
              "dit_num_classes": 10, "dit_num_frames": 0,
              "dtype": "bfloat16"}
SCHEDULE = {"kind": "linear", "T": 1000, "beta_min": 0.0001,
            "beta_max": 0.02}
LIMITS = {"x0_rel_l2": 0.05, "metric_gap": 0.05, "decision_mismatch": 0,
          "window_compiles": 0, "failed": 0}

CELLS = {
    "tiny-dit.teacache.poisson": ("tiny-dit", "tiny.teacache.poisson"),
    "tiny-dit.uncached.poisson": ("tiny-dit", "tiny.uncached.poisson"),
    "tiny-video.teacache.backlog": ("tiny-video", "tiny.teacache.backlog"),
}


def paths() -> None:
    """Put the benchmark and the program on sys.path."""
    for p in (str(BENCH), str(CHECKOUT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def make_root(tmp: Path, rate: float = 40.0) -> Path:
    """A root holding a copy of `bench/` and a BENCHMARK.json whose cells
    are the tiny ones; returns the root."""
    root = Path(tmp) / "root"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    b = root / "bench"
    for name, family, frames, tokens in (("tiny-dit", "dit", 0, 16),
                                         ("tiny-video", "video_dit", 4, 8)):
        model = dict(TINY_MODEL, dit_num_frames=frames,
                     dit_patch_tokens=tokens)
        _write(b / "configs" / f"{name}.json", {
            "name": name, "source": "test", "reduced": [],
            "repo_config": "dit-video" if frames else "dit-xl",
            "family": family, "model": model,
            "engine": {"slots": 4 if frames else 3, "max_steps": 6},
            "noise_schedule": SCHEDULE, "weight_seed": 0})
    common = {"steps": {"6": 0.75, "3": 0.25}, "cfg_scale": 4.0,
              "traffic_seed": 0, "drain_s": 60.0}
    _write(b / "traffic" / "tiny.teacache.poisson.json",
           dict(common, policy="teacache", arrivals="poisson",
                rate_per_s=rate, warmup_s=0.5))
    _write(b / "traffic" / "tiny.uncached.poisson.json",
           dict(common, policy="uncached", arrivals="poisson",
                rate_per_s=rate, warmup_s=0.5))
    _write(b / "traffic" / "tiny.teacache.backlog.json",
           dict(common, policy="teacache_video", arrivals="backlog",
                depth=4, steps={"6": 1.0}, warmup_s=0.5))
    for cell in CELLS:
        checks = dict(LIMITS)
        if "uncached" in cell:
            del checks["metric_gap"]        # no thresholded policy
        _write(b / "limits" / f"{cell}.json", {"sample": 3,
                                               "checks": checks})
    spec["configs"] = [{"name": n, "source": "test",
                        "file": f"bench/configs/{n}.json", "reduced": [],
                        "why": "test"} for n in ("tiny-dit", "tiny-video")]
    spec["workloads"] = [{"name": c, "config": cfg, "traffic": mix,
                          "chips": 1, "why": "test"}
                         for c, (cfg, mix) in CELLS.items()]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    _write(root / "BENCHMARK.json", spec)
    return root
