"""chip_smoke.py on the CPU: its serving phases at DiT-XL/2's reduced
widths, its refusal to run without a TPU, and the compile-cache placement
it performs first."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax

from repro.configs import dit_xl
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_smoke_passes_on_reduced_dit_xl():
    smoke = _load_chip_smoke()
    out = smoke.serve_smoke(dit_xl.SMOKE, log=lambda *_: None)
    assert out["served"] == smoke.N_REQUESTS
    assert out["recompiles"] == {"cached": 0, "none": 0}
    assert set(out["rel_err"]) == {"guided", "unguided"}
    assert all(0.0 <= e <= smoke.REL_TOL for e in out["rel_err"].values())
    # TeaCache plans on the device, so its fused want pass was warmed
    assert "want" in out["compile_seconds"]["cached"]
    assert {"0", "1", "2", "4", "8", "16"} <= set(
        out["compile_seconds"]["cached"])


def _run_script(path, cwd, env):
    return subprocess.run([sys.executable, str(path)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = _run_script(ROOT / "chip_smoke.py", ROOT, env)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
    assert not (tmp_path / "cache").exists()   # refused before any compile


def test_chip_smoke_exits_nonzero_outside_the_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = _run_script(tmp_path / "chip_smoke.py", tmp_path, env)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_compile_cache_defaults_to_the_checkout(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    assert compile_cache.CHECKOUT == ROOT
    monkeypatch.setattr(compile_cache, "CHECKOUT", tmp_path)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.configure_compile_cache()
        assert path == str(tmp_path / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        compilation_cache.reset_cache()


def test_compile_cache_env_variable_wins(monkeypatch, tmp_path):
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv(compile_cache.CACHE_ENV, env_dir)
    prev = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache() == env_dir
    # JAX reads the variable itself; no other directory is set in code
    assert jax.config.jax_compilation_cache_dir == prev
