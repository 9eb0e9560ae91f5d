"""Program profiling: compile-time capture, XLA cost analysis, profiler
trace contexts.

The survey's redundancy claim — caching works because consecutive steps
recompute nearly identical activations — is usually reported in *rows* or
*steps* saved.  This module turns it into FLOPs: `engine.warmup()` AOT-
compiles each bucket-size tick program through `compile_program`, keeping
per-program compile seconds and the XLA cost model's FLOPs / bytes, and
`redundancy_ratio` combines those with telemetry row counters into the
measured ratio  (theoretical FLOPs avoided) / (dense FLOPs) — what the
cache ACTUALLY saved of the compute a dense pool would have run.

`profiler_trace` is the opt-in `jax.profiler` context for benchmark runs
(`bench_serving --profile-dir ...`): a no-op unless a directory is given,
so nothing ships a profiler dependency into the hot path.
"""
from __future__ import annotations

import math
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

from .clock import monotonic

__all__ = ["ProgramIR", "ProgramProfile", "capture_ir", "compile_program",
           "program_cost", "flops_per_row", "redundancy_ratio",
           "profiler_trace"]


@dataclass(frozen=True)
class ProgramProfile:
    """One compiled program's cost card (engine.warmup fills one per
    bucket size / dense tick kind)."""
    key: object                 # bucket size (int) or tick kind (str)
    compile_seconds: float
    flops: float                # XLA cost model; nan when unavailable
    bytes_accessed: float       # XLA cost model; nan when unavailable
    #: repro.analysis.ir findings attached by engine.warmup(verify=True);
    #: empty means verified-clean OR not verified — check engine.ir_findings
    #: (None = never verified) to tell the two apart
    ir_findings: Tuple = ()

    def as_dict(self) -> Dict:
        d = {"key": self.key, "compile_seconds": self.compile_seconds,
             "flops": self.flops, "bytes_accessed": self.bytes_accessed}
        if self.ir_findings:
            d["ir_findings"] = [
                f.to_dict() if hasattr(f, "to_dict") else str(f)
                for f in self.ir_findings]
        return d


@dataclass(frozen=True)
class ProgramIR:
    """The inspectable intermediate representations of one jit program,
    captured at trace/lower time (a `Compiled` executable no longer
    carries its jaxpr, so engines capture this during warmup).

    `jaxpr` is the ClosedJaxpr — closed-over arrays (an accidentally baked
    table or param tree) appear as `.consts`; the model params are program
    operands, so a large const is always a closure-capture leak.
    `lowered_text` is the StableHLO module as text; donated-and-actually-
    aliased arguments carry a `tf.aliasing_output` attribute there, which
    is what the ir-donation check keys on."""
    key: object
    jaxpr: object                              # jax ClosedJaxpr
    lowered_text: str                          # StableHLO module text
    fn_file: str = ""                          # def-site of the python fn
    fn_line: int = 0


def _fn_def_site(jitted) -> Tuple[str, int]:
    """Best-effort (file, line) of the python function under a jit wrapper,
    for anchoring findings that have no per-eqn source info."""
    fn = getattr(jitted, "__wrapped__", jitted)
    code = getattr(fn, "__code__", None)
    if code is None:
        return "", 0
    return code.co_filename, code.co_firstlineno


def capture_ir(jitted, *args, key=None, **kwargs) -> ProgramIR:
    """Trace + lower a jit'd function on example args and keep the IRs
    (without compiling).  Engines use this to re-capture IR for programs
    whose compiled executables were already swapped in by a prior warmup."""
    traced = jitted.trace(*args, **kwargs)
    fn_file, fn_line = _fn_def_site(jitted)
    return ProgramIR(key=key, jaxpr=traced.jaxpr,
                     lowered_text=traced.lower().as_text(),
                     fn_file=fn_file, fn_line=fn_line)


def program_cost(compiled) -> Dict[str, float]:
    """FLOPs / bytes from a compiled executable's XLA cost analysis.

    `cost_analysis()` returns a per-device list on some backends and a
    bare dict on others, and may be unimplemented entirely (some Pallas
    lowerings) — normalize to {"flops", "bytes_accessed"} with nan for
    anything the backend would not report."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {"flops": math.nan, "bytes_accessed": math.nan}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {"flops": math.nan, "bytes_accessed": math.nan}
    return {"flops": float(ca.get("flops", math.nan)),
            "bytes_accessed": float(ca.get("bytes accessed", math.nan))}


def compile_program(jitted, *args, key=None, want_ir=False, **kwargs):
    """AOT-compile a jit'd function on example args.

    Returns (compiled, ProgramProfile) — or (compiled, profile, ProgramIR)
    with `want_ir=True`, sharing one trace/lower pipeline so IR capture
    costs no extra trace.  The compiled executable is directly callable
    with matching-shape args — the engine swaps it into its tick-program
    cache so warmup's compile is never paid twice — and its cost analysis
    prices the program in FLOPs/bytes."""
    t0 = monotonic()
    ir = None
    if want_ir:
        traced = jitted.trace(*args, **kwargs)
        lowered = traced.lower()
        fn_file, fn_line = _fn_def_site(jitted)
        ir = ProgramIR(key=key, jaxpr=traced.jaxpr,
                       lowered_text=lowered.as_text(),
                       fn_file=fn_file, fn_line=fn_line)
    else:
        lowered = jitted.lower(*args, **kwargs)
    compiled = lowered.compile()
    dt = monotonic() - t0
    cost = program_cost(compiled)
    profile = ProgramProfile(key=key, compile_seconds=dt,
                             flops=cost["flops"],
                             bytes_accessed=cost["bytes_accessed"])
    return (compiled, profile, ir) if want_ir else (compiled, profile)


def flops_per_row(profiles: Dict) -> float:
    """Marginal backbone FLOPs per gathered row, from the per-bucket
    program profiles: (flops[largest bucket] - flops[skip]) / bucket.
    Subtracting the bucket-0 (skip) program removes the per-slot policy /
    DDIM arithmetic every tick pays regardless of rows; nan when the
    profiles are missing or costless (backend without a cost model)."""
    buckets = sorted(k for k in profiles if isinstance(k, int) and k > 0)
    if not buckets:
        return math.nan
    largest = buckets[-1]
    base = profiles.get(0)
    f_base = base.flops if base is not None and not math.isnan(
        base.flops) else 0.0
    f_top = profiles[largest].flops
    if math.isnan(f_top):
        return math.nan
    return max(f_top - f_base, 0.0) / largest


def redundancy_ratio(profiles: Dict, rows_computed: int, rows_padding: int,
                     rows_saved: int) -> Dict[str, float]:
    """The survey's redundancy ratio, measured: theoretical FLOPs avoided
    over the FLOPs a dense (no-cache, whole-pool) serving run would have
    dispatched for the same traffic.

    rows_* come straight from ServingTelemetry (backbone_rows_computed /
    _padding / _saved).  Padding rows DO run through the backbone, so they
    count against the saving — the ratio prices the pow-2 bucket waste
    honestly."""
    fpr = flops_per_row(profiles)
    dispatched = rows_computed + rows_padding
    dense = dispatched + rows_saved
    avoided = rows_saved - rows_padding  # padding burns part of the saving
    if math.isnan(fpr) or dense <= 0:
        return {"flops_per_row": fpr, "dense_flops": math.nan,
                "flops_avoided": math.nan, "redundancy_ratio": math.nan}
    return {"flops_per_row": fpr,
            "dense_flops": fpr * (rows_computed + rows_saved),
            "flops_avoided": fpr * avoided,
            "redundancy_ratio": avoided / (rows_computed + rows_saved)}


@contextmanager
def profiler_trace(log_dir: Optional[str] = None):
    """Opt-in `jax.profiler.trace` context: profiles the enclosed block
    into `log_dir` (TensorBoard / Perfetto-loadable) when a directory is
    given, and is a strict no-op otherwise — benchmarks wrap their timed
    sections in this unconditionally."""
    if not log_dir:
        yield
        return
    import jax
    with jax.profiler.trace(log_dir):
        yield
