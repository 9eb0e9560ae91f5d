"""verify_programs: run every IR contract check over an engine's
warmup-compiled program set and return registry Findings.

The engine captures a ProgramIR per program (bucket sizes, dense tick
kinds, the fused want pass) either during `warmup(verify=True)` or on
demand via `engine._capture_program_ir()`; this module walks them:

  ir-host-callback   no pure_/io_/debug_callback or infeed/outfeed
  ir-dtype           no f64/c128 consts or intermediates, no weak-typed
                     outputs; also checks the engine's schedule tables
  ir-donation        donate_argnums claims actually alias (engine
                     programs donate nothing today, so this validates
                     the claim-vs-alias bookkeeping stays consistent)
  ir-const-bloat     no const > threshold: params and tables are
                     program operands, a big const is closure capture

Findings anchor on the eqn's user-frame source line when jax recorded
one (so `# repro-lint: disable=ir-*` inline suppressions work), else on
the program's python def-site.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional

from ..base import Finding
from .jaxpr_checks import (DEFAULT_CONST_THRESHOLD, IRIssue, find_const_bloat,
                           find_f64, find_host_callbacks)

__all__ = ["verify_programs", "verify_programs_by_key", "issue_to_finding"]

_CATEGORY_RULE = {
    "host-callback": "ir-host-callback",
    "dtype": "ir-dtype",
    "donation": "ir-donation",
    "const-bloat": "ir-const-bloat",
    "pallas": "ir-pallas",
    "retrace": "ir-retrace",
}


def _repo_root(root: Optional[str]) -> str:
    if root:
        return root
    from ..runner import find_repo_root
    return find_repo_root()


def _read_line(root: str, relpath: str, line: int) -> str:
    try:
        with open(os.path.join(root, relpath), encoding="utf-8") as f:
            lines = f.read().splitlines()
        return lines[line - 1].strip() if 0 < line <= len(lines) else ""
    except OSError:
        return ""


def issue_to_finding(issue: IRIssue, root: str, *,
                     fallback_file: str = "", fallback_line: int = 0,
                     prefix: str = "") -> Finding:
    """IRIssue -> registry Finding, anchored on a repo-relative source
    line so fingerprints/suppressions behave exactly like AST findings."""
    file, line = issue.file, issue.line
    if not file:
        file, line = fallback_file, fallback_line
    rel = ""
    if file:
        try:
            rel = os.path.relpath(file, root).replace(os.sep, "/")
        except ValueError:
            rel = file.replace(os.sep, "/")
    if not rel or rel.startswith(".."):
        # source outside the repo (jax internals) — anchor on the repo
        # file the caller named, or a stable placeholder
        rel, line = fallback_file and os.path.relpath(
            fallback_file, root).replace(os.sep, "/") or "src/repro", 1
    rule = _CATEGORY_RULE.get(issue.category, f"ir-{issue.category}")
    return Finding(rule, rel, max(int(line), 1), 0,
                   (prefix + issue.message) if prefix else issue.message,
                   snippet=_read_line(root, rel, max(int(line), 1)))


def _engine_level_issues(engine) -> List[IRIssue]:
    """Checks on engine-owned host tables that feed the programs: the
    noise-schedule tables are gathered into every tick, so an f64 table
    re-promotes per-request DDIM coefficients off the f32 path."""
    issues = []
    sched = getattr(engine, "sched", None)
    for name in ("betas", "alpha_bars"):
        tab = getattr(sched, name, None)
        dt = str(getattr(tab, "dtype", ""))
        if dt == "float64":
            issues.append(IRIssue(
                "dtype",
                f"engine noise schedule table '{name}' is float64 — cast "
                f"to float32 at the NoiseSchedule boundary"))
    return issues


def verify_programs_by_key(engine, *, root: Optional[str] = None,
                           const_threshold: int = DEFAULT_CONST_THRESHOLD
                           ) -> Dict[object, List[Finding]]:
    """All IR findings for one engine, grouped by program key ("__engine__"
    for engine-level table checks).  Warms + captures IR as needed."""
    root = _repo_root(root)
    program_ir = engine._capture_program_ir()
    by_key: Dict[object, List[Finding]] = {}
    for key, ir in sorted(program_ir.items(), key=lambda kv: str(kv[0])):
        issues = []
        issues += find_host_callbacks(ir.jaxpr)
        issues += find_f64(ir.jaxpr)
        issues += find_const_bloat(ir.jaxpr, const_threshold)
        # engine programs donate nothing today; an aliasing attr showing
        # up anyway would mean the jit wrappers grew donation the engine
        # does not account for — surface it rather than ignore it
        from .jaxpr_checks import count_aliased_inputs
        aliased = count_aliased_inputs(ir.lowered_text)
        if aliased:
            issues.append(IRIssue(
                "donation",
                f"program aliases {aliased} input(s) but the engine "
                f"declares no donation — buffer reuse the slot pool does "
                f"not account for"))
        if issues:
            by_key[key] = [
                issue_to_finding(i, root, fallback_file=ir.fn_file,
                                 fallback_line=ir.fn_line,
                                 prefix=f"[program {key!r}] ")
                for i in issues]
    eng_issues = _engine_level_issues(engine)
    if eng_issues:
        import inspect
        try:
            sched_file = inspect.getsourcefile(type(engine.sched))
            sched_line = inspect.getsourcelines(type(engine.sched))[1]
        except Exception:
            sched_file, sched_line = "", 0
        by_key["__engine__"] = [
            issue_to_finding(i, root, fallback_file=sched_file or "",
                             fallback_line=sched_line)
            for i in eng_issues]
    return by_key


def verify_programs(engine, *, root: Optional[str] = None,
                    const_threshold: int = DEFAULT_CONST_THRESHOLD
                    ) -> List[Finding]:
    """Flat list of IR findings over every warmup-compiled program of
    `engine` (plus engine-level table checks).  Empty == verified clean."""
    by_key = verify_programs_by_key(engine, root=root,
                                    const_threshold=const_threshold)
    return [f for _, fs in sorted(by_key.items(), key=lambda kv: str(kv[0]))
            for f in fs]
