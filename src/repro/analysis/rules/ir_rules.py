"""The six ir-* rules: IR-level verification surfaced through the
ordinary rule registry, so `repro-lint --rule 'ir-*'`, inline
suppressions, the fingerprinted baseline and JSON reports all apply to
compiled-artifact findings exactly as to AST findings.

Five of the rules share one cached golden context (repro.analysis.ir
.golden): tiny image+video engines warmed with IR capture, verified, and
served through a mixed session under the retrace sentinel — built once
per lint process.  Each rule then reports its slice of the findings.
ir-pallas drives the kernel lint separately (no engine involved), and
ir-donation additionally checks the training step's donate_argnums
against its lowered aliasing.
"""
from __future__ import annotations

import os
from typing import List

from ..base import Finding, ProjectRule, register

_ENGINE_REL = "src/repro/serving/diffusion/engine.py"
_TRAIN_REL = "src/repro/train/loop.py"


def _context_error_finding(rule_id: str, err: str) -> Finding:
    return Finding(rule_id, _ENGINE_REL, 1, 0,
                   f"golden lint context failed to build — IR contracts "
                   f"unverifiable: {err}")


def _program_findings(rule_id: str) -> List[Finding]:
    """This rule's slice of the golden context's verify_programs output."""
    from ..ir.golden import golden_context
    ctx = golden_context()
    if ctx.error:
        return [_context_error_finding(rule_id, ctx.error)]
    out = []
    for f in ctx.program_findings:
        if f.rule == rule_id:
            out.append(Finding(rule_id, f.path, f.line, f.col, f.message,
                               snippet=f.snippet))
    return out


@register
class IRHostCallbackRule(ProjectRule):
    id = "ir-host-callback"
    description = ("host callback / infeed / outfeed primitives in a "
                   "warmup-compiled serving program (jaxpr ground truth)")
    rationale = ("a pure_/io_/debug_callback in a tick program round-trips "
                 "to the host on every dispatch — the AST host-sync rule "
                 "sees source taint, this sees the actual primitive")

    def check_project(self, root: str) -> List[Finding]:
        return _program_findings(self.id)


@register
class IRDtypeRule(ProjectRule):
    id = "ir-dtype"
    description = ("float64 / weak-type leaks in compiled serving programs "
                   "and the engine's schedule tables")
    rationale = ("an f64 const or intermediate doubles hot-path memory "
                 "traffic; a weak-typed output re-promotes every "
                 "downstream consumer — with x64 disabled, f64 can only "
                 "enter via closed-over host numpy tables")

    def check_project(self, root: str) -> List[Finding]:
        return _program_findings(self.id)


@register
class IRConstBloatRule(ProjectRule):
    id = "ir-const-bloat"
    description = ("large closed-over constants (model params included) "
                   "baked into compiled serving programs")
    rationale = ("every baked const is duplicated per program variant "
                 "(one per bucket size) and invalidates the executable "
                 "when the host object changes — params and tables "
                 "belong in arguments")

    def check_project(self, root: str) -> List[Finding]:
        return _program_findings(self.id)


@register
class IRDonationRule(ProjectRule):
    id = "ir-donation"
    description = ("donate_argnums claims that the lowered program does "
                   "not actually alias (silent no-op donation)")
    rationale = ("un-aliased donation still allocates: the training step "
                 "would hold two copies of every param/opt leaf, and an "
                 "engine program aliasing buffers the slot pool still "
                 "references would corrupt live state")

    def check_project(self, root: str) -> List[Finding]:
        findings = _program_findings(self.id)
        findings.extend(self._check_train_step(root))
        return findings

    def _check_train_step(self, root: str) -> List[Finding]:
        """Drive the real training step exactly as train_loop jits it
        (donate_argnums=(0,)) and demand every TrainState leaf aliases."""
        try:
            import jax
            import jax.numpy as jnp
            from repro.configs import get_smoke_config
            from repro.diffusion import linear_schedule
            from repro.train.steps import (init_train_state,
                                           make_diffusion_train_step)
            from ..ir.jaxpr_checks import check_donation

            cfg = get_smoke_config("dit-xl").reduced(
                num_layers=1, d_model=32, num_heads=2, num_kv_heads=2,
                d_ff=64)
            state = init_train_state(jax.random.PRNGKey(0), cfg)
            step_fn = make_diffusion_train_step(cfg, linear_schedule(50),
                                                total_steps=5)
            # jit exactly as train_loop does (loop.py donate=True default)
            step_fn = jax.jit(step_fn, donate_argnums=(0,))
            batch = {"latents": jnp.zeros((2, cfg.dit_tokens, cfg.dit_in_dim),
                                          jnp.float32),
                     "labels": jnp.zeros((2,), jnp.int32),
                     "key": jax.random.PRNGKey(1)}
            text = step_fn.lower(state, batch).as_text()
            leaves = len(jax.tree_util.tree_leaves(state))
            issue = check_donation(text, leaves,
                                   "train_loop step_fn donate_argnums=(0,)")
        except Exception as e:
            return [Finding(self.id, _TRAIN_REL, 1, 0,
                            f"cannot drive the training step's donation "
                            f"check: {e!r}")]
        if issue is None:
            return []
        line = _find_line(root, _TRAIN_REL, "donate_argnums")
        return [Finding(self.id, _TRAIN_REL, line, 0, issue.message,
                        snippet=_read_line(root, _TRAIN_REL, line))]


@register
class IRRetraceRule(ProjectRule):
    id = "ir-retrace"
    description = ("steady-state serving after engine.warmup() triggered "
                   "a jit recompile during the golden mixed session")
    rationale = ("warmup promises the complete program set; one silent "
                 "retrace pays an XLA compile inside a live tick — "
                 "latency SLAs and the autotuner's row pricing both "
                 "assume it never happens")

    def check_project(self, root: str) -> List[Finding]:
        from ..ir.golden import golden_context
        ctx = golden_context()
        if ctx.error:
            return [_context_error_finding(self.id, ctx.error)]
        line = _find_line(root, _ENGINE_REL, "def tick(self)")
        findings = []
        if not ctx.sentinel_live:
            findings.append(Finding(
                self.id, _ENGINE_REL, line, 0,
                "retrace sentinel selftest failed: neither the "
                "jax.monitoring backend-compile event nor the pxla "
                "compile log detected a known compile — the zero-"
                "recompile claim is unverifiable",
                snippet=_read_line(root, _ENGINE_REL, line)))
        if ctx.retrace_count != 0:
            names = ", ".join(sorted(set(ctx.retrace_names))) or "<unnamed>"
            findings.append(Finding(
                self.id, _ENGINE_REL, line, 0,
                f"golden mixed image+video session compiled "
                f"{ctx.retrace_count} program(s) AFTER warmup "
                f"(expected 0): {names}",
                snippet=_read_line(root, _ENGINE_REL, line)))
        return findings


@register
class IRPallasRule(ProjectRule):
    id = "ir-pallas"
    description = ("Pallas kernel structural lint: grid/BlockSpec "
                   "divisibility, index-map arity, dtype consistency")
    rationale = ("the kernels run under interpret=True on CPU, which "
                 "forgives malformed BlockSpecs that are fatal or silent "
                 "garbage on a real TPU — lint the call structure without "
                 "executing it")

    def check_project(self, root: str) -> List[Finding]:
        from ..ir import lint_pallas_kernels
        from ..ir.verify import issue_to_finding
        try:
            issues = lint_pallas_kernels()
        except Exception as e:
            return [Finding(self.id, "src/repro/kernels/__init__.py", 1, 0,
                            f"pallas lint crashed: {e!r}")]
        return [issue_to_finding(i, root,
                                 fallback_file=os.path.join(
                                     root, "src/repro/kernels/__init__.py"),
                                 fallback_line=1)
                for i in issues]


def _read_line(root: str, relpath: str, line: int) -> str:
    try:
        with open(os.path.join(root, relpath), encoding="utf-8") as f:
            lines = f.read().splitlines()
        return lines[line - 1].strip() if 0 < line <= len(lines) else ""
    except OSError:
        return ""


def _find_line(root: str, relpath: str, needle: str) -> int:
    try:
        with open(os.path.join(root, relpath), encoding="utf-8") as f:
            for i, text in enumerate(f.read().splitlines(), 1):
                if needle in text:
                    return i
    except OSError:
        pass
    return 1
