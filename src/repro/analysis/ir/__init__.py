"""repro.analysis.ir — IR-level program verification.

Where `repro.analysis.rules` lints *source text*, this subpackage lints
the *compiled artifacts*: the jaxprs and lowered StableHLO of the
engine's warmup-compiled program set, the jit cache's steady-state
behavior under a golden serving session, and the structural validity of
every Pallas kernel call.  Source-level taint analysis is a heuristic;
the jaxpr is ground truth.

  jaxpr_checks   recursive eqn walks: host callbacks, f64/weak-type
                 leaks, const bloat, donation aliasing (lowered text)
  verify         `verify_programs(engine)` -> registry Findings over
                 every warmup-compiled program
  retrace        RetraceSentinel: count jit cache misses in a scope
                 (jax.monitoring backend-compile events + pxla compile
                 logs for program names)
  pallas_lint    grid/BlockSpec/index-map/dtype checks over every
                 pl.pallas_call in src/repro/kernels, via interception
  golden         the cached lint-time fixture: tiny image+video engines,
                 verified + served under the sentinel

Everything surfaces through the ordinary rule registry as the six
`ir-*` rules (`repro-lint --rule 'ir-*'`), and through
`engine.warmup(verify=True)` at runtime.
"""
from .jaxpr_checks import (DEFAULT_CONST_THRESHOLD, HOST_CALLBACK_PRIMS,
                           IRIssue, check_donation, count_aliased_inputs,
                           donation_report, find_const_bloat, find_f64,
                           find_host_callbacks, iter_eqns)
from .pallas_lint import (PallasCallCapture, check_capture,
                          intercept_pallas_calls, lint_pallas_kernels)
from .retrace import RetraceSentinel
from .verify import issue_to_finding, verify_programs, verify_programs_by_key

__all__ = [
    "DEFAULT_CONST_THRESHOLD", "HOST_CALLBACK_PRIMS", "IRIssue",
    "check_donation", "count_aliased_inputs", "donation_report",
    "find_const_bloat", "find_f64", "find_host_callbacks", "iter_eqns",
    "PallasCallCapture", "check_capture", "intercept_pallas_calls",
    "lint_pallas_kernels",
    "RetraceSentinel",
    "issue_to_finding", "verify_programs",
    "verify_programs_by_key",
]
