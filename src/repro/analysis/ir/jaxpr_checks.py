"""Jaxpr / lowered-HLO contract checks over captured ProgramIR.

These are the IR-ground-truth versions of contracts the AST layer can only
infer from source text:

  * host callbacks — a `pure_callback` / `io_callback` / `debug_callback`
    (or infeed/outfeed) primitive anywhere in a tick program means every
    dispatch round-trips to the host, silently serializing serving.
  * f64 / weak-type leaks — an f64 const or intermediate doubles memory
    traffic on the hot path; a weak-typed *output* re-promotes whatever
    downstream program consumes it.
  * donation aliasing — `donate_argnums` that fails to alias (shape/dtype
    mismatch between donated input and any output) silently no-ops: the
    "in-place" update still allocates.  The lowered StableHLO is the
    ground truth: actually-aliased args carry a `tf.aliasing_output`
    attribute.
  * const bloat — closed-over arrays become jaxpr consts baked into the
    executable.  Serving programs take the model params and every table
    as arguments, so any const above the threshold is closure-capture
    bloat (an array that should have been an argument).

Every check returns `IRIssue`s — (category, message, file, line) tuples
the verify layer turns into registry Findings.  Issues carry the eqn's
user-frame source location when jax recorded one, else the program's
python def-site, so inline suppressions keep working.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Iterator, List, Optional, Tuple

__all__ = ["IRIssue", "iter_eqns", "find_host_callbacks", "find_f64",
           "find_const_bloat", "count_aliased_inputs", "check_donation",
           "donation_report", "DEFAULT_CONST_THRESHOLD"]

#: consts above this byte count are flagged as closure-capture bloat;
#: small baked scalars/tables are normal
DEFAULT_CONST_THRESHOLD = 1 << 16        # 64 KiB

#: primitives whose presence in a serving program means a host round trip
HOST_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback", "infeed", "outfeed"})


@dataclass(frozen=True)
class IRIssue:
    """One contract violation found in a program's IR."""
    category: str                # "host-callback" | "dtype" | ...
    message: str
    file: str = ""               # absolute source path when known
    line: int = 0


def _eqn_site(eqn) -> Tuple[str, int]:
    """User-code (file, line) of one jaxpr equation, when jax recorded a
    source_info trace for it (it usually did)."""
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is not None:
            return frame.file_name, frame.start_line
    except Exception:
        pass
    return "", 0


def iter_eqns(closed_jaxpr) -> Iterator:
    """All equations of a ClosedJaxpr, recursing into sub-jaxprs (scan/
    cond/while bodies, inner pjit calls) — a callback hidden inside a
    lax.cond branch is still a callback."""
    stack = [closed_jaxpr.jaxpr]
    while stack:
        jaxpr = stack.pop()
        for eqn in jaxpr.eqns:
            yield eqn
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    stack.append(sub)


def _sub_jaxprs(value) -> List:
    """Extract inner jaxprs from an eqn param value (ClosedJaxpr, bare
    Jaxpr, or a list/tuple of either — `branches` of lax.cond)."""
    out = []
    vals = value if isinstance(value, (list, tuple)) else (value,)
    for v in vals:
        if hasattr(v, "eqns"):                       # bare Jaxpr
            out.append(v)
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):  # ClosedJaxpr
            out.append(v.jaxpr)
    return out


# ----------------------------------------------------------------------
def find_host_callbacks(closed_jaxpr) -> List[IRIssue]:
    """Host-callback / infeed / outfeed primitives anywhere in the
    program, sub-jaxprs included."""
    issues = []
    for eqn in iter_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if name in HOST_CALLBACK_PRIMS:
            f, ln = _eqn_site(eqn)
            issues.append(IRIssue(
                "host-callback",
                f"host callback primitive '{name}' in a serving program — "
                f"every dispatch round-trips to the host", f, ln))
    return issues


_WIDE = ("float64", "complex128", "int64")


def find_f64(closed_jaxpr, *, check_weak_outputs: bool = True,
             allow_int64: bool = True) -> List[IRIssue]:
    """f64/c128 values in device code: consts, per-eqn outputs, and
    weak-typed program outputs.

    int64 is tolerated by default (index arithmetic lands there even with
    x64 disabled on some paths); float64 never is — with x64 disabled it
    can only enter via a closed-over f64 numpy table, exactly the
    schedule-table bug class."""
    issues = []
    wide = set(_WIDE) - ({"int64"} if allow_int64 else set())
    for i, c in enumerate(closed_jaxpr.consts):
        dt = str(getattr(c, "dtype", ""))
        if dt in wide:
            issues.append(IRIssue(
                "dtype",
                f"closed-over const #{i} is {dt} "
                f"(shape {tuple(getattr(c, 'shape', ()))}) — a host-side "
                f"wide-dtype table leaked into device code"))
    for eqn in iter_eqns(closed_jaxpr):
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt in wide:
                f, ln = _eqn_site(eqn)
                issues.append(IRIssue(
                    "dtype",
                    f"'{eqn.primitive.name}' produces {dt} "
                    f"inside the program — wide-dtype promotion on the "
                    f"device path", f, ln))
                break                 # one issue per eqn is enough
    if check_weak_outputs:
        for i, var in enumerate(closed_jaxpr.jaxpr.outvars):
            aval = getattr(var, "aval", None)
            if getattr(aval, "weak_type", False):
                issues.append(IRIssue(
                    "dtype",
                    f"program output #{i} is weak-typed "
                    f"({getattr(aval, 'dtype', '?')}) — it will re-promote "
                    f"in whatever downstream program consumes it"))
    return issues


# ----------------------------------------------------------------------
def _const_spec(c) -> Tuple[Tuple[int, ...], str]:
    return (tuple(getattr(c, "shape", ())), str(getattr(c, "dtype", "")))


def _nbytes(c) -> int:
    nb = getattr(c, "nbytes", None)
    if nb is not None:
        return int(nb)
    size = getattr(c, "size", 0)
    item = getattr(getattr(c, "dtype", None), "itemsize", 1)
    return int(size) * int(item)


def find_const_bloat(closed_jaxpr,
                     threshold_bytes: int = DEFAULT_CONST_THRESHOLD
                     ) -> List[IRIssue]:
    """Closed-over consts above `threshold_bytes`: each is an array (model
    params included) baked into the executable instead of passed as an
    argument."""
    issues = []
    for i, c in enumerate(closed_jaxpr.consts):
        nb = _nbytes(c)
        if nb > threshold_bytes:
            shape, dtype = _const_spec(c)
            issues.append(IRIssue(
                "const-bloat",
                f"closed-over const #{i}: shape {shape} {dtype}, {nb} "
                f"bytes (> {threshold_bytes}) baked into the executable — "
                f"pass it as an argument instead"))
    return issues


# ----------------------------------------------------------------------
# donation: the lowered StableHLO marks each actually-aliased argument
# with a `tf.aliasing_output = <n> : i32` arg attribute; counting those
# against the donated leaf count exposes silent no-op donations
_ALIAS_RE = re.compile(r"tf\.aliasing_output")


def count_aliased_inputs(lowered_text: str) -> int:
    """Number of program arguments the compiler actually aliased to an
    output (donated buffers that really update in place)."""
    return len(_ALIAS_RE.findall(lowered_text))


def donation_report(jitted, *args, **kwargs) -> dict:
    """Lower a jit'd-with-donation function on example args and report how
    many inputs actually aliased.  The caller compares `aliased` with the
    leaf count of what it donated."""
    text = jitted.lower(*args, **kwargs).as_text()
    return {"aliased": count_aliased_inputs(text)}


def check_donation(lowered_text: str, donated_leaves: int,
                   label: str = "program") -> Optional[IRIssue]:
    """None when every donated leaf aliased; an issue otherwise (including
    the claimed-but-zero case — donation that silently no-ops)."""
    if donated_leaves <= 0:
        return None
    aliased = count_aliased_inputs(lowered_text)
    if aliased >= donated_leaves:
        return None
    return IRIssue(
        "donation",
        f"{label}: donate_argnums claimed {donated_leaves} donated "
        f"buffer leaves but the compiled program aliases only {aliased} — "
        f"the un-aliased leaves still allocate (donation silently no-ops, "
        f"usually a pytree/argnum or shape/dtype mismatch)")
